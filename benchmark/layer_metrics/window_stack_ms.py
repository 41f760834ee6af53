"""Median time of the host-side ``np.stack`` of one window's batches (span
``fused_fit.stack``, on the thread that runs it: the side thread
``mxtpu-window-put`` with the prefetch pool, else the loop's), over the
windows the timed ``fit`` dispatched."""
from benchmark.reduce import window_spans


def read(run):
    return window_spans.median_ms(run, 'fused_fit.stack')
