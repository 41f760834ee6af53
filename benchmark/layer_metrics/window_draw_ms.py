"""Median time the fit loop takes to draw one window's batches from the
iterator (span ``fused_fit.draw``, on the loop's thread; the iterator's own
cost per batch is the ``fused_fit.next`` spans inside it), over the windows
the timed ``fit`` dispatched."""
from benchmark.reduce import window_spans


def read(run):
    return window_spans.median_ms(run, 'fused_fit.draw')
