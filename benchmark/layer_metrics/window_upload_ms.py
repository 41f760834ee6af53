"""Median time of one window's ``device_put`` calls (span
``fused_fit.upload``, on the thread that makes them), over the windows the
timed ``fit`` dispatched. The span ends when the calls return. On the v5e
they return at once and the runtime copies on behind them: the transfer's
end is then on the runtime's own lines of the capture's ``/host:CPU`` plane
(``tpu::System::TransferToDevice``), not in this number."""
from benchmark.reduce import window_spans


def read(run):
    return window_spans.median_ms(run, 'fused_fit.upload')
