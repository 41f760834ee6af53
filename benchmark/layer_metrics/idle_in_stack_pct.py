"""Share of the device's idle time in the traced slice during which the
loop waited in ``fused_fit.put`` while the side thread was in
``fused_fit.stack``, plus the idle time with the loop itself in ``.stack``
where there is no pool: the chip waiting for the host-side ``np.stack``
(``benchmark/reduce/host_spans.py``)."""
from benchmark.reduce import host_spans


def read(run):
    return host_spans.idle_share(run, ('fused_fit.stack',))
