"""Share of the device's idle time in the traced slice during which the
innermost span on the loop's thread was ``fused_fit.draw`` or the
``fused_fit.next`` inside it: the chip waiting for the iterator
(``benchmark/reduce/host_spans.py``)."""
from benchmark.reduce import host_spans


def read(run):
    return host_spans.idle_share(run, ('fused_fit.draw', 'fused_fit.next'))
