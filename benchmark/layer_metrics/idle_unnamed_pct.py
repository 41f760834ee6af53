"""Share of the device's idle time in the traced slice that no program
span on the loop's thread covers: what the measurement still cannot see
(``benchmark/reduce/host_spans.py``)."""
from benchmark.reduce import host_spans


def read(run):
    return host_spans.idle_share(run, ('',))
