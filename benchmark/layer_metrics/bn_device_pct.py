"""Share of the device's busy time in ``BatchNorm`` nodes, every pass, from
the traced slice (``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    return scopes.share(run, lambda op, phase, inner: op == 'BatchNorm')
