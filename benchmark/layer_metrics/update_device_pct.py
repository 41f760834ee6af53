"""Share of the device's busy time in the optimizer's update of the fused
window (the scope ``update``), from the traced slice
(``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    return scopes.share(run, lambda op, phase, inner: op == 'update')
