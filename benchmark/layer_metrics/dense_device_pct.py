"""Share of the device's busy time in ``FullyConnected`` and ``GatedMLP``
nodes (projections, the dense MLP, the heads), every pass, from the traced
slice (``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    return scopes.share(
        run, lambda op, phase, inner: op in ('FullyConnected', 'GatedMLP'))
