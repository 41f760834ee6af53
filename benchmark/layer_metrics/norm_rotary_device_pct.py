"""Share of the device's busy time in ``RMSNorm`` and ``RotaryEmbedding``
nodes, every pass, from the traced slice (``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    return scopes.share(
        run, lambda op, phase, inner: op in ('RMSNorm', 'RotaryEmbedding'))
