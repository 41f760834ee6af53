"""Share of the device's busy time in ``GatedDeltaRule`` nodes (the chain
of chunks' kernels and XLA's part of a chunk beside them: forward, the
mirrored stages' second forward, backward), from the traced slice
(``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    if 'linear_key_head_dim' not in run['config']:
        return None
    return scopes.share(run, lambda op, phase, inner: op == 'GatedDeltaRule')
