"""Share of the device's busy time in ``GatedShortConv`` nodes (forward,
the mirrored stages' second forward, backward), from the traced slice
(``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    if 'conv_L_cache' not in run['config']:
        return None
    return scopes.share(run, lambda op, phase, inner: op == 'GatedShortConv')
