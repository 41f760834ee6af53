"""Share of the device's busy time in the second forward of the mirrored
stages (phase ``refwd``: instructions under ``rematted_computation``, with
the linearisation jax traces there), from the traced slice
(``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    return scopes.share(run, lambda op, phase, inner: phase == 'refwd')
