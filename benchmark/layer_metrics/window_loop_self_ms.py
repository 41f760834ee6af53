"""Self time a step of the compiled window's ``while`` instructions, in
milliseconds: the scan's own bookkeeping, 0.02 ms in a sound capture. Over
a millisecond a step the capture lost events and its breakdown is not to be
believed. From the traced slice (``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    t = scopes.table(run)
    if t is None:
        return None
    return 1e3 * t['loops'].get('while', 0.0)
