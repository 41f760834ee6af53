"""Share of the device's busy time in instructions to which the compiled
window's scope map gives no symbol node and no window part (or which it
does not hold): what the trace still cannot name. From the traced slice
(``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    t = scopes.table(run)
    if t is None or not t['busy_s']:
        return None
    return 100.0 * (t['unscoped_s'] + t['unmapped_s']) / t['busy_s']
