"""How late the load generator sent requests against its schedule, 95th
percentile: a starved generator must not read as a fast server."""
from benchmark import harness


def read(run):
    reqs = (run.get('serve') or {}).get('requests')
    if not reqs:
        return None
    return 1e3 * harness.percentile([r['sent'] - r['due'] for r in reqs], 95)
