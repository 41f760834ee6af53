"""95th percentile of the time a request waits in the batcher's queue
before a dispatch takes it (the batcher's own ``queue_wait_log``)."""
from benchmark import harness


def read(run):
    waits = (run.get('serve') or {}).get('queue_wait_ms')
    return harness.percentile(waits, 95) if waits else None
