"""Time one chip spends in collective operations per training step (the
union of its collective events in the traced slice over the slice's
steps)."""


def read(run):
    trace = run.get('trace') or {}
    if not trace.get('collective_s') or not run.get('trace_steps'):
        return None
    return 1e3 * trace['collective_s'] / run['trace_steps']
