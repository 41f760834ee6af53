"""Share of the traced slice in which a chip runs a collective and no
compute: the part of the gradient exchange that the backward pass does not
hide."""


def read(run):
    trace = run.get('trace') or {}
    if not trace.get('collective_s') or not trace.get('window_s'):
        return None
    return 100.0 * trace['collective_exposed_s'] / trace['window_s']
