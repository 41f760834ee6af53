"""Share of the traced slice in which no operation ran on the device: 1
less the union of the device-operation intervals over the slice, averaged
over the cell's chips."""


def read(run):
    trace = run.get('trace') or {}
    if not trace.get('devices') or not trace.get('window_s'):
        return None
    return 100.0 * (1.0 - trace['busy_s'] / trace['window_s'])
