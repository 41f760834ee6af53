"""Share of the device's busy time in operations that are neither a
convolution nor a fully-connected layer (normalisation, elementwise,
pooling, the update, copies, collectives), from the traced slice."""


def read(run):
    by = (run.get('trace') or {}).get('by_class')
    if not by or not sum(by.values()):
        return None
    return 100.0 * (1.0 - by.get('conv', 0.0) / sum(by.values()))
