"""Share of the device's busy time in the attention kernels of both kinds
(forward, backward and the mirrored blocks' recomputed forward), from the
traced slice."""


def read(run):
    k = run.get('kernels') or {}
    if not k.get('busy'):
        return None
    return 100.0 * (k.get('attention_window', 0.0)
                    + k.get('attention_full', 0.0)) / k['busy']
