"""Share of the device's busy time in the stream-mixing kernels
(``hyper_pre_*``, ``hyper_post_*``: forward, the mirrored stages' second
forward, backward), from the traced slice."""


def read(run):
    k = run.get('kernels') or {}
    if not k.get('busy') or 'hyper_pre' not in k:
        return None
    return 100.0 * (k['hyper_pre'] + k.get('hyper_post', 0.0)) / k['busy']
