"""Share of the device's busy time in the ``attention_blockdiff_*`` kernels
(forward and the one backward; a mirrored stage keeps their operands,
output and log-sum-exp, so the forward runs once a step), from the traced
slice."""


def read(run):
    k = run.get('kernels') or {}
    if not k.get('busy') or 'attention_blockdiff' not in k:
        return None
    return 100.0 * k['attention_blockdiff'] / k['busy']
