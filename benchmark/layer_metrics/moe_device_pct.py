"""Share of the device's busy time in the routed expert layers: the
grouped expert product's kernels, and what XLA runs around them (routing,
the gathers into and out of the sorted buffer) where the capture names its
events by the symbol's scopes; where it does not, the kernels alone."""


def read(run):
    k = run.get('kernels') or {}
    if not k.get('busy') or 'moe_expert' not in k:
        return None
    return 100.0 * (k['moe_expert'] + k.get('moe_other', 0.0)) / k['busy']
