"""Share of the device's busy time in ``ShortConv`` nodes (the causal
depthwise convolution and its SiLU over a linear-attention layer's
[q | k | v]: forward, the mirrored stages' second forward, backward), from
the traced slice (``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    if 'linear_conv_kernel_dim' not in run['config']:
        return None
    return scopes.share(run, lambda op, phase, inner: op == 'ShortConv')
