"""Share of the device's busy time in ``GroupedQueryAttention`` and
``LatentAttention`` nodes less their ``attention_*`` kernel calls: pads,
per-head layouts, the softmax's own term, the gate. From the traced slice
(``reduce/scopes.py``)."""
from benchmark.reduce import scopes

OPS = ('GroupedQueryAttention', 'LatentAttention')


def read(run):
    return scopes.share(
        run, lambda op, phase, inner: op in OPS,
        lambda op, kernel: op in OPS and kernel.startswith('attention_'))
