"""Share of the device's busy time in ``MoE`` nodes less their
``moe_expert_matmul*`` kernel calls: routing, the plan, the gathers, the
combine, the ``dw`` sums and the shared expert, every pass. From the traced
slice (``reduce/scopes.py``)."""
from benchmark.reduce import scopes


def read(run):
    return scopes.share(
        run, lambda op, phase, inner: op == 'MoE',
        lambda op, kernel: op == 'MoE'
        and kernel.startswith('moe_expert_matmul'))
