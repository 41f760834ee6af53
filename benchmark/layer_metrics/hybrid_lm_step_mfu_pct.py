"""Model FLOP/s utilisation of training a decoder of the ``lfm2_moe``
family: the operations the forward and backward passes require per token
(``reduce/flops_hybrid.py``: the conv operators' and the attention's
projections, attention at 64-wide heads, the dense MLP, the routed experts
held, the tied head; no recomputation, no elementwise work), times this
run's tokens per second, over the chip's published bf16 peak: the share of
the whole step."""
from benchmark.reduce import flops_hybrid, peaks


def read(run):
    if run['device_kind'] == 'cpu' or not run.get('samples_s') \
            or 'seq_len' not in run or 'conv_L_cache' not in run['config']:
        return None
    need = flops_hybrid.required_flops(run['config'], run['seq_len'])
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['train'] * run['samples_s'] / (run['chips'] * peak)
