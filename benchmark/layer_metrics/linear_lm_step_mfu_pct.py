"""Model FLOP/s utilisation of training a decoder of the ``olmo_hybrid``
family: the operations the forward and backward passes require per token
(``reduce/flops_linear.py``: the linear-attention layers' projections and
their scan at a chunk of 64, the attention layer's projections and
products, the MLPs, the head; no recomputation, no elementwise work),
times this run's tokens per second, over the chip's published bf16 peak:
the share of the whole step."""
from benchmark.reduce import flops_linear, peaks


def read(run):
    if run['device_kind'] == 'cpu' or not run.get('samples_s') \
            or 'seq_len' not in run \
            or 'linear_key_head_dim' not in run['config']:
        return None
    need = flops_linear.required_flops(run['config'], run['seq_len'])
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['train'] * run['samples_s'] / (run['chips'] * peak)
