"""Model FLOP/s utilisation of training a decoder of the ``xing4_0``
family: the operations both losses' forward and backward passes require
per token (``reduce/flops_hyper.py``: the prediction module, its head and
the mixing maps beside ``flops_latent``'s parts, no recomputation), times
this run's tokens per second, over the chip's published bf16 peak."""
from benchmark.reduce import flops_hyper, peaks


def read(run):
    if run['device_kind'] == 'cpu' or not run.get('samples_s') \
            or 'seq_len' not in run or 'hc_mult' not in run['config']:
        return None
    need = flops_hyper.required_flops(run['config'], run['seq_len'])
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['train'] * run['samples_s'] / (run['chips'] * peak)
