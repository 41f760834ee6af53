"""Model FLOP/s utilisation of training a decoder of the ``sdar_moe``
family as a block-diffusion model: the operations the forward and backward
passes require per CLEAN token (``reduce/flops_blockdiff.py``: twice the
layers' projections, router and held experts, because a step runs the
noisy and the clean copy; attention over the pairs the mask leaves; the
head once; no recomputation, no elementwise work), times this run's clean
tokens per second, over the chip's published bf16 peak: the share of the
whole step."""
from benchmark.reduce import flops_blockdiff, peaks


def read(run):
    if run['device_kind'] == 'cpu' or not run.get('samples_s') \
            or 'seq_len' not in run or 'block_length' not in run['config']:
        return None
    need = flops_blockdiff.required_flops(run['config'], run['seq_len'])
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['train'] * run['samples_s'] / (run['chips'] * peak)
