"""Model FLOP/s utilisation of training a decoder: the operations the
forward and backward passes require per token (``reduce/flops_lm.py``,
from the configuration's shapes at the step's sequence length, the routed
experts at their expected pairs, no recomputation), times this run's
tokens per second, over the chip's published bf16 peak."""
from benchmark.reduce import flops_lm, peaks


def read(run):
    if run['device_kind'] == 'cpu' or not run.get('samples_s') \
            or 'seq_len' not in run:
        return None
    need = flops_lm.required_flops(run['config'], run['seq_len'])
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['train'] * run['samples_s'] / (run['chips'] * peak)
