"""Model FLOP/s utilisation of training: the operations the forward and
backward passes require per sample (counted from shapes by
``benchmark/reduce/flops.py``, no recomputation), times this run's
samples per second, over chips times the chip's published bf16 peak."""
from benchmark.reduce import flops, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    if not run.get('samples_s'):
        return None
    model = run['config']['reference'].split(':')[1]
    need = flops.required_flops(model, run['param_shapes'],
                                run['config']['input_shape'])
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['train'] * run['samples_s'] / (run['chips'] * peak)
