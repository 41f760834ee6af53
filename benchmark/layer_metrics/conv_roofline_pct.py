"""The convolutions' share of their roofline, which is the compute bound
(their arithmetic intensity at these shapes is far right of the ridge):
the operations the convolutions of forward and backward require for the
samples of the traced slice, over the chip's bf16 peak, over the summed
device time of the convolution events on one chip."""
from benchmark.reduce import flops, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    trace = run.get('trace') or {}
    conv_s = trace.get('by_class', {}).get('conv', 0.0)
    if not conv_s or not run.get('trace_steps'):
        return None
    model = run['config']['reference'].split(':')[1]
    need = flops.required_flops(model, run['param_shapes'],
                                run['config']['input_shape'])
    samples = run['trace_steps'] * run['batch'] / run['chips']
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['conv_train'] * samples / peak / conv_s
