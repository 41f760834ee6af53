"""The stream-mixing kernels' share of their roofline, forward and
backward: the least time the chip could take to move the bytes that the
``hyper_pre_*`` and ``hyper_post_*`` kernels of every sublayer and collapse
have to move in the traced slice (``reduce/flops_hyper.mixing_bytes``: each
array once, no recomputation; memory bounds them), over their summed device
time. A mirrored block runs its forward kernels a second time in the
backward pass, which the share counts against them."""
from benchmark.reduce import flops_hyper, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    k = run.get('kernels') or {}
    seconds = k.get('hyper_pre', 0.0) + k.get('hyper_post', 0.0)
    if not seconds or not run.get('trace_steps') \
            or 'hc_mult' not in run['config']:
        return None
    bytes_ = flops_hyper.mixing_bytes(run['config'], run['seq_len'],
                                      run['batch'])
    peak = peaks.peaks_of(run['device_kind'])
    return 100.0 * bytes_ / peak['hbm_bytes_s'] * run['trace_steps'] / seconds
