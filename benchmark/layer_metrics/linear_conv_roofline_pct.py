"""The linear-attention layers' convolution's share of its roofline,
forward and backward: the least time the chip could take to move the bytes
that ``silu(conv(.))`` over ``[q | k | v]`` has to move in a trained step
(``reduce/flops_linear.conv_bytes``: two arrays of [rows, H (2 dk + dv)]
forward and three backward, each once, no recomputation; memory bounds
it), over the device time of the ``ShortConv`` nodes, every pass, from the
traced slice joined to the compiled window's scope map
(``reduce/scopes.py``): whatever implements the op, kernels or one of
XLA's fusions, is held to the same bytes. A mirrored block runs the op's
forward a second time in the backward pass, which the share counts
against it."""
from benchmark.reduce import flops_linear, peaks, scopes


def read(run):
    if run['device_kind'] == 'cpu' or 'seq_len' not in run \
            or 'linear_conv_kernel_dim' not in run['config']:
        return None
    t = scopes.table(run)
    if t is None:
        return None
    seconds = sum(v for (op, _, _), v in t['rows'].items()
                  if op == 'ShortConv')
    if not seconds:
        return None
    bytes_ = flops_linear.conv_bytes(run['config'], run['seq_len'],
                                     run['batch'])
    return 100.0 * bytes_ / peaks.peaks_of(run['device_kind'])['hbm_bytes_s'] \
        / seconds
