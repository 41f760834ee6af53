"""The gated short convolution's share of its roofline, forward and
backward: the least time the chip could take to move the bytes that the
conv operators of every conv layer have to move in a trained step
(``reduce/flops_hybrid.conv_bytes``: four arrays of [rows, hidden] forward
and seven backward, each once, no recomputation; memory bounds it), over
the device time of the ``GatedShortConv`` nodes, every pass, from the
traced slice joined to the compiled window's scope map
(``reduce/scopes.py``): whatever implements the op, kernels or one of
XLA's fusions, is held to the same bytes. A mirrored block runs the op's
forward a second time in the backward pass, which the share counts
against it."""
from benchmark.reduce import flops_hybrid, peaks, scopes


def read(run):
    if run['device_kind'] == 'cpu' or 'conv_L_cache' not in run['config'] \
            or 'seq_len' not in run:
        return None
    t = scopes.table(run)
    if t is None:
        return None
    seconds = sum(v for (op, _, _), v in t['rows'].items()
                  if op == 'GatedShortConv')
    if not seconds:
        return None
    bytes_ = flops_hybrid.conv_bytes(run['config'], run['seq_len'],
                                     run['batch'])
    return 100.0 * bytes_ / peaks.peaks_of(run['device_kind'])['hbm_bytes_s'] \
        / seconds
