"""The gated delta rule's share of its roofline, forward and backward: the
least time the chip could take for what the scans of every
linear-attention layer have to do in a trained step
(``reduce/flops_linear.delta_rule_least_seconds``: the chunked form's
products at a chunk of 64 over the bf16 peak, or q, k, v, g, beta and o
each moved once forward and those and their cotangents once backward over
the memory bandwidth, whichever is larger; no recomputation), over the
device time of the ``GatedDeltaRule`` nodes, every pass, from the traced
slice joined to the compiled window's scope map (``reduce/scopes.py``):
whatever implements the op, the kernels ``delta_rule_*`` and XLA's part
beside them, is held to the same work. A mirrored block's second forward
counts against it."""
from benchmark.reduce import flops_linear, peaks, scopes


def read(run):
    if run['device_kind'] == 'cpu' or 'seq_len' not in run \
            or 'linear_key_head_dim' not in run['config']:
        return None
    t = scopes.table(run)
    if t is None:
        return None
    seconds = sum(v for (op, _, _), v in t['rows'].items()
                  if op == 'GatedDeltaRule')
    if not seconds:
        return None
    least = flops_linear.delta_rule_least_seconds(
        run['config'], run['seq_len'], run['batch'],
        peaks.peaks_of(run['device_kind']))
    return 100.0 * least / seconds
