"""The grouped expert product's share of its roofline, forward and
backward, at the pairs the program's counters report for the traced slice
(``moe.window`` events): the least time for the products of those pairs
and for reading every held expert's weights three times a layer and step
(``reduce/flops_lm.expert_work``), over the summed device time of the
``moe_expert_matmul*`` kernels. Memory bounds it: each held expert sees a
few hundred rows a step."""
from benchmark.reduce import flops_lm, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('moe_expert')
    pairs = run.get('moe_pairs_traced')
    if not seconds or not pairs or not run.get('trace_steps'):
        return None
    cfg = run['config']
    flops, bytes_ = flops_lm.expert_work(cfg, pairs)
    bytes_ += 3 * flops_lm.expert_weight_bytes(cfg) \
        * flops_lm.sparse_layers(cfg) * run['trace_steps']
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least / seconds
