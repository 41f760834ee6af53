"""The grouped expert product's share of its roofline under the sigmoid
router, forward and backward, at the pairs the program's counters report
for the traced slice (``moe.window`` events): the least time for the
products of those pairs and for reading every held expert's weights three
times a layer and step (``reduce/flops_latent.expert_work``), over the
summed device time of the ``moe_expert_matmul*`` kernels.
``moe_expert_roofline_pct`` for a configuration whose sparse layers are
counted from ``first_k_dense_replace``."""
from benchmark.reduce import flops_latent, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('moe_expert')
    pairs = run.get('moe_pairs_traced')
    cfg = run['config']
    if not seconds or not pairs or not run.get('trace_steps') \
            or 'first_k_dense_replace' not in cfg:
        return None
    flops, bytes_ = flops_latent.expert_work(cfg, pairs)
    bytes_ += 3 * flops_latent.expert_weight_bytes(cfg) \
        * flops_latent.sparse_layers(cfg) * run['trace_steps']
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least / seconds
