"""``latent_moe_expert_roofline_pct`` for a configuration of the
``xing4_0`` family: the expert layers counted are the sparse layers and the
prediction module's (``reduce/flops_hyper.sparse_layers``), and the
kernels' time is that of the ``moe_expert_matmul*`` instructions alone."""
from benchmark.reduce import flops_hyper, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('moe_expert')
    pairs = run.get('moe_pairs_traced')
    cfg = run['config']
    if not seconds or not pairs or not run.get('trace_steps') \
            or 'hc_mult' not in cfg:
        return None
    flops, bytes_ = flops_hyper.expert_work(cfg, pairs)
    bytes_ += 3 * flops_hyper.expert_weight_bytes(cfg) \
        * flops_hyper.sparse_layers(cfg) * run['trace_steps']
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least / seconds
