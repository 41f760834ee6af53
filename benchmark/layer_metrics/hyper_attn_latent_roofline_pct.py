"""``attn_latent_roofline_pct`` for a configuration of the ``xing4_0``
family: the latent attention kernels' share of their roofline over every
block computed, the prediction module's too
(``reduce/flops_hyper.attention_work``), at the cell's own length (4096
keys), with the kernels' time summed by their instructions' own names."""
from benchmark.reduce import flops_hyper, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('attention_latent')
    if not seconds or not run.get('trace_steps') \
            or 'hc_mult' not in run['config']:
        return None
    flops, bytes_ = flops_hyper.attention_work(
        run['config'], run['seq_len'], run['batch'])
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least * run['trace_steps'] / seconds
