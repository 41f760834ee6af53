"""``attn_full_roofline_pct`` for a configuration of the ``olmo_hybrid``
family: the causal full attention kernels' share of their roofline with as
many key/value heads as query heads (``reduce/flops_linear.attention_work``,
over ``flops_lm.visible_pairs``), over the attention layers alone, with the
kernels' time summed by their instructions' own names
(``attention_full_*``)."""
from benchmark.reduce import flops_linear, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('attention_full')
    if not seconds or not run.get('trace_steps') \
            or 'linear_key_head_dim' not in run['config']:
        return None
    flops, bytes_ = flops_linear.attention_work(
        run['config'], run['seq_len'], run['batch'])
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least * run['trace_steps'] / seconds
