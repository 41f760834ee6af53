"""``attn_full_roofline_pct`` for a configuration of the ``lfm2_moe``
family: the causal full attention kernels' share of their roofline at
heads 64 wide (``reduce/flops_hybrid.attention_work``: a score product 64
deep half-fills the chip's contraction), over the attention layers alone,
with the kernels' time summed by their instructions' own names
(``attention_full_*``; the transposes that carry narrow heads across them
are XLA's and are not in it)."""
from benchmark.reduce import flops_hybrid, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('attention_full')
    if not seconds or not run.get('trace_steps') \
            or 'conv_L_cache' not in run['config']:
        return None
    flops, bytes_ = flops_hybrid.attention_work(
        run['config'], run['seq_len'], run['batch'])
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least * run['trace_steps'] / seconds
