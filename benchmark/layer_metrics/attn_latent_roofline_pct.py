"""The latent attention kernels' share of their roofline, forward and
backward: the least time the chip could take for the work every layer
requires in the traced slice (operations over the bf16 peak or bytes over
the memory bandwidth, whichever is larger;
``reduce/flops_latent.attention_work``: compute bounds it at 8192 tokens),
over the summed device time of the ``attention_latent_*`` kernels. A
mirrored block keeps the forward kernel's outputs, so no kernel runs twice;
the rotary products are 64 wide and fill half of a 128-wide pass, which
the share counts against the kernels."""
from benchmark.reduce import flops_latent, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('attention_latent')
    if not seconds or not run.get('trace_steps') or 'seq_len' not in run:
        return None
    flops, bytes_ = flops_latent.attention_work(
        run['config'], run['seq_len'], run['batch'])
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least * run['trace_steps'] / seconds
