"""The ``attention_blockdiff_*`` kernels' share of their roofline: the
least time for the work the mask's TRUE pairs require in the traced slice
(``reduce/flops_blockdiff.attention_work``: 2 forward and 5 backward
products over ``B^2 n (n + 1)`` pairs a head, or the bytes of the kernels'
operands, whichever takes longer), over the kernels' time summed by their
instructions' own names. The kernel blocks a walk visits and the mask
empties in part (the diagonal pairs; the noisy block pair, of which 4 x 4
blocks on the diagonal hold anything) are the kernels' cost, not required
work."""
from benchmark.reduce import flops_blockdiff, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('attention_blockdiff')
    if not seconds or not run.get('trace_steps') \
            or 'block_length' not in run['config']:
        return None
    flops, bytes_ = flops_blockdiff.attention_work(
        run['config'], run['seq_len'], run['batch'])
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least * run['trace_steps'] / seconds
