"""The sliding-window attention kernels' share of their roofline,
forward and backward: the least time the chip could take for the work the
layers of this kind require in the traced slice (operations over the bf16
peak or bytes over the memory bandwidth, whichever is larger;
``reduce/flops_lm.attention_work``), over the summed device time of the
``attention_window_*`` kernels. The kernels also run the recomputed
forward pass of each mirrored block, which is not required work."""
from benchmark.reduce import flops_lm, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('attention_window')
    if not seconds or not run.get('trace_steps') or 'seq_len' not in run:
        return None
    flops, bytes_ = flops_lm.attention_work(
        run['config'], run['seq_len'], run['batch'], windowed=True)
    peak = peaks.peaks_of(run['device_kind'])
    least = max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
    return 100.0 * least * run['trace_steps'] / seconds
