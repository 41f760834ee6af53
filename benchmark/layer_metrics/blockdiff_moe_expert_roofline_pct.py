"""``moe_expert_roofline_pct`` for a configuration of the ``sdar_moe``
family trained as a block-diffusion model: the grouped expert products'
share of their roofline at the pairs the program's counters report for the
traced slice (``moe.window`` events; the noisy and the clean rows are
routed alike), against the larger of the products' time and the bytes'
(``reduce/flops_blockdiff.expert_least_seconds``: Kanana's shapes, 16 held
experts of 2048 x 768, but at a pair a row, 512 rows each, where the
products bound it),
over the summed device time of the ``moe_expert_matmul*`` instructions
alone."""
from benchmark.reduce import flops_blockdiff, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('moe_expert')
    pairs = run.get('moe_pairs_traced')
    if not seconds or not pairs or not run.get('trace_steps') \
            or 'block_length' not in run['config']:
        return None
    least = flops_blockdiff.expert_least_seconds(
        run['config'], pairs, run['trace_steps'],
        peaks.peaks_of(run['device_kind']))
    return 100.0 * least / seconds
