"""``moe_expert_roofline_pct`` for a configuration of the ``lfm2_moe``
family: the grouped expert products' share of their roofline at the pairs
the program's counters report for the traced slice (``moe.window``
events), against the larger of the products' time and the bytes'
(``reduce/flops_hybrid.expert_least_seconds``: at 512 rows a held expert
of 2048 x 1536 the products bound it, where the other decoder cells'
experts are bound by their weights' bytes), over the summed device time of
the ``moe_expert_matmul*`` instructions alone."""
from benchmark.reduce import flops_hybrid, peaks


def read(run):
    if run['device_kind'] == 'cpu':     # a rehearsal: no device number
        return None
    seconds = (run.get('kernels') or {}).get('moe_expert')
    pairs = run.get('moe_pairs_traced')
    if not seconds or not pairs or not run.get('trace_steps') \
            or 'conv_L_cache' not in run['config']:
        return None
    least = flops_hybrid.expert_least_seconds(
        run['config'], pairs, run['trace_steps'],
        peaks.peaks_of(run['device_kind']))
    return 100.0 * least / seconds
