"""Model FLOP/s utilisation of training a decoder with latent attention:
the operations the forward and backward passes require per token
(``reduce/flops_latent.py``, from the configuration's shapes at the step's
sequence length, the routed experts at their expected pairs, no
recomputation), times this run's tokens per second, over the chip's
published bf16 peak. ``lm_step_mfu_pct`` for the ``deepseek_v3`` family's
keys."""
from benchmark.reduce import flops_latent, peaks


def read(run):
    if run['device_kind'] == 'cpu' or not run.get('samples_s') \
            or 'seq_len' not in run or 'kv_lora_rank' not in run['config']:
        return None
    need = flops_latent.required_flops(run['config'], run['seq_len'])
    peak = peaks.peaks_of(run['device_kind'])['bf16_flops']
    return 100.0 * need['train'] * run['samples_s'] / (run['chips'] * peak)
