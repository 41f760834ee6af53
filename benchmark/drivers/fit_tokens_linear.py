"""Driver ``fit_tokens_linear``: ``fit_tokens``'s job for a dense decoder
whose layers are mostly gated delta-rule linear attention
(``configs/olmo_hybrid_7b.json``), run by ``fit_tokens_heads`` under limits
set from this cell's own readings.

``drivers/fit_tokens_heads.py`` runs a one-headed configuration with no
expert layer as it stands (``fit_tokens_hybrid.py`` says what it gives: the
metrics from the configuration, the reference walked with three float32
copies of the parameters, which 928.9 M parameters need more than any cell
before; with no ``MoE`` node its checks of the expert layers hold nothing,
and the reference's ``_loss_and_grad`` returns no pairs). A run is a process
of its own, so this driver binds, for this process only, what this family
needs, runs ``fit_tokens_heads.run`` unchanged, and checks one thing more:

* `LIMITS`: this cell's own (below; PERF.md section 2 has every reading
  beside its limit, sound and under the float8 control). The routing flips
  are 0 of 0 here: ``pairs`` is carried because ``fit_tokens_heads`` reads
  every key, and holds nothing.
* `KERNEL_GROUPS`: ``delta_rule`` (``delta_rule_fwd`` / ``_bwd``) and
  ``attention_full`` (``attention_full_*`` at 30 heads on 30), summed from
  the reduced capture's table by the instruction's own name.
* a traced run's counter ``delta_rule.rows`` (the rows the
  ``GatedDeltaRule`` nodes were handed, from their auxiliary states: every
  node ran in every step, at the size the iterator sent) is the driver's
  own product: windows x steps x tokens a step x linear layers.

Traffic file keys: ``fit_tokens``'s.
"""
from benchmark.drivers import fit_tokens_heads

# This cell's readings beside each limit (my chip runs, PR 49: largest of 30
# sound runs on 29 seeds, 25 of them from ``git archive`` of the tree | the
# float8 control on three seeds, smallest first | the smaller of two
# faults planted in the program at this size, the chain's state dropped
# between chunks and beta without its factor 2; PERF.md section 2 has them
# all). Every limit stands 2.2 or more over the largest sound reading. The
# two distances separate: the control is "not correct" by both on every
# seed. The loss does not (the control's smallest is twice the sound
# runs' largest: float8 hardly moves it), so it takes the accepted decoder
# cells' 5e-4, three times the largest sound reading; the control's other
# two seeds and both faults fail it. The two worst-leaf numbers do not
# either (a gap of norms hardly sees unbiased rounding noise, as in every
# decoder cell): they stand against a fault, which reads 14 and 21 times
# the limit. The worst leaf of a sound run is a linear-attention layer's
# on every seed, most often the decay's projection (``lin_a_weight``: 30
# rows whose gradient sums over every row of the recurrence).
LIMITS = {'loss': 5e-4,     # 1.55e-4 | 3.21e-4, 6.45e-4, 1.61e-3 | 0.0136
          'grad': 0.025,            # 0.0092 | 0.0129, 0.0309, 0.0310 | 0.351
          'change': 0.015,          # 0.0061 | 0.0099, 0.0160, 0.0228 | 0.318
          'grad_distance': 0.045,   # 0.0149 | 0.1307, 0.1355, 0.1514 | 0.475
          'change_distance': 0.035,  # 0.0131 | 0.0980, 0.1007, 0.1031 | 0.401
          'pairs': 0.012}           # 0 of 0: no expert layer

KERNEL_GROUPS = (('delta_rule', 'delta_rule_'),
                 ('attention_full', 'attention_full_'))


def bind():
    """Put this family's limits and kernel groups where this process's
    ``fit_tokens_heads`` looks them up."""
    fit_tokens_heads.LIMITS = LIMITS
    fit_tokens_heads.KERNEL_GROUPS = KERNEL_GROUPS


def linear_layers(cfg):
    return sum(1 for k in cfg['layer_types'] if k == 'linear_attention')


def run(ctx):
    bind()
    out = fit_tokens_heads.run(ctx)
    run_ = out['run']
    if ctx.trace:
        ctx.checks.equal(
            'rows handed to the linear-attention layers (delta_rule.rows)',
            run_['counters'].get('delta_rule.rows'),
            run_['windows'] * run_['steps_per_window'] * run_['batch']
            * run_['seq_len'] * linear_layers(ctx.config))
        ctx.log('largest magnitude of a recurrent state after a step\'s '
                'last row (delta_rule.state_abs_max): %s'
                % run_['gauges'].get('delta_rule.state_abs_max'))
    return out
