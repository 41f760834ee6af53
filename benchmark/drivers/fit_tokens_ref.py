"""Driver ``fit_tokens_ref``: ``fit_tokens`` with the plain reference that
the configuration's file names.

``drivers/fit_tokens.py`` and ``compare_lm_training.py`` look the reference
up under the module name ``laguna``, whatever the configuration's
``reference`` key says, and ``reduce/kernel_times.py`` groups the kernels of
that one configuration. A run is a process of its own, so this driver binds,
for this process only, what a second decoder configuration needs, and then
runs ``fit_tokens`` unchanged: the same schedule, readings and checks.

* the reference: ``benchmark/reference/<file>.py`` from the configuration's
  ``reference`` key (``"<file>:<model>"``), checked for the five names the
  comparison calls, bound as ``fit_tokens.laguna`` and
  ``compare_lm_training.laguna``;
* the kernel groups of the traced slice: ``LATENT_GROUPS`` ahead of
  ``kernel_times.GROUPS``, so that ``run['kernels']`` carries
  ``attention_latent`` from the one reduction of the capture.

The comparison's limits stay ``compare_lm_training.LIMITS``: PERF.md section
2 has this configuration's readings beside them.

What follows from running under the unchanged files: every leaf's name ends
in ``_weight`` (two axes at least), ``_gamma`` or ``_stats``, and every
auxiliary state is an expert layer's statistics.
"""
import os

from benchmark import compare_lm_training, harness
from benchmark.drivers import fit_tokens
from benchmark.reduce import kernel_times

HERE = os.path.dirname(os.path.abspath(__file__))
NEEDED = ('param_shapes', 'hashable', 'working_weights', '_loss_and_grad',
          'sgd_momentum_step')
LATENT_GROUPS = (('attention_latent', ('attention_latent',)),)


def load_reference(cfg):
    """The module ``benchmark/reference/<file>.py`` that `cfg` names."""
    name = str(cfg['reference']).split(':')[0]
    ref = harness.load_file_module(os.path.join(
        os.path.dirname(HERE), 'reference', name + '.py'))
    missing = [n for n in NEEDED if not hasattr(ref, n)]
    if missing:
        raise ValueError('reference %r lacks %s' % (name, missing))
    return ref


def bind(cfg):
    """Put `cfg`'s reference and the kernel groups where this process's
    ``fit_tokens`` and ``compare_lm_training`` look them up."""
    ref = load_reference(cfg)
    fit_tokens.laguna = compare_lm_training.laguna = ref
    if kernel_times.GROUPS[:len(LATENT_GROUPS)] != LATENT_GROUPS:
        kernel_times.GROUPS = LATENT_GROUPS + tuple(kernel_times.GROUPS)
    return ref


def run(ctx):
    bind(ctx.config)
    return fit_tokens.run(ctx)
