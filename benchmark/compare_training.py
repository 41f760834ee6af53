"""The comparison that decides ``correct`` for a trained configuration.

The program was driven through two check windows (``drivers/fit.py``): in
window A only the first step had a learning rate, in window B only the
first three. The plain reference follows the same four steps from the
same seeded parameters on the same batches, in float32, and the update
rule of multi-precision SGD with momentum (window A's step at a thousandth
of the rate, `A_LR_SCALE`, so that its coasting momentum leaves the weights
at the seeded point for window B); where the rate is 0 the
momentum coasts (``mom *= m; w += mom``), which needs no gradient and is
applied in closed form. Compared:

* the loss of window A's first step and of window B's three steps;
* the first gradient as the optimizer got it, recovered from the program's
  momentum after window A: ``g = -mom_A / (m**(W-1) * lr_A) - wd * w_0``;
* the change of the (master) parameters over window B.

Gradient and change are compared twice. By the worst leaf: the gap
between the program's norm of that leaf and the reference's, against the
reference's norm of that leaf or of its median leaf, whichever is larger;
that catches a leaf that is not trained or trained at another scale. And by
their distance, ``||program - reference|| / ||reference||`` over all leaves
together: a gap of norms hardly sees unbiased rounding noise (it adds in
quadrature), the distance sees it in full, and it is the number the
lower-precision control fails.

Limits (`LIMITS`) were set from chip readings, PERF.md section 2: above
the largest value sound runs gave over a dozen seeds, below the smallest
the control gave (the reference computed with float8 operands,
``quant=True``: the nearest precision below the configuration's bfloat16).
The loss and the change hardly move under the control; they are held at
three times the sound runs' largest, against the faults they are there to
catch (part of a batch left out; a step that returns its state
unchanged).
"""
import functools

import numpy as np

from benchmark.reference import convnets

# window A's one step is taken at this share of the configuration's rate
A_LR_SCALE = 1e-3

LIMITS = {'loss': 0.07, 'grad': 0.4, 'change': 0.4,
          'grad_distance': 0.125, 'change_distance': 0.125}


def _place(devices):
    """How arrays reach the reference's devices: parameters whole on each,
    batches split by rows over them."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ('dp',))
    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P('dp'))
    return (lambda tree: jax.device_put(tree, whole),
            lambda x: jax.device_put(x, rows))


def follow(model, start, batches, window, opt, devices, quant=False):
    """The reference's four steps. Returns losses (A1, B1, B2, B3), the
    first gradient and the change over window B, leaf by leaf, as
    numpy."""
    import jax
    import jax.numpy as jnp
    lr, m, wd = (float(opt['learning_rate']), float(opt['momentum']),
                 float(opt['wd']))
    put_whole, put_rows = _place(devices)

    @functools.partial(jax.jit, static_argnums=3)
    def step(w, mom, g, rate):
        return convnets.sgd_momentum_step(w, mom, g, rate, m, wd)

    @functools.partial(jax.jit, static_argnums=2)
    def coast(w, mom, n):
        s = sum(m ** k for k in range(1, n + 1))
        return ({k: w[k] + s * mom[k] for k in w},
                {k: mom[k] * m ** n for k in w})

    w = put_whole({k: jnp.asarray(v) for k, v in start.items()})
    mom = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses = []

    def one(w, mom, xy, rate):
        x, y = put_rows(jnp.asarray(xy[0])), put_rows(jnp.asarray(xy[1]))
        loss, g = convnets.loss_and_grad(model, w, x, y, quant)
        losses.append(float(loss))
        return step(w, mom, g, rate) + (g,)

    w, mom, g_first = one(w, mom, batches['A'][0], lr * A_LR_SCALE)
    w, mom = coast(w, mom, window - 1)
    w_a = w
    for xy in batches['B'][:3]:
        w, mom, _ = one(w, mom, xy, lr)
    w, mom = coast(w, mom, window - 3)
    to_np = lambda t: {k: np.asarray(v) for k, v in t.items()}   # noqa: E731
    return losses, to_np(g_first), to_np({k: w[k] - w_a[k] for k in w})


def program_readings(prog, window, opt):
    """The same three readings from the program's losses and optimizer
    state."""
    lr, m, wd = (float(opt['learning_rate']), float(opt['momentum']),
                 float(opt['wd']))
    grad, change = {}, {}
    for n, (w_a, mom_a) in prog['state_a'].items():
        g = -mom_a / (m ** (window - 1) * lr * A_LR_SCALE) \
            - convnets.weight_decay_of(n, wd) * prog['start'][n]
        grad[n] = g
        change[n] = prog['state_b'][n][0] - w_a
    return list(prog['loss_a']) + list(prog['loss_b']), grad, change


def _norm(x):
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


def worst_leaf(got, want):
    """(gap, leaf): the largest gap between the two sides' norms of a leaf,
    against the reference's norm of that leaf or of its median leaf."""
    ref = {n: _norm(v) for n, v in want.items()}
    floor = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for n in want:
        gap = abs(_norm(got[n]) - ref[n]) / max(ref[n], floor, 1e-30)
        if gap > worst or where is None:
            worst, where = gap, n
    return worst, where


def distance(got, want):
    """||got - want|| / ||want|| over all leaves together."""
    num = sum(np.sum(np.square(got[n] - want[n], dtype=np.float64))
              for n in want)
    den = sum(np.sum(np.square(want[n], dtype=np.float64)) for n in want)
    return float(np.sqrt(num / max(den, 1e-300)))


def gaps(got, want):
    """The numbers compared, readings `got` against `want`, and the leaves
    that set the worst-leaf ones."""
    loss = max(abs(a - b) / max(abs(b), 1e-30)
               for a, b in zip(got[0], want[0]))
    grad, grad_leaf = worst_leaf(got[1], want[1])
    change, change_leaf = worst_leaf(got[2], want[2])
    return ({'loss': loss, 'grad': grad, 'change': change,
             'grad_distance': distance(got[1], want[1]),
             'change_distance': distance(got[2], want[2])},
            {'grad': grad_leaf, 'change': change_leaf})


def check(ctx, cfg, prog, batches, window):
    model = cfg['reference'].split(':')[1]
    want = follow(model, prog['start'], batches, window, cfg['optimizer'],
                  ctx.devices)
    got = program_readings(prog, window, cfg['optimizer'])
    ctx.log('losses of steps A1 B1 B2 B3: program %s, reference %s'
            % (['%.5f' % v for v in got[0]], ['%.5f' % v for v in want[0]]))
    g, leaves = gaps(got, want)
    ctx.log('worst leaves: gradient %s, change %s'
            % (leaves['grad'], leaves['change']))
    ctx.checks.equal('losses read from the check windows', len(got[0]), 4)
    ctx.checks.at_most('loss gap, steps A1 B1 B2 B3', g['loss'],
                       LIMITS['loss'])
    ctx.checks.at_most('first gradient gap, worst leaf', g['grad'],
                       LIMITS['grad'])
    ctx.checks.at_most('change over three steps gap, worst leaf',
                       g['change'], LIMITS['change'])
    ctx.checks.at_most('first gradient, distance', g['grad_distance'],
                       LIMITS['grad_distance'])
    ctx.checks.at_most('change over three steps, distance',
                       g['change_distance'], LIMITS['change_distance'])
    return g
