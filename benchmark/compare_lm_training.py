"""The comparison that decides ``correct`` for a trained decoder
configuration (``drivers/fit_tokens.py``), beside ``compare_training.py``,
whose readings and algebra it takes: the loss of window A's first step and
of window B's three, the first gradient as the optimizer got it (from the
momentum after window A), and the change of the master parameters over
window B, each by the worst leaf and by distance (``gaps``,
``program_readings``). What differs is the reference that follows the four
steps, ``reference/laguna.py``, and how it is fitted onto the chip beside
811 M float32 parameters: weights, momentum and one gradient live on the
device, donated from step to step; the first gradient and window A's
weights go to the host as soon as they exist.

One more reading, where the configuration has routed experts: the
token-expert pairs that landed on the experts held here, per sparse layer,
in each of the four steps. The program's router works in bfloat16 and the
reference's in float32, so a pair near the top-k boundary can flip; the
flips are counted (the gap of the two counts, summed over layers, against
the reference's count) and held to a limit.

A run of the cell has 360 s in the driver's check, its set-up and this
comparison included, so the reference's time is kept off the run's path
where it can be: ``Prepared`` compiles the reference's four programs for
the chip in a thread of its own while the module is still being set up
(compiling needs the host alone), the arrays cross in one ``device_get``
and not leaf by leaf, and the norms and distances (``gaps``: the numbers
of ``compare_training.gaps``, float64 sums) are taken leaf by leaf on a
few threads in one pass over the four readings.

Limits (`LIMITS`), set as PERF.md section 2 says, from chip readings of PR
27: above the largest that sound runs gave over 15 seeds, below the
smallest of the control (``tools/control_lm.py``: the reference with
float8 operands in every matrix product). Here every number separates:
the control's smallest is 6 to 27 times the sound runs' largest, and each
limit lies a factor of 2.4 or more from both. Each limit's readings are
beside it and in PERF.md section 2.
"""
import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.compare_training import A_LR_SCALE
from benchmark.reference import laguna

# (largest of 15 sound runs on 15 seeds | smallest of the control on 3
# seeds); my chip runs, PR 27. The worst gradient leaf is always a router's
# or an expert's: the program routes in bfloat16 and the reference in
# float32, so some pairs near the top-k boundary go to other experts, and
# in one run of the 15 that moved a router's gradient norm by 1.4% (the
# other 14: 0.56% at most). Its limit lies midway (in ratio) between the
# two readings; the others keep a factor of 2.4 or more to both sides.
LIMITS = {'loss': 5e-4,             # 1.6e-4 | 1.2e-3
          'grad': 0.035,            # 0.0141 | 0.085
          'change': 0.025,          # 0.0083 | 0.070
          'grad_distance': 0.02,    # 0.0036 | 0.085
          'change_distance': 0.02,  # 0.0033 | 0.088
          'pairs': 0.012}           # 0.0038 (6 traced runs) | 0.029

THREADS = 8
CHUNK = 1 << 20     # elements of a leaf summed at a time, in float64


def _programs(momentum, wd):
    """The reference's programs as jitted functions: the rounding of the
    masters, loss and gradient, the update and the coasting steps (rates
    and factors enter as float32 scalars, so each compiles once)."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, mom, g, rate):
        return laguna.sgd_momentum_step(w, mom, g, rate, momentum, wd)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def coast(w, mom, s, f):
        return ({k: w[k] + s * mom[k] for k in w},
                {k: mom[k] * f for k in w})

    return {'working': jax.jit(laguna.working_weights),
            'grad': laguna._loss_and_grad, 'step': step, 'coast': coast}


class Prepared:
    """The reference's programs compiled ahead of their first call, in a
    thread started at once: `shapes` are the parameters', `batch_shape` a
    step's ``(batch, seq_len)``. ``programs()`` waits for the thread and
    gives the compiled programs (or raises what the compilation raised)."""

    def __init__(self, cfg, shapes, batch_shape, opt, quant=False, log=None):
        self.cfg_json = laguna.hashable(cfg)
        self.quant = bool(quant)
        self.jitted = _programs(float(opt['momentum']), float(opt['wd']))
        self.compiled, self.error, self.seconds = None, None, 0.0
        self.log = log or (lambda msg: None)
        self.thread = threading.Thread(
            target=self._compile, args=(dict(shapes), tuple(batch_shape)),
            name='reference-compile', daemon=True)
        self.thread.start()

    def _compile(self, shapes, batch_shape):
        import jax
        import jax.numpy as jnp
        t = time.perf_counter()
        try:
            w = {k: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
                 for k, s in shapes.items()}
            ids = jax.ShapeDtypeStruct(batch_shape, jnp.int32)
            x = jax.ShapeDtypeStruct((), jnp.float32)
            j = self.jitted
            self.compiled = {
                'working': j['working'].lower(w).compile(),
                'grad': j['grad'].lower(w, ids, ids, self.cfg_json,
                                        self.quant).compile(),
                'step': j['step'].lower(w, w, w, x).compile(),
                'coast': j['coast'].lower(w, w, x, x).compile()}
        except BaseException as e:  # noqa: BLE001 - raised by programs()
            self.error = e
        self.seconds = time.perf_counter() - t

    def programs(self):
        self.thread.join()
        if self.error is not None:
            raise self.error
        self.log('the reference\'s programs were compiled ahead in %.1fs, '
                 'beside the set-up' % self.seconds)
        return self.compiled


def fetch(tree):
    """{name: numpy} of a dict of device arrays: every leaf's copy is
    started before the first is waited for."""
    import jax
    return dict(zip(tree, jax.device_get(list(tree.values()))))


def follow(cfg, start, batches, window, opt, device, quant=False,
           prepared=None, log=None):
    """The reference's four steps on `device`. Returns (losses (A1, B1, B2,
    B3), pairs per sparse layer in each of the four steps, the first
    gradient and the change over window B leaf by leaf, as numpy)."""
    import jax
    import jax.numpy as jnp
    log = log or (lambda msg: None)
    lr, m = float(opt['learning_rate']), float(opt['momentum'])
    if prepared is None:
        shape = np.shape(batches['A'][0][0])
        prepared = Prepared(cfg, {k: v.shape for k, v in start.items()},
                            shape, opt, quant, log)
    prog = prepared.programs()
    f32 = lambda v: jnp.asarray(v, jnp.float32)     # noqa: E731

    def ids(v):
        return jax.device_put(np.asarray(v, np.int32), device)

    def coast(w, mom, n):
        return prog['coast'](w, mom, f32(sum(m ** k for k in range(1, n + 1))),
                             f32(m ** n))

    t = time.perf_counter()
    # `start` is emptied as its leaves reach the device: the host keeps no
    # second copy beside the readings
    w = {k: jax.device_put(start.pop(k), device) for k in sorted(start)}
    mom = jax.tree_util.tree_map(jnp.zeros_like, w)
    jax.block_until_ready(mom)
    log('reference: parameters on the device: %.1fs'
        % (time.perf_counter() - t))
    losses, pairs = [], []

    def one(w, mom, xy, rate, keep=False):
        t = time.perf_counter()
        loss, n, g = prog['grad'](prog['working'](w), ids(xy[0]), ids(xy[1]))
        losses.append(float(loss))
        pairs.append([int(v) for v in np.asarray(n)])
        t1 = time.perf_counter()
        kept = fetch(g) if keep else None
        t2 = time.perf_counter()
        out = prog['step'](w, mom, g, f32(rate)) + (kept,)
        log('reference: step %d: loss and gradient %.1fs%s'
            % (len(losses), t1 - t,
               ', gradient to the host %.1fs' % (t2 - t1) if keep else ''))
        return out

    w, mom, g_first = one(w, mom, batches['A'][0], lr * A_LR_SCALE, True)
    w, mom = coast(w, mom, window - 1)
    t = time.perf_counter()
    w_a = fetch(w)
    log('reference: weights after window A to the host: %.1fs'
        % (time.perf_counter() - t))
    for xy in batches['B'][:3]:
        w, mom, _ = one(w, mom, xy, lr)
    w, mom = coast(w, mom, window - 3)
    del mom
    t = time.perf_counter()
    w_b = fetch(w)
    del w

    def minus(k):
        return k, w_b.pop(k) - w_a.pop(k)

    with ThreadPoolExecutor(THREADS) as pool:
        change = dict(pool.map(minus, sorted(w_b)))
    log('reference: change over window B on the host: %.1fs'
        % (time.perf_counter() - t))
    return losses, pairs, g_first, change


def _sums(got, want):
    """(sum got^2, sum want^2, sum (got - want)^2) of one leaf, float64, a
    chunk at a time so that no float64 copy of the leaf is made."""
    a, b = np.ravel(got), np.ravel(want)
    s = np.zeros(3)
    for i in range(0, a.size, CHUNK):
        x = a[i:i + CHUNK].astype(np.float64)
        y = b[i:i + CHUNK].astype(np.float64)
        s[0] += x @ x
        s[1] += y @ y
        x -= y
        s[2] += x @ x
    return s


def _reading_gaps(got, want):
    """(worst-leaf gap, its leaf, distance over all leaves) of one reading:
    ``compare_training.worst_leaf`` and ``distance``, from one pass."""
    names = list(want)
    with ThreadPoolExecutor(THREADS) as pool:
        sums = dict(zip(names, pool.map(
            lambda n: _sums(got[n], want[n]), names)))
    ref = {n: float(np.sqrt(sums[n][1])) for n in names}
    floor = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for n in names:
        gap = abs(float(np.sqrt(sums[n][0])) - ref[n]) \
            / max(ref[n], floor, 1e-30)
        if gap > worst or where is None:
            worst, where = gap, n
    num = sum(sums[n][2] for n in names)
    den = sum(sums[n][1] for n in names)
    return worst, where, float(np.sqrt(num / max(den, 1e-300)))


def gaps(got, want):
    """The numbers compared, readings `got` against `want` (each: losses,
    first gradient, change), and the leaves that set the worst-leaf ones:
    what ``compare_training.gaps`` gives."""
    loss = max(abs(a - b) / max(abs(b), 1e-30)
               for a, b in zip(got[0], want[0]))
    grad, grad_leaf, grad_distance = _reading_gaps(got[1], want[1])
    change, change_leaf, change_distance = _reading_gaps(got[2], want[2])
    return ({'loss': loss, 'grad': grad, 'change': change,
             'grad_distance': grad_distance,
             'change_distance': change_distance},
            {'grad': grad_leaf, 'change': change_leaf})


def pair_flips(got, want):
    """(share, flips): the gap of the two sides' pair counts summed over
    steps and layers, against the reference's total."""
    flips = sum(abs(a - b) for g, w in zip(got, want) for a, b in zip(g, w))
    return flips / max(sum(sum(w) for w in want), 1), flips


def check(ctx, cfg, prog, batches, window):
    """`prog`: the driver's readings: `losses` of the four steps, `grad`
    and `change` leaf by leaf (``compare_training.program_readings``'
    algebra, applied in place by the driver), `start` (consumed here),
    `prepared` (the reference's programs compiled ahead, or absent) and
    `pairs`, the program's per-step counts of the four steps, or None where
    the run has no telemetry to carry them."""
    want = follow(cfg, prog.pop('start'), batches, window, cfg['optimizer'],
                  ctx.devices[0], prepared=prog.get('prepared'), log=ctx.log)
    got = (prog['losses'], prog['grad'], prog['change'])
    ctx.log('losses of steps A1 B1 B2 B3: program %s, reference %s'
            % (['%.5f' % v for v in got[0]], ['%.5f' % v for v in want[0]]))
    t = time.perf_counter()
    g, leaves = gaps(got, (want[0], want[2], want[3]))
    ctx.log('norms and distances of the readings: %.1fs'
            % (time.perf_counter() - t))
    ctx.log('worst leaves: gradient %s, change %s'
            % (leaves['grad'], leaves['change']))
    chk = ctx.checks
    chk.equal('losses read from the check windows', len(got[0]), 4)
    chk.at_most('loss gap, steps A1 B1 B2 B3', g['loss'], LIMITS['loss'])
    chk.at_most('first gradient gap, worst leaf', g['grad'], LIMITS['grad'])
    chk.at_most('change over three steps gap, worst leaf', g['change'],
                LIMITS['change'])
    chk.at_most('first gradient, distance', g['grad_distance'],
                LIMITS['grad_distance'])
    chk.at_most('change over three steps, distance', g['change_distance'],
                LIMITS['change_distance'])
    ctx.log('pairs on the experts held, per sparse layer, steps A1 B1 B2 B3: '
            'reference %s' % (want[1],))
    if prog.get('pairs') is not None:
        share, flips = pair_flips(prog['pairs'], want[1])
        ctx.log('program %s: %d routing flips' % (prog['pairs'], flips))
        chk.at_most('pairs computed against the reference, flips',
                    share, LIMITS['pairs'])
        g['pairs'] = share
    return g
