"""Driver ``fit_tokens_heads``: ``fit_tokens``'s job for a decoder whose
symbol has several heads, trained through ``Module.fit`` with the metrics
the configuration names.

``drivers/fit_tokens.py`` fixes ``eval_metric=['ce', 'acc']``, reads one
loss a step and looks its reference up by a module's name; a symbol with a
second loss (a multi-token-prediction head) needs each metric bound to the
output it reads, a second loss read from the check windows, and a
reference whose loss-and-gradient program gives that second loss too. This
driver takes from ``fit_tokens`` and ``fit_tokens_ref`` what they share
(the iterator, the readings of the optimizer's state, the reference loaded
from the configuration's ``reference`` key) and follows ``fit_tokens.run``
step for step otherwise: the two check windows A and B, the timed ``fit``
of as many windows as ``--seconds`` take and one more, the rate from the
first fetch to the last, a traced slice, ``compare_lm_training.check``'s
six numbers, and a ``run`` dict of the same shape. What differs:

* ``eval_metric`` comes from the configuration's ``eval_metric`` list
  (``metric``, ``output``, ``label``): each becomes
  ``mx.metric.create(metric, output_names=[output], label_names=[label])``.
  The first is the main loss (a cross-entropy on the first output).
* A second cross-entropy, on another output, is the prediction module's.
  Its output is aligned to the label rows and its row 0 is uniform (the
  builder says why), so over T rows the metric reads ``((T - 1) L_mtp +
  ln V') / T`` with ``ln V' = -log(1 / V + 1e-12)``; ``L_mtp`` of the four
  check steps follows and is compared with the reference's under the loss
  limit.
* The reference's ``_loss_and_grad`` returns ``(L_main, pairs, gradient,
  L_mtp)`` and can be handed the float32 masters (``at_masters``: it
  rounds them where it uses them). ``compare_lm_training.follow`` holds
  masters, momentum, a rounded copy and the gradient at once, four float32
  copies of 913.5 M parameters, 14.6 GB beside the program's 3.2 GB of
  temporaries on a chip of 16.9: ``follow`` here walks the same four steps
  with three (`Reference`: the programs compiled ahead on a thread, as
  ``compare_lm_training.Prepared``), and ``check`` compares what
  ``compare_lm_training.check`` compares, with its functions (``gaps``,
  ``pair_flips``) under this cell's own limits (`LIMITS` below), and the
  second loss.
* Kernel groups of the traced slice are summed from the reduced capture's
  own table (``run['trace']['by_name']``) by the *instruction's* name (a
  Pallas kernel's custom call carries the name the program gave it), not by
  an event's whole text: a fusion that reads a kernel's result is not the
  kernel. ``run['kernels']`` has ``hyper_pre``, ``hyper_post``,
  ``attention_latent``, ``moe_expert`` and ``busy``.
* The expert layers' statistics are read from the auxiliary states that
  ``MoE`` nodes write; the mixing nodes' own (the gauge
  ``hyper.res_dev_max`` of a traced run) are ``run['hyper_res_dev_max']``.

Traffic file keys: ``fit_tokens``'s.
"""
import gc
import json
import math
import os
import pickle
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import compare_lm_training, data_lm, harness, weights_lm
from benchmark.drivers import fit_tokens, fit_tokens_ref
from benchmark.drivers.fit import Schedule, live_arrays, read_spans

# A traced run captures the last CAPTURED of its timed windows (fit_tokens
# captures them all): the slice is the CAPTURED - 1 whole periods between
# their starts. Four windows of this model were a capture that took 61 s to
# stop and 69 s to reduce, of the 360 s a run has.
CAPTURED = 2

# This cell's limits (PERF.md section 2 has the readings beside them; my
# chip runs, PR 37: largest of the sound runs | smallest of the float8
# control on three seeds). The streams are carried in bfloat16 and a
# sublayer's coefficients are functions of them, so a rounding moves all
# of a token's columns alike: the distances to the float32 reference are
# three times those of the other decoder cells, whose limits
# (``compare_lm_training.LIMITS``) a sound run here failed. The control
# fails both distances and the flips on every seed; the losses and the
# worst-leaf numbers do not separate and stand against a gross fault (an
# untrained leaf reads 1.0).
LIMITS = {'loss': 1.5e-3,           # 5.7e-4 (second head) | 6.4e-4
          'grad': 0.06,             # 0.0236 | 0.084
          'change': 0.1,            # 0.0377 | 0.079
          'grad_distance': 0.05,    # 0.0167 | 0.173
          'change_distance': 0.05,  # 0.0181 | 0.182
          'pairs': 0.012}           # 0.0034 (2 traced runs) | 0.029


class Capture(harness.Capture):
    """``harness.Capture`` without the interpreter's own calls (the
    profiler's Python tracer, on by default): no reduction reads them, and
    the capture is stopped and parsed on a run's path."""

    def start(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_start = time.perf_counter()

    def reduce_beside(self, windows):
        """Both reductions of the capture (``reduce/trace.py``'s and
        ``reduce/host_spans.py``'s, over the whole periods between the
        starts of `windows` windows) in a process of their own: parsing a
        capture is minutes of Python, which on a thread held the
        interpreter against the reference's steps. Returns the process;
        :meth:`reduced` waits for it."""
        code = ('import pickle, sys\n'
                'from benchmark.reduce import host_spans, trace\n'
                'path = trace.newest_xplane(sys.argv[1])\n'
                'n = int(sys.argv[3])\n'
                'pickle.dump({"trace": trace.reduce_file('
                'path, float(sys.argv[2]), 1, n), "host_spans": '
                'host_spans.reduce_file(path, 1, n)}, sys.stdout.buffer)\n')
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=repo)
        return subprocess.Popen(
            [sys.executable, '-c', code, self.dir,
             repr(self.t_stop - self.t_start), str(windows)],
            cwd=repo, env=env, stdout=subprocess.PIPE)

    @staticmethod
    def reduced(process):
        out, _ = process.communicate()
        if process.returncode:
            raise RuntimeError('the capture\'s reduction failed (%d)'
                               % process.returncode)
        return pickle.loads(out)
KERNEL_GROUPS = (('hyper_pre', 'hyper_pre_'), ('hyper_post', 'hyper_post_'),
                 ('attention_latent', 'attention_latent_'),
                 ('moe_expert', 'moe_expert_matmul'))


def bind(cfg):
    """`cfg`'s reference, bound where ``compare_lm_training``'s programs
    look the update up."""
    ref = fit_tokens_ref.load_reference(cfg)
    compare_lm_training.laguna = ref
    return ref


class Reference:
    """The reference's programs, ready before their first call
    (``compare_lm_training.Prepared``'s way, in two parts): they are traced
    and lowered here, at once and on the caller's thread, and compiled on
    a thread of their own. Tracing is Python and holds the interpreter's
    lock: on a thread beside ``fit``'s own tracing of the window each
    slowed the other, on every run's path; the compilation is the
    compiler's and runs beside it. `shapes` are the parameters',
    `batch_shape` a step's ``(batch, seq_len)``. ``programs()`` waits for
    the thread."""

    def __init__(self, ref, cfg, shapes, batch_shape, opt, quant=False,
                 log=None):
        import jax
        import jax.numpy as jnp
        self.log = log or (lambda msg: None)
        t = time.perf_counter()
        jitted = compare_lm_training._programs(float(opt['momentum']),
                                               float(opt['wd']))
        w = {k: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
             for k, s in shapes.items()}
        ids = jax.ShapeDtypeStruct(tuple(batch_shape), jnp.int32)
        x = jax.ShapeDtypeStruct((), jnp.float32)
        self.lowered = {
            'grad': ref._loss_and_grad.lower(w, ids, ids, ref.hashable(cfg),
                                             bool(quant), True),
            'step': jitted['step'].lower(w, w, w, x),
            'coast': jitted['coast'].lower(w, w, x, x)}
        self.log('the reference\'s programs traced and lowered: %.1fs'
                 % (time.perf_counter() - t))
        self.compiled, self.error, self.seconds = None, None, 0.0
        self.thread = threading.Thread(
            target=self._compile, name='reference-compile', daemon=True)
        self.thread.start()

    def _compile(self):
        t = time.perf_counter()
        try:
            self.compiled = {k: v.compile() for k, v in self.lowered.items()}
        except BaseException as e:  # noqa: BLE001 - raised by programs()
            self.error = e
        self.lowered = None
        self.seconds = time.perf_counter() - t

    def programs(self):
        self.thread.join()
        if self.error is not None:
            raise self.error
        self.log('the reference\'s programs were compiled in %.1fs, beside '
                 'the set-up' % self.seconds)
        return self.compiled


def follow(reference, start, batches, window, opt, device, log=None):
    """``compare_lm_training.follow`` with the masters handed to the
    loss-and-gradient program itself. Returns (losses (A1, B1, B2, B3),
    pairs per expert layer in each step, the first gradient and the change
    over window B leaf by leaf as numpy, the second head's four losses)."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor
    log = log or (lambda msg: None)
    fetch = compare_lm_training.fetch
    lr, m = float(opt['learning_rate']), float(opt['momentum'])
    prog = reference.programs()
    f32 = lambda v: jnp.asarray(v, jnp.float32)     # noqa: E731

    def ids(v):
        return jax.device_put(np.asarray(v, np.int32), device)

    def coast(w, mom, n):
        return prog['coast'](w, mom, f32(sum(m ** k for k in range(1, n + 1))),
                             f32(m ** n))

    t = time.perf_counter()
    w = {k: jax.device_put(start.pop(k), device) for k in sorted(start)}
    mom = jax.tree_util.tree_map(jnp.zeros_like, w)
    jax.block_until_ready(mom)
    log('reference: parameters on the device: %.1fs'
        % (time.perf_counter() - t))
    losses, second, pairs = [], [], []

    def one(w, mom, xy, rate, keep=False):
        t = time.perf_counter()
        loss, n, g, mtp = prog['grad'](w, ids(xy[0]), ids(xy[1]))
        losses.append(float(loss))
        second.append(float(mtp))
        pairs.append([int(v) for v in np.asarray(n)])
        t1 = time.perf_counter()
        kept = fetch(g) if keep else None
        out = prog['step'](w, mom, g, f32(rate)) + (kept,)
        log('reference: step %d: loss and gradient %.1fs%s'
            % (len(losses), t1 - t, ', gradient to the host %.1fs'
               % (time.perf_counter() - t1) if keep else ''))
        return out

    w, mom, g_first = one(w, mom, batches['A'][0],
                          lr * compare_lm_training.A_LR_SCALE, True)
    w, mom = coast(w, mom, window - 1)
    w_a = fetch(w)
    for xy in batches['B'][:3]:
        w, mom, _ = one(w, mom, xy, lr)
    w, mom = coast(w, mom, window - 3)
    del mom
    w_b = fetch(w)
    del w
    with ThreadPoolExecutor(compare_lm_training.THREADS) as pool:
        change = dict(pool.map(lambda k: (k, w_b.pop(k) - w_a.pop(k)),
                               sorted(w_b)))
    return losses, pairs, g_first, change, second


def second_gap(got, want):
    return max(abs(a - b) / max(abs(b), 1e-30) for a, b in zip(got, want))


def check(ctx, cfg, prog, batches, window):
    """``compare_lm_training.check`` over :func:`follow`: the same six
    numbers under `LIMITS`, and `prog['second']`, the second head's four
    losses, under the loss limit."""
    want = follow(prog['reference'], prog.pop('start'), batches, window,
                  cfg['optimizer'], ctx.devices[0], log=ctx.log)
    got = (prog['losses'], prog['grad'], prog['change'])
    ctx.log('losses of steps A1 B1 B2 B3: program %s, reference %s'
            % (['%.5f' % v for v in got[0]], ['%.5f' % v for v in want[0]]))
    g, leaves = compare_lm_training.gaps(got, (want[0], want[2], want[3]))
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
    ctx.log('pairs on the experts held, per expert layer, steps A1 B1 B2 B3: '
            'reference %s' % (want[1],))
    if prog.get('pairs') is not None:
        share, flips = compare_lm_training.pair_flips(prog['pairs'], want[1])
        ctx.log('program %s: %d routing flips' % (prog['pairs'], flips))
        chk.at_most('pairs computed against the reference, flips',
                    share, LIMITS['pairs'])
        g['pairs'] = share
    if prog.get('second') is not None:
        ctx.log('second head, losses of steps A1 B1 B2 B3: program %s, '
                'reference %s' % (['%.5f' % v for v in prog['second']],
                                  ['%.5f' % v for v in want[4]]))
        g['second_loss'] = second_gap(prog['second'], want[4])
        chk.at_most('second head loss gap, steps A1 B1 B2 B3',
                    g['second_loss'], LIMITS['loss'])
    return g


def make_metric(mx, cfg):
    """(the composite metric, index of the main loss, index of the second
    loss or None)."""
    metric = mx.metric.CompositeEvalMetric()
    losses = []
    for i, m in enumerate(cfg['eval_metric']):
        metric.add(mx.metric.create(m['metric'], output_names=[m['output']],
                                    label_names=[m['label']]))
        if m['metric'] == 'ce':
            losses.append(i)
    return metric, losses[0], losses[1] if len(losses) > 1 else None


def kernel_seconds(by_name, busy_s):
    """{group: device seconds} of `KERNEL_GROUPS` from the reduced
    capture's table, whose keys start with the instruction's own name."""
    out = {g: 0.0 for g, _ in KERNEL_GROUPS}
    for key, seconds in by_name.items():
        for group, prefix in KERNEL_GROUPS:
            if key.startswith(prefix):
                out[group] += seconds
                break
    out['busy'] = busy_s
    return out


def run(ctx):
    import jax

    cfg, tr = ctx.config, ctx.traffic
    batch, seq_len = int(tr['batch']), int(tr['seq_len'])
    W = int(tr['steps_per_window'])
    opt = cfg['optimizer']
    tokens_step = batch * seq_len

    def long_compile(event, duration, **_):
        if event.endswith('backend_compile_duration') and duration >= 5.0:
            ctx.log('a program compiled in %.1fs on thread %s'
                    % (duration, threading.current_thread().name))

    jax.monitoring.register_event_duration_secs_listener(long_compile)
    # first the program: a program without the builder fails here, at once
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.transformer import moe_stat_names

    sym = fit_tokens.build_symbol(cfg)
    param_names, aux_names, shapes = fit_tokens.symbol_shapes(
        sym, batch, seq_len)
    ref = bind(cfg)
    if {n: tuple(shapes[n]) for n in param_names} \
            != {n: tuple(v) for n, v in ref.param_shapes(cfg).items()}:
        raise ValueError('the builder\'s parameters are not the '
                         'reference\'s: %s' % sorted(
                             set(param_names) ^ set(ref.param_shapes(cfg))))
    moe_names = [n for n in moe_stat_names(sym) if n in aux_names]
    reference = Reference(ref, cfg, {n: shapes[n] for n in param_names},
                          (batch, seq_len), opt, log=ctx.log)

    t = time.perf_counter()
    made = weights_lm.make_params(shapes, ctx.seed)
    start = {n: made[n] for n in param_names}
    aux_start = {n: made[n] for n in aux_names}
    del made
    t1 = time.perf_counter()
    arg_params = fit_tokens.per_leaf(lambda n: mx.nd.array(start[n]),
                                     param_names)
    aux_params = {n: mx.nd.array(v) for n, v in aux_start.items()}
    ctx.log('%d parameter arrays, %.1f M parameters, and %d auxiliary '
            'arrays made from the seed in %.1fs, handed over as host '
            'arrays in %.1fs'
            % (len(param_names), sum(v.size for v in start.values()) / 1e6,
               len(aux_names), t1 - t, time.perf_counter() - t1))

    pool = data_lm.token_pool(ctx.seed, int(tr['pool_tokens']),
                              int(cfg['vocab_size']))
    ctx.log('stream of %d tokens, %d distinct ids'
            % (len(pool), len(np.unique(pool))))
    it = fit_tokens.make_iter(mx, pool, batch, seq_len, W)
    metric, main_at, second_at = make_metric(mx, cfg)

    steps = []  # (epoch, cumulative ce sum, tokens, second ce sum, when)

    def note(param):
        ms = param.eval_metric.metrics
        steps.append((param.epoch, float(ms[main_at].sum_metric),
                      int(ms[main_at].num_inst),
                      float(ms[second_at].sum_metric)
                      if second_at is not None else 0.0,
                      time.perf_counter()))

    def step_losses(epoch, second=False):
        out, prev = [], (0.0, 0, 0.0)
        for e, s, n, s2, _ in steps:
            if e == epoch:
                rows = max(n - prev[1], 1)
                out.append((s2 - prev[2]) / rows if second
                           else (s - prev[0]) / rows)
                prev = (s, n, s2)
        return out

    def fetched(epoch):
        return [t for e, _, _, _, t in steps if e == epoch][::W]

    def second_loss(metric_value):
        """L_mtp from the metric's mean over T rows, of which row 0 is
        uniform and the other T - 1 are the module's positions."""
        first_row = -math.log(1.0 / int(cfg['vocab_size']) + 1e-12)
        return (metric_value * seq_len - first_row) / (seq_len - 1)

    sched = Schedule(float(opt['learning_rate']), W)
    mod = mx.mod.Module(sym, context=mx.tpu(0))

    def fit(epoch):
        mod.fit(it, eval_metric=metric, kvstore=tr['kvstore'],
                optimizer=opt['name'],
                optimizer_params={
                    'learning_rate': float(opt['learning_rate']),
                    'momentum': float(opt['momentum']),
                    'wd': float(opt['wd']),
                    'multi_precision': bool(opt['multi_precision']),
                    'lr_scheduler': sched},
                arg_params=arg_params, aux_params=aux_params,
                batch_end_callback=note, begin_epoch=epoch,
                num_epoch=epoch + 1)
        jax.block_until_ready(live_arrays(mod))

    def expert_stats():
        return fit_tokens.expert_stats(mod, moe_names)

    # -- set-up: the two check windows, which are also the warm-up --------
    first_batches, stats = {}, {}
    period = 0.0
    for epoch, label in ((0, 'A'), (1, 'B')):
        first_batches[label] = [it.cut(it.k + i) for i in range(3)]
        it.plan(windows=1)
        t = time.perf_counter()
        fit(epoch)
        period = fetched(epoch)[0] - it.boundaries[0]
        ctx.log('check window %s: %.1fs, %.2fs of them from its dispatch to '
                'its fetch; losses of its first steps %s, of the second '
                'head %s'
                % (label, time.perf_counter() - t, period,
                   ['%.5f' % v for v in step_losses(epoch)[:3]],
                   ['%.5f' % second_loss(v)
                    for v in step_losses(epoch, True)[:3]]))
        t = time.perf_counter()
        arg_params = aux_params = None      # the module has them now
        if label == 'A':
            # g = -mom_A / (m**(W-1) * lr_A) - wd * w_0
            mom_a = fit_tokens.optimizer_state(mod, param_names, 1)
            scale = np.float32(-1.0 / (float(opt['momentum']) ** (W - 1)
                                       * float(opt['learning_rate'])
                                       * compare_lm_training.A_LR_SCALE))
            wd = np.float32(opt['wd'])
            grad = fit_tokens.per_leaf(
                lambda n: mom_a.pop(n) * scale - wd * start[n] if wd
                else mom_a.pop(n) * scale, param_names)
            w_a = fit_tokens.optimizer_state(mod, param_names, 0)
        else:
            w_b = fit_tokens.optimizer_state(mod, param_names, 0)
            change = fit_tokens.per_leaf(lambda n: w_b.pop(n) - w_a.pop(n),
                                         param_names)
        stats[label] = expert_stats()
        ctx.log('optimizer state read from the updater: %.1fs'
                % (time.perf_counter() - t))

    loop = mod.__dict__.get('_fused_fit_cache')
    fused_window = loop[1].window if loop else 0
    in_graph = bool(loop) and loop[1].stat_fns is not None
    reference.thread.join()     # no compilation beside the timed windows

    # -- the timed windows --------------------------------------------------
    windows = 1 + max(1, math.ceil(ctx.seconds / max(period, 1e-6)))
    capture = Capture(ctx.workdir) if ctx.trace else None
    in_use = []

    def at_boundary(done):
        in_use.append((ctx.devices[0].memory_stats() or {})
                      .get('bytes_in_use', 0))
        if capture is not None and done == windows - CAPTURED + 1:
            # the device is at the start of window `done - 1`; the
            # CAPTURED windows from `done` on begin after this instant
            capture.start()

    counters0 = dict(telemetry.snapshot()['counters']) if ctx.trace else {}
    compiles0 = ctx.compiles.compiles
    it.plan(windows=windows, at_boundary=at_boundary)
    setup_s = time.perf_counter() - ctx.t0
    wall0 = time.time()
    fit(2)
    t_end = time.perf_counter()
    if capture is not None:
        capture.stop()
        ctx.log('capture stopped: %.1fs' % (time.perf_counter() - t_end))
    wall1 = time.time()
    compiled_inside = ctx.compiles.compiles - compiles0
    memory_peak = harness.memory_peak(ctx.devices)

    losses = step_losses(2)
    at = fetched(2)
    periods = len(at) - 1
    elapsed = at[-1] - at[0] if periods > 0 else float('nan')
    tokens_s = periods * W * tokens_step / elapsed
    ctx.log('timed: %d windows of %d steps of %d tokens, done %.2fs after '
            'the first dispatch; %d windows in the %.3fs between the first '
            'fetch and the last: %.1f tokens/s'
            % (windows, W, tokens_step, t_end - it.boundaries[0], periods,
               elapsed, tokens_s))
    window_loss = [float(np.mean(losses[i * W:(i + 1) * W]))
                   for i in range(len(losses) // W)]
    ctx.log('per-window loss %s; of the second head %s'
            % (['%.4f' % v for v in window_loss],
               ['%.4f' % second_loss(float(np.mean(
                   step_losses(2, True)[i * W:(i + 1) * W])))
                for i in range(len(losses) // W)]))
    ctx.log('device GB in use at each window boundary %s'
            % ['%.2f' % (b / 1e9) for b in in_use])

    chk = ctx.checks
    chk.equal('fused window size', fused_window, W)
    chk.true('every metric computed inside the window', in_graph)
    chk.equal('windows drawn', it.epoch_drawn, windows * W)
    chk.equal('steps seen by the callback', len(losses), windows * W)
    chk.true('measured for --seconds', elapsed >= 0.98 * ctx.seconds,
             '%.2fs of %gs' % (elapsed, ctx.seconds))
    chk.true('every window loss finite',
             window_loss and all(np.isfinite(window_loss)))
    chk.true('last window loss below the first',
             window_loss and window_loss[-1] < window_loss[0],
             '%.4f -> %.4f' % (window_loss[0], window_loss[-1])
             if window_loss else '')
    chk.equal('compilations inside the window', compiled_inside, 0)
    stats['timed'] = expert_stats()
    for label, per in stats.items():
        ctx.log('expert layers after window %s: %s' % (label, json.dumps(
            {n: {k: round(v, 3) for k, v in s.items()}
             for n, s in per.items()})))
    chk.equal('pairs dropped by the expert layers (last steps)',
              sum(s['dropped'] for per in stats.values()
                  for s in per.values()), 0)
    chk.true('every token routed by every expert layer',
             all(s['tokens'] == tokens_step for per in stats.values()
                 for s in per.values()))

    run = {'cell': ctx.cell.name, 'config': cfg, 'traffic': tr, 'chips': 1,
           'device_kind': ctx.devices[0].device_kind,
           'samples_s': tokens_s, 'windows': windows,
           'steps_per_window': W, 'batch': batch, 'seq_len': seq_len,
           'memory_peak_bytes': memory_peak,
           'param_shapes': {n: shapes[n] for n in param_names},
           'expert_stats': stats['timed']}
    pairs = None
    if ctx.trace:
        t = time.perf_counter()
        counters1 = dict(telemetry.snapshot()['counters'])
        run['counters'] = {k: v - counters0.get(k, 0)
                           for k, v in counters1.items()}
        run['gauges'] = dict(telemetry.snapshot().get('gauges', {}))
        run['hyper_res_dev_max'] = run['gauges'].get('hyper.res_dev_max')
        telemetry.shutdown()        # writes the buffered log out
        log_path = os.environ['MXTPU_TELEMETRY_PATH']
        ctx.log('set-up spans, seconds: %s' % json.dumps(
            [[s['name'], round(s['dur_ms'] / 1e3, 1)]
             for s in read_spans(log_path, 0, wall0)
             if s['name'].startswith('fit.') or s['dur_ms'] >= 1e3]))
        run['spans'] = read_spans(log_path, wall0, wall1)
        run['trace_steps'] = (CAPTURED - 1) * W

        # host work alone, in a process of its own beside the reference
        t_reduce = time.perf_counter()
        reducing = capture.reduce_beside(CAPTURED)
        events = fit_tokens.read_events(log_path, 'moe.window')
        # the slice is the periods of all captured windows but the last
        run['moe_pairs_traced'] = int(sum(
            np.sum(e['pairs'])
            for e in events[2:][windows - CAPTURED:windows - 1]))
        chk.equal('fused windows counted by the program',
                  run['counters'].get('fused_fit.windows'), windows)
        chk.equal('steps counted by the program',
                  run['counters'].get('fit.steps'), windows * W)
        puts = sum(1 for s in run['spans'] if s['name'] == 'fused_fit.put')
        chk.equal('uploads (fused_fit.put spans)', puts, windows)
        if moe_names:
            chk.equal('pairs dropped (moe.dropped)',
                      run['counters'].get('moe.dropped'), 0)
            chk.equal('tokens routed (moe.tokens)',
                      run['counters'].get('moe.tokens'),
                      windows * W * tokens_step * len(moe_names))
            chk.equal('moe.window events of the check windows',
                      len(events[:2]), 2)
            if len(events) >= 2:
                a, b = events[0]['pairs'], events[1]['pairs']
                pairs = [a[0], b[0], b[1], b[2]]

    # -- free the program, then the plain reference -------------------------
    prog = {'losses': step_losses(0)[:1] + step_losses(1)[:3],
            'grad': grad, 'change': change, 'start': start, 'pairs': pairs,
            'reference': reference,
            'second': None if second_at is None else [
                second_loss(v) for v in
                step_losses(0, True)[:1] + step_losses(1, True)[:3]]}
    del mod, loop, start
    gc.collect()
    t = time.perf_counter()
    check(ctx, cfg, prog, first_batches, W)
    ctx.log('reference and comparison: %.1fs' % (time.perf_counter() - t))
    if ctx.trace:
        run.update(Capture.reduced(reducing))
        run['kernels'] = kernel_seconds(run['trace']['by_name'],
                                        run['trace']['busy_s'])
        ctx.log('capture reduced %.1fs after it was stopped'
                % (time.perf_counter() - t_reduce))
        ctx.log('device seconds by kernel in the traced slice: %s'
                % json.dumps({k: round(v, 4)
                              for k, v in run['kernels'].items()}))
        ctx.log('costliest device operations: %s' % json.dumps(
            [[k, round(v, 3)] for k, v in sorted(
                run['trace']['by_name'].items(), key=lambda kv: -kv[1])[:40]]))

    return {'setup_s': setup_s, 'end_to_end': {'train_samples_s': tokens_s},
            'attempted': windows * W,
            'failed': max(0, windows * W - len(losses)),
            'memory_peak_bytes': memory_peak, 'run': run}
