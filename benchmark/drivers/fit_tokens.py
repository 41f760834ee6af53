"""Driver ``fit_tokens``: a decoder configuration trained on token
sequences through ``Module.fit``.

It follows ``drivers/fit.py`` step for step, and takes from it what does
not depend on the kind of sample (the schedule of the three fits, the
reading of spans, the slice of the traced run): one bound ``Module`` is
built from the seed and driven through ``fit`` three times, always through
the fused window and the window pipeline a user's ``fit`` takes:

1. check window A: the first step at a thousandth of the configuration's
   rate, 0 after it (through the optimizer's own ``lr_scheduler``); from the
   momentum left after the window the first gradient follows.
2. check window B: three steps at the configuration's rate, then 0: the
   three losses and the parameters' change.
3. the timed ``fit``: as many windows as ``--seconds`` take and one more,
   by the period check window B had on the device (here one window is 20.6
   s: two windows), and the rate between like instants: from the first
   window's fetch to the last's. The device paces this loop: the first two
   windows are dispatched back to back into an empty pipeline, so the
   second starts on the device the moment the first ends, and the fetches
   lie one device period apart. (``drivers/fit.py`` stops by the clock at a
   dispatch and so runs two windows past the span it measures: 41 s of
   every run here, which the 360 s a run of this cell is given do not
   have.) A sample is a token: ``train_samples_s`` is tokens a second. A
   traced run captures these same windows, from before the first dispatch,
   and reads the whole periods between their starts; its rate is taken
   under the capture.

What differs: a batch is ``(batch, seq_len)`` token ids cut from a seeded
token stream (``benchmark/data_lm.py``) at a rolling offset, as new
float32 arrays built by ``mx.nd.array``, and its label the same cut one
token on; the one output is ``(batch * seq_len, vocabulary)``. Parameters
come from ``benchmark/weights_lm.py`` and reach ``fit`` as host arrays (a
second float32 copy on the device would not fit beside the optimizer's
state) and are dropped once the module has them. After windows A and B the
float32 masters and the momentum are read from the module's updater
(``updater.states``), not through ``save_optimizer_states`` and a pickle
(6.5 GB twice over), every leaf's copy started before the first is waited
for, and reduced to the two readings the comparison takes, the first
gradient and the change: a run then holds a few float32 copies of the
parameters on the host, whose machine has 40 GB. The reference's programs
are compiled meanwhile, in a thread (``compare_lm_training.Prepared``). The
expert
layers' statistics are read from their auxiliary states after each check
window (no pair dropped, every token routed); a traced run also has the
program's ``moe.*`` counters and each step's pairs (``moe.window`` events)
for the comparison with the reference's counts
(``benchmark/compare_lm_training.py``).

Traffic file keys: ``batch``, ``seq_len``, ``steps_per_window`` (the
program's default on the device; the run checks it), ``kvstore``,
``pool_tokens``.
"""
import gc
import json
import math
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import compare_lm_training, data_lm, harness, weights_lm
from benchmark.drivers.fit import Schedule, live_arrays, read_spans
from benchmark.reference import laguna


def build_symbol(cfg):
    """The configuration's network: its builder gets the configuration
    itself (the published keys) beside the builder's own arguments."""
    spec = dict(cfg['builder'])
    spec['kwargs'] = dict(spec.get('kwargs', {}), config=cfg)
    return harness.build_symbol({'builder': spec})


def symbol_shapes(sym, batch, seq_len):
    """(parameter names, auxiliary names, {name: shape} of both)."""
    args, _, auxs = sym.infer_shape(data=(batch, seq_len),
                                    softmax_label=(batch, seq_len))
    shapes = {n: s for n, s in zip(sym.list_arguments(), args)
              if n not in ('data', 'softmax_label')}
    params = list(shapes)
    aux = sym.list_auxiliary_states()
    shapes.update(zip(aux, auxs))
    return params, aux, shapes


def make_iter(mx, pool, batch, seq_len, window):
    """A ``DataIter`` over the token stream: every batch a new array cut at
    a rolling offset, so that the window pipeline stacks and uploads every
    window."""
    shape = (batch, seq_len)

    class TokenIter(mx.io.DataIter):
        def __init__(self):
            super().__init__(batch)
            self.provide_data = [mx.io.DataDesc('data', shape, np.float32)]
            self.provide_label = [mx.io.DataDesc('softmax_label', shape,
                                                 np.float32)]
            self.k = 0              # batches drawn since the start
            self.drawn = 0          # batches drawn in this epoch
            self.limit = 0          # windows in this epoch
            self.boundaries = []    # when each window had been dispatched
            self.at_boundary = None     # hook(windows drawn)

        def plan(self, windows, at_boundary=None):
            self.limit, self.at_boundary = windows, at_boundary
            self.drawn, self.epoch_drawn, self.boundaries = 0, 0, []

        def cut(self, k):
            """(ids, next ids) of batch number k, float32 (batch, seq)."""
            ids, nxt = data_lm.cut(pool, k, batch, seq_len)
            return ids.astype(np.float32), nxt.astype(np.float32)

        def reset(self):        # fit calls it at every epoch's end
            self.epoch_drawn, self.drawn = self.drawn, 0

        def next(self):
            i = self.drawn
            if i % window == 0 and i:
                # the loop draws the next window right after it has
                # dispatched one: this instant is window `done`'s dispatch
                done = i // window
                self.boundaries.append(time.perf_counter())
                if self.at_boundary is not None:
                    self.at_boundary(done)
                if done >= self.limit:
                    raise StopIteration
            ids, nxt = self.cut(self.k)
            self.k += 1
            self.drawn += 1
            return mx.io.DataBatch(
                data=[mx.nd.array(ids)], label=[mx.nd.array(nxt)], pad=0,
                index=None, provide_data=self.provide_data,
                provide_label=self.provide_label)

    return TokenIter()


def optimizer_state(mod, param_names, which):
    """{name: float32 numpy} of the masters (`which` 0) or the momentum
    (1), read from the updater that holds the module's optimizer state:
    one ``device_get`` of every leaf."""
    from mxnet_tpu.module.fused_fit import updater_keys, updater_obj
    states = updater_obj(mod).states
    keys = updater_keys(mod, param_names)
    return compare_lm_training.fetch(
        {n: states[keys[n]][which]._data for n in param_names})


def per_leaf(fn, names):
    """{name: fn(name)} on a few threads (numpy leaves the lock alone)."""
    with ThreadPoolExecutor(compare_lm_training.THREADS) as pool:
        return dict(zip(names, pool.map(fn, names)))


def expert_stats(mod, aux_names):
    """{auxiliary state: {statistic: value}} as the expert layers left them
    after the last step."""
    from mxnet_tpu.ops.transformer import MOE_STATS
    aux = mod._exec_group.execs[0].aux_dict
    return {n: dict(zip(MOE_STATS, aux[n].asnumpy().tolist()))
            for n in aux_names}


def read_events(path, name):
    out = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get('type') == 'event' and rec.get('name') == name:
                    out.append(rec)
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
    # first the program: its import decides where the compile cache lies,
    # and a program without the builder fails here, at once
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    sym = build_symbol(cfg)
    param_names, aux_names, shapes = symbol_shapes(sym, batch, seq_len)
    if {n: tuple(shapes[n]) for n in param_names} \
            != {n: tuple(v) for n, v in laguna.param_shapes(cfg).items()}:
        raise ValueError('the builder\'s parameters are not the '
                         'reference\'s: %s' % sorted(
                             set(param_names)
                             ^ set(laguna.param_shapes(cfg))))
    # The reference's programs compile beside the set-up, on the host
    # alone, and start at once: the chip's compiler takes every core it
    # finds, so what of it is still running when the window's compilation
    # begins makes both slower.
    prepared = compare_lm_training.Prepared(
        cfg, {n: shapes[n] for n in param_names}, (batch, seq_len), opt,
        log=ctx.log)

    t = time.perf_counter()
    made = weights_lm.make_params(shapes, ctx.seed)
    start = {n: made[n] for n in param_names}
    aux_start = {n: made[n] for n in aux_names}
    del made
    t1 = time.perf_counter()
    # host arrays: fit copies them into the bound (bfloat16) arrays
    arg_params = per_leaf(lambda n: mx.nd.array(start[n]), param_names)
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
    it = make_iter(mx, pool, batch, seq_len, W)

    steps = []      # (epoch, cumulative ce sum, cumulative tokens, when)

    def note(param):
        ce = param.eval_metric.metrics[0]
        steps.append((param.epoch, float(ce.sum_metric), int(ce.num_inst),
                      time.perf_counter()))

    def step_losses(epoch):
        out, prev = [], (0.0, 0)
        for e, s, n, _ in steps:
            if e == epoch:
                out.append((s - prev[0]) / max(n - prev[1], 1))
                prev = (s, n)
        return out

    def fetched(epoch):
        """When each window of `epoch` had been fetched: its first step's
        callback (they run in a row after the window's one fetch)."""
        return [t for e, _, _, t in steps if e == epoch][::W]

    sched = Schedule(float(opt['learning_rate']), W)
    mod = mx.mod.Module(sym, context=mx.tpu(0))

    def fit(epoch):
        mod.fit(it, eval_metric=['ce', 'acc'], kvstore=tr['kvstore'],
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

    # -- set-up: the two check windows, which are also the warm-up --------
    first_batches, stats = {}, {}
    period = 0.0
    for epoch, label in ((0, 'A'), (1, 'B')):
        first_batches[label] = [it.cut(it.k + i) for i in range(3)]
        it.plan(windows=1)
        t = time.perf_counter()
        fit(epoch)
        # from the window's dispatch to its fetch: its time on the device
        period = fetched(epoch)[0] - it.boundaries[0]
        ctx.log('check window %s: %.1fs, %.2fs of them from its dispatch to '
                'its fetch; losses of its first steps %s'
                % (label, time.perf_counter() - t, period,
                   ['%.5f' % v for v in step_losses(epoch)[:3]]))
        t = time.perf_counter()
        arg_params = aux_params = None      # the module has them now
        # the readings of compare_training.program_readings, leaf by leaf:
        # 811 M parameters are 3.2 GB an array on the host
        if label == 'A':
            # g = -mom_A / (m**(W-1) * lr_A) - wd * w_0
            mom_a = optimizer_state(mod, param_names, 1)
            scale = np.float32(-1.0 / (float(opt['momentum']) ** (W - 1)
                                       * float(opt['learning_rate'])
                                       * compare_lm_training.A_LR_SCALE))
            wd = np.float32(opt['wd'])
            grad = per_leaf(
                lambda n: mom_a.pop(n) * scale - wd * start[n] if wd
                else mom_a.pop(n) * scale, param_names)
            w_a = optimizer_state(mod, param_names, 0)
        else:
            w_b = optimizer_state(mod, param_names, 0)
            change = per_leaf(lambda n: w_b.pop(n) - w_a.pop(n), param_names)
        stats[label] = expert_stats(mod, aux_names)
        ctx.log('optimizer state read from the updater: %.1fs'
                % (time.perf_counter() - t))

    loop = mod.__dict__.get('_fused_fit_cache')
    fused_window = loop[1].window if loop else 0
    prepared.thread.join()      # no compilation beside the timed windows

    # -- the timed windows --------------------------------------------------
    # as many as --seconds take, and the one whose fetch starts the clock
    windows = 1 + max(1, math.ceil(ctx.seconds / max(period, 1e-6)))
    capture = harness.Capture(ctx.workdir) if ctx.trace else None
    in_use = []

    def at_boundary(done):
        in_use.append((ctx.devices[0].memory_stats() or {})
                      .get('bytes_in_use', 0))

    counters0 = dict(telemetry.snapshot()['counters']) if ctx.trace else {}
    compiles0 = ctx.compiles.compiles
    it.plan(windows=windows, at_boundary=at_boundary)
    setup_s = time.perf_counter() - ctx.t0
    if capture is not None:
        capture.start()
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
    # the rate: between like instants of a full pipeline. The loop keeps
    # one window running and one queued: the first two are dispatched back
    # to back and every later one when a window has been fetched, so each
    # window starts on the device the moment the one before it ends, and
    # from the first fetch to the last the device ran one window per fetch.
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
    ctx.log('per-window loss %s' % ['%.4f' % v for v in window_loss])
    ctx.log('device GB in use at each window boundary %s'
            % ['%.2f' % (b / 1e9) for b in in_use])

    chk = ctx.checks
    chk.equal('fused window size', fused_window, W)
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
    stats['timed'] = expert_stats(mod, aux_names)
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
        from benchmark.reduce import kernel_times
        t = time.perf_counter()
        counters1 = dict(telemetry.snapshot()['counters'])
        run['counters'] = {k: v - counters0.get(k, 0)
                           for k, v in counters1.items()}
        run['gauges'] = dict(telemetry.snapshot().get('gauges', {}))
        telemetry.shutdown()        # writes the buffered log out
        log_path = os.environ['MXTPU_TELEMETRY_PATH']
        ctx.log('set-up spans, seconds: %s' % json.dumps(
            [[s['name'], round(s['dur_ms'] / 1e3, 1)]
             for s in read_spans(log_path, 0, wall0)
             if s['name'].startswith('fit.') or s['dur_ms'] >= 1e3]))
        run['spans'] = read_spans(log_path, wall0, wall1)
        run['trace_steps'] = (windows - 1) * W

        def reduce_capture():
            # the capture holds the timed windows whole: the slice is the
            # windows - 1 whole periods between their starts
            t = time.perf_counter()
            run['trace'] = capture.reduce(1, whole_periods_of=windows)
            run['kernels'] = kernel_times.reduce_capture(
                capture.dir, whole_periods_of=windows)
            ctx.log('capture reduced in %.1fs, beside the reference'
                    % (time.perf_counter() - t))

        # host work alone: it runs while the reference has the device
        reducing = threading.Thread(target=reduce_capture,
                                    name='capture-reduce')
        reducing.start()
        events = read_events(log_path, 'moe.window')
        run['moe_pairs_traced'] = int(sum(
            np.sum(e['pairs']) for e in events[2:][:windows - 1]))
        chk.equal('fused windows counted by the program',
                  run['counters'].get('fused_fit.windows'), windows)
        chk.equal('steps counted by the program',
                  run['counters'].get('fit.steps'), windows * W)
        puts = sum(1 for s in run['spans'] if s['name'] == 'fused_fit.put')
        chk.equal('uploads (fused_fit.put spans)', puts, windows)
        if aux_names:
            chk.equal('pairs dropped (moe.dropped)',
                      run['counters'].get('moe.dropped'), 0)
            chk.equal('tokens routed (moe.tokens)',
                      run['counters'].get('moe.tokens'),
                      windows * W * tokens_step * len(aux_names))
            chk.equal('moe.window events of the check windows',
                      len(events[:2]), 2)
            if len(events) >= 2:
                a, b = events[0]['pairs'], events[1]['pairs']
                pairs = [a[0], b[0], b[1], b[2]]

    # -- free the program, then the plain reference -------------------------
    prog = {'losses': step_losses(0)[:1] + step_losses(1)[:3],
            'grad': grad, 'change': change, 'start': start, 'pairs': pairs,
            'prepared': prepared}
    del mod, loop, start
    gc.collect()
    t = time.perf_counter()
    compare_lm_training.check(ctx, cfg, prog, first_batches, W)
    ctx.log('reference and comparison: %.1fs' % (time.perf_counter() - t))
    if ctx.trace:
        reducing.join()
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
