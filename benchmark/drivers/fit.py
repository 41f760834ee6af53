"""Driver ``fit``: a configuration trained through ``Module.fit``.

One bound ``Module`` is built from the seed and driven through ``fit``
three times, always through the fused window and the window pipeline a
user's ``fit`` takes:

1. check window A (one window): the learning rate is a thousandth of the
   configuration's for the first step (the momentum then coasts for the
   rest of the window, and must not carry the weights away from the seeded
   point) and 0 after it, through the optimizer's own
   ``lr_scheduler`` (the rate enters the compiled window as an array, so
   this is the timed program). From the momentum left after the window the
   first gradient *as the optimizer got it* follows exactly.
2. check window B (one window): three steps at the configuration's rate,
   then 0. Gives each of the three steps' losses and the parameters'
   change after the three.
3. the timed ``fit``: the iterator ends the epoch at a window boundary
   once ``--seconds`` have passed since the first window's dispatch. The
   rate is taken between like instants, the first window's dispatch and the
   last's, over the windows dispatched in between: whole periods of the
   pipeline whether the host or the device paces it. (Counting the last
   window's samples up to ``block_until_ready`` would credit a host-paced
   loop with a window whose preparation lay before the clock started.)

Windows A and B are also the warm-up: they compile and run the window
program, the upload and the metric fetch that the timed ``fit`` uses. After
the timed window the module is freed and the plain reference follows
windows A and B from the same seeded parameters and the same batches
(``benchmark/compare_training.py``).

Traffic file keys: ``batch`` (global), ``steps_per_window`` (the
program's default on the device; the run checks it), ``kvstore``,
``pool_rows``.
"""
import gc
import json
import os
import pickle
import time

import numpy as np

from benchmark import compare_training, data, harness, weights

# windows in the traced slice; the slice is cut to the TRACE_WINDOWS - 1 whole
# periods between their starts
TRACE_WINDOWS = 3


class Schedule:
    """``lr_scheduler`` of the three fits: window A steps once, window B
    three times, the timed windows at every step."""

    def __init__(self, lr, window):
        self.base_lr = lr
        self.lr = lr
        self.window = window

    def __call__(self, num_update):
        w = self.window
        if num_update <= w:
            return self.lr * compare_training.A_LR_SCALE \
                if num_update == 1 else 0.0
        if num_update <= 2 * w:
            return self.lr if num_update - w <= 3 else 0.0
        return self.lr


def make_iter(mx, pool, labels, batch, image_shape, window):
    """A ``DataIter`` over the host pool: every batch a new array cut at a
    rolling offset, as a user's iterator hands over new arrays, so the
    window pipeline stacks and uploads every window."""

    class PoolIter(mx.io.DataIter):
        def __init__(self):
            super().__init__(batch)
            self.provide_data = [mx.io.DataDesc(
                'data', (batch,) + tuple(image_shape), np.float32)]
            self.provide_label = [mx.io.DataDesc(
                'softmax_label', (batch,), np.float32)]
            self.k = 0              # batches drawn since the start
            self.drawn = 0          # batches drawn in this epoch
            self.limit = 0          # windows in this epoch; 0: by the clock
            self.seconds = 0.0
            self.dispatched = []    # when each window had been dispatched
            self.at_boundary = None     # hook(windows drawn) -> stop?

        def plan(self, windows=0, seconds=0.0, at_boundary=None):
            self.limit, self.seconds = windows, seconds
            self.at_boundary = at_boundary
            self.drawn, self.epoch_drawn, self.dispatched = 0, 0, []

        def offsets(self, first, count):
            return [data.batch_offset(first + i, len(pool), batch)
                    for i in range(count)]

        def reset(self):        # fit calls it at every epoch's end
            self.epoch_drawn, self.drawn = self.drawn, 0

        def next(self):
            i = self.drawn
            if i % window == 0 and i:
                done = i // window
                if self.limit and done >= self.limit:
                    raise StopIteration
                if not self.limit:
                    # the loop draws the next window right after it has
                    # dispatched one: this instant is window `done`'s
                    # dispatch
                    self.dispatched.append(time.perf_counter())
                    over = (self.dispatched[-1] - self.dispatched[0]
                            >= self.seconds)
                    if self.at_boundary(done, over):
                        raise StopIteration
            off = data.batch_offset(self.k, len(pool), batch)
            self.k += 1
            self.drawn += 1
            return mx.io.DataBatch(
                data=[mx.nd.array(pool[off:off + batch])],
                label=[mx.nd.array(labels[off:off + batch]
                                   .astype(np.float32))],
                pad=0, index=None, provide_data=self.provide_data,
                provide_label=self.provide_label)

    return PoolIter()


def live_arrays(mod):
    """The device arrays the bound executors train."""
    return [nd._data for block in mod._exec_group.param_arrays
            for nd in block]


def optimizer_state(mod, param_names, workdir):
    """{name: (master float32 weights, momentum)} as numpy, through
    ``save_optimizer_states`` (which also brings sharded state back to its
    canonical shapes)."""
    path = os.path.join(workdir, 'optimizer.states')
    mod.save_optimizer_states(path)
    with open(path, 'rb') as f:
        states = pickle.loads(f.read())     # bytes this process wrote
    os.remove(path)
    out, plain = {}, None
    for key, st in states.items():
        name = key if isinstance(key, str) else param_names[key]
        if isinstance(st, tuple):
            master, mom = st
            out[name] = (master.asnumpy().astype(np.float32),
                         mom.asnumpy().astype(np.float32))
        else:       # a float32 parameter has no master copy
            if plain is None:
                plain = mod.get_params()[0]
            out[name] = (plain[name].asnumpy().astype(np.float32),
                         st.asnumpy().astype(np.float32))
    return out


def read_spans(path, t_from, t_to):
    """The program's span records that started inside [t_from, t_to]."""
    spans = []
    if not os.path.exists(path):
        return spans
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get('type') == 'span' and t_from <= rec['t'] <= t_to:
                spans.append(rec)
    return spans


def run(ctx):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.ndarray.ndarray import from_jax

    cfg, tr = ctx.config, ctx.traffic
    batch, W = int(tr['batch']), int(tr['steps_per_window'])
    image_shape, classes = tuple(cfg['input_shape']), int(cfg['classes'])
    opt = cfg['optimizer']
    chips = ctx.cell.chips
    contexts = mx.tpu(0) if chips == 1 \
        else [mx.tpu(i) for i in range(chips)]

    sym = harness.build_symbol(cfg)
    param_names, aux_names, shapes = harness.symbol_shapes(
        sym, batch, image_shape)
    made = weights.make_params(shapes, ctx.seed, cfg.get('init'))
    # handed over as they lie on the device; a host copy for the reference
    first = mx.tpu(0)
    arg_params = {n: from_jax(made[n], first) for n in param_names}
    aux_params = {n: from_jax(made[n], first) for n in aux_names}
    start = {n: np.asarray(made[n]) for n in param_names}
    del made
    ctx.log('%d parameter and %d auxiliary arrays made from the seed'
            % (len(param_names), len(aux_names)))

    pool, labels = data.image_pool(ctx.seed, int(tr['pool_rows']),
                                   image_shape, classes)
    ctx.log('pool of %d images, %.0f MB' % (len(pool), pool.nbytes / 1e6))
    it = make_iter(mx, pool, labels, batch, image_shape, W)

    steps = []          # (epoch, cumulative ce sum, cumulative rows)

    def note(param):
        ce = param.eval_metric.metrics[0]
        steps.append((param.epoch, float(ce.sum_metric), int(ce.num_inst)))

    def step_losses(epoch):
        out, prev = [], (0.0, 0)
        for e, s, n in steps:
            if e == epoch:
                out.append((s - prev[0]) / max(n - prev[1], 1))
                prev = (s, n)
        return out

    sched = Schedule(float(opt['learning_rate']), W)
    mod = mx.mod.Module(sym, context=contexts)

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
    first_batches = {}
    for epoch, label in ((0, 'A'), (1, 'B')):
        first_batches[label] = it.offsets(it.k, 3)
        it.plan(windows=1)
        t = time.perf_counter()
        fit(epoch)
        ctx.log('check window %s: %.1fs; losses of its first steps %s'
                % (label, time.perf_counter() - t,
                   ['%.5f' % v for v in step_losses(epoch)[:3]]))
        state = optimizer_state(mod, param_names, ctx.workdir)
        if label == 'A':
            state_a = state
        else:
            state_b = state
    del state

    loop = mod.__dict__.get('_fused_fit_cache')
    fused_window = loop[1].window if loop else 0

    # -- the timed window ---------------------------------------------------
    capture = harness.Capture(ctx.workdir) if ctx.trace else None
    mark, in_use = {}, []

    def at_boundary(done, over):
        """Called before each window's first draw but the first; True
        ends the epoch. Traced run: once the time is up, wait for the
        device, start the capture and let TRACE_WINDOWS more windows
        through."""
        # The pipeline's upload resolvers are reference cycles (each sets an
        # attribute on itself) that hold a window's 2.5 GB device stack
        # until Python's cycle collector runs, which with a large heap is
        # rarely: the fifth window exhausted the chip (PERF.md, Findings).
        # Until the program breaks the cycle, the iterator collects.
        gc.collect()
        in_use.append((ctx.devices[0].memory_stats() or {})
                      .get('bytes_in_use', 0))
        if capture is None:
            return over
        if 'capture_at' in mark:
            return done >= mark['capture_at'] + TRACE_WINDOWS
        if over:
            jax.block_until_ready(live_arrays(mod))
            mark['capture_at'] = done
            capture.start()
        return False

    counters0 = dict(telemetry.snapshot()['counters']) if ctx.trace else {}
    compiles0 = ctx.compiles.compiles
    it.plan(seconds=ctx.seconds, at_boundary=at_boundary)
    setup_s = time.perf_counter() - ctx.t0
    wall0 = time.time()
    fit(2)
    t_end = time.perf_counter()
    if capture is not None:
        capture.stop()
    wall1 = time.time()
    compiled_inside = ctx.compiles.compiles - compiles0
    memory_peak = harness.memory_peak(ctx.devices)

    windows = it.epoch_drawn // W
    losses = step_losses(2)
    # the rate: the windows dispatched from the first window's dispatch to
    # the last's, over that time (in a traced run: up to the capture)
    last = mark.get('capture_at', windows)
    periods = last - 1
    elapsed = it.dispatched[last - 1] - it.dispatched[0]
    samples_s = periods * W * batch / elapsed
    ctx.log('timed: %d windows of %d steps, batch %d, done %.2fs after the '
            'first dispatch; %d windows in the %.3fs between the first '
            'dispatch and the last: %.1f samples/s'
            % (windows, W, batch, t_end - it.dispatched[0], periods,
               elapsed, samples_s))
    window_loss = [float(np.mean(losses[i * W:(i + 1) * W]))
                   for i in range(len(losses) // W)]
    ctx.log('per-window loss %s' % ['%.4f' % v for v in window_loss])
    ctx.log('device GB in use at each window boundary %s'
            % ['%.2f' % (b / 1e9) for b in in_use])

    chk = ctx.checks
    chk.equal('fused window size', fused_window, W)
    chk.equal('steps seen by the callback', len(losses), windows * W)
    chk.true('every window loss finite',
             window_loss and all(np.isfinite(window_loss)))
    chk.true('last window loss below the first',
             window_loss and window_loss[-1] < window_loss[0],
             '%.4f -> %.4f' % (window_loss[0], window_loss[-1])
             if window_loss else '')
    chk.equal('compilations inside the window', compiled_inside, 0)

    run = {'cell': ctx.cell.name, 'config': cfg, 'traffic': tr,
           'chips': chips, 'device_kind': ctx.devices[0].device_kind,
           'samples_s': samples_s, 'windows': windows,
           'steps_per_window': W, 'batch': batch,
           'memory_peak_bytes': memory_peak,
           'param_shapes': {n: shapes[n] for n in param_names}}
    if ctx.trace:
        counters1 = dict(telemetry.snapshot()['counters'])
        run['counters'] = {k: v - counters0.get(k, 0)
                           for k, v in counters1.items()}
        telemetry.shutdown()        # writes the buffered log out
        run['spans'] = read_spans(os.environ['MXTPU_TELEMETRY_PATH'],
                                  wall0, wall1)
        run['trace'] = capture.reduce(chips, whole_periods_of=TRACE_WINDOWS)
        run['trace_steps'] = (TRACE_WINDOWS - 1) * W
        chk.equal('fused windows counted by the program',
                  run['counters'].get('fused_fit.windows'), windows)
        chk.equal('steps counted by the program',
                  run['counters'].get('fit.steps'), windows * W)
        puts = sum(1 for s in run['spans'] if s['name'] == 'fused_fit.put')
        chk.equal('uploads (fused_fit.put spans)', puts, windows)

    # -- free the program, then the plain reference -------------------------
    prog = {
        'loss_a': step_losses(0)[:1], 'loss_b': step_losses(1)[:3],
        'state_a': state_a, 'state_b': state_b, 'start': start}
    del mod, loop, arg_params, aux_params
    gc.collect()
    t = time.perf_counter()
    batches = {k: [(pool[o:o + batch], labels[o:o + batch]) for o in offs]
               for k, offs in first_batches.items()}
    compare_training.check(ctx, cfg, prog, batches, W)
    ctx.log('reference and comparison: %.1fs' % (time.perf_counter() - t))

    return {'setup_s': setup_s, 'end_to_end': {'train_samples_s': samples_s},
            'attempted': windows * W,
            'failed': max(0, windows * W - len(losses)),
            'memory_peak_bytes': memory_peak, 'run': run}
