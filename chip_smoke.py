#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                one TPU chip, one process
    python chip_smoke.py --four-chips   one host with four (run by hand)

With no argument it drives the main path once, through the entry points a
user would call, at the full width of ResNet-50 (3x224x224, 1000 classes,
batch 32, bf16 compute with fp32 master weights):

1. ``mx.mod.Module(sym, context=mx.tpu(0)).fit(...)`` on synthetic data for
   three fused windows (96 steps at the TPU default of 32 steps a call);
2. ``save_checkpoint`` -> ``ServingEngine.from_checkpoint(..., mx.tpu(0))``
   -> ``warmup`` -> ``DynamicBatcher`` -> ``serving.http.start_server`` and
   HTTP requests of 1, 3 and 32 rows, compared with ``Module.predict``;
3. the five Pallas kernels that ops/nn.py, ops/transformer.py and
   parallel/ring_attention.py route to on a TPU,
   compiled (not interpreted), against their jnp oracles;
4. one program compiled twice, to show the persistent compile cache.

With ``--four-chips`` it runs ONLY data-parallel ``Module.fit`` over
``[mx.tpu(i) for i in range(4)]`` with ``kvstore='device'`` at global batch
128 and the same seed and batch on ``mx.tpu(0)`` alone, both in float32, and
compares the losses: the first four steps tightly, then two whole windows.

It fails at once, non-zero, unless ``jax.devices()[0].platform == 'tpu'``
(with the option: unless there are four such devices); any failed phase or
assertion exits non-zero. Progress, timings and compile seconds go on
earlier lines; the last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
The numbers it prints are existence proofs, not benchmark results.

JAX is imported in this process, the one that uses the chip, and no child
that needs the chip is started.
"""
import argparse
import gc
import io
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# The four-chip fit against the one-chip fit of the same seed and global
# batch: same math, another reduction order. Both arms run in float32 with
# full-precision matmuls, so that rounding starts at 1e-7 and not at bf16's
# 4e-3. Training then amplifies whatever difference there is: over two
# 32-step windows the float32 arms still ended 0.25% and 1.2% apart (bf16:
# 0.09% and 3.7%; my chip runs, PR 23), as far as two bf16 runs on ONE chip
# that differ only in BatchNorm's one-pass or two-pass statistics (0.15%,
# 1.4%). A whole window's loss therefore cannot tell a fault from rounding
# in any precision, and is held to 5%, which catches a gross fault (a wrong
# learning-rate scale, a shard left out). The tight comparison is the first
# FOUR_CHIP_EARLY_STEPS steps, before amplification: set before its first
# run, expecting under 1e-4 there (1e-6 of reduction-order noise, times e per
# step at the worst), where BatchNorm statistics taken per shard or a
# gradient summed and not averaged move the loss by 1e-2 or more. Measured
# then: 4.7e-4, which is how far one-pass BatchNorm statistics on ONE chip sit
# from the two-pass form; four chips agree with that form to 4e-6 (CHANGES.md).
FOUR_CHIP_LR = 0.02
FOUR_CHIP_LOSS_RTOL = 0.05
FOUR_CHIP_EARLY_STEPS = 4
FOUR_CHIP_EARLY_RTOL = 1e-3
# served logits against Module.predict on the same rows (both bf16 on the chip)
SERVE_ATOL = 2e-2


_T0 = time.perf_counter()


def log(msg):
    print('[chip_smoke %7.1fs] %s' % (time.perf_counter() - _T0, msg),
          flush=True)


def counters():
    from mxnet_tpu import telemetry
    return dict(telemetry.snapshot()['counters'])


def delta(before, name):
    return counters().get(name, 0) - before.get(name, 0)


def platforms_of(arrays):
    return sorted({d.platform for a in arrays for d in a.devices()})


def live_params(mod):
    """The jax arrays the bound executors train (not host copies)."""
    group = mod._exec_group
    return [nd._data for block in group.param_arrays + group.aux_arrays
            for nd in block]


# ---------------------------------------------------------------------------
# A.1 / C: Module.fit
# ---------------------------------------------------------------------------

def synthetic_iter(mx, np, batch, image_shape, num_classes, steps, seed):
    """`steps` batches of learnable synthetic images: ten of the classes,
    each a fixed random pattern under unit noise, so the loss has
    somewhere to go within a hundred steps."""
    rng = np.random.RandomState(seed)
    used = min(10, num_classes)
    n = batch * steps
    patterns = rng.standard_normal((used,) + image_shape).astype('float32')
    label = rng.randint(0, used, n)
    noise = rng.standard_normal((min(n, 256),) + image_shape) \
        .astype('float32')
    data = patterns[label] + noise[np.arange(n) % len(noise)]
    return mx.io.NDArrayIter(data, label.astype('float32'),
                             batch_size=batch, shuffle=False,
                             label_name='softmax_label')


def phase_fit(mx, sym, contexts, image_shape, num_classes, batch, windows,
              seed, platform, kvstore='local', steps_per_window=None,
              lr=0.1):
    """Module.fit for `windows` fused windows, one epoch each, so that each
    epoch's cross-entropy is one window's mean loss. Returns (module,
    per-window losses, steps per window)."""
    import numpy as np
    from mxnet_tpu.module.window_pipeline import window_size

    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(sym, context=contexts)
    if steps_per_window:     # the user's knob; unset: 32 on a TPU, 4 on CPU
        os.environ['MXTPU_FIT_STEPS_PER_CALL'] = str(steps_per_window)
    W = window_size(mod)
    train = synthetic_iter(mx, np, batch, image_shape, num_classes, W, seed)
    log('fit: %d windows of %d steps, batch %d, lr %g, contexts %s, '
        'kvstore %r' % (windows, W, batch, lr, contexts, kvstore))

    per_epoch = {}

    def note(param):
        per_epoch[param.epoch] = dict(param.eval_metric.get_name_value())

    before = counters()
    t = time.perf_counter()
    mod.fit(train, eval_metric=['ce', 'acc'], kvstore=kvstore,
            optimizer='sgd',
            optimizer_params={'learning_rate': lr, 'momentum': 0.9,
                              'wd': 1e-4, 'multi_precision': True},
            initializer=mx.init.Xavier(rnd_type='gaussian',
                                       factor_type='in', magnitude=2),
            batch_end_callback=note, num_epoch=windows)
    os.environ.pop('MXTPU_FIT_STEPS_PER_CALL', None)
    params = live_params(mod)
    for a in params:
        a.block_until_ready()
    dt = time.perf_counter() - t

    losses = [per_epoch[e]['cross-entropy'] for e in range(windows)]
    log('fit: %.1fs wall (compile %.1fs in %d compiles, %d cache hits); '
        'per-window loss %s, accuracy %s'
        % (dt, delta(before, 'xla.compile_secs'),
           delta(before, 'xla.compiles'), delta(before, 'xla.cache_hits'),
           ['%.4f' % v for v in losses],
           ['%.3f' % per_epoch[e]['accuracy'] for e in range(windows)]))

    # the fused window was taken, not the per-batch loop
    got_windows = delta(before, 'fused_fit.windows')
    assert got_windows == windows, \
        'fused windows run: %d, expected %d (per-batch fallback?)' \
        % (got_windows, windows)
    assert delta(before, 'fit.steps') == windows * W
    # everything that was trained lives where it was asked to
    where = platforms_of(params)
    log('fit: %d parameter and state arrays live on %s (%s)'
        % (len(params), where, sorted({str(d) for a in params
                                       for d in a.devices()})))
    assert where == [platform], where
    assert all(np.isfinite(v) for v in losses), losses
    return mod, losses, W


def assert_learned(losses):
    assert losses[-1] < losses[0], 'loss did not go down: %s' % losses


# ---------------------------------------------------------------------------
# A.2: checkpoint -> ServingEngine -> HTTP
# ---------------------------------------------------------------------------

def _post_npy(port, x):
    import numpy as np
    buf = io.BytesIO()
    np.save(buf, x)
    req = urllib.request.Request(
        'http://127.0.0.1:%d/predict' % port, data=buf.getvalue(),
        headers={'Content-Type': 'application/x-npy'})
    with urllib.request.urlopen(req, timeout=300) as r:
        assert r.status == 200, r.status
        payload = json.loads(r.read().decode('utf-8'))
    return np.asarray(payload['outputs'][0], np.float32)


def phase_serve(mx, mod, ctx, workdir, image_shape, max_batch, row_counts,
                seed, platform):
    """save_checkpoint -> from_checkpoint -> warmup -> batcher -> HTTP;
    answers must equal Module.predict on the same rows on the same device."""
    import numpy as np
    from mxnet_tpu.serving import ServingEngine, DynamicBatcher
    from mxnet_tpu.serving.http import start_server

    prefix = os.path.join(workdir, 'smoke')
    mod.save_checkpoint(prefix, 1)
    before = counters()
    t = time.perf_counter()
    engine = ServingEngine.from_checkpoint(
        prefix, 1, [('data', image_shape)], context=ctx,
        max_batch=max_batch)
    warmed = engine.warmup()
    log('serve: %d bucket programs %s warm in %.1fs (compile %.1fs, %d '
        'cache hits)' % (warmed, engine.buckets, time.perf_counter() - t,
                         delta(before, 'xla.compile_secs'),
                         delta(before, 'xla.cache_hits')))
    served_params = [nd._data for block in
                     engine.module._exec_group.param_arrays for nd in block]
    where = platforms_of(served_params)
    log('serve: engine parameters live on %s' % where)
    assert where == [platform], where

    # the reference: Module.predict over the same checkpoint on `ctx`
    ref_mod = mx.mod.Module.load(prefix, 1, data_names=['data'],
                                 label_names=[], context=ctx)
    ref_mod.bind(data_shapes=[('data', (max_batch,) + image_shape)],
                 for_training=False)

    def reference(x):
        pad = np.zeros((max_batch - len(x),) + image_shape, np.float32)
        it = mx.io.NDArrayIter(np.concatenate([x, pad]), None,
                               batch_size=max_batch)
        return ref_mod.predict(it).asnumpy()[:len(x)]

    rng = np.random.RandomState(seed + 1)
    requests = [rng.standard_normal((n,) + image_shape).astype(np.float32)
                for n in row_counts]

    # a bucket program's output is a device array: see where it ran
    pieces, _, bucket = engine.dispatch_rows([requests[0]])[0]
    ran_on = platforms_of(pieces)
    log('serve: bucket %d program ran on %s' % (bucket, ran_on))
    assert ran_on == [platform], ran_on

    compiles0 = counters().get('xla.compiles', 0)
    server = start_server(engine, DynamicBatcher(engine, max_wait_ms=5),
                          port=0)
    answers = [None] * len(requests)
    errors = []

    def client():
        try:
            for i, x in enumerate(requests):
                answers[i] = _post_npy(server.port, x)
        except Exception as e:  # noqa: BLE001 — reported by the main thread
            errors.append(e)

    try:
        t = time.perf_counter()
        th = threading.Thread(target=client)
        th.start()
        th.join(timeout=600)
        assert not th.is_alive(), 'HTTP client timed out'
        assert not errors, errors
        log('serve: %d HTTP requests of %s rows answered in %.2fs on port %d'
            % (len(requests), list(row_counts), time.perf_counter() - t,
               server.port))
        log('serve: dispatches (rows, bucket, requests): %s'
            % list(server.batcher.dispatch_log))
    finally:
        server.stop()
    assert counters().get('xla.compiles', 0) == compiles0, \
        'serving compiled after warmup'

    for x, got in zip(requests, answers):
        want = reference(x)
        assert got.shape == want.shape == (len(x), want.shape[1])
        assert np.isfinite(got).all()
        err = float(np.abs(got - want).max())
        log('serve: %2d rows: max |served - Module.predict| = %.3g'
            % (len(x), err))
        assert err <= SERVE_ATOL, err
        np.testing.assert_allclose(got.sum(axis=1), 1.0, atol=1e-2)
    return engine


# ---------------------------------------------------------------------------
# A.3: the Pallas kernels, compiled
# ---------------------------------------------------------------------------

def kernel_cases(full):
    """(name, kernel, oracle, [(shape, dtype, kind)], tolerance), the
    tolerance relative and absolute at once: BF for kernels that answer
    in bfloat16 (four units in the last place), F32 for float32. Full
    widths are those of tests/unittest/test_tpu_compile.py."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk

    def xent_ref(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, True)

    def flash_ref(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        return pk._flash_ref(q, k, v, True, q.shape[-1] ** -0.5)

    bf, f32 = jnp.bfloat16, jnp.float32
    BF, F32 = 2.0 ** -6, 1e-4
    s = (lambda a, b: a) if full else (lambda a, b: b)
    return [
        ('flash_fwd', flash, flash_ref,
         [(s((8, 1024, 8, 128), (2, 64, 2, 16)), bf, 'x')] * 3, BF),
        ('flash_fwd_long', flash, flash_ref,
         [(s((1, 8192, 8, 128), (1, 128, 2, 16)), bf, 'x')] * 3, BF),
        ('fused_layernorm', pk.fused_layernorm,
         lambda x, g, b: pk._ln_ref(x, g, b, 1e-5),
         [(s((8192, 1024), (64, 128)), bf, 'x'),
          (s((1024,), (128,)), f32, 'x'), (s((1024,), (128,)), f32, 'x')],
         BF),
        ('fused_rmsnorm', pk.fused_rmsnorm,
         lambda x, g: pk._rms_ref(x, g, 1e-6),
         [(s((8192, 4096), (64, 128)), bf, 'x'),
          (s((4096,), (128,)), f32, 'x')], BF),
        ('fused_softmax', pk.fused_softmax,
         lambda x: jax.nn.softmax(x.astype(f32), -1).astype(x.dtype),
         [(s((8192, 1024), (64, 128)), bf, 'x')], BF),
        ('fused_softmax_1000', pk.fused_softmax,
         lambda x: jax.nn.softmax(x, -1),
         [(s((32, 1000), (8, 100)), f32, 'x')], F32),
        ('softmax_xent_1000', pk.softmax_xent, xent_ref,
         [(s((32, 1000), (8, 100)), f32, 'x'),
          (s((32,), (8,)), jnp.int32, s(1000, 100))], F32),
        ('softmax_xent_decoder', pk.softmax_xent, xent_ref,
         [(s((8192, 16384), (64, 256)), bf, 'x'),
          (s((8192,), (64,)), jnp.int32, s(16384, 256))], F32),
        ('softmax_xent_32000', pk.softmax_xent, xent_ref,
         [(s((8192, 32000), (64, 320)), f32, 'x'),
          (s((8192,), (64,)), jnp.int32, s(32000, 320))], F32),
    ]


def phase_kernels(device, full, compiled):
    """Each kernel on `device` against its jnp oracle; with `compiled`, the
    program must hold the Mosaic kernel (tpu_custom_call), not the
    interpreter."""
    import jax
    import numpy as np

    rng = np.random.RandomState(0)
    for name, fn, ref, specs, tol in kernel_cases(full):
        args = []
        for shape, dtype, kind in specs:
            host = (rng.standard_normal(shape) if kind == 'x'
                    else rng.randint(0, kind, shape))
            args.append(jax.device_put(np.asarray(host).astype(dtype),
                                       device))
        t = time.perf_counter()
        exe = jax.jit(fn).lower(*args).compile()
        t_compile = time.perf_counter() - t
        if compiled:
            assert 'tpu_custom_call' in exe.as_text(), \
                '%s: no Mosaic kernel in the compiled program' % name
        got = exe(*args)
        with jax.default_matmul_precision('highest'):
            want = jax.jit(ref)(*args)
        assert platforms_of([got]) == [device.platform]
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert got.shape == want.shape and np.isfinite(got).all(), name
        excess = float((np.abs(got - want) - tol * np.abs(want)).max())
        log('kernel %-22s %-28s compile %.2fs, max |kernel - oracle| = %.3g'
            ' (|oracle| up to %.3g, tolerance %.3g relative + absolute)'
            % (name, 'x'.join(map(str, specs[0][0])) + ' '
               + np.dtype(specs[0][1]).name, t_compile,
               float(np.abs(got - want).max()), float(np.abs(want).max()),
               tol))
        assert excess <= tol, (name, excess, tol)
        del args, got, want, exe


# ---------------------------------------------------------------------------
# F: the persistent compile cache
# ---------------------------------------------------------------------------

def phase_cache(device):
    """Compile one program, then the same program again from a new function
    object: the second compile is served from the persistent cache."""
    import jax
    import jax.numpy as jnp

    # placed by config.enable_compile_cache when mxnet_tpu was imported
    cache_dir = jax.config.jax_compilation_cache_dir
    log('compile cache: directory in use %s (JAX_COMPILATION_CACHE_DIR %s)'
        % (cache_dir, os.environ.get('JAX_COMPILATION_CACHE_DIR', 'unset')))
    x = jax.device_put(jnp.ones((256, 256), jnp.float32), device)

    def make():
        return jax.jit(lambda a: jnp.tanh(a @ a + 23.0).sum())

    before = counters()
    float(make()(x))
    first = delta(before, 'xla.cache_hits')
    float(make()(x))
    second = delta(before, 'xla.cache_hits')
    total = counters().get('xla.cache_hits', 0)
    log('compile cache: hits after compiling one program once %d, twice %d;'
        ' %d hits and %d compiles (%.1fs) in this whole run'
        % (first, second, total, counters().get('xla.compiles', 0),
           counters().get('xla.compile_secs', 0.0)))
    if cache_dir:
        assert second > first, 'the second compile missed the cache'
    return cache_dir


# ---------------------------------------------------------------------------
# C: four chips
# ---------------------------------------------------------------------------

def window_programs(mod):
    """The compiled fused-fit window executables of `mod` (telemetry's
    registrar keeps one per argument signature)."""
    _, loop = mod.__dict__['_fused_fit_cache']
    return [exe for prog in loop._programs.values()
            for exe in prog._compiled.values() if exe]


def phase_spmd_facts(mod, n_dev, batch, image_shape, W):
    """The data-parallel fit really spanned `n_dev` devices."""
    import jax
    from mxnet_tpu.module.executor_group import SPMDExecutorGroup

    group = mod._exec_group
    assert isinstance(group, SPMDExecutorGroup), type(group).__name__
    mesh_devs = list(group.mesh.devices.flat)
    assert len(set(mesh_devs)) == n_dev, mesh_devs
    log('four chips: SPMDExecutorGroup over mesh %s' % mesh_devs)

    p = group.param_arrays[0][0]._data
    log('four chips: parameter %s sharding %s spans %d devices'
        % (group.param_names[0], p.sharding, len(p.sharding.device_set)))
    assert len(p.sharding.device_set) == n_dev

    # the window's data stack (W, batch, C, H, W): what the compiled
    # program was given, and the per-device shape that makes of it
    stack = (W, batch) + tuple(image_shape)
    exes = window_programs(mod)
    assert exes, 'no compiled fused window found'
    for exe in exes:
        shardings = jax.tree_util.tree_leaves(exe.input_shardings)
        local = {tuple(s.shard_shape(stack)) for s in shardings
                 if len(s.device_set) == n_dev
                 and getattr(s, 'spec', None) is not None
                 and len(s.spec) >= 2 and s.spec[1] == 'dp'}
        log('four chips: window input %s is sharded over %d devices as %s'
            % (stack, n_dev, sorted(local)))
        assert local == {(W, batch // n_dev) + tuple(image_shape)}, local
    return group


def collectives_of(mod):
    """Collective ops in the compiled fused window (compiled.as_text())."""
    import re
    found = {}
    for exe in window_programs(mod):
        text = exe.as_text()
        for op in ('all-reduce', 'all-gather', 'reduce-scatter',
                   'collective-permute', 'all-to-all'):
            n = len(re.findall(r'= [^=\n]*\b%s(?:-start)?\(' % op, text))
            if n:
                found[op] = found.get(op, 0) + n
    return found


def full_precision_ops(mod):
    return sum(exe.as_text().count('operand_precision={highest,highest}')
               for exe in window_programs(mod))


def four_chip_arms(mx, sym, image_shape, num_classes, global_batch, n_dev,
                   windows, seed, platform, steps_per_window):
    """The same fit over `n_dev` chips and on chip 0 alone. Returns the two
    lists of per-window losses and the steps per window."""
    fit = dict(kvstore='device', lr=FOUR_CHIP_LR)
    many, many_losses, W = phase_fit(
        mx, sym, [mx.tpu(i) for i in range(n_dev)], image_shape,
        num_classes, global_batch, windows, seed, platform,
        steps_per_window=steps_per_window, **fit)
    phase_spmd_facts(many, n_dev, global_batch, image_shape, W)
    found = collectives_of(many)
    log('four chips: collectives in the compiled window: %s' % found)
    assert found, 'no collective in the compiled data-parallel window'
    full = full_precision_ops(many)
    del many
    gc.collect()
    one, one_losses, _ = phase_fit(
        mx, sym, mx.tpu(0), image_shape, num_classes, global_batch,
        windows, seed, platform, steps_per_window=W, **fit)
    full = (full, full_precision_ops(one))
    log('four chips: matmuls and convolutions at full float32 precision '
        'in the compiled windows: %d on %d chips, %d on one'
        % (full[0], n_dev, full[1]))
    assert min(full) > 0, full
    return many_losses, one_losses, W


def phase_four_chips(mx, sym, image_shape, num_classes, global_batch, n_dev,
                     windows, seed, platform):
    import jax
    args = (mx, sym, image_shape, num_classes, global_batch, n_dev)
    # a float32 matmul takes one bf16 pass through the MXU unless asked
    with jax.default_matmul_precision('highest'):
        for n, W, rtol in ((1, FOUR_CHIP_EARLY_STEPS, FOUR_CHIP_EARLY_RTOL),
                           (windows, None, FOUR_CHIP_LOSS_RTOL)):
            many_losses, one_losses, W = four_chip_arms(
                *args, n, seed, platform, W)
            for w, (a, b) in enumerate(zip(many_losses, one_losses)):
                rel = abs(a - b) / max(abs(b), 1e-6)
                log('four chips: steps %d-%d loss %.7f on %d chips, %.7f on '
                    'one (relative difference %.3g, allowed %.3g)'
                    % (w * W, (w + 1) * W - 1, a, n_dev, b, rel, rtol))
                assert rel <= rtol, (w, a, b)
    assert_learned(many_losses)
    assert_learned(one_losses)


# ---------------------------------------------------------------------------

def resnet50(dtype='float16'):
    sys.path.insert(0, os.path.join(HERE, 'examples', 'image-classification'))
    from symbols.resnet import get_symbol
    return get_symbol(num_classes=1000, num_layers=50,
                      image_shape='3,224,224', dtype=dtype)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the four-chip data-parallel fit and the '
                         'one-chip fit it is compared with')
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != 'tpu' or len(devices) < need:
        print('chip_smoke: needs %d TPU device(s); jax.devices() = %s'
              % (need, devices), file=sys.stderr)
        return 2
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    log('devices: %s' % devices)

    workdir = tempfile.mkdtemp(prefix='chip_smoke_')
    # what `train_imagenet.py --dtype float16` means on a TPU: bfloat16
    os.environ.setdefault('MXTPU_F16_AS_BF16', '1')
    # the repo's own counters (fused_fit.windows, xla.compiles, cache_hits)
    os.environ.setdefault('MXTPU_TELEMETRY', '1')
    os.environ.setdefault('MXTPU_TELEMETRY_PATH',
                          os.path.join(workdir, 'telemetry.jsonl'))
    try:
        import mxnet_tpu as mx
        if args.four_chips:
            phase_four_chips(mx, resnet50('float32'), (3, 224, 224), 1000,
                             128, 4, 2, args.seed, 'tpu')
        else:
            mod, losses, _ = phase_fit(mx, resnet50(), mx.tpu(0),
                                       (3, 224, 224), 1000, 32, 3,
                                       args.seed, 'tpu')
            assert_learned(losses)
            engine = phase_serve(mx, mod, mx.tpu(0), workdir, (3, 224, 224),
                                 32, (1, 3, 32), args.seed, 'tpu')
            del mod, engine
            gc.collect()
            phase_kernels(devices[0], full=True, compiled=True)
            phase_cache(devices[0])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    log('all phases passed')
    print(json.dumps({'ok': True, 'device': device}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
