"""Headline benchmark: ResNet-50 training throughput (images/sec) + MFU.

Mirrors the reference's `train_imagenet.py` perf table config
(docs/how_to/perf.md:150-190, batch 32, synthetic data): one full
training step — forward, softmax CE, backward, mixed-precision
SGD-momentum update (bf16 compute, fp32 master weights via the
registered `mp_sgd_mom_update` op), BatchNorm stat updates — compiled
to a single donated-buffer XLA computation.

vs_baseline divides by the strongest single-GPU reference number:
P100 batch-32 ResNet-50 training at 181.53 img/s (BASELINE.md).

Needs the chip: with no TPU device visible it exits non-zero and prints
no result, and a phase that fails fails the run. A number from a CPU
run is never printed under these metrics' names. The whole bench is one
process; it starts no child that needs the chip.

Prints JSON lines {"metric", "value", "unit", "vs_baseline", ...};
the LAST line is the training number (the inference line precedes it).
"""
import json
import os
import sys
import time

import numpy as np

# P100 batch-32 training rows, docs/how_to/perf.md:150-190 (AlexNet is
# the table's 8x-batch column: batch 256)
BASELINE_IMG_S = {'resnet50': 181.53, 'alexnet': 1869.69,
                  'inceptionv3': 129.98}
# 'resnet50' (the baseline-comparable default), 'alexnet'/'inceptionv3'
# (the other two train_imagenet.py perf-table columns), or 'transformer'
# (the matmul-dominated MFU probe: GPT-style decoder, flash-attention
# Pallas kernel + fused rmsnorm)
MODEL = os.environ.get('MXTPU_BENCH_MODEL', 'resnet50')
BATCH = int(os.environ.get('MXTPU_BENCH_BATCH',
                           '256' if MODEL == 'alexnet' else '32'))
# gradient-memory tradeoff knob (BASELINE.md "Memory-mirroring"); same
# values the executor honors: '1' = full remat, 'dots' = keep matmuls
MIRROR = os.environ.get('MXTPU_BACKWARD_DO_MIRROR',
                        os.environ.get('MXNET_BACKWARD_DO_MIRROR', ''))
MIRROR = '' if MIRROR in ('', '0', 'false', 'False') else MIRROR
# steps fused into one XLA call via lax.scan (in-graph train loop, the
# standard TPU pattern): one dispatch and one fetch per window, so host
# overhead per step shrinks with the window. 32 matches the fused-fit
# default for a module bound to TPU devices.
STEPS_PER_CALL = int(os.environ.get('MXTPU_BENCH_STEPS_PER_CALL', '32'))
WARMUP_STEPS = 3
# Peak dense bf16 FLOP/s per chip lives in ONE place —
# mxnet_tpu/telemetry/xla.py — shared by this bench's MFU and the
# telemetry summary's xla.mfu gauge (see _peak_flops below).


def _log(msg):
    print('[bench] ' + msg, file=sys.stderr, flush=True)


def init_backend():
    """The TPU devices, or exit non-zero: this bench measures the chip
    and nothing else."""
    import jax
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        _log('FATAL: no TPU device is visible (jax.devices() = %s); '
             'bench.py measures the chip and prints nothing without one'
             % devs)
        sys.exit(2)
    _log('backend up: %s' % devs)
    return devs, devs[0].platform


def build_transformer_step():
    """GPT-style decoder train step: bf16 compute / fp32 masters, causal
    flash attention (ops/pallas_kernels) + fused rmsnorm, SwiGLU-free
    4x MLP, tied CE loss. The matmul-dominated MFU probe — ResNet's
    small-spatial conv gradients cap its MFU; this is the shape the MXU
    is built for."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    from mxnet_tpu.ops.registry import get as get_op

    D = int(os.environ.get('MXTPU_BENCH_DMODEL', '1024'))
    L = int(os.environ.get('MXTPU_BENCH_LAYERS', '8'))
    S = int(os.environ.get('MXTPU_BENCH_SEQ', '1024'))
    V = int(os.environ.get('MXTPU_BENCH_VOCAB', '16384'))
    B = int(os.environ.get('MXTPU_BENCH_BATCH', '8'))
    DH = 128
    H = D // DH

    rng = np.random.RandomState(0)

    def p(*shape, scale=None):
        s = scale if scale is not None else (shape[0] ** -0.5)
        return jnp.asarray((rng.standard_normal(shape) * s)
                           .astype(np.float32))

    masters = [p(V, D, scale=0.02)]                      # embed
    for i in range(L):
        masters += [jnp.ones((D,), jnp.float32),          # ln1
                    p(D, 3 * D), p(D, D),                 # qkv, out
                    jnp.ones((D,), jnp.float32),          # ln2
                    p(D, 4 * D), p(4 * D, D)]             # up, down
    masters += [jnp.ones((D,), jnp.float32), p(D, V, scale=0.02 ** 0.5)]
    masters = tuple(masters)

    def rms(x, g):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x.astype(jnp.float32) *
                jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * g

    def fwd(params, tokens):
        it = iter(params)
        embed = next(it)
        x = embed[tokens]                                 # (B,S,D) bf16
        for _ in range(L):
            g1, wqkv, wo, g2, wup, wdn = (next(it) for _ in range(6))
            h = rms(x, g1)
            qkv = h @ wqkv
            q, k, v = jnp.split(qkv.reshape(B, S, H, 3 * DH), 3, axis=-1)
            a = flash_attention(q, k, v, causal=True)
            x = x + a.reshape(B, S, D) @ wo
            h = rms(x, g2)
            x = x + jax.nn.gelu(h @ wup) @ wdn
        gf, head = next(it), next(it)
        return rms(x, gf) @ head                          # (B,S,V)

    mp_update = get_op('mp_sgd_mom_update').fn
    attrs = {'lr': 0.01, 'momentum': 0.9, 'wd': 0.0,
             'rescale_grad': 1.0, 'clip_gradient': -1.0}

    def step(masters, aux, vel, tokens, labels, key):
        def loss_fn(bf16_params):
            logits = fwd(bf16_params, tokens).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return jnp.mean(lse - gold), aux

        bf16 = tuple(m.astype(jnp.bfloat16) for m in masters)
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(bf16)
        new_m, new_v = [], []
        for m, g, v in zip(masters, grads, vel):
            _, nv, m32 = mp_update(attrs, m.astype(jnp.bfloat16), g, v, m)
            new_m.append(m32)
            new_v.append(nv)
        return tuple(new_m), aux, tuple(new_v), loss

    vel = tuple(jnp.zeros_like(m) for m in masters)
    tokens = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, V, (B, S)), jnp.int32)
    key = jax.random.PRNGKey(0)
    return step, masters, (), vel, tokens, labels, key


def build_train_step():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.ops.registry import get as get_op

    zoo_name = {'resnet50': 'resnet50_v1', 'alexnet': 'alexnet',
                'inceptionv3': 'inceptionv3'}[MODEL]
    image = 299 if MODEL == 'inceptionv3' else 224
    data_shape = (BATCH, 3, image, image)
    net = vision.get_model(zoo_name, classes=1000)
    net.hybridize()
    _, sym = net._get_graph(
        type('P', (), {'shape': data_shape,
                       'context': None})())  # placeholder-shaped trace
    prog = _GraphProgram(sym)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=data_shape)
    arg_names, aux_names = prog.arg_names, prog.aux_names

    rng = np.random.RandomState(0)
    data_idx = arg_names.index('data')
    masters = []  # fp32 master weights
    for name, shape in zip(arg_names, arg_shapes):
        masters.append(jnp.asarray(_host_init(name, shape, rng)))
    aux_arrays = tuple(jnp.asarray(_host_init(n, s, rng))
                       for n, s in zip(aux_names, aux_shapes))
    runner = prog.make_runner()
    if MIRROR:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if MIRROR == 'dots' else None)
        runner = jax.checkpoint(runner, policy=policy, static_argnums=(3,))
        _log('backward mirroring ON (%s): forward rematerialized in bwd'
             % MIRROR)
    mp_update = get_op('mp_sgd_mom_update').fn

    # BN-free AlexNet diverges (loss=nan by warmup) at the BN-nets' 0.1:
    # its 9216->4096 FC stack amplifies He-init activations with nothing
    # renormalizing them. 0.01 is the original AlexNet recipe's lr.
    lr = 0.01 if MODEL == 'alexnet' else 0.1
    momentum, wd = 0.9, 1e-4
    attrs = {'lr': lr, 'momentum': momentum, 'wd': wd,
             'rescale_grad': 1.0, 'clip_gradient': -1.0}

    def step(masters, aux, vel, images, labels, key):
        # bf16 working copies of the fp32 masters: the whole fwd+bwd runs
        # on the MXU in bf16; the update runs in fp32 (mp_sgd_mom_update).
        def loss_fn(bf16_args):
            a = list(bf16_args)
            a[data_idx] = images
            # aux (BN running stats) also feed the graph in bf16 — fp32
            # aux would promote activations to fp32 mid-network; the
            # UPDATED stats are stored back as fp32 masters below
            aux_bf16 = tuple(x.astype(jnp.bfloat16) for x in aux)
            outs, new_aux = runner(tuple(a), aux_bf16, key, True)
            new_aux = tuple(x.astype(jnp.float32) for x in new_aux)
            logits = outs[0].astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
            return jnp.mean(lse - gold), new_aux

        bf16_args = tuple(m.astype(jnp.bfloat16) for m in masters)
        (loss, new_aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(bf16_args)
        new_masters, new_vel = [], []
        for i, (m, g, v) in enumerate(zip(masters, grads, vel)):
            if i == data_idx:
                new_masters.append(m)
                new_vel.append(v)
                continue
            _, nv, m32 = mp_update(attrs, m.astype(jnp.bfloat16), g, v, m)
            new_masters.append(m32)
            new_vel.append(nv)
        return tuple(new_masters), new_aux, tuple(new_vel), loss

    vel = tuple(jnp.zeros_like(m) for m in masters)
    images = jnp.asarray(rng.standard_normal(data_shape), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, (BATCH,)), jnp.int32)
    key = jax.random.PRNGKey(0)
    return step, tuple(masters), aux_arrays, vel, images, labels, key


def _host_init(name, shape, rng):
    """Host-side (numpy) parameter init by name convention — values only
    need to be numerically sane for a throughput bench."""
    if 'gamma' in name or 'var' in name:
        return np.ones(shape, np.float32)
    if 'beta' in name or 'bias' in name or 'mean' in name:
        return np.zeros(shape, np.float32)
    fan_in = int(np.prod(shape[1:])) if len(shape) > 1 else shape[0]
    std = (2.0 / max(1, fan_in)) ** 0.5
    return (rng.standard_normal(shape) * std).astype(np.float32)


def _analyze_step(compiled):
    """XLA's cost/memory analysis of the compiled step, via the
    telemetry program registrar (mxnet_tpu/telemetry/programs) — the
    same record every framework compile site publishes. Registering as
    a step program also feeds xla.step_flops for the MFU gauge (the
    scan body is counted once by XLA regardless of trip count, so the
    record's flops are per-step already). Returns the analysis dict
    (flops, bytes_accessed, temp_bytes, ... — zeros where the backend
    doesn't report); works with telemetry off too."""
    from mxnet_tpu.telemetry import programs as _programs
    rec = _programs.note_program('bench.train_step', compiled,
                                 step_flops=True)
    # the registrar logs analysis failures at debug; the bench operator
    # must SEE why the headline flops/MFU would be zero
    if not rec['flops']:
        _log('cost_analysis unavailable (flops=0) — MFU and the '
             'per-step flops line will be missing/zero')
    if not rec['temp_bytes']:
        _log('memory_analysis unavailable (temp_bytes=0)')
    return rec


def _peak_flops(device):
    from mxnet_tpu.telemetry.xla import device_peak_flops
    peak, _ = device_peak_flops(device)
    return peak, getattr(device, 'device_kind', '') or ''


def _wrap_health_sentinel(raw_step):
    """The train step + the in-graph health sentinel vector
    (telemetry/health step_stats: param norm, update/param ratio,
    finite flags) computed ONCE PER STEP inside the scan — exactly
    where the MXTPU_HEALTH fused-fit path runs it, so the measured
    overhead reflects W sentinel computations per dispatch, not one.
    Takes the raw (unfused) step; the STEPS_PER_CALL fusion is the
    SAME _wrap_steps_per_call the baseline uses (the A/B must not
    compare differently-fused programs)."""
    from mxnet_tpu.telemetry import health as _health

    def one(m, a, v, images, labels, key):
        m2, a2, v2, loss = raw_step(m, a, v, images, labels, key)
        hv = _health.step_stats((loss,), params=m, new_params=m2)
        return m2, a2, v2, (loss, hv)

    return _wrap_steps_per_call(one)


def _measure_health_overhead(raw_step, masters, aux, vel, images, labels,
                             key, per_step_base):
    """Compile the sentinel-wrapped step (sentinel per scan step, like
    the real fused path) and time it against the base per-dispatch
    time. Returns the JSON-ready dict or None (the probe must never
    cost the headline number — it runs after the main measurement and
    consumes the donated buffers it is handed)."""
    import jax
    try:
        t0 = time.perf_counter()
        step_h = _wrap_health_sentinel(raw_step)
        compiled = jax.jit(step_h, donate_argnums=(0, 1, 2)).lower(
            masters, aux, vel, images, labels, key).compile()
        _log('health-sentinel probe compile: %.1fs'
             % (time.perf_counter() - t0))
        masters, aux, vel, (loss, hv) = compiled(
            masters, aux, vel, images, labels, key)            # warmup
        float(np.asarray(loss))
        from mxnet_tpu import telemetry as _tele
        n = int(min(100, max(5, 8.0 / max(per_step_base, 1e-4))))
        t0 = time.perf_counter()
        for _ in range(n):
            # same per-dispatch wrapper as the baseline loop (span +
            # counter): the comparison must not credit the sentinel
            # with the baseline's telemetry bookkeeping
            with _tele.span('bench.dispatch', 'bench'):
                masters, aux, vel, (loss, hv) = compiled(
                    masters, aux, vel, images, labels, key)
            _tele.counter('fit.steps').inc(STEPS_PER_CALL)
        float(np.asarray(loss))
        per_step_h = (time.perf_counter() - t0) / n
        overhead = 100.0 * (per_step_h - per_step_base) / per_step_base
        _log('health sentinel overhead: %.2f%% (%.4fs vs %.4fs per '
             'dispatch, %d probe steps, sentinel per scan step)'
             % (overhead, per_step_h, per_step_base, n))
        hv_host = np.asarray(hv)
        return {'sentinel_overhead_pct': round(overhead, 2),
                'probe_steps': n,
                'finite': bool(np.all(hv_host[..., 0] != 0))}
    except Exception as e:  # noqa: BLE001 — the probe must never kill
        _log('health overhead probe failed: %s' % e)
        return None


def _wrap_steps_per_call(step):
    """Fuse STEPS_PER_CALL steps into one device call via lax.scan —
    shared by the measuring path and the compile-only probe children,
    which must compile the SAME program or the warm-compile number
    would time a cache miss of a different (unwrapped) computation."""
    if STEPS_PER_CALL <= 1:
        return step
    import jax
    inner = step

    def step(masters, aux, vel, images, labels, key):
        def body(carry, _):
            m, a, v = carry
            m, a, v, loss = inner(m, a, v, images, labels, key)
            return (m, a, v), loss
        (m, a, v), losses = jax.lax.scan(
            body, (masters, aux, vel), None, length=STEPS_PER_CALL)
        # last step's ys — tree_map so a step whose ys is a pytree
        # (the health probe's (loss, sentinel) pair) fuses through the
        # same wrapper; for the plain scalar loss this is losses[-1]
        return m, a, v, jax.tree_util.tree_map(lambda x: x[-1], losses)

    return step


def run_infer_bench(platform, kind):
    """ResNet-50 inference throughput through the REAL Module.predict
    API: the fused window path (module/fused_eval.py, one dispatch +
    one fetch per W batches) vs the per-batch reference path
    (MXTPU_FUSED_EVAL=0). bf16 compute via a Cast at the input —
    type inference makes every downstream parameter bf16, mirroring
    the training bench's compute dtype. Returns the JSON-ready dict
    (both numbers printed; the fused one is the headline)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.config import flags as _flags
    from mxnet_tpu.gluon.model_zoo import vision

    # a window of 8 keeps the synthetic set (2 windows) small enough to
    # stage on the host while still amortizing dispatch 8x
    saved_w = os.environ.get('MXTPU_EVAL_STEPS_PER_CALL')
    os.environ.setdefault('MXTPU_EVAL_STEPS_PER_CALL', '8')
    _flags.reload('MXTPU_EVAL_STEPS_PER_CALL')
    W = _flags.get('MXTPU_EVAL_STEPS_PER_CALL') or 32
    batch = BATCH
    image = 224
    n = 2 * W * batch
    _log('building resnet50 inference module (bf16, batch %d, W=%d)...'
         % (batch, W))
    net = vision.get_model('resnet50_v1', classes=1000)
    net.hybridize()
    data_shape = (batch, 3, image, image)
    _, sym = net._get_graph(
        type('P', (), {'shape': data_shape, 'context': None})())
    sym_bf = sym(data=mx.sym.Cast(mx.sym.Variable('data'),
                                  dtype='bfloat16'))
    ctx = mx.tpu(0)
    mod = mx.mod.Module(sym_bf, label_names=[], context=ctx)
    rng = np.random.RandomState(0)
    X = rng.standard_normal((n, 3, image, image)).astype(np.float32)
    it = mx.io.NDArrayIter(X, None, batch_size=batch)
    mod.bind(data_shapes=it.provide_data, for_training=False)
    mod.init_params()

    def timed_predict():
        it.reset()
        t0 = time.perf_counter()
        out = mod.predict(it, reset=False)
        # host fetch = true barrier (per-batch predict is fully async;
        # the fused path is already host-resident by construction)
        np.asarray(out.asnumpy())
        return n / (time.perf_counter() - t0)

    results = {}
    saved_fe = os.environ.get('MXTPU_FUSED_EVAL')
    try:
        for label, flag in (('fused', '1'), ('per_batch', '0')):
            os.environ['MXTPU_FUSED_EVAL'] = flag
            _flags.reload('MXTPU_FUSED_EVAL')
            t = time.perf_counter()
            timed_predict()       # warmup: compiles this path's program
            _log('infer %s warmup: %.1fs' % (label,
                                             time.perf_counter() - t))
            results[label] = timed_predict()
            _log('infer %s: %.2f img/s' % (label, results[label]))
    finally:
        # restore the caller's flags exactly (an explicit
        # MXTPU_FUSED_EVAL=0 opt-out must survive this A/B, including
        # into any late-reprobe child that inherits os.environ)
        for var, saved in (('MXTPU_FUSED_EVAL', saved_fe),
                           ('MXTPU_EVAL_STEPS_PER_CALL', saved_w)):
            if saved is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = saved
            _flags.reload(var)

    out = {
        'metric': 'resnet50_infer_throughput_bf16',
        'value': round(results['fused'], 2),
        'unit': 'images/sec',
        'per_batch_value': round(results['per_batch'], 2),
        'speedup_vs_per_batch': round(results['fused']
                                      / max(results['per_batch'], 1e-9), 3),
        'batch': batch,
        'eval_steps_per_call': W,
        'device': kind or platform,
        'platform': platform,
    }
    return out


def run_serving_bench(platform):
    """Closed-loop load generator against the in-process serving plane
    (mxnet_tpu/serving, ISSUE 13): a ServingEngine over a small MLP
    with the bucket ladder pre-warmed, a DynamicBatcher in front, and
    K client threads each running a closed request loop (send 1-4
    rows, wait for the answer, repeat) — no HTTP, so the numbers
    measure queue+coalesce+dispatch+split, not socket overhead.
    Banks serving_p50_ms / serving_p99_ms / serving_throughput_rps /
    pad_fraction (tools/bench_diff.py gates the p99 at 10%)."""
    import threading as _threading
    import mxnet_tpu as mx
    from mxnet_tpu.serving import DynamicBatcher, ServingEngine

    clients = int(os.environ.get('MXTPU_BENCH_SERVE_CLIENTS', '4'))
    per_client = int(os.environ.get('MXTPU_BENCH_SERVE_REQS', '50'))
    max_batch = int(os.environ.get('MXTPU_BENCH_SERVE_MAX_BATCH', '16'))
    hidden = 64
    _log('serving bench: %d clients x %d closed-loop requests, '
         'bucket ladder up to %d...' % (clients, per_client, max_batch))
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=hidden, name='srv_fc1')
    act = mx.sym.Activation(fc1, act_type='relu', name='srv_relu')
    fc2 = mx.sym.FullyConnected(act, num_hidden=8, name='srv_fc2')
    sym = mx.sym.SoftmaxOutput(fc2, name='softmax')
    ctx = mx.tpu(0)
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[('data', (max_batch, 16))], for_training=False)
    mod.init_params()
    engine = ServingEngine(mod, max_batch=max_batch)
    t = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t
    batcher = DynamicBatcher(engine, max_wait_ms=2.0).start()

    lats, errors = [], [0]
    lock = _threading.Lock()

    def client(seed):
        rng = np.random.RandomState(seed)
        mine = []
        for _ in range(per_client):
            rows = int(rng.randint(1, 5))
            x = rng.standard_normal((rows, 16)).astype(np.float32)
            t0 = time.perf_counter()
            try:
                batcher.predict([x], timeout=60)
            except Exception:  # noqa: BLE001 — counted, never fatal
                with lock:
                    errors[0] += 1
                continue
            mine.append((time.perf_counter() - t0) * 1e3)
        with lock:
            lats.extend(mine)

    threads = [_threading.Thread(target=client, args=(1000 + i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    # read the ledgers AFTER close(): it joins the dispatcher and the
    # fetch pool, so the final batch's stage entry has landed and no
    # thread mutates the deques mid-iteration
    batcher.close()
    log = list(batcher.dispatch_log)
    queue_waits = list(batcher.queue_wait_log)
    stage_log = list(batcher.stage_log)
    if not lats:
        raise RuntimeError('serving bench produced no successful requests')
    total_rows = sum(r for r, _, _ in log)
    bucket_rows = sum(b for _, b, _ in log)

    def _stage_p50(key):
        vals = [s[key] for s in stage_log if s.get(key) is not None]
        return round(float(np.percentile(vals, 50)), 3) if vals else None

    out = {
        'serving_p50_ms': round(float(np.percentile(lats, 50)), 3),
        'serving_p99_ms': round(float(np.percentile(lats, 99)), 3),
        'serving_throughput_rps': round(len(lats) / wall, 2),
        # per-stage breakdown (the tracing plane's host-measured
        # decomposition; queue wait gated by tools/bench_diff.py)
        'serving_queue_wait_p50_ms': round(
            float(np.percentile(queue_waits, 50)), 3)
        if queue_waits else None,
        'serving_stage_p50_ms': {
            'coalesce': _stage_p50('coalesce_ms'),
            'pad': _stage_p50('pad_ms'),
            'dispatch': _stage_p50('dispatch_ms'),
            'fetch': _stage_p50('fetch_ms'),
            'split': _stage_p50('split_ms'),
        },
        'pad_fraction': round((bucket_rows - total_rows)
                              / float(max(bucket_rows, 1)), 4),
        'requests': len(lats),
        'errors': errors[0],
        'clients': clients,
        'dispatches': len(log),
        'mean_batch': round(total_rows / float(max(len(log), 1)), 2),
        'coalesced_dispatches': sum(1 for _, _, n in log if n > 1),
        'max_batch': max_batch,
        'warmup_s': round(warm_s, 2),
    }
    _log('serving: %.1f req/s, p50 %.2f ms, p99 %.2f ms, '
         'mean batch %.1f over %d dispatches (%d coalesced), '
         'pad %.1f%%'
         % (out['serving_throughput_rps'], out['serving_p50_ms'],
            out['serving_p99_ms'], out['mean_batch'], out['dispatches'],
            out['coalesced_dispatches'], 100 * out['pad_fraction']))
    stages = out['serving_stage_p50_ms']
    _log('serving stages p50: queue %s ms, %s'
         % (out['serving_queue_wait_p50_ms'],
            ', '.join('%s %s ms' % (k, stages[k])
                      for k in ('coalesce', 'pad', 'dispatch', 'fetch',
                                'split'))))
    return out


def run_fused_window_ab(platform):
    """Donation + BN-one-pass A/B (ISSUE 12) through the REAL
    Module.fit fused window on a conv+BatchNorm net: the 'pre' arm
    rebuilds the pre-PR program (MXTPU_FUSED_DONATE=0,
    MXTPU_BN_ONEPASS=0 — undonated carry, two-pass stats), the 'tuned'
    arm runs the shipped defaults. Per arm: one warm fit (compiles the
    window), two timed epochs, then the window program's
    temp/live/alias bytes off the registrar gauges."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry as _tele
    from mxnet_tpu.config import flags as _flags

    saved = {v: os.environ.get(v) for v in
             ('MXTPU_FUSED_DONATE', 'MXTPU_BN_ONEPASS',
              'MXTPU_FIT_STEPS_PER_CALL')}
    os.environ['MXTPU_FIT_STEPS_PER_CALL'] = '4'
    _flags.reload('MXTPU_FIT_STEPS_PER_CALL')
    batch, windows_per_epoch = 8, 4
    n = batch * 4 * windows_per_epoch
    ctx = mx.tpu(0)
    res = {}
    try:
        for arm, (don, bn) in (('pre', ('0', '0')),
                               ('tuned', ('1', '1'))):
            os.environ['MXTPU_FUSED_DONATE'] = don
            os.environ['MXTPU_BN_ONEPASS'] = bn
            _flags.reload('MXTPU_FUSED_DONATE')
            _flags.reload('MXTPU_BN_ONEPASS')
            mx.random.seed(13)
            rng = np.random.RandomState(13)
            # distinct symbol names per arm -> distinct program records
            name = 'fwab_%s' % arm
            d = mx.sym.Variable('data')
            h = d
            for i in range(3):
                h = mx.sym.Convolution(h, num_filter=32, kernel=(3, 3),
                                       pad=(1, 1),
                                       name='%s_conv%d' % (name, i))
                h = mx.sym.BatchNorm(h, name='%s_bn%d' % (name, i))
                h = mx.sym.Activation(h, act_type='relu')
            h = mx.sym.FullyConnected(mx.sym.Flatten(h), num_hidden=16,
                                      name='%s_fc' % name)
            sym = mx.sym.SoftmaxOutput(h, name=name)
            X = rng.standard_normal((n, 3, 16, 16)).astype(np.float32)
            y = (rng.rand(n) * 16).astype(int).astype(np.float32)
            it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False,
                                   label_name='%s_label' % name)
            mod = mx.mod.Module(sym, context=ctx,
                                label_names=('%s_label' % name,))
            okw = dict(optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),
                                         ('momentum', 0.9)),
                       eval_metric='acc')
            t = time.perf_counter()
            mod.fit(it, num_epoch=1, **okw)      # compile + warm
            _log('fused-window A/B %s warmup: %.1fs'
                 % (arm, time.perf_counter() - t))
            t0 = time.perf_counter()
            mod.fit(it, begin_epoch=1, num_epoch=3, **okw)
            dt = time.perf_counter() - t0
            snap = _tele.snapshot() if _tele.enabled() else {}
            g = snap.get('gauges', {})
            pfx = 'program.fused_fit.window[%s].' % name
            res[arm] = {
                'img_s': round(2 * n / dt, 2),
                'temp_bytes': int(g.get(pfx + 'temp_bytes', 0)) or None,
                'live_bytes': int(g.get(pfx + 'live_bytes', 0)) or None,
                'alias_bytes': int(g.get(pfx + 'alias_bytes', 0)) or None}
            _log('fused-window A/B %s: %.2f img/s, temp=%s live=%s'
                 % (arm, res[arm]['img_s'], res[arm]['temp_bytes'],
                    res[arm]['live_bytes']))
    finally:
        for var, old in saved.items():
            if old is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = old
            _flags.reload(var)
    pre, tuned = res['pre'], res['tuned']
    ab = {'batch': batch, 'pre': pre, 'tuned': tuned,
          'speedup': round(tuned['img_s'] / max(pre['img_s'], 1e-9), 3)}
    if pre['live_bytes'] and tuned['live_bytes']:
        ab['live_bytes_drop_pct'] = round(
            100.0 * (pre['live_bytes'] - tuned['live_bytes'])
            / pre['live_bytes'], 1)
    if pre['temp_bytes'] and tuned['temp_bytes']:
        ab['temp_bytes_drop_pct'] = round(
            100.0 * (pre['temp_bytes'] - tuned['temp_bytes'])
            / pre['temp_bytes'], 1)
    return ab


def run_sharded_update_ab(platform):
    """Sharded-vs-replicated weight-update A/B (MXTPU_SHARDED_UPDATE,
    arXiv:2004.13336) through the REAL Module.fit fused window over a
    dp mesh of all local devices. Only meaningful at dp > 1 (returns
    None otherwise — the ZeRO layout is a documented no-op at dp=1).
    Per arm: one warm fit (compiles the window), then two timed
    epochs; the per-device optimizer-state footprint comes off the
    update.opt_state_bytes_per_device gauge the loop publishes, and
    the update collectives' traffic off the roofline's per-opcode
    accounting for the sharded arm's window program. MXTPU_BENCH_AB_*
    env knobs size the probe model."""
    import jax
    ndev = len(jax.devices())
    if ndev < 2:
        _log('sharded-update A/B skipped: dp=1 (single device)')
        return None
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry as _tele
    from mxnet_tpu.config import flags as _flags

    hidden = int(os.environ.get('MXTPU_BENCH_AB_HIDDEN', '512'))
    feat = int(os.environ.get('MXTPU_BENCH_AB_FEATURES', '64'))
    batch = 8 * ndev
    windows_per_epoch = 4
    saved = {v: os.environ.get(v) for v in
             ('MXTPU_SHARDED_UPDATE', 'MXTPU_FIT_STEPS_PER_CALL')}
    os.environ['MXTPU_FIT_STEPS_PER_CALL'] = '4'
    _flags.reload('MXTPU_FIT_STEPS_PER_CALL')
    n = batch * 4 * windows_per_epoch
    ctx_fn = mx.tpu
    ctxs = [ctx_fn(i) for i in range(ndev)]
    res = {}
    try:
        for arm, flag in (('replicated', '0'), ('sharded', '1')):
            os.environ['MXTPU_SHARDED_UPDATE'] = flag
            _flags.reload('MXTPU_SHARDED_UPDATE')
            mx.random.seed(11)
            rng = np.random.RandomState(11)
            # distinct symbol names per arm -> distinct program records
            # in the registrar/roofline (the merge rule would otherwise
            # keep whichever variant parsed larger)
            name = 'ab_%s' % arm
            data = mx.sym.Variable('data')
            h = mx.sym.Activation(mx.sym.FullyConnected(
                data, num_hidden=hidden, name='%s_fc1' % name),
                act_type='relu')
            h = mx.sym.Activation(mx.sym.FullyConnected(
                h, num_hidden=hidden, name='%s_fc2' % name),
                act_type='relu')
            sym = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
                h, num_hidden=16, name='%s_fc3' % name), name=name)
            X = rng.standard_normal((n, feat)).astype(np.float32)
            y = (rng.rand(n) * 16).astype(int).astype(np.float32)
            it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False,
                                   label_name='%s_label' % name)
            mod = mx.mod.Module(sym, context=ctxs,
                                label_names=('%s_label' % name,))
            okw = dict(optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),
                                         ('momentum', 0.9)),
                       kvstore='device', eval_metric='acc')
            t = time.perf_counter()
            mod.fit(it, num_epoch=1, **okw)      # compile + warm
            _log('sharded-update A/B %s warmup: %.1fs'
                 % (arm, time.perf_counter() - t))
            t0 = time.perf_counter()
            mod.fit(it, begin_epoch=1, num_epoch=3, **okw)
            dt = time.perf_counter() - t0
            g = _tele.snapshot()['gauges'] if _tele.enabled() else {}
            loop = mod.__dict__.get('_fused_fit_cache')
            res[arm] = {
                'img_s': round(2 * n / dt, 2),
                'opt_state_bytes_per_device':
                    int(g['update.opt_state_bytes_per_device'])
                    if 'update.opt_state_bytes_per_device' in g else None,
                'engaged': bool(loop is not None
                                and loop[1]._zero is not None)}
            _log('sharded-update A/B %s: %.2f img/s, opt state '
                 '%s B/device' % (arm, res[arm]['img_s'],
                                  res[arm]['opt_state_bytes_per_device']))
        comm = _tele.roofline.comm_bytes_by_op('fused_fit.window[ab_sharded')
        upd_comm = sum(v for k, v in comm.items()
                       if k.startswith(('reduce-scatter', 'all-gather')))
    finally:
        for var, old in saved.items():
            if old is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = old
            _flags.reload(var)
    r0, r1 = res['replicated'], res['sharded']
    ab = {'dp': ndev, 'batch': batch, 'hidden': hidden,
          'replicated_img_s': r0['img_s'], 'sharded_img_s': r1['img_s'],
          'sharded_speedup': round(r1['img_s'] / max(r0['img_s'], 1e-9), 3),
          'sharded_engaged': r1['engaged'],
          'opt_state_bytes_per_device': r1['opt_state_bytes_per_device'],
          'opt_state_bytes_per_device_replicated':
              r0['opt_state_bytes_per_device']}
    if upd_comm:
        # per-step bytes the sharded update moves between chips
        # (reduce-scatter'd grads + all-gather'd params; CPU lowerings
        # without the reduce-scatter pass show the all-gather half)
        ab['update_comm_bytes'] = round(upd_comm, 1)
    return ab


def _telemetry_breakdown(device, step_ms=None):
    """The dispatch/compile breakdown + peak device bytes from the
    telemetry registry, as a JSON-ready dict (None when telemetry is
    off or empty) — BENCH_*.json carries this from this round on.
    ``step_ms`` is the measured per-step wall time — the roofline's
    denominator (the registry can only see per-DISPATCH spans here,
    which cover STEPS_PER_CALL steps each)."""
    try:
        from mxnet_tpu import telemetry as _tele
        if not _tele.enabled():
            return None
        _tele.xla.sample_memory(device)
        snap = _tele.snapshot()
        tel = {}
        c = snap['counters']
        if c.get('xla.compiles'):
            tel['compiles'] = int(c['xla.compiles'])
            tel['compile_secs'] = round(c.get('xla.compile_secs', 0.0), 3)
        if c.get('xla.cache_hits'):
            # compiles served from the persistent compile cache
            tel['cache_hits'] = int(c['xla.cache_hits'])
            tel['cache_saved_secs'] = round(
                c.get('xla.cache_saved_secs', 0.0), 3)
        h = snap['histograms'].get('bench.dispatch')
        if h and h['count']:
            tel['dispatch_ms'] = {k: round(h[k], 3)
                                  for k in ('p50', 'p95', 'max')}
        g = snap['gauges']
        if 'xla.peak_bytes_in_use' in g:
            tel['peak_device_bytes'] = int(g['xla.peak_bytes_in_use'])
        if 'xla.bytes_in_use' in g:
            tel['live_device_bytes'] = int(g['xla.bytes_in_use'])
        # sharded weight update (ISSUE 9): the per-device optimizer-
        # state footprint the fused loop published, when a Module fit
        # ran in this process BEFORE this fold (the A/B probe runs
        # after it, so its gauges land only in out['sharded_update_ab'])
        if 'update.opt_state_bytes_per_device' in g:
            tel['opt_state_bytes_per_device'] = \
                int(g['update.opt_state_bytes_per_device'])
            tel['sharded_update'] = bool(g.get('update.sharded'))
        # quantized gradient collectives (ISSUE 17): wire bytes per
        # sync step + ratio, with the measured/modeled provenance the
        # gauges carry — bench_diff gates the byte count
        if 'comm.bytes_on_wire_per_step' in g:
            tel['bytes_on_wire_per_step'] = \
                int(g['comm.bytes_on_wire_per_step'])
            if g.get('comm.compression_ratio') is not None:
                tel['compression_ratio'] = \
                    float(g['comm.compression_ratio'])
            if g.get('comm.mode'):
                tel['compress_mode'] = g['comm.mode']
            if g.get('comm.bytes_src'):
                tel['comm_bytes_src'] = g['comm.bytes_src']
        # training-health counts (ISSUE 4): anomalies / non-finite
        # steps seen by the sentinels, when MXTPU_HEALTH ran
        hc = {n[len('health.'):]: int(v) for n, v in c.items()
              if n.startswith('health.')}
        if hc:
            tel['health'] = hc
        # cluster aggregation (ISSUE 5): the last sync round's per-host
        # gauges + straggler attribution, when MXTPU_TELEMETRY_SYNC_EVERY
        # ran; plus the live endpoint's port when one is serving
        clus = _tele.cluster.snapshot_cluster()
        if clus:
            tel['cluster'] = clus
        live_port = _tele.serve.port()
        if live_port is not None:
            tel['live_endpoint_port'] = live_port
        # per-program cost attribution (ISSUE 3): FLOPs/bytes per
        # compiled program — bench.train_step plus whatever the Module
        # paths compiled — alongside the top-line numbers
        progs = _tele.programs.snapshot_programs()
        if progs:
            tel['programs'] = {
                n: {'flops': r['flops'],
                    'bytes_accessed': r['bytes_accessed'],
                    'temp_bytes': r['temp_bytes'],
                    'compiles': r['compiles'],
                    'dispatches': r['dispatches']}
                for n, r in sorted(progs.items())}
        # roofline attribution (ISSUE 7): per-layer class + achieved/
        # peak placement and the collective accounting, published to
        # gauges/JSONL by summarize() and folded here (layers truncated
        # to the summary block's TOP_N — the JSONL record keeps all)
        roof = _tele.roofline.summarize(step_time_ms=step_ms)
        if roof:
            top_n = _tele.roofline.TOP_N
            tel['roofline'] = dict(roof, layers=roof['layers'][:top_n],
                                   n_layers=len(roof['layers']))
        # memory attribution (ISSUE 19): per-layer HBM shares + the
        # headroom/steps-to-OOM forecast — same truncation treatment;
        # per-program peak bytes ride the programs dict above
        mem = _tele.memory.summarize()
        if mem:
            lay = mem.get('layers') or []
            tel['memory'] = dict(mem, layers=lay[:_tele.memory.TOP_N],
                                 n_layers=len(lay))
            if mem.get('peaks') and tel.get('programs'):
                for n, pk in mem['peaks'].items():
                    if n in tel['programs']:
                        tel['programs'][n]['peak_bytes'] = int(pk)
        # goodput attribution (ISSUE 16): where this process's wall-
        # clock went, bucketed — AFTER roofline.summarize so the comm
        # bucket reads the just-published provenance-labeled share
        good = _tele.goodput.current()
        if good:
            tel['goodput'] = good
        # step timeline (ISSUE 20): the per-step phase decomposition
        # (compute / collective-wait / io / host-side shares) —
        # bench_diff gates the host-side share (host_overhead_pct)
        pb = _tele.timeline.phase_breakdown()
        if pb:
            tel['step_phase_breakdown'] = pb
            tl = _tele.timeline.summarize()
            if tl:
                tel['timeline'] = tl
        return tel or None
    except Exception as e:  # noqa: BLE001 — the bench number must survive
        _log('telemetry fold-in failed: %s' % e)
        return None


def main():
    _log('python up, pid=%d' % os.getpid())
    # telemetry rides every bench run (ISSUE 1): the compile/dispatch
    # breakdown and peak device bytes fold into the emitted JSON below.
    # setdefault: an explicit MXTPU_TELEMETRY=0 still wins.
    import tempfile
    os.environ.setdefault('MXTPU_TELEMETRY', '1')
    os.environ.setdefault('MXTPU_TELEMETRY_PATH',
                          os.path.join(tempfile.gettempdir(),
                                       'bench_telemetry.jsonl'))
    # roofline attribution rides every bench run (ISSUE 7): per-layer
    # achieved-vs-peak classification + collective accounting fold into
    # the emitted JSON below. setdefault: an explicit =0 still wins.
    os.environ.setdefault('MXTPU_ROOFLINE', '1')
    # memory plane rides every bench run (ISSUE 19): per-layer HBM
    # attribution + headroom forecast fold into the emitted JSON below,
    # and bench_diff gates the headroom. setdefault: an explicit =0
    # still wins.
    os.environ.setdefault('MXTPU_MEMORY', '1')
    # step timeline rides every bench run (ISSUE 20): the phase
    # decomposition folds into the emitted JSON below and bench_diff
    # gates the host-side share. setdefault: an explicit =0 still wins.
    os.environ.setdefault('MXTPU_TIMELINE', '1')
    devices, platform = init_backend()
    import jax

    t = time.perf_counter()
    if MODEL == 'transformer':
        _log('building GPT-style decoder train step '
             '(bf16, flash attention)...')
        step, masters, aux, vel, images, labels, key = \
            build_transformer_step()
        tokens_per_batch = int(images.shape[0] * images.shape[1])
    else:
        _log('building %s train step (bf16 compute, fp32 masters)...'
             % MODEL)
        step, masters, aux, vel, images, labels, key = build_train_step()
        tokens_per_batch = None
    _log('build+init: %.1fs' % (time.perf_counter() - t))

    raw_step = step   # pre-fusion form: the health probe re-fuses it
    if STEPS_PER_CALL > 1:         # with a sentinel inside each step
        step = _wrap_steps_per_call(step)
        _log('fusing %d steps per device call (lax.scan)' % STEPS_PER_CALL)

    from mxnet_tpu import telemetry as _tele

    t = time.perf_counter()
    _log('compiling (first compile can take 20-40s)...')
    jstep = jax.jit(step, donate_argnums=(0, 1, 2))
    lowered = jstep.lower(masters, aux, vel, images, labels, key)
    compiled = lowered.compile()
    compile_cold_s = time.perf_counter() - t
    step_analysis = _analyze_step(compiled)
    # XLA cost analysis counts a scan (while-loop) body ONCE regardless
    # of trip count (verified: identical flops at 1 vs 8 steps/call), so
    # scale to per-dispatch flops here (the registrar already fed the
    # per-step value to the MFU gauge)
    flops_per_step = step_analysis['flops'] * STEPS_PER_CALL
    temp_bytes = step_analysis['temp_bytes']
    _log('compile: %.1fs, step flops=%.3e, xla temp=%.1f MiB'
         % (compile_cold_s, flops_per_step, temp_bytes / 2**20))

    # the persistent cache is placed by config.enable_compile_cache (one
    # function decides); this run's compile is the cold or the warm one,
    # and xla.cache_hits says which — pair the two up across runs
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        served_from_cache = bool(
            _tele.snapshot()['counters'].get('xla.cache_hits', 0))
    except Exception:  # noqa: BLE001
        served_from_cache = None
    if served_from_cache:
        _log('train-step compile served from the persistent cache '
             '(%.1fs)' % compile_cold_s)

    t = time.perf_counter()
    warm_t0 = t
    warm_losses = []
    for _ in range(WARMUP_STEPS):
        masters, aux, vel, loss = compiled(
            masters, aux, vel, images, labels, key)
        # bench drives the raw compiled object, so the registrar's
        # wrapper never sees these dispatches — count them explicitly
        # or the bench.train_step program record reports dispatches=0
        _tele.programs.note_dispatch('bench.train_step')
        warm_losses.append(loss)   # scalar handles: banked post-barrier
    # sync via host fetch: the device->host copy is the barrier
    loss_val = float(np.asarray(loss))
    warmup_dt = time.perf_counter() - t
    _log('warmup (%d steps): %.1fs, loss=%.4f'
         % (WARMUP_STEPS, warmup_dt, loss_val))

    # Scale the measured run to ~10-30s of wall clock.
    per_step = max(1e-4, warmup_dt / WARMUP_STEPS)
    bench_steps = int(min(200, max(10, 15.0 / per_step)))
    _log('measuring %d steps...' % bench_steps)
    bench_losses = []
    t0 = time.perf_counter()
    for _ in range(bench_steps):
        # span = host-side dispatch cost per device call; device
        # compute overlaps asynchronously behind it
        with _tele.span('bench.dispatch', 'bench'):
            masters, aux, vel, loss = compiled(
                masters, aux, vel, images, labels, key)
        _tele.programs.note_dispatch('bench.train_step')  # see warmup
        # feeds the xla.mfu estimate together with note_step_flops above
        _tele.counter('fit.steps').inc(STEPS_PER_CALL)
        if _tele.timeline.enabled():
            # feeds the step-phase ledger so the timeline fold below
            # can decompose the step (dispatch share + wall per step)
            _tele.timeline.note_step(STEPS_PER_CALL)
        bench_losses.append(loss)
    float(np.asarray(loss))  # host fetch = true barrier (see warmup)
    dt = time.perf_counter() - t0

    # run-ledger feed (ISSUE 15): bank the warmup + measured loss
    # trajectory as `scalars` records. Dispatch is async, so per-call
    # enqueue clocks would bunch at the loop head — timestamps are
    # amortized evenly over each phase's measured wall time instead
    # (only deltas matter to time_to_loss). Fetched AFTER the barrier:
    # zero syncs inside the timed region.
    ledger_final_loss = None
    ledger_time_to_loss = None
    try:
        from mxnet_tpu.telemetry import ledger as _ledger
        if _ledger.enabled():
            # phase clocks are perf_counter (process uptime) — shift
            # them onto the epoch timeline so every scalars record's
            # 't' matches the rest of the JSONL (documented contract)
            epoch_anchor = time.time() - time.perf_counter()
            for phase_t0, phase_dt, losses, base in (
                    (warm_t0, warmup_dt, warm_losses, 0),
                    (t0, dt, bench_losses, WARMUP_STEPS)):
                n = len(losses)
                for i, l in enumerate(losses):
                    _ledger.feed((base + i + 1) * STEPS_PER_CALL,
                                 float(np.asarray(l)),
                                 t=epoch_anchor + phase_t0
                                 + (i + 1) * phase_dt / n)
            ledger_final_loss = _ledger.final_loss()
            tgt = _ledger.progress_target(0.9)
            secs = _ledger.time_to_loss(tgt)
            if tgt is not None and secs is not None:
                ledger_time_to_loss = {'target': round(tgt, 6),
                                       'seconds': secs}
    except Exception as e:  # noqa: BLE001 — the ledger must never cost
        _log('ledger feed failed (headline unaffected): %s' % e)
    del warm_losses, bench_losses

    # sentinel-overhead probe (MXTPU_BENCH_HEALTH=0 skips): the same
    # in-graph reductions MXTPU_HEALTH adds, timed against the base
    # step — keeps the <2% overhead contract measured across releases.
    # Runs AFTER the measurement, consuming the now-expendable buffers.
    health_probe = None
    if os.environ.get('MXTPU_BENCH_HEALTH', '1') != '0':
        health_probe = _measure_health_overhead(
            raw_step, masters, aux, vel, images, labels, key,
            dt / bench_steps)

    peak, kind = _peak_flops(devices[0])
    mfu = (flops_per_step * bench_steps / dt / peak) if peak else None
    if MODEL == 'transformer':
        tok_s = bench_steps * STEPS_PER_CALL * tokens_per_batch / dt
        _log('%.0f tokens/s over %d calls x %d steps (%.2fs); '
             'device=%s mfu=%s'
             % (tok_s, bench_steps, STEPS_PER_CALL, dt, kind,
                '%.1f%%' % (100 * mfu) if mfu is not None else 'n/a'))
        out = {
            'metric': 'transformer_train_throughput_bf16',
            'value': round(tok_s, 1),
            'unit': 'tokens/sec',
            'batch': int(images.shape[0]),
            'seq': int(images.shape[1]),
            'device': kind or platform,
            'platform': platform,
            'steps_per_call': STEPS_PER_CALL,
        }
        if mfu is not None:
            # the perf north star is 50% MFU; report progress against it
            out['vs_baseline'] = round(mfu / 0.5, 3)
    else:
        img_s = bench_steps * STEPS_PER_CALL * BATCH / dt
        _log('%.2f img/s over %d calls x %d steps (%.2fs); '
             'device=%s mfu=%s'
             % (img_s, bench_steps, STEPS_PER_CALL, dt, kind,
                '%.1f%%' % (100 * mfu) if mfu is not None else 'n/a'))
        out = {
            'metric': '%s_train_throughput_bf16' % MODEL,
            'value': round(img_s, 2),
            'unit': 'images/sec',
            'vs_baseline': round(img_s / BASELINE_IMG_S[MODEL], 3),
            'batch': BATCH,
            'device': kind or platform,
            'platform': platform,
            'steps_per_call': STEPS_PER_CALL,
        }
    if mfu is not None:
        out['mfu'] = round(mfu, 4)
    if ledger_final_loss is not None:
        # run-ledger metrics (ISSUE 15): tools/bench_diff.py gates
        # final_loss (a nan/diverged run must not bank as a healthy
        # throughput number); time_to_loss is ledger context, ungated.
        # bench_steps scales with measured throughput, so convergence
        # is only comparable between runs that trained the same number
        # of steps — final_loss_step lets bench_diff skip the gate
        # (visibly) on a mismatch instead of conflating a throughput
        # change with a convergence change
        out['final_loss'] = round(float(ledger_final_loss), 6)
        out['final_loss_step'] = \
            (WARMUP_STEPS + bench_steps) * STEPS_PER_CALL
    if ledger_time_to_loss is not None:
        out['time_to_loss'] = ledger_time_to_loss
    if health_probe:
        out['health'] = health_probe
    if temp_bytes:
        out['xla_temp_bytes'] = temp_bytes
    if step_analysis.get('live_bytes'):
        # steady-state per-dispatch footprint (args + temp + outputs
        # minus donated-alias bytes): the donation ledger's gated metric
        out['xla_live_bytes'] = step_analysis['live_bytes']
    if MIRROR:
        out['backward_mirror'] = MIRROR
    if served_from_cache is not None:
        # one number per run; 'served_from_cache' says whether THIS run
        # was the warm one (pair up across runs)
        out['compile_cache'] = {'dir': cache_dir,
                                'compile_s': round(compile_cold_s, 2),
                                'served_from_cache': served_from_cache}
    tel = _telemetry_breakdown(
        devices[0], step_ms=dt / (bench_steps * STEPS_PER_CALL) * 1e3)
    if tel:
        out['telemetry'] = tel
        # top-level copy of the gated metric (tools/bench_diff.py gates
        # goodput_pct: lower = regression) + the per-bucket breakdown
        # the diff renders next to it
        good = tel.get('goodput') or {}
        if good.get('goodput_pct') is not None:
            out['goodput_pct'] = good['goodput_pct']
            out['goodput'] = {'buckets': good.get('buckets'),
                              'badput_top': good.get('badput_top'),
                              'wall_s': good.get('wall_s')}
        # top-level copy of the headroom gate (bench_diff gates
        # mem_headroom_pct: lower = regression) — a program that grew
        # its footprint shows up as a shrunken safety margin here
        mem = tel.get('memory') or {}
        if mem.get('headroom_pct') is not None:
            out['mem_headroom_pct'] = mem['headroom_pct']
        # top-level copy of the wire-byte gate (bench_diff gates
        # bytes_on_wire_per_step: higher = regression)
        if tel.get('bytes_on_wire_per_step') is not None:
            out['bytes_on_wire_per_step'] = \
                tel['bytes_on_wire_per_step']
            if tel.get('compression_ratio') is not None:
                out['compression_ratio'] = tel['compression_ratio']
        # top-level copy of the step-phase gate (bench_diff gates
        # host_overhead_pct: higher = regression) — host-side work
        # creeping into the step shows up as a grown share here
        pb = tel.get('step_phase_breakdown') or {}
        if pb.get('host_pct') is not None:
            out['step_phase_breakdown'] = pb
            out['host_overhead_pct'] = pb['host_pct']
    # sharded-vs-replicated weight-update A/B (MXTPU_SHARDED_UPDATE):
    # only runs at dp > 1, and AFTER the telemetry fold above so the
    # probe model's compiles/programs/roofline never contaminate the
    # headline's telemetry block (the infer probe follows the same
    # rule); a failure must never cost the headline number
    sharded_ab = None
    if os.environ.get('MXTPU_BENCH_SHARDED_AB', '1') != '0':
        try:
            sharded_ab = run_sharded_update_ab(platform)
        except Exception as e:  # noqa: BLE001
            _log('sharded-update A/B failed (headline unaffected): %s' % e)
    # donation + BN-one-pass A/B (ISSUE 12): real Module.fit fused
    # window, pre-PR program vs shipped defaults — temp/live bytes,
    # throughput. Runs after the telemetry fold for
    # the same contamination rule.
    fused_ab = None
    if os.environ.get('MXTPU_BENCH_FUSED_AB', '1') != '0':
        try:
            fused_ab = run_fused_window_ab(platform)
        except Exception as e:  # noqa: BLE001
            _log('fused-window A/B failed (headline unaffected): %s' % e)
    if fused_ab:
        out['fused_window_ab'] = fused_ab
    # serving bench (ISSUE 13): closed-loop load against the in-process
    # continuous-batching plane; same contamination/failure rules as
    # the A/Bs above — the headline number is never at risk
    serving = None
    if os.environ.get('MXTPU_BENCH_SERVING', '1') != '0':
        try:
            serving = run_serving_bench(platform)
        except Exception as e:  # noqa: BLE001
            _log('serving bench failed (headline unaffected): %s' % e)
    if serving:
        out['serving_bench'] = serving
        # top-level copies of the gated/ledger metrics
        # (tools/bench_diff.py gates serving_p99_ms AND
        # serving_queue_wait_p50_ms at 10%)
        for k in ('serving_p50_ms', 'serving_p99_ms',
                  'serving_throughput_rps', 'pad_fraction',
                  'serving_queue_wait_p50_ms', 'serving_stage_p50_ms'):
            if serving.get(k) is not None:
                out[k] = serving[k]
    if sharded_ab:
        out['sharded_update_ab'] = sharded_ab
        # top-level copies of the gated/ledger metrics: per-device
        # optimizer-state bytes with the sharded update ON (the
        # tools/bench_diff.py gate reads this) and the update
        # collectives' per-step traffic
        if sharded_ab.get('opt_state_bytes_per_device') is not None:
            out['opt_state_bytes_per_device'] = \
                sharded_ab['opt_state_bytes_per_device']
        if sharded_ab.get('update_comm_bytes') is not None:
            out['update_comm_bytes'] = sharded_ab['update_comm_bytes']
    # inference tier: fused Module.predict vs the per-batch path, printed
    # BEFORE the training line (the LAST line stays the training
    # number). A failure here fails the run.
    if MODEL == 'resnet50':
        print(json.dumps(run_infer_bench(platform, kind or platform)),
              flush=True)
    print(json.dumps(out), flush=True)


if __name__ == '__main__':
    main()
