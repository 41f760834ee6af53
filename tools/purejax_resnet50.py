"""Pure-JAX ResNet-50 control + per-op time breakdown.

Two modes, one process each (it takes the chip itself, starts no child,
syncs via host fetch, and exits non-zero without a TPU):

  python tools/purejax_resnet50.py            # control train-step bench
  python tools/purejax_resnet50.py breakdown  # per-conv-op microbench

**control** builds a ResNet-50 v1 train step in *raw JAX only* — no
mxnet_tpu imports anywhere near the compute path — with the exact
bench.py configuration (batch 32 synthetic data, bf16 compute, fp32
masters, SGD momentum+wd, BN running-stat updates, lax.scan
steps-per-call fusion, donated buffers). If its img/s matches
bench.py's, the framework adds no overhead and the remaining MFU gap
is XLA's conv lowering on this chip; if it is materially faster, the
delta is framework overhead to hunt down.

**breakdown** enumerates every (conv config x {fwd, bwd_input,
bwd_filter}) in ResNet-50 batch-32 and times each *individually* on
the device (data-dependent scan chain so XLA cannot overlap
iterations), emitting per-op ms, FLOPs, and MFU: an op-by-op
measurement beside the sampled per-HLO profile (MXTPU_XPROF).

Output: one JSON line per result on stdout.
"""
import json
import os
import sys
import time

import numpy as np

BATCH = int(os.environ.get('MXTPU_BENCH_BATCH', '32'))
STEPS_PER_CALL = int(os.environ.get('MXTPU_BENCH_STEPS_PER_CALL', '32'))
PEAK_BF16 = {'v6': 918e12, 'v5p': 459e12, 'v5': 197e12,
             'v4': 275e12, 'v3': 123e12, 'v2': 45e12}


def _log(msg):
    print('[purejax] ' + msg, file=sys.stderr, flush=True)


def _peak(device):
    kind = (getattr(device, 'device_kind', '') or '').lower()
    for sub, p in PEAK_BF16.items():
        if sub in kind:
            return p, kind
    return 0.0, kind


# ---------------------------------------------------------------------------
# ResNet-50 v1 in raw JAX (NHWC compute, bf16, BN running stats)
# ---------------------------------------------------------------------------

STAGES = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
          (3, 512, 2048, 2)]


def init_params(rng):
    """params: list of (kind, array) fp32; kinds: conv HWIO, bn (gamma,
    beta), fc (w, b). Returns (params, bn_stats)."""
    params, stats = [], []

    def conv(kh, kw, cin, cout):
        std = (2.0 / (kh * kw * cin)) ** 0.5
        params.append(('conv', (rng.standard_normal(
            (kh, kw, cin, cout)) * std).astype(np.float32)))

    def bn(c):
        params.append(('gamma', np.ones((c,), np.float32)))
        params.append(('beta', np.zeros((c,), np.float32)))
        stats.append(np.zeros((c,), np.float32))   # mean
        stats.append(np.ones((c,), np.float32))    # var

    conv(7, 7, 3, 64)
    bn(64)
    cin = 64
    for n_blocks, mid, cout, stride in STAGES:
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            if b == 0:
                conv(1, 1, cin, cout)   # projection shortcut
                bn(cout)
            conv(1, 1, cin, mid)
            bn(mid)
            conv(3, 3, mid, mid)        # stride s
            bn(mid)
            conv(1, 1, mid, cout)
            bn(cout)
            cin = cout
    std = (2.0 / 2048) ** 0.5
    params.append(('fc_w', (rng.standard_normal(
        (2048, 1000)) * std).astype(np.float32)))
    params.append(('fc_b', np.zeros((1000,), np.float32)))
    return params, stats


def forward(param_arrays, kinds, stats, x, train=True, momentum=0.9):
    """x: (N,H,W,C) bf16. Returns (logits fp32, new_stats)."""
    import jax
    import jax.numpy as jnp

    it = iter(param_arrays)
    sit = iter(stats)
    new_stats = []

    def conv(x, w, stride):
        return jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), (stride, stride),
            [((w.shape[0] - 1) // 2, w.shape[0] // 2)] * 2,
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))

    def bnorm(x):
        gamma, beta = next(it), next(it)
        rmean, rvar = next(sit), next(sit)
        if train:
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, (0, 1, 2))
            var = jnp.var(xf, (0, 1, 2))
            new_stats.append(momentum * rmean + (1 - momentum) * mean)
            new_stats.append(momentum * rvar + (1 - momentum) * var)
        else:
            mean, var = rmean, rvar
            new_stats.extend([rmean, rvar])
        inv = jax.lax.rsqrt(var + 1e-5) * gamma
        return ((x.astype(jnp.float32) - mean) * inv + beta).astype(x.dtype)

    x = conv(x, next(it), 2)
    x = jax.nn.relu(bnorm(x))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    for n_blocks, mid, cout, stride in STAGES:
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            if b == 0:
                sc = conv(x, next(it), s)
                sc = bnorm(sc)
            else:
                sc = x
            # v1 semantics (matches the framework's BottleneckV1,
            # gluon/model_zoo/vision/resnet.py: stride on the FIRST
            # 1x1 conv, not the 3x3 — v1.5 would be ~12% more FLOPs)
            h = jax.nn.relu(bnorm(conv(x, next(it), s)))
            h = jax.nn.relu(bnorm(conv(h, next(it), 1)))
            h = bnorm(conv(h, next(it), 1))
            x = jax.nn.relu(h + sc)
    x = jnp.mean(x.astype(jnp.float32), (1, 2))
    return x @ next(it) + next(it), new_stats


def control_bench():
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    params, stats = init_params(rng)
    kinds = [k for k, _ in params]
    masters = tuple(jnp.asarray(a) for _, a in params)
    stats = tuple(jnp.asarray(s) for s in stats)
    vel = tuple(jnp.zeros_like(m) for m in masters)
    images = jnp.asarray(rng.standard_normal((BATCH, 224, 224, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, (BATCH,)), jnp.int32)
    lr, mom, wd = 0.1, 0.9, 1e-4

    def one_step(carry, _):
        masters, stats, vel = carry

        def loss_fn(bf16):
            logits, new_stats = forward(bf16, kinds, stats, images)
            logits = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
            return jnp.mean(lse - gold), new_stats

        bf16 = tuple(m.astype(jnp.bfloat16) for m in masters)
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(bf16)
        new_m, new_v = [], []
        for m, g, v in zip(masters, grads, vel):
            g32 = g.astype(jnp.float32) + wd * m
            nv = mom * v + g32
            new_m.append(m - lr * nv)
            new_v.append(nv)
        return (tuple(new_m), tuple(new_stats), tuple(new_v)), loss

    def step(masters, stats, vel):
        (m, s, v), losses = jax.lax.scan(
            one_step, (masters, stats, vel), None, length=STEPS_PER_CALL)
        return m, s, v, losses[-1]

    t = time.perf_counter()
    jstep = jax.jit(step, donate_argnums=(0, 1, 2))
    compiled = jstep.lower(masters, stats, vel).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops_per_step = float(cost.get('flops', 0.0)) * STEPS_PER_CALL
    _log('compile %.1fs, flops/dispatch=%.3e'
         % (time.perf_counter() - t, flops_per_step))

    t = time.perf_counter()
    for _ in range(3):
        masters, stats, vel, loss = compiled(masters, stats, vel)
    loss_v = float(np.asarray(loss))   # host fetch = true barrier
    warm = time.perf_counter() - t
    _log('warmup 3 calls %.1fs loss=%.3f' % (warm, loss_v))

    calls = int(min(60, max(8, 15.0 / max(1e-3, warm / 3))))
    t0 = time.perf_counter()
    for _ in range(calls):
        masters, stats, vel, loss = compiled(masters, stats, vel)
    float(np.asarray(loss))
    dt = time.perf_counter() - t0

    dev = jax.devices()[0]
    peak, kind = _peak(dev)
    img_s = calls * STEPS_PER_CALL * BATCH / dt
    mfu = flops_per_step * calls / dt / peak if peak else None
    out = {'metric': 'purejax_resnet50_control', 'value': round(img_s, 2),
           'unit': 'images/sec', 'batch': BATCH,
           'steps_per_call': STEPS_PER_CALL, 'device': kind,
           'platform': dev.platform}
    if mfu is not None:
        out['mfu'] = round(mfu, 4)
    print(json.dumps(out), flush=True)


# ---------------------------------------------------------------------------
# Per-op breakdown
# ---------------------------------------------------------------------------

def conv_configs():
    """Every conv in ResNet-50 batch-BATCH as (count, H, W, cin, cout,
    k, stride) — H,W are the *input* spatial dims."""
    cfgs = {}

    def add(h, cin, cout, k, s):
        key = (h, cin, cout, k, s)
        cfgs[key] = cfgs.get(key, 0) + 1

    add(224, 3, 64, 7, 2)
    h, cin = 56, 64
    for n_blocks, mid, cout, stride in STAGES:
        for b in range(n_blocks):
            s = stride if b == 0 else 1
            if b == 0:
                add(h, cin, cout, 1, s)
            # v1: stride rides the first 1x1 (see forward())
            add(h, cin, mid, 1, s)
            add(h // s, mid, mid, 3, 1)
            add(h // s, mid, cout, 1, 1)
            cin = cout
            if b == 0:
                h //= s
    return [(c,) + k for k, c in cfgs.items()]


def breakdown():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    peak, kind = _peak(dev)
    rng = np.random.RandomState(0)
    rows = []
    R1, R2 = 32, 160

    def timed(fn, *args):
        """Per-rep time via a two-point fit: run a data-dependent scan
        chain at lengths R1 and R2 and take the slope
        (T2 - T1) / (R2 - R1). Each call pays a roughly constant
        dispatch+fetch cost on the host; differencing cancels it
        exactly where dividing by REPS leaves it as a floor. Returns
        ONLY a scalar to the host (a full-output fetch would dwarf the
        op), and chains
        iterations with a 1e-30-scaled tap — numerically identity in
        bf16 but not symbolically zero, so XLA cannot fold the
        dependency away and hoist the op out of the loop."""
        def chain_of(reps):
            def chain(args):
                def body(c, _):
                    out = fn(*c)
                    # sum over the WHOLE output: a sliced tap lets
                    # XLA slice the conv itself down to one column
                    # (observed as >100% MFU); the full reduction is
                    # fused into the conv epilogue
                    tap = jnp.sum(out.astype(jnp.float32)) * 1e-30
                    return tuple(a * (1 + tap).astype(a.dtype)
                                 if i == 0 else a
                                 for i, a in enumerate(c)), ()
                c, _ = jax.lax.scan(body, args, None, length=reps)
                return jnp.sum(fn(*c).astype(jnp.float32))
            comp = jax.jit(chain).lower(args).compile()
            float(np.asarray(comp(args)))   # warmup + barrier
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                float(np.asarray(comp(args)))
                ts.append(time.perf_counter() - t0)
            return sorted(ts)[1]
        return max(1e-9, (chain_of(R2) - chain_of(R1)) / (R2 - R1))

    for count, h, cin, cout, k, s in conv_configs():
        x = jnp.asarray(rng.standard_normal((BATCH, h, h, cin)),
                        jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * 0.05,
                        jnp.bfloat16)
        pad = [((k - 1) // 2, k // 2)] * 2

        def conv(x, w, stride=s, pad=pad):
            return jax.lax.conv_general_dilated(
                x, w, (stride, stride), pad,
                dimension_numbers=('NHWC', 'HWIO', 'NHWC'))

        ho = h // s
        flops = 2.0 * BATCH * ho * ho * cin * cout * k * k
        y = jnp.asarray(rng.standard_normal((BATCH, ho, ho, cout)),
                        jnp.bfloat16)

        def bwd_in(y, w, x=x):
            _, vjp = jax.vjp(lambda xx: conv(xx, w), x)
            return vjp(y)[0]

        def bwd_w(y, x, w=w):
            _, vjp = jax.vjp(lambda ww: conv(x, ww), w)
            return vjp(y)[0]

        for mode, fn, args in (('fwd', conv, (x, w)),
                               ('bwd_input', bwd_in, (y, w)),
                               ('bwd_filter', bwd_w, (y, x))):
            dt = timed(fn, *args)
            mfu = flops / dt / peak if peak else None
            rows.append({'op': 'conv', 'mode': mode, 'count': count,
                         'in_hw': h, 'cin': cin, 'cout': cout, 'k': k,
                         'stride': s, 'ms': round(dt * 1e3, 4),
                         'gflops': round(flops / 1e9, 2),
                         'mfu': round(mfu, 4) if mfu is not None else None,
                         'total_ms': round(dt * 1e3 * count, 4)})
            _log('%s k=%d s=%d %dx%d %d->%d x%d: %.3f ms  mfu=%.1f%%'
                 % (mode, k, s, h, h, cin, cout, count, dt * 1e3,
                    100 * (mfu or 0)))

    # FC layer fwd+bwd for completeness
    x = jnp.asarray(rng.standard_normal((BATCH, 2048)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((2048, 1000)) * 0.02, jnp.bfloat16)
    dt = timed(lambda x, w: x @ w, x, w)
    rows.append({'op': 'fc', 'mode': 'fwd', 'count': 1, 'ms':
                 round(dt * 1e3, 4),
                 'gflops': round(2.0 * BATCH * 2048 * 1000 / 1e9, 3)})

    conv_rows = [r for r in rows if r['op'] == 'conv']
    total = {m: sum(r['total_ms'] for r in conv_rows if r['mode'] == m)
             for m in ('fwd', 'bwd_input', 'bwd_filter')}
    summary = {'metric': 'resnet50_conv_op_breakdown', 'batch': BATCH,
               'device': kind, 'sum_ms_per_step': {
                   k: round(v, 3) for k, v in total.items()},
               'worst_bwd_filter': sorted(
                   (r for r in conv_rows if r['mode'] == 'bwd_filter'),
                   key=lambda r: -r['total_ms'])[:5],
               'rows': rows}
    print(json.dumps(summary), flush=True)


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else 'control'
    import jax
    if jax.devices()[0].platform != 'tpu':
        _log('no TPU device is visible (%s); nothing is measured'
             % jax.devices())
        sys.exit(2)
    _log('backend: %s' % jax.devices())
    if mode == 'control':
        control_bench()
    else:
        breakdown()


if __name__ == '__main__':
    main()
