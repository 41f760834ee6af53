"""On-chip inference scoring tier (BASELINE.md table 1).

The reference's `benchmark_score.py` table (docs/how_to/perf.md:115-146)
scores AlexNet / VGG-16 / Inception-v3 / ResNet-50 / ResNet-152 at
batch 1 and 32. This tool scores the same model-zoo networks on the
TPU (host-fetch barrier, scan-fused repeats so the host's per-dispatch
cost cannot cap a 1-3 ms forward). One process: it takes the chip itself
and starts no child, and without a TPU it exits non-zero:

    python tools/score_bench.py                 # full table
    python tools/score_bench.py --models resnet50_v1 --batches 32

Forward-only inference in bfloat16 (the TPU inference dtype; the MXU
has no fp32 peak worth scoring against) on synthetic data via the
model zoo's hybridized graphs — the same `_GraphProgram` trace a user
gets from `net.hybridize()`. One JSON line per (model, batch), then a
summary line with the P100 baseline ratios.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# reference table, P100 column (docs/how_to/perf.md:115-146)
P100 = {
    ('alexnet', 1): 624.84, ('alexnet', 32): 4883.77,
    ('vgg16', 1): 294.6, ('vgg16', 32): 854.4,
    ('inception-bn', 1): 139.82, ('inception-bn', 32): 1197.74,
    ('inceptionv3', 1): 80.17, ('inceptionv3', 32): 493.72,
    ('resnet50_v1', 1): 162.27, ('resnet50_v1', 32): 713.17,
    ('resnet152_v1', 1): 58.99, ('resnet152_v1', 32): 294.17,
}
# pretrained-model speed table, single K80 batch 32
# (example/image-classification/README.md:147-157)
K80_PRETRAINED = {
    ('inception-bn', 32): 152.0,
    ('resnet18_v1', 32): 185.0, ('resnet34_v1', 32): 172.0,
    ('resnet50_v1', 32): 109.0, ('resnet101_v1', 32): 78.0,
    ('resnet152_v1', 32): 57.0,
}
DEFAULT_MODELS = ['alexnet', 'vgg16', 'inception-bn', 'inceptionv3',
                  'resnet18_v1', 'resnet34_v1', 'resnet50_v1',
                  'resnet101_v1', 'resnet152_v1']


def _log(msg):
    print('[score] ' + msg, file=sys.stderr, flush=True)


def build_forward(model, batch):
    """(compiled_chain, reps, flops_per_fwd) for a scan of ``reps``
    data-chained bf16 forwards of the zoo model."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.executor import _GraphProgram

    image = 299 if model == 'inceptionv3' else 224
    shape = (batch, 3, image, image)
    if model == 'inception-bn':
        # symbol-defined network (examples/image-classification/symbols)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            'examples', 'image-classification'))
        from symbols.inception_bn import get_symbol
        # SoftmaxOutput's label input is unused in inference mode
        sym = get_symbol(num_classes=1000,
                         image_shape='3,%d,%d' % (image, image))
    else:
        net = vision.get_model(model, classes=1000)
        net.initialize(mx.init.Xavier())
        net.hybridize()
        _, sym = net._get_graph(
            type('P', (), {'shape': shape, 'context': None})())
    prog = _GraphProgram(sym)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=shape)
    runner = prog.make_runner()
    rng = np.random.RandomState(0)

    def init(name, s):
        if 'gamma' in name or 'var' in name:
            return np.ones(s, np.float32)
        if 'beta' in name or 'bias' in name or 'mean' in name:
            return np.zeros(s, np.float32)
        fan = int(np.prod(s[1:])) if len(s) > 1 else s[0]
        return (rng.standard_normal(s) * (2.0 / max(1, fan)) ** 0.5) \
            .astype(np.float32)

    data_idx = prog.arg_names.index('data')
    args = [jnp.asarray(init(n, s)).astype(jnp.bfloat16)
            for n, s in zip(prog.arg_names, arg_shapes)]
    aux = tuple(jnp.asarray(init(n, s)).astype(jnp.bfloat16)
                for n, s in zip(prog.aux_names, aux_shapes))
    x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    key = jax.random.PRNGKey(0)

    # reps sized so one chain call is ~1-2 s of device time (ResNet-50
    # b32 measures ~6 ms/forward; scale by batch and image area)
    est_ms = 6.0 * batch / 32.0 * (image / 224.0) ** 2
    reps = int(np.clip(1500.0 / est_ms, 16, 512))

    def chain(args_t, aux_t, x):
        def body(c, _):
            xx = c
            full = list(args_t)
            full[data_idx] = xx
            outs, _ = runner(tuple(full), aux_t, key, False)
            # 1e-30 tap: numerically identity, but keeps iterations
            # data-dependent so XLA cannot CSE/hoist the forward
            tap = jnp.sum(outs[0].astype(jnp.float32)) * 1e-30
            return (xx * (1 + tap).astype(xx.dtype)), ()
        c, _ = jax.lax.scan(body, x, None, length=reps)
        full = list(args_t)
        full[data_idx] = c
        outs, _ = runner(tuple(full), aux_t, key, False)
        return jnp.sum(outs[0].astype(jnp.float32))

    jfn = jax.jit(chain)
    lowered = jfn.lower(tuple(args), aux, x)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    # XLA cost analysis counts a scan body ONCE regardless of trip
    # count (verified in bench.py): total = 1 body + 1 final forward
    flops = float(cost.get('flops', 0.0)) / 2.0
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, (list, tuple)):
            ma = ma[0]
        n_param = sum(int(np.prod(s)) for n, s in
                      zip(prog.arg_names, arg_shapes)
                      if n not in ('data', 'softmax_label'))
        n_param += sum(int(np.prod(s)) for s in aux_shapes)
        mem = {'xla_temp_bytes': int(ma.temp_size_in_bytes),
               'param_bytes': 2 * n_param}   # bf16 resident weights
    except Exception:  # noqa: BLE001
        mem = {}
    return compiled, tuple(args), aux, x, reps, flops, mem


def score(model, batch, peak):
    import jax
    t = time.perf_counter()
    compiled, args, aux, x, reps, flops, mem = build_forward(model, batch)
    _log('%s b%d: compile %.1fs (reps=%d)'
         % (model, batch, time.perf_counter() - t, reps))
    float(np.asarray(compiled(args, aux, x)))   # warmup + barrier
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(np.asarray(compiled(args, aux, x)))
        ts.append(time.perf_counter() - t0)
    dt = sorted(ts)[1] / (reps + 1)
    ips = batch / dt
    mfu = flops / dt / peak if peak else None
    row = {'metric': 'benchmark_score', 'model': model, 'batch': batch,
           'value': round(ips, 2), 'unit': 'images/sec',
           'dtype': 'bfloat16'}
    if (model, batch) in P100:
        row['vs_p100'] = round(ips / P100[(model, batch)], 2)
    if (model, batch) in K80_PRETRAINED:
        row['vs_k80_pretrained'] = round(
            ips / K80_PRETRAINED[(model, batch)], 2)
    if mfu is not None:
        row['mfu'] = round(mfu, 4)
    row.update(mem)
    print(json.dumps(row), flush=True)
    _log('%s b%d: %.1f img/s (%.2fx P100)'
         % (model, batch, ips, row.get('vs_p100', 0)))
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--models', default=','.join(DEFAULT_MODELS))
    ap.add_argument('--batches', default='1,32')
    args = ap.parse_args()
    import jax
    from bench import _peak_flops   # shared device-kind -> peak table
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        _log('no TPU device is visible (%s); nothing is scored' % dev)
        sys.exit(2)
    peak, _kind = _peak_flops(dev)
    _log('backend: %s' % dev)
    rows = []
    for model in args.models.split(','):
        for b in (int(x) for x in args.batches.split(',')):
            try:
                rows.append(score(model, b, peak))
            except Exception as e:  # noqa: BLE001
                _log('%s b%d FAILED: %s' % (model, b, e))
    ok = [r for r in rows if 'vs_p100' in r]
    k80 = [r for r in rows if 'vs_k80_pretrained' in r]
    summary = {'metric': 'benchmark_score_summary',
               'value': round(min((r['vs_p100'] for r in ok), default=0.0),
                              2),
               'unit': 'min_vs_p100',
               'all_above_p100': bool(ok) and all(
                   r['vs_p100'] >= 1.0 for r in ok),
               'all_above_k80_pretrained': bool(k80) and all(
                   r['vs_k80_pretrained'] >= 1.0 for r in k80),
               'rows': rows}
    print(json.dumps(summary), flush=True)


if __name__ == '__main__':
    main()
