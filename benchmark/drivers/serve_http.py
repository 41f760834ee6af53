"""Driver ``serve_http``: a configuration served over loopback HTTP.

Seeded weights -> ``mx.model.save_checkpoint`` ->
``ServingEngine.from_checkpoint(context=mx.tpu(0), max_batch=...)`` ->
``warmup`` (the whole bucket ladder: coalescing makes every row count up
to the largest) -> ``DynamicBatcher`` (default wait) ->
``serving.http.start_server(port=0)``. The load comes from a child
process (``benchmark/loadgen.py``) that never imports JAX: an open loop
on the schedule the traffic file describes, each request timed from the
instant it was due.

After the window the engine is freed and the plain reference computes the
inference-mode forward pass, float32, on a seeded sample of the requests
that were answered, the largest among them; compared is the worst row's
distance between served and reference log-probabilities (each centred over
the classes, so that it is a distance of logits), relative to the
reference's norm.

``setup`` / ``window`` / ``finish`` are separate so that
``benchmark/tools/sweep_serve.py`` can offer several rates to one warm
server.
"""
import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import harness, loadgen, weights
from benchmark.reference import convnets

# Worst row's relative distance of centred log-probabilities, served against
# the float32 reference. Set from chip readings (PERF.md section 2): above
# the largest that sound runs gave, below the smallest the float8 control
# gave.
LIMIT_LOGIT_GAP = 0.02
TRACE_SLICE_S = 2.0


def spawn_generator(ctx, traffic, image_shape, classes):
    """The load generator as a child process (it never imports JAX); it
    prints READY once its request bodies are built."""
    traffic_file = os.path.join(ctx.workdir, 'traffic.json')
    with open(traffic_file, 'w') as f:
        json.dump(traffic, f)
    return subprocess.Popen(
        [sys.executable, os.path.join(harness.HERE, 'loadgen.py'),
         '--traffic', traffic_file, '--seed', str(ctx.seed),
         '--image-shape', ','.join(str(v) for v in image_shape),
         '--classes', str(classes)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)


def await_ready(child):
    ready = child.stdout.readline().strip()
    if ready != 'READY':
        raise RuntimeError('load generator said %r' % ready)


def quit_generator(child):
    try:
        child.stdin.write('QUIT\n')
        child.stdin.flush()
        child.wait(timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        child.kill()
        child.wait()


def setup(ctx):
    import mxnet_tpu as mx
    from mxnet_tpu.serving import DynamicBatcher, ServingEngine
    from mxnet_tpu.ndarray.ndarray import from_jax
    from mxnet_tpu.serving.http import start_server

    cfg, tr = ctx.config, ctx.traffic
    image_shape, classes = tuple(cfg['input_shape']), int(cfg['classes'])
    max_batch = int(tr['max_batch'])

    # the generator builds its bodies while the engine warms
    child = spawn_generator(ctx, tr, image_shape, classes)

    sym = harness.build_symbol(cfg)
    _, aux_names, shapes = harness.symbol_shapes(sym, max_batch,
                                                 image_shape)
    made = weights.make_params(shapes, ctx.seed, cfg.get('init'))
    first = mx.tpu(0)
    prefix = os.path.join(ctx.workdir, 'served')
    mx.model.save_checkpoint(
        prefix, 1, sym,
        {n: from_jax(made[n], first) for n in shapes if n not in aux_names},
        {n: from_jax(made[n], first) for n in aux_names})
    params = {n: np.asarray(v) for n, v in made.items()}    # for the reference
    del made
    engine = ServingEngine.from_checkpoint(
        prefix, 1, [('data', image_shape)], context=mx.tpu(0),
        max_batch=max_batch)
    warmed = engine.warmup()
    ctx.log('engine warm: %d bucket programs %s' % (warmed, engine.buckets))
    batcher = DynamicBatcher(engine)
    inner = {}
    if ctx.trace:
        # the benchmark's own span around the call into the batcher
        real = batcher.predict

        def timed(arrays, timeout=None, trace_id=None):
            t = time.perf_counter()
            out = real(arrays, timeout=timeout, trace_id=trace_id)
            inner[trace_id] = (time.perf_counter() - t) * 1e3
            return out
        batcher.predict = timed
    server = start_server(engine, batcher, port=0)
    await_ready(child)
    return {'child': child, 'server': server, 'engine': engine,
            'batcher': batcher, 'inner': inner, 'params': params,
            'image_shape': image_shape, 'classes': classes}


def window(ctx, s, rate, seconds, capture=None):
    """Offer `rate` rows/s for `seconds`; returns the generator's
    records."""
    out = os.path.join(ctx.workdir, 'requests.json')
    s['child'].stdin.write('GO %d %r %r %s\n'
                           % (s['server'].port, rate, seconds, out))
    s['child'].stdin.flush()
    tracer = None
    if capture is not None:
        def slice_():
            time.sleep(0.4 * seconds)
            capture.start()
            time.sleep(min(TRACE_SLICE_S, 0.4 * seconds))
            capture.stop()
        tracer = threading.Thread(target=slice_)
        tracer.start()
    done = s['child'].stdout.readline().strip()
    if tracer is not None:
        tracer.join()
    if done != 'DONE':
        raise RuntimeError('load generator said %r' % done)
    with open(out) as f:
        return json.load(f)


def finish(s):
    quit_generator(s['child'])
    s['server'].stop()


def summarise(records, seconds, limit_ms, timeout_s):
    """The end-to-end numbers of one window."""
    reqs = records['requests']
    lat = [(r['done'] - r['due']) * 1e3 if r['ok'] else timeout_s * 1e3
           for r in reqs]
    good_rows = sum(r['rows'] for r, ms in zip(reqs, lat)
                    if r['ok'] and ms <= limit_ms)
    return {'serve_p50_ms': harness.percentile(lat, 50),
            'serve_p95_ms': harness.percentile(lat, 95),
            'serve_samples_s': good_rows / seconds,
            'requests': len(reqs),
            'failed': sum(1 for r in reqs if not r['ok']),
            'offered_rows': sum(r['rows'] for r in reqs),
            'late_p95_ms': 1e3 * harness.percentile(
                [r['sent'] - r['due'] for r in reqs], 95)}


def logit_gap(served, ref_log_probs):
    """Worst row's ||c_served - c_ref|| / ||c_ref||, c the log-probabilities
    centred over the classes."""
    got = np.log(np.maximum(np.asarray(served, np.float64), 1e-300))
    want = np.asarray(ref_log_probs, np.float64)
    got -= got.mean(axis=1, keepdims=True)
    want = want - want.mean(axis=1, keepdims=True)
    return float(np.max(np.linalg.norm(got - want, axis=1)
                        / np.linalg.norm(want, axis=1)))


def sampled(ctx, s, records):
    """(images, served answers) of the kept requests that were answered."""
    pool = loadgen.body_pool(ctx.traffic, ctx.seed, s['image_shape'])
    images, answers = [], []
    for r in records['requests']:
        if r.get('answer') is not None and r['ok']:
            v = loadgen.variant_of(int(r['id']), ctx.traffic)
            images.append(loadgen.body_rows(pool, r['rows'], v))
            answers.append(np.asarray(r['answer'], np.float32))
    return np.concatenate(images), np.concatenate(answers)


def reference_log_probs(cfg, params, images, quant=False):
    import jax.numpy as jnp
    model = cfg['reference'].split(':')[1]
    p = {k: jnp.asarray(v) for k, v in params.items()}
    out = []
    for i in range(0, len(images), 32):
        # blocks of 32 rows, the last padded: one shape, so one program,
        # which the compile cache holds after the first run
        block = np.zeros((32,) + images.shape[1:], np.float32)
        rows = images[i:i + 32]
        block[:len(rows)] = rows
        out.append(np.asarray(convnets.predict_log_probs(
            model, p, jnp.asarray(block), quant))[:len(rows)])
    return np.concatenate(out)


def run(ctx):
    tr = ctx.traffic
    s = setup(ctx)
    try:
        capture = harness.Capture(ctx.workdir) if ctx.trace else None
        compiles0 = ctx.compiles.compiles
        setup_s = time.perf_counter() - ctx.t0
        records = window(ctx, s, float(tr['rate_rows_s']), ctx.seconds,
                         capture)
        compiled_inside = ctx.compiles.compiles - compiles0
        batcher = s['batcher']
        serve = {'requests': records['requests'],
                 'batcher_ms': dict(s['inner']),
                 'queue_wait_ms': list(batcher.queue_wait_log),
                 'dispatch_log': [list(d) for d in batcher.dispatch_log]}
    finally:
        finish(s)
    memory_peak = harness.memory_peak(ctx.devices)
    if ctx.keep:
        os.makedirs(ctx.keep, exist_ok=True)
        with open(os.path.join(ctx.keep, 'requests_%d.json' % ctx.seed),
                  'w') as f:
            json.dump([{k: r[k] for k in ('rows', 'due', 'sent', 'done',
                                          'ok')}
                       for r in records['requests']], f)
    e2e = summarise(records, ctx.seconds, float(tr['latency_limit_ms']),
                    float(tr['timeout_s']))
    ctx.log('%d requests (%d rows) offered at %g rows/s for %gs; %d failed;'
            ' generator late p95 %.2f ms'
            % (e2e['requests'], e2e['offered_rows'], tr['rate_rows_s'],
               ctx.seconds, e2e['failed'], e2e['late_p95_ms']))
    for r in records['requests']:
        if not r['ok']:
            ctx.log('failed request %s: %d rows, due %.3fs, sent %.3fs, '
                    'done %.3fs, status %s, %s'
                    % (r['id'], r['rows'], r['due'], r['sent'], r['done'],
                       r['status'], r.get('error', 'wrong answer')))
    log = serve['dispatch_log']
    if log:
        ctx.log('last %d dispatches: %.2f rows each, %.1f%% padding'
                % (len(log), sum(d[0] for d in log) / len(log),
                   100.0 * (1 - sum(d[0] for d in log)
                            / max(1, sum(d[1] for d in log)))))

    run_data = {'cell': ctx.cell.name, 'config': ctx.config, 'traffic': tr,
                'chips': 1, 'device_kind': ctx.devices[0].device_kind,
                'serve': serve, 'memory_peak_bytes': memory_peak}
    if capture is not None:
        run_data['trace'] = capture.reduce(1)

    chk = ctx.checks
    chk.equal('requests that failed', e2e['failed'], 0)
    chk.equal('compilations after warm-up', compiled_inside, 0)
    images, answers = sampled(ctx, s, records)
    params = s['params']
    s.clear()
    gc.collect()
    t = time.perf_counter()
    want = reference_log_probs(ctx.config, params, images)
    gap = logit_gap(answers, want)
    ctx.log('reference forward of %d sampled rows: %.1fs'
            % (len(images), time.perf_counter() - t))
    chk.at_most('served logits gap, worst row', gap, LIMIT_LOGIT_GAP)

    return {'setup_s': setup_s,
            'end_to_end': {k: e2e[k] for k in
                           ('serve_p50_ms', 'serve_p95_ms',
                            'serve_samples_s')},
            'attempted': e2e['requests'], 'failed': e2e['failed'],
            'memory_peak_bytes': memory_peak, 'run': run_data}
