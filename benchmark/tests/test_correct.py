"""What decides ``correct``, shown to fail.

* the control: the plain reference with float8 operands in the program's
  place gives gaps over the limits (at a size a test can hold; the chip
  readings at the cells' own sizes are in PERF.md);
* the harness driven past its look for a chip, on the CPU at a tiny size
  in float32 (and a tenth of the learning rate: three steps on eight 32x32
  images at 0.1 are chaotic even in float32), comes out correct; with the timed path broken underneath
  (an optimizer step that returns its state unchanged; served answers
  shifted by one row) it comes out not correct.
"""
import io
import json
import os
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import compare_training, data, run, weights  # noqa: E402

TINY = ['--set', 'config.input_shape=[3,32,32]',
        '--set', 'config.builder.kwargs.image_shape=3,32,32',
        '--set', 'config.builder.kwargs.dtype=float32',
        '--set', 'config.optimizer.learning_rate=0.01',
        '--set', 'traffic.batch=8', '--set', 'traffic.steps_per_window=4',
        '--set', 'traffic.pool_rows=32', '--set', 'traffic.rate_rows_s=40',
        '--set', 'traffic.body_pool_rows=40']


def drive(cell):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(['--workload', cell, '--seed', str(2 ** 31 + 9),
                       '--seconds', '2', '--trace', '0'] + TINY,
                      require_chip=False)
    assert rc == 0
    print(out.getvalue())
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_float8_control_fails_the_training_limits():
    from benchmark.tests.test_flops import resnet50_shapes
    import jax
    shapes = resnet50_shapes()
    start = {k: np.asarray(v)
             for k, v in weights.make_params(shapes, 3).items()}
    pool, labels = data.image_pool(3, 32, (3, 64, 64), 1000)
    cut = lambda o: (pool[o:o + 16], labels[o:o + 16])      # noqa: E731
    batches = {'A': [cut(0)], 'B': [cut(1), cut(2), cut(3)]}
    opt = {'learning_rate': 0.1, 'momentum': 0.9, 'wd': 1e-4}
    dev = jax.devices()[:1]
    want = compare_training.follow('resnet50', start, batches, 4, opt, dev)
    got = compare_training.follow('resnet50', start, batches, 4, opt, dev,
                                  quant=True)
    g = compare_training.gaps(got, want)[0]
    assert any(g[k] > compare_training.LIMITS[k] for k in g), g


def test_fit_run_is_correct_and_a_frozen_step_is_not():
    assert drive('resnet50_fit')['correct'] is True
    from mxnet_tpu.ops import registry
    op = registry.get('sgd_mom_update')
    real = op.fn
    op.fn = lambda attrs, weight, grad, mom: (weight, mom)
    try:
        assert drive('resnet50_fit')['correct'] is False
    finally:
        op.fn = real


SERVE_CELL = {
    'workloads': [{'name': 'resnet50_serve_open', 'config': 'resnet50_v1',
                   'traffic': 'http_open_0p8knee', 'chips': 1, 'why': '-'}],
    'end_to_end': [{'name': n, 'unit': u, 'better': 'lower', 'bound': 0.1,
                    'source': 'host_clock',
                    'workloads': ['resnet50_serve_open']}
                   for n, u in (('serve_p50_ms', 'ms'), ('serve_p95_ms', 'ms'),
                                ('serve_samples_s', 'samples/s'))],
}


def test_serve_run_is_correct_and_a_shifted_answer_is_not(monkeypatch):
    # the serving cell is not in BENCHMARK.json yet (PERF.md section 7): the
    # test adds it to what the harness reads
    from benchmark import harness
    load = harness.load_json

    def with_serve_cell(path):
        d = load(path)
        if os.path.basename(path) == 'BENCHMARK.json':
            for key, extra in SERVE_CELL.items():
                d[key] = d[key] + extra
        return d
    monkeypatch.setattr(harness, 'load_json', with_serve_cell)
    assert drive('resnet50_serve_open')['correct'] is True
    from mxnet_tpu.serving.engine import ServingEngine
    real = ServingEngine.fetch_chunks

    def shifted(self, chunks, timings=None):
        return [np.roll(o, 1, axis=-1)
                for o in real(self, chunks, timings=timings)]
    ServingEngine.fetch_chunks = shifted
    try:
        assert drive('resnet50_serve_open')['correct'] is False
    finally:
        ServingEngine.fetch_chunks = real
