"""Fused inference/eval fast path (module/fused_eval.py).

The contract under test: with MXTPU_FUSED_EVAL on (default), score /
predict / iter_predict compile W forward steps per device call yet
produce IDENTICAL metric values, merged outputs, callback cadence, and
pad/num_batch handling to the reference per-batch loop (reference
base_module.py:204/292), falling back silently when the module/metric
combination cannot fuse — mirroring tests/unittest/test_fused_fit.py
for the read-only half of the API.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metric as metric_mod
from mxnet_tpu.module.fused_eval import FusedEvalLoop


def _mlp_mod(n=56, batch=8, ctx=None, n_classes=4, seed=7,
             for_training=False):
    mx.random.seed(seed)
    np.random.seed(seed)
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=32, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu')
    fc2 = mx.sym.FullyConnected(act, num_hidden=n_classes, name='fc2')
    out = mx.sym.SoftmaxOutput(fc2, name='softmax')
    X = np.random.randn(n, 10).astype(np.float32)
    y = (np.random.rand(n) * n_classes).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False,
                           label_name='softmax_label')
    mod = mx.mod.Module(out, context=ctx or mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=for_training)
    mod.init_params()
    return mod, it


def _run(fn, fused):
    os.environ['MXTPU_FUSED_EVAL'] = '1' if fused else '0'
    try:
        return fn()
    finally:
        os.environ.pop('MXTPU_FUSED_EVAL', None)


@pytest.mark.parametrize('metric', ['acc', 'ce', 'mse'])
def test_fused_score_matches_per_batch(metric):
    """Identical metric value + identical per-batch callback trajectory
    across stats mode (acc/ce) and stacked-output host mode (mse)."""
    def run():
        mod, it = _mlp_mod()
        traj = []
        res = mod.score(it, metric,
                        batch_end_callback=lambda p: traj.append(
                            (p.nbatch,
                             p.eval_metric.get_name_value()[0][1])))
        return res, traj
    (res_f, traj_f) = _run(run, True)
    (res_u, traj_u) = _run(run, False)
    assert [n for n, _ in res_f] == [n for n, _ in res_u]
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)
    assert [n for n, _ in traj_f] == [n for n, _ in traj_u] \
        == list(range(7))
    np.testing.assert_allclose([v for _, v in traj_f],
                               [v for _, v in traj_u],
                               rtol=1e-6, atol=1e-7)


def test_fused_score_composite_and_topk():
    def run():
        comp = metric_mod.CompositeEvalMetric()
        comp.add('acc')
        comp.add(metric_mod.TopKAccuracy(top_k=3))
        comp.add('ce')
        mod, it = _mlp_mod(n=48, batch=6, n_classes=6)
        return mod.score(it, comp)
    res_f = _run(run, True)
    res_u = _run(run, False)
    assert [n for n, _ in res_f] == [n for n, _ in res_u]
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize('merge', [True, False])
def test_fused_predict_matches_per_batch(merge):
    def run():
        mod, it = _mlp_mod()
        out = mod.predict(it, merge_batches=merge)
        if merge:
            return [out.asnumpy()]
        return [o.asnumpy() for outs in out for o in outs]
    outs_f = _run(run, True)
    outs_u = _run(run, False)
    assert len(outs_f) == len(outs_u)
    for a, b in zip(outs_f, outs_u):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_fused_predict_pad_inside_window():
    """60 samples / batch 8 = 8 batches, last pad=4 — with W=4 the
    padded batch lands INSIDE a full window, not the tail: the merged
    output must still trim the pad rows exactly like the reference."""
    def run():
        mod, it = _mlp_mod(n=60)
        return mod.predict(it).asnumpy()
    a_f = _run(run, True)
    a_u = _run(run, False)
    assert a_f.shape == (60, 4) and a_u.shape == (60, 4)
    np.testing.assert_allclose(a_f, a_u, rtol=1e-5, atol=1e-6)


def test_fused_iter_predict_pad_and_nbatch():
    def run():
        mod, it = _mlp_mod(n=60)
        return [(nb, [o.asnumpy() for o in outs], b.pad)
                for outs, nb, b in mod.iter_predict(it)]
    its_f = _run(run, True)
    its_u = _run(run, False)
    assert [i[0] for i in its_f] == [i[0] for i in its_u]
    assert [i[2] for i in its_f] == [i[2] for i in its_u]
    for (_, outs_f, _), (_, outs_u, _) in zip(its_f, its_u):
        for a, b in zip(outs_f, outs_u):
            assert a.shape == b.shape   # pad trimmed identically
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('num_batch', [2, 5, 7, 100])
def test_fused_num_batch_truncation(num_batch):
    """num_batch below one window (all tail), mid-window, at the batch
    count, and beyond it — score and predict both stop at the same
    point as the reference loop."""
    def run():
        mod, it = _mlp_mod(n=64, batch=8)   # 8 batches, W=4 on CPU
        res = mod.score(it, 'acc', num_batch=num_batch)
        out = mod.predict(it, num_batch=num_batch)
        return res, out.asnumpy()
    (res_f, out_f) = _run(run, True)
    (res_u, out_u) = _run(run, False)
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)
    assert out_f.shape == out_u.shape
    np.testing.assert_allclose(out_f, out_u, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('metric_key', ['acc', 'topk', 'ce'])
def test_fused_score_column_labels(metric_key):
    """(N, 1) column labels (CSVIter and friends): every reference
    metric RAVELS the label, so the in-graph stats must too — without
    it the (batch,) argmax broadcast against (batch, 1) labels into a
    (batch, batch) hit matrix and silently inflated num_inst."""
    def mk_metric():
        return metric_mod.TopKAccuracy(top_k=3) if metric_key == 'topk' \
            else metric_mod.create(metric_key)

    def run():
        mx.random.seed(7)
        np.random.seed(7)
        data = mx.sym.Variable('data')
        fc = mx.sym.FullyConnected(data, num_hidden=4, name='fc')
        out = mx.sym.SoftmaxOutput(fc, name='softmax')
        X = np.random.randn(56, 10).astype(np.float32)
        y = (np.random.rand(56) * 4).astype(int).astype(
            np.float32).reshape(-1, 1)
        it = mx.io.NDArrayIter(X, y, batch_size=8,
                               label_name='softmax_label')
        mod = mx.mod.Module(out, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label, for_training=False)
        mod.init_params()
        m = mk_metric()
        res = mod.score(it, m)
        return res, m.num_inst
    (res_f, n_f) = _run(run, True)
    (res_u, n_u) = _run(run, False)
    assert n_f == n_u == 56    # not inflated to batch^2 per step
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)


def test_fused_score_topk_exceeding_classes():
    """top_k larger than the class count: the reference metric clamps
    (top_k = min(num_classes, top_k)); the in-graph stat must too
    instead of letting lax.top_k raise out of score()."""
    def run():
        mod, it = _mlp_mod(n_classes=3)
        return mod.score(it, metric_mod.TopKAccuracy(top_k=5))
    res_f = _run(run, True)
    res_u = _run(run, False)
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)


def test_fused_score_width1_output_falls_back_to_host_metric():
    """A single-column (N, 1) output: reference Accuracy SKIPS the
    argmax when the class dim is 1 and compares raw values, so the
    in-graph argmax stats must decline — the window still fuses, but in
    stacked-output mode where the real metric runs on the host."""
    from mxnet_tpu.module.fused_eval import FusedEvalLoop as FEL

    def run():
        mx.random.seed(7)
        np.random.seed(7)
        data = mx.sym.Variable('data')
        fc = mx.sym.FullyConnected(data, num_hidden=1, name='fc')
        out = mx.sym.SoftmaxOutput(fc, name='softmax')
        X = np.random.randn(56, 10).astype(np.float32)
        y = (np.random.rand(56) > 0.5).astype(np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=8,
                               label_name='softmax_label')
        mod = mx.mod.Module(out, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label, for_training=False)
        mod.init_params()
        if os.environ.get('MXTPU_FUSED_EVAL') == '1':
            loop = FEL.build(mod, metric_mod.create('acc'))
            assert loop is not None and loop.stat_fns is None
        return mod.score(it, 'acc')
    res_f = _run(run, True)
    res_u = _run(run, False)
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)


def test_fused_eval_silent_fallback():
    """Ineligible configurations decline the fast path (build None)
    without changing results: flag off, monitor installed, non-Module
    subclass."""
    os.environ['MXTPU_FUSED_EVAL'] = '1'
    try:
        mod, it = _mlp_mod(for_training=True)
        assert FusedEvalLoop.build(mod, metric_mod.create('acc')) is not None
        assert FusedEvalLoop.build(mod, None) is not None
        # flag off
        os.environ['MXTPU_FUSED_EVAL'] = '0'
        assert FusedEvalLoop.build(mod, metric_mod.create('acc')) is None
        os.environ['MXTPU_FUSED_EVAL'] = '1'
        # a monitor forces the per-op staged path — decline, and score
        # still answers through the reference loop
        mod2, it2 = _mlp_mod(for_training=True)
        mod2.install_monitor(mx.mon.Monitor(1))
        assert FusedEvalLoop.build(mod2, metric_mod.create('acc')) is None
        res = mod2.score(it2, 'acc')
        mod3, it3 = _mlp_mod(for_training=True)
        res3 = mod3.score(it3, 'acc')
        np.testing.assert_allclose([v for _, v in res],
                                   [v for _, v in res3],
                                   rtol=1e-6, atol=1e-7)

        # a user subclass must not silently take the fused form
        class MyModule(mx.mod.Module):
            pass
        mx.random.seed(7)
        np.random.seed(7)
        data = mx.sym.Variable('data')
        out = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(data, num_hidden=4, name='fc'),
            name='softmax')
        sub = MyModule(out, context=mx.cpu())
        sub.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label, for_training=False)
        sub.init_params()
        assert FusedEvalLoop.build(sub, metric_mod.create('acc')) is None
    finally:
        os.environ.pop('MXTPU_FUSED_EVAL', None)


def test_fused_eval_loop_cached_across_calls():
    """Repeated score()/predict() calls reuse the loop object and its
    compiled programs; an equal-config fresh metric instance rebinds
    into the cached loop; score and predict cache independently."""
    os.environ['MXTPU_FUSED_EVAL'] = '1'
    try:
        mod, it = _mlp_mod()
        mod.score(it, 'acc')
        sig_a, loop_a = mod.__dict__['_fused_eval_cache']['score']
        progs_a = [id(p) for p, _ in loop_a._programs.values()]
        assert len(progs_a) == 1
        m2 = metric_mod.create('acc')
        mod.score(it, m2)
        sig_b, loop_b = mod.__dict__['_fused_eval_cache']['score']
        assert loop_b is loop_a
        assert [id(p) for p, _ in loop_b._programs.values()] == progs_a
        assert loop_b.children == [m2]
        assert m2.num_inst > 0
        # different metric config -> fresh loop
        mod.score(it, metric_mod.create('top_k_accuracy', top_k=3))
        _, loop_c = mod.__dict__['_fused_eval_cache']['score']
        assert loop_c is not loop_a
        # predict caches in its own slot, leaving score's intact
        mod.predict(it)
        cache = mod.__dict__['_fused_eval_cache']
        assert set(cache) == {'score', 'predict'}
        mod.predict(it)
        assert cache['predict'][1]._programs   # compiled + retained
        # flag off -> cache cleared
        os.environ['MXTPU_FUSED_EVAL'] = '0'
        mod.score(it, 'acc')
        assert '_fused_eval_cache' not in mod.__dict__
    finally:
        os.environ.pop('MXTPU_FUSED_EVAL', None)


def test_fused_eval_buffer_reusing_iterator():
    """Iterators may reuse their DataBatch/NDArray buffers between
    batches: the windowed path snapshots arrays at draw time, so
    deferred metric application and stacked outputs see each batch's
    own contents."""
    from mxnet_tpu.io import DataBatch, DataDesc

    class ReusingIter:
        def __init__(self, X, Y, batch):
            self.X, self.Y, self.batch = X, Y, batch
            self._data = mx.nd.zeros((batch, X.shape[1]))
            self._label = mx.nd.zeros((batch,))
            self._b = DataBatch(data=[self._data], label=[self._label],
                                pad=0)
            self.provide_data = [DataDesc('data', (batch, X.shape[1]))]
            self.provide_label = [DataDesc('softmax_label', (batch,))]
            self.batch_size = batch
            self._i = 0

        def __iter__(self):
            return self

        def reset(self):
            self._i = 0

        def __next__(self):
            if (self._i + 1) * self.batch > len(self.X):
                raise StopIteration
            sl = slice(self._i * self.batch, (self._i + 1) * self.batch)
            self._data[:] = self.X[sl]
            self._label[:] = self.Y[sl]
            self._i += 1
            return self._b

        next = __next__

    def run(fused, reuse):
        os.environ['MXTPU_FUSED_EVAL'] = '1' if fused else '0'
        try:
            mod, it = _mlp_mod(n=56, batch=8)
            if reuse:
                # the same data the NDArrayIter holds, replayed through
                # a buffer-reusing iterator
                it = ReusingIter(it._np_data[0], it._np_label[0], 8)
            res = mod.score(it, 'mse')      # host-metric mode
            out = mod.predict(it)
            return res, out.asnumpy()
        finally:
            os.environ.pop('MXTPU_FUSED_EVAL', None)

    res_f, out_f = run(True, reuse=True)
    res_u, out_u = run(False, reuse=False)
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(out_f, out_u, rtol=1e-5, atol=1e-6)


def test_fused_eval_spmd_multi_device():
    """8-CPU-device SPMD executor group under the eval window: params
    replicated on the mesh, batch stacks dp-sharded."""
    def run():
        ctx = [mx.cpu(i) for i in range(8)]
        mod, it = _mlp_mod(n=64, ctx=ctx)
        res = mod.score(it, 'acc')
        out = mod.predict(it)
        return res, out.asnumpy()
    (res_f, out_f) = _run(run, True)
    (res_u, out_u) = _run(run, False)
    np.testing.assert_allclose([v for _, v in res_f],
                               [v for _, v in res_u], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(out_f, out_u, rtol=1e-5, atol=1e-6)


def test_fused_eval_after_fit_validation_path():
    """fit(eval_data=...) drives score through the fused window while
    the fused fit window trains — both caches coexist on the module and
    the validation metric matches a per-batch score of the same
    state."""
    os.environ['MXTPU_FUSED_EVAL'] = '1'
    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        mod, it = _mlp_mod(n=64, batch=8, for_training=True)
        _, val = _mlp_mod(n=32, batch=8, seed=11)
        mod.fit(it, eval_data=val, num_epoch=1, optimizer='sgd',
                optimizer_params=(('learning_rate', 0.1),),
                kvstore='local', eval_metric='acc')
        assert '_fused_fit_cache' in mod.__dict__
        assert '_fused_eval_cache' in mod.__dict__
        fused_val = mod.score(val, 'acc')
        os.environ['MXTPU_FUSED_EVAL'] = '0'
        ref_val = mod.score(val, 'acc')
        np.testing.assert_allclose([v for _, v in fused_val],
                                   [v for _, v in ref_val],
                                   rtol=1e-6, atol=1e-7)
    finally:
        os.environ.pop('MXTPU_FUSED_EVAL', None)
        os.environ.pop('MXTPU_FUSED_FIT', None)


def test_eval_telemetry_gauge(tmp_path, monkeypatch):
    """score/predict set the eval_samples_per_sec gauge and count
    eval.batches when telemetry is on."""
    import mxnet_tpu.telemetry as tele
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH',
                       str(tmp_path / 'tele.jsonl'))
    from mxnet_tpu.config import flags
    flags.reload('MXTPU_TELEMETRY')
    flags.reload('MXTPU_TELEMETRY_PATH')
    tele._reset_for_tests()
    try:
        mod, it = _mlp_mod()
        mod.score(it, 'acc')
        mod.predict(it)
        snap = tele.snapshot()
        assert snap['gauges'].get('eval_samples_per_sec', 0) > 0
        assert snap['counters'].get('eval.batches', 0) >= 14
    finally:
        monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
        flags.reload('MXTPU_TELEMETRY')
        tele._reset_for_tests()


def test_compile_cache_round_trip(tmp_path):
    """JAX_COMPILATION_CACHE_DIR: a second process compiling the same
    program is served from the persistent cache at that directory
    (telemetry counts the hits) — the warm-start path."""
    import subprocess
    import sys
    code = r'''
import json
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import telemetry as tele
x = mx.nd.array(np.random.RandomState(0).randn(8, 8).astype(np.float32))
y = (x * 2 + 1).sum()          # a couple of jitted computations
print(json.dumps({'val': float(y.asnumpy()),
                  'cache_hits': int(tele.snapshot()['counters']
                                    .get('xla.cache_hits', 0))}))
'''
    import json
    env = dict(os.environ)
    env['JAX_COMPILATION_CACHE_DIR'] = str(tmp_path / 'xla_cache')
    env['MXTPU_TELEMETRY'] = '1'
    env['MXTPU_TELEMETRY_PATH'] = str(tmp_path / 't.jsonl')
    env['JAX_PLATFORMS'] = 'cpu'
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, '-c', code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert os.listdir(str(tmp_path / 'xla_cache'))   # populated
    assert outs[0]['val'] == outs[1]['val']
    assert outs[1]['cache_hits'] > 0                 # warm start served
