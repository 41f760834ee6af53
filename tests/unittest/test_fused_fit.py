"""Fused Module.fit fast path (module/fused_fit.py).

The contract under test: with MXTPU_FUSED_FIT on (default), fit
compiles W steps per device call yet produces IDENTICAL parameters and
per-batch metric values to the reference per-batch loop (reference
base_module.py:376) across kvstore modes, update ops, SPMD contexts,
and window-tail sizes.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metric as metric_mod
from mxnet_tpu.module.fused_fit import FusedFitLoop


def _mlp_mod(n=56, batch=8, ctx=None, n_classes=4, seed=7):
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
    return mx.mod.Module(out, context=ctx or mx.cpu()), it


def _fit(fused, kvstore='local', momentum=0.9, metric='acc', cb=None,
         optimizer='sgd', optimizer_params=None, grad_req='write',
         **build_kw):
    os.environ['MXTPU_FUSED_FIT'] = '1' if fused else '0'
    try:
        mod, it = _mlp_mod(**build_kw)
        if optimizer_params is None:
            optimizer_params = (('learning_rate', 0.1),
                                ('momentum', momentum))
        if grad_req != 'write':
            # pre-bind with the requested grad_req; fit()'s own bind
            # call is then a no-op on the already-bound module
            mod.bind(data_shapes=it.provide_data,
                     label_shapes=it.provide_label, for_training=True,
                     grad_req=grad_req)
        mod.fit(it, num_epoch=2, optimizer=optimizer,
                optimizer_params=optimizer_params,
                kvstore=kvstore, eval_metric=metric,
                batch_end_callback=cb)
        args, auxs = mod.get_params()
        return ({k: v.asnumpy() for k, v in args.items()}, mod)
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)


def _assert_same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-6,
                                    err_msg=k)


@pytest.mark.parametrize('kvstore', ['local', 'device', None])
def test_fused_matches_reference_loop(kvstore):
    """Identical final params + identical per-batch metric trajectory
    across kvstore modes (updater path and update-on-kvstore path)."""
    traj_f, traj_u = [], []
    a_f, _ = _fit(True, kvstore=kvstore,
                  cb=lambda p: traj_f.append(
                      p.eval_metric.get_name_value()[0][1]))
    a_u, _ = _fit(False, kvstore=kvstore,
                  cb=lambda p: traj_u.append(
                      p.eval_metric.get_name_value()[0][1]))
    _assert_same(a_f, a_u)
    np.testing.assert_allclose(traj_f, traj_u, atol=1e-9)
    assert len(traj_f) == 14  # 7 batches x 2 epochs: callback per batch


def test_fused_window_tail():
    """56/8 = 7 batches vs window 4: one fused window + a 3-batch tail
    through the reference path per epoch, interleaved safely."""
    a_f, _ = _fit(True)
    a_u, _ = _fit(False)
    _assert_same(a_f, a_u)


def test_fused_plain_sgd_no_momentum():
    a_f, _ = _fit(True, momentum=0.0)
    a_u, _ = _fit(False, momentum=0.0)
    _assert_same(a_f, a_u)


def test_fused_spmd_multi_device():
    """8-CPU-device SPMD executor group under the fused window: params
    replicated on the mesh, batch stacks dp-sharded."""
    ctx = [mx.cpu(i) for i in range(8)]
    a_f, _ = _fit(True, ctx=ctx, n=64, kvstore='device')
    a_u, _ = _fit(False, ctx=ctx, n=64, kvstore='device')
    _assert_same(a_f, a_u)


def test_fused_composite_metric_values():
    comp_f = metric_mod.CompositeEvalMetric()
    comp_f.add('acc')
    comp_f.add(metric_mod.TopKAccuracy(top_k=3))
    comp_f.add('ce')
    comp_u = metric_mod.CompositeEvalMetric()
    comp_u.add('acc')
    comp_u.add(metric_mod.TopKAccuracy(top_k=3))
    comp_u.add('ce')
    vf, vu = [], []
    _fit(True, metric=comp_f, n_classes=6, n=48, batch=6,
         cb=lambda p: vf.append(tuple(
             v for _, v in p.eval_metric.get_name_value())))
    _fit(False, metric=comp_u, n_classes=6, n=48, batch=6,
         cb=lambda p: vu.append(tuple(
             v for _, v in p.eval_metric.get_name_value())))
    np.testing.assert_allclose(np.array(vf), np.array(vu),
                               rtol=1e-5, atol=1e-7)


def test_fused_eligibility_gates():
    """Unsupported configurations decline the fast path (None) instead
    of changing behavior; widened ones engage it."""
    mod, it = _mlp_mod()
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(kvstore='device', optimizer='sgd')
    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        assert FusedFitLoop.build(mod, metric_mod.create('acc')) is not None
        # a metric without a stats plan takes the HOST-fallback mode
        loop = FusedFitLoop.build(mod, metric_mod.create('mse'))
        assert loop is not None and loop.stat_fns is None
        # flag off
        os.environ['MXTPU_FUSED_FIT'] = '0'
        assert FusedFitLoop.build(mod, metric_mod.create('acc')) is None
        os.environ['MXTPU_FUSED_FIT'] = '1'
        # Adam now has a plan (round-5 widening)
        mod2, it2 = _mlp_mod()
        mod2.bind(data_shapes=it2.provide_data,
                  label_shapes=it2.provide_label)
        mod2.init_params()
        mod2.init_optimizer(kvstore='device', optimizer='adam')
        assert FusedFitLoop.build(mod2, metric_mod.create('acc')) is not None
        # an optimizer with no fused plan still declines
        mod3, it3 = _mlp_mod()
        mod3.bind(data_shapes=it3.provide_data,
                  label_shapes=it3.provide_label)
        mod3.init_params()
        mod3.init_optimizer(kvstore='device', optimizer='adadelta')
        assert FusedFitLoop.build(mod3, metric_mod.create('acc')) is None
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)


@pytest.mark.parametrize('opt,params', [
    ('adam', (('learning_rate', 0.01),)),
    ('nag', (('learning_rate', 0.05), ('momentum', 0.9))),
    ('rmsprop', (('learning_rate', 0.01),)),
    ('rmsprop', (('learning_rate', 0.01), ('centered', True))),
    ('ftrl', (('learning_rate', 0.1),)),
])
def test_fused_matches_reference_loop_other_optimizers(opt, params):
    """Round-5 widening: every optimizer with a fused-op plan produces
    the reference loop's exact trajectory (Adam's per-update-count
    bias correction is folded into the per-batch lr rows)."""
    a_f, _ = _fit(True, optimizer=opt, optimizer_params=params)
    a_u, _ = _fit(False, optimizer=opt, optimizer_params=params)
    _assert_same(a_f, a_u)


def test_fused_grad_req_add_matches_reference_loop():
    """grad_req='add' carries the accumulators through the scan and
    writes them back — same params AND same accumulated grad buffers
    as the reference loop."""
    grads = {}
    args = {}
    for fused in (True, False):
        a, mod = _fit(fused, grad_req='add')
        args[fused] = a
        grads[fused] = {n: g.asnumpy().copy() for n, g in
                        mod._exec_group.execs[0].grad_dict.items()
                        if g is not None}
    _assert_same(args[True], args[False])
    _assert_same(grads[True], grads[False])


def test_fused_custom_metric_host_mode_matches_reference_loop():
    """A metric with no in-graph stats plan (user CustomMetric) runs in
    host-fallback mode: same params and same per-batch metric values."""
    def feval(label, pred):
        return float(np.mean(np.abs(pred[np.arange(len(label)),
                                         label.astype(int)] - 1.0)))
    vf, vu = [], []
    a_f, _ = _fit(True, metric=metric_mod.CustomMetric(feval, name='dist'),
                  cb=lambda p: vf.append(p.eval_metric.get_name_value()[0][1]))
    a_u, _ = _fit(False, metric=metric_mod.CustomMetric(feval, name='dist'),
                  cb=lambda p: vu.append(p.eval_metric.get_name_value()[0][1]))
    _assert_same(a_f, a_u)
    np.testing.assert_allclose(vf, vu, rtol=1e-6, atol=1e-8)
    assert len(vf) == 14


@pytest.mark.parametrize('step_kind', ['aligned', 'mid_window'])
def test_fused_scheduler_no_recompile_and_exact_equality(step_kind):
    """lr enters the compiled window as traced per-batch rows: a
    scheduler boundary yields the exact reference trajectory whether
    it lands on a window edge or MID-window (round-5: per-step lr
    sampling), with one compiled program despite the lr changing."""
    import mxnet_tpu.module.fused_fit as ff
    W = ff._window_size(_mlp_mod()[0])
    step = W if step_kind == 'aligned' else max(2, W - 1)
    results = {}
    for fused in (True, False):
        os.environ['MXTPU_FUSED_FIT'] = '1' if fused else '0'
        try:
            mod, it = _mlp_mod(n=64, batch=8)
            sched = mx.lr_scheduler.FactorScheduler(step=step, factor=0.5)
            mod.fit(it, num_epoch=2, optimizer='sgd',
                    optimizer_params=(('learning_rate', 0.2),
                                      ('momentum', 0.9),
                                      ('lr_scheduler', sched)),
                    kvstore='local', eval_metric='acc')
            args, _ = mod.get_params()
            results[fused] = {k: v.asnumpy() for k, v in args.items()}
        finally:
            os.environ.pop('MXTPU_FUSED_FIT', None)
    _assert_same(results[True], results[False])


def test_fused_program_cache_single_entry_across_lr_changes():
    """Directly: 3 windows with 3 different lrs compile ONE program."""
    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        mod, it = _mlp_mod(n=96, batch=8)   # 12 batches = 3 windows @ W=4
        sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.7)
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params()
        mod.init_optimizer(kvstore='local', optimizer='sgd',
                           optimizer_params=(('learning_rate', 0.1),
                                             ('momentum', 0.9),
                                             ('lr_scheduler', sched)))
        loop = FusedFitLoop.build(mod, metric_mod.create('acc'))
        assert loop is not None
        loop.run_epoch(it, metric_mod.create('acc'), 0, None)
        assert len(loop._programs) == 1
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)


def test_fused_optimizer_state_roundtrip(tmp_path):
    """Optimizer state written back by the fused path is the state the
    checkpoint APIs see: save after fused fit == save after reference
    fit (same trajectory, same momentum buffers)."""
    paths = {}
    for fused in (True, False):
        _, mod = _fit(fused, kvstore='local')
        p = str(tmp_path / ('states_%d' % fused))
        mod.save_optimizer_states(p)
        paths[fused] = p
    import pickle
    sf = pickle.loads(open(paths[True], 'rb').read())
    su = pickle.loads(open(paths[False], 'rb').read())
    assert set(sf.keys()) == set(su.keys())
    for k in sf:
        a, b = sf[k], su[k]
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_fused_buffer_reusing_iterator_matches_reference_loop():
    """Iterators may reuse their DataBatch/NDArray buffers between
    batches (the reference engine copies on consumption): the fused
    window snapshots the underlying jax arrays at draw time, so data,
    labels, tail batches, and deferred host-metric application all see
    each batch's own contents."""
    from mxnet_tpu.io import DataBatch, DataDesc

    class ReusingIter:
        """Yields the SAME DataBatch/NDArray objects every batch,
        mutating them in place."""

        def __init__(self, X, Y, batch):
            self.X, self.Y, self.batch = X, Y, batch
            self._data = mx.nd.zeros((batch, X.shape[1]))
            self._label = mx.nd.zeros((batch,))
            self._b = DataBatch(data=[self._data], label=[self._label])
            self.provide_data = [DataDesc('data', (batch, X.shape[1]))]
            self.provide_label = [DataDesc('softmax_label', (batch,))]
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

    def run(fused, metric, reuse):
        os.environ['MXTPU_FUSED_FIT'] = '1' if fused else '0'
        try:
            mx.random.seed(11)
            np.random.seed(11)
            data = mx.sym.Variable('data')
            fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
            act = mx.sym.Activation(fc1, act_type='relu')
            fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
            out = mx.sym.SoftmaxOutput(fc2, name='softmax')
            X = np.random.randn(56, 10).astype(np.float32)
            y = (np.random.rand(56) * 4).astype(int).astype(np.float32)
            it = ReusingIter(X, y, 8) if reuse else \
                mx.io.NDArrayIter(X, y, batch_size=8, shuffle=False,
                                  label_name='softmax_label')
            mod = mx.mod.Module(out, context=mx.cpu())
            traj = []
            mod.fit(it, num_epoch=2, optimizer='sgd',
                    optimizer_params=(('learning_rate', 0.1),
                                      ('momentum', 0.9)),
                    kvstore='local', eval_metric=metric,
                    batch_end_callback=lambda p: traj.append(
                        p.eval_metric.get_name_value()[0][1]))
            args, _ = mod.get_params()
            return {k: v.asnumpy() for k, v in args.items()}, traj
        finally:
            os.environ.pop('MXTPU_FUSED_FIT', None)

    # oracle: the reference loop over a fresh-buffer iterator with the
    # SAME data (the unfused loop on the reusing iterator itself reads
    # labels after its prefetch overwrote them — the reference code's
    # own draw-ahead ordering — so it is not the ground truth here)
    for metric in ('acc', 'mse'):   # stats mode AND host-metric mode
        a_f, t_f = run(True, metric, reuse=True)
        a_u, t_u = run(False, metric, reuse=False)
        _assert_same(a_f, a_u)
        np.testing.assert_allclose(t_f, t_u, rtol=1e-6, atol=1e-8,
                                   err_msg=metric)


def test_fused_spmd_sharded_update_matches_replicated():
    """MXTPU_SHARDED_UPDATE (cross-replica weight-update sharding,
    arXiv:2004.13336) is a pure execution-layout change: the SPMD fused
    window produces the replicated update's trajectory, and both match
    the unfused loop."""
    import subprocess
    import sys
    code = r'''
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax; jax.config.update('jax_platforms', 'cpu')
import json
import numpy as np
import mxnet_tpu as mx

mx.random.seed(7)
np.random.seed(7)
data = mx.sym.Variable('data')
fc1 = mx.sym.FullyConnected(data, num_hidden=32, name='fc1')
act = mx.sym.Activation(fc1, act_type='relu')
fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
out = mx.sym.SoftmaxOutput(fc2, name='softmax')
X = np.random.randn(64, 10).astype(np.float32)
y = (np.random.rand(64) * 4).astype(int).astype(np.float32)
it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=False,
                       label_name='softmax_label')
mod = mx.mod.Module(out, context=[mx.cpu(i) for i in range(8)])
mod.fit(it, num_epoch=2, optimizer='sgd',
        optimizer_params=(('learning_rate', 0.1), ('momentum', 0.9)),
        kvstore='device', eval_metric='acc')
# the path under test must have engaged: SPMD group + fused window
from mxnet_tpu.module.executor_group import SPMDExecutorGroup
from mxnet_tpu.module.fused_fit import FusedFitLoop
assert isinstance(mod._exec_group, SPMDExecutorGroup)
assert FusedFitLoop.build(mod, mx.metric.create('acc')) is not None
args, _ = mod.get_params()
print(json.dumps({k: v.asnumpy().tolist() for k, v in args.items()}))
'''
    outs = {}
    for flag in ('0', '1'):
        env = dict(os.environ)
        env['MXTPU_SHARDED_UPDATE'] = flag
        env['MXTPU_FUSED_FIT'] = '1'
        env['JAX_PLATFORMS'] = 'cpu'
        r = subprocess.run([sys.executable, '-c', code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        import json
        outs[flag] = json.loads(r.stdout.strip().splitlines()[-1])
    assert outs['0'].keys() == outs['1'].keys()
    for k in outs['0']:
        np.testing.assert_allclose(np.array(outs['1'][k]),
                                   np.array(outs['0'][k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_fused_loop_reused_across_fit_calls():
    """Epoch-at-a-time drivers (fit(begin_epoch=e, num_epoch=e+1) in a
    loop — the resume / eval-between-epochs pattern, and
    tools/fed_fit_bench.py) must NOT retrace + recompile the window on
    every call: the loop and its compiled programs are cached on the
    module and reused while the executor/optimizer/metric/window
    signature is unchanged (a retrace per call was the fed-fit
    pathology).
    The epoch-at-a-time trajectory equals one fit(num_epoch=2)."""
    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        mod, it = _mlp_mod(n=64, batch=8)
        first = None
        for epoch in range(2):
            mod.fit(it, num_epoch=epoch + 1, begin_epoch=epoch,
                    optimizer='sgd',
                    optimizer_params=(('learning_rate', 0.1),
                                      ('momentum', 0.9)),
                    kvstore='local', eval_metric='acc',
                    force_init=(epoch == 0))
            sig, loop = mod.__dict__['_fused_fit_cache']
            progs = [id(p) for p in loop._programs.values()]
            if first is None:
                first = (id(loop), progs)
                assert len(progs) == 1
            else:
                # same loop object, same compiled program objects
                assert id(loop) == first[0]
                assert progs == first[1]
        args_a = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

        mod2, it2 = _mlp_mod(n=64, batch=8)
        mod2.fit(it2, num_epoch=2, optimizer='sgd',
                 optimizer_params=(('learning_rate', 0.1),
                                   ('momentum', 0.9)),
                 kvstore='local', eval_metric='acc')
        args_b = {k: v.asnumpy() for k, v in mod2.get_params()[0].items()}
        _assert_same(args_a, args_b)
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)


def test_fused_loop_cache_invalidation():
    """The reuse signature tracks what the traced window depends on: a
    different metric CONFIG rebuilds (fresh stat fns), while an
    equal-config fresh metric instance reuses; disabling the flag
    clears the cache."""
    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        mod, it = _mlp_mod(n=64, batch=8)
        fit_kw = dict(optimizer='sgd',
                      optimizer_params=(('learning_rate', 0.1),
                                        ('momentum', 0.9)),
                      kvstore='local')
        mod.fit(it, num_epoch=1, eval_metric='acc', **fit_kw)
        _, loop_a = mod.__dict__['_fused_fit_cache']
        # equal-config fresh instance -> reuse, stats land in the NEW
        # metric object via _rebind_metric
        m2 = metric_mod.create('acc')
        mod.fit(it, num_epoch=1, eval_metric=m2, **fit_kw)
        _, loop_b = mod.__dict__['_fused_fit_cache']
        assert loop_b is loop_a
        assert loop_b.children == [m2]
        assert m2.num_inst > 0  # the reused window updated the new metric
        # different config -> rebuild
        mod.fit(it, num_epoch=1,
                eval_metric=metric_mod.create('top_k_accuracy', top_k=3),
                **fit_kw)
        _, loop_c = mod.__dict__['_fused_fit_cache']
        assert loop_c is not loop_a
        # flag off -> fallback loop AND cache cleared
        os.environ['MXTPU_FUSED_FIT'] = '0'
        mod.fit(it, num_epoch=1, eval_metric='acc', **fit_kw)
        assert '_fused_fit_cache' not in mod.__dict__
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)


def test_fused_exhausted_iterator_raises_like_reference_loop():
    """An iterator left exhausted (e.g. by a score() pass between
    epoch-at-a-time fit calls) must raise StopIteration out of fit in
    the fused path exactly as the reference loop's unguarded first
    next() does (reference base_module.py:482) — never silently train
    a zero-batch epoch."""
    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        mod, it = _mlp_mod(n=64, batch=8)
        mod.fit(it, num_epoch=1, optimizer='sgd',
                optimizer_params=(('learning_rate', 0.1),),
                kvstore='local', eval_metric='acc')
        for _ in it:       # drain (fit's epoch-end reset made it fresh)
            pass
        with pytest.raises(StopIteration):
            mod.fit(it, num_epoch=2, begin_epoch=1, optimizer='sgd',
                    optimizer_params=(('learning_rate', 0.1),),
                    kvstore='local', eval_metric='acc')
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)


def test_fused_exactly_one_window_epoch_completes():
    """An epoch of EXACTLY W batches must complete normally (stats
    applied, callbacks fired) — the exhausted-iterator guard must not
    misfire on the pending window whose stats are deliberately fetched
    one window late."""
    import mxnet_tpu.module.fused_fit as ff
    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        W = ff._window_size(_mlp_mod()[0])
        cb_count = []
        mod, it = _mlp_mod(n=8 * W, batch=8)   # exactly W batches
        mod.fit(it, num_epoch=1, optimizer='sgd',
                optimizer_params=(('learning_rate', 0.1),),
                kvstore='local', eval_metric='acc',
                batch_end_callback=lambda p: cb_count.append(p.nbatch))
        assert len(cb_count) == W, cb_count
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)
