"""Fused-window buffer economics (MXTPU_FUSED_DONATE, ISSUE 12).

The contract under test: the fused-fit window's steady state is
allocation-free where XLA allows it — the param/optimizer/aux carry
aliases in place onto the matching outputs and the input/label stacks
are donated for their lifetime — with the evidence on the telemetry
registrar (``program.<window>.live_bytes`` / ``alias_bytes``), not a
device run. Numerics are bit-exact against the undonated reference
program (MXTPU_FUSED_DONATE=0), a rebuilt window never re-uses a
donated buffer, the identity cache never hands a consumed stack back
to a donating program, the optimizer host tail overlaps the upload
(the side thread's ``fused_fit.stack``/``.upload`` spans against the
loop's ``.put`` wait), and MXTPU_REMAT_POLICY threads a
checkpoint policy into the window build.

Backend note (measured, not assumed): XLA:CPU's ``memory_analysis``
books an aliasing win under ``alias_size_in_bytes`` while its
liveness-packed ``temp_size_in_bytes`` barely moves — the registrar's
``live_bytes`` (args + temp + outputs - alias: what one dispatch makes
XLA hold beyond caller-owned buffers) is therefore the CPU-measurable
donation metric, and ``temp_bytes`` is gated against regression here
(device backends move it).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags

_FLAGS = ('MXTPU_FUSED_DONATE', 'MXTPU_REMAT_POLICY', 'MXTPU_FUSED_FIT',
          'MXTPU_FUSED_FIT_PREFETCH', 'MXTPU_FIT_STEPS_PER_CALL',
          'MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH', 'MXTPU_BN_ONEPASS',
          'MXTPU_SHARDED_UPDATE')


def _reload():
    for f in _FLAGS:
        flags.reload(f)


@pytest.fixture
def clean_flags(monkeypatch):
    monkeypatch.setenv('MXTPU_FUSED_FIT', '1')
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', '4')
    _reload()
    telemetry._reset_for_tests()
    yield monkeypatch
    telemetry._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


def _mlp(name='softmax'):
    """Param-heavy MLP: the donation win (aliased carry vs fresh
    outputs) dominates the footprint, so the live-bytes drop is large
    and stable. Ops explicitly named for deterministic program names."""
    d = mx.sym.Variable('data')
    h = d
    for i in range(3):
        h = mx.sym.Activation(
            mx.sym.FullyConnected(h, num_hidden=512, name='fc%d' % i),
            act_type='relu', name='relu%d' % i)
    h = mx.sym.FullyConnected(h, num_hidden=10, name='out')
    return mx.sym.SoftmaxOutput(h, name=name)


def _fit(num_epoch=1, seed=5, sym=None, begin_epoch=0, mod=None):
    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    n, bs = 64, 16
    X = rng.standard_normal((n, 64)).astype(np.float32)
    y = (rng.rand(n) * 10).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=bs)
    if mod is None:
        mod = mx.mod.Module(sym if sym is not None else _mlp(),
                            context=mx.cpu())
    mod.fit(it, begin_epoch=begin_epoch, num_epoch=num_epoch,
            optimizer='sgd',
            optimizer_params=(('learning_rate', 0.01),
                              ('momentum', 0.9)),
            eval_metric='acc')
    return mod


def _params(mod):
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


def _window_gauges(name='softmax'):
    g = telemetry.snapshot()['gauges']
    pfx = 'program.fused_fit.window[%s].' % name
    return {k: g.get(pfx + k, 0) for k in
            ('temp_bytes', 'live_bytes', 'alias_bytes')}


def test_donation_live_bytes_drop_30pct(clean_flags):
    """The acceptance gate, CPU-checkable via the registrar: full
    donation drops the fused window's steady-state live_bytes >= 30%
    vs the undonated pre-PR reference build, the donated carry shows
    up as nonzero alias_bytes, and temp_bytes does not regress."""
    clean_flags.setenv('MXTPU_TELEMETRY', '1')
    _reload()
    telemetry._reset_for_tests()

    clean_flags.setenv('MXTPU_FUSED_DONATE', '1')
    _reload()
    mod = _fit()
    assert mod.__dict__.get('_fused_fit_cache'), 'fused path did not engage'
    donated = _window_gauges()

    from mxnet_tpu.telemetry import programs
    programs._reset_for_tests()
    clean_flags.setenv('MXTPU_FUSED_DONATE', '0')
    _reload()
    _fit()
    undonated = _window_gauges()

    assert undonated['live_bytes'] > 0 and donated['live_bytes'] > 0
    assert undonated['alias_bytes'] == 0
    assert donated['alias_bytes'] > 0
    drop = 1.0 - donated['live_bytes'] / undonated['live_bytes']
    assert drop >= 0.30, (
        'donation reclaimed only %.1f%% of the window\'s steady-state '
        'footprint (donated %d vs undonated %d bytes)'
        % (100 * drop, donated['live_bytes'], undonated['live_bytes']))
    # donation must never grow what XLA plans as scratch
    assert donated['temp_bytes'] <= undonated['temp_bytes']


def test_donation_numerics_bit_exact(clean_flags):
    """Donated and undonated programs are the same computation: final
    params after two epochs match bit-for-bit."""
    clean_flags.setenv('MXTPU_FUSED_DONATE', '1')
    _reload()
    p1 = _params(_fit(num_epoch=2))
    clean_flags.setenv('MXTPU_FUSED_DONATE', '0')
    _reload()
    p0 = _params(_fit(num_epoch=2))
    assert set(p1) == set(p0)
    for k in p1:
        assert np.array_equal(p1[k], p0[k]), k


def test_donation_flag_flip_rebuilds_fresh_carries(clean_flags):
    """Donation safety across a window rebuild: a fit() that flips
    MXTPU_FUSED_DONATE between epochs must rebuild the loop (the old
    program's donated buffers are dead) and re-snapshot fresh carries
    — numerics match a reference run that made the same flip with
    donation off throughout, bit-exactly."""
    def run(flip_to):
        clean_flags.setenv('MXTPU_FUSED_DONATE', flip_to[0])
        _reload()
        mod = _fit(num_epoch=1)
        loop_a = mod.__dict__['_fused_fit_cache'][1]
        clean_flags.setenv('MXTPU_FUSED_DONATE', flip_to[1])
        _reload()
        _fit(num_epoch=2, begin_epoch=1, mod=mod)
        loop_b = mod.__dict__['_fused_fit_cache'][1]
        return _params(mod), loop_a, loop_b

    p_flip, la, lb = run(('1', '0'))
    assert la is not lb, 'flag flip must invalidate the cached loop'
    p_ref, ra, rb = run(('0', '0'))
    assert ra is rb, 'unchanged flags must reuse the cached loop'
    for k in p_ref:
        assert np.array_equal(p_flip[k], p_ref[k]), k
    # the reverse flip (into donation) rebuilds too
    p_flip2, la2, lb2 = run(('0', '1'))
    assert la2 is not lb2
    for k in p_ref:
        assert np.array_equal(p_flip2[k], p_ref[k]), k


def test_reset_bind_recaptures_fresh_carries(clean_flags):
    """A rebind (the _reset_bind path) after donated windows ran must
    rebuild the loop from the executor's CURRENT arrays — the donated
    originals are dead — and keep training without error."""
    clean_flags.setenv('MXTPU_FUSED_DONATE', '1')
    _reload()
    mod = _fit(num_epoch=1)
    loop_a = mod.__dict__.get('_fused_fit_cache')
    arg_p, aux_p = mod.get_params()
    # force_rebind tears the executor down and re-binds fresh buffers
    mod.bind(data_shapes=[('data', (16, 64))],
             label_shapes=[('softmax_label', (16,))],
             for_training=True, force_rebind=True)
    mod.set_params(arg_p, aux_p)
    _fit(num_epoch=2, begin_epoch=1, mod=mod)
    loop_b = mod.__dict__['_fused_fit_cache']
    assert loop_a is None or loop_a[1] is not loop_b[1]
    for v in _params(mod).values():
        assert np.all(np.isfinite(v))


class _SameBatchIter(mx.io.DataIter):
    """Yields the SAME NDArray objects every batch — the synthetic/
    benchmark iterator shape the pipeline's identity cache exists
    for. With donation on, a cached device stack would be a deleted
    buffer by the second window."""

    def __init__(self, batches):
        super(_SameBatchIter, self).__init__()
        self._n = batches
        self._i = 0
        self._data = mx.nd.array(
            np.random.RandomState(0).standard_normal((16, 64)))
        self._label = mx.nd.array(
            (np.random.RandomState(1).rand(16) * 10).astype(int))
        self.provide_data = [mx.io.DataDesc('data', (16, 64))]
        self.provide_label = [mx.io.DataDesc('softmax_label', (16,))]

    def reset(self):
        self._i = 0

    def next(self):
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        return mx.io.DataBatch(data=[self._data], label=[self._label])


def test_identity_cache_is_donation_safe(clean_flags):
    """Two epochs over an iterator that re-yields the same arrays: the
    identity cache hits, and with donation on it must re-place a fresh
    device stack per window (host-form cache) instead of handing back
    the consumed one — jax would raise on a deleted buffer."""
    clean_flags.setenv('MXTPU_FUSED_DONATE', '1')
    _reload()
    mx.random.seed(9)
    it = _SameBatchIter(batches=8)   # 2 windows/epoch at W=4
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.01),),
            eval_metric='acc')
    loop = mod.__dict__['_fused_fit_cache'][1]
    assert loop._pipe.donate is True
    for v in _params(mod).values():
        assert np.all(np.isfinite(v))


def _window_spans(path):
    """{win: {span name: record}} of the window spans in a JSONL log
    (one record per name and window but .next)."""
    import json
    out = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if r.get('type') == 'span' and 'win' in r \
                    and r['name'] != 'fused_fit.next':
                assert r['name'] not in out.setdefault(r['win'], {}), r
                out[r['win']][r['name']] = r
    return out


def test_overlap_histogram_populated(clean_flags, tmp_path):
    """The update/upload overlap evidence, read from the spans: with
    the prefetch pool on (default) every window's stack + upload run
    on the side thread and end before the loop's .put wait for the
    same `win` does, so what hid is their duration less that wait;
    serial mode runs them on the loop's own thread, before the .put,
    and hides nothing."""
    import threading

    def fit_and_read(name, epochs):
        clean_flags.setenv('MXTPU_TELEMETRY', '1')
        clean_flags.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / name))
        _reload()
        telemetry._reset_for_tests()
        _fit(num_epoch=epochs)
        telemetry.shutdown()
        wins = _window_spans(tmp_path / name)
        return [w for w in wins.values() if 'fused_fit.put' in w]

    loop_tid = threading.current_thread().name
    pooled = fit_and_read('pool.jsonl', 2)
    assert len(pooled) >= 2
    for w in pooled:
        stack, upload = w['fused_fit.stack'], w['fused_fit.upload']
        put = w['fused_fit.put']
        assert stack['tid'].startswith('mxtpu-window-put')
        assert upload['tid'] == stack['tid'] != put['tid']
        assert put['tid'] == loop_tid
        done = upload['t'] + upload['dur_ms'] / 1e3
        assert stack['t'] <= upload['t']
        assert done <= put['t'] + put['dur_ms'] / 1e3 + 1e-3

    clean_flags.setenv('MXTPU_FUSED_FIT_PREFETCH', '0')
    serial = fit_and_read('serial.jsonl', 1)
    assert serial
    for w in serial:
        upload, put = w['fused_fit.upload'], w['fused_fit.put']
        assert w['fused_fit.stack']['tid'] == loop_tid
        assert upload['tid'] == loop_tid
        # nothing left to wait for: the work was over before the put
        assert upload['t'] + upload['dur_ms'] / 1e3 <= put['t'] + 1e-3


def test_identity_cache_hit_opens_no_stack_span(clean_flags, tmp_path):
    """A window served by the identity cache stacks nothing: no .stack
    span, and with donation on (the device stack is consumed) still one
    .upload for the fresh placement."""
    clean_flags.setenv('MXTPU_FUSED_DONATE', '1')
    clean_flags.setenv('MXTPU_TELEMETRY', '1')
    clean_flags.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    _reload()
    telemetry._reset_for_tests()
    mx.random.seed(9)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(_SameBatchIter(batches=8), num_epoch=1, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.01),),
            eval_metric='acc')
    telemetry.shutdown()
    wins = _window_spans(tmp_path / 't.jsonl')
    first, second = [wins[k] for k in sorted(wins)
                     if 'fused_fit.put' in wins[k]]
    assert 'fused_fit.stack' in first and 'fused_fit.upload' in first
    assert 'fused_fit.stack' not in second
    assert 'fused_fit.upload' in second


@pytest.mark.parametrize('pooled', [True, False])
def test_upload_resolver_freed_by_refcount(pooled):
    """start_put's resolver refers to nothing but its result: once the
    loop drops it, it and the window's stacks go by reference counting
    alone, without waiting for a pass of the cycle collector (a window's
    device stack is gigabytes on the chip)."""
    import gc
    import weakref
    import jax
    from mxnet_tpu.module.window_pipeline import WindowPipeline

    pipe = WindowPipeline(2, lambda: jax.devices('cpu')[0])
    snaps = [((np.ones((2, 3), np.float32) * i,),
              (np.zeros((2,), np.float32),), 0, None) for i in range(2)]
    pool = pipe.pool() if pooled else None
    gc.collect()
    gc.disable()
    try:
        resolver = pipe.start_put(snaps, pool, 0)
        data_stack, label_stack = resolver()
        assert data_stack[0].shape == (2, 2, 3)
        gone = [weakref.ref(resolver), weakref.ref(data_stack[0])]
        pipe.drop_cache()
        del resolver, data_stack, label_stack
        assert [r() for r in gone] == [None, None]
    finally:
        gc.enable()
        if pool is not None:
            pool.shutdown(wait=True)


def test_remat_policy_unit_and_rebuild(clean_flags):
    """MXTPU_REMAT_POLICY: 'full'/'dots' thread a jax.checkpoint into
    the window body ('remat' lands in the traced jaxpr), 'none'
    explicitly overrides MXTPU_BACKWARD_DO_MIRROR, '' defers to it;
    flipping the flag between fit() calls rebuilds the cached loop."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.module import fused_fit as ff

    def f(x):
        return jnp.sin(x * 2.0)

    x = jnp.ones((4,))
    for policy, expect_remat in (('none', False), ('dots', True),
                                 ('full', True)):
        clean_flags.setenv('MXTPU_REMAT_POLICY', policy)
        _reload()
        jaxpr = jax.make_jaxpr(lambda v: jax.grad(
            lambda t: ff._remat_wrap(f)(t).sum())(v))(x)
        assert ('remat' in str(jaxpr)) == expect_remat, policy
    # '' defers to the mirror flag
    clean_flags.setenv('MXTPU_REMAT_POLICY', '')
    clean_flags.setenv('MXTPU_BACKWARD_DO_MIRROR', '1')
    flags.reload('MXTPU_BACKWARD_DO_MIRROR')
    _reload()
    jaxpr = jax.make_jaxpr(lambda v: jax.grad(
        lambda t: ff._remat_wrap(f)(t).sum())(v))(x)
    assert 'remat' in str(jaxpr)
    clean_flags.delenv('MXTPU_BACKWARD_DO_MIRROR')
    flags.reload('MXTPU_BACKWARD_DO_MIRROR')

    # and 'none' explicitly overrides a set mirror flag
    clean_flags.setenv('MXTPU_REMAT_POLICY', 'none')
    clean_flags.setenv('MXTPU_BACKWARD_DO_MIRROR', '1')
    flags.reload('MXTPU_BACKWARD_DO_MIRROR')
    _reload()
    jaxpr = jax.make_jaxpr(lambda v: jax.grad(
        lambda t: ff._remat_wrap(f)(t).sum())(v))(x)
    assert 'remat' not in str(jaxpr)
    clean_flags.delenv('MXTPU_BACKWARD_DO_MIRROR')
    flags.reload('MXTPU_BACKWARD_DO_MIRROR')

    # loop rebuild on flip
    clean_flags.setenv('MXTPU_REMAT_POLICY', '')
    _reload()
    mod = _fit(num_epoch=1)
    loop_a = mod.__dict__['_fused_fit_cache'][1]
    clean_flags.setenv('MXTPU_REMAT_POLICY', 'full')
    _reload()
    _fit(num_epoch=2, begin_epoch=1, mod=mod)
    loop_b = mod.__dict__['_fused_fit_cache'][1]
    assert loop_a is not loop_b
    # remat changes scheduling, not math: same-seed parity vs policy ''
    for v in _params(mod).values():
        assert np.all(np.isfinite(v))


def test_remat_policy_numerics_parity(clean_flags):
    """Remat trades memory for recompute; loss and gradients are
    bit-identical (jax.checkpoint contract) — final params after two
    epochs match the no-remat run exactly."""
    clean_flags.setenv('MXTPU_REMAT_POLICY', 'none')
    _reload()
    p_none = _params(_fit(num_epoch=2))
    clean_flags.setenv('MXTPU_REMAT_POLICY', 'full')
    _reload()
    p_full = _params(_fit(num_epoch=2))
    for k in p_none:
        assert np.array_equal(p_none[k], p_full[k]), k


@pytest.mark.skipif(len(__import__('jax').devices()) < 8,
                    reason='needs the 8-device CPU mesh')
def test_spmd_window_emits_no_involuntary_remat_warnings(clean_flags,
                                                         capfd):
    """The PR 9 known residue: the flag-on SPMD window's tiny s32
    index operands made GSPMD print '[spmd] Involuntary full
    rematerialization' warnings. The replicated pin on the scan
    index/lr/wd operands silences them — and training still works."""
    clean_flags.setenv('MXTPU_SHARDED_UPDATE', '1')
    _reload()
    mx.random.seed(3)
    rng = np.random.RandomState(3)
    d = mx.sym.Variable('data')
    h = mx.sym.Activation(
        mx.sym.FullyConnected(d, num_hidden=50, name='fc1'),
        act_type='relu', name='relu1')
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=10, name='fc2'),
        name='softmax')
    n, bs = 128, 16
    X = rng.standard_normal((n, 8)).astype(np.float32)
    y = (rng.rand(n) * 10).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=bs)
    mod = mx.mod.Module(sym, context=[mx.cpu(i) for i in range(8)])
    capfd.readouterr()
    mod.fit(it, num_epoch=1, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.01),
                              ('momentum', 0.9)),
            eval_metric='acc', kvstore='device')
    err = capfd.readouterr().err
    assert 'Involuntary full rematerialization' not in err
    loop = mod.__dict__['_fused_fit_cache'][1]
    assert loop._zero is not None, 'ZeRO path must still engage'
