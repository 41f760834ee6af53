"""A window's host stack is written into memory written before (ISSUE 28).

The first touch of new pages, not the copy, is what stacking a window
costs (2.7 s for 2.47 GB on the v5e's host against 0.1-1.0 s into memory
written before): ``WindowPipeline.device_batches`` takes the stack's memory
from ``ndarray._host_buffer``, which hands out the buffer of a dead stack
of that size where there is one. What must hold, on the cpu backend, where
``jax.device_put`` adopts such a buffer as the device array's own memory:
a buffer is written again only when nothing refers to it any more (decided
by reference, never by counting windows), the telemetry says how often the
reuse engaged, what is too large to keep takes new memory and costs the
idle buffers nothing, a fit's working set is never let go of as a whole,
and training reads the same numbers either way.
"""
import gc
import json

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.module.window_pipeline import WindowPipeline
from mxnet_tpu.ndarray import ndarray as nda

_FLAGS = ('MXTPU_FUSED_DONATE', 'MXTPU_FUSED_FIT', 'MXTPU_FUSED_FIT_PREFETCH',
          'MXTPU_FIT_STEPS_PER_CALL', 'MXTPU_TELEMETRY',
          'MXTPU_TELEMETRY_PATH')
W = 4
BATCH = (64, 1024)      # float32: 256 KiB a batch, a window's stack 1 MiB
STACK_BYTES = W * 64 * 1024 * 4


def _reload():
    for f in _FLAGS:
        flags.reload(f)


@pytest.fixture
def traced(monkeypatch, tmp_path):
    """Telemetry on, its log in the test's directory, and an idle list of
    the test's own."""
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    monkeypatch.setenv('MXTPU_FUSED_FIT', '1')
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', str(W))
    _reload()
    telemetry._reset_for_tests()
    _own_idle_list(monkeypatch)
    yield monkeypatch
    telemetry._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


def _settle():          # jax lets go of host memory at its next call
    jax.block_until_ready(jax.numpy.zeros(()) + 1)


def _own_idle_list(monkeypatch):
    """An empty idle list for this test, once what earlier tests still
    held has come back to theirs."""
    gc.collect()
    _settle()
    monkeypatch.setattr(nda, '_idle_buffers', {})


def _snaps(k):
    """Window k's draw-time snapshots: W distinct batches, as the loop
    holds them (the cpu-backed arrays of new NDArrays)."""
    return [((mx.nd.array(np.full(BATCH, k * W + i, np.float32))._data,),
             (mx.nd.array(np.full(BATCH[:1], k, np.float32))._data,),
             0, None) for i in range(W)]


def _stack_spans(path, prefix='window'):
    telemetry.shutdown()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    return sorted((r for r in recs if r.get('type') == 'span'
                   and r['name'] == prefix + '.stack'),
                  key=lambda r: r['win'])


def _counters(prefix='window'):
    c = telemetry.snapshot()['counters']
    return (int(c.get(prefix + '.stacks_reused', 0)),
            int(c.get(prefix + '.stacks_new', 0)))


both = pytest.mark.parametrize(
    'donate,threaded', [(True, True), (True, False),
                        (False, True), (False, False)],
    ids=['donate-thread', 'donate-inline', 'keep-thread', 'keep-inline'])


@both
def test_a_live_stack_is_never_written_again(traced, donate, threaded):
    """Six windows of distinct batches: each window's device stack still
    reads its own batches after the two following windows have been
    stacked, and no two live stacks share memory."""
    pipe = WindowPipeline(W, lambda: jax.devices('cpu')[0], donate=donate)
    pool = pipe.pool() if threaded else None
    live = []           # (window, its device stacks)
    try:
        for k in range(6):
            live.append((k, pipe.start_put(_snaps(k), pool, k)()))
            _settle()
            views = [np.asarray(d[0]) for _, (d, _) in live]
            for i, a in enumerate(views):
                assert not any(np.shares_memory(a, b) for b in views[:i])
            if len(live) == 3:
                j, (data, label) = live.pop(0)
                want = np.arange(j * W, j * W + W, dtype=np.float32)
                np.testing.assert_array_equal(
                    np.asarray(data[0]),
                    np.broadcast_to(want[:, None, None], (W,) + BATCH))
                np.testing.assert_array_equal(np.asarray(label[0]), j)
                del data, label, views, a
    finally:
        if pool is not None:
            pool.shutdown(wait=True)


@pytest.mark.parametrize('hold', [0, 1], ids=['consumed', 'held_a_window'])
@both
def test_from_the_third_window_on_the_stack_is_written_where_one_was(
        traced, tmp_path, donate, threaded, hold):
    """A loop that lets go of each window's stacks as the chip's fit loop
    does (its program consumes them), or one window later as the cpu's
    does: one buffer serves every window, or two rotate; the span's
    ``reused`` and the counters say so, and the buffers stay idle across
    ``drop_cache`` (an epoch's end)."""
    pipe = WindowPipeline(W, lambda: jax.devices('cpu')[0], donate=donate)
    pool = pipe.pool() if threaded else None
    seen, where, before = set(), [], None
    # buffers in rotation: one, and one more where the stack before is
    # still held when the next is taken: by the loop, or (donate off) by
    # the cache's cpu-backed device stacks, whose memory jax lets go of
    # only at its next call
    new = 2 if hold or not donate else 1
    try:
        for k in range(6):
            if k == 4:
                before = None
                pipe.drop_cache()       # an epoch ends here
                _settle()
                assert len(nda._idle_buffers[STACK_BYTES + 64]) == new
            data, label = pipe.start_put(_snaps(k), pool, k)()
            assert float(np.asarray(data[0])[W - 1, 0, 0]) == k * W + W - 1
            at = data[0].unsafe_buffer_pointer()
            where.append(at in seen)
            seen.add(at)
            before = (data, label) if hold else None
            del data, label
            _settle()
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
    assert where == [False] * new + [True] * (6 - new)
    assert len(seen) == new
    assert _counters() == (6 - new, new)
    spans = _stack_spans(tmp_path / 't.jsonl')
    assert [s['reused'] for s in spans] == \
        [0] * new + [STACK_BYTES] * (6 - new)
    assert all(s['bytes'] > s['reused'] for s in spans)    # the labels' too


@both
def test_a_stack_over_the_bound_takes_new_memory_and_is_not_kept(
        traced, tmp_path, donate, threaded):
    """What the idle buffers may not hold is stacked as before, into
    memory of its own, and costs the buffers that are idle nothing."""
    other, _ = nda._host_buffer((3 << 18,), np.float32)     # 3 MiB
    del other
    traced.setattr(nda, '_idle_limit', lambda idle: STACK_BYTES - 1)
    # the limit is under the idle 3 MiB too: what is idle stays until a
    # buffer that may be kept needs the room
    pipe = WindowPipeline(W, lambda: jax.devices('cpu')[0], donate=donate)
    pool = pipe.pool() if threaded else None
    try:
        for k in range(4):
            data, label = pipe.start_put(_snaps(k), pool, k)()
            assert float(np.asarray(data[0])[0, 0, 0]) == k * W
            del data, label
            _settle()
        pipe.drop_cache()
        _settle()
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
    assert _counters() == (0, 4)
    assert [s['reused'] for s in _stack_spans(tmp_path / 't.jsonl')] == [0] * 4
    assert {n: len(b) for n, b in nda._idle_buffers.items()} == \
        {(3 << 20) + 64: 1}


@pytest.mark.parametrize('room', ['fits', 'one_window_short'])
def test_a_fit_working_set_is_not_let_go_of_as_a_whole(monkeypatch, room):
    """The convnet cell in miniature: 32 batch-sized buffers and two
    window-sized ones go idle and are taken again, window after window.
    Under a limit that holds them all every buffer is one written before;
    under one that is a window short only the buffers idle longest go, a
    window's worth of bytes, and the idle list is never emptied."""
    batch, window = (1 << 18,), (32, 1 << 18)       # 1 MiB and 32 MiB
    _own_idle_list(monkeypatch)
    whole = 32 * (batch[0] * 4 + 64) + 2 * (32 * batch[0] * 4 + 64)
    limit = whole if room == 'fits' else whole - (32 << 20)
    monkeypatch.setattr(nda, '_idle_limit', lambda idle: limit)

    def take():         # nothing is written: untouched memory costs nothing
        return [nda._host_buffer(batch, np.float32) for _ in range(32)] + \
            [nda._host_buffer(window, np.float32) for _ in range(2)]

    held = take()
    assert not any(reused for _, reused in held)
    for _ in range(4):
        del held                # an epoch's end: everything goes idle
        idle = nda._idle_bytes()
        held = take()
        written_before = sum(a.nbytes for a, reused in held if reused)
        if room == 'fits':
            assert idle == whole
            assert all(reused for _, reused in held)
        else:
            assert limit - (33 << 20) < idle <= limit
            assert written_before >= 32 << 20


def _fit_losses(donate, prefetch, monkeypatch):
    """Two epochs of three windows of `Module.fit` on fixed seeds: every
    batch's cumulative metric as the callback sees it, and the parameters."""
    monkeypatch.setenv('MXTPU_FUSED_DONATE', '1' if donate else '0')
    monkeypatch.setenv('MXTPU_FUSED_FIT_PREFETCH', '1' if prefetch else '0')
    _reload()
    mx.random.seed(11)
    rng = np.random.RandomState(11)
    rows, bs = 3 * W * 64, 64       # a batch 1 MiB, a window's stack 4 MiB
    X = rng.standard_normal((rows, 4096)).astype(np.float32)
    y = (rng.rand(rows) * 10).astype(int).astype(np.float32)
    d = mx.sym.Variable('data')
    h = mx.sym.Activation(mx.sym.FullyConnected(d, num_hidden=32, name='fc0'),
                          act_type='relu')
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=10, name='out'), name='softmax')
    mod = mx.mod.Module(sym, context=mx.cpu())
    seen = []
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=bs), num_epoch=2,
            optimizer='sgd',
            optimizer_params=(('learning_rate', 0.05), ('momentum', 0.9)),
            eval_metric='ce',
            batch_end_callback=lambda p: seen.append(
                p.eval_metric.get()[1]))
    assert len(seen) == 2 * 3 * W
    return seen, {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


@pytest.mark.parametrize('donate,prefetch', [(True, True), (True, False),
                                             (False, True), (False, False)])
def test_fit_reads_the_same_numbers_from_reused_memory(traced, donate,
                                                       prefetch):
    """`Module.fit` over two epochs of three windows, once with nothing kept
    idle (every stack into new memory: the parent's behaviour) and once
    with reuse (the second epoch's stacks at the latest: the buffers
    outlive an epoch's end): losses and parameters are bit-equal."""
    the_limit = nda._idle_limit
    traced.setattr(nda, '_idle_limit', lambda idle: 0)
    new_losses, new_params = _fit_losses(donate, prefetch, traced)
    assert _counters('fused_fit') == (0, 6)
    traced.setattr(nda, '_idle_limit', the_limit)
    losses, params = _fit_losses(donate, prefetch, traced)
    assert _counters('fused_fit')[0] >= 1
    assert losses == new_losses
    assert params.keys() == new_params.keys()
    for k in params:
        np.testing.assert_array_equal(params[k], new_params[k])
