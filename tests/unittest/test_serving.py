"""Inference serving plane (mxnet_tpu/serving, ISSUE 13).

Contracts under test:
- bucket ladder: powers of two up to MXTPU_SERVE_MAX_BATCH, smallest
  covering bucket per request, chunking past the top bucket;
- engine parity: a full-bucket request answers BIT-identically to
  Module.predict at the same batch size; padded/chunked requests strip
  pad rows exactly (row counts and values match the reference);
- dynamic batcher: concurrent submitters coalesce into one padded
  dispatch (asserted via the dispatch ledger), a lone request flushes
  at MXTPU_SERVE_MAX_WAIT_MS, per-request splits return each caller
  exactly its own rows;
- zero-recompile steady state: after warmup the xla.compiles counter
  is FLAT across an arbitrary request-size mix;
- O(1) step cache: decode parity against a host-tracked per-step
  reference loop, LRU eviction at capacity, fresh-restart-from-zero
  for an evicted session, zero recompiles across decode steps;
- HTTP end to end: concurrent clients against an ephemeral-port server
  get Module.predict-parity answers with >= 1 dispatch provably
  coalescing multiple requests, and /models + /metrics answer 200
  mid-load;
- satellite: SPMD checkpoint captures carry canonical NamedSharding
  on every leaf (the PR 9 treatment extended to params/aux);
- satellite: telemetry_watch renders the serving line.
"""
import json
import os
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.serving import (DecodeEngine, DynamicBatcher, ServingEngine,
                               StepCache)
from mxnet_tpu.serving.engine import bucket_ladder

_FLAGS = ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH', 'MXTPU_FUSED_EVAL',
          'MXTPU_SERVE_MAX_BATCH', 'MXTPU_SERVE_MAX_WAIT_MS',
          'MXTPU_SERVE_SESSIONS', 'MXTPU_SERVE_BIND')


def _reload():
    for f in _FLAGS:
        flags.reload(f)


@pytest.fixture
def tele_on(tmp_path, monkeypatch):
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    _reload()
    telemetry._reset_for_tests()
    yield
    telemetry._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


def _mlp_sym(hidden=16, classes=4):
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=hidden, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu', name='relu1')
    fc2 = mx.sym.FullyConnected(act, num_hidden=classes, name='fc2')
    return mx.sym.SoftmaxOutput(fc2, name='softmax')


def _serving_engine(max_batch=8, seed=7, ctx=None):
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(_mlp_sym(), context=ctx or mx.cpu())
    mod.bind(data_shapes=[('data', (max_batch, 10))], for_training=False)
    mod.init_params()
    return ServingEngine(mod, max_batch=max_batch), mod


def _ref_predict(mod, x, batch):
    """Per-batch reference Module.predict over exactly x's rows."""
    os.environ['MXTPU_FUSED_EVAL'] = '0'
    flags.reload('MXTPU_FUSED_EVAL')
    try:
        pad = (-len(x)) % batch
        full = np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                           x.dtype)]) if pad else x
        it = mx.io.NDArrayIter(full, None, batch_size=batch)
        return mod.predict(it).asnumpy()[:len(x)]
    finally:
        os.environ.pop('MXTPU_FUSED_EVAL', None)
        flags.reload('MXTPU_FUSED_EVAL')


# ---------------------------------------------------------------------------
# bucket ladder + engine parity
# ---------------------------------------------------------------------------

def test_bucket_ladder():
    assert bucket_ladder(8) == [1, 2, 4, 8]
    assert bucket_ladder(1) == [1]
    assert bucket_ladder(12) == [1, 2, 4, 8, 12]   # non-power top kept
    with pytest.raises(ValueError):
        bucket_ladder(0)


def test_bucket_selection_and_flag(monkeypatch):
    eng, _ = _serving_engine(max_batch=8)
    assert eng.buckets == [1, 2, 4, 8]
    assert [eng.bucket_for(r) for r in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    with pytest.raises(ValueError, match='largest bucket'):
        eng.bucket_for(9)
    # the env flag drives the default ladder
    monkeypatch.setenv('MXTPU_SERVE_MAX_BATCH', '4')
    flags.reload('MXTPU_SERVE_MAX_BATCH')
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[('data', (4, 10))], for_training=False)
    mod.init_params()
    assert ServingEngine(mod).buckets == [1, 2, 4]
    monkeypatch.delenv('MXTPU_SERVE_MAX_BATCH')
    flags.reload('MXTPU_SERVE_MAX_BATCH')


def test_full_bucket_bit_identical_to_predict():
    """A full-bucket request runs the same forward at the same batch
    shape as Module.predict — answers must be bit-identical."""
    eng, mod = _serving_engine(max_batch=8)
    x = np.random.RandomState(0).randn(8, 10).astype(np.float32)
    out = eng.infer([x])[0]
    ref = _ref_predict(mod, x, 8)
    np.testing.assert_array_equal(out, ref)


def test_pad_strip_exactness():
    """Odd row counts pad up to a bucket and strip back exactly: the
    answer has exactly the request's rows, equal to the reference."""
    eng, mod = _serving_engine(max_batch=8)
    rng = np.random.RandomState(1)
    for rows in (1, 3, 5, 7):
        x = rng.standard_normal((rows, 10)).astype(np.float32)
        out = eng.infer([x])[0]
        assert out.shape == (rows, 4)
        # bit-exact even across bucket shapes: the forward is row-wise
        np.testing.assert_array_equal(out, _ref_predict(mod, x, 8))


def test_oversized_request_chunks():
    """Rows past the top bucket split across several dispatches and
    re-concatenate seamlessly."""
    eng, mod = _serving_engine(max_batch=8)
    x = np.random.RandomState(2).standard_normal((21, 10)) \
        .astype(np.float32)
    out = eng.infer([x])[0]
    assert out.shape == (21, 4)
    np.testing.assert_array_equal(out, _ref_predict(mod, x, 8))


def test_engine_input_validation():
    eng, _ = _serving_engine(max_batch=4)
    with pytest.raises(ValueError, match='0 rows'):
        eng.infer([np.zeros((0, 10), np.float32)])
    with pytest.raises(ValueError, match='per-example shape'):
        eng.infer([np.zeros((2, 9), np.float32)])
    with pytest.raises(ValueError, match='expected 1 input'):
        eng.infer([np.zeros((2, 10), np.float32)] * 2)


def test_spmd_engine_parity():
    """An SPMD-group module serves through the same engine: params
    place replicated on the mesh, inputs ride replicated (buckets need
    not divide dp), answers match the reference predict bit-exactly."""
    from mxnet_tpu.module.executor_group import SPMDExecutorGroup
    mx.random.seed(9)
    np.random.seed(9)
    mod = mx.mod.Module(_mlp_sym(),
                        context=[mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=[('data', (8, 10))], for_training=False)
    mod.init_params()
    assert isinstance(mod._exec_group, SPMDExecutorGroup)
    eng = ServingEngine(mod, max_batch=8)
    x = np.random.RandomState(10).standard_normal((5, 10)) \
        .astype(np.float32)
    out = eng.infer([x])[0]
    assert out.shape == (5, 4)
    np.testing.assert_array_equal(out, _ref_predict(mod, x, 8))


def test_engine_rejects_unsuitable_modules():
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    with pytest.raises(AssertionError):
        ServingEngine(mod)          # unbound
    with pytest.raises(ValueError, match='plain Module'):
        ServingEngine(object())


# ---------------------------------------------------------------------------
# dynamic batcher
# ---------------------------------------------------------------------------

def test_batcher_coalesces_queued_requests():
    """Requests submitted before the dispatcher runs coalesce into ONE
    padded dispatch (4 x 2 rows -> one batch of 8 in the ladder's top
    bucket), and every submitter gets exactly its own rows back."""
    eng, _ = _serving_engine(max_batch=8)
    x = np.random.RandomState(3).standard_normal((8, 10)) \
        .astype(np.float32)
    b = DynamicBatcher(eng, max_wait_ms=200)
    futs = [b.submit([x[2 * i:2 * i + 2]]) for i in range(4)]
    b.start()
    outs = [f.result(timeout=30) for f in futs]
    b.close()
    log = list(b.dispatch_log)
    assert log == [(8, 8, 4)], log     # 8 rows, bucket 8, 4 requests
    ref = eng.infer([x])[0]
    for i, o in enumerate(outs):
        assert o[0].shape == (2, 4)
        np.testing.assert_array_equal(o[0], ref[2 * i:2 * i + 2])


def test_batcher_concurrent_submitters_coalesce():
    """Submitters racing from threads: every request is answered and
    at least one dispatch carries more than one request (with a wait
    long enough to coalesce the burst)."""
    eng, _ = _serving_engine(max_batch=8)
    b = DynamicBatcher(eng, max_wait_ms=100).start()
    rng = np.random.RandomState(4)
    xs = [rng.standard_normal((2, 10)).astype(np.float32)
          for _ in range(6)]
    results = [None] * 6
    barrier = threading.Barrier(6)

    def client(i):
        barrier.wait()
        results[i] = b.predict([xs[i]], timeout=30)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log = list(b.dispatch_log)
    b.close()
    assert sum(r for r, _, _ in log) == 12      # every row served once
    assert max(n for _, _, n in log) > 1, log   # >=1 coalesced dispatch
    for i, r in enumerate(results):
        np.testing.assert_array_equal(r[0], eng.infer([xs[i]])[0])


def test_batcher_max_wait_flush():
    """A lone small request must not wait forever: it dispatches once
    MXTPU_SERVE_MAX_WAIT_MS expires, at its own (padded) size."""
    import time
    eng, _ = _serving_engine(max_batch=8)
    b = DynamicBatcher(eng, max_wait_ms=40).start()
    x = np.random.RandomState(5).standard_normal((3, 10)) \
        .astype(np.float32)
    t0 = time.monotonic()
    out = b.predict([x], timeout=30)
    waited = time.monotonic() - t0
    b.close()
    assert out[0].shape == (3, 4)
    assert list(b.dispatch_log) == [(3, 4, 1)]  # 3 rows -> bucket 4
    assert waited >= 0.03                       # the deadline bound it
    assert waited < 10


def test_batcher_error_propagates_per_request():
    eng, _ = _serving_engine(max_batch=4)
    b = DynamicBatcher(eng, max_wait_ms=5).start()
    with pytest.raises(ValueError, match='per-example shape'):
        b.submit([np.zeros((2, 9), np.float32)])
    ok = b.predict([np.zeros((2, 10), np.float32)], timeout=30)
    b.close()
    assert ok[0].shape == (2, 4)


def test_batcher_close_drains_queue():
    eng, _ = _serving_engine(max_batch=8)
    b = DynamicBatcher(eng, max_wait_ms=1000)
    x = np.ones((2, 10), np.float32)
    fut = b.submit([x])
    b.start()
    b.close()                       # drain=True: the answer still lands
    assert fut.result(timeout=5)[0].shape == (2, 4)
    # a submit that races past close() fails fast — never a future
    # that no dispatcher will ever resolve (the HTTP-handler-vs-stop
    # race)
    with pytest.raises(RuntimeError, match='closed'):
        b.submit([x])


def test_decode_failed_call_does_not_register_session():
    """A decode rejected on token validation must not touch the LRU
    table: a later correct call for that session is FRESH (zero
    state), never seeded with a reused slot's leftovers."""
    eng, _, H, F = _decode_setup(capacity=2)
    tok = np.random.RandomState(15).standard_normal((1, F)) \
        .astype(np.float32)
    eng.decode(['a'], [tok])
    eng.cache.drop('a')             # slot freed, device rows left dirty
    with pytest.raises(ValueError, match='shape'):
        eng.decode(['b'], [np.zeros((1, F + 1), np.float32)])
    assert 'b' not in eng.cache.sessions()
    o_b = eng.decode(['b'], [tok])[0]       # must be a FRESH step
    o_new = eng.decode(['c'], [tok])[0]
    np.testing.assert_array_equal(o_b, o_new)


# ---------------------------------------------------------------------------
# zero-recompile steady state + serving metrics
# ---------------------------------------------------------------------------

def test_zero_recompile_steady_state(tele_on):
    """After warmup the xla.compiles counter must be FLAT across an
    arbitrary request-size mix — the serving latency contract."""
    eng, _ = _serving_engine(max_batch=8)
    eng.warmup()
    snap = telemetry.snapshot()['counters']
    compiles0 = snap.get('xla.compiles', 0)
    assert compiles0 >= len(eng.buckets)    # warmup compiled the ladder
    b = DynamicBatcher(eng, max_wait_ms=2).start()
    rng = np.random.RandomState(6)
    futs = [b.submit([rng.standard_normal((int(rng.randint(1, 9)), 10))
                      .astype(np.float32)]) for _ in range(30)]
    for f in futs:
        f.result(timeout=60)
    b.close()
    snap = telemetry.snapshot()
    assert snap['counters'].get('xla.compiles', 0) == compiles0
    # the serving metric families flowed through the shared registry
    assert snap['counters'].get('serve.requests') == 30
    assert snap['counters'].get('serve.dispatches', 0) >= 1
    assert snap['histograms']['serve.request_latency']['count'] == 30
    assert snap['gauges'].get('serve.request_latency_p99_ms') is not None
    assert snap['gauges'].get('serve.buckets_warm') == len(eng.buckets)
    assert 0.0 <= snap['gauges'].get('serve.pad_fraction') <= 1.0
    # per-bucket programs landed in the registrar under serve.* names
    progs = telemetry.programs.snapshot_programs()
    assert any(n.startswith('serve.predict[') for n in progs)


# ---------------------------------------------------------------------------
# O(1) step cache
# ---------------------------------------------------------------------------

def test_step_cache_lru_table():
    c = StepCache(2)
    slots, fresh = c.lookup(['a', 'b'])
    assert fresh.all() and len(set(slots)) == 2
    s2, f2 = c.lookup(['a'])
    assert s2[0] == slots[0] and not f2[0]   # cached, same slot
    c.lookup(['c'])                          # evicts LRU = 'b'
    assert set(c.sessions()) == {'a', 'c'}
    s3, f3 = c.lookup(['b'])                 # re-admitted as fresh
    assert f3[0]                             # (evicting LRU 'a')
    assert set(c.sessions()) == {'c', 'b'}
    with pytest.raises(ValueError, match='duplicate'):
        c.lookup(['x', 'x'])
    assert c.drop('c') and not c.drop('c')


def _decode_setup(capacity=4, H=12, F=6, seed=11):
    mx.random.seed(seed)
    np.random.seed(seed)
    cell = mx.rnn.LSTMCell(num_hidden=H)
    x = mx.sym.Variable('data')
    states = [mx.sym.Variable('state_h'), mx.sym.Variable('state_c')]
    out, new_states = cell(x, states)
    step_sym = mx.sym.Group([out] + list(new_states))
    names = ('data', 'state_h', 'state_c')

    def bind(batch):
        m = mx.mod.Module(step_sym, data_names=names, label_names=[])
        m.bind(data_shapes=[('data', (batch, F)),
                            ('state_h', (batch, H)),
                            ('state_c', (batch, H))], for_training=False)
        return m

    mod = bind(4)
    mod.init_params(initializer=mx.initializer.Uniform(0.5))
    args, auxs = mod.get_params()
    ref = bind(1)
    ref.init_params(arg_params=args, aux_params=auxs)
    eng = DecodeEngine(mod, state_names=('state_h', 'state_c'),
                       capacity=capacity, max_batch=4)
    return eng, ref, H, F


def _ref_decode(ref, tokens, H):
    """Host-tracked per-step reference: feed states explicitly."""
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu.io import DataBatch
    h = np.zeros((1, H), np.float32)
    c = np.zeros((1, H), np.float32)
    outs = []
    for t in range(tokens.shape[0]):
        ref.forward(DataBatch(data=[nd.array(tokens[t][None]),
                                    nd.array(h), nd.array(c)]),
                    is_train=False)
        o = [a.asnumpy() for a in ref.get_outputs()]
        outs.append(o[0][0])
        h, c = o[1], o[2]
    return outs


def test_decode_matches_stepwise_reference():
    """Interleaved two-session decode through the device ring matches
    a host-tracked per-step reference for each session."""
    eng, ref, H, F = _decode_setup()
    rng = np.random.RandomState(12)
    T = 5
    toks = {s: rng.standard_normal((T, F)).astype(np.float32)
            for s in 'ab'}
    got = {s: [] for s in 'ab'}
    for t in range(T):
        o = eng.decode(['a', 'b'],
                       [np.stack([toks['a'][t], toks['b'][t]])])
        got['a'].append(o[0][0])
        got['b'].append(o[0][1])
    for s in 'ab':
        want = _ref_decode(ref, toks[s], H)
        for t in range(T):
            np.testing.assert_allclose(got[s][t], want[t],
                                       rtol=1e-5, atol=1e-6)


def test_decode_lru_eviction_and_fresh_restart():
    """Past capacity the LRU session evicts; when it returns it starts
    from zero state — identical to a brand-new session."""
    eng, _, H, F = _decode_setup(capacity=3)
    rng = np.random.RandomState(13)
    tok = rng.standard_normal((1, F)).astype(np.float32)
    for s in ('a', 'b', 'c'):
        eng.decode([s], [tok])
    eng.decode(['d'], [tok])                  # capacity 3: evicts 'a'
    assert 'a' not in eng.cache.sessions()
    o_back = eng.decode(['a'], [tok])[0]      # fresh restart
    o_new = eng.decode(['fresh'], [tok])[0]
    np.testing.assert_array_equal(o_back, o_new)


def test_decode_zero_recompile_and_o1(tele_on):
    """After warmup, T decode steps run T fixed-shape dispatches with
    ZERO further compiles — the O(1)-per-token contract."""
    eng, _, H, F = _decode_setup()
    eng.warmup()
    compiles0 = telemetry.snapshot()['counters'].get('xla.compiles', 0)
    rng = np.random.RandomState(14)
    for _ in range(10):
        eng.decode(['a', 'b', 'c'],
                   [rng.standard_normal((3, F)).astype(np.float32)])
    snap = telemetry.snapshot()
    assert snap['counters'].get('xla.compiles', 0) == compiles0
    assert snap['counters'].get('serve.decode_steps') >= 10
    assert snap['gauges'].get('serve.sessions_live') == 3


def test_decode_failed_dispatch_resets_ring_not_engine():
    """A runtime failure in the step program must not brick the
    engine: the donated ring rebuilds (sessions restart from zero
    state) and the next decode works."""
    eng, _, H, F = _decode_setup()
    tok = np.random.RandomState(16).standard_normal((1, F)) \
        .astype(np.float32)
    eng.decode(['a'], [tok])
    bucket = eng.buckets[0]
    good = eng._programs[bucket]

    def boom(*a, **k):
        raise RuntimeError('injected device failure')

    eng._programs[bucket] = (boom, good[1])
    with pytest.raises(RuntimeError, match='injected'):
        eng.decode(['a'], [tok])
    eng._programs[bucket] = good
    # engine still serves; 'a' restarted from zero state like a fresh
    # session (the ring was rebuilt)
    o_a = eng.decode(['a'], [tok])[0]
    o_new = eng.decode(['fresh'], [tok])[0]
    np.testing.assert_array_equal(o_a, o_new)


def test_decode_contract_validation():
    eng, _, H, F = _decode_setup()
    with pytest.raises(ValueError, match='empty'):
        eng.decode([], [np.zeros((0, F), np.float32)])
    with pytest.raises(ValueError, match='largest bucket'):
        eng.decode(list('abcde'), [np.zeros((5, F), np.float32)])
    with pytest.raises(ValueError, match='shape'):
        eng.decode(['a'], [np.zeros((1, F + 1), np.float32)])


# ---------------------------------------------------------------------------
# HTTP end to end
# ---------------------------------------------------------------------------

def _post(port, path, body, ctype='application/json'):
    req = urllib.request.Request(
        'http://127.0.0.1:%d%s' % (port, path), data=body,
        headers={'Content-Type': ctype})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get(port, path):
    try:
        with urllib.request.urlopen(
                'http://127.0.0.1:%d%s' % (port, path), timeout=10) as r:
            return r.status, r.read().decode('utf-8')
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode('utf-8')


def test_http_serve_and_query_end_to_end(tele_on, tmp_path):
    """The acceptance drive, checkpoint -> endpoint: a trained
    module's save_checkpoint artifact loads through
    ServingEngine.from_checkpoint onto an ephemeral port, concurrent
    HTTP clients get BIT-identical answers to Module.predict, >= 1
    dispatch provably coalesces multiple requests, /models + a 200
    /metrics scrape answer mid-load, and xla.compiles stays flat
    after bucket warmup."""
    from mxnet_tpu.serving.http import start_server
    mx.random.seed(7)
    np.random.seed(7)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    X0 = np.random.RandomState(0).randn(32, 10).astype(np.float32)
    y0 = (np.random.RandomState(1).rand(32) * 4).astype(int) \
        .astype(np.float32)
    mod.fit(mx.io.NDArrayIter(X0, y0, batch_size=8,
                              label_name='softmax_label'), num_epoch=1)
    prefix = str(tmp_path / 'model')
    mod.save_checkpoint(prefix, 1)
    eng = ServingEngine.from_checkpoint(prefix, 1,
                                        data_shapes=[('data', (10,))],
                                        context=mx.cpu(), max_batch=8)
    eng.warmup()
    compiles0 = telemetry.snapshot()['counters'].get('xla.compiles', 0)
    srv = start_server(eng, DynamicBatcher(eng, max_wait_ms=100), port=0)
    try:
        port = srv.port
        X = np.random.RandomState(20).standard_normal((8, 10)) \
            .astype(np.float32)
        results = {}
        scrapes = {}
        barrier = threading.Barrier(5)

        def client(i):
            barrier.wait()
            body = json.dumps(
                {'data': X[2 * i:2 * i + 2].tolist()}).encode()
            results[i] = _post(port, '/predict', body)

        def scraper():
            barrier.wait()
            scrapes['metrics'] = _get(port, '/metrics')

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)] + \
            [threading.Thread(target=scraper)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # zero recompiles through the concurrent serving drive (read
        # BEFORE the reference predict below compiles its own program)
        assert telemetry.snapshot()['counters'].get('xla.compiles', 0) \
            == compiles0

        # parity: each client's slice is BIT-identical to the trained
        # module's own predict over the same rows
        ref = _ref_predict(mod, X, 8)
        for i in range(4):
            code, payload = results[i]
            assert code == 200
            assert payload['rows'] == 2
            np.testing.assert_array_equal(
                np.array(payload['outputs'][0], np.float32),
                ref[2 * i:2 * i + 2])
        # >=1 dispatch provably coalesced multiple requests
        log = list(srv.batcher.dispatch_log)
        assert max(n for _, _, n in log) > 1, log
        assert sum(r for r, _, _ in log) == 8
        # mid-load metrics scrape answered 200 with exposition text
        code, body = scrapes['metrics']
        assert code == 200
        # /metrics again after the load: the serve family is present
        code, body = _get(port, '/metrics')
        assert code == 200
        assert 'mxtpu_serve_requests_total' in body
        assert 'mxtpu_serve_request_latency_ms' in body
        # /models describes the ladder
        code, body = _get(port, '/models')
        m = json.loads(body)['models'][0]
        assert m['buckets'] == [1, 2, 4, 8] and m['warmed']
        # /healthz probe
        code, body = _get(port, '/healthz')
        assert code == 200 and json.loads(body)['status'] == 'ok'
        # npy body round-trips
        import io as _io
        buf = _io.BytesIO()
        np.save(buf, X[:3])
        code, payload = _post(port, '/predict', buf.getvalue(),
                              ctype='application/x-npy')
        assert code == 200 and payload['rows'] == 3
        # malformed body answers 400, counted — the server survives
        code, payload = _post(port, '/predict', b'garbage')
        assert code == 400 and 'error' in payload
    finally:
        srv.stop()


def _two_input_sym():
    a = mx.sym.Variable('data_a')
    b = mx.sym.Variable('data_b')
    fa = mx.sym.FullyConnected(a, num_hidden=8, name='ma')
    fb = mx.sym.FullyConnected(b, num_hidden=8, name='mb')
    head = mx.sym.FullyConnected(fa + fb, num_hidden=3, name='head')
    return mx.sym.SoftmaxOutput(head, name='softmax')


def test_http_multi_input_end_to_end(tele_on):
    """PR 12 residue closed: a multi-input graph served through the
    `inputs` JSON form answers HTTP->batcher->engine with
    Module.predict parity — not just parsing coverage. Also pins the
    single-input `data` form rejecting a multi-input model with a 400
    that names the inputs."""
    from mxnet_tpu.serving.http import start_server
    mx.random.seed(11)
    np.random.seed(11)
    mod = mx.mod.Module(_two_input_sym(),
                        data_names=('data_a', 'data_b'),
                        context=mx.cpu())
    mod.bind(data_shapes=[('data_a', (8, 6)), ('data_b', (8, 4))],
             for_training=False)
    mod.init_params()
    eng = ServingEngine(mod, max_batch=8)
    eng.warmup()
    srv = start_server(eng, DynamicBatcher(eng, max_wait_ms=50), port=0)
    try:
        port = srv.port
        rs = np.random.RandomState(3)
        Xa = rs.standard_normal((6, 6)).astype(np.float32)
        Xb = rs.standard_normal((6, 4)).astype(np.float32)

        # reference: the module's own predict over the same rows (pad
        # to the bound batch; multi-input NDArrayIter orders by the
        # module's data_names)
        os.environ['MXTPU_FUSED_EVAL'] = '0'
        flags.reload('MXTPU_FUSED_EVAL')
        try:
            pad = (-len(Xa)) % 8
            full_a = np.concatenate([Xa, np.zeros((pad, 6), np.float32)])
            full_b = np.concatenate([Xb, np.zeros((pad, 4), np.float32)])
            it = mx.io.NDArrayIter({'data_a': full_a, 'data_b': full_b},
                                   None, batch_size=8)
            ref = mod.predict(it).asnumpy()[:len(Xa)]
        finally:
            os.environ.pop('MXTPU_FUSED_EVAL', None)
            flags.reload('MXTPU_FUSED_EVAL')

        # concurrent clients through the `inputs` form coalesce and
        # come back row-exact
        results = {}
        slices = [(0, 2), (2, 6)]
        barrier = threading.Barrier(len(slices))

        def client(i):
            lo, hi = slices[i]
            barrier.wait()
            body = json.dumps(
                {'inputs': {'data_a': Xa[lo:hi].tolist(),
                            'data_b': Xb[lo:hi].tolist()}}).encode()
            results[i] = _post(port, '/predict', body)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(slices))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (lo, hi) in enumerate(slices):
            code, payload = results[i]
            assert code == 200, payload
            assert payload['rows'] == hi - lo
            np.testing.assert_allclose(
                np.array(payload['outputs'][0], np.float32),
                ref[lo:hi], rtol=1e-6, atol=1e-7)

        # a missing input names the gap; the single-input `data` form
        # names the inputs to use instead
        code, payload = _post(port, '/predict', json.dumps(
            {'inputs': {'data_a': Xa[:1].tolist()}}).encode())
        assert code == 400 and 'data_b' in payload['error']
        code, payload = _post(port, '/predict', json.dumps(
            {'data': Xa[:1].tolist()}).encode())
        assert code == 400 and 'inputs' in payload['error']
    finally:
        srv.stop()


@pytest.mark.slow
def test_serve_model_cli_whole_process(tmp_path):
    """The literal tools/serve_model.py drive in its own process:
    checkpoint on disk -> CLI -> concurrent HTTP clients bit-identical
    to Module.predict (heavy: a full interpreter + jax import + ladder
    warmup per run, hence the slow lane)."""
    import subprocess
    import time
    mx.random.seed(7)
    np.random.seed(7)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    X0 = np.random.RandomState(0).randn(32, 10).astype(np.float32)
    y0 = (np.random.RandomState(1).rand(32) * 4).astype(int) \
        .astype(np.float32)
    mod.fit(mx.io.NDArrayIter(X0, y0, batch_size=8,
                              label_name='softmax_label'), num_epoch=1)
    prefix = str(tmp_path / 'model')
    mod.save_checkpoint(prefix, 1)
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, 'tools', 'serve_model.py'),
         prefix, '--epoch', '1', '--data-shape', '10', '--port', '0',
         '--context', 'cpu', '--max-batch', '8', '--max-wait-ms', '100'],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        port = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line and proc.poll() is not None:
                break
            if 'on port' in line:
                port = int(line.rsplit('port', 1)[1].split()[0])
                break
        assert port, 'server never announced its port'
        X = np.random.RandomState(20).standard_normal((8, 10)) \
            .astype(np.float32)
        results = {}
        barrier = threading.Barrier(4)

        def client(i):
            barrier.wait()
            body = json.dumps(
                {'data': X[2 * i:2 * i + 2].tolist()}).encode()
            results[i] = _post(port, '/predict', body)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ref = _ref_predict(mod, X, 8)
        for i in range(4):
            code, payload = results[i]
            assert code == 200, payload
            np.testing.assert_array_equal(
                np.array(payload['outputs'][0], np.float32),
                ref[2 * i:2 * i + 2])
        code, body = _get(port, '/models')
        assert code == 200
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_serve_model_cli_help():
    import subprocess
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, 'tools', 'serve_model.py'),
         '--help'], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert 'serve' in out.stdout.lower()
    assert '--data-shape' in out.stdout


# ---------------------------------------------------------------------------
# satellite: SPMD checkpoint capture carries canonical NamedSharding
# ---------------------------------------------------------------------------

def test_spmd_capture_leaves_named_sharding(tmp_path, monkeypatch):
    """PR 9 residue: params/aux leaves captured from fused-window
    outputs are relabelled (or resharded) onto the canonical
    NamedSharding before the orbax save — no GSPMDSharding leaf
    reaches serialization, so the engine-facing load path is
    warning-free."""
    from jax.sharding import NamedSharding
    from mxnet_tpu.module import checkpointing as ckmod
    monkeypatch.setenv('MXTPU_CKPT_DIR', str(tmp_path / 'ckpt'))
    monkeypatch.setenv('MXTPU_CKPT_EVERY', '4')
    monkeypatch.setenv('MXTPU_CKPT_ASYNC', '0')
    monkeypatch.setenv('MXTPU_CKPT_RESUME', '0')
    for f in ('MXTPU_CKPT_DIR', 'MXTPU_CKPT_EVERY', 'MXTPU_CKPT_ASYNC',
              'MXTPU_CKPT_RESUME'):
        flags.reload(f)
    bad = []
    orig = ckmod.TrainCheckpointer._capture

    def spy(self):
        tree, meta = orig(self)
        for fam in ('params', 'aux', 'opt', 'gacc'):
            for k, v in (tree.get(fam) or {}).items():
                if not isinstance(v.sharding, NamedSharding):
                    bad.append((fam, k, type(v.sharding).__name__))
        return tree, meta

    monkeypatch.setattr(ckmod.TrainCheckpointer, '_capture', spy)
    mx.random.seed(3)
    np.random.seed(3)
    X = np.random.RandomState(3).randn(64, 10).astype(np.float32)
    y = (np.random.RandomState(4).rand(64) * 4).astype(int) \
        .astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16,
                           label_name='softmax_label')
    mod = mx.mod.Module(_mlp_sym(hidden=10),
                        context=[mx.cpu(i) for i in range(8)])
    mod.fit(it, num_epoch=1, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),
                              ('momentum', 0.9)),
            kvstore='device')
    assert not bad, bad
    for f in ('MXTPU_CKPT_DIR', 'MXTPU_CKPT_EVERY', 'MXTPU_CKPT_ASYNC',
              'MXTPU_CKPT_RESUME'):
        monkeypatch.delenv(f, raising=False)
        flags.reload(f)


# ---------------------------------------------------------------------------
# satellite: watch line
# ---------------------------------------------------------------------------

def _tools():
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    tools = os.path.join(repo, 'tools')
    if tools not in sys.path:
        sys.path.insert(0, tools)


def test_watch_renders_serving_line():
    _tools()
    import telemetry_watch
    summary = {
        'elapsed_s': 60.0, 'host': 0,
        'snapshot': {
            'counters': {'serve.requests': 1240, 'serve.errors': 2},
            'gauges': {'serve.request_latency_p99_ms': 18.7,
                       'serve.queue_depth': 3,
                       'serve.batch_size_p50': 8,
                       'serve.pad_fraction': 0.12},
            'histograms': {'serve.request_latency': {
                'count': 1240, 'sum': 14000.0, 'p50': 11.2,
                'p95': 17.0}},
        },
    }
    frame = '\n'.join(telemetry_watch.render(summary, reqs_per_s=310.2))
    line = [ln for ln in frame.splitlines() if 'serving' in ln]
    assert len(line) == 1
    ln = line[0]
    assert '1240 reqs' in ln and '310.20 req/s' in ln
    assert 'p50 11.2 ms' in ln and 'p99 18.7 ms' in ln
    assert 'queue 3' in ln and 'batch p50 8' in ln and 'pad 12%' in ln
    assert '2 errors' in ln
    # no serve metrics -> no serving line (and no crash)
    frame = '\n'.join(telemetry_watch.render(
        {'snapshot': {'counters': {}, 'gauges': {}, 'histograms': {}}}))
    assert 'serving' not in frame

