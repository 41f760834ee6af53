"""Request tracing, SLO plane and flight recorder (ISSUE 14).

Contracts under test:
- trace propagation: 4 submitters coalescing into ONE dispatch emit 4
  ``trace`` JSONL records sharing that dispatch's span id, each with
  the queue/coalesce/pad/dispatch/fetch/split breakdown;
- the HTTP drive: a client-supplied ``X-Request-Id`` is echoed and
  names a trace record whose stage sum tracks the measured latency;
  ``Accept: application/x-npy`` answers a raw .npy body;
- exemplars: the ``serve.request_latency`` /metrics summary carries a
  trace-id exemplar on its top quantile line;
- SLO plane: sustained injected 5xx flips /healthz to the
  ``slo_degraded`` state (distinct from hung/non-finite) and back on
  recovery, with the slo.* gauges live;
- flight recorder: dumps on an injected ``hang:`` fault (watchdog
  stall) and an injected ``nan-grad:`` fault (non-finite incident),
  each carrying the pre-incident records;
- zero overhead: with MXTPU_TELEMETRY=0 no trace ids, no ring, no SLO
  state, no telemetry I/O; lowering is byte-identical with the
  recorder on or off;
- satellites: roofline gauges republish at the cluster sync cadence,
  telemetry_watch renders the SLO + stage lines,
  tools/trace_report.py renders a dump.
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
from mxnet_tpu import faults, telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.serving import DynamicBatcher, ServingEngine
from mxnet_tpu.telemetry import export as tele_export
from mxnet_tpu.telemetry import flight, slo, trace

_FLAGS = ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH', 'MXTPU_HEALTH',
          'MXTPU_SLO_LATENCY_MS', 'MXTPU_SLO_ERROR_PCT',
          'MXTPU_SLO_WINDOW', 'MXTPU_FLIGHT_RECORDER',
          'MXTPU_WATCHDOG_SECS', 'MXTPU_FAULT_INJECT',
          'MXTPU_FUSED_FIT', 'MXTPU_SERVE_MAX_WAIT_MS',
          'MXTPU_TELEMETRY_SYNC_EVERY')


def _reload():
    for f in _FLAGS:
        flags.reload(f)


@pytest.fixture
def tele_on(tmp_path, monkeypatch):
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    _reload()
    telemetry._reset_for_tests()
    faults._reset_for_tests()
    yield tmp_path
    telemetry._reset_for_tests()
    faults._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


@pytest.fixture
def tele_off(monkeypatch):
    monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
    _reload()
    telemetry._reset_for_tests()
    faults._reset_for_tests()
    yield
    telemetry._reset_for_tests()
    faults._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


def _mlp_sym(hidden=16, classes=4):
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=hidden, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu', name='relu1')
    fc2 = mx.sym.FullyConnected(act, num_hidden=classes, name='fc2')
    return mx.sym.SoftmaxOutput(fc2, name='softmax')


def _serving_engine(max_batch=8, seed=7):
    mx.random.seed(seed)
    np.random.seed(seed)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[('data', (max_batch, 10))], for_training=False)
    mod.init_params()
    return ServingEngine(mod, max_batch=max_batch), mod


def _jsonl(path):
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def _flush_sink():
    if telemetry._state.sink is not None:
        telemetry._state.sink.flush()


# ---------------------------------------------------------------------------
# trace ids
# ---------------------------------------------------------------------------

def test_trace_id_minting_and_headers():
    assert len(trace.new_trace_id()) == 16
    assert len(trace.new_span_id()) == 8
    assert trace.from_headers({'X-Request-Id': 'abc-123'}) == 'abc-123'
    # sanitized + bounded
    got = trace.from_headers({'X-Request-Id': 'a b!' + 'x' * 100})
    assert got.startswith('a_b_') and len(got) <= 64
    tp = '00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01'
    assert trace.from_headers({'traceparent': tp}) \
        == '0af7651916cd43dd8448eb211c80319c'
    assert trace.from_headers({'traceparent': 'garbage'}) is None
    assert trace.from_headers({}) is None
    # X-Request-Id wins over traceparent
    assert trace.from_headers({'X-Request-Id': 'mine',
                               'traceparent': tp}) == 'mine'


# ---------------------------------------------------------------------------
# trace propagation through a provably-coalesced dispatch
# ---------------------------------------------------------------------------

def test_coalesced_dispatch_traces_share_span(tele_on):
    """4 submitters -> ONE dispatch -> 4 trace records sharing its
    span id, each carrying the full stage breakdown."""
    eng, _ = _serving_engine(max_batch=8)
    x = np.random.RandomState(3).standard_normal((8, 10)) \
        .astype(np.float32)
    b = DynamicBatcher(eng, max_wait_ms=200)
    futs = [b.submit([x[2 * i:2 * i + 2]], trace_id='client-%d' % i)
            for i in range(4)]
    b.start()
    for f in futs:
        f.result(timeout=60)
    b.close()
    assert list(b.dispatch_log) == [(8, 8, 4)]   # provably coalesced
    _flush_sink()
    traces = [r for r in _jsonl(tele_on / 't.jsonl')
              if r['type'] == 'trace']
    assert len(traces) == 4
    assert sorted(t['trace_id'] for t in traces) \
        == ['client-%d' % i for i in range(4)]
    spans = {t['dispatch_span'] for t in traces}
    assert len(spans) == 1 and None not in spans   # ONE shared span
    for t in traces:
        assert t['status'] == 'ok' and t['rows'] == 2
        for stage in trace.STAGES:
            assert stage + '_ms' in t['stages'], (stage, t)
    # the shared-stage values are identical across passengers
    assert len({t['stages']['dispatch_ms'] for t in traces}) == 1
    # per-request queue waits were logged host-side too
    assert len(b.queue_wait_log) == 4


def test_trace_off_with_telemetry_off(tele_off):
    """MXTPU_TELEMETRY=0: no trace ids are minted, no ring exists, no
    SLO state, and the batcher round performs zero telemetry I/O."""
    io_before = tele_export._io_calls
    eng, _ = _serving_engine(max_batch=4)
    b = DynamicBatcher(eng, max_wait_ms=5).start()
    fut = b.submit([np.zeros((2, 10), np.float32)], trace_id='ignored')
    fut.result(timeout=60)
    b.close()
    assert not trace.enabled()
    assert trace.start('x') is None
    assert not flight.enabled()
    assert flight._state.ring is None
    assert flight.dump('nope') is None
    assert not slo.enabled()
    assert slo.snapshot_slo() is None
    assert tele_export._io_calls == io_before
    assert telemetry.get_registry().names() == []
    # no telemetry/flight thread appeared (batcher's own threads are
    # its dispatcher + fetch pool, named mxtpu-serve-*)
    for t in threading.enumerate():
        assert not t.name.startswith(('mxtpu-telemetry', 'mxtpu-flight'))


def test_lowering_byte_identical_with_recorder_on_off(tmp_path,
                                                      monkeypatch):
    """The recorder (and the whole tracing plane) is host-side only:
    the executor's fused fwd+bwd lowers byte-identically with
    MXTPU_FLIGHT_RECORDER on vs off."""
    import jax.numpy as jnp
    from mxnet_tpu import random as _random

    def _lowered_text(ring_on):
        telemetry._reset_for_tests()
        monkeypatch.setenv('MXTPU_TELEMETRY', '1')
        monkeypatch.setenv('MXTPU_TELEMETRY_PATH',
                           str(tmp_path / ('f%d.jsonl' % ring_on)))
        monkeypatch.setenv('MXTPU_FLIGHT_RECORDER',
                           '2048' if ring_on else '0')
        _reload()
        telemetry._reset_for_tests()
        assert flight.enabled() is bool(ring_on)
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
        mod.bind(data_shapes=[('data', (8, 10))],
                 label_shapes=[('softmax_label', (8,))])
        mod.init_params()
        ex = mod._exec_group.execs[0]
        arg_data = tuple(a._data for a in ex.arg_arrays)
        aux_data = tuple(a._data for a in ex.aux_arrays)
        heads = (jnp.ones((8, 4), jnp.float32),)
        return ex._fwd_bwd.lower(arg_data, aux_data, _random.next_key(),
                                 heads).as_text()

    try:
        assert _lowered_text(True) == _lowered_text(False)
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
        _reload()


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------

def test_request_latency_exemplar_on_metrics(tele_on):
    from mxnet_tpu.telemetry import serve as tserve
    eng, _ = _serving_engine(max_batch=4)
    b = DynamicBatcher(eng, max_wait_ms=2).start()
    b.predict([np.zeros((2, 10), np.float32)], trace_id='slowpoke')
    b.close()
    snap = telemetry.snapshot()
    ex = snap['histograms']['serve.request_latency'].get('exemplar')
    assert ex and ex['labels']['trace_id'] == 'slowpoke'
    body = tserve.render_prometheus(snap, host=0)
    # the exemplar lands as a sibling info-style gauge (the declared
    # 0.0.4 text format has no exemplar syntax — a '#' suffix on a
    # sample line would fail a strict scraper)
    ex_lines = [ln for ln in body.splitlines()
                if ln.startswith('mxtpu_serve_request_latency_ms'
                                 '_exemplar{')]
    assert len(ex_lines) == 1, body
    assert 'trace_id="slowpoke"' in ex_lines[0]
    # the quantile sample lines themselves stay plain-parseable
    lat = [ln for ln in body.splitlines()
           if ln.startswith('mxtpu_serve_request_latency_ms{')
           and 'quantile' in ln]
    assert lat and all('#' not in ln for ln in lat)


# ---------------------------------------------------------------------------
# HTTP end to end: client trace id, breakdown sum, npy accept
# ---------------------------------------------------------------------------

def _post(port, path, body, ctype='application/json', headers=None):
    hdrs = {'Content-Type': ctype}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        'http://127.0.0.1:%d%s' % (port, path), data=body, headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _get(port, path):
    try:
        with urllib.request.urlopen(
                'http://127.0.0.1:%d%s' % (port, path), timeout=10) as r:
            return r.status, r.read().decode('utf-8')
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode('utf-8')


def test_http_trace_breakdown_and_npy_accept(tele_on):
    """The acceptance drive: a client-supplied trace id yields a trace
    record whose stage sum tracks the measured request latency, the id
    is echoed, and Accept: application/x-npy answers raw npy."""
    from mxnet_tpu.serving.http import start_server
    eng, _ = _serving_engine(max_batch=8)
    eng.warmup()
    srv = start_server(eng, DynamicBatcher(eng, max_wait_ms=5), port=0)
    try:
        port = srv.port
        X = np.random.RandomState(1).standard_normal((3, 10)) \
            .astype(np.float32)
        body = json.dumps({'data': X.tolist()}).encode()
        code, raw, hdrs = _post(port, '/predict', body,
                                headers={'X-Request-Id': 'wire-42'})
        assert code == 200
        assert hdrs.get('X-Request-Id') == 'wire-42'
        payload = json.loads(raw)
        assert payload['trace_id'] == 'wire-42'
        ref = np.array(payload['outputs'][0], np.float32)

        # npy accept: raw .npy body, first output, rows header
        import io as _io
        code, raw, hdrs = _post(port, '/predict', body,
                                headers={'Accept': 'application/x-npy',
                                         'X-Request-Id': 'wire-43'})
        assert code == 200
        assert hdrs.get('X-Rows') == '3' and hdrs.get('X-Outputs') == '1'
        got = np.load(_io.BytesIO(raw), allow_pickle=False)
        np.testing.assert_array_equal(got, ref)

        # with telemetry on and NO client id, a minted one is echoed
        code, raw, hdrs = _post(port, '/predict', body)
        assert code == 200
        minted = hdrs.get('X-Request-Id')
        assert minted and len(minted) == 16
    finally:
        srv.stop()
    _flush_sink()
    traces = {r['trace_id']: r
              for r in _jsonl(tele_on / 't.jsonl')
              if r['type'] == 'trace'}
    assert {'wire-42', 'wire-43', minted} <= set(traces)
    t = traces['wire-42']
    assert t['rows'] == 3 and t['status'] == 'ok'
    stage_sum = sum(t['stages'].values())
    # the breakdown accounts for ~the measured latency (host thread
    # handoffs are the only unmeasured gaps)
    assert 0.3 * t['total_ms'] <= stage_sum <= 1.7 * t['total_ms'], t


# ---------------------------------------------------------------------------
# SLO plane
# ---------------------------------------------------------------------------

def _arm_slo(monkeypatch, tmp_path, latency_ms='100000', error_pct='50',
             window='16'):
    monkeypatch.setenv('MXTPU_SLO_LATENCY_MS', latency_ms)
    monkeypatch.setenv('MXTPU_SLO_ERROR_PCT', error_pct)
    monkeypatch.setenv('MXTPU_SLO_WINDOW', window)
    _reload()
    telemetry._reset_for_tests()


def test_slo_degraded_and_recovery_direct(tele_on, monkeypatch):
    from mxnet_tpu.telemetry import serve as tserve
    _arm_slo(monkeypatch, tele_on)
    assert slo.enabled()
    # 16 bad requests: burn = 100/50 = 2x over a full window
    for _ in range(16):
        slo.note_request(1.0, error=True)
    ok, body = tserve.healthz_payload()
    assert not ok and body['status'] == 'slo_degraded'
    assert body['slo']['degraded'] and body['slo']['burn_rate'] >= 1.0
    g = telemetry.snapshot()['gauges']
    assert g['slo.degraded'] == 1
    assert g['slo.burn_rate'] >= 1.0
    assert g['slo.error_budget_pct'] == 50.0
    # the degraded transition dumped the flight recorder
    assert os.path.exists(tele_on / 'flight-slo-burn.jsonl')
    # recovery: a window of good traffic clears the state
    for _ in range(16):
        slo.note_request(1.0, error=False)
    ok, body = tserve.healthz_payload()
    assert ok and body['status'] == 'ok'
    assert telemetry.snapshot()['gauges']['slo.degraded'] == 0
    # the transition records landed in the JSONL stream
    _flush_sink()
    events = [r['event'] for r in _jsonl(tele_on / 't.jsonl')
              if r['type'] == 'slo']
    assert events == ['degraded', 'recovered']


def test_slo_http_5xx_flip_and_recovery(tele_on, monkeypatch):
    """Sustained injected 5xx flips the serving /healthz to
    slo_degraded (503) and back once traffic recovers."""
    from mxnet_tpu.serving.http import start_server
    _arm_slo(monkeypatch, tele_on)
    eng, _ = _serving_engine(max_batch=4)
    srv = start_server(eng, DynamicBatcher(eng, max_wait_ms=1), port=0)
    try:
        port = srv.port
        body = json.dumps({'data': [[0.0] * 10]}).encode()
        code, _body = _get(port, '/healthz')
        assert code == 200 and json.loads(_body)['status'] == 'ok'

        def boom(arrays, timings=None):
            raise RuntimeError('injected 5xx')

        good = eng.dispatch_rows
        eng.dispatch_rows = boom
        for _ in range(16):
            code, raw, _h = _post(port, '/predict', body)
            assert code == 500
        code, raw = _get(port, '/healthz')
        assert code == 503, raw
        assert json.loads(raw)['status'] == 'slo_degraded'
        # recovery: restore the engine, run a window of good traffic
        eng.dispatch_rows = good
        for _ in range(16):
            code, raw, _h = _post(port, '/predict', body)
            assert code == 200
        code, raw = _get(port, '/healthz')
        assert code == 200 and json.loads(raw)['status'] == 'ok'
    finally:
        srv.stop()


def test_slo_client_errors_do_not_burn_budget(tele_on, monkeypatch):
    """400s (malformed bodies) never count against the error budget."""
    from mxnet_tpu.serving.http import ServingServer
    _arm_slo(monkeypatch, tele_on)
    eng, _ = _serving_engine(max_batch=4)
    srv = ServingServer(eng, DynamicBatcher(eng, max_wait_ms=1))
    srv.batcher.start()
    try:
        for _ in range(20):
            code, payload = srv.predict_payload(b'garbage', None)
            assert code == 400
    finally:
        srv.batcher.close()
    snap = slo.snapshot_slo()
    assert snap['window_requests'] == 0 and not snap['degraded']


# ---------------------------------------------------------------------------
# flight recorder on injected faults
# ---------------------------------------------------------------------------

def _fit_small(num_epoch=1, batch=4, n=16):
    np.random.seed(0)
    mx.random.seed(0)
    X = np.random.randn(n, 10).astype(np.float32)
    y = (np.random.rand(n) * 4).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch,
                           label_name='softmax_label')
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),))
    return mod


def test_flight_dump_on_injected_hang(tele_on, monkeypatch):
    """An injected hang: fault wedges a dispatch seam; the watchdog
    trips and dumps flight-hang.jsonl with the pre-stall spans."""
    monkeypatch.setenv('MXTPU_FAULT_INJECT', 'hang:2:2')
    monkeypatch.setenv('MXTPU_WATCHDOG_SECS', '0.5')
    monkeypatch.setenv('MXTPU_FUSED_FIT', '0')   # per-step marks/seams
    _reload()
    telemetry._reset_for_tests()
    faults._reset_for_tests()
    _fit_small()
    path = tele_on / 'flight-hang.jsonl'
    assert path.exists(), 'watchdog trip did not dump the recorder'
    recs = _jsonl(path)
    assert recs[0]['type'] == 'flight' and recs[0]['reason'] == 'hang'
    assert recs[0]['records'] == len(recs) - 1
    # the ring carried the pre-stall spans (the per-batch loop's)
    assert any(r.get('type') == 'span' for r in recs[1:])
    # the hang incident itself is on the normal JSONL stream
    _flush_sink()
    assert any(r['type'] == 'hang'
               for r in _jsonl(tele_on / 't.jsonl'))


def test_flight_dump_on_injected_nan_grad(tele_on, monkeypatch):
    """An injected nan-grad: fault triggers a non-finite incident; the
    health plane dumps flight-nonfinite.jsonl."""
    monkeypatch.setenv('MXTPU_FAULT_INJECT', 'nan-grad:1')
    monkeypatch.setenv('MXTPU_HEALTH', '1')
    monkeypatch.setenv('MXTPU_FUSED_FIT', '0')
    _reload()
    telemetry._reset_for_tests()
    faults._reset_for_tests()
    _fit_small()
    path = tele_on / 'flight-nonfinite.jsonl'
    assert path.exists(), 'non-finite incident did not dump the recorder'
    recs = _jsonl(path)
    assert recs[0]['type'] == 'flight' \
        and recs[0]['reason'] == 'nonfinite'
    assert len(recs) > 1
    _flush_sink()
    assert any(r['type'] == 'health' and r.get('event') == 'nonfinite'
               for r in _jsonl(tele_on / 't.jsonl'))


def test_flight_ring_bounded_and_dump_capped(tele_on, monkeypatch):
    monkeypatch.setenv('MXTPU_FLIGHT_RECORDER', '4')
    _reload()
    telemetry._reset_for_tests()
    for i in range(10):
        telemetry.event('tick', i=i)
    ring = flight.snapshot_flight()
    assert len(ring) == 4                      # bounded
    assert [r['i'] for r in ring] == [6, 7, 8, 9]   # newest retained
    # dumps per reason are bounded too (newest wins, no disk fill)
    paths = [flight.dump('spam') for _ in range(10)]
    assert sum(1 for p in paths if p) == flight._MAX_DUMPS_PER_REASON


# ---------------------------------------------------------------------------
# satellite: roofline republish at the cluster sync cadence
# ---------------------------------------------------------------------------

def test_cluster_sync_republishes_roofline(tele_on, monkeypatch):
    from mxnet_tpu.telemetry import cluster, roofline
    monkeypatch.setenv('MXTPU_TELEMETRY_SYNC_EVERY', '1')
    _reload()
    telemetry._reset_for_tests()
    calls = []
    monkeypatch.setattr(roofline, 'republish',
                        lambda: calls.append(1))
    assert cluster.enabled()
    cluster.sync_now()
    assert calls, 'sync_now did not refresh the roofline gauges'


def test_roofline_republish_publishes_gauges(tele_on, monkeypatch):
    from mxnet_tpu.telemetry import roofline
    d = {'layers': [{'layer': 'conv0', 'class': 'memory_bound',
                     'roof_pct': 41.0, 'headroom_ms': 1.2}],
         'worst_action': 'try MXTPU_REMAT_POLICY',
         'comm': {'bytes': 1024, 'time_ms': 0.5, 'overlap_pct': 10.0,
                  'pct_of_step': 3.0}}
    monkeypatch.setattr(roofline, 'enabled', lambda: True)
    monkeypatch.setattr(roofline, 'analyze',
                        lambda **kw: dict(d))
    out = roofline.republish()
    assert out is not None
    g = telemetry.snapshot()['gauges']
    assert g['roofline.worst_layer'] == 'conv0'
    assert g['roofline.comm_pct_of_step'] == 3.0
    # the refreshed analysis became the snapshot (no JSONL record)
    assert roofline.snapshot_roofline()['worst_action'] \
        == 'try MXTPU_REMAT_POLICY'


# ---------------------------------------------------------------------------
# satellites: watch lines, trace_report tool
# ---------------------------------------------------------------------------

def _tools():
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    tools = os.path.join(repo, 'tools')
    if tools not in sys.path:
        sys.path.insert(0, tools)


def test_watch_renders_slo_and_stage_lines():
    _tools()
    import telemetry_watch
    summary = {
        'elapsed_s': 60.0, 'host': 0,
        'snapshot': {
            'counters': {'serve.requests': 100},
            'gauges': {'slo.latency_objective_ms': 250.0,
                       'slo.error_budget_pct': 1.0,
                       'slo.burn_rate': 1.4,
                       'slo.budget_remaining_pct': 63.0,
                       'slo.degraded': 1},
            'histograms': {
                'serve.request_latency': {'count': 100, 'sum': 1000.0,
                                          'p50': 9.0, 'p95': 20.0},
                'serve.queue_wait': {'count': 100, 'sum': 400.0,
                                     'p50': 4.1, 'p95': 9.0},
                'serve.pad': {'count': 20, 'sum': 2.0, 'p50': 0.1,
                              'p95': 0.2},
                'serve.dispatch': {'count': 20, 'sum': 40.0, 'p50': 2.0,
                                   'p95': 3.0},
                'serve.fetch': {'count': 20, 'sum': 30.0, 'p50': 1.5,
                                'p95': 2.5},
            },
        },
    }
    frame = '\n'.join(telemetry_watch.render(summary))
    stage = [ln for ln in frame.splitlines() if 'stages' in ln]
    assert len(stage) == 1
    assert 'queue p50 4.1 ms' in stage[0]
    assert 'pad p50 0.1 ms' in stage[0]
    assert 'compute p50 3.5 ms' in stage[0]     # dispatch + fetch
    slo_line = [ln for ln in frame.splitlines() if 'slo' in ln]
    assert len(slo_line) == 1
    ln = slo_line[0]
    assert 'latency obj 250 ms' in ln and 'err budget 1%' in ln
    assert 'burn 1.4x' in ln and 'budget left 63%' in ln
    assert 'DEGRADED' in ln
    # no slo gauges -> no slo line (and no crash)
    frame = '\n'.join(telemetry_watch.render(
        {'snapshot': {'counters': {}, 'gauges': {}, 'histograms': {}}}))
    assert 'slo' not in frame and 'stages' not in frame


def test_trace_report_renders_traces_and_flight(tmp_path, capsys):
    _tools()
    import trace_report
    path = tmp_path / 'flight-test.jsonl'
    recs = [
        {'type': 'flight', 'reason': 'test', 't': 100.0, 'records': 4,
         'ring_size': 64},
        {'type': 'span', 'name': 'fit.dispatch', 't': 99.0,
         'dur_ms': 3.2},
        {'type': 'trace', 'trace_id': 'aaa111', 'dispatch_span': 'dd1',
         'rows': 2, 'status': 'ok', 't': 99.5, 'total_ms': 7.0,
         'stages': {'queue_wait_ms': 4.0, 'dispatch_ms': 2.0}},
        {'type': 'trace', 'trace_id': 'bbb222', 'dispatch_span': 'dd1',
         'rows': 1, 'status': 'ok', 't': 99.6, 'total_ms': 7.1,
         'stages': {'queue_wait_ms': 4.1, 'dispatch_ms': 2.0}},
        {'type': 'anomaly', 'detector': 'loss', 't': 99.9},
    ]
    path.write_text('\n'.join(json.dumps(r) for r in recs) + '\n')
    rc = trace_report.main([str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'reason=test' in out
    assert 'span=1' in out and 'trace=2' in out and 'anomaly=1' in out
    # the two passengers of the shared dispatch group together
    assert 'dispatch dd1 (2 requests)' in out
    assert 'aaa111' in out and 'bbb222' in out
    # trace filter
    rc = trace_report.main([str(path), '--trace', 'aaa'])
    out = capsys.readouterr().out
    assert 'aaa111' in out and 'bbb222' not in out
