"""Live telemetry plane (mxnet_tpu/telemetry/{serve,cluster}).

Contracts under test:
- Prometheus text exposition: HELP/TYPE lines, the host label on every
  sample, counter _total suffix, summary quantiles carrying the
  histogram p50/p95 (golden test);
- /healthz answers 200 while clean and flips to 503 — with the
  incident digest as the body — once a non-finite incident is on
  record;
- scrape-during-fit acceptance: an HTTP scrape against a RUNNING fit
  returns valid exposition text with live, increasing counters;
- cluster aggregation on the 8-device forced-host mesh: per-host
  gauges, spread, slowest-host id and the straggler classification
  land in the registry, the JSONL stream, the summary table and
  /metrics; the sync hook fires exactly every SYNC_EVERY steps and
  does NO collective work on the steps between;
- the telemetry-off / port-unset no-op contract extends to the new
  subsystem: no thread, no socket, no registry writes;
- JsonlSink size cap (MXTPU_TELEMETRY_MAX_MB): writing stops at the
  cap, telemetry.dropped_records keeps counting, one warning.
"""
import json
import logging
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.telemetry import cluster, serve
from mxnet_tpu.telemetry import export as tele_export

_FLAGS = ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH', 'MXTPU_TELEMETRY_PORT',
          'MXTPU_TELEMETRY_SYNC_EVERY', 'MXTPU_TELEMETRY_MAX_MB',
          'MXTPU_HEALTH', 'MXTPU_HEALTH_ACTION')


def _reload_flags():
    for f in _FLAGS:
        flags.reload(f)


@pytest.fixture
def tele_live(tmp_path, monkeypatch):
    """Telemetry ON with the live endpoint on an ephemeral port and a
    2-step cluster sync cadence; fully restored afterwards."""
    path = tmp_path / 'telemetry.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    monkeypatch.setenv('MXTPU_TELEMETRY_PORT', '0')
    monkeypatch.setenv('MXTPU_TELEMETRY_SYNC_EVERY', '2')
    _reload_flags()
    telemetry._reset_for_tests()
    yield path
    telemetry._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload_flags()


@pytest.fixture
def tele_off(monkeypatch):
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload_flags()
    telemetry._reset_for_tests()
    yield
    telemetry._reset_for_tests()
    _reload_flags()


def _records(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _get(port, path):
    """(status, body) for a GET against the live endpoint; 4xx/5xx
    answers return their body too instead of raising."""
    try:
        with urllib.request.urlopen(
                'http://127.0.0.1:%d%s' % (port, path), timeout=10) as r:
            return r.status, r.read().decode('utf-8')
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode('utf-8')


def _serve_threads():
    return [t for t in threading.enumerate()
            if t.name == serve._THREAD_NAME]


def _mlp_fit(num_epoch=1, batch=8, n=32, cb=None, **fit_kw):
    np.random.seed(0)
    mx.random.seed(0)
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu')
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
    out = mx.sym.SoftmaxOutput(fc2, name='softmax')
    X = np.random.randn(n, 10).astype(np.float32)
    y = (np.random.rand(n) * 4).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch,
                           label_name='softmax_label')
    mod = mx.mod.Module(out, context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),),
            batch_end_callback=cb, **fit_kw)
    return mod


# ---------------------------------------------------------------------------
# Prometheus exposition format
# ---------------------------------------------------------------------------

def test_prometheus_golden():
    """The renderer's output is pinned: HELP/TYPE lines, host label on
    every sample, counter _total suffix, info-style string gauges, and
    summary quantiles carrying the histogram p50/p95."""
    snap = {
        'counters': {'fit.steps': 8},
        'gauges': {'fit.input_bound_pct': 0.25,
                   'cluster.straggler_class': 'input_bound'},
        'histograms': {'fit.batch': {
            'count': 2, 'sum': 3.0, 'mean': 1.5, 'min': 1.0, 'max': 2.0,
            'p50': 1.0, 'p95': 2.0}},
    }
    golden = (
        '# HELP mxtpu_fit_steps_total mxnet_tpu counter fit.steps\n'
        '# TYPE mxtpu_fit_steps_total counter\n'
        'mxtpu_fit_steps_total{host="3"} 8\n'
        '# HELP mxtpu_cluster_straggler_class mxnet_tpu gauge '
        'cluster.straggler_class\n'
        '# TYPE mxtpu_cluster_straggler_class gauge\n'
        'mxtpu_cluster_straggler_class{host="3",value="input_bound"} 1\n'
        '# HELP mxtpu_fit_input_bound_pct mxnet_tpu gauge '
        'fit.input_bound_pct\n'
        '# TYPE mxtpu_fit_input_bound_pct gauge\n'
        'mxtpu_fit_input_bound_pct{host="3"} 0.25\n'
        '# HELP mxtpu_fit_batch_ms mxnet_tpu span histogram fit.batch '
        '(milliseconds; quantiles over the recent window)\n'
        '# TYPE mxtpu_fit_batch_ms summary\n'
        'mxtpu_fit_batch_ms{host="3",quantile="0.5"} 1\n'
        'mxtpu_fit_batch_ms{host="3",quantile="0.95"} 2\n'
        'mxtpu_fit_batch_ms_sum{host="3"} 3\n'
        'mxtpu_fit_batch_ms_count{host="3"} 2\n')
    assert serve.render_prometheus(snap, host=3) == golden


def test_prometheus_empty_and_unlabeled():
    out = serve.render_prometheus(
        {'counters': {}, 'gauges': {}, 'histograms': {}})
    assert out == '\n'
    out = serve.render_prometheus({'counters': {'a.b': 1}})
    assert 'mxtpu_a_b_total 1' in out          # no label block at all
    # non-finite gauge values render, never 500 the scrape
    out = serve.render_prometheus(
        {'gauges': {'g.inf': float('inf'), 'g.ninf': float('-inf'),
                    'g.nan': float('nan')}})
    assert 'mxtpu_g_inf +Inf' in out
    assert 'mxtpu_g_ninf -Inf' in out
    assert 'mxtpu_g_nan NaN' in out


# ---------------------------------------------------------------------------
# strict text-format 0.0.4 lint over the FULL /metrics payload
# ---------------------------------------------------------------------------

import re as _re

_PROM_NAME_RE = _re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*$')
_PROM_VALUE_RE = _re.compile(
    r'^(?:[+-]?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?|\+Inf|-Inf|NaN)$')


def _parse_prom_labels(s):
    """Character-level parse of a '{k="v",...}' label block. The only
    legal escapes in a label value are \\\\, \\" and \\n (format 0.0.4);
    anything else — raw newline, stray backslash, unterminated quote,
    duplicate key, trailing comma — is a lint failure."""
    assert s[0] == '{' and s[-1] == '}', s
    body, out, i = s[1:-1], {}, 0
    while i < len(body):
        j = body.index('=', i)
        key = body[i:j]
        assert _PROM_NAME_RE.match(key), 'bad label name %r' % key
        assert body[j + 1] == '"', 'unquoted label value in %r' % s
        i, val = j + 2, []
        while True:
            assert i < len(body), 'unterminated label value in %r' % s
            c = body[i]
            if c == '\\':
                nxt = body[i + 1]
                assert nxt in ('\\', '"', 'n'), \
                    'illegal escape \\%s in %r' % (nxt, s)
                val.append({'\\': '\\', '"': '"', 'n': '\n'}[nxt])
                i += 2
            elif c == '"':
                i += 1
                break
            else:
                assert c != '\n', 'raw newline inside label value'
                val.append(c)
                i += 1
        assert key not in out, 'duplicate label %r in %r' % (key, s)
        out[key] = ''.join(val)
        if i < len(body):
            assert body[i] == ',', 'garbage after label value in %r' % s
            i += 1
            assert i < len(body), 'trailing comma in %r' % s
    return out


def _lint_prometheus(text):
    """Strict structural lint of a full exposition payload. Every
    sample must belong to a declared family (HELP before TYPE, one of
    each), counters must end in _total with non-negative values,
    quantile labels may only appear on summaries, and summary _sum /
    _count samples resolve to their family. Returns
    {family: {'type': t, 'samples': [(name, labels, value)]}}."""
    assert text.endswith('\n'), 'payload must end with a newline'
    families, helped = {}, set()
    for ln in text.split('\n')[:-1]:
        if not ln:
            continue
        if ln.startswith('#'):
            m = _re.match(
                r'^# (HELP|TYPE) ([a-zA-Z_:][a-zA-Z0-9_:]*) (.+)$', ln)
            assert m, 'malformed comment line: %r' % ln
            kind, name, rest = m.groups()
            if kind == 'HELP':
                assert name not in helped, 'duplicate HELP %s' % name
                helped.add(name)
            else:
                assert name not in families, 'duplicate TYPE %s' % name
                assert rest in ('counter', 'gauge', 'summary',
                                'histogram', 'untyped'), \
                    'bad TYPE %r for %s' % (rest, name)
                assert name in helped, 'TYPE before HELP for %s' % name
                families[name] = {'type': rest, 'samples': []}
            continue
        m = _re.match(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$', ln)
        assert m, 'malformed sample line: %r' % ln
        name, labels, value = m.groups()
        assert _PROM_VALUE_RE.match(value), \
            'bad sample value %r on %r' % (value, ln)
        fam = families.get(name)
        if fam is None:                       # summary child samples
            for suffix in ('_sum', '_count'):
                if name.endswith(suffix):
                    cand = families.get(name[:-len(suffix)])
                    if cand and cand['type'] in ('summary', 'histogram'):
                        fam = cand
        assert fam is not None, 'sample %r has no TYPE family' % name
        lab = _parse_prom_labels(labels) if labels else {}
        if 'quantile' in lab:
            assert fam['type'] == 'summary', \
                'quantile label on non-summary sample %r' % name
        if fam['type'] == 'counter':
            assert name.endswith('_total'), \
                'counter sample %r lacks _total' % name
            assert not value.startswith('-'), 'negative counter %r' % name
        fam['samples'].append((name, lab, value))
    for name, fam in families.items():
        assert fam['samples'], 'TYPE %s declared with no samples' % name
    return families


def test_prometheus_strict_lint_full_metrics(tele_live):
    """The ENTIRE /metrics payload after a real fit + summary parses
    under the strict 0.0.4 lint — goodput.* gauges, cluster roll-up,
    histogram summaries and an exemplar sibling included — and nasty
    label content (quotes, backslashes, newlines, braces) round-trips
    through the escaper."""
    _mlp_fit(num_epoch=2)
    telemetry.write_summary(log=False)     # publishes goodput.* gauges
    reg = telemetry.get_registry()
    nasty = 'a"b\\c\nd{},= '
    reg.gauge('lint.nasty').set(nasty)
    reg.gauge('lint.inf').set(float('inf'))
    reg.gauge('lint.nan').set(float('nan'))
    reg.histogram('lint.span').observe(
        7.5, exemplar={'trace_id': 'abc"1\\2', 'route': 'x\ny'})
    status, body = _get(serve.port(), '/metrics')
    assert status == 200
    fams = _lint_prometheus(body)
    # pre-existing families all survive the lint, host-labeled
    for f in ('mxtpu_fit_steps_total', 'mxtpu_fused_fit_dispatch_ms',
              'mxtpu_cluster_hosts', 'mxtpu_xla_compiles_total'):
        assert f in fams, '%s missing from /metrics' % f
        assert all(lab.get('host') == '0'
                   for _, lab, _ in fams[f]['samples'])
    # the goodput plane is on /metrics: one gauge per bucket + the
    # verdict gauges, and the info-style strings parse as labels
    for b in ('step', 'compile', 'input_wait', 'checkpoint', 'eval',
              'comm', 'rework', 'overhead'):
        assert 'mxtpu_goodput_%s_s' % b in fams
    assert fams['mxtpu_goodput_goodput_pct']['type'] == 'gauge'
    (_, lab, v), = fams['mxtpu_goodput_badput_top']['samples']
    assert lab['value'] in ('step', 'compile', 'input_wait', 'checkpoint',
                            'eval', 'comm', 'rework', 'overhead')
    assert v == '1'
    # nasty label content round-trips exactly through the escaper
    (_, lab, _), = fams['mxtpu_lint_nasty']['samples']
    assert lab['value'] == nasty
    # ... and the raw escaped form is what's on the wire
    assert 'value="a\\"b\\\\c\\nd{},= "' in body
    # non-finite gauges render as the spec's literals
    assert fams['mxtpu_lint_inf']['samples'][0][2] == '+Inf'
    assert fams['mxtpu_lint_nan']['samples'][0][2] == 'NaN'
    # the exemplar sibling gauge carries its (escaped) trace labels
    (_, lab, v), = fams['mxtpu_lint_span_ms_exemplar']['samples']
    assert lab['trace_id'] == 'abc"1\\2'
    assert lab['route'] == 'x\ny'
    assert v == '7.5'
    # summaries: quantiles + _sum/_count resolved to the family
    names = [n for n, _, _ in fams['mxtpu_lint_span_ms']['samples']]
    assert 'mxtpu_lint_span_ms_sum' in names
    assert 'mxtpu_lint_span_ms_count' in names


def test_prometheus_lint_rejects_malformed():
    """The lint itself has teeth: hand-broken payloads fail."""
    ok = ('# HELP mxtpu_x mxnet_tpu gauge x\n'
          '# TYPE mxtpu_x gauge\n'
          'mxtpu_x{host="0"} 1\n')
    _lint_prometheus(ok)
    for bad in (
            ok.replace(' 1\n', ' one\n'),              # non-numeric value
            ok.replace('# HELP mxtpu_x mxnet_tpu gauge x\n', ''),
            ok.replace('gauge\n', 'gouge\n'),          # bad TYPE
            ok.replace('host="0"', 'host="0'),         # unterminated
            ok.replace('host="0"', r'host="a\q"'),     # illegal escape
            ok.replace('host="0"', 'host="0",host="1"'),
            ok + 'mxtpu_orphan 2\n',                   # no TYPE family
            ok.replace('mxtpu_x{host="0"} 1\n',
                       'mxtpu_x{host="0",quantile="0.5"} 1\n'),
    ):
        with pytest.raises(AssertionError):
            _lint_prometheus(bad)


# ---------------------------------------------------------------------------
# endpoints against a live registry
# ---------------------------------------------------------------------------

def test_scrape_during_fit(tele_live):
    """Acceptance: scraping /metrics WHILE fit runs yields valid
    exposition text whose fit.steps counter is live and increasing."""
    import re
    seen = []

    def scrape(param):
        port = serve.port()
        assert port is not None
        status, body = _get(port, '/metrics')
        assert status == 200
        m = re.search(r'^mxtpu_fit_steps_total\{host="0"\} (\d+)$',
                      body, re.M)
        if m:
            seen.append(int(m.group(1)))

    _mlp_fit(num_epoch=2, cb=scrape)
    assert seen, 'no scrape captured a fit.steps sample mid-fit'
    assert seen == sorted(seen)
    assert seen[-1] >= 4                  # live and increasing
    # the summary endpoint serves the same registry as JSON
    status, body = _get(serve.port(), '/summary')
    assert status == 200
    summ = json.loads(body)
    assert summ['snapshot']['counters']['fit.steps'] == 8
    assert summ['host'] == 0
    assert 'telemetry summary' in summ['table']


def test_healthz_flips_to_503_on_incident(tele_live, monkeypatch):
    """/healthz: 200 + ok while clean; 503 + the incident digest after
    an injected non-finite step."""
    monkeypatch.setenv('MXTPU_HEALTH', '1')
    monkeypatch.setenv('MXTPU_HEALTH_ACTION', 'record')
    _reload_flags()
    telemetry._reset_for_tests()
    from mxnet_tpu.telemetry import health
    assert telemetry.enabled() and health.enabled()
    port = serve.port()
    status, body = _get(port, '/healthz')
    assert status == 200
    assert json.loads(body)['status'] == 'ok'
    # inject: sentinel row with the all-finite flag down
    health.note_step(np.array([0.0, 1.0, 1.0, 1.0, 0.0], np.float32),
                     source='test-inject', step=7)
    status, body = _get(port, '/healthz')
    assert status == 503
    digest = json.loads(body)
    assert digest['status'] == 'degraded'
    inc = digest['health']['incidents'][0]
    assert inc['source'] == 'test-inject'
    assert inc['step'] == 7


def test_unknown_path_404(tele_live):
    telemetry.enabled()
    status, _ = _get(serve.port(), '/nope')
    assert status == 404


# ---------------------------------------------------------------------------
# cluster aggregation
# ---------------------------------------------------------------------------

def test_cluster_gauges_from_fit(tele_live):
    """On the (single-process) 8-device forced-host mesh, a fit with
    SYNC_EVERY=2 publishes cluster.* gauges into the registry, the
    JSONL stream, the summary table and /metrics."""
    _mlp_fit(num_epoch=2)
    snap = telemetry.snapshot()
    g = snap['gauges']
    assert g['cluster.hosts'] == 1
    assert 'cluster.h0.step_time_ms' in g
    assert g['cluster.slowest_host'] == 0
    assert g['cluster.straggler_class'] == 'balanced'
    assert snap['counters']['cluster.syncs'] >= 1
    clus = cluster.snapshot_cluster()
    assert clus['hosts'] == 1 and len(clus['per_host']) == 1
    # /metrics carries the family, host-labeled
    status, body = _get(serve.port(), '/metrics')
    assert status == 200
    assert 'mxtpu_cluster_hosts{host="0"} 1' in body
    assert 'mxtpu_cluster_straggler_class{host="0",value="balanced"} 1' \
        in body
    # summary table + JSONL record + summary record
    table = telemetry.write_summary(log=False)
    assert '-- cluster --' in table
    assert 'hosts             1' in table
    telemetry.shutdown()
    recs = _records(tele_live)
    assert any(r['type'] == 'cluster' and r['host'] == 0 for r in recs)
    summ = [r for r in recs if r['type'] == 'summary'][-1]
    assert summ['cluster']['hosts'] == 1


def test_cluster_sync_cadence(tele_live, monkeypatch):
    """The allgather fires exactly every SYNC_EVERY steps — off-sync
    steps never reach the collective."""
    telemetry.enabled()
    calls = []
    real = cluster._allgather
    monkeypatch.setattr(cluster, '_allgather',
                        lambda vals: (calls.append(1), real(vals))[1])
    assert cluster.enabled()
    for _ in range(5):
        cluster.note_step()               # every=2: fires at 2 and 4
    assert len(calls) == 2
    cluster.note_step(2)                  # window-sized: 1 pending + 2 >= 2
    assert len(calls) == 3


def test_cluster_straggler_classification(tele_live):
    """A gathered matrix with one slow, input-starved host names that
    host and classifies it input-bound (the PR 4 classifier)."""
    telemetry.enabled()
    mat = np.array([[10.0, 2.0, 8.0, 1 << 20],
                    [20.0, 55.0, 18.0, 2 << 20]], np.float32)
    snap = cluster._publish(mat, steps=128)
    assert snap['slowest_host'] == 1
    assert snap['straggler'] == 'input_bound'
    assert snap['spread_pct'] > 5
    g = telemetry.snapshot()['gauges']
    assert g['cluster.h1.io_wait_pct'] == 55.0
    assert g['cluster.slowest_host'] == 1
    # a compute-bound slow host classifies the other way
    mat[1, 1] = 3.0
    assert cluster._publish(mat, steps=256)['straggler'] == 'compute_bound'
    # the summary table marks the slowest host's row
    table = tele_export.summary_table(
        telemetry.snapshot(), cluster=cluster.snapshot_cluster())
    assert '-- cluster --' in table and '1*' in table
    assert 'straggler         compute_bound (slowest host 1)' in table


def test_cluster_straggler_communication_bound(tele_live):
    """A slow host that is NOT input-starved but spends >30% of its
    step in collectives (the roofline's comm_pct sync slot) classifies
    communication_bound — the verdict grounded in per-collective
    numbers, not inference. A 4-column matrix (no roofline slot) keeps
    the old two-way classification."""
    telemetry.enabled()
    mat = np.array([[10.0, 2.0, 8.0, 1 << 20, 40.0],
                    [20.0, 3.0, 18.0, 2 << 20, 45.0]], np.float32)
    snap = cluster._publish(mat, steps=128)
    assert snap['slowest_host'] == 1
    assert snap['straggler'] == 'communication_bound'
    assert snap['per_host'][1]['comm_pct'] == 45.0
    g = telemetry.snapshot()['gauges']
    assert g['cluster.h1.comm_pct'] == 45.0
    assert g['cluster.straggler_class'] == 'communication_bound'
    # io-wait still wins: an input-starved host reads input_bound even
    # with a high comm share (it is waiting on the host, not the wire)
    mat[1, 1] = 55.0
    assert cluster._publish(mat, steps=256)['straggler'] == 'input_bound'
    # no comm slot (pre-roofline sender / crafted 4-col matrix): the
    # comm_pct row entry is omitted and the comm verdict is unreachable
    mat4 = np.array([[10.0, 2.0, 8.0, 1 << 20],
                     [20.0, 3.0, 18.0, 2 << 20]], np.float32)
    snap4 = cluster._publish(mat4, steps=384)
    assert snap4['straggler'] == 'compute_bound'
    assert snap4['per_host'][1]['comm_pct'] is None


def test_summary_payload_carries_roofline(tele_live):
    """/summary exposes the roofline analysis key (None while the flag
    is off — the payload shape is stable either way)."""
    telemetry.enabled()
    payload = serve.summary_payload()
    assert 'roofline' in payload
    assert payload['roofline'] is None     # MXTPU_ROOFLINE unset here


# ---------------------------------------------------------------------------
# the no-op contract extends to serve/cluster
# ---------------------------------------------------------------------------

def test_no_server_without_port(tmp_path, monkeypatch):
    """Telemetry ON but the port unset: no thread, no socket, and the
    cluster hook stays off without SYNC_EVERY."""
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    for f in ('MXTPU_TELEMETRY_PORT', 'MXTPU_TELEMETRY_SYNC_EVERY'):
        monkeypatch.delenv(f, raising=False)
    _reload_flags()
    telemetry._reset_for_tests()
    try:
        assert telemetry.enabled()
        assert serve.port() is None
        assert serve._server is None
        assert not _serve_threads()
        assert not cluster.enabled()
        cluster.note_step()               # no-op: no time bookkeeping
        assert cluster._state.steps == 0
        assert cluster.snapshot_cluster() is None
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
        _reload_flags()


def test_no_op_when_telemetry_off(tele_off, monkeypatch):
    """Telemetry OFF: even with port + cadence env set, a fit spawns no
    server thread, runs no sync, and the registry stays empty."""
    monkeypatch.setenv('MXTPU_TELEMETRY_PORT', '0')
    monkeypatch.setenv('MXTPU_TELEMETRY_SYNC_EVERY', '1')
    _reload_flags()
    io_before = tele_export._io_calls
    _mlp_fit(num_epoch=1)
    assert not telemetry.enabled()
    assert serve._server is None
    assert not _serve_threads()
    assert serve.maybe_start() is None    # guarded even if called directly
    assert not cluster.enabled()
    assert telemetry.get_registry().names() == []
    assert tele_export._io_calls == io_before


# ---------------------------------------------------------------------------
# JsonlSink size cap (MXTPU_TELEMETRY_MAX_MB)
# ---------------------------------------------------------------------------

def test_jsonl_sink_size_cap(tmp_path, caplog):
    path = tmp_path / 'capped.jsonl'
    sink = tele_export.JsonlSink(str(path), max_bytes=256)
    with caplog.at_level(logging.WARNING):
        for i in range(50):
            sink.emit({'type': 'event', 'name': 'e%d' % i,
                       'pad': 'x' * 32})
    sink.close()
    size = os.path.getsize(path)
    assert 0 < size <= 256
    kept = _records(path)
    assert 0 < len(kept) < 50
    warns = [r for r in caplog.records
             if 'MXTPU_TELEMETRY_MAX_MB' in r.getMessage()]
    assert len(warns) == 1                # warned once, not per drop
    # post-cap emits are dropped silently (no growth, no raise)
    sink2 = tele_export.JsonlSink(str(path), max_bytes=256)
    # (padded past the cap: a short record can still fit under it when
    # the first sink's variable-width timestamps left a few bytes free)
    sink2.emit({'type': 'event', 'name': 'late', 'pad': 'x' * 256})
    sink2.close()
    assert os.path.getsize(path) == size


def test_jsonl_sink_cap_counts_drops(tele_live):
    """With telemetry live, dropped records land in the
    telemetry.dropped_records counter."""
    assert telemetry.enabled()
    sink = telemetry._state.sink
    sink._max_bytes = sink._bytes         # cap exactly where we stand
    telemetry.event('overflow-1')
    telemetry.event('overflow-2')
    assert telemetry.get_registry().counter(
        'telemetry.dropped_records').value == 2


def test_fit_cap_via_env(tmp_path, monkeypatch):
    """The flag wires through telemetry decide: a tiny cap stops the
    JSONL mid-fit while metrics stay live in-process."""
    path = tmp_path / 'tiny.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    monkeypatch.setenv('MXTPU_TELEMETRY_MAX_MB', '0.001')   # ~1 KB
    _reload_flags()
    telemetry._reset_for_tests()
    try:
        _mlp_fit(num_epoch=2)
        assert os.path.getsize(path) <= 1024
        reg = telemetry.get_registry()
        assert reg.counter('telemetry.dropped_records').value > 0
        assert reg.counter('fit.steps').value == 8    # metrics unhurt
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
        _reload_flags()


# ---------------------------------------------------------------------------
# nbatch threading into executor incidents (PR 4 residue)
# ---------------------------------------------------------------------------

def test_executor_incident_carries_step(tmp_path, monkeypatch):
    """The per-batch loop's nbatch reaches executor-level incidents:
    step is the real batch index, not None — and /healthz shows it."""
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 'h.jsonl'))
    monkeypatch.setenv('MXTPU_TELEMETRY_PORT', '0')
    monkeypatch.setenv('MXTPU_HEALTH', '1')
    monkeypatch.setenv('MXTPU_HEALTH_ACTION', 'record')
    monkeypatch.setenv('MXTPU_FUSED_FIT', '0')
    _reload_flags()
    flags.reload('MXTPU_FUSED_FIT')
    telemetry._reset_for_tests()
    try:
        from mxnet_tpu.telemetry import health
        np.random.seed(1)
        w = (np.random.randn(16, 10) * 0.1).astype(np.float32)
        w[0, 0] = np.nan
        _mlp_fit(num_epoch=1,
                 arg_params={'fc1_weight': mx.nd.array(w)},
                 allow_missing=True)
        hs = health.snapshot_health()
        incidents = hs['incidents']
        assert incidents, 'poisoned weight produced no incident'
        # every batch is bad (the weight is poisoned), and each incident
        # names ITS batch index via the note_batch context
        assert incidents[0]['source'] == 'executor'
        assert incidents[0]['step'] == 0
        assert [i['step'] for i in incidents[:4]] == [0, 1, 2, 3]
        # fit cleared the context: a later custom-loop incident must
        # not inherit batch 3
        assert health._state.cur_step is None
        status, body = _get(serve.port(), '/healthz')
        assert status == 503
        assert json.loads(body)['health']['incidents'][0]['step'] == 0
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS + ('MXTPU_FUSED_FIT',):
            monkeypatch.delenv(f, raising=False)
        _reload_flags()
        flags.reload('MXTPU_FUSED_FIT')


# ---------------------------------------------------------------------------
# straggler-aware input re-balancing (MXTPU_ELASTIC_INPUT)
# ---------------------------------------------------------------------------

class _ShardIter:
    def __init__(self, num_parts=4, part_index=1):
        self.num_parts, self.part_index = num_parts, part_index

    def shard_info(self):
        return self.num_parts, self.part_index

    def set_shard(self, part_index):
        self.part_index = part_index


@pytest.fixture
def elastic_on(tele_live, monkeypatch):
    monkeypatch.setenv('MXTPU_ELASTIC_INPUT', '1')
    flags.reload('MXTPU_ELASTIC_INPUT')
    telemetry._reset_for_tests()
    yield tele_live
    telemetry._reset_for_tests()
    monkeypatch.delenv('MXTPU_ELASTIC_INPUT', raising=False)
    flags.reload('MXTPU_ELASTIC_INPUT')


def test_elastic_decides_on_input_bound_round(elastic_on):
    """An input-bound slowest host in a gathered round advances the
    shard shift (identically on every host — the decision is pure math
    over the identical matrix); a compute-bound or balanced round does
    not. The shift applies at the next epoch boundary via the iterator
    shard protocol and is consumed exactly once."""
    assert cluster.elastic_enabled()
    nanv = float('nan')
    # balanced spread: no decision
    assert cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [10.2, 2.0, 0.0, 0.0, nanv]]),
        steps=4) is None
    # slow + compute-bound: no decision
    assert cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [20.0, 4.0, 0.0, 0.0, nanv]]),
        steps=6) is None
    # slow + input-bound: shift
    info = cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [20.0, 60.0, 0.0, 0.0, nanv]]),
        steps=8)
    assert info == {'step': 8, 'input_bound_host': 1, 'shift': 1,
                    'spread_pct': info['spread_pct']}
    assert cluster.shard_shift() == 1
    reg = telemetry.get_registry()
    assert reg.gauge('cluster.elastic_shift').value == 1
    it = _ShardIter(num_parts=4, part_index=1)
    assert cluster.apply_shard_shift(it) == 2 and it.part_index == 2
    assert cluster.apply_shard_shift(it) is None     # consumed
    # a second round shifts again, applied as a delta on the CURRENT part
    cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [20.0, 60.0, 0.0, 0.0, nanv]]),
        steps=16)
    assert cluster.apply_shard_shift(it) == 3
    telemetry._state.sink.flush()
    recs = [r for r in _records(elastic_on) if r['type'] == 'elastic']
    assert [r['event'] for r in recs] == ['shift', 'reshard', 'shift',
                                          'reshard']


def test_elastic_iterator_without_protocol_warns_once(elastic_on, caplog):
    nanv = float('nan')
    cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [20.0, 60.0, 0.0, 0.0, nanv]]),
        steps=4)

    class Plain:
        pass

    with caplog.at_level(logging.WARNING):
        assert cluster.apply_shard_shift(Plain()) is None
    assert 'shard_info' in caplog.text
    # the shift is consumed (no warning storm every epoch)
    caplog.clear()
    with caplog.at_level(logging.WARNING):
        assert cluster.apply_shard_shift(Plain()) is None
    assert 'shard_info' not in caplog.text


def test_elastic_off_is_inert(tele_live):
    """Cluster sync on but MXTPU_ELASTIC_INPUT off: no decision, no
    shift, apply_shard_shift is one cached check."""
    assert cluster.enabled() and not cluster.elastic_enabled()
    nanv = float('nan')
    assert cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [20.0, 60.0, 0.0, 0.0, nanv]]),
        steps=4) is None
    it = _ShardIter()
    assert cluster.apply_shard_shift(it) is None and it.part_index == 1
    assert cluster.shard_shift() == 0
    assert not [r for r in _records(tele_live)
                if r.get('type') == 'elastic']


def test_elastic_single_host_never_shifts(elastic_on):
    nanv = float('nan')
    assert cluster._elastic_decide(
        np.array([[10.0, 90.0, 0.0, 0.0, nanv]]), steps=4) is None
    assert cluster.shard_shift() == 0


def test_capped_sink_keeps_mtime_heartbeat(tmp_path):
    """A sink that hit MXTPU_TELEMETRY_MAX_MB appends nothing ever
    again, but keeps touching the file's mtime at the flush cadence —
    the supervisor liveness tier watches (size, mtime), so a
    healthy-but-capped child is never liveness-killed in a loop."""
    import time as _time
    p = tmp_path / 'capped.jsonl'
    sink = tele_export.JsonlSink(str(p), max_bytes=1)
    sink.emit({'type': 'x'})            # trips the cap
    assert sink._capped
    size0 = os.path.getsize(p)
    os.utime(p, (1.0, 1.0))             # pretend the file is ancient
    sink._last_flush = _time.time() - 60
    sink.emit({'type': 'y'})            # dropped, but heartbeats
    st = os.stat(p)
    assert st.st_mtime > 1.0, 'capped sink must keep the mtime fresh'
    assert st.st_size == size0, 'the cap contract (no growth) holds'
    sink.close()


def test_elastic_disables_on_unshardable_iterator(elastic_on, caplog):
    """A single-shard iterator can never be re-balanced: the first
    apply warns once and DISABLES the elastic tier, so sync rounds stop
    deciding (and logging/gauging) shifts that can never move data."""
    nanv = float('nan')
    cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [20.0, 60.0, 0.0, 0.0, nanv]]),
        steps=4)
    it = _ShardIter(num_parts=1, part_index=0)
    with caplog.at_level(logging.WARNING):
        assert cluster.apply_shard_shift(it) is None
    assert 'single shard' in caplog.text
    assert not cluster.elastic_enabled()
    # no further decisions, ever
    assert cluster._elastic_decide(np.array(
        [[10.0, 2.0, 0.0, 0.0, nanv], [20.0, 60.0, 0.0, 0.0, nanv]]),
        steps=8) is None
    assert cluster.shard_shift() == 1   # frozen where it was
