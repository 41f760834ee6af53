"""Runtime telemetry subsystem (mxnet_tpu/telemetry).

Contracts under test:
- registry semantics: counter/gauge/histogram, kind conflicts, snapshot;
- span tracer: nesting paths, histogram recording, exception unwind;
- JSONL exporter round-trip;
- the zero-overhead no-op path: with MXTPU_TELEMETRY unset a fit run
  creates no file and makes ZERO telemetry I/O calls;
- the acceptance run: with MXTPU_TELEMETRY=1 a short Module.fit on CPU
  yields a JSONL log with fit-batch spans, at least one compile event,
  and an end-of-run summary;
- satellites: Speedometer gauge (pinned log format unchanged), kvstore
  byte counters, retrace-storm warning, Monitor._rms_stat on empty.
"""
import json
import logging
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.telemetry import export as tele_export
from mxnet_tpu.telemetry.registry import Registry


def _reload_tele_flags():
    for f in ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH',
              'MXTPU_TELEMETRY_RETRACE_WARN'):
        flags.reload(f)


@pytest.fixture
def tele_path(tmp_path, monkeypatch):
    """Telemetry ON, logging to a tmp JSONL; restored OFF afterwards."""
    path = tmp_path / 'telemetry.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    _reload_tele_flags()
    telemetry._reset_for_tests()
    yield path
    # this teardown runs BEFORE monkeypatch's env undo, so drop the env
    # here and reload: the flag cache must not keep the tmp values
    telemetry._reset_for_tests()
    monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
    monkeypatch.delenv('MXTPU_TELEMETRY_PATH', raising=False)
    _reload_tele_flags()


@pytest.fixture
def tele_off(monkeypatch):
    """Telemetry decisively OFF (undo any earlier test's state)."""
    monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
    _reload_tele_flags()
    telemetry._reset_for_tests()
    yield
    telemetry._reset_for_tests()
    _reload_tele_flags()


def _records(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _mlp_fit(num_epoch=2, batch=8, n=32, cb=None):
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
            batch_end_callback=cb)
    return mod


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_semantics():
    r = Registry()
    c = r.counter('a')
    c.inc()
    c.inc(2)
    c.inc(0.5)            # float increments (compile seconds)
    assert c.value == 3.5
    assert r.counter('a') is c          # create-once


def test_gauge_semantics():
    r = Registry()
    g = r.gauge('g')
    assert g.value is None
    g.set(3)
    g.set(7)
    assert g.value == 7                 # last write wins


def test_histogram_semantics():
    r = Registry()
    h = r.histogram('h')
    for v in range(1, 101):
        h.observe(v)
    assert h.count == 100
    assert h.min == 1 and h.max == 100
    assert h.mean == pytest.approx(50.5)
    assert h.percentile(0) == 1
    assert h.percentile(100) == 100
    assert h.percentile(50) in (50, 51)
    assert h.percentile(95) in (95, 96)
    st = h.stats()
    assert st['count'] == 100 and st['p95'] in (95, 96)


def test_histogram_empty():
    h = Registry().histogram('h')
    assert h.percentile(50) is None
    assert h.stats()['mean'] is None


def test_kind_conflict_raises():
    r = Registry()
    r.counter('x')
    with pytest.raises(TypeError):
        r.gauge('x')


def test_snapshot_shape():
    r = Registry()
    r.counter('c').inc(2)
    r.gauge('g').set(1.5)
    r.histogram('h').observe(10)
    snap = r.snapshot()
    assert snap['counters'] == {'c': 2}
    assert snap['gauges'] == {'g': 1.5}
    assert snap['histograms']['h']['count'] == 1


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_paths(tele_path):
    assert telemetry.enabled()
    with telemetry.span('outer'):
        assert telemetry.current_span_path() == 'outer'
        with telemetry.span('inner'):
            assert telemetry.current_span_path() == 'outer/inner'
        assert telemetry.current_span_path() == 'outer'
    assert telemetry.current_span_path() is None
    reg = telemetry.get_registry()
    assert reg.histogram('outer').count == 1
    assert reg.histogram('inner').count == 1
    telemetry.shutdown()
    spans = [r for r in _records(tele_path) if r['type'] == 'span']
    paths = {r['name']: r['path'] for r in spans}
    assert paths == {'outer': 'outer', 'inner': 'outer/inner'}
    # inner closed before outer, so it is emitted first
    assert [r['name'] for r in spans] == ['inner', 'outer']


def test_span_unwinds_on_exception(tele_path):
    with pytest.raises(RuntimeError):
        with telemetry.span('boom'):
            raise RuntimeError('x')
    assert telemetry.current_span_path() is None
    assert telemetry.get_registry().histogram('boom').count == 1


def test_span_noop_when_disabled(tele_off):
    assert not telemetry.enabled()
    s = telemetry.span('anything')
    assert s is telemetry._NULL_SPAN
    with s:
        pass
    # attributes change nothing: the same shared object, no annotation
    assert telemetry.span('x', win=1) is telemetry._NULL_SPAN
    assert telemetry.span('x', 'cat', win=1, bytes=2) is \
        telemetry._NULL_SPAN
    # nothing registered anywhere
    assert telemetry.get_registry().get('anything') is None
    assert telemetry.get_registry().get('x') is None


def _host_plane_events(trace_dir, prefix):
    """{line index: [(name, stats)]} of the events of a capture's
    /host:CPU plane whose names start with `prefix`."""
    import glob
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), 'plugins', 'profile',
                                   '*', '*.xplane.pb'))
    assert files
    profile = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = {}
    for plane in profile.planes:
        if plane.name != '/host:CPU':
            continue
        for i, line in enumerate(plane.lines):
            hits = [(e.name, {str(k): v for k, v in e.stats})
                    for e in line.events if e.name.startswith(prefix)]
            if hits:
                out[i] = hits
    return out


def test_span_is_a_profiler_event_on_its_thread(tele_path, tmp_path):
    """A span opened during a jax.profiler capture is an event of the
    capture's /host:CPU plane (the device's clock), its attribute among
    the statistics, on the line of the thread it ran on; the JSONL
    record carries the thread's name and the attributes."""
    import threading
    import jax

    def side():
        with telemetry.span('tspan.side', win=3, bytes=7):
            pass

    t = threading.Thread(target=side, name='tspan-side-thread')
    jax.profiler.start_trace(str(tmp_path / 'trace'))
    try:
        with telemetry.span('tspan.loop', win=3):
            t.start()
            t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    lines = _host_plane_events(tmp_path / 'trace', 'tspan.')
    where = {name: (i, stats) for i, hits in lines.items()
             for name, stats in hits}
    assert set(where) == {'tspan.loop', 'tspan.side'}
    assert where['tspan.loop'][1]['win'] == 3
    assert where['tspan.side'][1]['win'] == 3
    assert where['tspan.side'][1]['bytes'] == 7
    assert where['tspan.loop'][0] != where['tspan.side'][0]

    telemetry.shutdown()
    recs = {r['name']: r for r in _records(tele_path)
            if r['type'] == 'span'}
    assert recs['tspan.loop']['win'] == 3
    assert recs['tspan.loop']['tid'] == threading.current_thread().name
    assert recs['tspan.side']['tid'] == 'tspan-side-thread'
    assert recs['tspan.side']['bytes'] == 7
    # a span on a side thread has a path of its own
    assert recs['tspan.side']['path'] == 'tspan.side'
    for r in recs.values():
        assert {'name', 'path', 't', 'dur_ms'} <= set(r)


# ---------------------------------------------------------------------------
# JSONL exporter
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip(tmp_path):
    path = tmp_path / 'log.jsonl'
    sink = tele_export.JsonlSink(str(path))
    recs = [{'type': 'event', 'name': 'e%d' % i, 'i': i} for i in range(5)]
    for r in recs:
        sink.emit(dict(r))
    sink.flush()
    sink.emit({'type': 'event', 'name': 'after-flush'})
    sink.close()
    got = _records(path)
    assert len(got) == 6
    for r in got:
        assert 't' in r                     # stamped on emit
    assert [r.get('i') for r in got[:5]] == [0, 1, 2, 3, 4]
    assert got[5]['name'] == 'after-flush'
    sink.emit({'type': 'event'})            # post-close: dropped, no raise


def test_jsonl_append_only(tmp_path):
    path = tmp_path / 'log.jsonl'
    s1 = tele_export.JsonlSink(str(path))
    s1.emit({'type': 'event', 'name': 'first'})
    s1.close()
    s2 = tele_export.JsonlSink(str(path))
    s2.emit({'type': 'event', 'name': 'second'})
    s2.close()
    assert [r['name'] for r in _records(path)] == ['first', 'second']


# ---------------------------------------------------------------------------
# zero-overhead no-op path
# ---------------------------------------------------------------------------

def test_disabled_fit_zero_telemetry_io(tele_off, tmp_path, monkeypatch):
    """MXTPU_TELEMETRY unset: a fit run writes no file and makes zero
    telemetry I/O calls (the acceptance criterion's negative half)."""
    # the log's default path is relative: a file would land here
    monkeypatch.chdir(tmp_path)
    io_before = tele_export._io_calls
    _mlp_fit(num_epoch=1)
    with telemetry.span('x', win=1):
        pass
    assert os.listdir(str(tmp_path)) == []
    assert tele_export._io_calls == io_before
    assert telemetry._state.sink is None
    assert not telemetry._state.active
    # nothing leaked into the (inactive) registry either
    assert telemetry.get_registry().names() == []
    assert not os.path.exists(os.path.join(str(tmp_path),
                                           'telemetry.jsonl'))


def test_disabled_metric_handles_are_noops(tele_off):
    from mxnet_tpu.telemetry.registry import (NULL_COUNTER, NULL_GAUGE,
                                              NULL_HISTOGRAM)
    assert telemetry.counter('c') is NULL_COUNTER
    assert telemetry.gauge('g') is NULL_GAUGE
    assert telemetry.histogram('h') is NULL_HISTOGRAM
    telemetry.counter('c').inc(5)
    telemetry.gauge('g').set(5)
    telemetry.histogram('h').observe(5)
    assert telemetry.get_registry().names() == []


# ---------------------------------------------------------------------------
# the acceptance run: short Module.fit on CPU with telemetry on
# ---------------------------------------------------------------------------

def test_fit_telemetry_acceptance_reference_loop(tele_path, monkeypatch):
    """Reference per-batch loop: the JSONL log carries fit-batch spans,
    at least one compile event, and the end-of-run summary."""
    monkeypatch.setenv('MXTPU_FUSED_FIT', '0')
    _mlp_fit(num_epoch=2)
    table = telemetry.write_summary(log=False)
    telemetry.shutdown()
    recs = _records(tele_path)
    spans = [r for r in recs if r['type'] == 'span']
    assert sum(1 for r in spans if r['name'] == 'fit.batch') == 8
    for sub in ('fit.dispatch', 'fit.metric', 'executor.forward',
                'executor.backward', 'module.update'):
        assert any(r['name'] == sub for r in spans), sub
    # nested spans carry their parent path
    d = next(r for r in spans if r['name'] == 'fit.dispatch')
    assert d['path'] == 'fit.batch/fit.dispatch'
    assert any(r['type'] == 'compile' for r in recs)
    summaries = [r for r in recs if r['type'] == 'summary']
    assert summaries, 'no end-of-run summary record'
    snap = summaries[-1]['snapshot']
    assert snap['counters']['fit.steps'] == 8
    assert snap['counters']['fit.epochs'] == 2
    assert snap['counters']['io.batches'] == 8
    assert snap['counters']['xla.compiles'] >= 1
    assert snap['histograms']['fit.batch']['count'] == 8
    # the human-readable table renders the same registry
    assert 'fit.steps' in table and 'telemetry summary' in table


def test_fit_telemetry_fused_loop(tele_path):
    """Fused window path: window spans + steps-per-call gauge, and
    fit.steps still counts every trained batch."""
    _mlp_fit(num_epoch=2)
    snap = telemetry.snapshot()
    assert snap['counters']['fit.steps'] == 8
    assert snap['counters']['fused_fit.windows'] >= 1
    assert snap['gauges']['fused_fit.steps_per_call'] >= 1
    for h in ('fused_fit.draw', 'fused_fit.put', 'fused_fit.dispatch',
              'fused_fit.fetch', 'fused_fit.build'):
        assert h in snap['histograms'], h
    telemetry.shutdown()
    recs = _records(tele_path)
    assert any(r['type'] == 'span' and r['name'] == 'fused_fit.dispatch'
               for r in recs)
    assert any(r['type'] == 'compile' for r in recs)


def test_fused_fit_window_spans_share_win(tele_path):
    """A fused fit of two windows gives, per window, one .draw with
    `window` many .next inside it, one .stack and one .upload on the
    side thread, one .put, one .dispatch and one .fetch on the loop's,
    all with the window's `win`; and the set-up spans around them."""
    import threading
    _mlp_fit(num_epoch=1, n=64)         # 8 batches: two windows of 4
    telemetry.shutdown()
    spans = [r for r in _records(tele_path) if r['type'] == 'span']
    dispatched = sorted(r['win'] for r in spans
                        if r['name'] == 'fused_fit.dispatch')
    assert len(dispatched) == 2 and dispatched[1] == dispatched[0] + 1
    loop_tid = threading.current_thread().name
    for win in dispatched:
        mine = [r for r in spans if r.get('win') == win]
        count = {}
        for r in mine:
            count[r['name']] = count.get(r['name'], 0) + 1
        assert count == {
            'fused_fit.draw': 1, 'fused_fit.next': 4,
            'fused_fit.stack': 1, 'fused_fit.upload': 1,
            'fused_fit.put': 1, 'fused_fit.dispatch': 1,
            'fused_fit.fetch': 1}, (win, count)
        for r in mine:
            side = r['name'] in ('fused_fit.stack', 'fused_fit.upload')
            assert r['tid'].startswith('mxtpu-window-put') if side \
                else r['tid'] == loop_tid, r
            if side:
                assert r['bytes'] == 4 * (8 * 10 + 8) * 4
                assert r['path'] == r['name']
        assert all(r['path'] == 'fused_fit.draw/fused_fit.next'
                   for r in mine if r['name'] == 'fused_fit.next')
    # the draw that found the iterator at its end: numbered, no upload
    last = [r['name'] for r in spans if r.get('win') == dispatched[1] + 1]
    assert sorted(last) == ['fused_fit.draw', 'fused_fit.next']
    names = [r['name'] for r in spans]
    for setup in ('fit.bind', 'fit.init_params', 'fit.init_optimizer',
                  'fused_fit.build'):
        assert names.count(setup) == 1, setup


def test_fit_results_identical_with_telemetry(tele_path, monkeypatch):
    """Instrumentation must not perturb training: same params with
    telemetry on and off."""
    a = {k: v.asnumpy() for k, v in _mlp_fit(num_epoch=1).get_params()[0]
         .items()}
    telemetry._reset_for_tests()
    monkeypatch.delenv('MXTPU_TELEMETRY')
    flags.reload('MXTPU_TELEMETRY')
    b = {k: v.asnumpy() for k, v in _mlp_fit(num_epoch=1).get_params()[0]
         .items()}
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=0, atol=0, err_msg=k)


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_speedometer_gauge_and_pinned_format(tele_path, caplog):
    """The samples/sec gauge is recorded without altering the pinned
    `Speed:` log-line format the compat tests parse."""
    import re
    from mxnet_tpu.model import BatchEndParam
    sm = mx.callback.Speedometer(batch_size=8, frequent=2)
    with caplog.at_level(logging.INFO):
        for nbatch in range(3):
            sm(BatchEndParam(epoch=0, nbatch=nbatch, eval_metric=None,
                             locals=None))
    g = telemetry.get_registry().gauge('speedometer.samples_per_sec')
    assert g.value is not None and g.value > 0
    lines = [r.getMessage() for r in caplog.records]
    hits = [ln for ln in lines
            if re.search(r'Speed: ([0-9.]+) samples/sec', ln)]
    assert len(hits) == 1
    assert re.search(r'Iter\[0\] Batch \[2\]\tSpeed: [0-9.]+ samples/sec',
                     hits[0])


def test_kvstore_push_pull_counters(tele_path):
    kv = mx.kv.create('local')
    a = mx.nd.ones((4, 8))
    kv.init('w', a)
    kv.push('w', mx.nd.ones((4, 8)))
    out = mx.nd.zeros((4, 8))
    kv.pull('w', out=out)
    reg = telemetry.get_registry()
    assert reg.counter('kvstore.push_bytes').value == 4 * 8 * 4
    assert reg.counter('kvstore.pull_bytes').value == 4 * 8 * 4
    assert reg.histogram('kvstore.push').count == 1
    assert reg.histogram('kvstore.pull').count == 1


def test_prefetching_iter_counts_batches_once(tele_path):
    """PrefetchingIter must not double-count io.batches: the inner
    iterator's next() (on the producer thread) is the single count."""
    X = np.zeros((32, 4), np.float32)
    y = np.zeros((32,), np.float32)
    it = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, y, batch_size=8))
    n = sum(1 for _ in it)
    assert n == 4
    reg = telemetry.get_registry()
    # the producer may have prefetched past the consumer, but each
    # batch is counted exactly once: never more than the 4 real batches
    assert reg.counter('io.batches').value == 4
    assert reg.histogram('io.prefetch_wait').count >= 4


def test_retrace_storm_warns_once(tele_path, caplog):
    key = ('test-graph', (1, 2, 3))
    with caplog.at_level(logging.WARNING):
        for _ in range(8):
            telemetry.xla.note_retrace(key)
    storms = [r for r in caplog.records if 'retrace storm' in r.getMessage()]
    assert len(storms) == 1           # warned once, at threshold + 1
    assert telemetry.get_registry().counter('xla.retraces').value == 7
    telemetry.shutdown()
    recs = _records(tele_path)
    assert any(r['type'] == 'retrace_storm' for r in recs)


def test_monitor_rms_stat_empty_array():
    from mxnet_tpu.monitor import _rms_stat
    assert _rms_stat(mx.nd.zeros((0,))) == 'nan'
    assert _rms_stat(mx.nd.zeros((0, 4))) == 'nan'
    # non-empty still numeric
    v = float(_rms_stat(mx.nd.ones((2, 2))))
    assert v == pytest.approx(1.0)


def test_summary_table_renders_empty():
    from mxnet_tpu.telemetry.export import summary_table
    out = summary_table({'counters': {}, 'gauges': {}, 'histograms': {}})
    assert 'no metrics recorded' in out


# ---------------------------------------------------------------------------
# per-program cost attribution (ISSUE 3)
# ---------------------------------------------------------------------------

def test_layer_names_in_compiled_hlo(tele_off):
    """jax.named_scope threads symbol layer names into the compiled
    program: HLO metadata attributes ops to fc1/fc2, not fusion.123.
    Independent of MXTPU_TELEMETRY (scopes are trace-time metadata)."""
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu', name='relu1')
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
    out = mx.sym.SoftmaxOutput(fc2, name='softmax')
    mod = mx.mod.Module(out, context=mx.cpu())
    mod.bind(data_shapes=[('data', (8, 10))],
             label_shapes=[('softmax_label', (8,))])
    mod.init_params()
    ex = mod._exec_group.execs[0]
    from mxnet_tpu import random as _random
    arg_data = tuple(a._data for a in ex.arg_arrays)
    aux_data = tuple(a._data for a in ex.aux_arrays)
    compiled = ex._fwd.lower(arg_data, aux_data, _random.next_key(),
                             False).compile()
    txt = compiled.as_text()
    for name in ('fc1', 'relu1', 'fc2'):
        assert name in txt, '%s missing from compiled HLO' % name


def test_fit_program_gauges_and_step_flops(tele_path):
    """Acceptance: a plain Module.fit yields program.* gauges,
    per-program FLOPs/bytes in the summary table, and the compiled
    step's FLOPs as xla.step_flops."""
    _mlp_fit(num_epoch=1)
    snap = telemetry.snapshot()
    prog_gauges = [n for n in snap['gauges'] if n.startswith('program.')]
    assert prog_gauges, 'no program.* gauges after fit'
    assert snap['gauges']['xla.step_flops'] > 0
    assert snap['counters']['program.compiles'] >= 1
    progs = telemetry.programs.snapshot_programs()
    assert any(n.startswith('fused_fit.window') for n in progs), progs
    rec = next(r for n, r in progs.items()
               if n.startswith('fused_fit.window'))
    assert rec['flops'] > 0 and rec['bytes_accessed'] > 0
    assert rec['compiles'] >= 1 and rec['dispatches'] >= 1
    table = telemetry.write_summary(log=False)
    assert '-- programs --' in table
    assert 'fused_fit.window' in table
    telemetry.shutdown()
    recs = _records(tele_path)
    assert any(r['type'] == 'program' and r.get('flops', 0) > 0
               for r in recs)
    summ = [r for r in recs if r['type'] == 'summary'][-1]
    assert summ.get('programs'), 'summary record carries no programs'


def test_fit_per_batch_loop_registers_executor_programs(tele_path,
                                                        monkeypatch):
    """The reference per-batch loop's executor programs (fwd_bwd) go
    through the registrar too, and fwd_bwd feeds the step FLOPs."""
    monkeypatch.setenv('MXTPU_FUSED_FIT', '0')
    _mlp_fit(num_epoch=1)
    progs = telemetry.programs.snapshot_programs()
    assert any(n.startswith('executor.fwd_bwd[') for n in progs), progs
    assert telemetry.snapshot()['gauges']['xla.step_flops'] > 0


@pytest.mark.parametrize('tele_on', ['0', '1'])
def test_fit_acceptance_on_off(tele_on, tmp_path, monkeypatch):
    """The off-by-default contract, guarded in the SAME suite as the
    on-path acceptance: with MXTPU_TELEMETRY=0 the new compile-site
    hooks add no telemetry I/O and leave the registry empty; with =1
    the per-program records and summary appear."""
    path = tmp_path / 'onoff.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', tele_on)
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    _reload_tele_flags()
    telemetry._reset_for_tests()
    try:
        io_before = tele_export._io_calls
        _mlp_fit(num_epoch=1)
        if tele_on == '0':
            assert tele_export._io_calls == io_before
            assert telemetry.get_registry().names() == []
            assert telemetry.programs.snapshot_programs() == {}
            assert not path.exists()
        else:
            telemetry.write_summary(log=False)
            telemetry.shutdown()
            recs = _records(path)
            assert any(r['type'] == 'program' for r in recs)
            summ = [r for r in recs if r['type'] == 'summary'][-1]
            assert summ['snapshot']['counters']['fit.steps'] == 4
            assert summ.get('programs')
    finally:
        telemetry._reset_for_tests()
        monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
        monkeypatch.delenv('MXTPU_TELEMETRY_PATH', raising=False)
        _reload_tele_flags()


def test_registered_program_numerics_match_lazy_jit(tele_path):
    """The AOT interceptor dispatches the SAME computation the lazy jit
    would have run (and falls back cleanly on a signature change)."""
    import jax.numpy as jnp
    import jax

    def f(x, y):
        return x * 2.0 + y

    wrapped = telemetry.programs.register('test.prog', jax.jit(f))
    a = jnp.arange(4.0)
    out = wrapped(a, 1.0)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a) * 2.0 + 1.0)
    out2 = wrapped(a + 1, 1.0)        # same signature: cached executable
    np.testing.assert_allclose(np.asarray(out2),
                               (np.asarray(a) + 1) * 2.0 + 1.0)
    # a varying traced python scalar must NOT key a fresh compile —
    # jit specializes on its type, not its value
    out_s = wrapped(a, 0.25)
    np.testing.assert_allclose(np.asarray(out_s),
                               np.asarray(a) * 2.0 + 0.25)
    out3 = wrapped(jnp.arange(7.0), 2.0)   # new shape: second program
    assert out3.shape == (7,)
    progs = telemetry.programs.snapshot_programs()
    assert progs['test.prog']['compiles'] == 2
    assert progs['test.prog']['dispatches'] == 4


def test_backend_compile_error_raised_once(tele_path):
    """A compile that fails in the backend (RESOURCE_EXHAUSTED among
    them) reaches the caller as it is after ONE attempt: the lazy jit
    is not asked to compile the same program again. What the
    ahead-of-time path itself cannot take still falls back."""
    class XlaRuntimeError(RuntimeError):
        pass

    class _Lowered:
        def __init__(self, exc):
            self.exc = exc

        def compile(self):
            raise self.exc

    class _Jitted:
        def __init__(self, exc):
            self.exc = exc
            self.lowered = self.called = 0

        def lower(self, *args):
            self.lowered += 1
            return _Lowered(self.exc)

        def __call__(self, *args):
            self.called += 1
            return 'lazy'

    oom = _Jitted(XlaRuntimeError('RESOURCE_EXHAUSTED: out of memory'))
    prog = telemetry.programs.register('test.oom', oom)
    with pytest.raises(XlaRuntimeError, match='RESOURCE_EXHAUSTED'):
        prog(np.zeros(3, np.float32))
    assert (oom.lowered, oom.called) == (1, 0)

    layout = _Jitted(TypeError('argument layout'))
    prog = telemetry.programs.register('test.layout', layout)
    assert prog(np.zeros(3, np.float32)) == 'lazy'
    assert prog(np.zeros(3, np.float32)) == 'lazy'
    assert (layout.lowered, layout.called) == (1, 2)


def test_step_flops_keeps_max_across_recompiles(tele_path):
    """A tail-batch shape variant compiling LAST must not shrink the
    per-step FLOPs the whole run's MFU is computed from."""
    full = {'flops': 1e9, 'bytes_accessed': 0.0, 'temp_bytes': 0,
            'argument_bytes': 0, 'output_bytes': 0,
            'generated_code_bytes': 0}
    tail = dict(full, flops=1e8)
    telemetry.programs.note_program('step_prog', analysis=full,
                                    step_flops=True)
    telemetry.programs.note_program('step_prog', analysis=tail,
                                    step_flops=True)
    assert telemetry.get_registry().gauge('xla.step_flops').value == 1e9
    # ... and the guard is GLOBAL: the tail's executor.fwd_bwd (a
    # different, smaller step program compiling after the fused window)
    # must not shrink it either
    telemetry.programs.note_program('other_step_prog', analysis=tail,
                                    step_flops=True)
    assert telemetry.get_registry().gauge('xla.step_flops').value == 1e9
    # per-name records keep the largest variant per field, not the last
    rec = telemetry.programs.snapshot_programs()['step_prog']
    assert rec['flops'] == 1e9 and rec['compiles'] == 2


def test_memory_stats_unavailable_warns_once(tele_path, caplog,
                                             monkeypatch):
    """An unsupported backend must WARN (once per process), not bury
    the explanation at debug forever."""
    monkeypatch.setattr(telemetry.xla, '_memory_stats_warned', False)

    class _Dev:
        platform = 'fake'

        def memory_stats(self):
            raise RuntimeError('memory_stats unimplemented')

    with caplog.at_level(logging.WARNING):
        assert telemetry.xla.sample_memory(_Dev()) is None
        assert telemetry.xla.sample_memory(_Dev()) is None
    warns = [r for r in caplog.records
             if 'memory_stats() unavailable' in r.getMessage()]
    assert len(warns) == 1


def test_oom_report(tele_path, caplog):
    """RESOURCE_EXHAUSTED yields a per-program memory breakdown (log +
    JSONL 'oom' record), once per process; other errors don't."""
    analysis = {'flops': 1e9, 'bytes_accessed': 2e9, 'temp_bytes': 1 << 30,
                'argument_bytes': 1 << 28, 'output_bytes': 1 << 20,
                'generated_code_bytes': 0}
    telemetry.programs.note_program('p1', analysis=analysis)
    assert not telemetry.programs.maybe_oom_report(
        RuntimeError('some unrelated failure'))
    with caplog.at_level(logging.ERROR):
        assert telemetry.programs.maybe_oom_report(RuntimeError(
            'RESOURCE_EXHAUSTED: Out of memory while trying to allocate '
            '1073741824 bytes'))
    msgs = [r.getMessage() for r in caplog.records
            if 'per-program memory breakdown' in r.getMessage()]
    assert len(msgs) == 1 and 'p1' in msgs[0]
    # second report is suppressed (crash-loops must not spam)
    with caplog.at_level(logging.ERROR):
        assert telemetry.programs.maybe_oom_report(
            RuntimeError('RESOURCE_EXHAUSTED: again'))
    assert len([r for r in caplog.records
                if 'per-program memory breakdown' in r.getMessage()]) == 1
    telemetry.shutdown()
    recs = _records(tele_path)
    ooms = [r for r in recs if r['type'] == 'oom']
    assert len(ooms) == 1 and 'p1' in ooms[0]['programs']


def test_report_cli_matches_live_summary(tele_path):
    """tools/telemetry_report renders the JSONL into the same table the
    live run logged (same renderer — offline traces read identically)."""
    import sys
    _mlp_fit(num_epoch=1)
    table = telemetry.write_summary(log=False)
    telemetry.shutdown()
    tools_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), 'tools')
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import telemetry_report
    out = telemetry_report.render(telemetry_report.load(str(tele_path)))
    # identical modulo the header's elapsed (rounded for the JSONL)
    assert out.splitlines()[1:] == table.splitlines()[1:]
    assert '-- programs --' in out
