"""telemetry/watchdog.py — the hang watchdog.

Contracts under test:
- flags off = NO thread ever, note_progress is one cached-bool no-op,
  and the lowered step program is byte-identical (the telemetry
  off-contract pattern — trivially: nothing is ever traced);
- a stall past MXTPU_WATCHDOG_SECS trips ONE hang incident: the
  counter, the JSONL ``hang`` record with all-thread stacks + the last
  progress mark, and the /healthz flip to a 503 ``hung`` digest;
- progress resuming clears the hang state (healthz back to 200) and
  re-arms for a later stall;
- suspend() (fit's exit path) disarms so post-training idle time can
  never false-trip;
- abort hooks run (bounded) before an action=abort exit — the
  checkpointer's drain path rides this.

The action=abort exit itself (os._exit(85)) is a whole-process
contract: tests/unittest/test_resilience.py drives it under the real
supervisor in the chaos lane.
"""
import json
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.telemetry import watchdog, serve

_WD_FLAGS = ('MXTPU_WATCHDOG_SECS', 'MXTPU_WATCHDOG_ACTION',
             'MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH')


def _reload():
    for f in _WD_FLAGS:
        flags.reload(f)


def _wd_threads():
    return [t for t in threading.enumerate()
            if t.name == 'mxtpu-watchdog' and t.is_alive()]


@pytest.fixture
def wd_off(monkeypatch):
    for f in _WD_FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()
    telemetry._reset_for_tests()
    yield
    telemetry._reset_for_tests()
    _reload()


@pytest.fixture
def wd_on(tmp_path, monkeypatch):
    """Watchdog armed at 0.25s (warn) with telemetry into a tmp log."""
    monkeypatch.setenv('MXTPU_WATCHDOG_SECS', '0.25')
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    _reload()
    telemetry._reset_for_tests()
    yield {'tele_path': tmp_path / 't.jsonl'}
    telemetry._reset_for_tests()
    for f in _WD_FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


def _wait_for(cond, timeout=5.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(0.02)
    return False


def test_flags_off_no_thread_no_op(wd_off):
    assert not watchdog.enabled()
    watchdog.note_progress('fit.step')     # must be a no-op
    assert not _wd_threads()
    assert watchdog.hang_info() is None
    assert watchdog.snapshot_watchdog() is None


def test_armed_but_idle_has_no_thread(wd_on):
    """The monitor thread only starts at the FIRST progress mark."""
    assert watchdog.enabled()
    assert not _wd_threads()


def test_stall_trips_incident_and_healthz_flips(wd_on):
    telemetry.enabled()                     # open the sink
    watchdog.note_progress('fit.step')
    assert _wd_threads()
    assert _wait_for(lambda: watchdog.hang_info() is not None)
    hi = watchdog.hang_info()
    assert hi['last_progress'] == 'fit.step'
    assert hi['stalled_s'] >= 0.25 and hi['threshold_s'] == 0.25
    assert 'MainThread' in hi['stacks']
    assert telemetry.get_registry().counter('watchdog.hangs').value == 1
    ok, body = serve.healthz_payload()
    assert not ok and body['status'] == 'hung'
    assert body['hang']['last_progress'] == 'fit.step'
    # the JSONL record landed (the trip flushes the sink; the monitor
    # thread sets hang_info first, so wait for the record's last byte)
    def whole_lines():
        return [ln for ln in open(wd_on['tele_path'])
                if ln.strip() and ln.endswith('\n')]
    assert _wait_for(lambda: any('"hang"' in ln for ln in whole_lines()))
    recs = [json.loads(ln) for ln in whole_lines()]
    hangs = [r for r in recs if r['type'] == 'hang']
    assert len(hangs) == 1
    assert hangs[0]['stacks'] and hangs[0]['action'] == 'warn'
    # progress resumes -> the hang clears and healthz goes green
    watchdog.note_progress('fit.step')
    assert watchdog.hang_info() is None
    ok, body = serve.healthz_payload()
    assert ok and body['status'] == 'ok'
    # ...but the last digest stays available for reports
    assert watchdog.snapshot_watchdog()['stalled_s'] >= 0.25
    # and a LATER stall trips again (re-armed)
    assert _wait_for(lambda: watchdog.hang_info() is not None)
    assert telemetry.get_registry().counter('watchdog.hangs').value == 2


def test_suspend_prevents_false_trip(wd_on):
    watchdog.note_progress('fit.step')
    watchdog.suspend()
    time.sleep(0.7)
    assert watchdog.hang_info() is None
    assert telemetry.get_registry().counter('watchdog.hangs').value == 0
    # the next mark re-arms
    watchdog.note_progress('fit.step')
    assert _wait_for(lambda: watchdog.hang_info() is not None)


def test_suspend_clears_active_hang(wd_on):
    """fit unwinding past a warn-mode hang must not leave /healthz
    stuck at 503 'hung' forever: suspend() clears the active digest."""
    watchdog.note_progress('fit.step')
    assert _wait_for(lambda: watchdog.hang_info() is not None)
    watchdog.suspend()
    assert watchdog.hang_info() is None
    ok, body = serve.healthz_payload()
    assert ok and body['status'] == 'ok'
    # the digest survives for reports, marked inactive
    assert watchdog.snapshot_watchdog()['active'] is False


def test_abort_hooks_run_before_exit_path(wd_on, monkeypatch):
    """The abort path runs registered hooks (bounded) before os._exit;
    patch the exit so the trip is observable in-process."""
    monkeypatch.setenv('MXTPU_WATCHDOG_ACTION', 'abort')
    _reload()
    telemetry._reset_for_tests()
    ran = []
    exited = []
    monkeypatch.setattr(watchdog.os, '_exit',
                        lambda code: (exited.append(code),
                                      watchdog.suspend()))
    watchdog.add_abort_hook(lambda: ran.append('drain'))
    watchdog.note_progress('fit.step')
    assert _wait_for(lambda: exited != [])
    assert exited == [watchdog.HANG_EXIT_CODE] and ran == ['drain']


def test_fit_marks_and_suspends(wd_on):
    """A real fit feeds marks (thread comes up) and suspends at exit —
    no false trip afterwards, no incident during the run."""
    data = mx.sym.Variable('data')
    fc = mx.sym.FullyConnected(data, num_hidden=4, name='fc')
    sym = mx.sym.SoftmaxOutput(fc, name='softmax')
    np.random.seed(0)
    X = np.random.randn(32, 6).astype(np.float32)
    y = (np.random.rand(32) * 4).astype(int).astype(np.float32)
    mod = mx.mod.Module(sym, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=8, label_name='softmax_label')
    mod.fit(it, num_epoch=1, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),))
    assert _wd_threads()
    assert telemetry.get_registry().counter('watchdog.hangs').value == 0
    # fit suspended the monitor: idling past the threshold is clean
    time.sleep(0.7)
    assert watchdog.hang_info() is None


def test_score_and_predict_disarm_on_exit(wd_on):
    """Standalone eval after fit must not leave the watchdog armed:
    score()/predict() marks re-arm it, their exit disarms it — long
    post-eval host work cannot false-trip (or be abort-killed)."""
    data = mx.sym.Variable('data')
    fc = mx.sym.FullyConnected(data, num_hidden=4, name='fc')
    sym = mx.sym.SoftmaxOutput(fc, name='softmax')
    np.random.seed(0)
    X = np.random.randn(32, 6).astype(np.float32)
    y = (np.random.rand(32) * 4).astype(int).astype(np.float32)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=8,
                              label_name='softmax_label'),
            num_epoch=1, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),))
    mod.score(mx.io.NDArrayIter(X, y, batch_size=8,
                                label_name='softmax_label'), 'acc')
    time.sleep(0.7)
    assert watchdog.hang_info() is None
    mod.predict(mx.io.NDArrayIter(X, y, batch_size=8,
                                  label_name='softmax_label'))
    time.sleep(0.7)
    assert watchdog.hang_info() is None
    assert telemetry.get_registry().counter('watchdog.hangs').value == 0


def test_lowered_program_byte_identical_with_watchdog(wd_off, monkeypatch):
    """The watchdog is purely host-side: the executor's lowered step
    program is byte-identical with the flag on or off (the same
    off-contract assertion the health sentinels keep)."""
    import jax

    def lower_text():
        telemetry._reset_for_tests()
        data = mx.sym.Variable('data')
        fc = mx.sym.FullyConnected(data, num_hidden=4, name='fc')
        sym = mx.sym.SoftmaxOutput(fc, name='softmax')
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[('data', (8, 6))],
                 label_shapes=[('softmax_label', (8,))], for_training=True)
        mod.init_params(initializer=mx.init.Uniform(0.01))
        e = mod._exec_group.execs[0]
        args = tuple(a._data for a in e.arg_dict.values())
        auxs = tuple(a._data for a in e.aux_dict.values())
        key = jax.random.PRNGKey(0)
        return jax.jit(e._run_eager, static_argnums=(3,)).lower(
            args, auxs, key, True).as_text()

    off = lower_text()
    monkeypatch.setenv('MXTPU_WATCHDOG_SECS', '60')
    _reload()
    on = lower_text()
    assert on == off
