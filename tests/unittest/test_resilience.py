"""Resilient training: checkpoints, restart-from-last-good, faults.

The recovery-loop contracts (module/checkpointing.py,
module/resilient_fit.py, mxnet_tpu/faults.py, tools/train_supervisor):

- kill-and-resume parity: a supervised fit with an injected nan-grad
  at step k restores from last-good, resumes, and reaches final params
  identical (within tolerance) to an uninterrupted run of the same
  seed — on BOTH the fused-window and per-batch loops;
- the async save does not block the step loop (a slowed write overlaps
  batches trained after it started) and a clean run's final state
  always commits (the busy-writer skip never drops the end state);
- flags off = zero new overhead: no checkpointer object, no writer
  thread, no armed fault, empty registry;
- every fault kind drills its recovery path: checkpoint-corrupt falls
  back to an older step, dispatch-exception exercises restart backoff
  without a health incident, slow-host delays the step counter;
- restart budget/retryability in resilient_fit, restart records in the
  JSONL stream, and the whole-process supervisor's relaunch loop.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.module.resilient_fit import resilient_fit, is_retryable
from mxnet_tpu.telemetry.health import TrainingHealthError

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, 'tools'))

_RES_FLAGS = ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH', 'MXTPU_HEALTH',
              'MXTPU_HEALTH_ACTION', 'MXTPU_CKPT_DIR', 'MXTPU_CKPT_EVERY',
              'MXTPU_CKPT_KEEP', 'MXTPU_CKPT_ASYNC', 'MXTPU_CKPT_RESUME',
              'MXTPU_RESTART_MAX', 'MXTPU_RESTART_BACKOFF',
              'MXTPU_FAULT_INJECT', 'MXTPU_FUSED_FIT')


def _reload():
    for f in _RES_FLAGS:
        flags.reload(f)


def _reset():
    telemetry._reset_for_tests()
    faults._reset_for_tests()


@pytest.fixture
def res_env(tmp_path, monkeypatch):
    """Telemetry + health(raise) + checkpointing into a tmp dir, zero
    restart backoff; fully restored afterwards. Yields a dict the test
    mutates (fault spec etc.) before calling its fit helpers."""
    ckpt_dir = tmp_path / 'ckpts'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH',
                       str(tmp_path / 'telemetry.jsonl'))
    monkeypatch.setenv('MXTPU_HEALTH', '1')
    monkeypatch.setenv('MXTPU_HEALTH_ACTION', 'raise')
    monkeypatch.setenv('MXTPU_CKPT_DIR', str(ckpt_dir))
    monkeypatch.setenv('MXTPU_CKPT_EVERY', '2')
    monkeypatch.setenv('MXTPU_RESTART_BACKOFF', '0')
    _reload()
    _reset()
    yield {'ckpt_dir': ckpt_dir,
           'tele_path': tmp_path / 'telemetry.jsonl',
           'monkeypatch': monkeypatch}
    _reset()
    for f in _RES_FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


@pytest.fixture
def all_off(monkeypatch):
    for f in _RES_FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()
    _reset()
    yield
    _reset()
    _reload()


def _records(path):
    # the JSONL sink buffers (_FLUSH_EVERY lines); drain it so records
    # emitted between fit attempts are on disk before we read
    sink = telemetry._state.sink
    if sink is not None:
        sink.flush()
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _mlp_sym():
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu')
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
    return mx.sym.SoftmaxOutput(fc2, name='softmax')


def _data(n=32):
    np.random.seed(0)
    X = np.random.randn(n, 10).astype(np.float32)
    y = (np.random.rand(n) * 4).astype(int).astype(np.float32)
    return X, y


def _iter(X, y, batch=8):
    return mx.io.NDArrayIter(X, y, batch_size=batch,
                             label_name='softmax_label')


def _run(X, y, num_epoch, resilient=False, batch=8, callback=None):
    """One fit from mx seed 0; returns (module, restarts)."""
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    kw = dict(num_epoch=num_epoch, optimizer='sgd',
              batch_end_callback=callback,
              optimizer_params=(('learning_rate', 0.1),))
    if resilient:
        restarts = resilient_fit(mod, _iter(X, y, batch), **kw)
    else:
        restarts = 0
        mod.fit(_iter(X, y, batch), **kw)
    return mod, restarts


def _reference(X, y, num_epoch):
    """Uninterrupted same-seed run with checkpoint/fault flags off."""
    os.environ.pop('MXTPU_FAULT_INJECT', None)
    os.environ.pop('MXTPU_CKPT_DIR', None)
    _reload()
    faults._reset_for_tests()
    mod, _ = _run(X, y, num_epoch)
    return mod


def _assert_params_match(a, b, tol=1e-6):
    pa, _ = a.get_params()
    pb, _ = b.get_params()
    assert set(pa) == set(pb)
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   atol=tol, err_msg=k)


# ---------------------------------------------------------------------------
# the acceptance pair: kill-and-resume parity + async non-blocking
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_kill_and_resume_parity_fused(res_env):
    """nan-grad at batch 5 (mid-window on the fused path): health
    raises, resilient_fit restores from the last-good checkpoint and
    resumes — final params identical to the uninterrupted run."""
    X, y = _data()
    res_env['monkeypatch'].setenv('MXTPU_FAULT_INJECT', 'nan-grad:5')
    _reload()
    mod, restarts = _run(X, y, num_epoch=4, resilient=True)
    assert restarts == 1
    recs = [r for r in _records(res_env['tele_path'])
            if r['type'] == 'restart']
    assert len(recs) == 1
    assert recs[0]['reason'] == 'TrainingHealthError'
    assert recs[0]['restore_step'] == 4
    assert recs[0]['diagnostic']['first_bad_layer'] == 'data'
    ref = _reference(X, y, num_epoch=4)
    _assert_params_match(mod, ref)


@pytest.mark.chaos
def test_kill_and_resume_parity_per_batch(res_env):
    """Same parity on the per-batch reference loop (fused fit off):
    the executor-path sentinel raises BEFORE the optimizer update, so
    restore lands on a checkpoint the nan never touched."""
    X, y = _data()
    mp = res_env['monkeypatch']
    mp.setenv('MXTPU_FUSED_FIT', '0')
    mp.setenv('MXTPU_FAULT_INJECT', 'nan-grad:5')
    mp.setenv('MXTPU_CKPT_EVERY', '3')
    _reload()
    mod, restarts = _run(X, y, num_epoch=4, resilient=True)
    assert restarts == 1
    os.environ['MXTPU_FUSED_FIT'] = '0'
    ref = _reference(X, y, num_epoch=4)
    _assert_params_match(mod, ref)


def test_async_save_overlaps_step_loop(res_env, monkeypatch):
    """The save must not block the next dispatch: with the write
    artificially slowed, batches keep completing strictly inside the
    save window, and the run's FINAL state still commits (the
    busy-writer skip is repaired by finish())."""
    from mxnet_tpu.parallel import checkpoint as pckpt
    from mxnet_tpu.module import checkpointing as mckpt
    saves = []
    real_save = pckpt.save

    def slow_save(mngr, step, state, wait=True, meta=None):
        t0 = time.time()
        time.sleep(0.4)
        out = real_save(mngr, step, state, wait=wait, meta=meta)
        saves.append((step, t0, time.time()))
        return out

    monkeypatch.setattr(pckpt, 'save', slow_save)
    X, y = _data(64)
    steps = []
    mod, _ = _run(X, y, num_epoch=2,
                  callback=lambda p: steps.append(time.time()))
    assert saves, 'no checkpoint was written'
    overlapped = [s for (_, t0, t1) in saves
                  for s in steps if t0 < s < t1]
    assert overlapped, 'no batch completed while a save was in flight'
    # the end state committed even though mid-run saves were skipped
    # while the slow writer was busy
    ckpt = mod.__dict__['_mxtpu_ckpt']
    assert ckpt.last_good == ckpt.global_step == 16
    snap = telemetry.snapshot()
    assert snap['counters']['ckpt.saves'] >= 1
    assert 'mxtpu-ckpt' not in [t.name.split('_')[0]
                                for t in threading.enumerate()
                                if t.is_alive() and 'ckpt' in t.name], \
        'writer thread must be torn down at fit end'


def test_fused_capture_metric_covers_saved_steps(res_env, monkeypatch):
    """A fused-path capture must flush the pipelined stats first: the
    saved eval-metric state covers every step the checkpoint claims
    (pre-fix it trailed one window — W samples were lost on resume)."""
    from mxnet_tpu.module import checkpointing as mckpt
    metas = []
    real = mckpt.TrainCheckpointer._do_save

    def spy(self, step, tree, meta):
        metas.append((step, meta['metric']))
        return real(self, step, tree, meta)

    monkeypatch.setattr(mckpt.TrainCheckpointer, '_do_save', spy)
    X, y = _data()                      # 4 batches of 8 per epoch
    _run(X, y, num_epoch=2)
    assert metas
    for step, metric in metas:
        covered = sum(n for _, _, n in metric)
        in_epoch = step % 4 or 4
        assert covered == in_epoch * 8, \
            'step %d capture covers %d samples' % (step, covered)


def test_flags_off_zero_overhead(all_off):
    """All flags off: no checkpointer is built, no writer thread ever
    exists, no fault is armed, and the registry stays empty — the same
    no-op contract the telemetry stack asserts."""
    X, y = _data()
    mod, _ = _run(X, y, num_epoch=1)
    assert '_mxtpu_ckpt' not in mod.__dict__
    assert not faults.enabled()
    assert telemetry.get_registry().names() == []
    assert not [t for t in threading.enumerate() if 'mxtpu-ckpt' in t.name]


# ---------------------------------------------------------------------------
# resume mechanics
# ---------------------------------------------------------------------------

def test_fresh_fit_resumes_from_last_good(res_env):
    """A NEW fit() against a directory holding certified checkpoints
    restores and skips the already-trained epochs — and the resumed
    run matches the uninterrupted one exactly."""
    X, y = _data()
    _run(X, y, num_epoch=2)
    recs = _records(res_env['tele_path'])
    assert any(r.get('name') == 'ckpt.save' for r in recs
               if r['type'] == 'span')
    # second process-equivalent: fresh module, same flags
    telemetry._reset_for_tests()
    mod2, _ = _run(X, y, num_epoch=4)
    ref = _reference(X, y, num_epoch=4)
    _assert_params_match(mod2, ref)


def test_resume_off_starts_fresh(res_env):
    """MXTPU_CKPT_RESUME=0 ignores existing checkpoints."""
    X, y = _data()
    _run(X, y, num_epoch=2)
    res_env['monkeypatch'].setenv('MXTPU_CKPT_RESUME', '0')
    _reload()
    telemetry._reset_for_tests()
    mod2, _ = _run(X, y, num_epoch=2)
    ckpt = mod2.__dict__['_mxtpu_ckpt']
    assert ckpt.restored_step is None


@pytest.mark.chaos
def test_warn_action_never_certifies_poisoned_capture(res_env):
    """MXTPU_HEALTH_ACTION=warn keeps training after a NaN trains into
    the params: every capture AFTER the incident is tainted and the
    last-good pointer must freeze at the last clean step."""
    X, y = _data()
    mp = res_env['monkeypatch']
    mp.setenv('MXTPU_HEALTH_ACTION', 'warn')
    mp.setenv('MXTPU_FAULT_INJECT', 'nan-grad:5')
    _reload()
    mod, _ = _run(X, y, num_epoch=4)      # runs to completion, poisoned
    ckpt = mod.__dict__['_mxtpu_ckpt']
    # saves at 4, 8, 12, 16 — only the pre-incident step 4 certifies
    assert ckpt.last_good == 4
    snap = telemetry.snapshot()
    assert snap['counters']['ckpt.uncertified'] >= 1


@pytest.mark.chaos
def test_corrupt_checkpoint_falls_back_to_older(res_env):
    """checkpoint-corrupt:8 scribbles over the newest committed step:
    the next resume falls back to step 4 and still completes."""
    X, y = _data()
    res_env['monkeypatch'].setenv('MXTPU_FAULT_INJECT',
                                  'checkpoint-corrupt:8')
    _reload()
    _run(X, y, num_epoch=2)          # saves at 4 and 8; 8 corrupted
    faults._reset_for_tests()
    os.environ.pop('MXTPU_FAULT_INJECT', None)
    _reload()
    telemetry._reset_for_tests()
    mod2, _ = _run(X, y, num_epoch=4)
    ckpt = mod2.__dict__['_mxtpu_ckpt']
    assert ckpt.restored_step == 4
    ref = _reference(X, y, num_epoch=4)
    _assert_params_match(mod2, ref)


# ---------------------------------------------------------------------------
# fault kinds / seams
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_dispatch_exception_restart(res_env):
    """An injected dispatch failure (no health incident) restores and
    retries through the restart budget."""
    X, y = _data()
    res_env['monkeypatch'].setenv('MXTPU_FAULT_INJECT',
                                  'dispatch-exception:5:dispatch')
    _reload()
    mod, restarts = _run(X, y, num_epoch=4, resilient=True)
    assert restarts == 1
    recs = [r for r in _records(res_env['tele_path'])
            if r['type'] == 'restart']
    assert recs and recs[0]['reason'] == 'FaultInjected'
    snap = telemetry.snapshot()
    assert snap['counters']['health.restarts'] == 1
    ref = _reference(X, y, num_epoch=4)
    _assert_params_match(mod, ref)


@pytest.mark.chaos
def test_executor_seam_per_batch(res_env):
    """The executor seam fires on the per-batch loop."""
    X, y = _data()
    mp = res_env['monkeypatch']
    mp.setenv('MXTPU_FUSED_FIT', '0')
    mp.setenv('MXTPU_FAULT_INJECT', 'dispatch-exception:3:executor')
    _reload()
    mod, restarts = _run(X, y, num_epoch=2, resilient=True)
    assert restarts == 1


@pytest.mark.chaos
def test_slow_host_fault_delays_steps(all_off, monkeypatch):
    """slow-host:0:40 sleeps ~40ms per counted step from step 0 on."""
    monkeypatch.setenv('MXTPU_FAULT_INJECT', 'slow-host:0:40')
    _reload()
    faults._reset_for_tests()
    assert faults.enabled()
    t0 = time.time()
    faults.note_steps(1)
    assert time.time() - t0 >= 0.03
    assert faults.spec() == ('slow-host', 0, '40')


def test_fault_parse_rejects_garbage(all_off, monkeypatch):
    monkeypatch.setenv('MXTPU_FAULT_INJECT', 'not-a-kind:3')
    _reload()
    faults._reset_for_tests()
    assert not faults.enabled()   # warn + disabled, never raises


# ---------------------------------------------------------------------------
# resilient_fit budget / retryability
# ---------------------------------------------------------------------------

class _FakeIter:
    def reset(self):
        pass


class _FakeModule:
    def __init__(self, fail_times, exc=RuntimeError):
        self.calls = 0
        self.fail_times = fail_times
        self.exc = exc

    def fit(self, train_data, **kw):
        self.calls += 1
        if self.calls <= self.fail_times:
            raise self.exc('boom %d' % self.calls)


def test_restart_budget_exhausted(all_off):
    m = _FakeModule(fail_times=99)
    with pytest.raises(RuntimeError):
        resilient_fit(m, _FakeIter(), restart_max=2, restart_backoff=0)
    assert m.calls == 3               # initial + 2 restarts


def test_restart_recovers_within_budget(all_off):
    m = _FakeModule(fail_times=2)
    restarts = resilient_fit(m, _FakeIter(), restart_max=3,
                             restart_backoff=0)
    assert restarts == 2 and m.calls == 3


def test_non_retryable_raises_immediately(all_off):
    m = _FakeModule(fail_times=99, exc=ValueError)
    with pytest.raises(ValueError):
        resilient_fit(m, _FakeIter(), restart_max=3, restart_backoff=0)
    assert m.calls == 1
    assert is_retryable(TrainingHealthError('x'))
    assert is_retryable(faults.FaultInjected('x'))
    assert not is_retryable(AssertionError('x'))
    assert not is_retryable(KeyboardInterrupt())


# ---------------------------------------------------------------------------
# restart records in tooling
# ---------------------------------------------------------------------------

def test_report_reconstructs_restart_counts(all_off):
    import telemetry_report
    recs = [{'type': 'restart', 'attempt': 1, 'reason': 'X'},
            {'type': 'restart', 'attempt': 2, 'reason': 'X'},
            {'type': 'restart', 'attempt': 2, 'final': True,
             'reason': 'clean_exit'}]
    health = telemetry_report._reconstruct_health(recs)
    assert health['restarts'] == 2
    from mxnet_tpu.telemetry import export
    lines = export._health_lines({'nonfinite_steps': 0, 'incidents': [],
                                  'anomaly_counts': {}, 'restarts': 2})
    assert any('restarts' in ln and '2' in ln for ln in lines)


# ---------------------------------------------------------------------------
# hang / host-loss faults + the watchdog/supervisor recovery tiers
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_hang_fault_sleeps_at_seam(all_off, monkeypatch):
    """hang:0:0.2 wedges the first dispatch seam for ~0.2s, once."""
    monkeypatch.setenv('MXTPU_FAULT_INJECT', 'hang:0:0.2')
    _reload()
    faults._reset_for_tests()
    assert faults.enabled()
    t0 = time.time()
    faults.maybe_raise('dispatch', upcoming=1)
    assert time.time() - t0 >= 0.15
    t0 = time.time()
    faults.maybe_raise('dispatch', upcoming=1)   # fired once: no re-sleep
    assert time.time() - t0 < 0.1


@pytest.mark.chaos
def test_host_loss_fault_exits_113(tmp_path):
    """host-loss:0 os._exits with the distinct code — driven in a
    subprocess (faults.py spec-loaded standalone: no package, no jax,
    so the child is fast)."""
    child = tmp_path / 'hl.py'
    child.write_text(
        "import importlib.util, os\n"
        "os.environ['MXTPU_FAULT_INJECT'] = 'host-loss:0'\n"
        "spec = importlib.util.spec_from_file_location('f', %r)\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "m.maybe_raise('dispatch', upcoming=1)\n"
        "raise SystemExit('host-loss did not fire')\n"
        % os.path.join(REPO, 'mxnet_tpu', 'faults.py'))
    proc = subprocess.run([sys.executable, str(child)],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 113, (proc.returncode, proc.stderr)


# the user-style training script the whole-process chaos tests drive
# (under tools/train_supervisor.py or standalone). CHILD_MARKER counts
# attempts and disarms the one-shot env fault on relaunch — an
# env-armed fault re-fires in EVERY relaunch otherwise (the env rides
# into each child).
_CHAOS_CHILD = '''
import os, re, sys
ndev = int(os.environ.get('CHILD_DEVICES', '8'))
f = re.sub(r'--xla_force_host_platform_device_count=\\d+', '',
           os.environ.get('XLA_FLAGS', ''))
os.environ['XLA_FLAGS'] = \\
    (f + ' --xla_force_host_platform_device_count=%d' % ndev).strip()
marker = os.environ['CHILD_MARKER']
first = not os.path.exists(marker)
open(marker, 'a').write('x\\n')
if not first:
    os.environ.pop('MXTPU_FAULT_INJECT', None)
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import mxnet_tpu as mx
data = mx.sym.Variable('data')
fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
act = mx.sym.Activation(fc1, act_type='relu')
fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
sym = mx.sym.SoftmaxOutput(fc2, name='softmax')
np.random.seed(0)
X = np.random.randn(64, 10).astype(np.float32)
y = (np.random.rand(64) * 4).astype(int).astype(np.float32)
mx.random.seed(0)
nctx = int(os.environ.get('CHILD_CONTEXTS', '1'))
ctx = [mx.cpu(i) for i in range(nctx)] if nctx > 1 else mx.cpu()
mod = mx.mod.Module(sym, context=ctx)
it = mx.io.NDArrayIter(X, y, batch_size=8, label_name='softmax_label')
mod.fit(it, num_epoch=3, optimizer='sgd',
        optimizer_params=(('learning_rate', 0.1),))
mod.save_params(os.environ['CHILD_OUT'])
'''


def _chaos_env(tmp_path, **extra):
    env = dict(os.environ)
    env.pop('MXTPU_FAULT_INJECT', None)
    env.update({'PYTHONPATH': REPO,
                'MXTPU_TELEMETRY': '1',
                'MXTPU_TELEMETRY_PATH': str(tmp_path / 'tele.jsonl'),
                'MXTPU_CKPT_DIR': str(tmp_path / 'ckpts'),
                'MXTPU_CKPT_EVERY': '2',
                'CHILD_MARKER': str(tmp_path / 'marker'),
                'CHILD_OUT': str(tmp_path / 'params')})
    env.update({k: str(v) for k, v in extra.items()})
    return env


def _load_params(path):
    import mxnet_tpu as mx_
    return {k: v.asnumpy() for k, v in mx_.nd.load(str(path)).items()}


def _reference_params(tmp_path, **extra):
    """The uninterrupted same-seed run of the chaos child (no faults,
    no checkpoints) — the parity baseline."""
    ref = tmp_path / 'ref'
    ref.mkdir()
    child = tmp_path / 'child.py'
    env = dict(os.environ)
    for k in ('MXTPU_FAULT_INJECT', 'MXTPU_CKPT_DIR', 'MXTPU_CKPT_EVERY',
              'MXTPU_WATCHDOG_SECS', 'MXTPU_WATCHDOG_ACTION',
              'MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH'):
        env.pop(k, None)
    env.update({'PYTHONPATH': REPO, 'CHILD_MARKER': str(ref / 'marker'),
                'CHILD_OUT': str(ref / 'params')})
    env.update({k: str(v) for k, v in extra.items()})
    proc = subprocess.run([sys.executable, str(child)], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return _load_params(ref / 'params')


@pytest.mark.chaos
@pytest.mark.slow
def test_hang_watchdog_abort_supervisor_relaunch_parity(tmp_path):
    """The hang chaos e2e: an injected wedged dispatch is detected by
    the in-process watchdog, aborted with the distinct exit code 85
    (after the abort hook drains + certifies the in-flight save), the
    supervisor relaunches, the relaunch restores from last-good, and
    the final parameters are BIT-EXACT against an uninterrupted
    same-seed run."""
    child = tmp_path / 'child.py'
    child.write_text(_CHAOS_CHILD)
    env = _chaos_env(tmp_path,
                     MXTPU_WATCHDOG_SECS='0.5',
                     MXTPU_WATCHDOG_ACTION='abort',
                     MXTPU_FAULT_INJECT='hang:13:600')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'train_supervisor.py'),
         '--backoff', '0', '--', sys.executable, str(child)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert 'hang watchdog abort' in proc.stderr
    # two attempts: the hung one (aborted 85) + the clean relaunch
    assert len(open(tmp_path / 'marker').read().split()) == 2
    recs = _records(tmp_path / 'tele.jsonl')
    hangs = [r for r in recs if r['type'] == 'hang']
    assert len(hangs) == 1 and hangs[0]['action'] == 'abort'
    restarts = [r for r in recs if r['type'] == 'restart'
                and not r.get('final')]
    assert len(restarts) == 1 and restarts[0]['exit_code'] == 85
    # the abort hook certified a checkpoint: the relaunch RESTORED
    # (ckpt.resume event) instead of starting fresh
    resumes = [r for r in recs if r.get('name') == 'ckpt.resume']
    assert resumes and resumes[0]['restored_step'] >= 2
    got = _load_params(tmp_path / 'params')
    ref = _reference_params(tmp_path)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)


@pytest.mark.chaos
@pytest.mark.slow
def test_host_loss_reshard_restore_8_to_4(tmp_path):
    """The host-loss chaos e2e: os._exit mid-window on an 8-device SPMD
    mesh, then a relaunch on HALF the mesh (4 devices) restores the
    8-device checkpoint (global shapes validated, orbax re-lays the
    shards out), resumes, and matches the uninterrupted 8-device run.
    Cross-mesh parity is ulp-level (the dp reduction order changes
    with the mesh size), not bit-exact — atol 1e-6."""
    child = tmp_path / 'child.py'
    child.write_text(_CHAOS_CHILD)
    # sync saves: the kill is os._exit with no drain, so only an
    # already-committed save can be certified at the next step
    common = dict(MXTPU_CKPT_ASYNC='0',
                  MXTPU_FAULT_INJECT='host-loss:13')
    env = _chaos_env(tmp_path, CHILD_DEVICES='8', CHILD_CONTEXTS='8',
                     **common)
    proc = subprocess.run([sys.executable, str(child)], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 113, (proc.returncode, proc.stderr[-2000:])
    assert (tmp_path / 'ckpts' / 'last_good.step').exists()
    # survivors relaunch on the smaller mesh; the marker disarms the
    # fault exactly as a supervisor relaunch would
    env = _chaos_env(tmp_path, CHILD_DEVICES='4', CHILD_CONTEXTS='4',
                     **common)
    proc = subprocess.run([sys.executable, str(child)], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = _records(tmp_path / 'tele.jsonl')
    resumes = [r for r in recs if r.get('name') == 'ckpt.resume']
    assert resumes, 'the 4-device relaunch did not restore'
    got = _load_params(tmp_path / 'params')
    ref = _reference_params(tmp_path, CHILD_DEVICES='8',
                            CHILD_CONTEXTS='8')
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], atol=1e-6, err_msg=k)


@pytest.mark.chaos
def test_supervisor_liveness_kills_wedged_child(tmp_path):
    """The supervisor-side liveness tier: a child whose telemetry JSONL
    stops growing is SIGTERM'd and relaunched against the same budget
    (reason liveness_timeout). The child is deliberately framework-free
    — a real child's startup compile would stall the log far longer
    than any test-scale threshold."""
    tele = tmp_path / 'tele.jsonl'
    marker = tmp_path / 'marker'
    child = tmp_path / 'child.py'
    child.write_text(
        "import json, os, signal, sys, time\n"
        "# a graceful save-and-exit-0 SIGTERM handler must NOT let a\n"
        "# liveness kill masquerade as a clean completion\n"
        "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0))\n"
        "first = not os.path.exists(%r)\n"
        "open(%r, 'a').write('x\\n')\n"
        "with open(%r, 'a') as f:\n"
        "    f.write(json.dumps({'type': 'span'}) + '\\n')\n"
        "    f.flush()\n"
        "    if first:\n"
        "        time.sleep(3600)   # wedged: no more records, ever\n"
        "sys.exit(0)\n" % (str(marker), str(marker), str(tele)))
    env = dict(os.environ)
    env.update({'MXTPU_TELEMETRY_PATH': str(tele)})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'train_supervisor.py'),
         '--backoff', '0', '--liveness', '2', '--quiet', '--',
         sys.executable, str(child)],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    recs = _records(tele)
    mid = [r for r in recs if r['type'] == 'restart' and not r.get('final')]
    assert len(mid) == 1 and mid[0]['reason'] == 'liveness_timeout'
    assert recs[-1]['final'] and recs[-1]['reason'] == 'clean_exit'


# ---------------------------------------------------------------------------
# kvstore transient-error retry
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_kvstore_pull_reconnects_after_broken_socket(all_off, monkeypatch):
    """A dead server socket is a transient error: pull reconnects and
    retrieves every shard instead of hanging or dying."""
    import mxnet_tpu as mx_
    monkeypatch.setenv('MXTPU_KVSTORE_TIMEOUT', '10')
    monkeypatch.setenv('MXTPU_KVSTORE_RETRIES', '2')
    flags.reload('MXTPU_KVSTORE_TIMEOUT')
    flags.reload('MXTPU_KVSTORE_RETRIES')
    kv = mx_.kv.create('dist_sync')
    a = mx_.nd.array(np.arange(8, dtype=np.float32))
    kv.init(7, a)
    kv._conns[0].sock.close()        # transient connection loss
    out = mx_.nd.zeros(8)
    kv.pull(7, out=out)
    np.testing.assert_array_equal(out.asnumpy(), a.asnumpy())


@pytest.mark.chaos
def test_kvstore_lost_push_is_loud_not_stale(all_off, monkeypatch):
    """A connection that dies with an un-applied push in flight must
    NOT be silently retried past: the server is missing a gradient, so
    the next pull raises ConnectionError (restore-from-checkpoint
    territory) instead of returning stale weights."""
    import mxnet_tpu as mx_
    monkeypatch.setenv('MXTPU_KVSTORE_TIMEOUT', '5')
    monkeypatch.setenv('MXTPU_KVSTORE_RETRIES', '2')
    flags.reload('MXTPU_KVSTORE_TIMEOUT')
    flags.reload('MXTPU_KVSTORE_RETRIES')
    kv = mx_.kv.create('dist_sync')
    a = mx_.nd.array(np.arange(8, dtype=np.float32))
    kv.init(11, a)
    kv._conns[0].sock.close()
    kv.push(11, mx_.nd.array(np.ones(8, dtype=np.float32)))   # lost
    # give the comm thread a moment to hit the dead socket
    deadline = time.time() + 5
    while not kv._conns[0].lost_push and time.time() < deadline:
        time.sleep(0.02)
    out = mx_.nd.zeros(8)
    from mxnet_tpu.kvstore_dist import LostPushError
    with pytest.raises(LostPushError, match='push'):
        kv.pull(11, out=out)
    assert issubclass(LostPushError, ConnectionError)
    # a server-side 'error' reply to a push is as lost as a dead
    # socket: the gate must fire for it too
    kv2 = mx_.kv.create('dist_sync')
    kv2.init(12, mx_.nd.array(np.arange(4, dtype=np.float32)))
    kv2._conns[0].lost_push = True     # what the error-reply path sets
    with pytest.raises(LostPushError):
        kv2._reconnect(0)


def test_kvstore_retry_budget_exhausts_to_connection_error(all_off,
                                                           monkeypatch):
    """Past the retry budget the failure surfaces as ConnectionError —
    the retryable family resilient_fit restarts on."""
    import mxnet_tpu as mx_
    monkeypatch.setenv('MXTPU_KVSTORE_TIMEOUT', '0.2')
    monkeypatch.setenv('MXTPU_KVSTORE_RETRIES', '1')
    flags.reload('MXTPU_KVSTORE_TIMEOUT')
    flags.reload('MXTPU_KVSTORE_RETRIES')
    kv = mx_.kv.create('dist_sync')
    a = mx_.nd.array(np.arange(4, dtype=np.float32))
    kv.init(9, a)
    err = ConnectionError('kvstore server 0 unreachable')
    monkeypatch.setattr(type(kv), '_request',
                        lambda self, sid, msg: (_ for _ in ()).throw(err))
    kv._conns[0].sock.close()
    out = mx_.nd.zeros(4)
    with pytest.raises(ConnectionError):
        kv.pull(9, out=out)
    from mxnet_tpu.module.resilient_fit import is_retryable
    assert is_retryable(err)


# ---------------------------------------------------------------------------
# optimizer-state drift names the offending leaf
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_opt_state_drift_warns_with_leaf_path(res_env, caplog):
    """A restore against a drifted optimizer (momentum state saved, a
    stateless optimizer live) must warn naming the owning parameter —
    never a generic 'starting fresh' with the cause swallowed."""
    import logging as _logging
    X, y = _data()
    mx.random.seed(0)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(_iter(X, y), num_epoch=2, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),
                              ('momentum', 0.9)))
    telemetry._reset_for_tests()
    mx.random.seed(0)
    mod2 = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    with caplog.at_level(_logging.WARNING):
        mod2.fit(_iter(X, y), num_epoch=2, optimizer='sgd',
                 optimizer_params=(('learning_rate', 0.1),))
    ckpt = mod2.__dict__['_mxtpu_ckpt']
    assert ckpt.restored_step is None       # fell through to fresh
    text = caplog.text
    assert 'fc1_weight' in text or 'fc2_weight' in text, text


# ---------------------------------------------------------------------------
# hang records in the offline report
# ---------------------------------------------------------------------------

def test_report_reconstructs_hang_incidents(all_off):
    """A crashed/aborted run's hang incidents survive into the offline
    report: counted, last digest kept (stacks elided), rendered."""
    import telemetry_report
    recs = [{'type': 'hang', 'stalled_s': 3.2, 'last_progress': 'fit.step',
             'stacks': {'MainThread': ['frame']}, 'action': 'abort'},
            {'type': 'restart', 'attempt': 1, 'reason': 'process_exit'}]
    health = telemetry_report._reconstruct_health(recs)
    assert health['hangs'] == 1 and health['restarts'] == 1
    assert health['last_hang']['last_progress'] == 'fit.step'
    assert 'stacks' not in health['last_hang']
    from mxnet_tpu.telemetry import export
    lines = export._health_lines({'nonfinite_steps': 0, 'incidents': [],
                                  'anomaly_counts': {}, 'hangs': 1})
    assert any('hangs' in ln and '1' in ln for ln in lines)
    # the summary path merges raw hang records into a clean relaunch's
    # summary (the relaunched child's counter never saw the abort)
    recs2 = [{'type': 'hang', 'stalled_s': 1.0, 'stacks': {}},
             {'type': 'summary', 'snapshot': {}, 'elapsed_s': 1.0}]
    health2 = telemetry_report._summary_parts(recs2)[3]
    assert health2['hangs'] == 1


def test_watch_renders_hang_restart_and_shift(all_off):
    import telemetry_watch
    summary = {'snapshot': {'counters': {'fit.steps': 10,
                                         'health.restarts': 2,
                                         'watchdog.hangs': 1},
                            'gauges': {'cluster.elastic_shift': 3},
                            'histograms': {}},
               'health': None, 'cluster': None}
    frame = '\n'.join(telemetry_watch.render(summary))
    assert '1 hang' in frame and '2 restarts' in frame
    assert 'shard shift 3' in frame


@pytest.mark.chaos
def test_train_supervisor_relaunches(tmp_path):
    """The whole-process supervisor relaunches an unclean exit and
    stops on the first clean one, logging each restart."""
    state = tmp_path / 'attempts'
    log = tmp_path / 'sup.jsonl'
    child = tmp_path / 'child.py'
    child.write_text(
        "import os, sys\n"
        "p = %r\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        "sys.exit(0 if n >= 2 else 1)\n" % str(state))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools',
                                      'train_supervisor.py'),
         '--backoff', '0', '--log', str(log), '--',
         sys.executable, str(child)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    recs = _records(log)
    mid = [r for r in recs if not r.get('final')]
    assert len(mid) == 2 and all(r['reason'] == 'process_exit'
                                 for r in mid)
    assert recs[-1]['final'] and recs[-1]['reason'] == 'clean_exit'
    assert 'MXTPU_CKPT_DIR is not set' in proc.stderr
