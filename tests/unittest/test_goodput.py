"""Goodput accounting plane (mxnet_tpu/telemetry/goodput.py):
wall-clock attribution from the step loop to the supervised fleet.

- pure bucket arithmetic: the sum invariant (buckets + overhead ==
  wall, overhead unclamped so over-attribution is visible), compile
  overlap, comm carve-out with provenance, rework pricing, prior-lost
  job books;
- instrumented CPU fit: the goodput record + gauges + summary block,
  with the attributed buckets bounded within 5% of measured wall;
- off contracts: MXTPU_GOODPUT=0 emits nothing; telemetry off is a
  true no-op and the lowered programs are byte-identical either way;
- restart rework: resilient_fit attributes the re-trained step span;
- the supervisor chain: MXTPU_GOODPUT_LOST_S accumulates across
  relaunches and the relaunched child reports prior_lost_s /
  job_goodput_pct;
- satellites: per-fit manifest re-emit with run_seq (run_compare keys
  on the latest), the watch line and the offline report's crashed-run
  reconstruction.
"""
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.telemetry import goodput
from mxnet_tpu.telemetry.goodput import BUCKETS, compute

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, 'tools'))

_G_FLAGS = ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH', 'MXTPU_GOODPUT',
            'MXTPU_GOODPUT_LOST_S', 'MXTPU_HEALTH', 'MXTPU_HEALTH_ACTION',
            'MXTPU_CKPT_DIR', 'MXTPU_CKPT_EVERY', 'MXTPU_RESTART_BACKOFF',
            'MXTPU_FAULT_INJECT', 'MXTPU_FUSED_FIT', 'MXTPU_SCALARS_EVERY')


def _reload():
    for f in _G_FLAGS:
        flags.reload(f)


@pytest.fixture
def tele_on(tmp_path, monkeypatch):
    """Telemetry + goodput on, logging to a tmp JSONL."""
    path = tmp_path / 'telemetry.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    _reload()
    telemetry._reset_for_tests()
    yield path
    telemetry._reset_for_tests()
    for f in _G_FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()


@pytest.fixture
def all_off(monkeypatch):
    for f in _G_FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload()
    telemetry._reset_for_tests()
    yield
    telemetry._reset_for_tests()
    _reload()


def _records(path):
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


def _fit(num_epoch=2, batch=8, n=32):
    np.random.seed(0)
    X = np.random.randn(n, 10).astype(np.float32)
    y = (np.random.rand(n) * 4).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch,
                           label_name='softmax_label')
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.fit(it, num_epoch=num_epoch, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),))
    return mod


def _snap(hists=None, counters=None):
    return {'counters': counters or {}, 'gauges': {},
            'histograms': {k: {'count': 1, 'sum': v}
                           for k, v in (hists or {}).items()}}


# ---------------------------------------------------------------------------
# pure arithmetic (compute() needs no telemetry at all)
# ---------------------------------------------------------------------------

def test_sum_invariant_exact():
    """Buckets + overhead == wall by construction, whatever the mix."""
    out = compute(_snap({'fit.dispatch': 2000.0, 'fit.draw': 500.0,
                         'ckpt.save': 250.0, 'eval.dispatch': 100.0},
                        {'xla.compile_secs': 1.0}),
                  10.0, rework_steps=5, total_steps=20,
                  comm_pct=25.0)
    assert out['wall_s'] == 10.0
    assert set(out['buckets']) == set(BUCKETS)
    assert abs(sum(out['buckets'].values()) - out['wall_s']) < 0.01


def test_empty_run_is_all_overhead():
    out = compute(_snap(), 4.0)
    assert out['buckets']['overhead'] == 4.0
    assert out['goodput_pct'] == 0.0
    assert out['badput_top'] == 'overhead'


def test_compile_carved_out_of_step():
    """Per-batch compiles block inside the dispatch span: compile
    seconds must come out of the step bucket, not count twice."""
    out = compute(_snap({'fit.dispatch': 1000.0},
                        {'xla.compile_secs': 0.4}), 1.0)
    assert out['buckets']['compile'] == 0.4
    assert abs(out['buckets']['step'] - 0.6) < 1e-9


def test_fused_build_absorbs_compile():
    """Fused-window compiles block inside fused_fit.build (its own
    span, never bucketed): the step bucket stays whole."""
    out = compute(_snap({'fused_fit.dispatch': 1000.0,
                         'fused_fit.build': 500.0},
                        {'xla.compile_secs': 0.4}), 2.0)
    assert out['buckets']['compile'] == 0.4
    assert abs(out['buckets']['step'] - 1.0) < 1e-9


def test_comm_carved_with_provenance():
    out = compute(_snap({'fit.dispatch': 1000.0}), 2.0,
                  comm_pct=25.0, comm_source='measured')
    assert abs(out['buckets']['comm'] - 0.25) < 1e-9
    assert abs(out['buckets']['step'] - 0.75) < 1e-9
    assert out['comm_source'] == 'measured'
    # provenance defaults to 'modeled', and absent comm omits the key
    assert compute(_snap(), 1.0, comm_pct=10.0)['comm_source'] == 'modeled'
    assert 'comm_source' not in compute(_snap(), 1.0)


def test_rework_priced_at_mean_step_cost():
    out = compute(_snap({'fit.dispatch': 10000.0}), 20.0,
                  rework_steps=10, total_steps=100)
    assert abs(out['buckets']['rework'] - 1.0) < 1e-9
    assert abs(out['buckets']['step'] - 9.0) < 1e-9
    assert out['rework_steps'] == 10


def test_badput_top_excludes_step():
    out = compute(_snap({'fit.dispatch': 5000.0, 'fit.draw': 1000.0}),
                  6.5)
    assert out['badput_top'] == 'input_wait'


def test_negative_overhead_is_visible():
    """Over-attribution (span sums past measured wall) must surface as
    negative overhead — the books still balance, loudly."""
    out = compute(_snap({'fit.dispatch': 3000.0}), 2.0)
    assert out['buckets']['overhead'] < 0.0
    assert abs(sum(out['buckets'].values()) - 2.0) < 0.01


def test_prior_lost_separates_job_books():
    """Prior dead attempts stretch the JOB's wall, never this
    process's: per-process buckets still sum to per-process wall."""
    out = compute(_snap({'fit.dispatch': 1000.0}), 2.0,
                  prior_lost_s=2.0)
    assert out['prior_lost_s'] == 2.0
    assert out['job_wall_s'] == 4.0
    assert out['goodput_pct'] == 50.0
    assert out['job_goodput_pct'] == 25.0
    assert abs(sum(out['buckets'].values()) - 2.0) < 0.01
    assert 'prior_lost_s' not in compute(_snap(), 1.0)


# ---------------------------------------------------------------------------
# the acceptance run: instrumented CPU fit
# ---------------------------------------------------------------------------

@pytest.fixture
def warmed():
    """The eager per-op compiles of a first fit (initializers, metric,
    iterator slices) block outside every step span, so compute() takes
    their seconds out of the step bucket; on a loaded machine they
    outweigh eight MLP steps and clamp it to 0. One fit before telemetry
    comes on leaves only the module's own programs to compile, inside
    the first fit.dispatch, where the carve-out is exact."""
    _fit(num_epoch=1)


def test_cpu_fit_buckets_sum_to_wall_within_5pct(warmed, tele_on):
    """Real fit: the goodput record's buckets + overhead sum to
    measured wall-clock, the attributed (non-overhead) share never
    exceeds wall by more than 5%, and every surface carries the same
    numbers (gauges, summary record, summary table block)."""
    _fit()
    telemetry.write_summary()
    recs = _records(tele_on)
    goods = [r for r in recs if r['type'] == 'goodput']
    assert len(goods) == 1
    g = goods[0]
    wall = g['wall_s']
    assert wall > 0
    total = sum(g['buckets'].values())
    assert abs(total - wall) <= 0.05 * wall + 0.01
    attributed = total - g['buckets']['overhead']
    assert attributed <= 1.05 * wall
    assert g['buckets']['step'] > 0          # the fit trained
    assert g['buckets']['compile'] > 0       # ... and compiled
    assert 0.0 <= g['goodput_pct'] <= 100.0
    assert g['badput_top'] in BUCKETS
    # summary record carries the same dict; gauges landed in its snapshot
    summ = [r for r in recs if r['type'] == 'summary'][-1]
    assert summ['goodput']['goodput_pct'] == g['goodput_pct']
    gauges = summ['snapshot']['gauges']
    assert gauges['goodput.goodput_pct'] == g['goodput_pct']
    for name in BUCKETS:
        assert gauges['goodput.%s_s' % name] == g['buckets'][name]
    # the summary table renders the block (and elides the raw gauges)
    from mxnet_tpu.telemetry.export import summary_table
    table = summary_table(summ['snapshot'], wall, goodput=summ['goodput'])
    assert '-- where the time went --' in table
    assert 'goodput.goodput_pct' not in table


def test_current_is_read_only(tele_on):
    """current() computes live numbers without publishing gauges or
    emitting records — the /summary scrape convention."""
    _fit(num_epoch=1)
    g = goodput.current()
    assert g is not None and g['buckets']['step'] > 0
    assert 'goodput.goodput_pct' not in telemetry.snapshot()['gauges']
    assert not any(r['type'] == 'goodput' for r in _records(tele_on))


def test_summary_payload_carries_goodput(tele_on):
    _fit(num_epoch=1)
    from mxnet_tpu.telemetry import serve
    payload = serve.summary_payload()
    assert payload['goodput']['buckets']['step'] > 0


# ---------------------------------------------------------------------------
# off contracts
# ---------------------------------------------------------------------------

def test_goodput_flag_off_emits_nothing(tele_on, monkeypatch):
    monkeypatch.setenv('MXTPU_GOODPUT', '0')
    _reload()
    telemetry._reset_for_tests()
    _fit(num_epoch=1)
    assert not goodput.enabled()
    assert goodput.current() is None
    goodput.note_rework(5)          # must be a no-op, not a crash
    assert goodput.summarize(1.0) is None
    telemetry.write_summary()
    recs = _records(os.environ['MXTPU_TELEMETRY_PATH'])
    assert not any(r['type'] == 'goodput' for r in recs)
    summ = [r for r in recs if r['type'] == 'summary'][-1]
    assert 'goodput' not in summ
    assert not any(k.startswith('goodput.')
                   for k in summ['snapshot']['gauges'])


def test_telemetry_off_true_noop(all_off):
    assert not goodput.enabled()
    assert goodput.current() is None
    assert goodput.summarize() is None
    goodput.note_rework(3)
    assert goodput._state.rework_steps == 0
    assert math.isnan(goodput.local_stats()[0])


def test_lowering_identical_with_goodput_on_or_off(tmp_path, monkeypatch):
    """The plane only reads registry snapshots — the traced programs
    must be byte-identical with the flag on vs off (same contract the
    health/dynamics/roofline planes pin)."""
    import jax.numpy as jnp
    from mxnet_tpu import random as _random

    def _lowered_text(on):
        telemetry._reset_for_tests()
        monkeypatch.setenv('MXTPU_TELEMETRY', '1')
        monkeypatch.setenv('MXTPU_TELEMETRY_PATH',
                           str(tmp_path / ('g%d.jsonl' % on)))
        monkeypatch.setenv('MXTPU_GOODPUT', '1' if on else '0')
        _reload()
        telemetry._reset_for_tests()
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
        for f in _G_FLAGS:
            monkeypatch.delenv(f, raising=False)
        _reload()


# ---------------------------------------------------------------------------
# restart rework
# ---------------------------------------------------------------------------

class _FakeCkpt:
    def __init__(self, last_good, global_step):
        self.last_good = last_good
        self.global_step = global_step

    def handle_failure(self, diag):
        pass


class _FlakyModule:
    """fit() raises once, then succeeds — with a fake checkpointer
    pinning exactly how many steps the crashed attempt loses."""

    def __init__(self, last_good, global_step):
        self.calls = 0
        self._mxtpu_ckpt = _FakeCkpt(last_good, global_step)

    def fit(self, it, **kw):
        self.calls += 1
        if self.calls == 1:
            raise RuntimeError('boom')


class _FakeIter:
    def reset(self):
        pass


def test_resilient_fit_attributes_exact_rework(tele_on):
    """rework_steps == crashed attempt's reached step - restore point,
    straight from the resilient_fit hook."""
    from mxnet_tpu.module.resilient_fit import resilient_fit
    m = _FlakyModule(last_good=4, global_step=7)
    restarts = resilient_fit(m, _FakeIter(), restart_max=2,
                             restart_backoff=0)
    assert restarts == 1
    assert goodput._state.rework_steps == 3
    assert telemetry.snapshot()['gauges']['goodput.rework_steps'] == 3
    out = goodput.summarize(10.0)
    assert out['rework_steps'] == 3


@pytest.mark.chaos
def test_real_crash_restore_reports_rework(tele_on, monkeypatch, tmp_path):
    """End-to-end in-process: injected nan-grad crashes the per-batch
    loop, resilient_fit restores from last-good, and the goodput record
    prices the re-trained span as nonzero rework badput."""
    from mxnet_tpu.module.resilient_fit import resilient_fit
    monkeypatch.setenv('MXTPU_HEALTH', '1')
    monkeypatch.setenv('MXTPU_HEALTH_ACTION', 'raise')
    monkeypatch.setenv('MXTPU_CKPT_DIR', str(tmp_path / 'ckpts'))
    monkeypatch.setenv('MXTPU_CKPT_EVERY', '3')
    monkeypatch.setenv('MXTPU_RESTART_BACKOFF', '0')
    monkeypatch.setenv('MXTPU_FUSED_FIT', '0')
    monkeypatch.setenv('MXTPU_FAULT_INJECT', 'nan-grad:5')
    _reload()
    telemetry._reset_for_tests()
    from mxnet_tpu import faults
    faults._reset_for_tests()
    np.random.seed(0)
    X = np.random.randn(32, 10).astype(np.float32)
    y = (np.random.rand(32) * 4).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=8,
                           label_name='softmax_label')
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    restarts = resilient_fit(mod, it, num_epoch=4, optimizer='sgd',
                             optimizer_params=(('learning_rate', 0.1),))
    assert restarts == 1
    telemetry.write_summary()
    recs = _records(os.environ['MXTPU_TELEMETRY_PATH'])
    restart = [r for r in recs if r['type'] == 'restart'][0]
    g = [r for r in recs if r['type'] == 'goodput'][-1]
    # the re-trained span: where the crashed attempt had reached minus
    # the restore point — nonzero, and exactly what the record claims
    assert g['rework_steps'] >= 1
    assert g['buckets']['rework'] > 0.0
    assert restart['restore_step'] is not None
    faults._reset_for_tests()


# ---------------------------------------------------------------------------
# the supervisor chain: lost-work seconds across relaunches
# ---------------------------------------------------------------------------

def test_lost_work_secs_pricing(tmp_path):
    import train_supervisor as sup
    # no pointer: the whole attempt is lost
    assert sup.lost_work_secs(30.0, ckpt_dir=str(tmp_path)) == 30.0
    assert sup.lost_work_secs(30.0, ckpt_dir='') == 30.0
    # pointer certified 10s before death: only the tail is lost
    ptr = tmp_path / 'last_good.step'
    ptr.write_text('12')
    now = time.time()
    os.utime(ptr, (now - 10.0, now - 10.0))
    lost = sup.lost_work_secs(30.0, ckpt_dir=str(tmp_path), now=now)
    assert 9.5 <= lost <= 10.5
    # ... clamped to the attempt's own lifetime
    assert sup.lost_work_secs(4.0, ckpt_dir=str(tmp_path), now=now) == 4.0


@pytest.mark.chaos
def test_supervisor_stamps_lost_work_into_relaunch(tmp_path):
    """Crash -> supervised relaunch -> the child sees the accumulated
    MXTPU_GOODPUT_LOST_S, reports prior_lost_s / job_goodput_pct in
    its goodput record, and the supervisor's restart record prices the
    dead attempt (lost_s / lost_total_s)."""
    state = tmp_path / 'attempts'
    sup_log = tmp_path / 'sup.jsonl'
    tele_log = tmp_path / 'child.jsonl'
    child = tmp_path / 'child.py'
    # attempt 0: burn ~0.3s and die. attempt 1: feed the registry a
    # little synthetic span time and write the summary — the goodput
    # plane reads MXTPU_GOODPUT_LOST_S on its own.
    child.write_text(
        "import os, sys, time\n"
        "p = %r\n"
        "n = int(open(p).read()) if os.path.exists(p) else 0\n"
        "open(p, 'w').write(str(n + 1))\n"
        "if n == 0:\n"
        "    time.sleep(0.3)\n"
        "    sys.exit(1)\n"
        "from mxnet_tpu import telemetry\n"
        "telemetry.enabled()\n"
        "h = telemetry._state.registry.histogram('fit.dispatch')\n"
        "h.observe(50.0)\n"
        "telemetry.write_summary()\n" % str(state))
    env = dict(os.environ, MXTPU_TELEMETRY='1',
               MXTPU_TELEMETRY_PATH=str(tele_log), JAX_PLATFORMS='cpu',
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   'PYTHONPATH', ''))
    env.pop('MXTPU_GOODPUT_LOST_S', None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools',
                                      'train_supervisor.py'),
         '--backoff', '0', '--log', str(sup_log), '--',
         sys.executable, str(child)],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    sup_recs = [json.loads(ln) for ln in open(sup_log) if ln.strip()]
    mid = [r for r in sup_recs if not r.get('final')]
    assert len(mid) == 1
    assert mid[0]['lost_s'] > 0.0
    assert mid[0]['lost_total_s'] == mid[0]['lost_s']
    child_recs = [json.loads(ln) for ln in open(tele_log) if ln.strip()]
    g = [r for r in child_recs if r['type'] == 'goodput'][-1]
    assert g['prior_lost_s'] == mid[0]['lost_total_s'] \
        or abs(g['prior_lost_s'] - mid[0]['lost_total_s']) < 0.1
    assert g['job_wall_s'] > g['wall_s']
    assert g['job_goodput_pct'] < g['goodput_pct'] \
        or g['goodput_pct'] == 0.0


# ---------------------------------------------------------------------------
# cluster aggregation: fleet goodput = the slowest host's
# ---------------------------------------------------------------------------

def test_cluster_fleet_goodput_and_culprit(tele_on):
    from mxnet_tpu.telemetry import cluster
    assert cluster.SYNC_KEYS[6:9] == ('goodput_pct', 'badput_top',
                                      'comm_src')
    nan = float('nan')
    mat = np.array([
        [5.0, 10.0, 4.0, 1e6, 12.0, 0.0, 90.0,
         float(BUCKETS.index('overhead')), 0.0],
        [9.0, 40.0, 8.0, 2e6, 35.0, 1.0, 60.0,
         float(BUCKETS.index('compile')), 1.0],
    ])
    cluster._publish(mat, 100)
    snap = cluster.snapshot_cluster()
    assert snap['fleet_goodput_pct'] == 60.0
    assert snap['goodput_culprit'] == 'h1:compile'
    rows = {r['host']: r for r in snap['per_host']}
    assert rows[1]['badput_top'] == 'compile'
    assert rows[0]['comm_src'] == 'modeled'
    assert rows[1]['comm_src'] == 'measured'
    gauges = telemetry.snapshot()['gauges']
    assert gauges['cluster.fleet_goodput_pct'] == 60.0
    assert gauges['cluster.goodput_culprit'] == 'h1:compile'
    assert gauges['cluster.h1.goodput_pct'] == 60.0
    assert gauges['cluster.h1.comm_src'] == 'measured'


def test_cluster_tolerates_short_and_nan_rows(tele_on):
    """Rows from a pre-goodput sender (shorter vector) and NaN goodput
    slots must not break the fleet roll-up."""
    from mxnet_tpu.telemetry import cluster
    nan = float('nan')
    mat = np.array([
        [5.0, 10.0, 4.0, 1e6, nan, 0.0, 80.0, nan, nan],
        [9.0, 40.0, 8.0, 2e6, nan, 1.0, nan, nan, nan],
    ])
    cluster._publish(mat, 50)
    snap = cluster.snapshot_cluster()
    assert snap['fleet_goodput_pct'] == 80.0
    assert snap['goodput_culprit'].startswith('h0')
    # all-NaN goodput column: no fleet keys, no crash
    mat2 = np.array([[5.0, 10.0, 4.0, 1e6, nan, 0.0, nan, nan, nan]])
    cluster._publish(mat2, 60)
    snap2 = cluster.snapshot_cluster()
    assert 'fleet_goodput_pct' not in snap2


def test_local_stats_encoding(tele_on):
    _fit(num_epoch=1)
    pct, idx = goodput.local_stats()
    assert 0.0 <= pct <= 100.0
    assert math.isnan(idx) or BUCKETS[int(idx)] in BUCKETS


# ---------------------------------------------------------------------------
# satellite: per-fit manifest re-emit with run_seq
# ---------------------------------------------------------------------------

def test_manifest_reemitted_per_fit_with_run_seq(tele_on):
    from mxnet_tpu.telemetry import ledger
    ledger.begin_run()
    ledger.begin_run()
    recs = [r for r in _records(tele_on) if r['type'] == 'manifest']
    assert [r['run_seq'] for r in recs] == [1, 2]
    led = ledger.snapshot_ledger()
    assert led['manifest']['run_seq'] == 2
    # run_seq is identity, not configuration: run_compare's config
    # diff iterates MANIFEST_KEYS and must not flag it
    assert 'run_seq' not in ledger.MANIFEST_KEYS
    # ensure_manifest stays once-per-process for non-fit callers
    ledger.ensure_manifest()
    recs = [r for r in _records(tele_on) if r['type'] == 'manifest']
    assert len(recs) == 2


def test_fit_emits_run_seq_manifest(tele_on):
    _fit(num_epoch=1)
    _fit(num_epoch=1)
    seqs = [r['run_seq'] for r in _records(tele_on)
            if r['type'] == 'manifest']
    assert seqs == [1, 2]


def test_run_compare_keys_on_latest_manifest(tmp_path):
    """A process that trained twice banks two manifests; the config
    diff must describe the LATEST fit, not the first."""
    import run_compare
    t0 = 1000.0

    def _log(path, flag_val, extra_manifest=None):
        recs = [{'type': 'manifest', 't': t0, 'run_seq': 1,
                 'flags': {'MXTPU_REMAT_POLICY': ''},
                 'jax_version': 'x', 'platform': 'cpu'}]
        if extra_manifest is not None:
            recs.append({'type': 'manifest', 't': t0 + 1, 'run_seq': 2,
                         'flags': {'MXTPU_REMAT_POLICY': extra_manifest},
                         'jax_version': 'x', 'platform': 'cpu'})
        recs += [{'type': 'scalars', 't': t0 + 2 + i, 'step': 25 * (i + 1),
                  'loss': 1.0 / (i + 1)} for i in range(4)]
        path.write_text('\n'.join(json.dumps(r) for r in recs) + '\n')

    base, cand = tmp_path / 'base.jsonl', tmp_path / 'cand.jsonl'
    _log(base, '')
    _log(cand, '', extra_manifest='full')
    rb = run_compare.load_run(str(base))
    rc = run_compare.load_run(str(cand))
    assert rc.manifest['run_seq'] == 2
    lines = run_compare.manifest_diff(rb, rc)
    assert any("MXTPU_REMAT_POLICY '' -> 'full'" in ln for ln in lines)


def test_report_reconstructs_goodput_from_crashed_log(tmp_path, capsys):
    """No summary record: the offline report re-derives the block from
    raw span/compile/restart/scalars records, rework included."""
    import telemetry_report
    t0 = 1000.0
    recs = [{'type': 'start', 't': t0}]
    for i in range(10):
        recs.append({'type': 'span', 'name': 'fit.dispatch',
                     'dur_ms': 200.0, 't': t0 + i})
        recs.append({'type': 'scalars', 'step': i + 1, 'loss': 0.5,
                     't': t0 + i + 0.5})
    recs.append({'type': 'compile', 'dur_s': 2.0, 't': t0 + 3})
    # a restart that restores to step 6 after reaching step 10:
    # 4 re-trained steps
    recs.append({'type': 'restart', 'attempt': 1, 'restore_step': 6,
                 't': t0 + 11})
    recs.append({'type': 'span', 'name': 'fit.dispatch',
                 'dur_ms': 100.0, 't': t0 + 20})
    path = tmp_path / 'crash.jsonl'
    path.write_text('\n'.join(json.dumps(r) for r in recs) + '\n')
    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert '-- where the time went --' in out
    assert 'rework' in out
    parts = telemetry_report._summary_parts(telemetry_report.load(
        str(path)))
    good = parts[7]
    assert good['rework_steps'] == 4
    assert good['buckets']['rework'] > 0.0
    assert good['buckets']['compile'] == 2.0
    assert abs(sum(good['buckets'].values()) - good['wall_s']) < 0.01


def test_watch_renders_goodput_line():
    import telemetry_watch
    summary = {
        'elapsed_s': 100.0, 'host': 0,
        'snapshot': {'counters': {}, 'gauges': {}, 'histograms': {}},
        'goodput': {'goodput_pct': 72.5, 'badput_top': 'input_wait',
                    'buckets': {'input_wait': 20.0}, 'rework_steps': 8,
                    'job_goodput_pct': 61.0},
    }
    frame = '\n'.join(telemetry_watch.render(summary))
    line = [ln for ln in frame.splitlines() if 'goodput' in ln]
    assert len(line) == 1
    ln = line[0]
    assert '72.5% productive' in ln
    assert 'top badput input_wait (20.0s)' in ln
    assert '8 steps reworked' in ln
    assert 'job 61.0% across restarts' in ln
    # no goodput data -> no line, no crash
    frame = '\n'.join(telemetry_watch.render(
        {'snapshot': {'counters': {}, 'gauges': {}, 'histograms': {}}}))
    assert 'goodput' not in frame

