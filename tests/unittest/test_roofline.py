"""Roofline attribution plane (mxnet_tpu/telemetry/roofline).

Contracts under test:
- HLO text -> per-layer cost parse (dot/convolution FLOPs from
  contraction dims, bytes from shapes, named-scope layer extraction
  through jvp/transpose wrappers, collective accounting, free ops);
- the scope map of the same synthetic module (telemetry/programs.py):
  each instruction's name -> node and pass, the key a capture's events
  are joined by (benchmark/reduce/scopes.py), beside the modeled
  classification;
- deterministic classification goldens against overridden peaks
  (compute-bound / memory-bound / overhead-bound);
- MXTPU_ROOFLINE=0/1 parametrized fit acceptance: =1 puts a ranked
  bottleneck block in the summary where every named layer carries a
  classification and an achieved/peak %, plus roofline.* gauges and a
  JSONL record; =0 leaves no trace anywhere;
- the no-op contract: the lowered step HLO is byte-identical with the
  flag on or off (attribution is host-side parsing, never graph edits);
- unknown-device peaks: warn once, publish roofline.peaks_unknown,
  honor the MXTPU_PEAK_TFLOPS / MXTPU_PEAK_HBM_GBS overrides;
- the offline CLI (tools/roofline_report.py) renders the JSONL record
  byte-identically to the live summary block.
"""
import json
import logging
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.telemetry import programs, roofline
from mxnet_tpu.telemetry import xla as tele_xla

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, 'tools'))

_FLAGS = ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH', 'MXTPU_ROOFLINE',
          'MXTPU_PEAK_TFLOPS',
          'MXTPU_PEAK_HBM_GBS')


def _reload_flags():
    for f in _FLAGS:
        flags.reload(f)


@pytest.fixture
def roof_on(tmp_path, monkeypatch):
    """Telemetry + roofline ON, logging to a tmp JSONL."""
    path = tmp_path / 'roofline.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    monkeypatch.setenv('MXTPU_ROOFLINE', '1')
    _reload_flags()
    telemetry._reset_for_tests()
    yield path
    telemetry._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
    _reload_flags()


def _records(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# A synthetic HLO module exercising every parse path: a dot (FLOPs
# from the contracting dim), an elementwise op, a tiny op (the
# overhead-bound golden), an all-reduce (comm accounting) and free ops
# (parameter/copy cost nothing).
_SYNTH_HLO = '''\
HloModule synthetic, entry_computation_layout={()->f32[64,64]{1,0}}
ENTRY %main () -> f32[64,64] {
  %p0 = f32[64,128]{1,0} parameter(0)
  %p1 = f32[64,128]{1,0} parameter(1)
  %dot.1 = f32[64,64]{1,0} dot(f32[64,128]{1,0} %p0, f32[64,128]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(main)/fc1/dot_general"}
  %add.2 = f32[64,64]{1,0} add(f32[64,64]{1,0} %dot.1, f32[64,64]{1,0} %dot.1), metadata={op_name="jit(main)/while/body/jvp(relu1)/add"}
  %multiply.5 = f32[4]{0} multiply(f32[4]{0} %p0, f32[4]{0} %p0), metadata={op_name="jit(main)/tiny/mul"}
  %all-reduce.3 = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %add.2), replica_groups={}, metadata={op_name="jit(main)/allreduce"}
  ROOT %copy.4 = f32[64,64]{1,0} copy(f32[64,64]{1,0} %all-reduce.3)
}
'''

_FC1_FLOPS = 2.0 * 64 * 64 * 128          # 2*M*N*K
_FC1_BYTES = 64 * 64 * 4 + 2 * 64 * 128 * 4
_ADD_FLOPS = 64 * 64                       # one per output element
_ADD_BYTES = 3 * 64 * 64 * 4
_AR_BYTES = 64 * 64 * 4


# ---------------------------------------------------------------------------
# HLO parse
# ---------------------------------------------------------------------------

def test_layer_from_op_name_unwraps():
    f = programs._layer_from_op_name
    assert roofline._layer_from_op_name is f       # one parser, imported
    assert f('jit(f)/jit(main)/fc1/dot_general') == 'fc1'
    assert f('jit(window_fn)/jit(main)/while/body/jvp(fc1)/dot_general') \
        == 'fc1'
    assert f('jit(f)/while/body/transpose(jvp(fc2))/reduce_sum') == 'fc2'
    assert f('jit(f)/jit(main)/relu1/jit(relu)/max') == 'relu1'
    # scan/update plumbing carries no layer
    assert f('jit(f)/jit(main)/while/body/add') is None
    assert f('/eq') is None
    assert f('params[0]') is None


def test_hlo_layer_costs_golden():
    costs = roofline.hlo_layer_costs(_SYNTH_HLO)
    assert costs['layers']['fc1'] == {'flops': _FC1_FLOPS,
                                      'bytes': _FC1_BYTES}
    assert costs['layers']['relu1'] == {'flops': _ADD_FLOPS,
                                        'bytes': _ADD_BYTES}
    assert costs['layers']['tiny']['flops'] == 4.0
    # free ops (parameter/copy) and the collective cost nothing here
    assert set(costs['layers']) == {'fc1', 'relu1', 'tiny'}
    assert costs['comm_bytes'] == _AR_BYTES
    assert costs['comm_ops'] == {'all-reduce': float(_AR_BYTES)}
    assert costs['flops_total'] == _FC1_FLOPS + _ADD_FLOPS + 4.0


def test_note_hlo_keeps_largest_variant(roof_on):
    roofline.note_hlo('p', _SYNTH_HLO)
    small = _SYNTH_HLO.replace('f32[64,128]', 'f32[8,128]')
    roofline.note_hlo('p', small)          # tail-batch recompile
    prog = roofline._pick_step_program()
    assert prog['layers']['fc1']['flops'] == _FC1_FLOPS


def test_analysis_calibrates_parsed_split(roof_on):
    """XLA's own cost_analysis totals rescale the parsed per-layer
    split, so layer numbers always sum to what XLA reported."""
    parsed_total = _FC1_FLOPS + _ADD_FLOPS + 4.0
    roofline.note_hlo('p', _SYNTH_HLO,
                      analysis={'flops': 2 * parsed_total})
    d = roofline.analyze(step_time_ms=1.0)
    assert sum(r['flops'] for r in d['layers']) \
        == pytest.approx(2 * parsed_total, rel=1e-6)


# ---------------------------------------------------------------------------
# scope map + classification goldens
# ---------------------------------------------------------------------------

def _set_peaks(monkeypatch, tflops, gbs):
    monkeypatch.setenv('MXTPU_PEAK_TFLOPS', str(tflops))
    monkeypatch.setenv('MXTPU_PEAK_HBM_GBS', str(gbs))
    flags.reload('MXTPU_PEAK_TFLOPS')
    flags.reload('MXTPU_PEAK_HBM_GBS')


def test_scope_map_classification_golden(roof_on, monkeypatch):
    """The deterministic end-to-end golden: synthetic HLO + overridden
    peaks -> the three classifications and the collective's accounting,
    and, from the same text, the map a capture's events are joined by:
    each instruction of the entry computation under its own name with
    the node its path names (the key the chrome-trace join had)."""
    _set_peaks(monkeypatch, 0.001, 0.1)    # 1e9 FLOP/s, 1e8 B/s
    roofline.note_hlo('p', _SYNTH_HLO)
    d = roofline.analyze(step_time_ms=3.0)
    assert d['source'] == 'modeled'
    assert d['peaks'] == 'override'
    assert 'trace_steps' not in d
    rows = {r['layer']: r for r in d['layers']}
    # fc1: roofline min = max(1048576/1e9, 81920/1e8)s = 1.049ms:
    # the FLOPs term dominates
    assert rows['fc1']['class'] == 'compute-bound'
    # relu1: the bytes term dominates (0.492ms)
    assert rows['relu1']['class'] == 'memory-bound'
    assert rows['tiny']['class'] == 'memory-bound'
    # the step's 3 ms go to the layers by their roofline minimum
    assert sum(r['time_ms'] for r in d['layers']) \
        == pytest.approx(3.0, abs=1e-3)
    assert rows['fc1']['time_ms'] / rows['relu1']['time_ms'] \
        == pytest.approx(1.048576 / 0.49152, rel=1e-3)
    # comm: 16 KiB on the wire, its time modeled at the HBM ceiling
    comm = d['comm']
    assert comm['source'] == 'modeled'
    assert comm['bytes'] == _AR_BYTES
    assert comm['time_ms'] == pytest.approx(_AR_BYTES / 1e8 * 1e3, abs=1e-4)
    assert comm['overlap_pct'] is None
    assert comm['pct_of_step'] == pytest.approx(
        100.0 * comm['time_ms'] / 3.0, abs=0.1)
    assert comm['ops'] == {'all-reduce': float(_AR_BYTES)}
    # the same text's map: name -> [node, phase, inner, opcode, fused
    # nodes, via]; what no path names takes its operand's node
    m = programs.scope_map(_SYNTH_HLO, {'fc1': 'FullyConnected',
                                        'relu1': 'Activation'})
    assert m['instrs'] == {
        'dot.1': ['fc1', 'fwd', None, 'dot', 0, ''],
        'add.2': ['relu1', 'fwd', None, 'add', 0, ''],
        'multiply.5': [None, None, None, 'multiply', 0, ''],  # no such node
        'all-reduce.3': ['relu1', 'fwd', None, 'all-reduce', 0, 'operand'],
        'copy.4': ['relu1', 'fwd', None, 'copy', 0, 'operand']}
    assert m['nodes'] == {'fc1': 'FullyConnected', 'relu1': 'Activation'}
    assert (m['named'], m['unscoped']) == (4, 2)


def test_modeled_fallback_without_trace(roof_on, monkeypatch):
    """No capture -> the measured step time distributes across layers
    by roofline-minimum time, labeled 'modeled' (never presented as a
    measurement)."""
    _set_peaks(monkeypatch, 0.001, 0.1)
    roofline.note_hlo('p', _SYNTH_HLO)
    d = roofline.analyze(step_time_ms=10.0)
    assert d['source'] == 'modeled'
    assert sum(r['time_ms'] for r in d['layers']) == pytest.approx(10.0)
    assert d['comm']['source'] == 'modeled'


def test_comm_pct_grounds_cluster_classifier(roof_on, monkeypatch):
    """The straggler classifier's communication_bound verdict comes
    from the roofline's per-collective numbers, not inference."""
    from mxnet_tpu.telemetry import cluster
    _set_peaks(monkeypatch, 0.001, 0.1)
    roofline.note_hlo('p', _SYNTH_HLO)
    roofline.summarize(step_time_ms=3.0)
    pct = roofline.comm_pct_of_step()
    assert pct is not None and pct > 0
    assert cluster.classify(2.0, comm_pct=45.0) == 'communication_bound'
    assert cluster.classify(55.0, comm_pct=45.0) == 'input_bound'
    assert cluster.classify(2.0, comm_pct=5.0) == 'compute_bound'
    assert cluster.classify(2.0) == 'compute_bound'


# ---------------------------------------------------------------------------
# fit acceptance + no-op contract
# ---------------------------------------------------------------------------

def _mlp_fit():
    np.random.seed(0)
    mx.random.seed(0)
    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu', name='relu1')
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
    out = mx.sym.SoftmaxOutput(fc2, name='softmax')
    X = np.random.randn(32, 10).astype(np.float32)
    y = (np.random.rand(32) * 4).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=8,
                           label_name='softmax_label')
    mod = mx.mod.Module(out, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.1),))
    return mod


@pytest.mark.parametrize('roof', ['0', '1'])
def test_fit_acceptance_on_off(roof, tmp_path, monkeypatch):
    """=1: the summary carries a ranked bottleneck block where every
    named layer has a classification and an achieved/peak %, plus
    roofline.* gauges and a JSONL record. =0: no trace anywhere."""
    path = tmp_path / 'onoff.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    monkeypatch.setenv('MXTPU_ROOFLINE', roof)
    # a host CPU has no peaks of its own: the test names them
    _set_peaks(monkeypatch, 0.1, 50.0)
    _reload_flags()
    telemetry._reset_for_tests()
    try:
        _mlp_fit()
        table = telemetry.write_summary(log=False)
        recs = _records(path)
        gauges = telemetry.snapshot()['gauges']
        roof_gauges = [n for n in gauges if n.startswith('roofline.')]
        if roof == '0':
            assert not roofline.enabled()
            assert '-- roofline' not in table
            assert roof_gauges == []
            assert not any(r['type'] == 'roofline' for r in recs)
        else:
            assert roofline.enabled()
            assert '-- roofline: fused_fit.window[softmax]' in table
            d = roofline.snapshot_roofline()
            layers = {r['layer']: r for r in d['layers']}
            for name in ('fc1', 'relu1', 'fc2', 'softmax'):
                assert name in layers, (name, sorted(layers))
                row = layers[name]
                assert row['class'] in ('compute-bound', 'memory-bound',
                                        'overhead-bound')
                assert row['roof_pct'] is not None
            assert gauges['roofline.layers'] == len(d['layers'])
            assert gauges['roofline.worst_layer'] == d['layers'][0]['layer']
            rr = [r for r in recs if r['type'] == 'roofline']
            assert rr and rr[-1]['layers'] == json.loads(
                json.dumps(d['layers']))
            summ = [r for r in recs if r['type'] == 'summary'][-1]
            assert summ.get('roofline')
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
        _reload_flags()


def test_roofline_off_lowering_byte_identical(tmp_path, monkeypatch):
    """Attribution is host-side HLO parsing — the lowered step program
    is byte-identical with the flag on or off (and with telemetry off
    entirely). The acceptance criterion's no-op contract."""
    import jax.numpy as jnp
    from mxnet_tpu import random as _random

    def _lowered_text(roof_on_):
        telemetry._reset_for_tests()
        monkeypatch.setenv('MXTPU_TELEMETRY', '1')
        monkeypatch.setenv('MXTPU_TELEMETRY_PATH',
                           str(tmp_path / ('r%s.jsonl' % roof_on_)))
        monkeypatch.setenv('MXTPU_ROOFLINE', roof_on_)
        _reload_flags()
        telemetry._reset_for_tests()
        np.random.seed(0)
        mx.random.seed(0)
        data = mx.sym.Variable('data')
        fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
        out = mx.sym.SoftmaxOutput(fc1, name='softmax')
        mod = mx.mod.Module(out, context=mx.cpu())
        mod.bind(data_shapes=[('data', (8, 10))],
                 label_shapes=[('softmax_label', (8,))])
        mod.init_params()
        ex = mod._exec_group.execs[0]
        arg_data = tuple(a._data for a in ex.arg_arrays)
        aux_data = tuple(a._data for a in ex.aux_arrays)
        heads = (jnp.ones((8, 16), jnp.float32),)
        return ex._fwd_bwd.lower(arg_data, aux_data, _random.next_key(),
                                 heads).as_text()

    try:
        assert _lowered_text('0') == _lowered_text('1')
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
        _reload_flags()


def test_off_no_parse_no_registry(tmp_path, monkeypatch):
    """MXTPU_ROOFLINE unset: the registrar hook is one cached-bool
    check — no HLO text is rendered, nothing lands in the store."""
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 'x.jsonl'))
    monkeypatch.delenv('MXTPU_ROOFLINE', raising=False)
    _reload_flags()
    telemetry._reset_for_tests()

    class _Boom:
        def as_text(self):
            raise AssertionError('HLO rendered with roofline off')

    try:
        roofline.note_compiled('p', _Boom())
        assert roofline._pick_step_program() is None
        assert roofline.analyze() is None
        assert roofline.summarize() is None
        assert roofline.comm_pct_of_step() is None
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
        _reload_flags()


# ---------------------------------------------------------------------------
# peak table: unknown device warn-once + overrides
# ---------------------------------------------------------------------------

class _FakeDev:
    device_kind = 'warp9000'
    platform = 'warp'


def test_unknown_device_warns_once_and_publishes(roof_on, caplog):
    with caplog.at_level(logging.WARNING):
        p1 = tele_xla.device_peaks(_FakeDev())
        p2 = tele_xla.device_peaks(_FakeDev())
    assert p1['source'] == 'unknown' and p1['flops'] == 0.0
    assert p2['source'] == 'unknown'
    warns = [r for r in caplog.records
             if 'no peak table entry' in r.getMessage()]
    assert len(warns) == 1                 # once per process
    assert 'MXTPU_PEAK_TFLOPS' in warns[0].getMessage()
    assert telemetry.get_registry() \
        .gauge('roofline.peaks_unknown').value == 1
    # MFU skips unknown kinds — after the warn, not silently
    peak, kind = tele_xla.device_peak_flops(_FakeDev())
    assert peak == 0.0 and kind == 'warp9000'


def test_peak_overrides_rescue_unknown_device(roof_on, monkeypatch,
                                              caplog):
    _set_peaks(monkeypatch, 123.0, 456.0)
    with caplog.at_level(logging.WARNING):
        p = tele_xla.device_peaks(_FakeDev())
    assert p['source'] == 'override'
    assert p['flops'] == pytest.approx(123e12)
    assert p['hbm_bytes_s'] == pytest.approx(456e9)
    assert not [r for r in caplog.records
                if 'no peak table entry' in r.getMessage()]
    peak, _ = tele_xla.device_peak_flops(_FakeDev())
    assert peak == pytest.approx(123e12)   # MFU honors the override


def test_partial_override_keeps_mfu_contract(roof_on, monkeypatch):
    """A lone MXTPU_PEAK_HBM_GBS (refining roofline bandwidth) must not
    promote a nominal/unknown FLOP/s value to trusted-for-MFU status —
    and a half-unknown device still warns + publishes peaks_unknown."""
    monkeypatch.setenv('MXTPU_PEAK_HBM_GBS', '456.0')
    flags.reload('MXTPU_PEAK_TFLOPS')
    flags.reload('MXTPU_PEAK_HBM_GBS')
    # CPU: hbm overridden, flops still not named -> no MFU
    p = tele_xla.device_peaks()
    assert p['hbm_source'] == 'override'
    assert p['flops_source'] == 'none'
    assert p['hbm_bytes_s'] == pytest.approx(456e9)
    peak, _ = tele_xla.device_peak_flops()
    assert peak == 0.0                     # never MFU against a guess
    # unknown kind: the un-overridden denominator is still missing —
    # the warn-once + peaks_unknown gauge must fire, not be suppressed
    pu = tele_xla.device_peaks(_FakeDev())
    assert pu['flops_source'] == 'unknown' and pu['flops'] == 0.0
    assert pu['hbm_source'] == 'override'
    assert telemetry.get_registry() \
        .gauge('roofline.peaks_unknown').value == 1


def test_cpu_has_no_peaks_and_no_mfu():
    """A host CPU gets no guessed denominators: no roofline share and
    no MFU is computed against it."""
    p = tele_xla.device_peaks()            # conftest pins the CPU mesh
    assert p['source'] == 'none'
    assert p['flops'] == 0.0 and p['hbm_bytes_s'] == 0.0
    peak, _ = tele_xla.device_peak_flops()
    assert peak == 0.0


def test_unlisted_tpu_kind_is_an_error():
    """On the chip path a device that is not in the table is an error,
    not a default."""
    class _NewTpu:
        device_kind = 'TPU v99'
        platform = 'tpu'
    with pytest.raises(mx.base.MXNetError, match='no entry in'):
        tele_xla.device_peaks(_NewTpu())


# ---------------------------------------------------------------------------
# offline CLI round-trip
# ---------------------------------------------------------------------------

def test_roofline_report_matches_live_block(roof_on, monkeypatch,
                                            capsys):
    """JSONL -> tools/roofline_report.py reproduces the live summary
    block byte-for-byte (the acceptance criterion's round-trip)."""
    import roofline_report
    _set_peaks(monkeypatch, 0.001, 0.1)
    roofline.note_hlo('p', _SYNTH_HLO)
    telemetry.gauge('fit.steps')           # touch registry (no-op value)
    table = telemetry.write_summary(log=False)
    telemetry._state.sink.flush()
    lines = table.splitlines()
    i = next(j for j, ln in enumerate(lines)
             if ln.startswith('-- roofline'))
    j = next((k for k in range(i + 1, len(lines))
              if lines[k].startswith('-- ')), len(lines))
    live_block = '\n'.join(lines[i:j])
    assert roofline_report.main([str(roof_on)]) == 0
    out = capsys.readouterr().out
    assert out.rstrip('\n') == live_block
    # --json round-trips the analysis dict itself
    assert roofline_report.main([str(roof_on), '--json']) == 0
    d = json.loads(capsys.readouterr().out)
    assert d['layers'] and d['comm']['bytes'] == _AR_BYTES


def test_roofline_report_no_record(tmp_path, capsys):
    import roofline_report
    p = tmp_path / 'empty.jsonl'
    p.write_text('{"type": "start", "pid": 1}\n')
    assert roofline_report.main([str(p)]) == 1
