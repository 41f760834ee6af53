"""The compiled program's instruction-to-scope map
(mxnet_tpu/telemetry/programs.py: scope_of, scope_map) and its join with
a capture's per-instruction seconds (benchmark/reduce/scopes.py).

Contracts under test:
- an ``op_name`` path -> (node, phase, inner): forward, backward, the
  second forward of a mirrored stage as this jax spells it, window parts,
  kernel names and planted inner scopes, paths XLA merged with ``;``;
- the walk of an HLO text: only instructions that can be device events,
  fusions charged to their root's node and counted as mixed, a fusion or
  a copy without a path named by its insides or its neighbours;
- a small ``Module.fit`` on the CPU: under 5% of the compiled window's
  named instructions carry no scope, the update, the metric and the
  nodes land in their groups, a mirrored stage's second forward reads
  ``refwd``;
- the map is written once a compile, beside the telemetry log, also when
  the executable comes from the persistent compile cache; with telemetry
  off no HLO text is rendered and nothing is written;
- the set-up spans ``program.lower``, ``program.compile`` and
  ``fit.prepare_loop``;
- the benchmark's join: rows, kernels, loops, unscoped and unmapped
  seconds sum to the whole, and every reader returns None on a run
  without a capture.
"""
import glob
import json
import os
import re
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.telemetry import programs

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_FLAGS = ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH')
NODES = {'fc1': 'FullyConnected', 'bn1': 'BatchNorm', 'moe': 'MoE',
         'attn': 'LatentAttention'}
W = 'jit(window_fn)/jit(main)/window/while/body/closed_call/'


@pytest.mark.parametrize('path, want', [
    # forward and backward of a node inside the window's scan
    (W + 'jvp(fc1)/dot_general', ('fc1', 'fwd', None)),
    (W + 'transpose(jvp(fc1))/dot_general', ('fc1', 'bwd', None)),
    # a mirrored stage on jax 0.9: its backward pass, and the second
    # forward under rematted_computation
    (W + 'transpose(jvp(jvp()))/checkpoint/fc1/mul', ('fc1', 'bwd', None)),
    (W + 'transpose(jvp(jvp()))/checkpoint/rematted_computation/fc1/tanh',
     ('fc1', 'refwd', None)),
    # window parts: the innermost one, '-' for the pass
    (W + 'update/mul', ('update', '-', None)),
    (W + 'metric/_plan_one.<locals>.stats/reduce', ('metric', '-', None)),
    (W + 'sentinel/stack', ('sentinel', '-', None)),
    (W + 'dynamic_slice', ('window', '-', None)),
    ('jit(window_fn)/jit(main)/window/while', ('window', '-', None)),
    # a planted inner scope, a kernel's name below platform_dependent's
    # branches, a jitted helper's boundary dropped
    (W + 'jvp(moe)/router/jit(_take)/gather', ('moe', 'fwd', 'router')),
    (W + 'jvp(attn)/cond/branch_0_fun/cond/branch_0_fun/'
         'attention_latent_fwd/pallas_call',
     ('attn', 'fwd', 'attention_latent_fwd')),
    (W + 'transpose(jvp(jvp()))/checkpoint/moe/while/body/dw_sum/add',
     ('moe', 'bwd', 'dw_sum')),
    # two paths that XLA merged: the first is read
    (W + 'jvp(attn)/reshape;' + W + 'jvp(fc1)/dot_general',
     ('attn', 'fwd', None)),
    # a segment that names no node of the table is no node
    (W + 'jvp(elsewhere)/add', ('window', '-', None)),
    ('jit(f)/jit(main)/while/body/add', None),
    ('params[0]', None),
])
def test_scope_of(path, want):
    assert programs.scope_of(path, NODES) == want


def test_scope_of_without_a_table_takes_the_first_named_segment():
    assert programs.scope_of('jit(f)/jit(main)/fc9/relu/max') \
        == ('fc9', 'fwd', 'relu')
    assert programs._layer_from_op_name(W + 'jvp(fc9)/max') == 'fc9'
    assert programs._layer_from_op_name(W + 'update/mul') is None


# one entry computation, a scan's body and condition, two fused
# computations and a reducer; instructions as XLA prints them
_HLO = '''\
HloModule jit_window_fn, is_scheduled=true

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_clean (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %t.1 = f32[8,4]{1,0} tanh(%p0), metadata={op_name="WPjvp(fc1)/tanh"}
  ROOT %m.1 = f32[8,4]{1,0} multiply(%t.1, %t.1), metadata={op_name="WPjvp(fc1)/mul"}
}

%fused_mixed (p0: f32[8,4], p1: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[8,4]{1,0} parameter(1)
  %s.1 = f32[8,4]{1,0} subtract(%p0, %p1), metadata={op_name="WPupdate/sub"}
  ROOT %m.2 = f32[8,4]{1,0} multiply(%s.1, %p1), metadata={op_name="WPtranspose(jvp(bn1))/mul"}
}

%fused_bare (p0: f32[8,4]) -> f32[8,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  ROOT %n.1 = f32[8,4]{1,0} negate(%p0), metadata={op_name="WPupdate/neg"}
}

%body (arg: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %arg = (s32[], f32[8,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,4]{1,0} get-tuple-element(%arg), index=1
  %copy.7 = f32[8,4]{1,0} copy(%x)
  %fusion.1 = f32[8,4]{1,0} fusion(%copy.7), kind=kLoop, calls=%fused_clean, metadata={op_name="WPjvp(fc1)/mul"}
  %fusion.2 = f32[8,4]{1,0} fusion(%fusion.1, %x), kind=kLoop, calls=%fused_mixed, metadata={op_name="WPtranspose(jvp(bn1))/mul"}
  %fusion.3 = f32[8,4]{1,0} fusion(%fusion.2), kind=kLoop, calls=%fused_bare
  %moe_expert_matmul.4 = f32[8,4]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="WPjvp(moe)/cond/branch_0_fun/moe_expert_matmul/pallas_call"}
  %reduce.5 = f32[] reduce(%moe_expert_matmul.4, %i), dimensions={0,1}, to_apply=%region_add, metadata={op_name="WPmetric/reduce_sum"}
  %copy-start.6 = (f32[8,4]{1,0}, f32[8,4]{1,0}, u32[]) copy-start(%moe_expert_matmul.4)
  %copy-done.6 = f32[8,4]{1,0} copy-done(%copy-start.6)
  %orphan.8 = f32[8,4]{1,0} negate(%copy-done.6), metadata={op_name="jit(window_fn)/jit(main)/neg"}
  ROOT %tuple.1 = (s32[], f32[8,4]{1,0}) tuple(%i, %copy-done.6)
}

%cond (arg: (s32[], f32[8,4])) -> pred[] {
  %arg = (s32[], f32[8,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %c.1 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%i, %c.1), direction=LT, metadata={op_name="jit(window_fn)/jit(main)/window/while/cond/lt"}
}

ENTRY %main (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0} parameter(0), metadata={op_name="params[0]"}
  %c.0 = s32[] constant(0)
  %tuple.0 = (s32[], f32[8,4]{1,0}) tuple(%c.0, %p)
  %while.1 = (s32[], f32[8,4]{1,0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(window_fn)/jit(main)/window/while"}
  ROOT %out = f32[8,4]{1,0} get-tuple-element(%while.1), index=1
}
'''.replace('WP', W)


def test_scope_map_of_a_synthetic_window():
    m = programs.scope_map(_HLO, NODES)
    got = m['instrs']
    # only what can be a device event; no parameter, tuple or constant,
    # nothing of a fused computation or of a reducer
    assert set(got) == {'while.1', 'copy.7', 'fusion.1', 'fusion.2',
                        'fusion.3', 'moe_expert_matmul.4', 'reduce.5',
                        'copy-start.6', 'copy-done.6', 'orphan.8', 'lt.1'}
    assert got['while.1'] == ['window', '-', None, 'while', 0, '']
    assert got['fusion.1'] == ['fc1', 'fwd', None, 'fusion', 1, '']
    # a fusion across an update and a node: its root's, counted as mixed
    assert got['fusion.2'] == ['bn1', 'bwd', None, 'fusion', 2, '']
    # a fusion with no path of its own: what its insides name
    assert got['fusion.3'] == ['update', '-', None, 'fusion', 1, 'inside']
    assert got['moe_expert_matmul.4'] == ['moe', 'fwd', 'moe_expert_matmul', 'custom-call',
                                  0, '']
    assert got['reduce.5'] == ['metric', '-', None, 'reduce', 0, '']
    # XLA's own copy feeds fc1's fusion; the prefetch pair has no user
    # with a scope and takes its operand's
    assert got['copy.7'] == ['fc1', 'fwd', None, 'copy', 0, 'user']
    assert got['copy-start.6'][:3] == ['moe', 'fwd', 'moe_expert_matmul']
    assert got['copy-start.6'][5] == 'operand'
    assert got['copy-done.6'][5] == 'operand'
    # a path with no planted scope and no neighbour to lend one... has one
    # here (its operand); the condition's compare is the window's
    assert got['lt.1'][:2] == ['window', '-']
    assert m['nodes'] == {'fc1': 'FullyConnected', 'bn1': 'BatchNorm',
                          'moe': 'MoE'}
    # named: 6 event-level paths + 2 + 2 + 1 inside the fusions
    assert (m['named'], m['unscoped']) == (12, 1)


# ---------------------------------------------------------------------------
# a real window on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture
def tele_on(tmp_path, monkeypatch):
    path = tmp_path / 'tele.jsonl'
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(path))
    for f in _FLAGS:
        flags.reload(f)
    telemetry._reset_for_tests()
    yield path
    telemetry._reset_for_tests()
    for f in _FLAGS:
        monkeypatch.delenv(f, raising=False)
        flags.reload(f)


def _net(mirror=False):
    data = mx.sym.Variable('data')
    c1 = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                            name='conv1')
    bn = mx.sym.BatchNorm(c1, name='bn1')
    act = mx.sym.Activation(bn, act_type='relu', name='relu1')
    fl = mx.sym.Flatten(act, name='flat')
    if mirror:
        with mx.AttrScope(__force_mirroring__='stage1'):
            fc0 = mx.sym.FullyConnected(fl, num_hidden=16, name='fc0')
            fl = mx.sym.Activation(fc0, act_type='tanh', name='tanh0')
    fc = mx.sym.FullyConnected(fl, num_hidden=4, name='fc1')
    return mx.sym.SoftmaxOutput(fc, name='softmax')


def _fit(sym):
    np.random.seed(0)
    mx.random.seed(0)
    X = np.random.randn(32, 3, 8, 8).astype(np.float32)
    y = (np.random.rand(32) * 4).astype(int).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=8, label_name='softmax_label')
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer='sgd', eval_metric=['ce', 'acc'],
            optimizer_params=(('learning_rate', 0.1), ('momentum', 0.9)))
    assert mod.__dict__['_fused_fit_cache'][1].stat_fns is not None
    telemetry._state.sink.flush()
    return mod


def _records(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _window_map(log):
    recs = [r for r in _records(log) if r.get('type') == 'program'
            and r['name'].startswith('fused_fit.window[')]
    assert len(recs) == 1 and recs[0]['scopes']
    with open(os.path.join(os.path.dirname(log), recs[0]['scopes'])) as f:
        return recs[0], json.load(f)


def test_fit_window_leaves_few_instructions_without_a_scope(tele_on):
    _fit(_net())
    rec, m = _window_map(str(tele_on))
    assert m['program'] == rec['name'] == 'fused_fit.window[softmax]'
    # it was 276 of 451 before the window planted its parts
    assert m['named'] > 100 and m['unscoped'] < 0.05 * m['named']
    assert rec['scopes_unscoped'] == m['unscoped']
    assert rec['scopes_bytes'] == os.path.getsize(
        os.path.join(os.path.dirname(str(tele_on)), rec['scopes']))
    by = {}
    for node, phase, _inner, opcode, _fused, _via in m['instrs'].values():
        by.setdefault(node, set()).add(phase)
    # every layer with work forward and backward, by its op
    assert m['nodes'] == {'conv1': 'Convolution', 'bn1': 'BatchNorm',
                          'relu1': 'Activation', 'fc1': 'FullyConnected',
                          'softmax': 'SoftmaxOutput'}
    for node in ('conv1', 'bn1', 'fc1'):
        assert {'fwd', 'bwd'} <= by[node], (node, by[node])
    # the optimizer's update, the metric plan and the scan itself
    assert by['update'] == {'-'} and by['metric'] == {'-'}
    assert by['window'] == {'-'}
    whiles = [v for v in m['instrs'].values() if v[3] == 'while']
    assert whiles and all(v[0] == 'window' for v in whiles)
    # no instruction is named by the metric plan's function any more
    assert not any('_plan_one' in str(v[0]) for v in m['instrs'].values())


def test_a_mirrored_stage_reads_refwd(tele_on, monkeypatch):
    """What this jax writes into the path of a stage's second forward:
    every instruction of the compiled window (at this size XLA fuses the
    recomputed ones into the backward fusions that read them, which the
    map charges to their roots and counts as mixed)."""
    texts = []
    render = programs._hlo_text
    monkeypatch.setattr(programs, '_hlo_text',
                        lambda c: texts.append(render(c)) or texts[-1])
    _fit(_net(mirror=True))
    _, m = _window_map(str(tele_on))
    text = [t for t in texts if 'fc0' in t][-1]
    nodes = dict(programs._node_ops)
    phases = {}
    for path in re.findall(r'op_name="([^"]*)"', text):
        sc = programs.scope_of(path, nodes)
        if sc is not None:
            phases.setdefault(sc[0], set()).add(sc[1])
    # the stage's nodes run forward, again in the backward pass, and
    # backward, but for what an op named as dear to recompute: fc0
    # contracts (512 -> 16), so its output is kept and the product runs
    # once; a node outside any stage never reads refwd
    assert phases['fc0'] == {'fwd', 'bwd'}
    assert phases['tanh0'] == {'fwd', 'refwd', 'bwd'}
    for node in ('fc1', 'conv1', 'bn1', 'softmax'):
        assert 'refwd' not in phases[node], node
    assert m['unscoped'] < 0.05 * m['named']
    assert any(v[4] > 1 for v in m['instrs'].values())


def norm_block_step():
    """(step, parameters, nodes) of a small block whose RMSNorm lies in a
    mirrored stage: step(parameters) -> (outputs, gradients), traced as
    the fused window traces a symbol's runner. (tests/unittest/
    test_tpu_compile.py compiles the same step for a described v5e.)"""
    from mxnet_tpu.executor import _GraphProgram
    from test_transformer_ops import _training_step
    fc = lambda x, n, name: mx.sym.FullyConnected(  # noqa: E731
        x, num_hidden=n, no_bias=True, flatten=False, name=name)
    h = fc(mx.sym.Variable('data'), 128, 'fc0')
    with mx.AttrScope(__force_mirroring__='stage1'):
        h = mx.sym.RMSNorm(h, mx.sym.Variable('norm_gamma'), name='norm')
        h = mx.sym.Activation(fc(h, 128, 'fc1'), act_type='tanh',
                              name='tanh1')
    sym = mx.sym.SoftmaxOutput(mx.sym.Reshape(fc(h, 8, 'fc2'),
                                              shape=(-1, 8)), name='softmax')
    step, wrt = _training_step(sym, data=(2, 16, 64), softmax_label=(32,))
    prog = _GraphProgram(sym)
    nodes = {s: n.op for s, n in zip(prog.scope_names, prog.topo)
             if not n.is_variable()}
    return step, wrt, nodes


def test_rmsnorm_backward_kernel_lies_under_bwd():
    """The block's training step lowered for the TPU: the backward kernel's
    one call has a path that reads as RMSNorm `bwd` with the kernel's name
    below the node, though the node lies in a mirrored stage, whose second
    forward runs the forward kernel again (`refwd`)."""
    import jax
    step, wrt, nodes = norm_block_step()
    text = jax.jit(step).trace(wrt).lower(lowering_platforms=('tpu',)) \
        .as_text(debug_info=True)
    paths = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = [(kernel, programs.scope_of(paths[at], nodes))
             for kernel, at in re.findall(
                 r'kernel_name = "(fused_rmsnorm\w*)".*loc\((#loc\d+)\)$',
                 text, re.M)]
    assert sorted(calls) == [
        ('fused_rmsnorm', ('norm', 'fwd', 'fused_rmsnorm')),
        ('fused_rmsnorm', ('norm', 'refwd', 'fused_rmsnorm')),
        ('fused_rmsnorm_bwd', ('norm', 'bwd', 'fused_rmsnorm_bwd'))]
    assert nodes['norm'] == 'RMSNorm'


def test_set_up_spans(tele_on):
    _fit(_net())
    spans = [r for r in _records(str(tele_on)) if r.get('type') == 'span']
    by = {}
    for r in spans:
        by.setdefault(r['name'], []).append(r)
    window = [r for r in by['program.lower']
              if r['program'] == 'fused_fit.window[softmax]']
    assert len(window) == 1
    assert [r['program'] for r in by['program.compile']].count(
        'fused_fit.window[softmax]') == 1
    # the fused loop is built inside it, and it ends before the first draw
    prep = by['fit.prepare_loop']
    assert len(prep) == 1
    first_draw = min(r['t'] for r in by['fused_fit.draw'])
    assert prep[0]['t'] + prep[0]['dur_ms'] / 1e3 <= first_draw + 1e-3
    assert by['fit.init_optimizer'][0]['t'] <= prep[0]['t']


def test_map_is_written_on_a_compile_cache_hit(tele_on, tmp_path):
    """The driver's runs are warm: the executable then comes out of the
    persistent cache, and its text still carries the paths."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = {k: getattr(jax.config, k) for k in (
        'jax_compilation_cache_dir', 'jax_enable_compilation_cache',
        'jax_persistent_cache_min_compile_time_secs',
        'jax_persistent_cache_min_entry_size_bytes')}
    hits = []
    jax.monitoring.register_event_listener(
        lambda e, **_: hits.append(e) if e.endswith('cache_hits') else None)
    try:
        jax.config.update('jax_compilation_cache_dir', str(tmp_path / 'cc'))
        jax.config.update('jax_enable_compilation_cache', True)
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
        cc.reset_cache()
        programs.note_nodes({'layer_a': 'FullyConnected'})

        def f(x):
            with jax.named_scope('layer_a'):
                y = jnp.tanh(x @ x)
            with jax.named_scope('update'):
                return y - 0.1 * x

        x = jnp.ones((16, 16), jnp.float32)
        maps = []
        for attempt in range(2):
            prog = programs.register('cache_probe', jax.jit(f))
            np.testing.assert_allclose(prog(x), f(x), rtol=1e-6)
            telemetry._state.sink.flush()
            rec = [r for r in _records(str(tele_on))
                   if r.get('type') == 'program'][-1]
            with open(tmp_path / rec['scopes']) as fh:
                maps.append(json.load(fh))
            jax.clear_caches()      # the next compile asks the disk
        assert hits, 'the second compile did not come from the cache'
        for m in maps:
            nodes = {v[0] for v in m['instrs'].values()}
            assert {'layer_a', 'update'} <= nodes
        assert maps[0]['instrs'] == maps[1]['instrs']
        assert len(glob.glob(str(tmp_path / '*.scopes.cache_probe.*'))) == 2
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_nothing_is_rendered_with_telemetry_off(tmp_path, monkeypatch):
    monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 'off.jsonl'))
    for f in _FLAGS:
        flags.reload(f)
    telemetry._reset_for_tests()
    called = []
    monkeypatch.setattr(programs, '_hlo_text',
                        lambda c: called.append('text') or '')
    monkeypatch.setattr(programs, 'scope_map',
                        lambda *a, **k: called.append('walk') or {})

    class _Boom:
        def as_text(self):
            raise AssertionError('HLO rendered with telemetry off')

        def runtime_executable(self):
            raise AssertionError('executable read with telemetry off')

        def cost_analysis(self):
            return {}

        def memory_analysis(self):
            return None

    try:
        programs.note_program('p', compiled=_Boom())
        programs.note_nodes({'n': 'Op'})
        assert programs._node_ops == {}
        _fit(_net()) if False else None
        import jax
        fn = jax.jit(lambda x: x + 1)
        assert programs.register('q', fn) is fn     # no wrapper at all
        assert not called
        assert not os.listdir(tmp_path)
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
            flags.reload(f)


def test_a_fit_with_telemetry_off_writes_no_map(tmp_path, monkeypatch):
    monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 'off.jsonl'))
    for f in _FLAGS:
        flags.reload(f)
    telemetry._reset_for_tests()
    monkeypatch.setattr(programs, '_hlo_text', lambda c: 1 / 0)
    monkeypatch.setattr(programs, 'scope_map', lambda *a, **k: 1 / 0)
    try:
        np.random.seed(0)
        X = np.random.randn(32, 3, 8, 8).astype(np.float32)
        y = np.zeros(32, np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=8,
                               label_name='softmax_label')
        mod = mx.mod.Module(_net(), context=mx.cpu())
        mod.fit(it, num_epoch=1, optimizer='sgd', eval_metric=['ce', 'acc'],
                optimizer_params=(('learning_rate', 0.1),))
        assert programs._node_ops == {}
        assert not os.listdir(tmp_path)
    finally:
        telemetry._reset_for_tests()
        for f in _FLAGS:
            monkeypatch.delenv(f, raising=False)
            flags.reload(f)


# ---------------------------------------------------------------------------
# the benchmark's join
# ---------------------------------------------------------------------------

def _by_name():
    """Seconds over a slice of 2 steps, keyed as reduce/trace.py keys
    them; one event of another program."""
    return {'while.1 (s32[], f32[8,4]) ': 0.002,
            'copy.7 f32[8,4] ': 0.010,
            'fusion.1 f32[8,4] kLoop': 0.100,
            'fusion.2 f32[8,4] kLoop': 0.060,
            'fusion.3 f32[8,4] kLoop': 0.040,
            'moe_expert_matmul.4 f32[8,4] ': 0.200,
            'reduce.5 f32[] ': 0.020,
            'copy-done.6 f32[8,4] ': 0.030,
            'orphan.8 f32[8,4] ': 0.004,
            'fusion.77 u32[2] kLoop': 0.006}


def test_join_sums_to_the_whole():
    from benchmark.reduce import scopes
    m = programs.scope_map(_HLO, NODES)
    # lend nothing to the orphan for this test: a truly unscoped event
    m['instrs']['orphan.8'] = [None, None, None, 'negate', 0, '']
    t = scopes.join(_by_name(), m, steps=2)
    assert t['rows'] == pytest.approx({
        ('FullyConnected', 'fwd', ''): 0.055,       # fusion.1 + copy.7
        ('BatchNorm', 'bwd', ''): 0.030,
        ('update', '-', ''): 0.020,
        ('MoE', 'fwd', 'moe_expert_matmul'): 0.115,         # the kernel + its copy
        ('metric', '-', ''): 0.010})
    assert t['kernels'] == pytest.approx({('MoE', 'moe_expert_matmul'): 0.100})
    assert t['loops'] == pytest.approx({'while': 0.001})
    assert t['unscoped_s'] == pytest.approx(0.002)
    assert t['unmapped_s'] == pytest.approx(0.003)
    assert t['mixed_s'] == pytest.approx(0.030)
    assert t['lent_s'] == pytest.approx(0.005 + 0.015)
    parts = sum(t['rows'].values()) + sum(t['loops'].values()) \
        + t['unscoped_s'] + t['unmapped_s']
    assert parts == pytest.approx(t['total_s']) == pytest.approx(0.236)


READERS = ('unscoped_device_pct', 'update_device_pct', 'window_loop_self_ms',
           'recompute_device_pct', 'dense_device_pct',
           'norm_rotary_device_pct', 'moe_glue_device_pct',
           'attn_glue_device_pct', 'bn_device_pct')


def _reader(name):
    from benchmark import harness
    return harness.load_file_module(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name + '.py'))


@pytest.mark.parametrize('name', READERS)
def test_reader_finds_nothing_in_an_untraced_run(name, monkeypatch):
    monkeypatch.delenv('MXTPU_TELEMETRY_PATH', raising=False)
    assert _reader(name).read({}) is None
    assert _reader(name).read({'trace': None, 'kernels': {}}) is None


def test_readers_on_a_joined_run(tmp_path, monkeypatch, capsys):
    """The whole path of a traced run: the log's program record names the
    map's file, the table is made once and printed, the readers read it."""
    log = tmp_path / 'telemetry.jsonl'
    m = programs.scope_map(_HLO, NODES)
    m['program'] = 'fused_fit.window[softmax]'
    m['instrs']['orphan.8'] = [None, None, None, 'negate', 0, '']
    (tmp_path / 'telemetry.scopes.w.1.json').write_text(json.dumps(m))
    log.write_text('\n'.join(json.dumps(r) for r in (
        {'type': 'program', 'name': 'executor.fwd', 'scopes': 'none.json'},
        {'type': 'program', 'name': 'fused_fit.window[softmax]',
         'scopes': 'telemetry.scopes.w.1.json'})) + '\n')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(log))
    run = {'trace': {'by_name': _by_name(), 'busy_s': 0.472},
           'trace_steps': 2, 'kernels': {}}
    got = {name: _reader(name).read(run) for name in READERS}
    busy = 0.236
    assert got['unscoped_device_pct'] == pytest.approx(100 * 0.005 / busy)
    assert got['update_device_pct'] == pytest.approx(100 * 0.020 / busy)
    assert got['window_loop_self_ms'] == pytest.approx(1.0)
    assert got['recompute_device_pct'] == 0.0
    assert got['dense_device_pct'] == pytest.approx(100 * 0.055 / busy)
    assert got['norm_rotary_device_pct'] == 0.0
    assert got['moe_glue_device_pct'] == pytest.approx(100 * 0.015 / busy)
    assert got['attn_glue_device_pct'] == 0.0
    assert got['bn_device_pct'] == pytest.approx(100 * 0.030 / busy)
    err = capsys.readouterr().err
    assert err.count('[bench scopes] fused_fit.window[softmax]') == 1
    assert re.search(r'apart by 0\.0000%', err)
