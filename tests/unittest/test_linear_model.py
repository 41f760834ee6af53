"""The whole ``olmo_hybrid`` model, beside ``test_linear_ops.py`` (whose
helpers and small configuration these cases take): builder shapes and
refusals, loss and every gradient against
``benchmark/reference/olmo_hybrid.py`` on both dispatch paths, ``Module.fit``
taking the fused window and following the reference's steps, the counter
and the gauge a traced window yields, a program that got the mechanism
wrong standing outside the limits, and the benchmark's own files for this
family (the operation counts by hand, the configuration's arithmetic, the
driver's bindings)."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.transformer import DELTA_STATS


@pytest.fixture(autouse=True)
def small_kernel_bodies(monkeypatch):
    """The interpreter compiles a kernel's body op by op, and the chunk
    kernels' bodies are unrolled over heads and over the substitution's
    steps: one head a grid step and blocks of 16 rows here keep a model's
    trace at the time it took when XLA had a chunk's state-free part.
    tests/unittest/test_linear_ops.py holds the kernels at the program's
    own numbers."""
    monkeypatch.setattr(pk, '_DELTA_CHUNK_HEADS', 1)
    monkeypatch.setattr(pk, '_DELTA_SOLVE_ROWS', 16)


REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    'linear_ops_cases', os.path.join(REPO, 'tests', 'unittest',
                                     'test_linear_ops.py'))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)
ref, builder, CFG, cases = ops.ref, ops.builder, ops.CFG, ops.cases
_close, _load, path, PATHS = ops._close, ops._load, ops.path, ops.PATHS
T = 80      # a chunk and a part of one


def _model(cfg, seed=0):
    shapes = ref.param_shapes(cfg)
    rng = np.random.RandomState(seed)
    return {n: np.ones(s, np.float32) if n.endswith('gamma') else
            (rng.randn(*s) / np.sqrt(s[1])).astype(np.float32)
            for n, s in shapes.items()}


# -- the whole model -------------------------------------------------------------------------

def test_builder_shapes_are_the_references():
    sym = builder.get_symbol(CFG)
    assert sym.list_outputs() == ['softmax_output']
    args, outs, auxs = sym.infer_shape(data=(2, T), softmax_label=(2, T))
    shapes = dict(zip(sym.list_arguments(), args))
    want = ref.param_shapes(CFG)
    assert set(shapes) - {'data', 'softmax_label'} == set(want)
    assert all(tuple(shapes[k]) == tuple(s) for k, s in want.items())
    assert shapes['layer0_lin_taps_weight'] == (4 * (12 + 12 + 24), 4)
    assert shapes['layer0_lin_A_log_weight'] == (1, 4)
    assert shapes['layer0_lin_o_norm_gamma'] == (24,)
    assert shapes['layer3_attn_q_norm_gamma'] == (64,)
    assert outs == [(2 * T, CFG['vocab_size'])]
    assert auxs == [(len(DELTA_STATS),)] * 3
    # every leaf has a rule in the benchmark's seeded initialisation
    assert all(k.endswith(('_weight', '_gamma')) and
               (len(s) >= 2 or k.endswith('_gamma')) for k, s in want.items())


@pytest.mark.parametrize('unbuilt', [
    dict(layer_types=['linear_attention', 'sliding_attention'] * 2),
    dict(layer_types=['linear_attention', 'full_attention']),
    dict(linear_num_key_heads=2),
    dict(rope_parameters={'rope_theta': 500000}),
    dict(tie_word_embeddings=True),
    dict(attention_bias=True),
    dict(hidden_act='gelu')], ids=lambda v: sorted(v)[0])
def test_builder_refuses_what_it_does_not_build(unbuilt):
    with pytest.raises(ValueError, match='olmo_hybrid'):
        builder.get_symbol(dict(CFG, **unbuilt))


def _bound(sym, p, tok, lab):
    ex = sym.simple_bind(mx.cpu(), data=tok.shape, softmax_label=lab.shape)
    for k, v in p.items():
        ex.arg_dict[k][:] = v
    ex.arg_dict['data'][:] = tok.astype(np.float32)
    ex.arg_dict['softmax_label'][:] = lab.astype(np.float32)
    return ex


def _loss_and_gradients(sym, p, tok, lab):
    ex = _bound(sym, p, tok, lab)
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()
    loss = -np.log(out[np.arange(tok.size), lab.reshape(-1)]).mean()
    return loss, {k: ex.grad_dict[k].asnumpy() for k in p}, ex


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_model_loss_and_gradient(path):
    sym = builder.get_symbol(CFG)
    p = _model(CFG, seed=1)
    rng = np.random.RandomState(1)
    tok, lab = rng.randint(0, 96, (1, T)), rng.randint(0, 96, (1, T))
    loss, grads, ex = _loss_and_gradients(sym, p, tok, lab)
    want, pairs, g = ref.loss_and_grad(
        {k: jnp.asarray(v) for k, v in p.items()}, tok, lab, CFG)
    assert abs(loss - float(want)) < 1e-5 and pairs.shape == (0,)
    for k in p:
        _close(grads[k], g[k], tol=1e-4)
    # every leaf takes gradient, the decay's two among them
    assert all(np.abs(v).max() > 0 for v in grads.values())
    rows = [ex.aux_dict['layer%d_lin_stats' % i].asnumpy() for i in range(3)]
    assert [float(r[0]) for r in rows] == [1.0 * T] * 3
    assert all(r[1] > 0 for r in rows)
    if path == 'kernel':
        return
    # at_masters: the reference handed float32 masters computes with their
    # bfloat16 roundings and gives the gradient there
    w = {k: jnp.asarray(v) for k, v in p.items()}
    a = ref._loss_and_grad(ref.working_weights(w), jnp.asarray(tok),
                           jnp.asarray(lab), ref.hashable(CFG), False, False)
    b = ref._loss_and_grad(w, jnp.asarray(tok), jnp.asarray(lab),
                           ref.hashable(CFG), False, True)
    assert float(a[0]) == float(b[0]) and float(b[3]) == 0.0
    for k in p:
        _close(b[2][k], a[2][k], tol=1e-6)


# -- Module.fit ----------------------------------------------------------------------------

def _reload_telemetry():
    for f in ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH'):
        flags.reload(f)
    telemetry._reset_for_tests()


def _fit(cfg, steps, lr, monkeypatch):
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', str(steps))
    sym = builder.get_symbol(cfg)
    p = _model(cfg, seed=3)
    toks = np.random.RandomState(4).randint(0, 96, (steps, T + 1))
    it = mx.io.NDArrayIter(toks[:, :T].astype(np.float32),
                           toks[:, 1:].astype(np.float32), batch_size=1,
                           label_name='softmax_label')
    sums = []

    def note(param):
        sums.append(float(param.eval_metric.metrics[0].sum_metric))

    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, eval_metric=['ce', 'acc'], optimizer='sgd',
            optimizer_params={'learning_rate': lr, 'momentum': 0.9,
                              'wd': 0.0},
            arg_params={k: mx.nd.array(v) for k, v in p.items()},
            aux_params={n: mx.nd.zeros((len(DELTA_STATS),))
                        for n in sym.list_auxiliary_states()},
            num_epoch=1, batch_end_callback=note)
    return mod, p, toks, np.diff([0.0] + sums) / T


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_fit_takes_the_fused_window_and_follows_the_reference(
        path, tmp_path, monkeypatch):
    """The window is built with ``ce`` and ``acc`` computed inside it, and
    three steps follow the reference's, the decay's leaves among the
    others; with telemetry on the window's one fetch brings the rows
    scanned and the largest state home."""
    steps, lr = 3, 0.05
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    _reload_telemetry()
    try:
        mod, p, toks, losses = _fit(CFG, steps, lr, monkeypatch)
        snap = telemetry.snapshot()
    finally:
        monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
        _reload_telemetry()
    loop = mod.__dict__['_fused_fit_cache'][1]
    assert loop.window == steps and loop.stat_fns is not None
    assert snap['counters']['delta_rule.rows'] == steps * T * 3
    assert snap['gauges']['delta_rule.state_abs_max'] > 0
    w = {k: jnp.asarray(v) for k, v in p.items()}
    mom = {k: jnp.zeros_like(v) for k, v in w.items()}
    want = []
    for i in range(steps):
        loss, _, g = ref.loss_and_grad(w, toks[i:i + 1, :T],
                                       toks[i:i + 1, 1:], CFG)
        want.append(float(loss))
        w, mom = ref.sgd_momentum_step(w, mom, g, lr, 0.9)
    np.testing.assert_allclose(losses, want, rtol=1e-4)
    got = mod.get_params()[0]
    for n in p:
        _close(got[n].asnumpy() - p[n], np.asarray(w[n]) - p[n], tol=2e-3)
    for n in ('layer0_lin_A_log_weight', 'layer1_lin_dt_bias_weight'):
        assert np.abs(got[n].asnumpy() - p[n]).max() > 1e-5


def test_telemetry_off_leaves_no_statistics_in_the_window(monkeypatch):
    monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
    _reload_telemetry()
    cfg = dict(CFG, num_hidden_layers=1, layer_types=['linear_attention'])
    mod = _fit(cfg, 2, 0.05, monkeypatch)[0]
    assert mod.__dict__['_fused_fit_cache'][1]._aux_stats == []
    assert not [k for k in telemetry.snapshot()['counters']
                if k.startswith('delta_rule.')]


# -- a program that got the mechanism wrong ------------------------------------------------

def _distance(got, want):
    """``compare_lm_training``'s distance over all leaves."""
    num = sum(float(np.sum((np.asarray(got[k], np.float64)
                            - np.asarray(want[k], np.float64)) ** 2))
              for k in want)
    den = sum(float(np.sum(np.asarray(want[k], np.float64) ** 2))
              for k in want)
    return (num / den) ** 0.5


WRONG = ['state_dropped_between_chunks', 'beta_without_the_factor_2',
         'norm_before_the_sub_layer']
# one layer of each kind is enough to be wrong in
TWO = dict(CFG, num_hidden_layers=2,
           layer_types=['linear_attention', 'full_attention'])


@pytest.fixture(scope='module')
def case():
    """(parameters, tokens, labels, the reference's gradient) at T = 2
    chunks and a part."""
    length = 2 * pk.DELTA_CHUNK + 16
    p = _model(TWO, seed=2)
    rng = np.random.RandomState(2)
    tok, lab = rng.randint(0, 96, (1, length)), rng.randint(0, 96, (1, length))
    return p, tok, lab, ref.loss_and_grad(
        {k: jnp.asarray(v) for k, v in p.items()}, tok, lab, TWO)[2]


@pytest.mark.parametrize('path', ['kernel'], indirect=True)
@pytest.mark.parametrize('wrong', WRONG)
def test_a_program_that_got_the_mechanism_wrong_fails(wrong, case, path,
                                                      monkeypatch):
    """Against the reference's gradient, in float32, where the right
    program stands at 1e-4 (``test_model_loss_and_gradient``): a program
    that drops the state between chunks, takes beta without the factor 2,
    or puts the norm before the sub-layer stands outside the distance's
    limit of ``drivers/fit_tokens_linear.py``, twice over."""
    from benchmark.drivers import fit_tokens_linear
    limit = fit_tokens_linear.LIMITS['grad_distance']
    p, tok, lab, want = case
    cfg = dict(TWO)
    if wrong == 'state_dropped_between_chunks':
        # every chunk starts from an empty state
        kernel = pk._delta_fwd_kernel

        def forgetful(*refs, heads):
            refs[-1][...] = jnp.zeros(refs[-1].shape, jnp.float32)
            return kernel(*refs, heads=heads)

        monkeypatch.setattr(pk, '_delta_fwd_kernel', forgetful)
    elif wrong == 'beta_without_the_factor_2':
        cfg['linear_allow_neg_eigval'] = False
    sym = _norm_first_symbol(cfg) if wrong == 'norm_before_the_sub_layer' \
        else builder.get_symbol(cfg)
    got = _loss_and_gradients(sym, p, tok, lab)[1]
    assert _distance(got, want) > 2 * limit


def _norm_first_symbol(cfg):
    """``builder.get_symbol`` with ``h + RMSNorm(F(h))`` read as ``h +
    F(RMSNorm(h))``: the same leaves and shapes (both norms of a block are
    ``hidden_size`` wide)."""
    source = open(os.path.join(REPO, 'examples', 'transformer', 'symbols',
                               'olmo_hybrid.py')).read()
    moved = source.replace(
        "op = linear_attention(h, name + '_lin')",
        "op = linear_attention(norm(h, name + '_op_norm'), name + '_lin')"
    ).replace(
        "op = attention(h, name + '_attn')",
        "op = attention(norm(h, name + '_op_norm'), name + '_attn')"
    ).replace(
        "h = h + norm(op, name + '_op_norm')", "h = h + op"
    ).replace(
        "data=h, w1_weight=var(p + '_w1_weight')",
        "data=norm(h, name + '_ffn_norm'), w1_weight=var(p + '_w1_weight')"
    ).replace(
        "return h + norm(mlp, name + '_ffn_norm')", "return h + mlp")
    assert moved.count("norm(h, name + '_op_norm')") == 2 \
        and "h + norm(" not in moved
    scope = {}
    exec(compile(moved, 'olmo_hybrid_norm_first', 'exec'), scope)
    return scope['get_symbol'](cfg)


# -- the benchmark's own files for this family ------------------------------------------------

FLOPS_CASES = ['test_required_flops_of_the_cut_model',
               'test_shares_of_the_required_operations',
               'test_scan_by_hand', 'test_delta_rule_least_time_by_hand',
               'test_conv_bytes_by_hand', 'test_attention_work_by_hand',
               'test_small_config_by_hand']


@pytest.mark.parametrize('case', FLOPS_CASES)
def test_flops_linear_against_a_count_by_hand(case):
    """The cases of ``benchmark/tests/test_flops_linear.py``, which the
    tier-1 run does not collect."""
    by_hand = _load('benchmark/tests/test_flops_linear.py',
                    'flops_linear_cases')
    assert sorted(n for n in dir(by_hand) if n.startswith('test_')) \
        == sorted(FLOPS_CASES)
    getattr(by_hand, case)()


def _config():
    with open(os.path.join(REPO, 'benchmark', 'configs',
                           'olmo_hybrid_7b.json')) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    """Against the catalog entry's numbers, written out here: a key that
    differs is named in ``reduced`` and is no width."""
    cfg = _config()
    published = dict(
        model_type='olmo_hybrid', vocab_size=100352, hidden_size=3840,
        intermediate_size=11008, num_hidden_layers=32,
        num_attention_heads=30, num_key_value_heads=30, hidden_act='silu',
        max_position_embeddings=65536, attention_bias=False,
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        layer_types=(['linear_attention'] * 3 + ['full_attention']) * 8,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={'rope_theta': None})
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == ['layer_types', 'num_hidden_layers', 'vocab_size']
    assert cfg['reduced'] == ['num_hidden_layers', 'layer_types',
                              'vocab_size']
    assert sorted(cfg['reduced_detail']) == sorted(cfg['reduced'])
    assert (cfg['num_hidden_layers'], cfg['vocab_size']) == (4, 12544)
    assert cfg['layer_types'] == published['layer_types'][:4]
    assert cfg['vocab_size'] * 8 == published['vocab_size']
    # 928.9 M parameters, 11.15 GB at 12 bytes each
    shapes = ref.param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert count == 928862196 and abs(count / 1e6 - 928.9) < 0.05
    assert round(count * 12 / 1e9, 2) == 11.15
    assert '928.9 M' in cfg['deployment'] and '11.15 GB' in cfg['deployment']
    mixer = sum(int(np.prod(s)) for k, s in shapes.items()
                if k.startswith('layer0_lin_') and not k.endswith('gamma'))
    assert round(mixer / 1e6, 2) == 88.75
    # the builder takes it as it stands
    sym = builder.get_symbol(cfg, **cfg['builder']['kwargs'])
    got = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(1, 64), softmax_label=(1, 64))[0]))
    assert {k: tuple(got[k]) for k in shapes} \
        == {k: tuple(s) for k, s in shapes.items()}


def test_the_driver_binds_this_cells_limits_and_kernel_groups():
    """``fit_tokens_linear``: ``fit_tokens_heads``'s run under this cell's
    own limits with this family's kernel groups; the reference is the one
    the configuration names and has what the comparison calls."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import compare_lm_training
    from benchmark.drivers import fit_tokens_heads, fit_tokens_linear
    from benchmark.drivers.fit_tokens_ref import NEEDED, load_reference
    cfg = _config()
    loaded = load_reference(cfg)
    assert all(hasattr(loaded, n) for n in NEEDED)
    assert loaded.param_shapes(cfg) == ref.param_shapes(cfg)
    assert fit_tokens_linear.linear_layers(cfg) == 3
    before = (fit_tokens_heads.LIMITS, fit_tokens_heads.KERNEL_GROUPS)
    try:
        fit_tokens_linear.bind()
        assert fit_tokens_heads.LIMITS == fit_tokens_linear.LIMITS
        assert sorted(fit_tokens_linear.LIMITS) \
            == sorted(compare_lm_training.LIMITS)
        seconds = fit_tokens_heads.kernel_seconds(
            {'delta_rule_fwd.3 bf16[1,30,4096,192]': 1.0,
             'delta_rule_bwd.1 (bf16[1,30,4096,96])': 2.0,
             'attention_full_bwd.2 bf16': 4.0,
             'fusion.delta_rule_fwd': 16.0}, 31.0)
        assert seconds == {'delta_rule': 3.0, 'attention_full': 4.0,
                           'busy': 31.0}
    finally:
        fit_tokens_heads.LIMITS, fit_tokens_heads.KERNEL_GROUPS = before
    assert cfg['eval_metric'] == [
        {'metric': 'ce', 'output': 'softmax_output',
         'label': 'softmax_label'},
        {'metric': 'acc', 'output': 'softmax_output',
         'label': 'softmax_label'}]
