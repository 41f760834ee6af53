"""The whole ``lfm2_moe`` model, beside ``test_hybrid_ops.py`` (whose helpers
and small configuration these cases take): builder shapes and refusals,
loss and every gradient against ``benchmark/reference/lfm2_moe.py`` with the
blocks mirrored or not, the tied leaf's gradient as the sum of its two
uses, ``Module.fit`` taking the fused window and following the reference's
steps with the tied leaf updated once, and the benchmark's own files for
this family (the operation counts by hand, the configuration's arithmetic,
the driver's bindings)."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.transformer import MOE_STATS, moe_stat_names

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    'hybrid_ops_cases', os.path.join(REPO, 'tests', 'unittest',
                                     'test_hybrid_ops.py'))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)
ref, builder, CFG, T, d = ops.ref, ops.builder, ops.CFG, ops.T, ops.d
_rand, _close, _load = ops._rand, ops._close, ops._load
TIED = ref.TIED


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
    names = sym.list_arguments()
    assert names.count(TIED) == 1           # one leaf, read twice
    shapes = dict(zip(names, args))
    want = ref.param_shapes(CFG)
    assert set(shapes) - {'data', 'softmax_label'} == set(want)
    assert all(tuple(shapes[k]) == tuple(s) for k, s in want.items())
    assert shapes['layer0_conv_taps_weight'] == (d, 3)
    assert shapes['layer1_attn_q_norm_gamma'] == (16,)
    assert shapes['layer1_moe_select_bias_weight'] == (1, 16)
    assert not any('shared' in k for k in shapes)
    assert outs == [(2 * T, CFG['vocab_size'])]
    assert moe_stat_names(sym) == sym.list_auxiliary_states() \
        == ['layer%d_moe_stats' % i for i in range(1, 5)]
    assert auxs == [(len(MOE_STATS),)] * 4
    # every leaf has a rule in the benchmark's seeded initialisation
    assert all(k.endswith(('_weight', '_gamma')) and
               (len(s) >= 2 or k.endswith('_gamma')) for k, s in want.items())


@pytest.mark.parametrize('unbuilt', [
    dict(conv_bias=True),
    dict(rope_parameters={'rope_theta': 1e6, 'rope_type': 'yarn',
                          'factor': 4}),
    dict(layer_types=['conv', 'sliding_attention', 'conv', 'conv', 'conv']),
    dict(layer_types=['conv', 'full_attention']),
    dict(use_expert_bias=False)], ids=lambda v: sorted(v)[0])
def test_builder_refuses_what_it_does_not_build(unbuilt):
    with pytest.raises(ValueError, match='lfm2_moe'):
        builder.get_symbol(dict(CFG, **unbuilt))


def _bound(sym, p, tok, lab):
    ex = sym.simple_bind(mx.cpu(), data=tok.shape, softmax_label=lab.shape)
    for k, v in p.items():
        ex.arg_dict[k][:] = v
    ex.arg_dict['data'][:] = tok.astype(np.float32)
    ex.arg_dict['softmax_label'][:] = lab.astype(np.float32)
    return ex


@pytest.mark.parametrize('remat', [True, False])
def test_model_loss_and_gradient(remat):
    cfg = dict(CFG, experts_held=8, expert_offset=4)
    sym = builder.get_symbol(cfg, remat=remat)
    p = _model(cfg, seed=1)
    rng = np.random.RandomState(1)
    tok, lab = rng.randint(0, 96, (2, T)), rng.randint(0, 96, (2, T))
    ex = _bound(sym, p, tok, lab)
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()
    want, pairs, g = ref.loss_and_grad(
        {k: jnp.asarray(v) for k, v in p.items()}, tok, lab, cfg)
    loss = -np.log(out[np.arange(2 * T), lab.reshape(-1)]).mean()
    assert abs(loss - float(want)) < 1e-5
    for k in p:
        _close(ex.grad_dict[k].asnumpy(), g[k], tol=1e-4)
    # the expert bias alone takes no gradient
    assert [k for k in p if not ex.grad_dict[k].asnumpy().any()] \
        == ['layer%d_moe_select_bias_weight' % i for i in range(1, 5)]
    got = [int(ex.aux_dict[k].asnumpy()[0]) for k in moe_stat_names(sym)]
    assert got == [int(v) for v in pairs]
    if not remat:
        return
    # at_masters: the reference handed float32 masters computes with their
    # bfloat16 roundings and gives the gradient there
    rounded = ref.working_weights({k: jnp.asarray(v) for k, v in p.items()})
    a = ref._loss_and_grad(rounded, jnp.asarray(tok), jnp.asarray(lab),
                           ref.hashable(cfg), False, False)
    b = ref._loss_and_grad({k: jnp.asarray(v) for k, v in p.items()},
                           jnp.asarray(tok), jnp.asarray(lab),
                           ref.hashable(cfg), False, True)
    assert float(a[0]) == float(b[0]) and float(b[3]) == 0.0
    for k in p:
        _close(b[2][k], a[2][k], tol=1e-6)


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses():
    """One variable read by ``Embedding`` and by the head: the gradient the
    executor gives is what the gather scatters plus what the product
    gives, each taken alone from the reference's own pieces."""
    cfg = dict(CFG, num_hidden_layers=2, layer_types=['conv',
                                                      'full_attention'])
    sym = builder.get_symbol(cfg)
    p = _model(cfg, seed=5)
    rng = np.random.RandomState(6)
    tok, lab = rng.randint(0, 96, (1, T)), rng.randint(0, 96, (1, T))
    ex = _bound(sym, p, tok, lab)
    ex.forward(is_train=True)
    ex.backward()
    w = {k: jnp.asarray(v) for k, v in p.items()}

    def untied(looked_up, head):
        cos, sin = ref.base.rope_tables(1000000, 16, T)
        h = looked_up[jnp.asarray(tok[0])]
        for i, kind in enumerate(cfg['layer_types']):
            h, _ = ref.block(w, 'layer%d' % i, h, cfg, kind,
                             ref.is_sparse(cfg, i), cos, sin)
        h = ref.rms_norm(h, w['final_norm_gamma'], cfg['norm_eps'])
        return ref.cross_entropy(head, h, jnp.asarray(lab[0])) / T

    with jax.default_matmul_precision('highest'):
        by_gather, by_product = jax.grad(untied, (0, 1))(w[TIED], w[TIED])
    assert np.abs(np.asarray(by_gather)).max() > 1e-4
    assert np.abs(np.asarray(by_product)).max() > 1e-4
    # rows that no token looked up get the product's share alone
    unseen = np.setdiff1d(np.arange(96), tok)
    assert not np.asarray(by_gather)[unseen].any()
    _close(ex.grad_dict[TIED].asnumpy(), by_gather + by_product, tol=1e-4)


# -- Module.fit ----------------------------------------------------------------------------

def test_fit_takes_the_fused_window_and_follows_the_reference(monkeypatch):
    """The window is built with ``ce`` and ``acc`` computed inside it, and
    three steps follow the reference's: the tied leaf is updated once a
    step by the sum of its gradients, the expert bias is as it was
    given."""
    steps, lr = 3, 0.05
    cfg = dict(CFG, experts_held=4)
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
            aux_params={n: mx.nd.zeros((len(MOE_STATS),))
                        for n in sym.list_auxiliary_states()},
            num_epoch=1, batch_end_callback=note)
    loop = mod.__dict__['_fused_fit_cache'][1]
    assert loop.window == steps and loop.stat_fns is not None
    w = {k: jnp.asarray(v) for k, v in p.items()}
    mom = {k: jnp.zeros_like(v) for k, v in w.items()}
    want = []
    for i in range(steps):
        loss, _, g = ref.loss_and_grad(w, toks[i:i + 1, :T],
                                       toks[i:i + 1, 1:], cfg)
        want.append(float(loss))
        w, mom = ref.sgd_momentum_step(w, mom, g, lr, 0.9)
    np.testing.assert_allclose(np.diff([0.0] + sums) / T, want, rtol=1e-4)
    got = mod.get_params()[0]
    for n in p:
        _close(got[n].asnumpy() - p[n], np.asarray(w[n]) - p[n], tol=2e-3)
    assert np.abs(got[TIED].asnumpy() - p[TIED]).max() > 1e-4
    for i in range(1, 5):
        n = 'layer%d_moe_select_bias_weight' % i
        np.testing.assert_array_equal(got[n].asnumpy(), p[n])


# -- the benchmark's own files for this family ------------------------------------------------

FLOPS_CASES = ['test_required_flops_of_the_cut_model',
               'test_shares_of_the_required_operations',
               'test_conv_bytes_by_hand', 'test_attention_work_by_hand',
               'test_expert_least_time_by_hand', 'test_small_config_by_hand']


@pytest.mark.parametrize('case', FLOPS_CASES)
def test_flops_hybrid_against_a_count_by_hand(case):
    """The cases of ``benchmark/tests/test_flops_hybrid.py``, which the
    tier-1 run does not collect."""
    cases = _load('benchmark/tests/test_flops_hybrid.py',
                  'flops_hybrid_cases')
    assert sorted(n for n in dir(cases) if n.startswith('test_')) \
        == sorted(FLOPS_CASES)
    getattr(cases, case)()


def _config():
    with open(os.path.join(REPO, 'benchmark', 'configs',
                           'lfm2_24b_a2b.json')) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    """Against the catalog entry's numbers, written out here: a key that
    differs is named in ``reduced`` and is no width."""
    cfg = _config()
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=11776, max_position_embeddings=128000,
        model_type='lfm2_moe', moe_intermediate_size=1536, norm_eps=1e-5,
        norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
        num_experts=64, num_experts_per_tok=4, num_hidden_layers=40,
        num_key_value_heads=8,
        rope_parameters={'rope_theta': 1000000, 'rope_type': 'default'},
        routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == ['num_dense_layers', 'num_hidden_layers', 'vocab_size']
    assert cfg['reduced'] == ['num_hidden_layers', 'num_dense_layers',
                              'layer_types', 'experts_held', 'vocab_size']
    assert sorted(cfg['reduced_detail']) == sorted(cfg['reduced'])
    assert (cfg['num_hidden_layers'], cfg['num_dense_layers'],
            cfg['experts_held'], cfg['vocab_size']) == (5, 1, 16, 16384)
    assert cfg['layer_types'] == ['conv', 'full_attention', 'conv', 'conv',
                                  'conv']
    # 788.0 M parameters, 9.46 GB at 12 bytes each
    count = sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values())
    assert count == 788052352 and abs(count / 1e6 - 788.0) < 0.06
    assert round(count * 12 / 1e9, 2) == 9.46
    assert '788.0 M' in cfg['deployment'] and '9.46 GB' in cfg['deployment']
    # the builder takes it as it stands
    sym = builder.get_symbol(cfg, **cfg['builder']['kwargs'])
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(1, 64), softmax_label=(1, 64))[0]))
    assert {k: tuple(shapes[k]) for k in ref.param_shapes(cfg)} \
        == {k: tuple(s) for k, s in ref.param_shapes(cfg).items()}


def test_the_driver_binds_this_cells_limits_and_kernel_groups():
    """``fit_tokens_hybrid``: ``fit_tokens_heads``'s run under
    ``compare_lm_training``'s limits (the loss, the gradient's worst
    leaf and the two distances its own), with this family's kernel groups; the
    reference is the one the configuration names and has what the
    comparison calls."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark import compare_lm_training
    from benchmark.drivers import fit_tokens_heads, fit_tokens_hybrid
    from benchmark.drivers.fit_tokens_ref import NEEDED, load_reference
    cfg = _config()
    loaded = load_reference(cfg)
    assert all(hasattr(loaded, n) for n in NEEDED)
    assert loaded.param_shapes(cfg) == ref.param_shapes(cfg)
    before = (fit_tokens_heads.LIMITS, fit_tokens_heads.KERNEL_GROUPS)
    try:
        fit_tokens_hybrid.bind()
        assert fit_tokens_heads.LIMITS == fit_tokens_hybrid.LIMITS
        kept = ('change', 'pairs')
        assert {k: fit_tokens_hybrid.LIMITS[k] for k in kept} \
            == {k: compare_lm_training.LIMITS[k] for k in kept}
        assert sorted(fit_tokens_hybrid.LIMITS) \
            == sorted(compare_lm_training.LIMITS)
        seconds = fit_tokens_heads.kernel_seconds(
            {'short_conv_fwd.3 bf16[1,8192,2048]': 1.0,
             'short_conv_bwd.1 (bf16[1,8192,6144], f32[8,2048])': 2.0,
             'attention_full_bwd.2 bf16': 4.0,
             'moe_expert_matmul_dw.7 f32': 8.0,
             'fusion.short_conv_fwd': 16.0}, 31.0)
        assert seconds == {'short_conv': 3.0, 'attention_full': 4.0,
                           'moe_expert': 8.0, 'busy': 31.0}
    finally:
        fit_tokens_heads.LIMITS, fit_tokens_heads.KERNEL_GROUPS = before
    assert cfg['eval_metric'] == [
        {'metric': 'ce', 'output': 'softmax_output',
         'label': 'softmax_label'},
        {'metric': 'acc', 'output': 'softmax_output',
         'label': 'softmax_label'}]
