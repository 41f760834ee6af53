"""What Xing4.0-29B-A4B (``xing4_0`` family) added, beside
``test_latent_ops.py`` and at small widths with the published ratios kept
(4 streams, 4 heads, keys of 32 + 16, values of 32, latents of 24 and 40,
16 experts, top 3, one shared), on the CPU in float32, against the plain
reference ``benchmark/reference/xing4_0.py``:

- the stream-mixing ops ``HyperPre``, ``HyperPost``, ``HyperCollapse``,
  forward and gradient, through the plain form and through the kernels
  (MXTPU_FORCE_PALLAS=1: the Pallas interpreter); the projected matrix
  doubly stochastic, the clamp active at both ends;
- ``LatentAttention(scale=...)``, the low-rank query block with YaRN;
- the metric plan's cases (the whole model, its shares and ``fit`` with two
  heads are ``test_hyper_model.py``'s, beside this file);
- Kanana's symbol lowers to the text it had; ``reduce/flops_hyper.py``
  against a count by hand; the driver binds what the configuration names.
"""
import hashlib
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import metric as metric_mod
from mxnet_tpu.module import window_pipeline
from mxnet_tpu.ops.transformer import HYPER_STATS

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for folder in (REPO, os.path.join(REPO, 'examples', 'transformer', 'symbols')):
    if folder not in sys.path:      # the builder imports its sibling
        sys.path.insert(0, folder)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load('benchmark/reference/xing4_0.py', 'xing4_0_reference')
builder = _load('examples/transformer/symbols/xing4_0.py', 'xing4_0_symbol')
latent = _load('tests/unittest/test_latent_ops.py', 'latent_ops_cases')
cases = latent.cases
path, PATHS, LM_IN = cases.path, cases.PATHS, cases.LM_IN
_rand, _close, _both, op = cases._rand, cases._close, cases._both, cases.op
_training_step = cases._training_step

YARN = {'beta_fast': 32, 'beta_slow': 1, 'factor': 64, 'mscale': 1,
        'mscale_all_dim': 1, 'original_max_position_embeddings': 16,
        'type': 'yarn'}
CFG = dict(
    model_type='xing4_0', hidden_size=64, vocab_size=96,
    num_hidden_layers=2, num_attention_heads=4, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=24, q_lora_rank=40,
    rope_theta=10000, rope_interleave=True, rope_scaling=YARN,
    rms_norm_eps=1e-6, intermediate_size=192, moe_intermediate_size=24,
    n_shared_experts=1, n_routed_experts=16, num_experts_per_tok=3,
    first_k_dense_replace=1, moe_layer_freq=1, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.0, scoring_func='sigmoid',
    experts_held=16, expert_offset=0, hc_mult=4, hc_sinkhorn_iters=6,
    hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
    num_nextn_predict_layers=1)
T, d, n, K = 32, 64, 4, 24
MIX = dict(n=n, eps=1e-6, sinkhorn_iters=20, sinkhorn_eps=1e-6,
           clamp_min=-30.0, clamp_max=30.0)


# -- the mixing ops -----------------------------------------------------------

def _mixing(seed, alpha=(1.0, 0.7, 1.3)):
    """A sublayer's mixing parameters under the reference's names."""
    return {'s_hc_weight': _rand(seed, K, n * d, scale=(n * d) ** -0.5),
            's_hc_bias_weight': _rand(seed + 1, 1, K, scale=0.2),
            's_hc_alpha_gamma': jnp.asarray(alpha, jnp.float32),
            's_norm_gamma': jnp.ones((d,), jnp.float32)}


def _sublayer(x, w, b, a, z, attrs=MIX):
    """X' of one sublayer whose update is tanh(RMSNorm(y)) * z, by the
    ops."""
    y, coef, x_pass = op('HyperPre', **attrs)(
        x, w, b, a, jnp.zeros((len(HYPER_STATS),)))[:3]
    normed = op('RMSNorm', eps=1e-6)(y, jnp.ones((d,), jnp.float32))
    return op('HyperPost', n=n)(x_pass, jnp.tanh(normed) * z, coef)


def _reference_sublayer(cfg):
    def want(x, w, b, a, z):
        p = {'s_hc_weight': w, 's_hc_bias_weight': b, 's_hc_alpha_gamma': a,
             's_norm_gamma': jnp.ones((d,), jnp.float32)}
        return ref.sublayer(p, 's', x[0].reshape(-1, n, d), cfg,
                            lambda normed: jnp.tanh(normed) * z[0]) \
            .reshape(x.shape)
    return want


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('length', [32, 21])
def test_stream_mixing_forward_and_gradient(path, length):
    p = _mixing(0)
    x, z = _rand(5, 1, length, n * d), _rand(6, 1, length, d)
    cfg = dict(CFG, hc_sinkhorn_iters=20)
    _both(jax.jit(_sublayer), jax.jit(_reference_sublayer(cfg)), x,
          p['s_hc_weight'], p['s_hc_bias_weight'], p['s_hc_alpha_gamma'], z)


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_collapse_forward_and_gradient(path):
    x = _rand(7, 2, T, n * d)
    w, b = _rand(8, n, n * d, scale=(n * d) ** -0.5), _rand(9, 1, n, scale=.2)
    a = jnp.asarray([0.8], jnp.float32)

    def want(x, w, b, a):
        p = {'c_weight': w, 'c_bias_weight': b, 'c_alpha_gamma': a}
        return jnp.stack([ref.collapse(p, 'c', x[i].reshape(-1, n, d), CFG)
                          for i in range(x.shape[0])])

    _both(op('HyperCollapse', n=n, eps=1e-6), want, x, w, b, a)


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_the_projected_matrix_is_doubly_stochastic_and_clamped(path):
    """20 rounds leave row and column sums within 1e-4 of 1 for arguments
    of moderate spread, and the op's statistic says how far; arguments
    past the clamp are cut at both ends: beyond them nothing changes."""
    x = _rand(10, 1, T, n * d)
    p = _mixing(11, alpha=(1.0, 1.0, 0.4))
    pre = op('HyperPre', **MIX)
    _, coef, _, dev = pre(x, p['s_hc_weight'], p['s_hc_bias_weight'],
                          p['s_hc_alpha_gamma'], jnp.zeros((1,)))
    m = np.asarray(coef)[0, :, n:n + n * n].reshape(T, n, n)
    worst = max(np.abs(m.sum(1) - 1).max(), np.abs(m.sum(2) - 1).max())
    assert worst < 1e-4 and (m > 0).all()
    np.testing.assert_allclose(float(dev[0]), worst, atol=1e-6)
    assert not np.asarray(coef)[0, :, n + n * n:].any()
    # one entry far above the clamp, one far below, through the bias
    far = np.zeros((1, K), np.float32)
    far[0, 2 * n], far[0, 2 * n + 1] = 1e4, -1e4
    at_clamp = np.zeros((1, K), np.float32)
    at_clamp[0, 2 * n], at_clamp[0, 2 * n + 1] = 30.0, -30.0
    zero_w = jnp.zeros((K, n * d))
    got = pre(x, zero_w, jnp.asarray(far), p['s_hc_alpha_gamma'],
              jnp.zeros((1,)))[1]
    want = pre(x, zero_w, jnp.asarray(at_clamp), p['s_hc_alpha_gamma'],
               jnp.zeros((1,)))[1]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    inside = at_clamp.copy()
    inside[0, 2 * n] = 29.0
    other = pre(x, zero_w, jnp.asarray(inside), p['s_hc_alpha_gamma'],
                jnp.zeros((1,)))[1]
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 0


# -- attention: a scale of its own, queries from a latent --------------------------------

@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_latent_attention_takes_a_scale(path):
    H, Dn, Dr = 4, 32, 16
    operands = latent._latent_operands(37)
    scale = (Dn + Dr) ** -0.5 * 2.005
    plain = op('LatentAttention', num_heads=H)
    # a factor on the scores is a factor on the queries
    _both(op('LatentAttention', num_heads=H, scale=scale),
          lambda qn, qr, kn, kr, v: plain(qn * 2.005, qr * 2.005, kn, kr, v),
          *operands)
    got = op('LatentAttention', num_heads=H, scale=scale)(*operands)
    assert np.abs(np.asarray(got - plain(*operands))).max() > 1e-3


def _block_symbol(cfg):
    deepseek = sys.modules['deepseek_v3']
    net = deepseek.Blocks(cfg, 'float32')
    return net.attention(mx.sym.Variable('a', dtype='float32'), 'blk')


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_low_rank_query_block_with_yarn(path):
    """The attention sublayer with ``q_lora_rank`` and YaRN scaling against
    the reference's, output and every gradient."""
    sym = _block_symbol(CFG)
    shapes = dict(zip(sym.list_arguments(),
                      sym.infer_shape(a=(1, T, d))[0]))
    assert shapes['blk_q_a_weight'] == (40, d)
    assert shapes['blk_q_b_weight'] == (4 * 48, 40)
    names = [k for k in sym.list_arguments() if k != 'a']
    p = {k: jnp.ones(shapes[k]) if k.endswith('gamma')
         else _rand(i, *shapes[k], scale=shapes[k][1] ** -0.5)
         for i, k in enumerate(names)}
    a = _rand(50, 1, T, d)
    ex = sym.simple_bind(mx.cpu(), a=(1, T, d))
    for k, v in p.items():
        ex.arg_dict[k][:] = np.asarray(v)
    ex.arg_dict['a'][:] = np.asarray(a)
    out = ex.forward(is_train=True)[0]
    cot = _rand(51, 1, T, d)
    ex.backward(mx.nd.array(np.asarray(cot)))
    tables = ref.rope_tables(CFG, T)
    assert abs(tables[2] - 2.0048) < 1e-3 and float(tables[0][0, 0]) == 1.0
    want, vjp = jax.vjp(lambda a, p: ref.attention_block(
        p, 'blk', a[0], CFG, tables)[None], a, p)
    _close(out.asnumpy(), want, tol=1e-4)
    da, dp = vjp(cot)
    _close(ex.grad_dict['a'].asnumpy(), da, tol=1e-4)
    for k in names:
        _close(ex.grad_dict[k].asnumpy(), dp[k], tol=1e-4)


def _model(cfg, seed=0):
    rng = np.random.RandomState(seed)
    return {k: np.ones(s, np.float32) if k.endswith('gamma') else
            (rng.randn(*s) / np.sqrt(s[1])).astype(np.float32)
            for k, s in ref.param_shapes(cfg).items()}


def _metric():
    m = mx.metric.CompositeEvalMetric()
    for name, out in (('ce', 'softmax_output'), ('acc', 'softmax_output'),
                      ('ce', 'mtp_softmax_output')):
        m.add(mx.metric.create(name, output_names=[out],
                               label_names=['softmax_label']))
    return m


def _plan(metrics, out_shapes, outputs, labels=('softmax_label',)):
    return window_pipeline.plan_metric_or_reason(
        mx.metric.create(metrics) if isinstance(metrics, list) else metrics,
        out_shapes, list(labels), list(outputs))


TWO = [(8, 5), (8, 5)]
NAMES = ['softmax_output', 'mtp_softmax_output']


@pytest.mark.parametrize('case', [
    'one_output_unchanged', 'named_outputs', 'unnamed_second_output',
    'unknown_output', 'unplanned_metric', 'one_output_named'])
def test_plan_metric(case):
    if case == 'one_output_unchanged':
        # the stat fns are _plan_one's own, handed outs and labels whole
        m = mx.metric.create(['ce', 'acc'])
        (children, fns), why = _plan(m, [(8, 5)], NAMES[:1])
        assert why is None and children == m.metrics
        assert [f.__qualname__ for f in fns] == [
            window_pipeline._plan_one(c).__qualname__ for c in m.metrics]
        assert window_pipeline.plan_metric(m, [(8, 5)], ['softmax_label'])
        assert window_pipeline.plan_metric(m, [(8,)], ['softmax_label']) \
            is None
    elif case == 'named_outputs':
        (children, fns), why = _plan(_metric(), TWO, NAMES)
        assert why is None and len(fns) == 3
        pred = jnp.asarray(np.random.RandomState(0).dirichlet(
            np.ones(5), 8), jnp.float32)
        uniform = jnp.full((8, 5), 0.2)
        lab = (jnp.arange(8) % 5).astype(jnp.float32)
        main = fns[0]((pred, uniform), (lab,))
        second = fns[2]((pred, uniform), (lab,))
        np.testing.assert_allclose(float(second[0]),
                                   8 * -np.log(0.2 + 1e-12), rtol=1e-6)
        assert abs(float(main[0]) - float(second[0])) > 1e-3
        assert float(main[1]) == float(second[1]) == 8.0
    elif case == 'unnamed_second_output':
        plan, why = _plan(['ce', 'acc'], TWO, NAMES)
        assert plan is None
        assert 'names no output' in why and 'mtp_softmax_output' in why
    elif case == 'unknown_output':
        m = mx.metric.create('ce', output_names=['other_output'],
                             label_names=['softmax_label'])
        plan, why = _plan(m, TWO, NAMES)
        assert plan is None and 'other_output' in why
    elif case == 'unplanned_metric':
        plan, why = _plan(mx.metric.create('mse'), [(8, 5)], NAMES[:1])
        assert plan is None and 'mse' in why.lower()
    else:
        m = mx.metric.create('acc', output_names=['softmax_output'],
                             label_names=['softmax_label'])
        assert _plan(m, [(8, 5)], NAMES[:1])[1] is None


# -- the other decoder configurations are left as they were ---------------------------------

# sha256 of the lowered text of one training step of the deepseek_v3
# builder's symbol at test_latent_ops.CFG's sizes, on the CPU, on each path,
# taken under pytest on the commit before this family came (e793ae8): the
# builder was rearranged (``Blocks``) and ``LatentAttention`` took an
# attribute, and Kanana's step is to lower as it did. Taken again on the
# tree of PR 41, which changed what a mirrored stage keeps (the names in the
# text and the checkpoint's policy; loss and gradients bit-equal to a bare
# checkpoint's, test_latent_ops.py), and on that of PR 43, which changed
# the expert layer's backward pass by intent, and on that of PR 46, which
# changed the way back from the sorted rows to the tokens by intent
# (test_latent_ops.py says how, and why 'kernel' was taken again on those of
# PR 47 and PR 48). Both paths again on the tree of PR 51: the dense layer's
# MLP and the four layers' shared experts each save their two hidden products
# (ten values more) and make them once (ten products fewer in the backward
# text; test_latent_ops.py).
KANANA_TEXT = {
    'plain':
    '4ff2e0bb07f5038f028439732c902db8d4c6b034fd6e9d09132b470f61872521',
    'kernel':
    '344a2fcc8ac9ebe3aa6d16f77115ad7a0202338aa0de95800a23a6aa539dcf36'}


def kanana_step_digest():
    step, wrt = _training_step(latent.builder.get_symbol(dict(latent.CFG)),
                               **LM_IN)
    text = jax.jit(step).lower(wrt).as_text()
    text = re.sub(r'(@\w+?)_\d+\b', r'\1', text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_kanana_lowers_to_the_text_it_had(path):
    assert kanana_step_digest() == KANANA_TEXT[path]


# -- one backward kernel an attention layer, in every decoder -----------------------------------

# builder, configuration, kernel name -> attention layers (Xing4.0's
# prediction module is one more block)
DECODERS = {
    'laguna_full': (lambda: (cases.builder, cases.CFG), 'attention_full', 2),
    'laguna_window': (lambda: (cases.builder, cases.CFG),
                      'attention_window', 3),
    'kanana': (lambda: (latent.builder, latent.CFG), 'attention_latent', 5),
    'xing4': (lambda: (builder, CFG), 'attention_latent', 3),
}


@pytest.mark.parametrize('path', ['kernel'], indirect=True)
@pytest.mark.parametrize('decoder', sorted(DECODERS))
def test_a_training_step_holds_one_backward_kernel_an_attention_layer(
        path, decoder):
    """The kernel-path training step of each decoder builder: one
    ``*_fwd`` and one ``*_bwd`` kernel an attention layer (each traced in
    its compiled and its interpreted form), no ``_dq`` and no ``_dkv``."""
    found, name, layers = DECODERS[decoder]
    build, cfg = found()
    step, wrt = _training_step(build.get_symbol(dict(cfg)), **LM_IN)
    calls = cases._kernel_calls(str(jax.make_jaxpr(step)(wrt)), name)
    assert calls == {'fwd': 2 * layers, 'bwd': 2 * layers, 'dq': 0, 'dkv': 0}


# -- the benchmark's own files for this family ------------------------------------------------

FLOPS_CASES = ['test_required_flops_of_the_cut_model',
               'test_mixing_bytes_by_hand', 'test_small_config_by_hand']


@pytest.mark.parametrize('case', FLOPS_CASES)
def test_flops_hyper_against_a_count_by_hand(case):
    """The cases of ``benchmark/tests/test_flops_hyper.py``, which the
    tier-1 run does not collect."""
    found = _load('benchmark/tests/test_flops_hyper.py', 'flops_hyper_cases')
    assert sorted(k for k in dir(found) if k.startswith('test_')) \
        == sorted(FLOPS_CASES)
    getattr(found, case)()


def test_the_driver_binds_what_the_configuration_names():
    """``fit_tokens_heads``: the reference of the configuration's
    ``reference`` key where the comparison's programs look it up, its
    loss-and-gradient program at the masters equal to that at their
    roundings; the metrics of the ``eval_metric`` key, each on its output;
    the kernel groups by instruction name; and the reference's parameters
    are the builder's at the published widths."""
    from benchmark import compare_lm_training
    from benchmark.drivers import fit_tokens_heads as driver
    cfg = json.load(open(os.path.join(
        REPO, 'benchmark', 'configs', 'xing4_0_29b_a4b.json')))
    was = compare_lm_training.laguna
    try:
        bound = driver.bind(cfg)
        assert compare_lm_training.laguna is bound
        assert bound.param_shapes(CFG) == ref.param_shapes(CFG)
    finally:
        compare_lm_training.laguna = was
    small = dict(CFG, experts_held=4)
    masters = {k: jnp.asarray(v + 1e-3 * np.abs(v)) for k, v in
               _model(small).items()}       # not bfloat16 values
    tok = np.random.RandomState(0).randint(0, 96, (1, T))
    at_masters = ref.loss_and_grad(masters, tok, tok, small,
                                   at_masters=True)
    rounded = ref.loss_and_grad(ref.working_weights(masters), tok, tok,
                                small)
    plain = ref.loss_and_grad(masters, tok, tok, small)
    assert float(at_masters[0]) == float(rounded[0]) != float(plain[0])
    assert float(at_masters[3]) == float(rounded[3])
    for k in masters:
        _close(at_masters[2][k], rounded[2][k], tol=1e-6)
    metric, main_at, second_at = driver.make_metric(mx, cfg)
    assert (main_at, second_at) == (0, 2)
    assert [(type(m), m.output_names) for m in metric.metrics] == [
        (metric_mod.CrossEntropy, ['softmax_output']),
        (metric_mod.Accuracy, ['softmax_output']),
        (metric_mod.CrossEntropy, ['mtp_softmax_output'])]
    table = {'hyper_pre_fwd.3 bf16[4096,3584]': 1.0,
             'hyper_pre_bwd.9 bf16[4096,14336]': 2.0,
             'hyper_post_fwd.1 bf16[4096,14336]': 4.0,
             'fusion.7 bf16[4096,14336] kLoop': 8.0,    # reads a kernel's
             'attention_latent_dq.2 bf16[1,4096,4096]': 16.0,
             'moe_expert_matmul_dw.5 f32[8,3584,1024]': 32.0}
    assert driver.kernel_seconds(table, 99.0) == {
        'hyper_pre': 3.0, 'hyper_post': 4.0, 'attention_latent': 16.0,
        'moe_expert': 32.0, 'busy': 99.0}
    sym = _load(cfg['builder']['file'], 'xing4_0_symbol_published') \
        .get_symbol(config=cfg, **cfg['builder']['kwargs'])
    args, outs, _ = sym.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    shapes = dict(zip(sym.list_arguments(), args))
    want = ref.param_shapes(cfg)
    assert set(shapes) - {'data', 'softmax_label'} == set(want)
    assert all(tuple(shapes[k]) == tuple(s) for k, s in want.items())
    total = sum(int(np.prod(s)) for s in want.values())
    assert abs(total / 1e6 - 913.5) < 0.2
    assert outs == [(4096, 16384)] * 2
