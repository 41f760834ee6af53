"""What kanana-2-30b-a3b (``deepseek_v3`` family) added to the decoder ops,
beside ``test_transformer_ops.py`` and at small widths with every ratio of
the published ones kept (4 heads, keys of 32 + 16, values of 32, a latent of
24, 16 experts, top 3, two shared), on the CPU in float32, against the plain
reference ``benchmark/reference/deepseek_v3.py`` (loaded by path):

- ``LatentAttention``: the dense form and the kernels (MXTPU_FORCE_PALLAS=1:
  the Pallas interpreter), output and the gradient of all five operands, at
  lengths that are and are not a multiple of the block;
- interleaved rotary pairs;
- the sigmoid router: a bias that changes the choice leaves the chosen
  experts' weights the bare scores';
- eight shares of two experts, the shared experts counted once, add up to
  the uncut reference's layer;
- the builder's shapes (a low-rank query path and YaRN scaling, which this
  builder refused until ``xing4_0`` came, are ``test_hyper_ops.py``'s), the
  whole model's loss and every gradient, a
  mirrored block that runs ``attention_latent_fwd`` once, three ``fit``
  steps through the fused window;
- ``laguna_s_2_1``'s symbol lowers to the text it had before this family
  came; ``reduce/flops_latent.py`` against a count by hand; the driver
  ``fit_tokens_ref`` binds what the configuration names.
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
from mxnet_tpu import telemetry
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops import registry
from mxnet_tpu.ops.transformer import MOE_STATS, moe_stat_names

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load('benchmark/reference/deepseek_v3.py', 'deepseek_v3_reference')
builder = _load('examples/transformer/symbols/deepseek_v3.py',
                'deepseek_v3_symbol')
# the other decoder's cases: its helpers, its fixture and its builder
cases = _load('tests/unittest/test_transformer_ops.py',
              'transformer_ops_cases')
path, PATHS, LM_IN = cases.path, cases.PATHS, cases.LM_IN
_rand, _close, _both, op = cases._rand, cases._close, cases._both, cases.op
_training_step, _kernel_calls = cases._training_step, cases._kernel_calls
_one_backward_kernel = cases._one_backward_kernel
_reload_telemetry = cases._reload_telemetry

CFG = dict(
    model_type='deepseek_v3', hidden_size=64, vocab_size=96,
    num_hidden_layers=5, num_attention_heads=4, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=24, q_lora_rank=None,
    rope_theta=1000000, rope_interleave=True, rope_scaling=None,
    rms_norm_eps=1e-6, intermediate_size=192, moe_intermediate_size=24,
    n_shared_experts=2, n_routed_experts=16, num_experts_per_tok=3,
    first_k_dense_replace=1, moe_layer_freq=1, n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.448, scoring_func='sigmoid',
    experts_held=16, expert_offset=0)
T, d, H, Dn, Dr, Dv = 32, 64, 4, 32, 16, 32


# -- latent attention ----------------------------------------------------------

def _latent_operands(length, seed=0):
    return (_rand(seed, 1, length, H * Dn), _rand(seed + 1, 1, length, H * Dr),
            _rand(seed + 2, 1, length, H * Dn), _rand(seed + 3, 1, length, Dr),
            _rand(seed + 4, 1, length, H * Dv))


def _reference_attention(length):
    def want(qn, qr, kn, kr, v):
        return ref.attention(
            qn[0].reshape(length, H, Dn), qr[0].reshape(length, H, Dr),
            kn[0].reshape(length, H, Dn), kr[0],
            v[0].reshape(length, H, Dv)).reshape(1, length, H * Dv)
    return want


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('length', [32, 37])
def test_latent_attention(path, length):
    """The op on both paths: output and the gradient of all five operands
    (the shared rotary key's is a sum over the heads)."""
    _both(op('LatentAttention', num_heads=H), _reference_attention(length),
          *_latent_operands(length))


@pytest.mark.parametrize('length,block', [
    (37, 8), (37, 16), (50, 16), (64, 16), (33, 32), (40, 8), (64, 512)])
def test_latent_kernels_against_the_dense_mask(length, block):
    """Forward and backward kernels, lengths that are and are not a
    multiple of the block."""
    _both(lambda *o: pk.latent_attention(*o, H, block, block, 'test'),
          _reference_attention(length), *_latent_operands(length, seed=7))


def _plain_latent(qn, qr, kn, kr, v, scale):
    """(out, lse) in plain float32, the [T, T] scores formed whole."""
    B, length, _ = qn.shape
    by_head = lambda x, D: x.reshape(B, length, H, D)  # noqa: E731
    s = (jnp.einsum('bqhd,bshd->bhqs', by_head(qn, Dn), by_head(kn, Dn),
                    precision='highest')
         + jnp.einsum('bqhd,bsd->bhqs', by_head(qr, Dr), kr,
                      precision='highest')) * scale
    seen = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]
    s = jnp.where(seen, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum('bhqs,bshd->bqhd', jnp.exp(s - lse[..., None]),
                     by_head(v, Dv), precision='highest')
    return out.reshape(B, length, H * Dv), lse


# name: length, block, the scores' scale (None: 1 / sqrt(Dn + Dr))
LATENT_BACKWARD_CASES = {
    'whole_blocks': (64, 16, None),
    'padded_length': (37, 16, None),
    'one_block': (40, 512, None),
    'a_scale_of_its_own': (48, 16, 0.29),
    'padded_with_a_scale': (50, 8, 0.29),
}


@pytest.mark.parametrize('case', sorted(LATENT_BACKWARD_CASES))
def test_latent_backward_one_kernel(case, monkeypatch):
    """All five gradients of the one backward kernel against ``jax.vjp`` of
    the plain float32 formulation and against the two kernels."""
    length, block, scale = LATENT_BACKWARD_CASES[case]
    operands = _latent_operands(length, seed=30)
    g_out = _rand(36, 1, length, H * Dv)
    (out, lse), vjp = jax.vjp(lambda *o: _plain_latent(
        *o, scale or (Dn + Dr) ** -0.5), *operands)
    want = vjp((g_out, jnp.zeros_like(lse)))

    def backward(name):
        f = lambda *a: pk.latent_attention_backward(  # noqa: E731
            *a, H, block, block, name, scale)
        args = operands + (out, lse, g_out)
        return cases._backward_kernels(str(jax.make_jaxpr(f)(*args)), name), \
            f(*args)

    which, one = backward('one')
    assert which == 'one'
    cases._two_kernels(monkeypatch)
    which, two = backward('two')
    assert which == 'two'
    for a, b, c in zip(one, want, two):
        _close(a, b)
        _close(a, c)


@pytest.mark.parametrize('length,fits', [
    (4096, True), (8192, True), (16384, True), (65536, False)])
def test_the_shapes_decide_between_one_latent_backward_kernel_and_two(
        length, fits):
    """[1, T, .] bfloat16 at the published widths (32 heads of 128 + 64 /
    128): which kernels run is a function of the shapes alone."""
    spec = lambda width, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (1, length, width), dtype)
    text = str(jax.make_jaxpr(lambda *a: pk.latent_attention_backward(
        *a, 32, name='rule'))(
        spec(4096), spec(2048), spec(4096), spec(64), spec(4096), spec(4096),
        jax.ShapeDtypeStruct((1, 32, length), jnp.float32), spec(4096)))
    assert cases._backward_kernels(text, 'rule') == \
        ('one' if fits else 'two')
    assert (pk._bwd_vmem(length, (128, 64), jnp.bfloat16) is not None) == fits


def test_latent_kernels_are_the_grouped_query_ones_where_both_apply():
    """With the rotary part zero and values as wide as keys the latent
    kernels compute what the grouped-query kernels compute at a scale of
    1 / sqrt(Dn + Dr)."""
    qn, qr, kn, kr, v = _latent_operands(40, seed=20)
    zero_q, zero_k = jnp.zeros_like(qr), jnp.zeros_like(kr)
    got = pk.latent_attention(qn, zero_q, kn, zero_k, v, H, 16, 16, 'test')
    want = pk.blockwise_attention(qn, kn, v, H, H, True, 0,
                                  (Dn + Dr) ** -0.5, 16, 16, 'test')
    _close(got, want)


def test_interleaved_rotary_embedding():
    x = _rand(2, 1, T, 4 * Dr)
    cos, sin = ref.rope_tables(CFG['rope_theta'], Dr, T)
    _both(op('RotaryEmbedding', num_heads=4, base=1e6, interleaved=True),
          lambda x: ref.apply_rope_interleaved(
              x.reshape(T, 4, Dr), cos, sin).reshape(1, T, 4 * Dr), x)
    # one head (the shared rotary key), and not the half-against-half form
    k = _rand(3, 1, T, Dr)
    got = op('RotaryEmbedding', num_heads=1, base=1e6, interleaved=True)(k)
    _close(got, ref.apply_rope_interleaved(k[0][:, None], cos, sin)
           .reshape(1, T, Dr))
    halves = op('RotaryEmbedding', num_heads=1, base=1e6)(k)
    assert np.abs(np.asarray(got - halves)).max() > 0.1


# -- the sigmoid router -------------------------------------------------------------

_MOE_ORDER = ('router', 'experts_w1', 'experts_w3', 'experts_w2',
              'shared_w1', 'shared_w3', 'shared_w2')


def _moe_params(seed, held, hidden=24):
    return {
        'm_router_weight': _rand(seed, 16, d, scale=0.3),
        'm_select_bias_weight': _rand(seed + 7, 1, 16, scale=0.2),
        'm_experts_w1_weight': _rand(seed + 1, held, d, hidden, scale=0.1),
        'm_experts_w3_weight': _rand(seed + 2, held, d, hidden, scale=0.1),
        'm_experts_w2_weight': _rand(seed + 3, held, hidden, d, scale=0.1),
        'm_shared_w1_weight': _rand(seed + 4, 2 * hidden, d, scale=0.1),
        'm_shared_w3_weight': _rand(seed + 5, 2 * hidden, d, scale=0.1),
        'm_shared_w2_weight': _rand(seed + 6, d, 2 * hidden, scale=0.1)}


def _moe_op(held, offset, **attrs):
    fn = op('MoE', **dict(dict(
        num_experts=16, num_experts_per_tok=3, experts_held=held,
        expert_offset=offset, norm_topk_prob=True, routed_scaling=2.448,
        scoring='sigmoid'), **attrs))
    stats = jnp.zeros((len(MOE_STATS),), jnp.float32)
    return lambda x, bias, *w: fn(x, *w, stats, bias)


def _moe_weights(p):
    return [p['m_select_bias_weight']] \
        + [p['m_%s_weight' % n] for n in _MOE_ORDER]


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('held,offset', [(16, 0), (4, 4)])
def test_sigmoid_moe_layer(path, held, offset):
    x, p = _rand(20, T, d), _moe_params(21, held)
    names = ['m_select_bias_weight'] + ['m_%s_weight' % n for n in _MOE_ORDER]

    def want(x, *w):
        return ref.moe_layer(dict(zip(names, w)), 'm', x, CFG, held,
                             offset)[0]

    _both(lambda x, *w: _moe_op(held, offset)(x, *w)[0], want, x,
          *_moe_weights(p))
    # the bias takes no gradient: it enters the choice alone
    g = jax.grad(lambda b: jnp.sum(_moe_op(held, offset)(
        x, b, *_moe_weights(p)[1:])[0] ** 2))(p['m_select_bias_weight'])
    assert not np.asarray(g).any()
    stats = dict(zip(MOE_STATS, np.asarray(
        _moe_op(held, offset)(x, *_moe_weights(p))[1])))
    assert stats['pairs'] == int(ref.moe_layer(p, 'm', x, CFG, held,
                                               offset)[1])
    assert stats['tokens'] == T and stats['dropped'] == 0


def test_a_selection_bias_changes_the_choice_and_not_the_weights():
    """Every token scores expert e at sigmoid(e - 7.5): bare, the choice is
    13, 14, 15. A bias of +20 on experts 0, 1, 2 makes them the choice, and
    their weights stay their own bare scores (tiny), normalised and scaled:
    a softmax router, or a bias that entered the weight, gives others."""
    x = jnp.ones((T, d), jnp.float32)
    router = jnp.asarray(np.repeat((np.arange(16) - 7.5)[:, None] / d, d, 1),
                         jnp.float32)
    bias = np.zeros((1, 16), np.float32)
    bias[0, :3] = 20.0
    p = _moe_params(50, 16)
    p['m_router_weight'], p['m_select_bias_weight'] = router, jnp.asarray(bias)
    idx, w = ref.route(x, router, jnp.asarray(bias), 3, 2.448)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 2]
    scores = 1.0 / (1.0 + np.exp(-(np.arange(3) - 7.5)))
    np.testing.assert_allclose(np.sort(np.asarray(w[0])),
                               scores / scores.sum() * 2.448, rtol=1e-5)
    got = _moe_op(16, 0)(x, *_moe_weights(p))[0]
    _close(got, ref.moe_layer(p, 'm', x, CFG, 16, 0)[0])
    # the three chosen experts' outputs at exactly those weights, plus the
    # shared experts: the layer, written out
    e = [ref.gated_mlp(x, p['m_experts_w1_weight'][i],
                       p['m_experts_w3_weight'][i],
                       p['m_experts_w2_weight'][i]) for i in range(3)]
    shared = ref.gated_mlp(x, p['m_shared_w1_weight'].T,
                           p['m_shared_w3_weight'].T,
                           p['m_shared_w2_weight'].T)
    by_hand = sum(s / scores.sum() * 2.448 * y for s, y in zip(scores, e))
    _close(got, by_hand + shared)
    # without the bias the choice is another, and so is the output
    p['m_select_bias_weight'] = jnp.zeros((1, 16), jnp.float32)
    unbiased = _moe_op(16, 0)(x, *_moe_weights(p))[0]
    assert np.abs(np.asarray(unbiased - got)).max() > 1e-3
    # the softmax router on the same logits weighs them otherwise
    soft = op('MoE', num_experts=16, num_experts_per_tok=3, experts_held=16,
              expert_offset=0, norm_topk_prob=True, routed_scaling=2.448)(
        x, *_moe_weights(p)[1:], jnp.zeros((len(MOE_STATS),)))[0]
    assert np.abs(np.asarray(soft - unbiased)).max() > 1e-3


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_eight_shares_of_the_sigmoid_expert_layer_add_up(path):
    """model-configs guide, section 4: the partial results of all 8 shares
    of 2 experts each, the shared experts counted once, are the uncut
    reference's layer; and their pairs are all T * top_k of them."""
    x, whole = _rand(30, T, d), _moe_params(31, 16)
    shared = op('GatedMLP')(x, *_moe_weights(whole)[5:])
    total, pairs = -7 * shared, 0       # every share adds it: once is owed
    for share in range(8):
        p = dict(whole)
        for n in ('w1', 'w3', 'w2'):
            key = 'm_experts_%s_weight' % n
            p[key] = whole[key][2 * share:2 * share + 2]
        out, stats = _moe_op(2, 2 * share)(x, *_moe_weights(p))
        total = total + out
        pairs += int(stats[0])
    _close(total, ref.moe_layer(whole, 'm', x, CFG, 16, 0)[0])
    assert pairs == T * 3


# -- the whole model ---------------------------------------------------------------

def _model(cfg, seed=0):
    shapes = ref.param_shapes(cfg)
    rng = np.random.RandomState(seed)
    return {n: np.ones(s, np.float32) if n.endswith('gamma') else
            (rng.randn(*s) / np.sqrt(s[1])).astype(np.float32)
            for n, s in shapes.items()}


def test_builder_shapes_are_the_references():
    sym = builder.get_symbol(CFG)
    args, outs, auxs = sym.infer_shape(data=(2, T), softmax_label=(2, T))
    shapes = dict(zip(sym.list_arguments(), args))
    want = ref.param_shapes(CFG)
    assert set(shapes) - {'data', 'softmax_label'} == set(want)
    assert all(tuple(shapes[n]) == tuple(s) for n, s in want.items())
    assert shapes['layer1_moe_select_bias_weight'] == (1, 16)
    assert outs == [(2 * T, CFG['vocab_size'])]
    assert moe_stat_names(sym) == sym.list_auxiliary_states()
    assert auxs == [(len(MOE_STATS),)] * 4
    # every leaf has a rule in the benchmark's seeded initialisation
    assert all(n.endswith(('_weight', '_gamma')) and
               (len(s) >= 2 or n.endswith('_gamma')) for n, s in want.items())


@pytest.mark.parametrize('unbuilt', [
    dict(rope_scaling={'rope_type': 'llama3', 'factor': 8}),
    dict(n_group=8, topk_group=4),
    dict(rope_scaling={'type': 'linear', 'factor': 40}),
    dict(scoring_func='softmax')], ids=lambda v: sorted(v)[0])
def test_builder_refuses_what_it_does_not_build(unbuilt):
    with pytest.raises(ValueError, match='deepseek_v3'):
        builder.get_symbol(dict(CFG, **unbuilt))


@pytest.mark.parametrize('remat', [True, False])
def test_model_forward_and_gradient(remat):
    cfg = dict(CFG, experts_held=8, expert_offset=4)
    sym = builder.get_symbol(cfg, remat=remat)
    # (seed 0 has a token whose third and fourth choice lie 7e-7 apart in
    # one layer: program and reference then choose differently)
    p = _model(cfg, seed=1)
    rng = np.random.RandomState(1)
    tok, lab = rng.randint(0, 96, (2, T)), rng.randint(0, 96, (2, T))
    ex = sym.simple_bind(mx.cpu(), data=(2, T), softmax_label=(2, T))
    for n, v in p.items():
        ex.arg_dict[n][:] = v
    ex.arg_dict['data'][:] = tok.astype(np.float32)
    ex.arg_dict['softmax_label'][:] = lab.astype(np.float32)
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()
    loss = -np.log(out[np.arange(2 * T), lab.reshape(-1)]).mean()
    want, pairs, g = ref.loss_and_grad(
        {k: jnp.asarray(v) for k, v in p.items()}, tok, lab, cfg)
    assert abs(loss - float(want)) < 1e-5
    for n in p:
        _close(ex.grad_dict[n].asnumpy(), g[n], tol=1e-4)
    assert not any(ex.grad_dict['layer%d_moe_select_bias_weight' % i]
                   .asnumpy().any() for i in range(1, 5))
    got = [int(ex.aux_dict[n].asnumpy()[0])
           for n in sym.list_auxiliary_states()]
    assert got == [int(v) for v in pairs]


# -- what a mirrored block keeps -------------------------------------------------------

def _named_bytes(layers, sparse, held):
    """The arrays that the ops of `layers` blocks, `sparse` of them with an
    expert layer, name for a mirrored stage, as (count, bytes): float32."""
    rows, k = 2 * T, CFG['num_experts_per_tok']
    block = [rows * H * Dv, 2 * H * T,                  # out, lse
             rows * H * Dn, rows * H * Dr, rows * Dr,   # queries, rotary key
             rows * (CFG['kv_lora_rank'] + Dr), rows * d]   # kv_a, attn_o
    R = -(-(rows * k + held * pk.GROUP_TILE) // pk.GROUP_TILE) \
        * pk.GROUP_TILE
    R += -R % mx.ops.transformer._pass_rows(R, rows, k, held, 16)
    # the choice, its scores, the weights, dest; row_pair, tile_group, n_tiles
    moe = [rows * k] * 4 + [R, R // pk.GROUP_TILE, 1]
    # a gated MLP's two hidden products: the shared experts', the dense one's
    moe += [rows * CFG['n_shared_experts'] * CFG['moe_intermediate_size']] * 2
    dense = [rows * CFG['intermediate_size']] * 2
    return (layers * len(block) + sparse * len(moe)
            + (layers - sparse) * len(dense),
            4 * (layers * sum(block) + sparse * sum(moe)
                 + (layers - sparse) * sum(dense)))


@pytest.mark.parametrize('path', ['kernel'], indirect=True)
def test_a_mirrored_block_runs_the_latent_forward_kernel_once(
        path, monkeypatch):
    """In the gradient of the mirrored blocks ``attention_latent_fwd`` is
    there as often as the backward kernel, once a block; under a bare
    checkpoint twice. ``executor.mirror_kept`` counts what the ops named,
    each array once, and their bytes follow from the shapes."""
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', os.devnull)
    _reload_telemetry()
    try:
        step, wrt = _training_step(builder.get_symbol(CFG), **LM_IN)
        calls = _kernel_calls(str(jax.make_jaxpr(step)(wrt)),
                              'attention_latent')
        gauges = telemetry.snapshot()['gauges']
    finally:
        monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
        _reload_telemetry()
    layers = CFG['num_hidden_layers']
    _one_backward_kernel(calls)
    count, size = _named_bytes(layers, layers - 1, 16)
    assert gauges['executor.mirror_kept'] == count
    assert gauges['executor.mirror_kept_bytes'] == size
    monkeypatch.setattr(registry, 'mirrored',
                        lambda f, kept: jax.checkpoint(f))
    step, wrt = _training_step(builder.get_symbol(CFG), **LM_IN)
    bare = _kernel_calls(str(jax.make_jaxpr(step)(wrt)), 'attention_latent')
    assert bare['fwd'] == 2 * calls['fwd'] and bare['bwd'] == calls['bwd']


def _sparse_block(**more):
    return builder.get_symbol(dict(
        CFG, num_hidden_layers=1, first_k_dense_replace=0, experts_held=4,
        **more))


@pytest.mark.parametrize('path', ['kernel'], indirect=True)
@pytest.mark.parametrize('q_lora_rank', [None, 48])
def test_the_second_forward_leaves_out_what_the_latent_block_named(
        path, q_lora_rank, monkeypatch):
    """The queries (their projection, the per-head split and the rotary
    turn behind them), the rotary key head, the two down-projections and
    the output projection are kept; ``k_nope`` and ``value`` are not: the
    second forward expands them from the latent again (``attn_kv_b``), and
    nothing else of the attention sublayer multiplies. The sigmoid
    router's top-k and gather and the plan run once."""
    step, wrt = _training_step(_sparse_block(q_lora_rank=q_lora_rank),
                               **LM_IN)
    text = str(jax.make_jaxpr(step)(wrt))
    names = set(re.findall(r'name\[name=(attention_latent_\w+)\]', text))
    assert names == {'attention_latent_' + n for n in (
        'q_nope', 'q_rope', 'k_rope', 'out', 'lse')}
    again = cases._computed_again
    assert again(step, wrt, 'dot_general', 'attn_q', 'attn_q_a', 'attn_q_b',
                 'attn_kv_a', 'attn_kv_b', 'attn_o') == {'layer0_attn_kv_b'}
    for prim in ('top_k', 'gather', 'cumsum', 'scatter', 'concatenate'):
        assert not again(step, wrt, prim), prim
    cases._bare_checkpoint(monkeypatch)
    step, wrt = _training_step(_sparse_block(q_lora_rank=q_lora_rank),
                               **LM_IN)
    assert len(again(step, wrt, 'dot_general', 'attn_q', 'attn_q_a',
                     'attn_q_b', 'attn_kv_a', 'attn_kv_b', 'attn_o')) \
        == (5 if q_lora_rank else 4)
    for prim in ('top_k', 'gather', 'cumsum', 'scatter'):
        assert again(step, wrt, prim), prim


@pytest.mark.parametrize('path', ['kernel'], indirect=True)
def test_a_mirrored_latent_blocks_gradients_are_the_bare_checkpoints(
        path, monkeypatch):
    """The kept values are the ones a recomputation makes: loss and every
    gradient of a block with the low-rank query path and the expert layer
    bit-equal to a bare ``jax.checkpoint`` of the same block."""
    sym = _sparse_block(q_lora_rank=48)
    step, wrt = _training_step(sym, **LM_IN)
    outs, grads = jax.jit(step)(wrt)
    cases._bare_checkpoint(monkeypatch)
    step, wrt = _training_step(sym, **LM_IN)
    for a, b in zip(outs + grads, sum(jax.jit(step)(wrt), ())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the selection bias alone takes no gradient
    assert sum(np.abs(np.asarray(g)).max() > 0 for g in grads) \
        == len(grads) - 1


# -- Module.fit ---------------------------------------------------------------------------

def test_fit_takes_the_fused_window_and_follows_the_reference(monkeypatch):
    steps, lr = 3, 0.05
    cfg = dict(CFG, experts_held=4)
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', str(steps))
    sym = builder.get_symbol(cfg)
    p = _model(cfg, seed=3)
    rng = np.random.RandomState(4)
    toks = rng.randint(0, 96, (steps, T + 1))
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
    assert mod.__dict__['_fused_fit_cache'][1].window == steps
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
    # the selection bias is as it was given: no gradient, no decay
    for i in range(1, 5):
        n = 'layer%d_moe_select_bias_weight' % i
        np.testing.assert_array_equal(got[n].asnumpy(), p[n])


# -- the other decoder configuration is left as it was -------------------------------------

# sha256 of the lowered text of one training step of the Laguna builder's
# symbol at test_transformer_ops.CFG's sizes, on the CPU, on each path: what
# a change to the other family's ops must leave as it is. Taken on the tree
# of PR 43, which changed the expert layer's backward pass in every decoder
# by intent (the weight gradients' kernel adds into the array it is given;
# the three dw bit-equal to the select-and-add they replace in a one-pass
# step, test_transformer_ops.py), and again on that of PR 46, which changed
# the way back from the sorted rows to the tokens by intent (a kernel adds
# each row into its token's sum where k gathers a direction walked the whole
# sequence; output, dx and d_pairs are the float32 reference's to 1e-5,
# test_transformer_ops.py). 'kernel' alone was taken again on the tree of PR
# 47, which changed RMSNorm's two kernels by intent: the backward rule of rows
# under 2048 elements is the kernel fused_rmsnorm_bwd (the gradients are jax.vjp's of the plain formula
# to float32 rounding, test_pallas.py) and both take their rows by bytes;
# 'plain', the path the CPU takes, did not move. 'kernel' alone again on the
# tree of PR 48, which made the causal and windowed walk one instance of the
# record every blockwise attention kernel reads (pallas_kernels.Walk): the two
# lowered texts differ in scalar int32 operations alone (a kernel body takes
# the held block index, one `minimum`, and computes `lo + step` once; an index
# map computes the walk's unused `live`, one `compare`), no vector operation
# among them (the count by operation is in CHANGES.md, PR 48); Kanana's,
# Xing4.0's and LFM2's 'kernel' digests moved with it, SDAR's did not.
# Both paths again on the tree of PR 51, which changed what a mirrored stage
# keeps by intent: a gated MLP (the dense layer's and each shared expert's)
# names its two hidden products, so each of the five saves two float32 values
# more and its backward text has two products fewer (10 `dot_general` fewer
# in all, and one `reduce_precision` of float32 to float32 more an MLP, jax's
# own mark on a saved value that the forward pass goes on to use; the count
# by operation is in CHANGES.md, PR 51). Kanana's, Xing4.0's and LFM2's moved
# with it on both paths; SDAR's, which has no gated MLP, moved on neither.
# Before that they were PR 41's (what a mirrored stage keeps), PR 33's, and
# those of the commit before this family came (faf5f29). The text is this jax's; a change of jax (or of Laguna's
# own ops) needs them taken again.
LAGUNA_TEXT = {
    'plain':
    '9b3873d08859c8bddb61398f13e9e19cc39aa39cedcb498fb5bc0c3c347b147a',
    'kernel':
    'ba43c05f1240078ddccc4b4de6eedcd2cf42878b4a7a1e45cbde8ed4096e10f6'}


def laguna_step_digest():
    step, wrt = _training_step(cases.builder.get_symbol(dict(cases.CFG)),
                               **LM_IN)
    text = jax.jit(step).lower(wrt).as_text()
    # the counter behind the private functions' names is the process's
    text = re.sub(r'(@\w+?)_\d+\b', r'\1', text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_laguna_lowers_to_the_text_it_had(path):
    assert laguna_step_digest() == LAGUNA_TEXT[path]


# -- the benchmark's own files for this family ----------------------------------------------

def _benchmark():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)


FLOPS_CASES = ['test_required_flops_of_the_cut_model',
               'test_attention_work_by_hand', 'test_expert_work_is_shared',
               'test_small_config_by_hand']


@pytest.mark.parametrize('case', FLOPS_CASES)
def test_flops_latent_against_a_count_by_hand(case):
    """The cases of ``benchmark/tests/test_flops_latent.py``, which the
    tier-1 run does not collect."""
    _benchmark()
    cases = _load('benchmark/tests/test_flops_latent.py',
                  'flops_latent_cases')
    assert sorted(n for n in dir(cases) if n.startswith('test_')) \
        == sorted(FLOPS_CASES)
    getattr(cases, case)()


def test_the_driver_binds_what_the_configuration_names():
    """``fit_tokens_ref``: the reference of the configuration's
    ``reference`` key where ``fit_tokens`` and the comparison look it up,
    the latent kernels' group first, and the reference's parameters are
    the builder's at the published widths."""
    _benchmark()
    from benchmark import compare_lm_training
    from benchmark.drivers import fit_tokens, fit_tokens_ref
    from benchmark.reduce import kernel_times
    cfg = json.load(open(os.path.join(
        REPO, 'benchmark', 'configs', 'kanana_2_30b_a3b.json')))
    was = (fit_tokens.laguna, compare_lm_training.laguna,
           kernel_times.GROUPS)
    try:
        bound = fit_tokens_ref.bind(cfg)
        assert fit_tokens.laguna is bound
        assert compare_lm_training.laguna is bound
        assert bound.__file__.endswith('reference/deepseek_v3.py')
        assert kernel_times.GROUPS[0][0] == 'attention_latent'
        assert kernel_times.GROUPS[1:] == was[2]
        fit_tokens_ref.bind(cfg)        # a second call adds no second group
        assert kernel_times.GROUPS[1:] == was[2]
        with pytest.raises(ValueError, match='lacks'):
            fit_tokens_ref.bind(dict(cfg, reference='convnets:resnet'))
        sym = fit_tokens.build_symbol(cfg)
        params, aux, shapes = fit_tokens.symbol_shapes(sym, 1, 64)
        want = bound.param_shapes(cfg)
        assert {n: tuple(shapes[n]) for n in params} \
            == {n: tuple(s) for n, s in want.items()}
        assert sum(int(np.prod(s)) for s in want.values()) == 575955968
        assert len(aux) == 4
    finally:
        (fit_tokens.laguna, compare_lm_training.laguna,
         kernel_times.GROUPS) = was
