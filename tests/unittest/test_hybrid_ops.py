"""The ops that a conv-attention hybrid with sparse experts needs
(``examples/transformer/symbols/lfm2_moe.py``), on the CPU in float32,
against the plain reference ``benchmark/reference/lfm2_moe.py``, on both
dispatch paths (the jnp form and the Pallas kernels, interpreted):

- ``GatedShortConv``: the output and all four gradients (the three thirds
  of its operand, the taps), with a sequence shorter than the taps and
  with a row-block boundary inside the sequence;
- attention at a head size of 64 against the dense form, forward and
  backward, in the projections' layout and in the by-head layout the
  compiled kernels take such heads in, with one backward kernel and two;
- ``MoE`` without a shared expert (``shared_hidden`` 0): three inputs
  fewer, the statistics where they were, the published 1e-6 on the sum of
  the chosen scores; four shares of 16 experts add up to the uncut layer;
- what a mirrored conv block computes again;
- the other decoders' lowered steps are the text they had.
"""
import hashlib
import importlib.util
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.transformer import (MOE_STATS, _dense_attention,
                                       moe_stat_names)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(rel, name):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *rel.split('/')))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load('benchmark/reference/lfm2_moe.py', 'lfm2_moe_reference')
builder = _load('examples/transformer/symbols/lfm2_moe.py', 'lfm2_moe_symbol')
cases = _load('tests/unittest/test_transformer_ops.py',
              'transformer_ops_cases')
hyper = _load('tests/unittest/test_hyper_ops.py', 'hyper_ops_cases')
path, PATHS, LM_IN = cases.path, cases.PATHS, cases.LM_IN
_rand, _close, _both, op = cases._rand, cases._close, cases._both, cases.op
_training_step = cases._training_step

CFG = dict(
    model_type='lfm2_moe', hidden_size=64, vocab_size=96,
    num_hidden_layers=5, num_dense_layers=1,
    layer_types=['conv', 'full_attention', 'conv', 'conv', 'conv'],
    num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
    conv_bias=False, norm_eps=1e-5, intermediate_size=160,
    moe_intermediate_size=24, num_experts=16, num_experts_per_tok=3,
    norm_topk_prob=True, use_expert_bias=True, routed_scaling_factor=1,
    rope_parameters={'rope_theta': 1000000, 'rope_type': 'default'},
    experts_held=16, expert_offset=0)
T, d = 32, 64


# -- the gated short convolution --------------------------------------------------------------

def _conv_want(bcx, w):
    C = w.shape[0]
    return jnp.stack([
        x[:, C:2 * C] * ref.short_conv(x[:, :C] * x[:, 2 * C:], w)
        for x in bcx])


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('length,taps', [(2, 3), (T, 3), (300, 3), (19, 4)],
                         ids=['shorter_than_the_taps', 'one_block',
                              'a_block_boundary_inside', 'four_taps'])
def test_gated_short_conv(path, length, taps):
    """300 rows are a block of 256 and one of 44 padded: rows 254-257 read
    across the boundary in both directions."""
    C = 16
    bcx, w = _rand(0, 2, length, 3 * C), _rand(1, C, taps)
    _both(op('GatedShortConv', kernel=taps), _conv_want, bcx, w)


def test_gated_short_conv_refuses_other_taps():
    with pytest.raises(ValueError, match='GatedShortConv'):
        op('GatedShortConv', kernel=3)(_rand(0, 1, 8, 48), _rand(1, 16, 4))
    with pytest.raises(ValueError, match='short_conv'):
        pk.short_conv_forward(_rand(0, 1, 8, 40), _rand(1, 16, 3))


def test_gated_short_conv_computes_in_float32_and_gives_the_operands_dtype():
    bcx, w = _rand(2, 1, 40, 48), _rand(3, 16, 3)
    want = _conv_want(bcx.astype(jnp.bfloat16).astype(jnp.float32),
                      w.astype(jnp.bfloat16).astype(jnp.float32))
    for force in ('0', '1'):
        os.environ['MXTPU_FORCE_PALLAS'] = force
        try:
            got = op('GatedShortConv', kernel=3)(bcx.astype(jnp.bfloat16),
                                                 w.astype(jnp.bfloat16))
        finally:
            os.environ.pop('MXTPU_FORCE_PALLAS')
        assert got.dtype == jnp.bfloat16
        # one rounding of the result, none inside
        _close(got.astype(jnp.float32), want, tol=8e-3)


# -- attention at a head size of 64 -----------------------------------------------------------

def _attention_operands(length, heads=8, kv_heads=2, D=64):
    return (_rand(4, 2, length, heads * D), _rand(5, 2, length, kv_heads * D),
            _rand(6, 2, length, kv_heads * D))


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_attention_at_head_size_64(path):
    """Group 4, as the published model's 32 heads on 8."""
    fn = op('GroupedQueryAttention', num_heads=8, num_kv_heads=2)
    _both(fn, lambda q, k, v: _dense_attention(q, k, v, 8, 2, 0),
          *_attention_operands(40))


@pytest.mark.parametrize('kernels', ['one_backward_kernel', 'two'])
def test_narrow_heads_cross_the_compiled_kernels_by_head(kernels,
                                                         monkeypatch):
    """The layout the chip's compiler needs for a head narrower than 128
    lanes, [B, H, T, D], through the interpreter: the same kernels, grids
    and numbers. (Whether it lowers for the chip is
    test_tpu_compile.py's.)"""
    monkeypatch.setattr(pk, '_BY_HEAD_INTERPRETED', True)
    if kernels == 'two':
        monkeypatch.setattr(pk, '_BWD_RESIDENT_BYTES', 1)
    assert pk._crosses_by_head(64, True) and pk._crosses_by_head(64, False)
    assert not pk._crosses_by_head(128, False)

    def fused(q, k, v):
        return pk.blockwise_attention(q, k, v, 8, 2, True, 0, None, 16, 16,
                                      'attention_full')

    q, k, v = _attention_operands(48)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: fused(*a).sum(), (0, 1, 2)))(
        q, k, v))
    calls = cases._kernel_calls(text, 'attention_full')
    if kernels == 'two':
        assert calls['fwd'] == calls['dq'] == calls['dkv'] > 0 == calls['bwd']
    else:
        cases._one_backward_kernel(calls)
    _both(fused, lambda q, k, v: _dense_attention(q, k, v, 8, 2, 0), q, k, v)


def test_wide_heads_keep_the_projections_layout(monkeypatch):
    """128 columns are a block as they lie: no transpose is made around
    the kernels, whoever compiles them."""
    monkeypatch.setattr(pk, '_BY_HEAD_INTERPRETED', True)
    q, k, v = _attention_operands(16, 2, 1, 128)
    text = str(jax.make_jaxpr(lambda *a: pk.attention_forward(
        *a, 2, 1, True, 0, None, 16, 16))(q, k, v))
    assert 'transpose' not in text


# -- the expert layer without a shared expert -------------------------------------------------

def _moe_params(seed, held, hidden=24):
    return {
        'm_router_weight': _rand(seed, 16, d, scale=0.3),
        'm_select_bias_weight': _rand(seed + 7, 1, 16, scale=0.05),
        'm_experts_w1_weight': _rand(seed + 1, held, d, hidden, scale=0.1),
        'm_experts_w3_weight': _rand(seed + 2, held, d, hidden, scale=0.1),
        'm_experts_w2_weight': _rand(seed + 3, held, hidden, d, scale=0.1)}


_MOE_ORDER = ('router', 'experts_w1', 'experts_w3', 'experts_w2')
_MOE_ATTRS = dict(num_experts=16, num_experts_per_tok=3, norm_topk_prob=True,
                  routed_scaling=1.0, scoring='sigmoid', shared_hidden=0,
                  norm_eps=ref.NORM_EPS)


def _moe_op(held, offset):
    fn = op('MoE', experts_held=held, expert_offset=offset, **_MOE_ATTRS)
    stats = jnp.zeros((len(MOE_STATS),), jnp.float32)
    return lambda x, bias, *w: fn(x, *w, stats, bias)


def _moe_weights(p):
    return [p['m_select_bias_weight']] \
        + [p['m_%s_weight' % n] for n in _MOE_ORDER]


@pytest.mark.parametrize('path,held,offset',
                         [('plain', 16, 0), ('kernel', 4, 8)],
                         indirect=['path'])
def test_moe_layer_without_a_shared_expert(path, held, offset):
    x, p = _rand(20, T, d), _moe_params(21, held)
    names = ['m_select_bias_weight'] + ['m_%s_weight' % n for n in _MOE_ORDER]

    def want(x, *w):
        return ref.moe_layer(dict(zip(names, w)), 'm', x, CFG, held,
                             offset)[0]

    layer = _moe_op(held, offset)
    _both(lambda x, *w: layer(x, *w)[0], want, x, *_moe_weights(p))
    stats = layer(x, *_moe_weights(p))[1]
    idx = ref.route(x, p['m_router_weight'], p['m_select_bias_weight'], 3,
                    1.0)[0]
    here = int(((idx >= offset) & (idx < offset + held)).sum())
    assert [float(v) for v in stats[:3]] == [here, T, 0.0]


def test_the_published_epsilon_is_an_attribute():
    """1e-6 on the sum of the chosen scores moves a weight by a millionth
    of itself; the op's own 1e-20 does not: told apart in float32 where the
    chosen scores are small."""
    x, p = _rand(22, T, d), _moe_params(23, 16)
    p['m_router_weight'] = p['m_router_weight'] - 2.0   # scores near 1e-7
    x = jnp.abs(x)
    ours = _moe_op(16, 0)(x, *_moe_weights(p))[0]
    theirs = op('MoE', experts_held=16, expert_offset=0,
                **dict(_MOE_ATTRS, norm_eps=1e-20))(
        x, *_moe_weights(p)[1:], jnp.zeros((len(MOE_STATS),)),
        p['m_select_bias_weight'])[0]
    want = ref.moe_layer(p, 'm', x, CFG, 16, 0)[0]
    _close(ours, want)
    assert np.abs(np.asarray(theirs - want)).max() \
        > 10 * np.abs(np.asarray(ours - want)).max()


def test_a_node_without_a_shared_expert_has_three_inputs_fewer():
    """``shared_hidden`` 0: no ``shared_*`` variables are made, the
    statistics are still the node's auxiliary state and the shapes
    follow."""
    data = mx.sym.Variable('data')
    with_bias = mx.sym.MoE(data=data, name='m', hidden=24, shared_hidden=0,
                           experts_held=4, expert_offset=0, **{
                               k: v for k, v in _MOE_ATTRS.items()
                               if k != 'shared_hidden'})
    assert with_bias.list_arguments() == [
        'data', 'm_router_weight', 'm_experts_w1_weight',
        'm_experts_w3_weight', 'm_experts_w2_weight', 'm_select_bias']
    assert with_bias.list_auxiliary_states() == ['m_stats']
    assert moe_stat_names(with_bias) == ['m_stats']
    args, outs, auxs = with_bias.infer_shape(data=(2, T, d))
    assert dict(zip(with_bias.list_arguments(), args))[
        'm_experts_w2_weight'] == (4, 24, d)
    assert outs == [(2, T, d)] and auxs == [(len(MOE_STATS),)]
    shared = mx.sym.MoE(data=data, name='m', hidden=24, shared_hidden=48,
                        experts_held=4, expert_offset=0, num_experts=16)
    assert 'm_shared_w2_weight' in shared.list_arguments()
    assert shared.list_auxiliary_states() == ['m_stats']
    # the step's statistics are written back where they were
    ex = with_bias.simple_bind(mx.cpu(), data=(1, T, d))
    for k, v in zip(['m_select_bias'] + ['m_%s_weight' % n
                                          for n in _MOE_ORDER],
                    _moe_weights(_moe_params(24, 4))):
        ex.arg_dict[k][:] = np.asarray(v)
    ex.arg_dict['data'][:] = np.asarray(_rand(25, 1, T, d))
    ex.forward(is_train=True)
    ex.backward()
    assert ex.aux_dict['m_stats'].asnumpy()[1] == T


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_four_shares_of_a_sparse_layer_add_up(path):
    """model-configs guide, section 4: the partial results of the four
    shares of a layer (offsets 0, 16, 32, 48 of 64 experts), with nothing
    counted twice since nothing is shared, are the uncut reference
    layer."""
    cfg = dict(CFG, num_experts=64, num_experts_per_tok=4)
    rng = np.random.RandomState(30)
    whole = {
        'm_router_weight': jnp.asarray(rng.randn(64, d) * 0.3, jnp.float32),
        'm_select_bias_weight': jnp.asarray(rng.randn(1, 64) * 0.05,
                                            jnp.float32),
        'm_experts_w1_weight': _rand(31, 64, d, 24, scale=0.1),
        'm_experts_w3_weight': _rand(32, 64, d, 24, scale=0.1),
        'm_experts_w2_weight': _rand(33, 64, 24, d, scale=0.1)}
    x = _rand(34, T, d)
    total, pairs = 0.0, 0
    for offset in (0, 16, 32, 48):
        part = dict(whole)
        for w in ('w1', 'w3', 'w2'):
            key = 'm_experts_%s_weight' % w
            part[key] = whole[key][offset:offset + 16]
        fn = op('MoE', experts_held=16, expert_offset=offset,
                **dict(_MOE_ATTRS, num_experts=64, num_experts_per_tok=4))
        out, stats = fn(x, *_moe_weights(part)[1:],
                        jnp.zeros((len(MOE_STATS),)),
                        part['m_select_bias_weight'])
        total, pairs = total + out, pairs + int(stats[0])
    _close(total, ref.moe_layer(whole, 'm', x, cfg, 64, 0)[0])
    assert pairs == T * 4


# -- what a mirrored conv block keeps ---------------------------------------------------------

def _conv_block(**more):
    return builder.get_symbol(dict(
        CFG, num_hidden_layers=1, layer_types=['conv'], num_dense_layers=1,
        **more))


@pytest.mark.parametrize('path', ['kernel'], indirect=True)
def test_the_second_forward_of_a_conv_block(path, monkeypatch):
    """By the rules of ``ops/registry.py``: the output projection keeps
    its result (it contracts nothing and expands nothing); the input
    projection expands and is computed again, and the operator's forward
    kernel behind it with it; the op names nothing."""
    step, wrt = _training_step(_conv_block(), **LM_IN)
    text = str(jax.make_jaxpr(step)(wrt))
    assert not re.findall(r'name\[name=(short_conv\w*)\]', text)
    again = cases._computed_again
    assert again(step, wrt, 'dot_general', 'conv_in', 'conv_out') \
        == {'layer0_conv_in'}
    assert text.count('name=short_conv_fwd') == 2 \
        and text.count('name=short_conv_bwd') == 1
    outs, grads = jax.jit(step)(wrt)
    cases._bare_checkpoint(monkeypatch)
    step, wrt = _training_step(_conv_block(), **LM_IN)
    assert again(step, wrt, 'dot_general', 'conv_in', 'conv_out') \
        == {'layer0_conv_in', 'layer0_conv_out'}
    for a, b in zip(outs + grads, sum(jax.jit(step)(wrt), ())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the other decoder configurations are left as they were ----------------------------------

# sha256 of the lowered text of one training step of the xing4_0 builder's
# symbol at test_hyper_ops.CFG's sizes, on the CPU, on each path, taken
# under pytest on the commit before this family came (5e95e14): ``MoE``'s
# shared expert became optional, the attention kernels took a second layout
# and the registry learnt an optional input's place, and Xing4.0's step is
# to lower as it did. Taken again on the tree of PR 43, which changed the
# expert layer's backward pass by intent, and on that of PR 46, which changed
# the way back from the sorted rows to the tokens by intent (test_latent_ops.py
# says how); 'kernel' again on those of PR 47 and PR 48 (there too). Both paths
# again on the tree of PR 51: the dense layer's MLP and the shared experts of
# the sparse layer and of the MTP module's each save their two hidden products
# for their mirrored stage (six values more) and
# make them once (six products fewer in the backward text). (Laguna's and
# Kanana's digests are in test_latent_ops.py and test_hyper_ops.py and are
# checked there.) The text is this jax's.
XING4_TEXT = {
    'plain':
    '06ac3e9344fe757baf2be06858fab561f348086f205a9ef1f64e8b82b1a654e1',
    'kernel':
    '850170f3a15747ee166f4672bf841caef6bc2e2e1dbe3f8a366206df75422edb'}


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_xing4_lowers_to_the_text_it_had(path):
    step, wrt = _training_step(hyper.builder.get_symbol(dict(hyper.CFG)),
                               **LM_IN)
    text = jax.jit(step).lower(wrt).as_text()
    # the counter behind the private functions' names is the process's
    text = re.sub(r'(@\w+?)_\d+\b', r'\1', text)
    assert hashlib.sha256(text.encode()).hexdigest() == XING4_TEXT[path]
