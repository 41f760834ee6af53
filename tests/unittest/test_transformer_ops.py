"""Decoder-block ops (ops/transformer.py), their kernels and the Laguna
builder against the plain reference (benchmark/reference/laguna.py, loaded
by path: there is one copy), at small widths on the CPU in float32:

- every op, forward and gradient, through the plain form and through the
  kernels (MXTPU_FORCE_PALLAS=1: the Pallas interpreter);
- windowed and full attention by blocks at lengths that are no multiple
  of the block;
- the share test: the parts that four shares of four experts give, the
  shared expert counted once, add up to the uncut layer;
- no pair dropped when every token picks the same experts;
- the sorted buffer walked in as many passes as the rows present need, and
  no activation of the lowered step with the buffer's worst-case length;
- the weight gradients' kernel adds into the array it is given, and the
  step lowered for the chip sums them in place;
- the whole model through ``Module.fit`` takes the fused window and after
  three steps matches the reference's losses and parameter change;
- telemetry off leaves the lowered window unchanged, on yields ``moe.*``.
"""
import functools
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops import registry
from mxnet_tpu.ops.transformer import (MOE_STATS, block_diffusion_mask,
                                       moe_stat_names)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load('benchmark/reference/laguna.py', 'laguna_reference')
builder = _load('examples/transformer/symbols/laguna.py', 'laguna_symbol')

ROPE = {
    'full_attention': {
        'rope_theta': 500000, 'rope_type': 'yarn', 'factor': 128,
        'original_max_position_embeddings': 16, 'beta_slow': 1,
        'beta_fast': 32, 'attention_factor': 1.4852030263919618,
        'partial_rotary_factor': 0.5},
    'sliding_attention': {'rope_type': 'default', 'rope_theta': 10000,
                          'partial_rotary_factor': 1}}
CFG = dict(
    hidden_size=64, head_dim=16, num_key_value_heads=2, vocab_size=96,
    num_hidden_layers=5, num_attention_heads=4,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4],
    layer_types=['full_attention'] + ['sliding_attention'] * 3
    + ['full_attention'],
    mlp_layer_types=['dense'] + ['sparse'] * 4, sliding_window=8,
    rms_norm_eps=1e-6, intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=16,
    num_experts_per_tok=3, experts_held=16, expert_offset=0,
    norm_topk_prob=True, moe_routed_scaling_factor=2.5,
    rope_parameters=ROPE)
T, D, KV, d = 32, 16, 2, 64
PATHS = ['plain', 'kernel']


@pytest.fixture
def path(request, monkeypatch):
    """'plain': the jnp form the CPU takes; 'kernel': the Pallas kernels,
    interpreted."""
    if request.param == 'kernel':
        monkeypatch.setenv('MXTPU_FORCE_PALLAS', '1')
    else:
        monkeypatch.delenv('MXTPU_FORCE_PALLAS', raising=False)
    flags.reload('MXTPU_FORCE_PALLAS')
    yield request.param
    monkeypatch.delenv('MXTPU_FORCE_PALLAS', raising=False)
    flags.reload('MXTPU_FORCE_PALLAS')


def _rand(seed, *shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


def _both(f, g, *args):
    """Outputs and gradients (of a fixed random cotangent) of f and g."""
    (of, vf), (og, vg) = jax.vjp(f, *args), jax.vjp(g, *args)
    _close(of, og)
    w = _rand(99, *og.shape)
    for a, b in zip(vf(w), vg(w)):
        _close(a, b)


def op(name, **attrs):
    fn = registry.get(name).fn
    return lambda *arrays: fn(attrs, *arrays)


# -- the ops against the reference ------------------------------------------

@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_rms_norm(path):
    x, g = _rand(0, 2, T, d), 1.0 + 0.1 * _rand(1, d)
    _both(op('RMSNorm', eps=1e-6), lambda x, g: ref.rms_norm(x, g, 1e-6),
          x, g)


@pytest.mark.parametrize('kind', sorted(ROPE))
def test_rotary_embedding(kind):
    x = _rand(2, 1, T, 6 * D)
    cos, sin = ref.rope_tables(ROPE[kind], D, T)
    attrs = builder._rope_attrs(ROPE[kind], D)
    _both(op('RotaryEmbedding', num_heads=6, **attrs),
          lambda x: ref.apply_rope(x.reshape(T, 6, D), cos, sin)
          .reshape(1, T, 6 * D), x)


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('heads,window', [(4, 0), (6, 8)])
def test_grouped_query_attention(path, heads, window):
    q, k, v = (_rand(3, 1, T, heads * D), _rand(4, 1, T, KV * D),
               _rand(5, 1, T, KV * D))
    gate = _rand(6, 1, T, heads)

    def want(q, k, v, gate):
        o = ref.attention(q[0].reshape(T, heads, D), k[0].reshape(T, KV, D),
                          v[0].reshape(T, KV, D), window)
        return (o * jax.nn.sigmoid(gate[0])[:, :, None]) \
            .reshape(1, T, heads * D)

    _both(op('GroupedQueryAttention', num_heads=heads, num_kv_heads=KV,
             window=window, gated=True), want, q, k, v, gate)


@pytest.mark.parametrize('length,window,block', [
    (37, 8, 8), (37, 0, 16), (50, 8, 16), (64, 0, 16), (33, 16, 32),
    (64, 8, 16), (40, 0, 8)])
def test_blockwise_attention_against_the_dense_mask(length, window, block):
    """Forward and backward kernels, lengths that are and are not a
    multiple of the block."""
    H = 6
    q, k, v = (_rand(7, 1, length, H * D), _rand(8, 1, length, KV * D),
               _rand(9, 1, length, KV * D))
    _both(lambda q, k, v: pk.blockwise_attention(
        q, k, v, H, KV, True, window, None, block, block, 'test'),
        lambda q, k, v: ref.attention(
            q[0].reshape(length, H, D), k[0].reshape(length, KV, D),
            v[0].reshape(length, KV, D), window).reshape(1, length, H * D),
        q, k, v)


@pytest.mark.parametrize('length,window,block', [
    (300, 8, 64), (130, 100, 16), (97, 40, 16), (256, 8, 64)])
def test_reference_attention_over_the_span_is_the_dense_masked_one(
        length, window, block):
    """On a windowed layer the reference multiplies a block of queries
    with the `window + block` keys that end with the block; against every
    key (one block of queries as long as the sequence) it gives the same
    output and gradients."""
    H = 6
    q, k, v, c = (_rand(11, length, H, D), _rand(12, length, KV, D),
                  _rand(13, length, KV, D), _rand(14, length, H, D))
    assert window + block < length      # the span path is the one taken

    def loss(q_block):
        return lambda q, k, v: jnp.sum(
            ref.attention(q, k, v, window, q_block=q_block) * c)

    with jax.default_matmul_precision('highest'):
        got = jax.value_and_grad(loss(block), (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(loss(length), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_windowed_attention_walks_only_its_window():
    """The grid's walking axis is as long as the window needs, not as the
    sequence: blocks outside are never visited."""
    full = pk._walk_of(8192, 8192, 512, 512)[4]
    win = pk._walk_of(8192, 8192, 256, 256, True, 512)[4]
    assert full.key_steps == 16 and win.key_steps == 3 \
        and win.query_steps == 3


# name: Tq, Tk, block_q, block_k, causal, window, block_length
WALKS = {
    'causal': (64, 64, 16, 16, True, 0, 0),
    'window': (128, 128, 16, 16, True, 40, 0),
    'window_off_the_block': (96, 96, 32, 16, True, 24, 0),
    'more_keys_than_queries': (32, 80, 16, 16, True, 0, 0),
    'more_keys_than_queries_window': (32, 80, 8, 16, True, 24, 0),
    'not_causal': (48, 80, 16, 16, False, 0, 0),
    'padded': (37, 37, 16, 16, True, 0, 0),
    'padded_window': (37, 53, 8, 8, True, 8, 0),
    'block_diffusion': (128, 128, 16, 16, True, 0, 4),
    'block_diffusion_long_blocks': (128, 128, 8, 8, True, 0, 32),
    'block_diffusion_padded': (60, 60, 16, 16, True, 0, 5),
}


@pytest.mark.parametrize('case', sorted(WALKS))
def test_a_walk_visits_every_seen_block_pair_once_from_either_side(case):
    """What the index maps and ``pl.when(live)`` of every blockwise kernel
    rely on, whatever the mask (``pk.Walk``): the tiles of ``seen`` are the
    dense mask's; a block pair that holds a seen element is walked, once,
    by ``key_block`` and by ``query_block``; the live steps of a walk come
    first, and past them the held index is the last live block (nothing
    new is fetched)."""
    Tq, Tk, block_q, block_k, causal, window, block_length = WALKS[case]
    blk_q, blk_k, pad_q, pad_k, walk = pk._walk_of(
        Tq, Tk, block_q, block_k, causal, window, block_length)
    assert (walk.nq, walk.nk) == ((Tq + pad_q) // blk_q, (Tk + pad_k) // blk_k)
    if block_length:
        dense = np.asarray(block_diffusion_mask(Tq // 2, block_length))
    else:
        rows = np.arange(Tq)[:, None] + Tk - Tq
        cols = np.arange(Tk)[None, :]
        dense = np.ones((Tq, Tk), bool)
        if causal:
            dense &= cols <= rows
        if window:
            dense &= cols > rows - window
    tiles = [[np.asarray(walk.seen(i, j)) for j in range(walk.nk)]
             for i in range(walk.nq)]
    np.testing.assert_array_equal(np.block(tiles)[:Tq, :Tk], dense)
    np.testing.assert_array_equal(
        np.asarray(walk.seen(1, 0, keys_first=True)), tiles[1][0].T)
    seen = {(i, j) for i in range(walk.nq) for j in range(walk.nk)
            if tiles[i][j][:max(0, Tq - i * blk_q)].any()}
    one_run = not (pad_q or pad_k or block_length)
    for block_of, steps, n, pair in (
            (walk.key_block, walk.key_steps, walk.nq, lambda i, j: (i, j)),
            (walk.query_block, walk.query_steps, walk.nk,
             lambda j, i: (i, j))):
        visited, longest = [], 0
        for i in range(n):
            walked = [block_of(i, s) for s in range(steps)]
            live = [int(b) for b, ok in walked if ok]
            assert [bool(ok) for _, ok in walked] \
                == [True] * len(live) + [False] * (steps - len(live))
            assert all(int(b) == live[-1] for b, ok in walked if not ok)
            if one_run and any(pair(i, j) not in seen for j in live):
                # nothing else is walked, but for the one step of a block
                # that sees nothing (its output is zeros, and written)
                assert len(live) == 1
            longest = max(longest, len(live))
            visited += [pair(i, j) for j in live]
        assert longest == steps
        assert len(set(visited)) == len(visited) and set(visited) >= seen


def test_gated_mlp():
    x = _rand(10, 2, T, d)
    w1, w3, w2 = (_rand(11, 128, d, scale=0.1), _rand(12, 128, d, scale=0.1),
                  _rand(13, d, 128, scale=0.1))
    _both(op('GatedMLP'), lambda x, a, b, c: ref.gated_mlp(x, a.T, b.T, c.T),
          x, w1, w3, w2)


def _moe_params(seed, held):
    return {
        'm_router_weight': _rand(seed, 16, d, scale=0.3),
        'm_experts_w1_weight': _rand(seed + 1, held, d, 32, scale=0.1),
        'm_experts_w3_weight': _rand(seed + 2, held, d, 32, scale=0.1),
        'm_experts_w2_weight': _rand(seed + 3, held, 32, d, scale=0.1),
        'm_shared_w1_weight': _rand(seed + 4, 32, d, scale=0.1),
        'm_shared_w3_weight': _rand(seed + 5, 32, d, scale=0.1),
        'm_shared_w2_weight': _rand(seed + 6, d, 32, scale=0.1)}


_MOE_ORDER = ('router', 'experts_w1', 'experts_w3', 'experts_w2',
              'shared_w1', 'shared_w3', 'shared_w2')


def _moe_op(held, offset):
    fn = op('MoE', num_experts=16, num_experts_per_tok=3, experts_held=held,
            expert_offset=offset, norm_topk_prob=True, routed_scaling=2.5)
    stats = jnp.zeros((len(MOE_STATS),), jnp.float32)
    return lambda x, *w: fn(x, *w, stats)


def _moe_weights(p):
    return [p['m_%s_weight' % n] for n in _MOE_ORDER]


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('held,offset', [(16, 0), (4, 4)])
def test_moe_layer(path, held, offset):
    x, p = _rand(20, T, d), _moe_params(21, held)
    names = ['m_%s_weight' % n for n in _MOE_ORDER]

    def want(x, *w):
        return ref.moe_layer(dict(zip(names, w)), 'm', x, CFG, held,
                             offset)[0]

    _both(lambda x, *w: _moe_op(held, offset)(x, *w)[0], want, x,
          *_moe_weights(p))
    stats = dict(zip(MOE_STATS, np.asarray(
        _moe_op(held, offset)(x, *_moe_weights(p))[1])))
    assert stats['pairs'] == int(ref.moe_layer(p, 'm', x, CFG, held,
                                               offset)[1])
    assert stats['tokens'] == T and stats['dropped'] == 0


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_shares_of_the_expert_layer_add_up(path):
    """model-configs guide, section 4: the partial results of all 4 shares
    of 4 experts each, the shared expert counted once, are the uncut
    reference's layer; and their pairs are all T * top_k of them."""
    x, whole = _rand(30, T, d), _moe_params(31, 16)
    shared = op('GatedMLP')(x, *_moe_weights(whole)[4:])
    total, pairs = -3 * shared, 0       # every share adds it: once is owed
    for share in range(4):
        p = dict(whole)
        for n in ('w1', 'w3', 'w2'):
            key = 'm_experts_%s_weight' % n
            p[key] = whole[key][4 * share:4 * share + 4]
        out, stats = _moe_op(4, 4 * share)(x, *_moe_weights(p))
        total = total + out
        pairs += int(stats[0])
    _close(total, ref.moe_layer(whole, 'm', x, CFG, 16, 0)[0])
    assert pairs == T * 3


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_no_pair_dropped_when_every_token_picks_the_same_experts(path):
    x = jnp.abs(_rand(40, T, d)) + 0.1
    p = _moe_params(41, 4)
    router = np.zeros((16, d), np.float32)
    router[0], router[1], router[2] = 3.0, 2.0, 1.0    # all rows: 0, 1, 2
    p['m_router_weight'] = jnp.asarray(router)
    out, stats = _moe_op(4, 0)(x, *_moe_weights(p))
    stats = dict(zip(MOE_STATS, np.asarray(stats)))
    assert stats['pairs'] == 3 * T and stats['dropped'] == 0
    assert stats['load_max'] == T
    _close(out, ref.moe_layer(p, 'm', x, CFG, 4, 0)[0])


def test_dispatch_plan_buffer_holds_the_worst_case():
    """Every token on every expert held: the sorted buffer is exactly
    full, every pair has a row of its own."""
    idx = jnp.tile(jnp.arange(3)[None], (T, 1))
    dest, row_pair, tile_group, n_tiles, counts = \
        mx.ops.transformer._dispatch_plan(idx, 3, 0)
    assert int(counts.sum()) == 3 * T
    rows = np.asarray(dest).reshape(-1)
    assert len(set(rows.tolist())) == 3 * T and rows.max() < len(row_pair)
    assert int(n_tiles[0]) == 3 and sorted(set(
        np.asarray(tile_group)[:3].tolist())) == [0, 1, 2]


# -- the sorted buffer is walked in passes -------------------------------------

sigmoid_ref = _load('benchmark/reference/deepseek_v3.py',
                    'deepseek_v3_reference')
LONG = 512      # tokens: a pass takes 1280 rows of a buffer of 2048


def _buffer_rows(tokens):
    """The worst-case length of the sorted buffer of 4 held experts, 3 a
    token, as `_dispatch_plan` sizes it."""
    tm = pk.GROUP_TILE
    return -(-(tokens * 3 + 4 * tm) // tm) * tm


def _pass_case(scoring, routing, tokens=LONG):
    """An expert layer that holds 4 of 16 experts, 3 a token, over `tokens`
    tokens: (layer, reference, arguments, rows the tiles present take).
    'same' sends every token to experts 0, 1 and 2."""
    x, p = jnp.abs(_rand(60, tokens, d)) + 0.1, _moe_params(61, 4)
    if routing == 'same':
        router = np.zeros((16, d), np.float32)
        router[0], router[1], router[2] = 0.03, 0.02, 0.01
        p['m_router_weight'] = jnp.asarray(router)
    names = ['m_%s_weight' % n for n in _MOE_ORDER]
    stats = jnp.zeros((len(MOE_STATS),), jnp.float32)
    attrs = dict(num_experts=16, num_experts_per_tok=3, experts_held=4,
                 expert_offset=0, norm_topk_prob=True, routed_scaling=2.5)
    if scoring == 'sigmoid':
        bias = jnp.zeros((1, 16), jnp.float32)
        fn = op('MoE', scoring='sigmoid', **attrs)
        cfg = dict(num_experts_per_tok=3, routed_scaling_factor=2.5)

        def layer(x, *w):
            return fn(x, *w, stats, bias)

        def want(x, *w):
            return sigmoid_ref.moe_layer(
                dict(zip(names, w), m_select_bias_weight=bias), 'm', x, cfg,
                4, 0)[0]

        idx = sigmoid_ref.route(x, p['m_router_weight'], bias, 3, 2.5)[0]
    else:
        fn = op('MoE', **attrs)

        def layer(x, *w):
            return fn(x, *w, stats)

        def want(x, *w):
            return ref.moe_layer(dict(zip(names, w)), 'm', x, CFG, 4, 0)[0]

        idx = ref.route(x, p['m_router_weight'], 3, 2.5)[0]
    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=16)[:4]
    tiles = np.maximum(-(-counts // pk.GROUP_TILE), 1).sum()
    return layer, want, [x] + _moe_weights(p), int(tiles) * pk.GROUP_TILE


def _select_and_add(gmm_dw):
    """The sum as it was before the kernel took the accumulator: the kernel's
    result from zeros, selected by the groups with a tile present, added."""
    def parents(x, y, tile_group, n_tiles, pass_index, acc):
        out = gmm_dw(x, y, tile_group, n_tiles, jnp.zeros_like(pass_index),
                     jnp.zeros_like(acc))
        present = jnp.arange(tile_group.shape[0]) < n_tiles[0]
        named = jnp.any((tile_group[:, None] == jnp.arange(acc.shape[0]))
                        & present[:, None], axis=0)[:, None, None]
        return acc + jnp.where(named, out, 0.0)

    return parents


def _expert_weight_gradients(layer, args):
    out, vjp = jax.vjp(lambda *a: layer(*a)[0], *args)
    return vjp(_rand(99, *out.shape))[2:5]


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('scoring', ['softmax', 'sigmoid'])
@pytest.mark.parametrize('routing,passes', [('even', 1), ('same', 2),
                                            ('same', 3)])
def test_passes_follow_the_rows_present(path, scoring, routing, passes,
                                        monkeypatch):
    """Where a pass is shorter than the worst-case buffer, an even routing
    takes one pass and every token on the same experts takes as many as its
    rows need; either way nothing is dropped, and the output, dx, the
    router's gradient (d_pairs) and the three dw are the reference's. (Three
    passes: 1024 tokens and a pass of the even share, 10 tiles, so that
    experts 1 and 2, tiles 8-15 and 16-23, each straddle a boundary.) The
    three dw are what selecting and adding a kernel's result gave: to the
    bit in one pass, where a group's sum starts from zero either way."""
    tokens = LONG if passes < 3 else 2 * LONG
    if passes == 3:
        monkeypatch.setattr(mx.ops.transformer, '_PASS_OVER_EVEN', 1)
    layer, want, args, rows = _pass_case(scoring, routing, tokens)
    R = _buffer_rows(tokens)
    rp = mx.ops.transformer._pass_rows(R, tokens, 3, 4, 16)
    assert (R, rp) == ((2048, 1280) if passes < 3 else (3584, 1280))
    stats = dict(zip(MOE_STATS, np.asarray(layer(*args)[1])))
    assert stats['passes'] == -(-rows // rp) == passes
    assert stats['dropped'] == 0 and stats['tokens'] == tokens
    if routing == 'same':
        assert stats['pairs'] == 3 * tokens
        assert rows == (3 * tokens // pk.GROUP_TILE + 1) * pk.GROUP_TILE
    _both(lambda *a: layer(*a)[0], want, *args)
    got = _expert_weight_gradients(layer, args)
    monkeypatch.setattr(mx.ops.transformer, '_gmm_dw',
                        _select_and_add(mx.ops.transformer._gmm_dw))
    for a, b in zip(got, _expert_weight_gradients(layer, args)):
        if passes == 1:
            np.testing.assert_array_equal(a, b)
        else:
            _close(a, b)


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_experts_sum_by_sorted_row_is_the_float32_reference(path):
    """`_experts` alone, every token on experts 0, 1 and 2 of the four held
    (each takes every row: 13 tiles, two passes of 10): the output, dx and
    d_pairs are the float32 sum over a token's pairs of w_pair * E(x) and
    its gradients to 1e-5, whatever order the kernel adds a token's terms
    in."""
    ops = mx.ops.transformer
    tokens, k, held = LONG, 3, 4
    x, p = _rand(80, tokens, d), _moe_params(81, held)
    w1, w3, w2 = _moe_weights(p)[1:4]
    w_pairs = jnp.abs(_rand(82, tokens, k)) + 0.1
    idx = jnp.tile(jnp.arange(k)[None], (tokens, 1))
    dest, row_pair, tile_group, n_tiles, _ = ops._dispatch_plan(idx, held, 0)
    rp = ops._pass_rows(row_pair.shape[0], tokens, k, held, 16)
    assert int(ops._num_passes(rp, n_tiles)) == 2
    plan = ops._whole_passes(rp, dest, row_pair, tile_group) + (n_tiles,)

    def want(x, w_pairs):
        hi = jax.lax.Precision.HIGHEST
        h1 = jnp.einsum('td,edh->teh', x, w1[:k], precision=hi)
        h3 = jnp.einsum('td,edh->teh', x, w3[:k], precision=hi)
        y = jnp.einsum('teh,ehd->ted', jax.nn.silu(h1) * h3, w2[:k],
                       precision=hi)
        return jnp.sum(w_pairs[:, :, None] * y, axis=1)

    def got(x, w_pairs):
        return ops._experts(rp, x, w_pairs, w1, w3, w2, *plan)

    (out, vjp), (ref_out, ref_vjp) = jax.vjp(got, x, w_pairs), jax.vjp(
        want, x, w_pairs)
    _close(out, ref_out, 1e-5)
    g = _rand(83, tokens, d)
    for a, b in zip(vjp(g), ref_vjp(g)):
        _close(a, b, 1e-5)


# name: tile_group of six tiles, tiles present, pass index
DW_CASES = {
    'every_group_present': ([0, 0, 1, 2, 3, 3], 6, 1),
    'a_group_without_a_tile': ([0, 0, 2, 2, 3, 3], 6, 1),
    'tiles_past_the_last_present': ([0, 0, 1, 2, 3, 3], 3, 2),
    'pass_0_reads_no_accumulator': ([0, 0, 2, 2, 3, 3], 6, 0),
}


@pytest.mark.parametrize('path', PATHS, indirect=True)
@pytest.mark.parametrize('case', sorted(DW_CASES))
def test_weight_gradient_adds_into_its_accumulator(path, case):
    """out[g] = acc[g] + x^T y over g's rows among the tiles present: a
    group without one keeps acc's values to the bit, tiles past the last
    present touch nothing, and in pass 0, where acc is zeros by contract,
    the kernel starts a group from zero whatever acc holds."""
    tile_group, n_tiles, nth = DW_CASES[case]
    tm = pk.GROUP_TILE
    x, y = _rand(70, 6 * tm, d), _rand(71, 6 * tm, 512)
    acc = _rand(72, 4, d, 512)
    if nth == 0 and path == 'plain':
        acc = jnp.zeros_like(acc)       # the plain form adds what it is given
    got = np.asarray(mx.ops.transformer._gmm_dw(
        x, y, jnp.asarray(tile_group, jnp.int32),
        jnp.asarray([n_tiles], jnp.int32), jnp.asarray([nth], jnp.int32),
        acc))
    x, y, acc = np.asarray(x), np.asarray(y), np.asarray(acc)
    for g in range(4):
        rows = [r for t in range(n_tiles) if tile_group[t] == g
                for r in range(t * tm, (t + 1) * tm)]
        if not rows:
            np.testing.assert_array_equal(got[g], acc[g])
        else:
            _close(got[g], x[rows].T @ y[rows] + (acc[g] if nth else 0.0))


def _under_scope(text, scope):
    """The operations of a text lowered with ``debug_info=True`` whose
    location lies under the named scope."""
    named = set(re.findall(r'^(#loc\d+) = loc\("[^"]*/%s/' % scope, text,
                           re.M))
    return [line for line in text.splitlines()
            if (at := re.search(r'loc\((#loc\d+)\)$', line))
            and at.group(1) in named]


_ALIAS = ('output_operand_alias<output_tuple_indices = [], '
          'operand_index = %d, operand_tuple_indices = []>')


@functools.lru_cache(maxsize=None)
def _step_lowered_for_the_chip():
    step, wrt = _training_step(
        builder.get_symbol(dict(CFG, experts_held=4)), **LM_IN)
    return jax.jit(step).trace(wrt).lower(lowering_platforms=('tpu',)) \
        .as_text(debug_info=True)


def test_the_step_lowered_for_the_chip_sums_weight_gradients_in_place():
    """One training step of the model, lowered for the TPU: under `dw_sum`
    are the three kernel calls a sparse layer, each with its accumulator
    (operand 5) aliased to its output, and neither a select nor an add; no
    select of the experts' weights' shapes is left anywhere."""
    text = _step_lowered_for_the_chip()
    ops = _under_scope(text, 'dw_sum')
    calls = [o for o in ops if 'kernel_name = "moe_expert_matmul_dw"' in o]
    assert len(calls) == 3 * CFG['mlp_layer_types'].count('sparse')
    assert all(_ALIAS % 5 in c for c in calls)
    assert not [o for o in ops if re.search(r'stablehlo\.(select|add)\b', o)]
    assert not re.search(r'stablehlo\.select.*-> tensor<4x(64x32|32x64)xf32>',
                         text)


def test_the_step_lowered_for_the_chip_sums_tokens_by_sorted_row():
    """The same step: the way back from the sorted rows to the tokens is one
    kernel call a sparse layer under `combine` (the output: rows, their
    weights, the sum as operand 5) and one under `gather` (dx: operand 4),
    each sum aliased to its output; no row of a whole sequence is looked up
    a pair at a time (the parent's `k` gathers a loop made `k` float32
    [T, d] adds under each scope: none is left)."""
    text = _step_lowered_for_the_chip()
    sparse = CFG['mlp_layer_types'].count('sparse')
    for scope, acc in (('combine', 5), ('gather', 4)):
        ops = _under_scope(text, scope)
        calls = [o for o in ops if 'kernel_name = "moe_rows_to_tokens"' in o]
        assert len(calls) == sparse and all(_ALIAS % acc in c for c in calls)
        assert not [o for o in ops if re.search(
            r'stablehlo\.add.*tensor<%dx%dxf32>$' % (2 * T, d), o)]


def test_a_pass_is_the_buffer_where_every_expert_is_held():
    for T_, k, held, experts in [(LONG, 3, 16, 16), (8192, 10, 256, 256)]:
        R = T_ * k + held * pk.GROUP_TILE
        assert mx.ops.transformer._pass_rows(R, T_, k, held, experts) == R
    # the two decoder cells: 8192 tokens, 8 of 256 top 10, 16 of 128 top 6
    assert mx.ops.transformer._pass_rows(66560, 8192, 10, 8, 256) == 6144
    assert mx.ops.transformer._pass_rows(51200, 8192, 6, 16, 128) == 14336


def _row_types(text, rows):
    """Element types of the tensor types in a lowered text that have `rows`
    leading rows."""
    return set(re.findall(r'tensor<%dx(?:\d+x)*(\w+)>' % rows, text))


def test_no_activation_has_the_buffers_worst_case_length(monkeypatch):
    """One training step of the model, lowered: of the worst-case length are
    the plan's int32 and pred vectors alone; the activations around the
    grouped products have a pass's length. (With a pass as long as the
    buffer, the same search finds them.)"""
    cfg = dict(CFG, experts_held=4)
    tokens = 2 * T
    R = _buffer_rows(tokens)
    rp = mx.ops.transformer._pass_rows(R, tokens, 3, 4, 16)
    assert rp < R

    def lowered():
        step, wrt = _training_step(builder.get_symbol(cfg), **LM_IN)
        return jax.jit(step).lower(wrt).as_text()

    text = lowered()
    assert _row_types(text, R) <= {'i32', 'i1'}, _row_types(text, R)
    assert {'f32'} <= _row_types(text, rp)
    monkeypatch.setattr(mx.ops.transformer, '_PASS_OVER_EVEN', 1000)
    assert 'f32' in _row_types(lowered(), R)


# -- the whole model ---------------------------------------------------------

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
    assert outs == [(2 * T, CFG['vocab_size'])]
    assert moe_stat_names(sym) == sym.list_auxiliary_states()
    assert auxs == [(len(MOE_STATS),)] * 4


@pytest.mark.parametrize('remat', [True, False])
def test_model_forward_and_gradient(remat):
    cfg = dict(CFG, experts_held=8, expert_offset=4)
    sym = builder.get_symbol(cfg, remat=remat)
    p = _model(cfg)
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
    got = [int(ex.aux_dict[n].asnumpy()[0])
           for n in sym.list_auxiliary_states()]
    assert got == [int(v) for v in pairs]


def test_mirrored_blocks_are_stages_of_their_own():
    """Each block's nodes are one recomputed stage; the embedding, the
    last norm, the head and the loss are not mirrored."""
    from mxnet_tpu.executor import _GraphProgram
    plan = _GraphProgram(builder.get_symbol(CFG))._mirror_plan()
    stages = [p for p in plan if p[1] is not None]
    assert len(stages) == CFG['num_hidden_layers']
    assert all(len(leaves) <= 2 for _, _, leaves in stages)
    assert not [p for p in _GraphProgram(
        builder.get_symbol(CFG, remat=False))._mirror_plan()
        if p[1] is not None]


# -- what a mirrored stage keeps ----------------------------------------------

def _one_block(kind, mlp='dense', **kw):
    """The model cut to one block of `kind` attention and a dense MLP (or
    a 'sparse' one: the expert layer, four experts held)."""
    cfg = dict(CFG, num_hidden_layers=1, layer_types=[kind],
               mlp_layer_types=[mlp], num_attention_heads_per_layer=[6],
               experts_held=4)
    return builder.get_symbol(cfg, **kw), cfg


def _resnet_unit(mirror):
    resnet = _load('examples/image-classification/symbols/resnet.py',
                   'resnet_symbol')
    with mx.AttrScope(**({'__force_mirroring__': 'unit'} if mirror else {})):
        body = resnet.residual_unit(mx.sym.Variable('data'), 8, (1, 1),
                                    False, 'unit', True)
    return mx.sym.MakeLoss(mx.sym.sum(body))


def _training_step(sym, seed=1, **input_shapes):
    """(step, parameters): step(parameters) -> (outputs, gradients) of the
    symbol's runner, traced as the executor and the fused window trace it."""
    from mxnet_tpu.executor import _GraphProgram, mirror_wrap
    prog = _GraphProgram(sym)
    run = prog.make_runner()
    shapes, _, aux_shapes = sym.infer_shape(**input_shapes)
    rng = np.random.RandomState(seed)
    args = [jnp.asarray(rng.randint(0, 90, s).astype(np.float32))
            if n in input_shapes and len(s) == 2 else
            jnp.asarray((rng.randn(*s) / np.sqrt(s[-1])).astype(np.float32))
            for n, s in zip(prog.arg_names, shapes)]
    aux = tuple(jnp.ones(s, jnp.float32) for s in aux_shapes)
    wrt_at = [i for i, n in enumerate(prog.arg_names)
              if n not in input_shapes]

    def step(wrt):
        def f(wrt):
            full = list(args)
            for i, w in zip(wrt_at, wrt):
                full[i] = w
            return run(tuple(full), aux, jnp.zeros((2,), jnp.uint32),
                       True)[0]

        outs, vjp = jax.vjp(mirror_wrap(f), wrt)
        return outs, vjp(tuple(jnp.ones_like(o) for o in outs))[0]

    return step, tuple(args[i] for i in wrt_at)


def _kernel_calls(text, name):
    return {k: text.count('name=%s_%s' % (name, k))
            for k in ('fwd', 'bwd', 'dq', 'dkv')}


def _one_backward_kernel(calls):
    """As many ``_bwd`` kernels as forward ones, and neither of the two
    that a sequence past the VMEM rule takes."""
    assert calls['fwd'] == calls['bwd'] > 0 == calls['dq'] == calls['dkv'], \
        calls


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for u in v if isinstance(v, (tuple, list)) else (v,):
            u = getattr(u, 'jaxpr', u)
            if hasattr(u, 'eqns'):
                yield u


def _second_forward(jaxpr, scope=None, inside=False, out=None):
    """{(primitive, scopes below the stage)} of the equations that the
    backward pass of a mirrored stage computes again: those of the
    checkpoint equations of a gradient's jaxpr that carry the recomputed
    forward's name stack, and what lies below them."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        here = None
        if scope is not None:
            here = (scope + '/' + stack).strip('/')
        elif inside and 'rematted_computation' in stack:
            here = stack.split('rematted_computation', 1)[1].strip(')/')
        if here is not None:
            out.add((eqn.primitive.name, here))
        for sub in _subjaxprs(eqn):
            _second_forward(sub, here,
                            inside or eqn.primitive.name == 'remat2', out)
    return out


def _computed_again(step, wrt, prim, *scopes):
    """The scopes among `scopes` (all there are, if none is given) under
    which the second forward of `step`'s gradient holds a `prim`."""
    found = {scope for p, scope in
             _second_forward(jax.make_jaxpr(step)(wrt).jaxpr) if p == prim}
    return {s for s in found if not scopes
            or any(s.split('/')[0].endswith(w) for w in scopes)}


def _bare_checkpoint(monkeypatch):
    """The stage as it was before an op could name a value."""
    monkeypatch.setattr(registry, 'mirrored',
                        lambda f, kept: jax.checkpoint(f))


KINDS = {'full': ('full_attention', 'attention_full'),
         'window': ('sliding_attention', 'attention_window')}
LM_IN = dict(data=(2, T), softmax_label=(2, T))


def _forward_kernel_runs_once(kind, monkeypatch):
    """(a) in the gradient of a mirrored block the forward kernel is there
    as often as the backward kernel, once; under a bare checkpoint twice."""
    kind, name = KINDS[kind]
    step, wrt = _training_step(_one_block(kind)[0], **LM_IN)
    calls = _kernel_calls(str(jax.make_jaxpr(step)(wrt)), name)
    _one_backward_kernel(calls)
    _bare_checkpoint(monkeypatch)
    step, wrt = _training_step(_one_block(kind)[0], **LM_IN)
    bare = _kernel_calls(str(jax.make_jaxpr(step)(wrt)), name)
    assert bare['fwd'] == 2 * calls['fwd'] and bare['bwd'] == calls['bwd']


def _gradients_are_the_bare_checkpoints(kind, monkeypatch):
    """(b) the kept values are the ones a recomputation makes: loss and
    every gradient bit-equal. (The full block has the dense MLP, whose two
    hidden products are kept in float32, as they were made; the windowed
    one the expert layer: its routing, its plan and its shared expert's
    two.)"""
    sym = _one_block(KINDS[kind][0],
                     'sparse' if kind == 'window' else 'dense')[0]
    step, wrt = _training_step(sym, **LM_IN)
    outs, grads = jax.jit(step)(wrt)
    _bare_checkpoint(monkeypatch)
    step, wrt = _training_step(sym, **LM_IN)
    outs_bare, grads_bare = jax.jit(step)(wrt)
    for a, b in zip(outs + grads, outs_bare + grads_bare):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(np.abs(np.asarray(g)).max() > 0 for g in grads)


def _no_stage_lowers_as_before(which, monkeypatch):
    """(c) a symbol with no mirrored stage meets no checkpoint and no
    policy, and a name outside a stage lowers to nothing."""
    if which == 'resnet_unit':
        sym, shapes = _resnet_unit(False), dict(data=(2, 8, 6, 6))
    else:
        sym = _one_block('full_attention', remat=False)[0]
        shapes = LM_IN

    def refuse(*_):
        raise AssertionError('a mirrored stage in an unmirrored symbol')

    monkeypatch.setattr(registry, 'mirrored', refuse)
    monkeypatch.setattr(registry, 'keeps_dear', refuse)
    step, wrt = _training_step(sym, **shapes)
    jaxpr = str(jax.make_jaxpr(step)(wrt))
    assert 'checkpoint' not in jaxpr and 'remat' not in jaxpr
    assert ('name[' in jaxpr) == (which != 'resnet_unit')
    text = jax.jit(step).lower(wrt).as_text()
    for module in (pk, mx.ops.transformer, mx.ops.nn):
        monkeypatch.setattr(module, 'dear', lambda x, name: x)
    step, wrt = _training_step(sym, **shapes)
    assert 'name[' not in str(jax.make_jaxpr(step)(wrt))
    unnamed = jax.jit(step).lower(wrt).as_text()
    if which != 'resnet_unit':
        # a name is no operation of the lowered program; it does take a
        # number from the counter behind the private functions' names
        text, unnamed = (re.sub(r'(@\w+?)_\d+\b', r'\1', t)
                         for t in (text, unnamed))
    assert unnamed == text


def _a_stage_with_no_name_keeps_nothing(_, monkeypatch):
    """(d) no op of the stage named a value: the policy is asked and saves
    nothing, and the gradient is the bare checkpoint's."""
    asked, policy = [], registry.keeps_dear

    def spy(prim, *avals, **params):
        asked.append(policy(prim, *avals, **params))
        return asked[-1]

    monkeypatch.setattr(registry, 'keeps_dear', spy)
    step, wrt = _training_step(_resnet_unit(True), data=(2, 8, 6, 6))
    jaxpr = str(jax.make_jaxpr(step)(wrt))
    assert 'remat' in jaxpr and 'name[' not in jaxpr
    assert asked and not any(asked)
    outs, grads = jax.jit(step)(wrt)
    _bare_checkpoint(monkeypatch)
    step, wrt = _training_step(_resnet_unit(True), data=(2, 8, 6, 6))
    for a, b in zip(outs + grads, sum(jax.jit(step)(wrt), ())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _the_whole_forward_mirror_keeps_them_too(kind, monkeypatch):
    """MXTPU_BACKWARD_DO_MIRROR=1 over a symbol without stages: the same
    policy, so the forward kernel runs once there too."""
    kind, name = KINDS[kind]
    monkeypatch.setenv('MXTPU_BACKWARD_DO_MIRROR', '1')
    try:
        step, wrt = _training_step(_one_block(kind, remat=False)[0], **LM_IN)
        jaxpr = str(jax.make_jaxpr(step)(wrt))
    finally:
        monkeypatch.delenv('MXTPU_BACKWARD_DO_MIRROR')
        flags.reload('MXTPU_BACKWARD_DO_MIRROR')
    calls = _kernel_calls(jaxpr, name)
    assert 'remat' in jaxpr
    _one_backward_kernel(calls)


PROJECTIONS = ('attn_q', 'attn_k', 'attn_v', 'attn_g', 'attn_o')


def _the_second_forward_leaves_out_what_was_named(kind, monkeypatch):
    """What the rules name is not made a second time: no projection of the
    attention sublayer, no rotary turn of a query or key (the angles' table
    alone), no top-k, none of the plan's scans, scatters and searches, and
    no product of a gated MLP, dense or shared (its ``silu(g) * u`` is: the
    one elementwise pass behind the two kept values); the router's product
    and the experts are. Under a bare checkpoint all of it is there twice."""
    kind, name = KINDS[kind]
    step, wrt = _training_step(_one_block(kind, 'sparse')[0], **LM_IN)
    assert not _computed_again(step, wrt, 'dot_general', *PROJECTIONS)
    assert _computed_again(step, wrt, 'dot_general') == {'layer0_moe/router'}
    assert 'layer0_moe/shared' in _computed_again(step, wrt, 'logistic')
    for prim in ('top_k', 'cumsum', 'scatter', 'sort', 'concatenate'):
        assert not _computed_again(step, wrt, prim), prim
    assert _computed_again(step, wrt, 'cos') == {
        'layer0_attn_q_rope', 'layer0_attn_k_rope'}
    dense, wrt_dense = _training_step(_one_block(kind)[0], **LM_IN)
    assert not _computed_again(dense, wrt_dense, 'dot_general')
    assert 'layer0_mlp' in _computed_again(dense, wrt_dense, 'logistic')
    _bare_checkpoint(monkeypatch)
    step, wrt = _training_step(_one_block(kind, 'sparse')[0], **LM_IN)
    assert _computed_again(step, wrt, 'dot_general', *PROJECTIONS) == {
        'layer0_' + p for p in PROJECTIONS}
    assert 'layer0_moe/shared' in _computed_again(step, wrt, 'dot_general')
    for prim in ('top_k', 'cumsum', 'scatter', 'concatenate'):
        assert _computed_again(step, wrt, prim), prim
    dense, wrt_dense = _training_step(_one_block(kind)[0], **LM_IN)
    assert 'layer0_mlp' in _computed_again(dense, wrt_dense, 'dot_general')


def _the_gauges_count_each_named_array_once(_, monkeypatch):
    """(e) executor.mirror_kept and _bytes of a dense and a sparse block:
    the arrays the ops named, from the shapes, the value projection that is
    also the kernel's value once, a gated MLP's two hidden products (the
    dense block's and the shared expert's) in float32."""
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', os.devnull)
    _reload_telemetry()
    try:
        heads = [4, 6]
        cfg = dict(CFG, num_hidden_layers=2, layer_types=CFG['layer_types'][:2],
                   mlp_layer_types=['dense', 'sparse'],
                   num_attention_heads_per_layer=heads, experts_held=4)
        step, wrt = _training_step(builder.get_symbol(cfg), **LM_IN)
        jax.make_jaxpr(step)(wrt)
        gauges = telemetry.snapshot()['gauges']
        rows, k = 2 * T, cfg['num_experts_per_tok']
        named = []      # (elements, bytes each)
        for H in heads:
            named += [(rows * H * D, 4)] * 2        # out, q
            named += [(2 * H * T, 4)]               # lse
            named += [(rows * KV * D, 4)] * 3       # k, v, k before rotary
            named += [(rows * H, 4), (rows * d, 4)]     # gate, attn_o
            if H * D <= d:
                named += [(rows * H * D, 4)]        # q before rotary
        R = _buffer_rows(rows)
        R += -R % mx.ops.transformer._pass_rows(R, rows, k, 4, 16)
        named += [(rows * k, 4)] * 4        # choice, scores, weights, dest
        named += [(R, 4), (R // 128, 4), (1, 4)]
        for hidden in (cfg['intermediate_size'],
                       cfg['shared_expert_intermediate_size']):
            named += [(rows * hidden, 4)] * 2       # x W1^T, x W3^T
        assert gauges['executor.mirror_kept'] == len(named)
        assert gauges['executor.mirror_kept_bytes'] == sum(
            n * b for n, b in named)
        # a symbol without a mirrored stage keeps nothing
        step, wrt = _training_step(builder.get_symbol(cfg, remat=False),
                                   **LM_IN)
        jax.make_jaxpr(step)(wrt)
        gauges = telemetry.snapshot()['gauges']
        assert gauges['executor.mirror_kept'] == 0
        assert gauges['executor.mirror_kept_bytes'] == 0
    finally:
        monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
        _reload_telemetry()


@pytest.mark.parametrize('path', ['kernel'], indirect=True)
@pytest.mark.parametrize('case,arg', [
    (_forward_kernel_runs_once, 'full'),
    (_forward_kernel_runs_once, 'window'),
    (_gradients_are_the_bare_checkpoints, 'full'),
    (_gradients_are_the_bare_checkpoints, 'window'),
    (_no_stage_lowers_as_before, 'resnet_unit'),
    (_no_stage_lowers_as_before, 'laguna_without_remat'),
    (_a_stage_with_no_name_keeps_nothing, 'resnet_unit'),
    (_the_whole_forward_mirror_keeps_them_too, 'full'),
    (_the_gauges_count_each_named_array_once, 'two_blocks'),
    (_the_second_forward_leaves_out_what_was_named, 'full'),
], ids=lambda v: v if isinstance(v, str) else v.__name__.strip('_'))
def test_a_mirrored_stage_keeps_what_an_op_named_as_dear(
        path, case, arg, monkeypatch):
    """A mirrored stage recomputes its block in the backward pass except
    the values an op named as dear (``registry.dear``: what the attention
    kernel's backward pass reads, a contracting projection's output, the
    expert layer's routing and plan, a gated MLP's two hidden products), on
    the kernels' path."""
    case(arg, monkeypatch)


def _fit(cfg, steps, monkeypatch, lr=0.05):
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
        ce = param.eval_metric.metrics[0]
        sums.append(float(ce.sum_metric))

    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, eval_metric=['ce', 'acc'], optimizer='sgd',
            optimizer_params={'learning_rate': lr, 'momentum': 0.9,
                              'wd': 0.0},
            arg_params={k: mx.nd.array(v) for k, v in p.items()},
            aux_params={n: mx.nd.zeros((len(MOE_STATS),))
                        for n in sym.list_auxiliary_states()},
            num_epoch=1, batch_end_callback=note)
    return mod, p, toks, np.diff([0.0] + sums) / T


def test_fit_takes_the_fused_window_and_follows_the_reference(monkeypatch):
    cfg = dict(CFG, experts_held=4)
    mod, p, toks, losses = _fit(cfg, 3, monkeypatch)
    assert mod.__dict__.get('_fused_fit_cache') is not None
    assert mod.__dict__['_fused_fit_cache'][1].window == 3
    w = {k: jnp.asarray(v) for k, v in p.items()}
    mom = {k: jnp.zeros_like(v) for k, v in w.items()}
    want = []
    for i in range(3):
        loss, _, g = ref.loss_and_grad(w, toks[i:i + 1, :T],
                                       toks[i:i + 1, 1:], cfg)
        want.append(float(loss))
        w, mom = ref.sgd_momentum_step(w, mom, g, 0.05, 0.9)
    np.testing.assert_allclose(losses, want, rtol=1e-4)
    got = mod.get_params()[0]
    for n in p:
        _close(got[n].asnumpy() - p[n], np.asarray(w[n]) - p[n], tol=2e-3)


def _window_text(mod):
    """The lowered text of the module's fused window."""
    from mxnet_tpu import random as _random
    loop = mod.__dict__['_fused_fit_cache'][1]
    fn = loop._build_program(loop._static_attrs(), None)
    params, states, aux, gaccs = loop._snapshot()
    lr, wd = loop._sample_window_lr()
    stack = jnp.zeros((loop.window, 1, T), jnp.float32)
    return fn.lower(params, states, aux, gaccs, (stack,), (stack,),
                    _random.next_key(), lr, wd).as_text()


def _reload_telemetry():
    for f in ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH'):
        flags.reload(f)
    telemetry._reset_for_tests()


def test_telemetry_off_leaves_the_window_unchanged_on_yields_moe_counters(
        tmp_path, monkeypatch):
    # a dense layer and two sparse ones, two steps: what is asserted is
    # sums over layers and steps, at any number of either
    steps, sparse = 2, 2
    cfg = dict(CFG, experts_held=4, num_hidden_layers=1 + sparse,
               num_attention_heads_per_layer=[4, 6, 4],
               layer_types=['full_attention', 'sliding_attention',
                            'full_attention'],
               mlp_layer_types=['dense'] + ['sparse'] * sparse)

    def run(on):
        telemetry._reset_for_tests()
        if on:
            monkeypatch.setenv('MXTPU_TELEMETRY', '1')
            monkeypatch.setenv('MXTPU_TELEMETRY_PATH',
                               str(tmp_path / 't.jsonl'))
        else:
            monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
        _reload_telemetry()
        mod = _fit(cfg, steps, monkeypatch)[0]
        return _window_text(mod), telemetry.snapshot()

    try:
        off, snap_off = run(False)
        on, snap_on = run(True)
        off_again, _ = run(False)
        assert off == off_again and on != off
        assert not [k for k in snap_off['counters'] if k.startswith('moe.')]
        c = snap_on['counters']
        assert c['moe.tokens'] == steps * T * sparse \
            and c['moe.dropped'] == 0
        assert 0 < c['moe.pairs'] <= steps * T * 3 * sparse
        # every step of every sparse layer, one pass each
        assert c['moe.layer_steps'] == c['moe.passes'] == steps * sparse
        assert snap_on['gauges']['moe.load_max_over_mean'] >= 1.0
    finally:
        monkeypatch.delenv('MXTPU_TELEMETRY', raising=False)
        _reload_telemetry()


def test_flash_attention_backward_is_the_blockwise_one():
    """flash_attention and flash_attention_lse differentiate through the
    same kernels (no dense vjp): gradients match the dense oracle, the
    log-sum-exp's cotangent included."""
    q, k, v = _rand(50, 2, 24, 3, D), _rand(51, 2, 40, 3, D), \
        _rand(52, 2, 40, 3, D)

    def f(q, k, v):
        o, lse = pk.flash_attention_lse(q, k, v, True, None, 8, 8)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def g(q, k, v):
        o, lse = pk._flash_lse_ref(q, k, v, True, D ** -0.5)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    for a, b in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                    jax.grad(g, (0, 1, 2))(q, k, v)):
        _close(a, b, tol=1e-4)


# -- the backward kernel: one where dk and dv of a sequence fit VMEM, else two ---------

def _plain_attention(q, k, v, heads, kv_heads, causal, window, scale):
    """(out, lse) of grouped-query attention in plain float32, the
    [Tq, Tk] scores formed whole; the mask is bottom-right aligned."""
    B, Tq, HD = q.shape
    Tk, D, group = k.shape[1], HD // heads, heads // kv_heads
    q5 = q.reshape(B, Tq, kv_heads, group, D)
    k4, v4 = k.reshape(B, Tk, kv_heads, D), v.reshape(B, Tk, kv_heads, D)
    s = jnp.einsum('bqkgd,bskd->bkgqs', q5, k4,
                   precision='highest') * scale
    rows = jnp.arange(Tq)[:, None] + Tk - Tq
    cols = jnp.arange(Tk)[None, :]
    seen = jnp.ones((Tq, Tk), bool)
    if causal:
        seen &= cols <= rows
    if window:
        seen &= cols > rows - window
    s = jnp.where(seen, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum('bkgqs,bskd->bqkgd', jnp.exp(s - lse[..., None]), v4,
                     precision='highest')
    return out.reshape(B, Tq, HD), lse.reshape(B, heads, Tq)


def _backward_kernels(text, name):
    """'one' or 'two': which backward kernels a traced call holds (under a
    trace each is there in its compiled and its interpreted form)."""
    calls = _kernel_calls(text, name)
    assert calls['dq'] == calls['dkv'] and (calls['bwd'] > 0) \
        != (calls['dq'] > 0), calls
    return 'one' if calls['bwd'] else 'two'


def _two_kernels(monkeypatch):
    """The rule as a sequence too long for VMEM meets it."""
    monkeypatch.setattr(pk, '_BWD_RESIDENT_BYTES', 0)


# name: Tq, Tk, heads, key/value heads, causal, window, block, g_lse given
BACKWARD_CASES = {
    'full_causal': (64, 64, 4, 4, True, 0, 16, False),
    'window_off_the_block': (64, 64, 6, 2, True, 24, 16, False),
    'group_of_three': (48, 48, 6, 2, True, 0, 16, False),
    'non_causal': (48, 48, 4, 2, False, 0, 16, False),
    'padded_length': (37, 37, 6, 2, True, 0, 16, False),
    'padded_window': (37, 37, 6, 2, True, 8, 8, False),
    'more_keys_than_queries': (24, 40, 4, 2, True, 0, 8, False),
    'log_sum_exp_cotangent': (32, 32, 4, 2, True, 0, 8, True),
    'one_block': (40, 40, 6, 2, True, 0, 512, False),
}


@pytest.mark.parametrize('case', sorted(BACKWARD_CASES))
def test_attention_backward_one_kernel(case, monkeypatch):
    """dq, dk and dv of the one backward kernel against ``jax.vjp`` of the
    plain float32 formulation and against the two kernels."""
    Tq, Tk, heads, kv, causal, window, block, with_lse = BACKWARD_CASES[case]
    q, k, v = (_rand(60, 2, Tq, heads * D), _rand(61, 2, Tk, kv * D),
               _rand(62, 2, Tk, kv * D))
    g_out = _rand(63, 2, Tq, heads * D)
    g_lse = _rand(64, 2, heads, Tq) if with_lse else None
    scale = D ** -0.5
    (out, lse), vjp = jax.vjp(lambda q, k, v: _plain_attention(
        q, k, v, heads, kv, causal, window, scale), q, k, v)
    want = vjp((g_out, jnp.zeros_like(lse) if g_lse is None else g_lse))

    def backward(name):
        f = lambda *a: pk.attention_backward(  # noqa: E731
            *a, heads, kv, causal, window, None, block, block, g_lse=g_lse,
            name=name)
        args = (q, k, v, out, lse, g_out)
        return _backward_kernels(str(jax.make_jaxpr(f)(*args)), name), \
            f(*args)

    which, one = backward('one')
    assert which == 'one'
    _two_kernels(monkeypatch)
    which, two = backward('two')
    assert which == 'two'
    for a, b, c in zip(one, want, two):
        _close(a, b)
        _close(a, c)


# operands [1, T, .] bfloat16 at Laguna's widths (head 128, 8 key/value
# heads): whether the one kernel runs is a function of the shapes alone
RULE_CASES = {
    'full_8192': (8192, 48, 0, 512, True),
    'window_8192': (8192, 72, 512, 256, True),
    'full_16384': (16384, 48, 0, 512, True),
    'full_65536': (65536, 48, 0, 512, False),
    'window_65536': (65536, 72, 512, 256, False),
}


@pytest.mark.parametrize('case', sorted(RULE_CASES))
def test_the_shapes_decide_between_one_backward_kernel_and_two(case):
    length, heads, window, block, fits = RULE_CASES[case]
    spec = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.bfloat16)
    wide, narrow = spec(1, length, heads * 128), spec(1, length, 8 * 128)
    text = str(jax.make_jaxpr(lambda *a: pk.attention_backward(
        *a, heads, 8, True, window, None, block, block, name='rule'))(
        wide, narrow, narrow, wide,
        jax.ShapeDtypeStruct((1, heads, length), jnp.float32), wide))
    assert _backward_kernels(text, 'rule') == ('one' if fits else 'two')
    assert (pk._bwd_vmem(length, (128, 128), jnp.bfloat16) is not None) \
        == fits


def test_seeded_weights_are_bfloat16_values_whatever_the_threads():
    """``benchmark/weights_lm.py``: every leaf from its own generator (the
    number of threads changes nothing), std 1/sqrt(fan_in), rounded to
    the nearest bfloat16 as ``lax.reduce_precision`` rounds."""
    weights_lm = _load('benchmark/weights_lm.py', 'weights_lm')
    shapes = {'a_weight': (64, 256), 'b_experts_w1_weight': (4, 256, 32),
              'embed_weight': (96, 64), 'n_gamma': (64,), 'm_stats': (5,)}
    one = weights_lm.make_params(shapes, 4000000007, threads=1)
    three = weights_lm.make_params(shapes, 4000000007, threads=3)
    other = weights_lm.make_params(shapes, 4000000008)
    raw = weights_lm.make_params(shapes, 4000000007, round_bf16=False)
    for n in shapes:
        np.testing.assert_array_equal(one[n], three[n])
        np.testing.assert_array_equal(one[n], np.asarray(
            jax.lax.reduce_precision(jnp.asarray(raw[n]), 8, 7)))
    assert not np.array_equal(one['a_weight'], other['a_weight'])
    assert abs(one['a_weight'].std() * 16 - 1) < 0.05
    assert abs(one['b_experts_w1_weight'].std() * 16 - 1) < 0.05
    assert abs(one['embed_weight'].std() - 1) < 0.05
    assert np.all(one['n_gamma'] == 1) and not one['m_stats'].any()


def test_threaded_gaps_are_the_convnet_comparisons_numbers(monkeypatch):
    """``compare_lm_training.gaps`` takes its norms and distances a chunk
    and a leaf at a time on threads; the numbers are those of
    ``compare_training.gaps``."""
    monkeypatch.syspath_prepend(REPO)
    from benchmark import compare_lm_training, compare_training
    monkeypatch.setattr(compare_lm_training, 'CHUNK', 1000)
    rng = np.random.default_rng(3)
    want = {'w%d' % i: rng.standard_normal((37, 11 * (i + 1)))
            .astype(np.float32) * 10.0 ** -i for i in range(5)}
    near = {n: v * (1 + 0.01 * i) + 1e-3 * rng.standard_normal(v.shape)
            .astype(np.float32) for i, (n, v) in enumerate(want.items())}
    got = ([1.0, 2.0, 3.0, 4.0], near, want)
    ref_side = ([1.0, 2.0, 3.003, 4.0], want, near)
    mine, my_leaves = compare_lm_training.gaps(got, ref_side)
    theirs, their_leaves = compare_training.gaps(got, ref_side)
    assert my_leaves == their_leaves
    for k, v in theirs.items():
        assert mine[k] == pytest.approx(v, rel=1e-9), k

