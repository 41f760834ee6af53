"""What block-diffusion training adds below the model
(``examples/transformer/symbols/sdar_moe.py``), on the CPU in float32:

- the two-run walk of the attention kernels against a brute-force list of
  the block pairs the mask leaves, with a block length larger than, equal
  to and smaller than a kernel block, and a padded length;
- the kernels (interpreted) and the plain form against a dense masked
  softmax, forward and all three gradients, both backward forms;
- ``RotaryEmbedding(period=)`` against positions given by hand;
- ``WeightedSoftmaxOutput``'s gradient against ``jax.grad`` of the
  objective;
- the in-window ``Perplexity(ignore_label)`` against ``metric.Perplexity``;
- the noising iterator;
- LFM2's lowered step is the text it had (Laguna's, Kanana's and Xing4's
  digests are in ``test_latent_ops.py``, ``test_hyper_ops.py`` and
  ``test_hybrid_ops.py`` and are checked there).
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
from mxnet_tpu.module import window_pipeline
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.transformer import _dense_attention, block_diffusion_mask

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


ref = _load('benchmark/reference/sdar_moe.py', 'sdar_moe_reference')
builder = _load('examples/transformer/symbols/sdar_moe.py', 'sdar_moe_symbol')
noising = _load('examples/transformer/blockdiff_iter.py', 'blockdiff_iter')
cases = _load('tests/unittest/test_transformer_ops.py',
              'transformer_ops_cases')
hybrid = _load('tests/unittest/test_hybrid_ops.py', 'hybrid_ops_cases')
path, PATHS, LM_IN = cases.path, cases.PATHS, cases.LM_IN
_rand, _close, _both, op = cases._rand, cases._close, cases._both, cases.op
_training_step = cases._training_step

CFG = dict(
    model_type='sdar_moe', hidden_size=64, vocab_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
    moe_intermediate_size=24, num_experts=16, num_experts_per_tok=3,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    tie_word_embeddings=False, experts_held=16, expert_offset=0,
    block_length=4)
L = 32
MASK_ID = 95
BD_IN = dict(data=(2, 2 * L), softmax_label=(2, L), loss_weight=(2, L))


# -- the walk -----------------------------------------------------------------------------------

def _pairs_left(length, block_length, blk):
    """Kernel block pairs (query block, key block) that hold a visible
    pair, by brute force over the dense mask."""
    seen = np.asarray(block_diffusion_mask(length, block_length))
    n = 2 * length // blk
    return {(i, j) for i in range(n) for j in range(n)
            if seen[i * blk:(i + 1) * blk, j * blk:(j + 1) * blk].any()}


@pytest.mark.parametrize('length,block_length,blk', [
    (64, 4, 16), (64, 16, 16), (64, 32, 8), (128, 4, 32), (64, 8, 64),
    (96, 4, 32), (48, 16, 24), (4096, 4, 512)],
    ids=lambda v: str(v))
def test_the_two_runs_are_the_block_pairs_the_mask_leaves(length,
                                                          block_length, blk):
    walk = pk._diffusion_walk(length, block_length, blk, 0)
    n = 2 * length // blk
    assert walk.nq == walk.nk == n
    want = _pairs_left(length, block_length, blk) if length < 4096 else None
    for side, block_of, steps in (
            ('keys', walk.key_block, walk.key_steps),
            ('queries', walk.query_block, walk.query_steps)):
        got, longest = [], 0
        for i in range(n):
            walked = [block_of(i, s) for s in range(steps)]
            live = [b for b, ok in walked if ok]
            # a walk that has ended stays on its last block: no new fetch
            assert all(b == live[-1] for b, ok in walked if not ok)
            assert len(set(live)) == len(live)
            longest = max(longest, len(live))
            got += [(i, j) if side == 'keys' else (j, i) for j in live]
        assert longest == steps
        if want is not None:
            assert set(got) == want and len(got) == len(want)
    if want is None:
        # the cell's shapes: 80 block pairs where a causal walk of 8192
        # rows makes 136, and at most 1.25 of the pairs the mask leaves
        needed, visited = pk.block_diffusion_pairs(length, block_length)
        assert (needed, visited) == (16793600, 80 * 512 * 512)
        assert visited <= 1.25 * needed


def test_a_length_that_is_no_whole_blocks_walks_every_block():
    blk, pad = pk._diffusion_blocks(30, 16)
    assert (blk, pad) == (16, 4)
    walk = pk._diffusion_walk(30, 5, blk, pad)
    assert walk.nq == walk.key_steps == 4 and walk.key_block(2, 3) == (3, True)
    assert pk._walk_of(60, 60, 16, 16, block_length=5)[:4] == (16, 16, 4, 4)
    assert pk._diffusion_blocks(64, 512) == (64, 0)
    assert pk._diffusion_blocks(4096, 512) == (512, 0)


# -- the kernels against a dense masked softmax ------------------------------------------------

def _dense_want(q, k, v, heads, kv_heads, block_length):
    """Softmax over the keys the mask leaves, written out."""
    B, T, HD = q.shape
    D, group = HD // heads, heads // kv_heads
    seen = ref.mask_rows(jnp.arange(T), T // 2, block_length)
    out = []
    for h in range(heads):
        g = h // group
        s = jnp.einsum('bqd,bkd->bqk', q[..., h * D:(h + 1) * D],
                       k[..., g * D:(g + 1) * D]) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum('bqk,bkd->bqd', p, v[..., g * D:(g + 1) * D]))
    return jnp.concatenate(out, axis=-1)


@pytest.mark.parametrize('length,block_length,block,two_kernels', [
    (64, 4, 16, False), (64, 4, 16, True), (64, 32, 8, False),
    (64, 32, 8, True), (64, 16, 16, False), (30, 5, 16, False),
    (30, 5, 16, True), (36, 4, 512, False)],
    ids=['B_under_a_block', 'B_under_a_block_two_kernels', 'B_over_a_block',
         'B_over_a_block_two_kernels', 'B_a_block', 'padded',
         'padded_two_kernels', 'one_block'])
def test_attention_kernels(length, block_length, block, two_kernels,
                           monkeypatch):
    if two_kernels:
        monkeypatch.setattr(pk, '_bwd_vmem', lambda *a: None)
    H, KV, D = 4, 2, 16
    q, k, v = (_rand(0, 2, 2 * length, H * D), _rand(1, 2, 2 * length, KV * D),
               _rand(2, 2, 2 * length, KV * D))

    def kernels(q, k, v):
        return pk.blockwise_attention(q, k, v, H, KV, True, 0, None, block,
                                      block, 'attention_blockdiff',
                                      block_length)

    want = lambda q, k, v: _dense_want(q, k, v, H, KV, block_length)  # noqa
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: kernels(q, k, v).sum(), (0, 1, 2)))(q, k, v))
    found = set(re.findall(
        r'name=(attention_blockdiff_(?:fwd|bwd|dq|dkv))\b', text))
    assert found == ({'attention_blockdiff_fwd', 'attention_blockdiff_dq',
                      'attention_blockdiff_dkv'} if two_kernels else
                     {'attention_blockdiff_fwd', 'attention_blockdiff_bwd'})
    _both(kernels, want, q, k, v)


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_grouped_query_attention_under_the_mask(path):
    H, KV, D = 4, 2, 16
    q, k, v = (_rand(3, 2, 2 * L, H * D), _rand(4, 2, 2 * L, KV * D),
               _rand(5, 2, 2 * L, KV * D))
    attn = op('GroupedQueryAttention', num_heads=H, num_kv_heads=KV,
              block_length=4)
    _both(attn, lambda q, k, v: _dense_want(q, k, v, H, KV, 4), q, k, v)
    # the plain form is one dense masked product
    _close(_dense_attention(q, k, v, H, KV, 0, 4),
           _dense_want(q, k, v, H, KV, 4))
    # and a plain causal mask is another function
    causal = op('GroupedQueryAttention', num_heads=H, num_kv_heads=KV)
    assert np.abs(np.asarray(causal(q, k, v))
                  - np.asarray(attn(q, k, v))).max() > 0.1


@pytest.mark.parametrize('attrs', [
    dict(block_length=-4), dict(block_length=5), dict(block_length=4,
                                                      window=8),
    dict(block_length=4, gated=True)], ids=str)
def test_grouped_query_attention_refuses(attrs):
    q, kv = _rand(0, 1, 2 * L, 64), _rand(1, 1, 2 * L, 32)
    with pytest.raises(ValueError, match='GroupedQueryAttention'):
        op('GroupedQueryAttention', num_heads=4, num_kv_heads=2,
           **attrs)(q, kv, kv)


# -- positions that restart ---------------------------------------------------------------------

def test_rotary_positions_restart_every_period():
    x = _rand(6, 1, 2 * L, 4 * 16)
    cos, sin = ref.base.rope_tables(1000000, 16, L)
    cos, sin = jnp.tile(cos, (2, 1)), jnp.tile(sin, (2, 1))

    def by_hand(x):
        return ref.apply_rope_halves(x.reshape(2 * L, 4, 16), cos, sin) \
            .reshape(1, 2 * L, 64)

    rope = op('RotaryEmbedding', num_heads=4, base=1000000.0, period=L)
    _both(rope, by_hand, x)
    # the two halves of one input turn alike; positions that run on do not
    twice = jnp.concatenate([x[:, :L], x[:, :L]], axis=1)
    got = np.asarray(rope(twice))
    np.testing.assert_array_equal(got[:, :L], got[:, L:])
    on = np.asarray(op('RotaryEmbedding', num_heads=4, base=1000000.0)(twice))
    np.testing.assert_array_equal(on[:, :L], got[:, :L])
    assert np.abs(on[:, L:] - got[:, L:]).max() > 0.1


# -- the weighted head --------------------------------------------------------------------------

def test_weighted_softmax_outputs_gradient_is_the_objectives():
    rows, classes = 24, 11
    z = _rand(7, rows, classes)
    rng = np.random.RandomState(8)
    label = np.where(rng.rand(rows) < 0.6, rng.randint(0, classes, rows),
                     -1).astype(np.float32)
    weight = (1.0 / rng.uniform(0.45, 0.95, rows)).astype(np.float32)
    head = op('WeightedSoftmaxOutput', ignore_label=-1.0,
              normalization='batch')
    out, vjp = jax.vjp(lambda z: head(z, jnp.asarray(label),
                                      jnp.asarray(weight)), z)
    _close(out, jax.nn.softmax(z, axis=-1))

    def objective(z):
        kept = label != -1
        ce = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                                  jnp.where(kept, label, 0).astype(int)
                                  [:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(kept, weight * ce, 0.0)) / rows

    got, = vjp(jnp.ones_like(out))      # the cotangent is not read
    _close(got, jax.grad(objective)(z), tol=1e-6)
    assert not np.asarray(got)[label == -1].any()
    # a weight of one everywhere is SoftmaxOutput's ignoring gradient times
    # the kept rows over all rows
    plain = op('SoftmaxOutput', use_ignore=True, ignore_label=-1.0,
               normalization='valid')
    _, vjp1 = jax.vjp(lambda z: head(z, jnp.asarray(label),
                                     jnp.ones(rows)), z)
    _, vjp2 = jax.vjp(lambda z: plain(z, jnp.asarray(label)), z)
    _close(vjp1(out)[0], vjp2(out)[0] * (label != -1).sum() / rows, tol=1e-6)


# -- the metric inside the window ---------------------------------------------------------------

@pytest.mark.parametrize('ignore', [-1, None])
def test_perplexity_inside_the_window_is_the_metrics(ignore):
    rows, classes = 40, 13
    rng = np.random.RandomState(9)
    pred = np.array(jax.nn.softmax(_rand(10, rows, classes), axis=-1))
    pred[3, :] = 0.0            # below the metric's floor of 1e-10
    label = rng.randint(0, classes, rows).astype(np.float32)
    if ignore is not None:
        label[rng.rand(rows) < 0.4] = ignore
    m = mx.metric.Perplexity(ignore_label=ignore)
    fn = window_pipeline._plan_one(m)
    s, n = fn((jnp.asarray(pred),), (jnp.asarray(label).reshape(8, 5),))
    m.update([mx.nd.array(label)], [mx.nd.array(pred)])
    assert int(n) == m.num_inst and (ignore is None) == (int(n) == rows)
    np.testing.assert_allclose(float(s), m.sum_metric, rtol=1e-6)
    plan, why = window_pipeline.plan_metric_or_reason(
        mx.metric.Perplexity(ignore_label=ignore,
                             output_names=['softmax_output'],
                             label_names=['softmax_label']),
        [(rows, classes)], builder.LABEL_NAMES, ['softmax_output'])
    assert why is None and len(plan[1]) == 1


# -- the iterator -------------------------------------------------------------------------------

def test_the_noise_is_a_function_of_seed_and_step():
    a = noising.noise(7, 3, 2, 4096, 4)
    b = noising.noise(7, 3, 2, 4096, 4)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert (noising.noise(7, 4, 2, 4096, 4)[0] != a[0]).any()
    assert (noising.noise(8, 3, 2, 4096, 4)[0] != a[0]).any()
    mask, weight = a
    t = 1.0 / weight
    assert weight.dtype == np.float32 and mask.dtype == bool
    assert t.min() >= 0.45 and t.max() <= 0.95
    # one level a block, and the masked share of the blocks near their t
    assert (t.reshape(2, -1, 4) == t.reshape(2, -1, 4)[..., :1]).all()
    assert abs(mask.mean() - t.mean()) < 0.02
    low, high = t < 0.55, t > 0.85
    assert abs(mask[low].mean() - t[low].mean()) < 0.03
    assert abs(mask[high].mean() - t[high].mean()) < 0.03


def test_the_iterator_yields_a_noisy_and_a_clean_copy():
    rng = np.random.RandomState(11)
    x = rng.randint(0, MASK_ID, (6, L)).astype(np.float32)
    it = noising.BlockDiffusionIter(mx.io.NDArrayIter(x, None, batch_size=2),
                                    4, MASK_ID, seed=5)
    assert [(d.name, d.shape) for d in it.provide_data] \
        == [('data', (2, 2 * L))]
    assert [(d.name, d.shape) for d in it.provide_label] \
        == [('softmax_label', (2, L)), ('loss_weight', (2, L))]
    seen = 0
    for epoch in range(2):
        for k, batch in enumerate(it):
            data = batch.data[0].asnumpy()
            label, weight = (a.asnumpy() for a in batch.label)
            mask, w = noising.noise(5, seen, 2, L, 4)
            x0 = x[2 * k:2 * k + 2]
            np.testing.assert_array_equal(data[:, L:], x0)
            np.testing.assert_array_equal(
                data[:, :L], np.where(mask, MASK_ID, x0))
            np.testing.assert_array_equal(label, np.where(mask, x0, -1))
            np.testing.assert_array_equal(weight, w)
            seen += 1
        it.reset()
    assert seen == 6       # the noise's step runs on over the epochs
    x[1, 3] = MASK_ID
    bad = noising.BlockDiffusionIter(mx.io.NDArrayIter(x, None, batch_size=2),
                                     4, MASK_ID)
    with pytest.raises(ValueError, match='mask id'):
        bad.next()
    with pytest.raises(ValueError, match='whole blocks'):
        noising.BlockDiffusionIter(mx.io.NDArrayIter(x, None, batch_size=2),
                                   5, MASK_ID)


# -- lowered steps ------------------------------------------------------------------------------

# sha256 of the lowered text of one training step of the lfm2_moe builder's
# symbol at test_hybrid_ops.CFG's sizes, on the CPU, on each path, taken
# under pytest on the commit before block diffusion came (3390d65), before
# any op was touched: ``GroupedQueryAttention`` learnt a mask, ``RotaryEmbedding`` a
# period and the attention wrappers a second walk, and LFM2's step is to
# lower as it did. Taken again on the tree of PR 46, which changed the way
# back from the expert layer's sorted rows to the tokens by intent
# (test_latent_ops.py says how). Both paths again on the tree of PR 51: the
# one dense layer's MLP saves its two hidden products for its mirrored stage
# and makes them once (two values more, two products fewer in the backward
# text; LFM2 has no shared expert). The text is this jax's.
LFM2_TEXT = {
    'plain':
    'fdf74cf84bf521d4b00607ef5644b0c222623ee11e4d6182e5dd630a2b58954e',
    'kernel':
    '72038768d47f0e51aef07ee4c76adea94d68f90a7593d959c06c49223dc24d72'}
# the same of this family's own step, at CFG's sizes, taken on the tree
# that brought it and again on that of PR 46, as above; both families'
# 'kernel' texts again on that of PR 47, and LFM2's on that of PR 48, which
# left this family's own as it was (test_latent_ops.py says how). PR 51 left
# both of this family's texts as they were: it has no gated MLP, dense or
# shared, and that change reaches nothing else
SDAR_TEXT = {
    'plain':
    'e0b556f21f64b9c73e3d4da275e279152c94dc95072f5f5478cf115dc92dff40',
    'kernel':
    'dcfdf3ab09f0a822d6e453b4feaf078cef0a87f5abe263fe141f9f00a28f19b6'}


def _digest(sym, **inputs):
    step, wrt = _training_step(sym, **inputs)
    text = jax.jit(step).lower(wrt).as_text()
    # the counter behind the private functions' names is the process's
    text = re.sub(r'(@\w+?)_\d+\b', r'\1', text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_lfm2_lowers_to_the_text_it_had(path):
    assert _digest(hybrid.builder.get_symbol(dict(hybrid.CFG)), **LM_IN) \
        == LFM2_TEXT[path]


@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_this_familys_step_lowers_to_its_text(path):
    assert _digest(builder.get_symbol(CFG, seq_len=L), **BD_IN) \
        == SDAR_TEXT[path]
