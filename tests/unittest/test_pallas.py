"""Pallas kernels vs their jnp oracles (interpret mode on the CPU mesh).

Same strategy as the reference's kernel tests (tests/cpp/operator/
batchnorm_test.cc: hand-written kernel vs reference impl across shapes/
dtypes) — here each pallas kernel is compared against the plain-jnp
formulation, forward and backward.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.parallel.ring_attention import attention_reference


def _rand(*shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).standard_normal(shape),
                       jnp.float32)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('Tq,Tk', [(64, 64), (32, 128), (16, 32)])
def test_flash_attention_forward(causal, Tq, Tk):
    """Includes causal decode shapes (Tq != Tk): the kernel mask must be
    bottom-right aligned like the oracle's tril(..., Tk - Tq)."""
    q = _rand(2, Tq, 4, 16, seed=0)
    k = _rand(2, Tk, 4, 16, seed=1)
    v = _rand(2, Tk, 4, 16, seed=2)
    out = pk.flash_attention(q, k, v, causal, None, 32, 32)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('Tq,Tk', [(32, 32), (16, 32)])
def test_flash_attention_grad(Tq, Tk):
    q = _rand(1, Tq, 2, 8, seed=0)
    k = _rand(1, Tk, 2, 8, seed=1)
    v = _rand(1, Tk, 2, 8, seed=2)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, True, None, 16, 16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_fused_rmsnorm():
    x = _rand(4, 24, 64, seed=3)
    g = _rand(64, seed=4)
    out = pk.fused_rmsnorm(x, g)
    inv = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x * inv * g),
                               rtol=1e-5, atol=1e-5)
    # grads flow and match
    f = lambda x, g: jnp.sum(pk.fused_rmsnorm(x, g) ** 2)  # noqa: E731
    r = lambda x, g: jnp.sum((x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g) ** 2)
    for a, b in zip(jax.grad(f, (0, 1))(x, g), jax.grad(r, (0, 1))(x, g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
@pytest.mark.parametrize('lead', [(3, 1368), (1037,), (0,)],
                         ids=['blocks', 'padded', 'empty'])
@pytest.mark.parametrize('D', [64, 128, 2048])
def test_fused_rmsnorm_backward_kernel(monkeypatch, D, lead, dtype):
    """RMSNorm's backward rule is the kernel ``fused_rmsnorm_bwd``
    (interpreted here, through the registry op under MXTPU_FORCE_PALLAS=1):
    its gradients are ``jax.vjp``'s of the plain formula to float32
    rounding, over several row blocks (4104 rows), a row count with no
    legal divisor (1037: padded) and an empty batch; dgamma is float32 and
    the same bits call after call."""
    from mxnet_tpu.ops.registry import get
    monkeypatch.setenv('MXTPU_FORCE_PALLAS', '1')
    x = _rand(*lead, D, seed=D).astype(dtype)
    dy = _rand(*lead, D, seed=D + 1).astype(dtype)
    g = 1 + 0.1 * _rand(D, seed=D + 2)
    _, vjp = jax.vjp(lambda x, g: get('RMSNorm').fn({}, x, g), x, g)
    _, ref_vjp = jax.vjp(lambda x, g: pk._rms_ref(x, g, 1e-6), x, g)
    # an empty batch launches nothing; rows of 2048 and more keep XLA's form
    assert ('fused_rmsnorm_bwd' in str(jax.make_jaxpr(vjp)(dy))) == (
        x.size > 0 and D < pk._RMS_BWD_WIDTHS)
    (dx, dg), (rdx, rdg) = vjp(dy), ref_vjp(dy)
    assert dx.dtype == dtype and dx.shape == x.shape
    assert dg.dtype == jnp.float32 and dg.shape == (D,)
    rdx = np.asarray(rdx.astype(jnp.float32))
    x32, dy32 = x.astype(jnp.float32), dy.astype(jnp.float32)
    terms = np.asarray(jnp.abs(dy32 * x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)).reshape(-1, D).sum(0))

    def check(dx, dg):
        # one unit in bfloat16's last place is 2^-7 of the value at most
        np.testing.assert_allclose(
            np.asarray(dx.astype(jnp.float32)), rdx, atol=1e-6,
            rtol=1e-5 if dtype == jnp.float32 else 2.0 ** -7)
        # a sum over the rows: to 1e-5 of the sum of its terms' sizes
        assert np.all(np.abs(np.asarray(dg - rdg)) <= 1e-5 * terms)

    check(dx, dg)
    # the kernel itself, at any width: right, and the same bits again
    dx, dg = pk.fused_rmsnorm_bwd(x, g, dy)
    assert dg.dtype == jnp.float32
    check(dx, dg)
    dx2, dg2 = pk.fused_rmsnorm_bwd(x, g, dy)
    np.testing.assert_array_equal(np.asarray(dg2), np.asarray(dg))
    np.testing.assert_array_equal(np.asarray(dx2.astype(jnp.float32)),
                                  np.asarray(dx.astype(jnp.float32)))


def test_fused_layernorm():
    x = _rand(8, 32, seed=5)
    g = _rand(32, seed=6)
    b = _rand(32, seed=7)
    out = pk.fused_layernorm(x, g, b)
    mu = x.mean(-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    ref = (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_softmax_xent():
    logits = _rand(64, 50, seed=8)
    labels = jnp.asarray(np.random.RandomState(9).randint(0, 50, 64),
                         jnp.int32)
    loss = pk.softmax_xent(logits, labels)
    ref = (jax.nn.logsumexp(logits, -1) -
           jnp.take_along_axis(logits, labels[:, None], -1)[:, 0])
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # backward: softmax - onehot
    g = jax.grad(lambda lg: pk.softmax_xent(lg, labels).sum())(logits)
    gref = jax.grad(lambda lg: (jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, labels[:, None], -1)[:, 0]).sum())(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_lse():
    """The lse output must equal logsumexp of the scaled scores — it is
    the exact merge statistic ring attention relies on."""
    q = _rand(2, 32, 2, 16, seed=20)
    k = _rand(2, 32, 2, 16, seed=21)
    v = _rand(2, 32, 2, 16, seed=22)
    out, lse = pk.flash_attention_lse(q, k, v, False, None, 16, 16)
    scale = 16 ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q * scale, k)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(jax.nn.logsumexp(s, -1)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_fused_softmax():
    x = _rand(32, 40, seed=23)
    y = pk.fused_softmax(x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jax.nn.softmax(x, -1)),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lambda x: (pk.fused_softmax(x) ** 2).sum())(x)
    g2 = jax.grad(lambda x: (jax.nn.softmax(x, -1) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


def test_registry_ops_dispatch_to_pallas(monkeypatch):
    """LayerNorm / softmax / softmax_cross_entropy invoke the fused
    kernels when the dispatch policy is on (TPU, or forced here) and
    match their jnp formulations."""
    from mxnet_tpu.ops.registry import get
    x = _rand(8, 32, seed=24)
    gamma = _rand(32, seed=25)
    beta = _rand(32, seed=26)
    labels = jnp.asarray(np.random.RandomState(27).randint(0, 32, 8),
                         jnp.int32)
    plain = {
        'LayerNorm': get('LayerNorm').fn({}, x, gamma, beta),
        'softmax': get('softmax').fn({}, x),
        'xent': get('softmax_cross_entropy').fn({}, x, labels),
    }
    monkeypatch.setenv('MXTPU_FORCE_PALLAS', '1')
    fused = {
        'LayerNorm': get('LayerNorm').fn({}, x, gamma, beta),
        'softmax': get('softmax').fn({}, x),
        'xent': get('softmax_cross_entropy').fn({}, x, labels),
    }
    for name in plain:
        np.testing.assert_allclose(np.asarray(fused[name]),
                                   np.asarray(plain[name]),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_ring_flash_vs_plain_accumulator():
    """ring_attention's flash path (default) against its plain-jnp
    accumulator on the same mesh — bit-for-tol identical merges."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import shard_map
    import functools as ft
    mesh = make_mesh({'sp': 4})
    q = _rand(2, 64, 2, 16, seed=30)
    k = _rand(2, 64, 2, 16, seed=31)
    v = _rand(2, 64, 2, 16, seed=32)
    spec = P(None, 'sp', None, None)
    for causal in (False, True):
        outs = {}
        for use_flash in (True, False):
            fn = ft.partial(shard_map,
                            mesh=mesh.mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)(
                lambda q, k, v, uf=use_flash, c=causal: ring_attention(
                    q, k, v, axis='sp', causal=c, use_flash=uf,
                    block_q=16, block_k=16))
            outs[use_flash] = fn(q, k, v)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(outs[True]),
                                   np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(outs[True]),
                                   np.asarray(outs[False]),
                                   rtol=2e-5, atol=2e-5)


def test_flash_inside_jit_and_vs_blockwise():
    from mxnet_tpu.parallel.ring_attention import blockwise_attention
    q = _rand(2, 64, 2, 16, seed=10)
    k = _rand(2, 64, 2, 16, seed=11)
    v = _rand(2, 64, 2, 16, seed=12)
    out = jax.jit(lambda q, k, v: pk.flash_attention(q, k, v, False,
                                                     None, 32, 32))(q, k, v)
    ref = blockwise_attention(q, k, v, block_size=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Mosaic block-rule compliance (real-TPU lowering enforces (8,128) tiling
# on the last two block dims; interpret mode on this CPU mesh does NOT —
# the round-3 transformer bench failed exactly there). These tests pin
# the block-size choosers to Mosaic-legal outputs for awkward shapes.
# ---------------------------------------------------------------------------

def test_block_choosers_mosaic_legal():
    from mxnet_tpu.ops.pallas_kernels import (_block_ok, _pad_and_block,
                                              _pick_block)
    for n in [1, 2, 3, 6, 7, 8, 13, 64, 96, 100, 120, 128, 250, 256,
              1000, 1024, 4096]:
        for want in [8, 128, 256]:
            b = _pick_block(want, n)
            assert n % b == 0 and _block_ok(b, n), (n, want, b)
    # large power-of-two inputs keep the intended tile sizes
    assert _pick_block(256, 4096) == 256
    assert _pick_block(128, 1024) == 128
    # prime sizes fall back to the full axis (always legal)
    assert _pick_block(128, 13) == 13
    # ...but the row kernels pre-pad instead of taking a huge full-array
    # block: N = 2 * prime has no legal divisor <= 128, so pad to a
    # multiple of 8 and tile at 8+ (the VMEM-safety guarantee)
    for n, want in [(1006, 128), (2 * 503, 256), (1024, 128), (13, 128)]:
        pad, blk = _pad_and_block(want, n)
        assert (n + pad) % blk == 0 and _block_ok(blk, n + pad)
        assert blk <= max(want, 8) or n <= want, (n, pad, blk)
    assert _pad_and_block(128, 1006) == (2, 112)
    assert _pad_and_block(128, 1024) == (0, 128)
    assert _pad_and_block(128, 13) == (0, 13)  # small full blocks are fine


def test_flash_lse_block_spec_is_mosaic_legal():
    """The LSE output is carried as [B, H, Tq, 1]: its (1, 1, blk_q, 1)
    block has minor dim == array dim and second-to-minor divisible by 8,
    at any length (an awkward one is padded to whole blocks). The pre-fix
    (1, blk_q) spec violated the rule on real TPU
    (bench_transformer_20260731T111706Z.log)."""
    from mxnet_tpu.ops.pallas_kernels import (_attn_blocks, _block_ok,
                                              flash_attention_lse)
    for Tq in [64, 96, 100, 128, 1024]:
        blk_q, _, pad_q, _ = _attn_blocks(Tq, Tq, 128, 128)
        assert blk_q % 8 == 0 and _block_ok(blk_q, Tq + pad_q) \
            and (Tq + pad_q) % blk_q == 0
        assert _block_ok(1, 1)          # minor dim of the [.., Tq, 1] lse
    # numerics unchanged by the layout change
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)
    out, lse = flash_attention_lse(q, k, v, causal=True)
    from mxnet_tpu.ops.pallas_kernels import _flash_lse_ref
    ref_out, ref_lse = _flash_lse_ref(q, k, v, True, 16 ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)


def test_norm_and_xent_odd_row_counts():
    """Odd/prime row counts must still produce Mosaic-legal blocks and
    exact numerics (pre-fix the halving loop could pick blk=2 etc.)."""
    from mxnet_tpu.ops.pallas_kernels import (fused_rmsnorm, softmax_xent)
    rng = np.random.RandomState(12)
    for n in [3, 7, 13, 100, 1006]:   # 1006 = 2*503 takes the pad path
        x = jnp.asarray(rng.randn(n, 32), jnp.float32)
        g = jnp.ones((32,), jnp.float32)
        got = np.asarray(fused_rmsnorm(x, g))
        x32 = np.asarray(x)
        want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        logits = jnp.asarray(rng.randn(n, 50), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 50, (n,)), jnp.int32)
        loss = np.asarray(softmax_xent(logits, labels))
        l32 = np.asarray(logits)
        lse = np.log(np.exp(l32 - l32.max(-1, keepdims=True)).sum(-1)) \
            + l32.max(-1)
        want = lse - l32[np.arange(n), np.asarray(labels)]
        np.testing.assert_allclose(loss, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_flash_awkward_seq_pads_q(causal):
    """Tq=28 with block_q=8 has no multiple-of-8 divisor: both axes are
    zero-padded to 32 and tiled at 8 (a whole-axis fallback would put an
    O(Tq x blk_k) score tile in VMEM on real TPU; padded keys are masked).
    Numerics must match the oracle exactly on the real rows."""
    from mxnet_tpu.ops.pallas_kernels import _attn_blocks, flash_attention
    assert _attn_blocks(28, 28, 8, 8) == (8, 8, 4, 4)
    q = _rand(2, 28, 2, 16, seed=40)
    k = _rand(2, 28, 2, 16, seed=41)
    v = _rand(2, 28, 2, 16, seed=42)
    out = flash_attention(q, k, v, causal, None, 8, 8)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_empty_and_tiny_block_requests():
    """Review regressions: zero-row inputs must not divide by zero, and
    a sub-8 block request must not trigger a whole-axis VMEM block."""
    from mxnet_tpu.ops.pallas_kernels import (_attn_blocks, _pad_and_block,
                                              flash_attention,
                                              fused_rmsnorm, softmax_xent)
    # empty batches launch nothing and return empty results
    assert fused_rmsnorm(jnp.zeros((0, 16)),
                         jnp.ones((16,))).shape == (0, 16)
    assert softmax_xent(jnp.zeros((0, 10)),
                        jnp.zeros((0,), jnp.int32)).shape == (0,)
    out = flash_attention(jnp.zeros((0, 8, 2, 4)), jnp.zeros((0, 8, 2, 4)),
                          jnp.zeros((0, 8, 2, 4)))
    assert out.shape == (0, 8, 2, 4)
    with pytest.raises(ValueError, match='at least one key'):
        flash_attention(jnp.zeros((1, 8, 2, 4)), jnp.zeros((1, 0, 2, 4)),
                        jnp.zeros((1, 0, 2, 4)))
    # block_q=4 at Tq=1024: want clamps to 8, never the 1024 whole axis
    assert _pad_and_block(4, 1024) == (0, 8)
    assert _attn_blocks(1024, 1024, 4, 4) == (8, 8, 0, 0)
    q = _rand(1, 64, 1, 8, seed=50)
    out = flash_attention(q, q, q, True, None, 4, 4)
    ref = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('force', [False, True], ids=['jnp', 'interpreted'])
def test_rows_wider_than_the_chips_vmem_bind_on_the_cpu(monkeypatch, force):
    """The row limit (32768 f32 elements) is the chip's: softmax,
    softmax_cross_entropy and LayerNorm at width 40000 bind and run in a
    jitted executor on the CPU mesh, through the jnp formulation and
    (MXTPU_FORCE_PALLAS=1) through the interpreted kernel, and agree."""
    import mxnet_tpu as mx
    monkeypatch.setenv('MXTPU_FORCE_PALLAS', '1' if force else '0')
    rng = np.random.RandomState(0)
    V = 40000
    x_np = rng.randn(4, V).astype(np.float32)
    lab = rng.randint(0, V, (4,)).astype(np.float32)
    x, y = mx.sym.Variable('x'), mx.sym.Variable('y')
    ex = mx.sym.softmax(x).simple_bind(mx.cpu(), x=(4, V))
    ex.arg_dict['x'][:] = x_np
    np.testing.assert_allclose(ex.forward()[0].asnumpy(),
                               np.asarray(jax.nn.softmax(x_np)), atol=1e-7)
    ex = mx.sym.softmax_cross_entropy(x, y).simple_bind(
        mx.cpu(), x=(4, V), y=(4,))
    ex.arg_dict['x'][:] = x_np
    ex.arg_dict['y'][:] = lab
    want = -np.asarray(jax.nn.log_softmax(x_np))[
        np.arange(4), lab.astype(int)].sum()
    np.testing.assert_allclose(ex.forward()[0].asnumpy(), want, rtol=1e-5)
    ex = mx.sym.LayerNorm(x, name='ln').simple_bind(mx.cpu(), x=(4, V))
    ex.arg_dict['x'][:] = x_np
    ex.arg_dict['ln_gamma'][:] = 1
    out = ex.forward()[0].asnumpy()
    np.testing.assert_allclose(out.mean(-1), 0, atol=1e-5)
    np.testing.assert_allclose(out.std(-1), 1, rtol=1e-3)


# name: (tokens, real rows of each of six tiles of 128, tiles present,
# passes). A tile's real rows come first and name different tokens, as the
# rows of one expert do; every tile is another expert's, so a token can own
# a row in each.
ROWS_TO_TOKENS_CASES = {
    'padding_rows_inside_a_present_tile': (256, [128, 70, 1, 0, 128, 9], 6, 1),
    'tiles_past_the_last_present_hold_nan': (
        256, [128, 70, 128, 128, 128, 128], 2, 1),
    'a_token_in_every_tile_and_tokens_in_none': (64, [32, 32, 32, 32, 32, 32],
                                                 6, 1),
    'a_second_pass_adds_into_the_first': (128, [128, 37, 128, 5, 0, 101], 6,
                                          2),
}


@pytest.mark.parametrize('scaled', [True, False], ids=['scale', 'no_scale'])
@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32],
                         ids=['bfloat16', 'float32'])
@pytest.mark.parametrize('case', sorted(ROWS_TO_TOKENS_CASES))
def test_rows_to_tokens_adds_sorted_rows_into_their_tokens(case, dtype,
                                                           scaled):
    """out[token[r]] = acc[token[r]] + scale[r] * src[r] over the real rows
    of the tiles present, against ``jnp.zeros(...).at[token].add(...)``:
    padding rows (token T) add nothing whatever they hold, tiles past the
    last present are not read (they hold NaN), a token may own a row in
    every tile or in none, and a later pass adds into what the first left
    where pass 0 starts from zeros without reading its accumulator."""
    T, real, present, passes = ROWS_TO_TOKENS_CASES[case]
    tm, d = pk.GROUP_TILE, 256
    rng = np.random.RandomState(len(case))
    want = jnp.zeros((T, d), jnp.float32)
    # pass 0 must not read what it is handed
    acc = jnp.full((T, d), np.nan, jnp.float32)
    for nth in range(passes):
        token = np.full((len(real), tm), T, np.int32)
        for t, n in enumerate(real):
            # the first 32 tokens only, where every tile has room for them:
            # those own a row in each tile, the others in none
            pool = 32 if case.startswith('a_token') else T
            token[t, :n] = rng.permutation(pool)[:n]
        token = token.reshape(-1)
        src = rng.standard_normal((len(token), d)).astype(np.float32)
        src[present * tm:] = np.nan
        src = jnp.asarray(src, dtype)
        scale = jnp.asarray(rng.standard_normal(len(token)), jnp.float32)
        acc = pk.rows_to_tokens(
            src, jnp.asarray(token), jnp.asarray([present], jnp.int32),
            jnp.asarray([nth], jnp.int32), acc, scale if scaled else None)
        live = token[:present * tm]
        rows = src[:present * tm].astype(jnp.float32)
        if scaled:
            rows = rows * scale[:present * tm, None]
        want = want + jnp.zeros((T + 1, d), jnp.float32).at[live].add(
            rows)[:T]
    assert acc.dtype == jnp.float32 and acc.shape == (T, d)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want), rtol=1e-6,
                               atol=1e-5)
    if case.startswith('a_token'):
        assert not np.asarray(acc)[32:].any()
