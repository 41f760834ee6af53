"""Rehearsal compiles: every Pallas kernel of the main path, compiled (not
interpreted, not run) by the TPU compiler installed here for a v5e that is
described and not attached, at the widths the repo talks about.

What the interpreter accepts and the chip's compiler refuses (a block that
does not fit VMEM, a slice off the tiling) fails here at no chip time.

Discipline (the on-chip-measurement guide, section 2): the topology is
described inside the module-scoped fixture below and nowhere else: not
at import, not in conftest.py, not in a skipif or parametrize argument,
not in a child process. Only the worker that is handed this file loads
the TPU's library, and all such compiles live in this ONE file.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        jax.config.update('jax_enable_compilation_cache', was)
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    yield desc
    jax.config.update('jax_enable_compilation_cache', was)
    cc.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *specs, kernels=()):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    # the kernel itself, not the interpreter's while loop and not jnp
    assert 'tpu_custom_call' in text, text[:2000]
    if kernels:
        found = set(re.findall(r'attention\w*_(?:fwd|bwd|dq|dkv)\b', text))
        assert found == set(kernels), found
    return compiled


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

def _attention(q, k, v, g, heads, window, block):
    out, lse = pk.attention_forward(q, k, v, heads, 8, True, window, None,
                                    block, block, 'attention')
    return pk.attention_backward(q, k, v, out, lse, g, heads, 8, True,
                                 window, None, block, block,
                                 name='attention')


def _blockdiff_attention(q, k, v, g):
    out, lse = pk.attention_forward(q, k, v, 32, 4, name='attention',
                                    block_length=4)
    return pk.attention_backward(q, k, v, out, lse, g, 32, 4,
                                 name='attention', block_length=4)


def _blockdiff_specs(length):
    return [((1, length, 4096), BF16), ((1, length, 512), BF16),
            ((1, length, 512), BF16), ((1, length, 4096), BF16)]


def _short_conv(x, w, g):
    return pk.short_conv_backward(x, w, g) + (pk.short_conv_forward(x, w),)


def _silu_conv(x, w, g):
    return pk.short_conv_backward(x, w, g, gated=False) \
        + (pk.short_conv_forward(x, w, gated=False),)


def _latent_attention(qn, qr, kn, kr, v, g, scale=None):
    out, lse = pk.latent_attention_forward(qn, qr, kn, kr, v, 32,
                                           scale=scale)
    return pk.latent_attention_backward(qn, qr, kn, kr, v, out, lse, g, 32,
                                        scale=scale)


def _attention_specs(heads, length=8192, head=128):
    return [((1, length, heads * head), BF16), ((1, length, 8 * head), BF16),
            ((1, length, 8 * head), BF16), ((1, length, heads * head), BF16)]


def _latent_specs(length):
    return [((1, length, 4096), BF16), ((1, length, 2048), BF16),
            ((1, length, 4096), BF16), ((1, length, 64), BF16),
            ((1, length, 4096), BF16), ((1, length, 4096), BF16)]


_ONE = ('attention_fwd', 'attention_bwd')
_TWO = ('attention_fwd', 'attention_dq', 'attention_dkv')
_LATENT_ONE = ('attention_latent_fwd', 'attention_latent_bwd')
_LATENT_TWO = ('attention_latent_fwd', 'attention_latent_dq',
               'attention_latent_dkv')


_HX, _HY = ((4096, 4 * 3584), BF16), ((4096, 3584), BF16)
_HW, _HR = ((pk.HYPER_COLS, 4 * 3584), BF16), ((1, pk.HYPER_COLS), F32)
_HC = ((4096, pk.HYPER_COLS), F32)

KERNELS = [
    # the [B, T, H, D] entry points are the blockwise forward kernel: no
    # key length is refused (32768 keys were, as whole-axis blocks)
    ('flash_fwd_b8_t1024', lambda q, k, v: pk.flash_attention(q, k, v, True),
     [((8, 1024, 8, 128), BF16)] * 3, ('attention_fwd',)),
    ('flash_fwd_b1_t8192', lambda q, k, v: pk.flash_attention(q, k, v, True),
     [((1, 8192, 8, 128), BF16)] * 3, ('attention_fwd',)),
    ('flash_fwd_b1_t32768', lambda q, k, v: pk.flash_attention(q, k, v, True),
     [((1, 32768, 8, 128), BF16)] * 3, ('attention_fwd',)),
    ('layernorm_8192x1024', pk.fused_layernorm,
     [((8192, 1024), BF16), ((1024,), F32), ((1024,), F32)]),
    ('rmsnorm_8192x4096', pk.fused_rmsnorm,
     [((8192, 4096), BF16), ((4096,), F32)]),
    # RMSNorm's backward kernel at the decoder cells' shapes: the per-head
    # norms (heads 128 and 64 wide on 8192 rows) and the block norms
    ('rmsnorm_bwd_262144x128', pk.fused_rmsnorm_bwd,
     [((262144, 128), BF16), ((128,), BF16), ((262144, 128), BF16)]),
    ('rmsnorm_bwd_262144x64', pk.fused_rmsnorm_bwd,
     [((262144, 64), BF16), ((64,), BF16), ((262144, 64), BF16)]),
    ('rmsnorm_bwd_8192x2048', pk.fused_rmsnorm_bwd,
     [((8192, 2048), BF16), ((2048,), BF16), ((8192, 2048), BF16)]),
    ('rmsnorm_bwd_8192x3584', pk.fused_rmsnorm_bwd,
     [((8192, 3584), BF16), ((3584,), BF16), ((8192, 3584), BF16)]),
    ('softmax_8192x1024', pk.fused_softmax, [((8192, 1024), BF16)]),
    ('softmax_32x1000', pk.fused_softmax, [((32, 1000), F32)]),
    ('xent_32x1000', pk.softmax_xent, [((32, 1000), F32), ((32,), I32)]),
    # a decoder's head (vocab 16384, 8x1024 tokens a step, the shape
    # chip_smoke.py runs) and a 32000-word LM head: refused before the row
    # block was taken from the row width
    ('xent_8192x16384', pk.softmax_xent,
     [((8192, 16384), BF16), ((8192,), I32)]),
    ('xent_8192x32000', pk.softmax_xent,
     [((8192, 32000), F32), ((8192,), I32)]),
    # the widest row that leaves the 8-row minimum block, through the
    # kernel with the most temporaries, and a 50k-word LM head
    ('layernorm_4096x65536', pk.fused_layernorm,
     [((4096, 65536), F32), ((65536,), F32), ((65536,), F32)]),
    ('xent_4096x50304', pk.softmax_xent,
     [((4096, 50304), BF16), ((4096,), I32)]),
    # the decoder block's kernels at Laguna-S-2.1's widths (head 128, 8
    # key/value heads, one 8192-token sequence): sliding layers have 72
    # query heads and a window of 512, full layers 48. The backward is the
    # one kernel that holds a key/value head's dk and dv of the whole
    # sequence in VMEM (32 MiB asked of Mosaic), as far as 16384 tokens;
    # at 65536 they do not fit and the two kernels compile
    ('attention_window_fwd_bwd', lambda q, k, v, g: _attention(
        q, k, v, g, 72, 512, 256), _attention_specs(72), _ONE),
    ('attention_full_fwd_bwd', lambda q, k, v, g: _attention(
        q, k, v, g, 48, 0, 512), _attention_specs(48), _ONE),
    ('attention_full_fwd_bwd_t16384', lambda q, k, v, g: _attention(
        q, k, v, g, 48, 0, 512), _attention_specs(48, 16384), _ONE),
    ('attention_full_fwd_bwd_t65536', lambda q, k, v, g: _attention(
        q, k, v, g, 48, 0, 512), _attention_specs(48, 65536), _TWO),
    # LFM2-24B-A2B's attention: 32 heads of 64 on 8 key/value heads. A
    # head's 64 columns of [B, T, H * 64] are no legal block (the minor
    # block is 128 lanes or the whole axis), which the interpreter does not
    # check: the compiled kernels take such heads as [B, H, T, 64], the one
    # backward kernel as far as the rule allows and the two past it
    ('attention_full_fwd_bwd_head64', lambda q, k, v, g: _attention(
        q, k, v, g, 32, 0, 512), _attention_specs(32, head=64), _ONE),
    ('attention_full_fwd_bwd_head64_t65536', lambda q, k, v, g: _attention(
        q, k, v, g, 32, 0, 512), _attention_specs(32, 65536, 64), _TWO),
    # and its gated short convolution: a step's [8192, 3 x 2048] projection
    # in blocks of 256 rows at the whole width, the taps (2048, 3)
    ('short_conv_fwd_bwd', _short_conv,
     [((1, 8192, 6144), BF16), ((2048, 3), BF16), ((1, 8192, 2048), BF16)]),
    # the same two bodies with their gates off, Olmo-Hybrid-7B's convolution
    # over [q | k | v]: 4096 rows of 11520 channels, 4 taps
    ('short_conv_fwd_bwd_ungated', _silu_conv,
     [((1, 4096, 11520), BF16), ((11520, 4), BF16),
      ((1, 4096, 11520), BF16)]),
    # SDAR-30B-A3B-Chat's attention under the block-diffusion mask: 32
    # heads on 4 key/value heads, two halves of 4096 rows in blocks of 4
    # positions, walked in two runs of kernel blocks; the one backward
    # kernel as far as the rule allows and the two past it
    ('attention_blockdiff_fwd_bwd', _blockdiff_attention,
     _blockdiff_specs(8192), _ONE),
    ('attention_blockdiff_fwd_bwd_t65536', _blockdiff_attention,
     _blockdiff_specs(65536), _TWO),
    # the held experts' grouped product: 8 experts of 3072 x 1024, the
    # static worst-case buffer of 8192 x 8 + 8 x 128 rows
    ('moe_expert_matmul', lambda x, w, t, n: pk.grouped_matmul(x, w, t, n),
     [((66560, 3072), BF16), ((8, 3072, 1024), BF16), ((520,), I32),
      ((1,), I32)]),
    ('moe_expert_matmul_transposed', lambda x, w, t, n: pk.grouped_matmul(
        x, w, t, n, transpose_w=True),
     [((66560, 3072), BF16), ((8, 1024, 3072), BF16), ((520,), I32),
      ((1,), I32)]),
    # the weight gradient adds into the float32 array it is given
    ('moe_expert_matmul_dw', pk.grouped_matmul_dw,
     [((66560, 3072), BF16), ((66560, 1024), BF16), ((520,), I32),
      ((1,), I32), ((1,), I32), ((8, 3072, 1024), F32)]),
    # latent attention at kanana-2-30b-a3b's widths: 32 heads, keys of
    # 128 + 64 (the 64 rotary ones shared by all heads), values of 128;
    # the one backward kernel holds a head's dq_nope and dq_rope. At
    # Xing4.0-29B-A4B's 4096 tokens with a scale of its own, and past the
    # rule, where the two kernels run
    ('attention_latent_fwd_bwd', _latent_attention, _latent_specs(8192),
     _LATENT_ONE),
    ('attention_latent_fwd_bwd_t4096_scale', lambda *a: _latent_attention(
        *a, scale=0.1147), _latent_specs(4096), _LATENT_ONE),
    ('attention_latent_fwd_bwd_t65536', _latent_attention,
     _latent_specs(65536), _LATENT_TWO),
    # and its held experts: 16 of 2048 x 768, top 6, a buffer of
    # 8192 x 6 + 16 x 128 rows
    ('moe_expert_matmul_768x16', lambda x, w, t, n: pk.grouped_matmul(
        x, w, t, n), [((51200, 2048), BF16), ((16, 2048, 768), BF16),
                      ((400,), I32), ((1,), I32)]),
    ('moe_expert_matmul_768x16_transposed',
     lambda x, w, t, n: pk.grouped_matmul(x, w, t, n, transpose_w=True),
     [((51200, 2048), BF16), ((16, 768, 2048), BF16), ((400,), I32),
      ((1,), I32)]),
    ('moe_expert_matmul_768x16_dw', pk.grouped_matmul_dw,
     [((51200, 2048), BF16), ((51200, 768), BF16), ((400,), I32),
      ((1,), I32), ((1,), I32), ((16, 2048, 768), F32)]),
    # LFM2-24B-A2B's held experts: 16 of 2048 x 1536, top 4, a buffer of
    # 8192 x 4 + 16 x 128 rows
    ('moe_expert_matmul_1536x16', lambda x, w, t, n: pk.grouped_matmul(
        x, w, t, n), [((34816, 2048), BF16), ((16, 2048, 1536), BF16),
                      ((272,), I32), ((1,), I32)]),
    ('moe_expert_matmul_1536x16_dw', pk.grouped_matmul_dw,
     [((34816, 2048), BF16), ((34816, 1536), BF16), ((272,), I32),
      ((1,), I32), ((1,), I32), ((16, 2048, 1536), F32)]),
    # Xing4.0-29B-A4B's, the widest float32 block: 8 of 3584 x 1024, top 4,
    # a buffer of 4096 x 4 + 8 x 128 rows
    ('moe_expert_matmul_3584x8_dw', pk.grouped_matmul_dw,
     [((17408, 3584), BF16), ((17408, 1024), BF16), ((136,), I32),
      ((1,), I32), ((1,), I32), ((8, 3584, 1024), F32)]),
    # the way back from the sorted rows to the tokens, a pass's rows into the
    # float32 sum of the whole sequence: SDAR-30B-A3B-Chat's 18432 rows a
    # pass into 8192 x 2048 (bfloat16 rows with their weights: the output;
    # float32 rows: dx), Laguna's 6144 into 8192 x 3072, Xing4.0's 5120 into
    # 4096 x 3584
    ('moe_rows_to_tokens_2048_scaled', pk.rows_to_tokens,
     [((18432, 2048), BF16), ((18432,), I32), ((1,), I32), ((1,), I32),
      ((8192, 2048), F32), ((18432,), F32)]),
    ('moe_rows_to_tokens_2048', pk.rows_to_tokens,
     [((18432, 2048), F32), ((18432,), I32), ((1,), I32), ((1,), I32),
      ((8192, 2048), F32)]),
    ('moe_rows_to_tokens_3072_scaled', pk.rows_to_tokens,
     [((6144, 3072), BF16), ((6144,), I32), ((1,), I32), ((1,), I32),
      ((8192, 3072), F32), ((6144,), F32)]),
    ('moe_rows_to_tokens_3584', pk.rows_to_tokens,
     [((5120, 3584), F32), ((5120,), I32), ((1,), I32), ((1,), I32),
      ((4096, 3584), F32)]),
    # the stream-mixing kernels at Xing4.0-29B-A4B's widths: 4 streams of
    # 3584, one 4096-token sequence, 32 coefficient columns
    ('hyper_pre_fwd', lambda x, w, a, b: pk.hyper_pre_forward(
        x, w, a, b, 4, 1e-6), [_HX, _HW, _HR, _HR]),
    ('hyper_pre_bwd', lambda x, w, a, c, dy, dc, gx: pk.hyper_pre_backward(
        x, w, a, c, dy, dc, gx, 4), [_HX, _HW, _HR, _HC, _HY, _HC, _HX]),
    ('hyper_post_fwd', lambda x, z, c: pk.hyper_post_forward(x, z, c, 4),
     [_HX, _HY, _HC]),
    ('hyper_post_bwd', lambda g, x, z, c: pk.hyper_post_backward(
        g, x, z, c, 4), [_HX, _HX, _HY, _HC]),
]


@pytest.mark.parametrize('name,fn,specs,kernels',
                         [k if len(k) == 4 else k + ((),) for k in KERNELS],
                         ids=[k[0] for k in KERNELS])
def test_kernel_compiles_for_v5e(one_chip, name, fn, specs, kernels):
    compiled = _compile(fn, one_chip, *specs, kernels=kernels)
    mem = compiled.memory_analysis()
    assert mem is not None and mem.temp_size_in_bytes >= 0


def test_delta_rule_kernels_compile_for_v5e(one_chip):
    """Olmo-Hybrid-7B's linear-attention layer: 30 heads of 96 key and 192
    value columns (neither a multiple of the 128 lanes: the operands are by
    head, [B, H, T, D]), one 4096-row sequence in chunks of 64. The chain:
    the state [96, 192] float32 of two heads in VMEM across a sequence's
    grid steps, forward and, the chunks walked from the end, backward.
    Beside it the three kernels of a chunk's state-free part (the solve,
    the chain's operands, their cotangents), which leave XLA no product
    of a chunk's [64, 64] blocks (the parent's program had 18)."""
    def fwd_bwd(q, k, v, g, beta, do):
        (o, smax), vjp = jax.vjp(pk.delta_rule, q, k, v, g, beta)
        return o, smax, vjp((do, jnp.zeros_like(smax)))

    B, H, L, dk, dv = 1, 30, 4096, 96, 192
    text = _compile(
        fwd_bwd, one_chip, ((B, H, L, dk), F32), ((B, H, L, dk), F32),
        ((B, H, L, dv), BF16), ((B, H, L), F32), ((B, H, L), F32),
        ((B, H, L, dv), BF16)).as_text()
    assert set(re.findall(r'delta_rule_((?:chunk_)?(?:solve|fwd|bwd))\b',
                          text)) == {'solve', 'chunk_fwd', 'chunk_bwd',
                                     'fwd', 'bwd'}
    assert not re.findall(r'\[[\d,]*64,64\]\S* (?:convolution|dot)\(', text)


def test_registry_ops_take_the_kernel_when_lowered_for_tpu(one_chip):
    """ops/nn.py picks by the platform being lowered for, not by
    jax.default_backend() (the CPU here): the same registry function is
    the Pallas kernel in a TPU program and plain jnp in a CPU one."""
    from mxnet_tpu.ops.registry import get
    ln = get('LayerNorm').fn
    fn = lambda x, g, b: ln({}, x, g, b)  # noqa: E731
    _compile(fn, one_chip, ((256, 1024), BF16), ((1024,), F32),
             ((1024,), F32))
    x = jnp.ones((8, 32), jnp.float32)
    cpu_text = jax.jit(fn).lower(x, jnp.ones(32), jnp.zeros(32)) \
        .compile().as_text()
    assert 'tpu_custom_call' not in cpu_text


def test_row_too_wide_for_vmem_raises_with_shapes(one_chip):
    """Rows of 131072 f32 do not fit even an 8-row block: refused when
    lowered for the chip, through the kernel and through the registry op
    that routes to it; the same symbol binds and runs on the CPU mesh
    (an LM head with a 50k vocabulary trains there)."""
    from mxnet_tpu.ops.registry import get
    sm = get('softmax').fn
    specs = [((16, 1 << 17), F32), ((16,), I32)]
    for fn, sp in ((pk.softmax_xent, specs),
                   (lambda x: sm({}, x), specs[:1])):
        with pytest.raises(ValueError, match=r'rows of 131072 elements'):
            _compile(fn, one_chip, *sp)
        args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in sp]
        assert 'tpu_custom_call' not in jax.jit(fn).lower(*args).as_text()


def test_short_conv_off_the_lanes_raises_with_shapes(one_chip):
    """64 channels: the thirds of a block would start inside a tile of 128
    lanes. Refused with the shapes when lowered for the chip; the
    interpreter runs it (tests/unittest/test_hybrid_ops.py)."""
    fn = lambda x, w: pk.short_conv_forward(x, w)  # noqa: E731
    with pytest.raises(ValueError, match=r'short_conv: 64 channels '
                                         r'\(operand \(1, 256, 192\) '
                                         r'bfloat16\)'):
        _compile(fn, one_chip, ((1, 256, 192), BF16), ((64, 3), BF16))
    args = [jax.ShapeDtypeStruct((1, 256, 192), BF16),
            jax.ShapeDtypeStruct((64, 3), BF16)]
    assert 'tpu_custom_call' not in jax.jit(fn).lower(*args).as_text()


def test_rmsnorm_backward_kernel_in_a_compiled_step_scope_map(one_chip):
    """A small block's training step compiled for the v5e, walked by the
    scope map: the call of `fused_rmsnorm_bwd` is an instruction of that
    name under RMSNorm `bwd`; under `refwd` (the node lies in a mirrored
    stage) and `fwd` stands the forward kernel alone."""
    from mxnet_tpu.telemetry import programs
    from test_scope_map import norm_block_step
    step, wrt, nodes = norm_block_step()
    specs = tuple(jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=one_chip)
                  for w in wrt)
    compiled = jax.jit(step).lower(specs).compile()
    m = programs.scope_map(programs._hlo_text(compiled), nodes)
    calls = sorted((name.rsplit('.', 1)[0], v[0], v[1])
                   for name, v in m['instrs'].items()
                   if v[3] == 'custom-call' and 'rmsnorm' in name)
    assert calls == [('fused_rmsnorm', 'norm', 'fwd'),
                     ('fused_rmsnorm', 'norm', 'refwd'),
                     ('fused_rmsnorm_bwd', 'norm', 'bwd')], calls
    assert m['nodes']['norm'] == 'RMSNorm'


def test_row_block_follows_width():
    blk = lambda d: pk._row_block('t', 256, np.zeros((0, d), np.float32))[0]  # noqa: E731
    assert blk(1024) == 256 and blk(4096) == 128
    assert blk(16384) == 32 and blk(32000) == 16 and blk(65536) == 8
