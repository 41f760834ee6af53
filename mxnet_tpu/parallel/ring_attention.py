"""Ring / blockwise attention — sequence & context parallelism.

The reference's only long-sequence mechanisms are bucketing and truncated
BPTT (SURVEY.md §5.7: BucketingModule, docs/how_to/bucketing.md) — memory
still scales with full sequence length on one device. This module is the
greenfield TPU answer: shard the sequence axis across the ``sp`` mesh
axis and stream K/V blocks around the ring with ``lax.ppermute``, keeping
a numerically-stable running softmax (flash-attention style log-sum-exp
accumulation) so no device ever materialises the full [T, T] score matrix.

Three interchangeable kernels:
- :func:`blockwise_attention` — single-device, K/V blocked via lax.scan
  (memory-efficient attention; the intra-device half of ring attention).
- :func:`ring_attention`     — sp-sharded, ppermute ring (call inside
  shard_map over the ``sp`` axis).
- :func:`ulysses_attention`  — sp-sharded via two all_to_alls (heads↔seq
  transpose), exact and cheap when head count ≥ sp size.

Shapes follow [batch, seq, heads, head_dim] throughout.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ['ring_attention', 'blockwise_attention', 'ulysses_attention',
           'striped_attention', 'stripe_layout', 'unstripe_layout',
           'make_ring_attention', 'attention_reference']

_NEG = -1e30


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain softmax attention — the correctness oracle for the kernels."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum('bqhd,bkhd->bhqk', q * scale, k)
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


def _block_accum(q, k, v, carry, scale, mask=None):
    """One flash step: fold a K/V block into (acc, running_max, denom).

    q: [B,Tq,H,D]; k,v: [B,Tk,H,D]; acc: [B,Tq,H,D]; m,l: [B,H,Tq]."""
    acc, m, l = carry
    s = jnp.einsum('bqhd,bkhd->bhqk', q * scale, k)
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # renormalise previous accumulator to the new max
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])            # [B,H,Tq,Tk]
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum('bhqk,bkhd->bqhd', p, v)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return acc_new, m_new, l_new


def _finalize(acc, l):
    l = jnp.maximum(l, 1e-30)                    # fully-masked rows → 0 output
    return acc / l.transpose(0, 2, 1)[..., None]


def blockwise_attention(q, k, v, block_size=512, causal=False, scale=None):
    """Memory-efficient attention: lax.scan over K/V blocks.

    Peak memory O(Tq·block) instead of O(Tq·Tk); same math as
    attention_reference to fp tolerance."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    block = min(block_size, Tk)
    if Tk % block:
        raise ValueError('Tk %d not divisible by block %d' % (Tk, block))
    nblk = Tk // block
    kb = k.reshape(B, nblk, block, H, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block, H, D).transpose(1, 0, 2, 3, 4)

    # queries align to the END of the key sequence (decode convention),
    # matching attention_reference's tril(..., Tk - Tq)
    qpos = jnp.arange(Tq) + (Tk - Tq)

    def scan_fn(carry, inp):
        i, kblk, vblk = inp
        mask = None
        if causal:
            kpos = i * block + jnp.arange(block)
            mask = qpos[:, None] >= kpos[None, :]          # [Tq, block]
            mask = mask[None, None]                        # [1,1,Tq,block]
        return _block_accum(q, kblk, vblk, carry, scale, mask), None

    init = (jnp.zeros_like(q),
            jnp.full((B, H, Tq), _NEG, q.dtype),
            jnp.zeros((B, H, Tq), q.dtype))
    (acc, m, l), _ = lax.scan(scan_fn, init, (jnp.arange(nblk), kb, vb))
    return _finalize(acc, l)


def ring_attention(q, k, v, axis='sp', causal=False, scale=None,
                   use_flash=True, block_q=128, block_k=128):
    """Ring attention over the ``axis`` mesh axis (call under shard_map).

    Each device holds the local sequence chunk of q/k/v
    [B, T/sp, H, D]. K/V chunks rotate around the ring; after sp steps
    every q chunk has attended to the full sequence. Communication is
    sp-1 ppermutes of the local K/V — bandwidth-optimal and overlapped
    with compute by XLA (latency hiding via the ring schedule).

    The local q×chunk block runs on the blockwise attention kernels
    (ops/pallas_kernels.flash_attention_lse, forward and backward —
    online softmax over K/V blocks in VMEM, a chunk of any length);
    per-chunk normalized outputs are merged exactly via the kernel's
    log-sum-exp. Pass ``use_flash=False`` for the plain-jnp accumulator
    (used as the cross-check oracle in tests).

    causal=True assumes chunks are laid out in sequence order along the
    axis (chunk c owns positions [c*T_local, (c+1)*T_local)).
    """
    B, Tl, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    qpos = jnp.arange(Tl)

    if use_flash:
        from ..ops.pallas_kernels import flash_attention_lse

        def body(step, carry):
            kk, vv, acc, m, l = carry
            src = (my - step) % n                 # whose chunk we hold now
            if causal:
                # diagonal chunk: causal flash; earlier chunks: full
                # attention; later chunks: computed then discarded (w=0)
                o, lse = lax.cond(
                    src == my,
                    lambda: flash_attention_lse(q, kk, vv, True, scale,
                                                block_q, block_k),
                    lambda: flash_attention_lse(q, kk, vv, False, scale,
                                                block_q, block_k))
                valid = src <= my
                lse = jnp.where(valid, lse, _NEG)
            else:
                valid = True
                o, lse = flash_attention_lse(q, kk, vv, False, scale,
                                             block_q, block_k)
            # exact merge of normalized chunk outputs via their lse
            m_new = jnp.maximum(m, lse)
            corr = jnp.exp(m - m_new)
            w = jnp.exp(lse - m_new)              # [B,H,Tl]
            # a discarded chunk meeting a still-empty accumulator gives
            # exp(_NEG - _NEG) = 1: force its weight to zero explicitly
            w = jnp.where(valid, w, 0.0)
            acc = (acc * corr.transpose(0, 2, 1)[..., None] +
                   o * w.transpose(0, 2, 1)[..., None])
            l = l * corr + w
            kk = lax.ppermute(kk, axis, perm)
            vv = lax.ppermute(vv, axis, perm)
            return kk, vv, acc, m_new, l
    else:
        def body(step, carry):
            kk, vv, acc, m, l = carry
            src = (my - step) % n                 # whose chunk we hold now
            if causal:
                # block-level causal: q chunk `my` vs k chunk `src`
                kpos = jnp.arange(Tl)
                gq = my * Tl + qpos               # global positions
                gk = src * Tl + kpos
                mask = (gq[:, None] >= gk[None, :])[None, None]
            else:
                mask = None
            acc, m, l = _block_accum(q, kk, vv, (acc, m, l), scale, mask)
            kk = lax.ppermute(kk, axis, perm)
            vv = lax.ppermute(vv, axis, perm)
            return kk, vv, acc, m, l

    init = (k, v,
            jnp.zeros_like(q),
            jnp.full((B, H, Tl), _NEG, q.dtype),
            jnp.zeros((B, H, Tl), q.dtype))
    _, _, acc, m, l = lax.fori_loop(0, n, body, init)
    return _finalize(acc, l)


def ulysses_attention(q, k, v, axis='sp', causal=False, scale=None):
    """DeepSpeed-Ulysses style: all_to_all seq↔heads so each device holds
    ALL positions for H/sp heads, runs plain attention, transposes back.
    Exact; needs H divisible by the axis size. Call under shard_map."""
    # [B, T/sp, H, D] -> [B, T, H/sp, D]
    q = lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
    o = attention_reference(q, k, v, causal=causal, scale=scale)
    return lax.all_to_all(o, axis, split_axis=1, concat_axis=2, tiled=True)


def make_ring_attention(mesh, axis='sp', causal=False, impl='ring', scale=None):
    """shard_map-wrapped callable on full arrays: shards q/k/v on the
    sequence dim over `axis`, runs the chosen kernel, unshards nothing
    (output stays sequence-sharded, matching the input layout)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    fn = {'ring': ring_attention, 'ulysses': ulysses_attention,
          'striped': striped_attention}[impl]
    spec = P(None, axis, None, None)

    @functools.partial(shard_map, mesh=mesh.mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def apply(q, k, v):
        return fn(q, k, v, axis=axis, causal=causal, scale=scale)
    return apply


def stripe_layout(x, sp, axis=1):
    """Reorder the sequence axis so CONTIGUOUS sharding over ``sp``
    devices yields the striped (round-robin) layout: shard s holds
    global positions s, s+sp, s+2sp, ... (Striped Attention, Brandon et
    al. 2023, arXiv:2311.09431). Apply before shard_map, invert with
    :func:`unstripe_layout`."""
    T = x.shape[axis]
    if T % sp != 0:
        raise ValueError('sp (%d) must divide the sequence length (%d)'
                         % (sp, T))
    shape = list(x.shape)
    # [..., T, ...] -> [..., T//sp, sp, ...] -> [..., sp, T//sp, ...]
    x = x.reshape(shape[:axis] + [T // sp, sp] + shape[axis + 1:])
    x = jnp.swapaxes(x, axis, axis + 1)
    return x.reshape(shape)


def unstripe_layout(x, sp, axis=1):
    """Inverse of :func:`stripe_layout`."""
    T = x.shape[axis]
    shape = list(x.shape)
    x = x.reshape(shape[:axis] + [sp, T // sp] + shape[axis + 1:])
    x = jnp.swapaxes(x, axis, axis + 1)
    return x.reshape(shape)


def striped_attention(q, k, v, axis='sp', causal=True, scale=None):
    """Striped ring attention (Brandon et al. 2023): with the
    round-robin token layout (:func:`stripe_layout`), every ring step
    computes a near-triangular block, so causal work is load-balanced
    across the ring — the contiguous-chunk schedule leaves early
    devices idle for late chunks and vice versa.

    Mask per step (device ``my`` holding k-chunk from ``src``): global
    positions are ``gq_i = my + sp*i``, ``gk_j = src + sp*j``, so
    ``gq_i >= gk_j`` reduces to ``i >= j`` when ``src <= my`` and
    ``i > j`` otherwise. Call under shard_map, inputs in striped
    layout."""
    B, Tl, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    i = jnp.arange(Tl)
    tri_incl = (i[:, None] >= i[None, :])[None, None]
    tri_strict = (i[:, None] > i[None, :])[None, None]

    def body(step, carry):
        kk, vv, acc, m, l = carry
        src = (my - step) % n
        mask = jnp.where(src <= my, tri_incl, tri_strict) if causal \
            else None
        acc, m, l = _block_accum(q, kk, vv, (acc, m, l), scale, mask)
        kk = lax.ppermute(kk, axis, perm)
        vv = lax.ppermute(vv, axis, perm)
        return kk, vv, acc, m, l

    init = (k, v,
            jnp.zeros_like(q),
            jnp.full((B, H, Tl), _NEG, q.dtype),
            jnp.zeros((B, H, Tl), q.dtype))
    _, _, acc, m, l = lax.fori_loop(0, n, body, init)
    return _finalize(acc, l)
