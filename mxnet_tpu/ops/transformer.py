"""Decoder-block ops: RMSNorm, rotary positions, grouped-query attention
(causal, optionally windowed, with a per-head output gate), latent
attention in its expanded form (keys wider than values, one rotary key
head for all heads), the gated short convolution of a conv-attention
hybrid, the gated MLP and a routed expert layer with or without a shared
expert (softmax router, or sigmoid scores with a selection bias).

Each is a pure JAX function like every op of the registry; gradients come
from autodiff or, where a kernel runs, from a ``custom_vjp``. Activations
are ``[batch, sequence, features]``. Weights of projections are the
registry's ``FullyConnected`` layout, ``(out, in)``; the experts held are
one array per projection, ``(experts_held, in, out)``.

Precision: matrix products take their operands as they come (bfloat16 in
a ``float16`` symbol under MXTPU_F16_AS_BF16) and accumulate in float32;
RMSNorm statistics, rotary angles, attention's softmax, the gate's
sigmoid, router logits and router softmax are float32.

Where an op has a kernel (``ops/pallas_kernels.py``: attention forward and
backward by blocks, at any head size: heads narrower than 128 lanes cross
the compiled kernels as ``[B, H, T, D]``; the grouped product of the
experts; the short convolution's two passes), operands on a TPU
take it and others take the plain jnp form, unless MXTPU_FORCE_PALLAS=1
routes every platform through the kernel (interpreted off the TPU), as for
the other registry ops.
"""
import functools
import math

import jax
import jax.numpy as jnp

from . import pallas_kernels as pk
from .registry import dear, get as registry_get, register

__all__ = ['MOE_STATS', 'moe_stat_names', 'HYPER_STATS', 'hyper_stat_names',
           'DELTA_STATS', 'delta_stat_names']


def _matmul(x, w):
    """x [..., in] times w (out, in): float32 accumulation, float32 out."""
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

@register('RMSNorm', input_names=['data', 'gamma'],
          param_defaults={'eps': 1e-6})
def _rms_norm(attrs, x, gamma):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis; statistics in
    float32, x's dtype out."""
    eps = float(attrs.get('eps', 1e-6))

    def plain(x, gamma):
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (x32 * inv * gamma.astype(jnp.float32)).astype(x.dtype)

    return pk.dispatch(lambda x, g: pk.fused_rmsnorm(x, g, eps), plain,
                       x, gamma)


# ---------------------------------------------------------------------------
# Rotary positions
# ---------------------------------------------------------------------------

def rope_inv_freq(head_dim, base, rotary_dim, scaling, factor, original_len,
                  beta_fast, beta_slow, attention_factor):
    """(inverse frequencies of the rotated pairs, factor on cos and sin).
    ``scaling`` 'default': the plain form; 'yarn': interpolated and
    extrapolated frequencies blended between the two correction
    dimensions (Peng et al., arXiv:2309.00071)."""
    dim = int(rotary_dim) or int(head_dim)
    pos = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if scaling == 'default':
        return 1.0 / pos, 1.0
    if scaling != 'yarn':
        raise ValueError('RotaryEmbedding: scaling %r' % (scaling,))

    def correction_dim(rotations):
        return dim * math.log(original_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv = ramp / (factor * pos) + (1.0 - ramp) / pos
    if not attention_factor:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv, float(attention_factor)


@register('RotaryEmbedding',
          param_defaults={'num_heads': 1, 'base': 10000.0, 'rotary_dim': 0,
                          'scaling': 'default', 'factor': 1.0,
                          'original_max_position': 0, 'beta_fast': 32.0,
                          'beta_slow': 1.0, 'attention_factor': 0.0,
                          'interleaved': False, 'period': 0})
def _rotary(attrs, x):
    """Rotary positions 0..T-1 on x [B, T, num_heads * D]: the first
    ``rotary_dim`` dimensions of each head (all of them if 0) are rotated,
    half against half, or with ``interleaved`` dimension 2i against 2i + 1;
    the rest pass through. ``period`` p > 0: positions restart every p
    rows (row r sits at r mod p: the copies of one sequence laid end to
    end share their positions)."""
    B, T, HD = x.shape
    H = int(attrs.get('num_heads', 1))
    D = HD // H
    inv, scale = rope_inv_freq(
        D, float(attrs.get('base', 10000.0)), int(attrs.get('rotary_dim', 0)),
        str(attrs.get('scaling', 'default')), float(attrs.get('factor', 1.0)),
        float(attrs.get('original_max_position', 0) or 1),
        float(attrs.get('beta_fast', 32.0)),
        float(attrs.get('beta_slow', 1.0)),
        float(attrs.get('attention_factor', 0.0)))
    half = inv.shape[0]
    period = int(attrs.get('period', 0))
    if period:
        with jax.named_scope('blockdiff_mask'):
            pos = (jnp.arange(T, dtype=jnp.int32) % period) \
                .astype(jnp.float32)
    else:
        pos = jnp.arange(T, dtype=jnp.float32)
    angle = pos[:, None] * inv[None]
    cos = (jnp.cos(angle) * scale)[None, :, None, :]
    sin = (jnp.sin(angle) * scale)[None, :, None, :]
    x4 = x.reshape(B, T, H, D).astype(jnp.float32)
    if attrs.get('interleaved', False):
        pairs = x4[..., :2 * half].reshape(B, T, H, half, 2)
        x1, x2, rest = pairs[..., 0], pairs[..., 1], x4[..., 2 * half:]
        turned = [jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                            axis=-1).reshape(B, T, H, 2 * half)]
    else:
        x1, x2, rest = (x4[..., :half], x4[..., half:2 * half],
                        x4[..., 2 * half:])
        turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    out = jnp.concatenate(turned + [rest], axis=-1)
    return out.astype(x.dtype).reshape(B, T, HD)


# ---------------------------------------------------------------------------
# Grouped-query attention
# ---------------------------------------------------------------------------

def block_diffusion_mask(L, B):
    """[2 L, 2 L] mask over [noisy ; clean], blocks of B positions: a
    noisy row sees its own noisy block and the clean blocks strictly before
    it, a clean row the clean blocks up to and including its own."""
    r = jnp.arange(2 * L)
    clean, blk = r >= L, (r % L) // B
    return (clean[None, :] & (blk[None, :] < blk[:, None])) \
        | ((clean[None, :] == clean[:, None])
           & (blk[None, :] == blk[:, None]))


def _dense_attention(q, k, v, heads, kv_heads, window, block_length=0):
    """The plain form: one dense masked product. For small shapes off the
    TPU."""
    B, T, HD = q.shape
    D, group = HD // heads, heads // kv_heads
    q5 = q.reshape(B, T, kv_heads, group, D).astype(jnp.float32)
    k4 = k.reshape(B, T, kv_heads, D).astype(jnp.float32)
    v4 = v.reshape(B, T, kv_heads, D).astype(jnp.float32)
    s = jnp.einsum('bqkgd,bskd->bkgqs', q5, k4) * D ** -0.5
    if block_length:
        seen = block_diffusion_mask(T // 2, block_length)
    else:
        rows, cols = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        seen = cols <= rows
        if window:
            seen &= cols > rows - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    out = jnp.einsum('bkgqs,bskd->bqkgd', p, v4)
    return out.reshape(B, T, HD).astype(q.dtype)


@register('GroupedQueryAttention',
          input_names=['query', 'key', 'value', 'gate'],
          param_defaults={'num_heads': 1, 'num_kv_heads': 1, 'window': 0,
                          'gated': False, 'block_length': 0},
          optional_inputs={'gate': 'gated'})
def _gqa(attrs, q, k, v, gate=None):
    """Causal attention of query [B, T, H * D] over key and value
    [B, T, KV * D]: query head i reads key/value head i // (H / KV),
    scores q k^T / sqrt(D), position t sees s <= t and, with ``window``
    w > 0, s > t - w. ``gated``: head i's output is multiplied by
    sigmoid(gate[..., i]), gate [B, T, H]. Returns [B, T, H * D].

    ``block_length`` B > 0 is the block-diffusion mask in place of the
    causal one: the T = 2 L rows are a noisy and a clean copy of L
    positions, [noisy ; clean], in blocks of B (B divides L); a noisy row
    sees its own noisy block in both directions and the clean blocks
    strictly before it, a clean row the clean blocks up to and including its
    own, nothing another block's noise (:func:`block_diffusion_mask`;
    arXiv:2503.09573). No window, no gate.

    On a TPU the blockwise kernels run it, forward and backward, named
    ``attention_window_*``, ``attention_full_*`` or
    ``attention_blockdiff_*`` in a device trace; no [T, T] array exists, a
    windowed layer walks only the blocks inside its window and a
    block-diffusion layer only the kernel blocks its mask does not empty
    (gauges ``attention.blockdiff.pairs_needed`` / ``pairs_visited``)."""
    H, KV = int(attrs['num_heads']), int(attrs['num_kv_heads'])
    window = int(attrs.get('window', 0))
    block_length = int(attrs.get('block_length', 0))
    if block_length:
        T = q.shape[1]
        if block_length < 1 or T % 2 or (T // 2) % block_length or window \
                or attrs.get('gated', False):
            raise ValueError(
                'GroupedQueryAttention(block_length=%d): %d rows are not '
                'two halves of whole blocks of that length, or a window or '
                'a gate was asked for' % (block_length, T))
        block, name = 512, 'attention_blockdiff'
        from .. import telemetry as _tele
        if _tele.enabled():
            needed, visited = pk.block_diffusion_pairs(T // 2, block_length)
            _tele.gauge('attention.blockdiff.pairs_needed').set(needed)
            _tele.gauge('attention.blockdiff.pairs_visited').set(visited)
    else:
        # blocks: a window is walked in blocks of half its size (so that
        # the blocks outside it are at most a third of those walked), full
        # attention in blocks of 512
        block = max(128, min(512, window // 2)) if window else 512
        name = 'attention_window' if window else 'attention_full'

    def fused(q, k, v):
        return pk.blockwise_attention(q, k, v, H, KV, True, window, None,
                                      block, block, name, block_length)

    def plain(q, k, v):
        return _dense_attention(q, k, v, H, KV, window, block_length)

    # the operands are what the backward kernel reads beside the forward
    # kernel's output (residuals of its custom_vjp, live in the backward
    # pass whatever happens): a mirrored stage that keeps them runs the
    # projections and the rotary turns behind them once
    q, k, v = dear(q, name + '_q'), dear(k, name + '_k'), dear(v, name + '_v')
    out = pk.dispatch(fused, plain, q, k, v)
    if gate is not None and attrs.get('gated', False):
        B, T, HD = out.shape
        with jax.named_scope('gate'):
            g = jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
            out = (out.reshape(B, T, H, HD // H).astype(jnp.float32) * g) \
                .astype(out.dtype).reshape(B, T, HD)
    return out


# ---------------------------------------------------------------------------
# Latent attention, expanded form
# ---------------------------------------------------------------------------

def _dense_latent_attention(q_nope, q_rope, k_nope, k_rope, v, heads,
                            scale=None):
    """The plain form: one dense masked product, float32. For small shapes
    off the TPU."""
    B, T, _ = q_nope.shape
    f32 = lambda x, n: x.reshape(B, T, n, -1).astype(jnp.float32)  # noqa
    qn, qr, kn, v4 = (f32(q_nope, heads), f32(q_rope, heads),
                      f32(k_nope, heads), f32(v, heads))
    kr = k_rope.astype(jnp.float32)
    s = (jnp.einsum('bqhd,bshd->bhqs', qn, kn)
         + jnp.einsum('bqhd,bsd->bhqs', qr, kr)) \
        * (scale or (qn.shape[-1] + qr.shape[-1]) ** -0.5)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    out = jnp.einsum('bhqs,bshd->bqhd', p, v4)
    return out.reshape(B, T, -1).astype(v.dtype)


@register('LatentAttention',
          input_names=['q_nope', 'q_rope', 'k_nope', 'k_rope', 'value'],
          param_defaults={'num_heads': 1, 'scale': 0.0})
def _latent_attention(attrs, q_nope, q_rope, k_nope, k_rope, v):
    """Causal multi-head latent attention as training computes it, with the
    keys and values expanded from the latent: q_nope and k_nope
    [B, T, H * Dn], q_rope [B, T, H * Dr] and k_rope [B, T, Dr], the one
    rotary key head every query head reads, value [B, T, H * Dv]; the head
    sizes are read from the widths. Head h's score is
    ``(q_nope_h k_nope_h^T + q_rope_h k_rope^T) / sqrt(Dn + Dr)``,
    position t sees s <= t; ``scale`` other than 0 takes the place of
    ``1 / sqrt(Dn + Dr)`` (a family whose rotary scaling also scales the
    scores). Returns [B, T, H * Dv].

    On a TPU the kernels ``attention_latent_fwd`` and ``_bwd`` run it
    (``_dq`` and ``_dkv`` for a sequence whose gradients do not fit VMEM):
    no [T, T] array exists and the rotary key is never broadcast."""
    H = int(attrs['num_heads'])
    scale = float(attrs.get('scale', 0.0)) or None

    def fused(*operands):
        return pk.latent_attention(*operands, H, 512, 512,
                                   'attention_latent', scale)

    def plain(*operands):
        return _dense_latent_attention(*operands, H, scale)

    # as GroupedQueryAttention, for the queries and the one rotary key
    # head. k_nope and value stay recomputed: they are the op's expansion
    # of a latent an eighth their size, one projection behind them
    q_nope, q_rope, k_rope = (dear(q_nope, 'attention_latent_q_nope'),
                              dear(q_rope, 'attention_latent_q_rope'),
                              dear(k_rope, 'attention_latent_k_rope'))
    return pk.dispatch(fused, plain, q_nope, q_rope, k_nope, k_rope, v)


# ---------------------------------------------------------------------------
# Hyper-connections: n residual streams mixed by per-token coefficients
# ---------------------------------------------------------------------------
# Manifold-constrained hyper-connections (arXiv:2512.24880). The residual of
# a token is X [n, d], carried as [B, T, n * d]. A sublayer F reads
# y = sum_j H_pre[j] X[j] and writes X'[i] = H_post[i] F(..y..) + sum_j
# M[i, j] X[j], with H_pre = sigmoid(.), H_post = 2 sigmoid(.) and M the
# projection of exp(clip(.)) onto the doubly stochastic matrices by
# alternating column and row normalisation; the arguments are
# alpha * (vec(X) W^T) / rms(vec(X)) + bias, float32.

# what HyperPre writes into its ``stats`` auxiliary state each step
HYPER_STATS = ('res_dev_max',)


def hyper_stat_names(symbol):
    """Names of the auxiliary states that the HyperPre nodes of `symbol`
    write their per-step statistics into, in graph order."""
    at = registry_get('HyperPre').input_names.index('stats')
    return [node.inputs[at][0].name for node in symbol._topo()
            if not node.is_variable() and node.op == 'HyperPre']


def _hyper_rows_of(w, bias, alpha, groups):
    """(w padded to HYPER_COLS rows, alpha's and bias's [1, HYPER_COLS]
    float32 rows): coefficient k of group g is scaled by alpha[g]."""
    K = w.shape[0]
    pad = pk.HYPER_COLS - K
    a = jnp.concatenate([jnp.broadcast_to(alpha[g].astype(jnp.float32),
                                          (size,))
                         for g, size in enumerate(groups)])
    return (jnp.pad(w, ((0, pad), (0, 0))),
            jnp.pad(a, (0, pad)).reshape(1, -1),
            jnp.pad(bias.astype(jnp.float32).reshape(-1), (0, pad))
            .reshape(1, -1))


def _hyper_pre_plain(x, w, a_row, b_row, n, eps):
    """pk.hyper_pre in plain jnp: (y, c, x)."""
    d = x.shape[1] // n
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    c = _matmul(x, w) * r * a_row + b_row
    hp = jax.nn.sigmoid(c)
    y = sum(hp[:, j:j + 1] * x32[:, j * d:(j + 1) * d] for j in range(n))
    c = c.at[:, -1].set(r[:, 0])        # as the kernel: 1 / rms rides here
    return y.astype(x.dtype), c, x


def _hyper_pre(x, w, bias, alpha, n, eps, groups):
    """(y [R, d], c [R, HYPER_COLS], x) for rows x [R, n * d]."""
    w, a_row, b_row = _hyper_rows_of(w, bias, alpha, groups)
    return pk.dispatch(
        lambda *ops: pk.hyper_pre(*ops, n, eps),
        lambda *ops: _hyper_pre_plain(*ops, n, eps), x, w, a_row, b_row)


def sinkhorn(m, iters, eps):
    """m [n, n, R], positive: `iters` times divided by its column sums +
    eps and then by its row sums + eps (a token a lane). One traced round
    in a ``lax.scan``: unrolled, the 13 nodes of a six-block model took
    its training step 145 s to lower and compile for a v5e, and 68 so."""
    def one(m, _):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=1, keepdims=True) + eps), None

    return jax.lax.scan(one, m, None, length=iters)[0]


def _hyper_coefficients(c, n, iters, eps, lo, hi):
    """(coef [R, HYPER_COLS]: H_post in columns [0, n), M_T row-major in
    [n, n + n^2), zeros after; the largest distance of M_T's row and
    column sums from 1) from the arguments c [R, HYPER_COLS]."""
    with jax.named_scope('coef'):
        ct = c.T                                    # a token a lane
        h_post = 2.0 * jax.nn.sigmoid(ct[n:2 * n])
        m0 = jnp.exp(jnp.clip(ct[2 * n:2 * n + n * n], lo, hi)) \
            .reshape(n, n, -1)
    with jax.named_scope('sinkhorn'):
        m = sinkhorn(m0, iters, eps)
    with jax.named_scope('coef'):
        dev = jnp.maximum(jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0)),
                          jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0)))
        coef = jnp.concatenate(
            [h_post, m.reshape(n * n, -1),
             jnp.zeros((pk.HYPER_COLS - n - n * n, c.shape[0]),
                       jnp.float32)])
        return coef.T, jax.lax.stop_gradient(dev)


@register('HyperPre',
          input_names=['data', 'weight', 'bias', 'alpha', 'stats'],
          param_defaults={'n': 4, 'eps': 1e-6, 'sinkhorn_iters': 20,
                          'sinkhorn_eps': 1e-6, 'clamp_min': -30.0,
                          'clamp_max': 30.0},
          num_outputs=4, num_visible_outputs=3, mutate_inputs={4: 3},
          aux_inputs=('stats',))
def _hyper_pre_op(attrs, x, w, bias, alpha, stats):
    """What a sublayer reads of ``n`` residual streams, and the coefficients
    it will write with. data [B, T, n * d]; weight (2n + n^2, n * d), bias
    (1, 2n + n^2), alpha (3,): with ``m = (x weight^T) / sqrt(mean(x^2) +
    eps)`` over the whole row, ``H_pre = sigmoid(alpha[0] m[:n] + bias)``,
    ``H_post = 2 sigmoid(alpha[1] m[n:2n] + bias)`` and ``M`` the matrix
    ``exp(clip(alpha[2] m[2n:] + bias, clamp_min, clamp_max))`` (row-major)
    after ``sinkhorn_iters`` rounds of division by its column sums +
    ``sinkhorn_eps`` and then by its row sums + ``sinkhorn_eps``; the
    gradient runs through every round. Returns (``y = sum_j H_pre[j] X[j]``
    [B, T, d]; the coefficients [B, T, HYPER_COLS] float32 that
    ``HyperPost`` takes; data itself, for ``HyperPost`` to read: its
    cotangent then comes back through this op, whose backward kernel adds
    it in its one pass over the streams). ``stats`` is an auxiliary state
    that receives HYPER_STATS: the largest distance of M's row and column
    sums from 1 this step.

    On a TPU the kernels ``hyper_pre_fwd`` and ``hyper_pre_bwd`` run it:
    each reads the streams once in their own precision and accumulates in
    float32."""
    n, eps = int(attrs['n']), float(attrs.get('eps', 1e-6))
    lead, nd = x.shape[:-1], x.shape[-1]
    y, c, x2 = _hyper_pre(x.reshape(-1, nd), w, bias, alpha, n, eps,
                          (n, n, n * n))
    coef, dev = _hyper_coefficients(
        c, n, int(attrs.get('sinkhorn_iters', 20)),
        float(attrs.get('sinkhorn_eps', 1e-6)),
        float(attrs.get('clamp_min', -30.0)),
        float(attrs.get('clamp_max', 30.0)))
    return (y.reshape(lead + (nd // n,)), coef.reshape(lead + (-1,)),
            x2.reshape(x.shape), dev.reshape(1).astype(stats.dtype))


@register('HyperCollapse', input_names=['data', 'weight', 'bias', 'alpha'],
          param_defaults={'n': 4, 'eps': 1e-6})
def _hyper_collapse_op(attrs, x, w, bias, alpha):
    """The streams read once more, after the last block: ``sum_j
    sigmoid(alpha[0] m + bias)[j] X[j]`` with ``m = (x weight^T) /
    sqrt(mean(x^2) + eps)``; data [B, T, n * d], weight (n, n * d), bias
    (1, n), alpha (1,). Returns [B, T, d]. ``HyperPre``'s kernels."""
    n, eps = int(attrs['n']), float(attrs.get('eps', 1e-6))
    lead, nd = x.shape[:-1], x.shape[-1]
    y, _, _ = _hyper_pre(x.reshape(-1, nd), w, bias, alpha, n, eps, (n,))
    return y.reshape(lead + (nd // n,))


@register('HyperPost', input_names=['data', 'update', 'coef'],
          param_defaults={'n': 4})
def _hyper_post_op(attrs, x, z, coef):
    """What a sublayer writes: ``X'[i] = H_post[i] update + sum_j M[i, j]
    X[j]`` with data [B, T, n * d] and coef as ``HyperPre`` gave them,
    update [B, T, d]. Returns [B, T, n * d]. On a TPU: ``hyper_post_fwd``
    and ``hyper_post_bwd``."""
    n = int(attrs['n'])
    nd, d = x.shape[-1], z.shape[-1]

    def plain(x, z, coef):
        z32 = z.astype(jnp.float32)
        xs = [x[:, j * d:(j + 1) * d].astype(jnp.float32) for j in range(n)]
        return jnp.concatenate(
            [coef[:, i:i + 1] * z32
             + sum(coef[:, n + i * n + j:n + i * n + j + 1] * xs[j]
                   for j in range(n)) for i in range(n)],
            axis=-1).astype(x.dtype)

    out = pk.dispatch(lambda *ops: pk.hyper_post(*ops, n), plain,
                      x.reshape(-1, nd), z.reshape(-1, d),
                      coef.reshape(-1, coef.shape[-1]))
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# Gated short convolution
# ---------------------------------------------------------------------------

def _causal_taps(u, w):
    """c_t = sum_j w[:, j] u_{t - (L - 1 - j)} for u [B, T, C] float32 and
    taps w (C, L), zero before the sequence's start: shifted slices."""
    L, T = w.shape[1], u.shape[-2]
    w32 = w.astype(jnp.float32)
    c = w32[:, L - 1] * u
    for k in range(1, L):       # tap L - 1 - k weighs u_{t-k}
        c = c + w32[:, L - 1 - k] * jnp.pad(
            u, ((0, 0), (k, 0), (0, 0)))[:, :T]
    return c


def _short_conv_plain(bcx, w):
    """pk.short_conv in plain jnp: shifted slices, float32."""
    C = w.shape[0]
    gate_in, gate_out, x = (bcx[..., i * C:(i + 1) * C].astype(jnp.float32)
                            for i in range(3))
    return (gate_out * _causal_taps(gate_in * x, w)).astype(bcx.dtype)


@register('GatedShortConv', input_names=['data', 'weight'],
          param_defaults={'kernel': 3})
def _gated_short_conv(attrs, bcx, w):
    """The gated short convolution of a conv-attention hybrid's operator,
    in the layout its input projection leaves: data [B, T, 3 C] holds the
    thirds ``[B | C | x]``, weight (C, kernel) the taps of a causal
    depthwise convolution without bias. With ``u = B * x`` (elementwise,
    zero before the sequence's start) and ``c_t = sum_j weight[:, j]
    u_{t - (kernel - 1 - j)}``, returns ``C * c`` [B, T, C]. float32
    inside, data's dtype out.

    On a TPU the kernels ``short_conv_fwd`` and ``short_conv_bwd`` run it:
    each reads its arrays once, a block of rows at the whole width, and
    writes no float32 array; the cotangent of data comes back as one
    [B, T, 3 C] array and the taps' summed over rows in float32. A
    mirrored stage keeps nothing of it: data is an expanding projection's
    output, three times the size of what it was made from, and the
    output is read by a projection that keeps its own."""
    if int(attrs.get('kernel', 3)) != w.shape[1]:
        raise ValueError('GatedShortConv: kernel %s against taps %s'
                         % (attrs.get('kernel'), tuple(w.shape)))
    return pk.dispatch(pk.short_conv, _short_conv_plain, bcx, w)


@register('ShortConv', input_names=['data', 'weight'],
          param_defaults={'kernel': 4})
def _short_conv_op(attrs, x, w):
    """A causal depthwise convolution under SiLU, as a linear-attention
    layer runs over its projections: data [B, T, C], weight (C, kernel)
    the taps, no bias, zero before the sequence's start. With ``c_t =
    sum_j weight[:, j] data_{t - (kernel - 1 - j)}`` returns ``silu(c)``,
    [B, T, C]; float32 inside, data's dtype out.

    On a TPU it takes ``GatedShortConv``'s two kernels with their gates
    off (``short_conv_fwd`` / ``short_conv_bwd``: each reads its arrays
    once, a block of rows at the whole width); XLA's form reads the
    operand once a tap."""
    if int(attrs.get('kernel', 4)) != w.shape[1]:
        raise ValueError('ShortConv: kernel %s against taps %s'
                         % (attrs.get('kernel'), tuple(w.shape)))

    def plain(x, w):
        c = _causal_taps(x.astype(jnp.float32), w)
        return jax.nn.silu(c).astype(x.dtype)

    return pk.dispatch(pk.silu_conv, plain, x, w)


# ---------------------------------------------------------------------------
# Gated delta rule
# ---------------------------------------------------------------------------

# what GatedDeltaRule writes into its ``stats`` auxiliary state each step
DELTA_STATS = ('rows', 'state_abs_max')
_DELTA_NORM_EPS = 1e-6      # under the root of q's and k's squared length


def delta_stat_names(symbol):
    """Names of the auxiliary states that the GatedDeltaRule nodes of
    `symbol` write their per-step statistics into, in graph order."""
    at = registry_get('GatedDeltaRule').input_names.index('stats')
    return [node.inputs[at][0].name for node in symbol._topo()
            if not node.is_variable() and node.op == 'GatedDeltaRule']


def _delta_rule_plain(q, k, v, g, beta):
    """pk.delta_rule in plain jnp: the recurrence row by row under
    ``lax.scan`` (and autodiff, which keeps a state a row: small shapes
    off the TPU). Operands by head, as the kernels take them."""
    hi = jax.lax.Precision.HIGHEST

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum('bhkv,bhk->bhv', S, k_t,
                                               precision=hi))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum('bhkv,bhk->bhv', S, q_t, precision=hi)

    B, H, _, dk = q.shape
    rows = tuple(jnp.moveaxis(x.astype(jnp.float32), 2, 0)
                 for x in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32),
                        rows)
    return jnp.moveaxis(o, 0, 2).astype(v.dtype), \
        jnp.max(jnp.abs(S), axis=(1, 2, 3))


@register('GatedDeltaRule',
          input_names=['query', 'key', 'value', 'g', 'beta', 'stats'],
          param_defaults={'num_heads': 1}, num_outputs=2,
          num_visible_outputs=1, mutate_inputs={5: 1}, aux_inputs=('stats',))
def _gated_delta_rule(attrs, q, k, v, g, beta, stats):
    """The gated delta rule of a linear-attention layer: per head, with a
    state S in R^{dk x dv} that is zero before the sequence's start,

        S_t = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S_t^T k_t)
        S_t = S_t + k_t u_t^T;   o_t = S_t^T q_t

    (so ``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
    v_t^T``). query and key [B, T, H * dk], value [B, T, H * dv], g (the
    log of the decay, <= 0) and beta [B, T, H]; a head's q and k are
    divided by their length here (the root of the squares' sum + 1e-6) and
    q by ``sqrt(dk)``. Returns [B, T, H * dv]; float32 inside, value's
    dtype out. ``stats`` is an auxiliary state that receives DELTA_STATS:
    the rows it was handed this step (B * T, written here and not by the
    kernels) and the largest magnitude of a state after the last row (with beta up to 2 a step's eigenvalue can be -1).

    On a TPU the chunked form runs it (``ops/pallas_kernels.py``): what a
    chunk of 64 rows needs that does not depend on the state is three
    kernels over all chunks at once (``delta_rule_solve``, the triangular
    solve in float32, ``delta_rule_chunk_fwd`` and ``_chunk_bwd``), and the
    chain of chunks is ``delta_rule_fwd`` and ``delta_rule_bwd``, which
    carry the state, or its cotangent, along the sequence. A mirrored stage
    keeps the chain's output, the states at the chunks' starts and the
    chunks' inverses: chain and solve run once a step and direction, the
    rest is made again. Elsewhere: the recurrence under ``lax.scan``."""
    H = int(attrs['num_heads'])
    B, T, _ = q.shape
    dk, dv = q.shape[2] // H, v.shape[2] // H

    def heads(x, D):
        return x.reshape(B, T, H, D).transpose(0, 2, 1, 3)

    def unit(x, scale):
        x = heads(x, dk).astype(jnp.float32)
        return x * (jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                  + _DELTA_NORM_EPS) * scale)

    with jax.named_scope('heads'):
        operands = (unit(q, dk ** -0.5), unit(k, 1.0), heads(v, dv),
                    g.astype(jnp.float32).transpose(0, 2, 1),
                    beta.astype(jnp.float32).transpose(0, 2, 1))
    o, smax = pk.dispatch(pk.delta_rule, _delta_rule_plain, *operands)
    with jax.named_scope('heads'):
        out = o.transpose(0, 2, 1, 3).reshape(B, T, H * dv)
    return out, jnp.stack([jnp.float32(B * T), jnp.max(smax)]) \
        .astype(stats.dtype)


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------

def _gated_mlp(x, w1, w3, w2):
    """(silu(x w1^T) * (x w3^T)) w2^T, weights (out, in); g, u are dear."""
    g, u = dear(_matmul(x, w1), 'mlp_gate'), dear(_matmul(x, w3), 'mlp_up')
    return _matmul((jax.nn.silu(g) * u).astype(x.dtype), w2).astype(x.dtype)


@register('GatedMLP', input_names=['data', 'w1_weight', 'w3_weight',
                                   'w2_weight'],
          param_defaults={'hidden': 0})
def _gated_mlp_op(attrs, x, w1, w3, w2):
    """The SwiGLU feed-forward layer: (silu(x W1) * (x W3)) W2 with W1, W3
    of (hidden, in) and W2 of (in, hidden); no bias."""
    return _gated_mlp(x, w1, w3, w2)


# ---------------------------------------------------------------------------
# Routed experts
# ---------------------------------------------------------------------------

def _sigmoid_scoring(attrs):
    return str(attrs.get('scoring', 'softmax')) == 'sigmoid'


def _has_shared(attrs):
    return int(attrs.get('shared_hidden', 0) or 0) > 0


# what the layer writes into its ``stats`` auxiliary state each step: the
# pairs computed here, the tokens routed, the pairs dropped (0), the fullest
# held expert's rows, that over the mean, the passes made over the sorted rows
MOE_STATS = ('pairs', 'tokens', 'dropped', 'load_max', 'load_max_over_mean',
             'passes')


def moe_stat_names(symbol):
    """Names of the auxiliary states that the MoE nodes of `symbol` write
    their per-step statistics into, in graph order."""
    out = []
    op = registry_get('MoE')
    for node in symbol._topo():
        if not node.is_variable() and node.op == 'MoE':
            # a layer without a shared expert has three inputs fewer
            src, _ = node.inputs[op.names_present(node.attrs).index('stats')]
            out.append(src.name)
    return out


def _gmm(x, w, tile_group, n_tiles, transpose_w=False):
    def plain(x, w, tile_group, n_tiles):
        rows = jnp.repeat(tile_group, pk.GROUP_TILE)
        form = 'rk,rnk->rn' if transpose_w else 'rk,rkn->rn'
        return jnp.einsum(form, x, w[rows],
                          preferred_element_type=jnp.float32).astype(x.dtype)

    def fused(x, w, tile_group, n_tiles):
        return pk.grouped_matmul(x, w, tile_group, n_tiles, transpose_w,
                                 name='moe_expert_matmul')

    return pk.dispatch(fused, plain, x, w, tile_group, n_tiles)


def _gmm_dw(x, y, tile_group, n_tiles, pass_index, acc):
    """acc [G, K, N] float32 plus each group's x^T y over its rows among
    the tiles present (``pk.grouped_matmul_dw``, which adds in place);
    `pass_index` [1] counts the calls into this acc so far, and at 0 acc
    is zeros."""
    def plain(x, y, tile_group, n_tiles, pass_index, acc):
        rows = jnp.repeat(tile_group, pk.GROUP_TILE)
        live = jnp.arange(x.shape[0]) < n_tiles[0] * pk.GROUP_TILE
        onehot = ((rows[:, None] == jnp.arange(acc.shape[0])[None])
                  & live[:, None])
        return acc + jnp.einsum('rk,rn,rg->gkn', x, y,
                                onehot.astype(x.dtype),
                                preferred_element_type=jnp.float32)

    def fused(x, y, tile_group, n_tiles, pass_index, acc):
        return pk.grouped_matmul_dw(x, y, tile_group, n_tiles, pass_index,
                                    acc, name='moe_expert_matmul_dw')

    return pk.dispatch(fused, plain, x, y, tile_group, n_tiles, pass_index,
                       acc)


def _rows_to_tokens(src, token, n_tiles, pass_index, acc, scale=None):
    """acc [T, d] float32 plus each sorted row among the tiles present,
    times its scale where one is given, added into its token's row
    (``pk.rows_to_tokens``, which adds in place): src [R, d], token [R] the
    row's token, T for a padding row, scale [R] float32. `pass_index` [1]
    counts the calls into this acc so far, and at 0 acc is zeros."""
    def plain(src, token, n_tiles, pass_index, acc, *scale):
        live = jnp.arange(src.shape[0]) < n_tiles[0] * pk.GROUP_TILE
        rows = src.astype(jnp.float32)
        if scale:
            rows = rows * scale[0][:, None]
        return acc.at[jnp.where(live, token, acc.shape[0])].add(
            rows, mode='drop')

    def fused(src, token, n_tiles, pass_index, acc, *scale):
        return pk.rows_to_tokens(src, token, n_tiles, pass_index, acc,
                                 *scale, name='moe_rows_to_tokens')

    return pk.dispatch(fused, plain, src, token, n_tiles, pass_index, acc,
                       *(() if scale is None else (scale,)))


def _dispatch_plan(idx, held, offset):
    """Where each token-expert pair that lands on an expert held here goes
    in the sorted buffer. idx [T, k] expert ids. Returns (dest [T, k] row
    of each pair, R for a pair routed elsewhere; row_pair [R] flat pair of
    each row, T * k for a padding row; tile_group [R / tile]; n_tiles [1];
    counts [held])."""
    T, k = idx.shape
    tm = pk.GROUP_TILE
    P = T * k
    R = -(-(T * min(k, held) + held * tm) // tm) * tm
    local = idx.reshape(-1) - offset
    here = (local >= 0) & (local < held)
    local = jnp.where(here, local, 0)
    onehot = (local[:, None] == jnp.arange(held)[None]) & here[:, None]
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    counts = rank[-1]
    rank = jnp.take_along_axis(rank, local[:, None], axis=1)[:, 0] - 1
    size = jnp.maximum(-(-counts // tm), 1) * tm    # whole tiles, one at least
    end = jnp.cumsum(size)
    dest = jnp.where(here, (end - size)[local] + rank, R)
    row_pair = jnp.full((R,), P, jnp.int32).at[dest].set(
        jnp.arange(P, dtype=jnp.int32), mode='drop')
    tile_group = jnp.clip(jnp.searchsorted(
        end, jnp.arange(R // tm, dtype=jnp.int32) * tm, side='right'),
        0, held - 1).astype(jnp.int32)
    n_tiles = (end[-1:] // tm).astype(jnp.int32)
    return dest.reshape(T, k), row_pair, tile_group, n_tiles, counts


def _rows(x, index):
    """x[index] with 0 rows for an index past the end."""
    return jnp.take(x, index, axis=0, mode='fill', fill_value=0)


# A pass of `_experts` takes this many times the rows that an even router
# would send to the experts held here (plus a tile for each). Seeded routers
# send a layer 0.5 to 2.2 times the even share in single steps (PERF.md,
# section 5), so a second pass is rare and a pass is a fraction of the
# worst-case buffer.
_PASS_OVER_EVEN = 2


def _pass_rows(R, T, k, held, num_experts):
    """How many rows of the sorted buffer one pass of :func:`_experts`
    takes: whole tiles, a function of the shapes alone, R at most (where
    every expert is held it is R: one pass over the whole buffer)."""
    tm = pk.GROUP_TILE
    rows = -(-_PASS_OVER_EVEN * T * k * held // num_experts) + held * tm
    return min(R, -(-rows // tm) * tm)


def _num_passes(rp, n_tiles):
    """Passes of rp rows that hold the n_tiles[0] tiles present."""
    return -(-n_tiles[0] // (rp // pk.GROUP_TILE))


def _whole_passes(rp, dest, row_pair, tile_group):
    """The plan padded to whole passes (a slice of a pass's length would
    otherwise be moved back inside the buffer), a pair of an expert held
    elsewhere sent past the padded end."""
    R = row_pair.shape[0]
    pad = -R % rp
    return (jnp.where(dest < R, dest, R + pad),
            jnp.pad(row_pair, (0, pad), constant_values=dest.size),
            jnp.pad(tile_group, (0, pad // pk.GROUP_TILE), mode='edge'))


def _pass_forward(rp, p, x, w1, w3, w2, row_pair, tile_group, n_tiles, k):
    """Pass p of the sorted buffer, rows [p * rp, (p + 1) * rp): its share
    of the plan (rows: the flat pair of each row, T * k for a padding row;
    token: the row's token, T for a padding row) and the gated MLP of each
    expert on its rows. Rows of tiles past the last present are left
    unwritten by the grouped products: nothing may fold them into a sum
    across rows."""
    tiles_pass = rp // pk.GROUP_TILE
    rows = jax.lax.dynamic_slice(row_pair, (p * rp,), (rp,))
    token = rows // k
    groups = jax.lax.dynamic_slice(tile_group, (p * tiles_pass,),
                                   (tiles_pass,))
    tiles = jnp.clip(n_tiles - p * tiles_pass, 0, tiles_pass)
    with jax.named_scope('gather'):
        xs = _rows(x, token)
    with jax.named_scope('experts'):
        h1 = _gmm(xs, w1, groups, tiles)
        h3 = _gmm(xs, w3, groups, tiles)
        act = (jax.nn.silu(h1.astype(jnp.float32))
               * h3.astype(jnp.float32)).astype(x.dtype)
        ys = _gmm(act, w2, groups, tiles)
    return (rows, token, groups, tiles), (xs, h1, h3, act, ys)


def _experts_forward(rp, x, w_pairs, w1, w3, w2, dest, row_pair, tile_group,
                     n_tiles):
    k = dest.shape[1]
    w_flat = w_pairs.reshape(-1)

    def one(p, out):
        (rows, token, _, tiles), (_, _, _, _, ys) = _pass_forward(
            rp, p, x, w1, w3, w2, row_pair, tile_group, n_tiles, k)
        with jax.named_scope('combine'):    # each row into its token's sum
            return _rows_to_tokens(ys, token, tiles, jnp.reshape(p, (1,)),
                                   out, _rows(w_flat, rows))

    out = jax.lax.fori_loop(0, _num_passes(rp, n_tiles), one,
                            jnp.zeros(x.shape, jnp.float32))
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts(rp, x, w_pairs, w1, w3, w2, dest, row_pair, tile_group, n_tiles):
    """sum over a token's pairs held here of w_pair * E(x): x [T, d],
    w_pairs [T, k] float32, the rest from :func:`_dispatch_plan` through
    :func:`_whole_passes`. The sorted buffer is walked in passes of rp rows
    (:func:`_pass_rows`), as many as the rows present need, so that the
    gathers, the gate and the sums follow the rows present as the grouped
    products do; only the plan has the worst-case length. Tokens go to the
    buffer by a gather, one lookup a sorted row; the way back, in both
    directions, is walked once by sorted row too: a kernel adds each row of
    the tiles present into its token's row of a float32 [T, d] sum
    (:func:`_rows_to_tokens`: the output, and dx), so a token's terms are
    added in expert order. The backward pass computes a pass's forward
    again from x: what is kept for it is x, w_pairs, the weights and the
    plan. Its three weight gradients are float32 arrays that each pass's
    grouped products add into in place."""
    return _experts_forward(rp, x, w_pairs, w1, w3, w2, dest, row_pair,
                            tile_group, n_tiles)


def _experts_fwd(rp, x, w_pairs, w1, w3, w2, dest, row_pair, tile_group,
                 n_tiles):
    out = _experts_forward(rp, x, w_pairs, w1, w3, w2, dest, row_pair,
                           tile_group, n_tiles)
    return out, (x, w_pairs, w1, w3, w2, dest, row_pair, tile_group, n_tiles)


def _experts_bwd(rp, res, g):
    x, w_pairs, w1, w3, w2, dest, row_pair, tile_group, n_tiles = res
    k = dest.shape[1]
    w_flat = w_pairs.reshape(-1)

    def one(p, carry):
        dx, d_pairs, dw1, dw3, dw2 = carry
        (rows, token, groups, tiles), (xs, h1, h3, act, ys) = _pass_forward(
            rp, p, x, w1, w3, w2, row_pair, tile_group, n_tiles, k)
        nth = jnp.reshape(p, (1,))
        with jax.named_scope('combine'):
            # a row's share of its pair's weight gradient, then each pair's
            # by its row (rp for a pair of another pass or of an expert held
            # elsewhere; a negative index would wrap before it fills)
            gs = _rows(g, token).astype(jnp.float32)
            rowdot = jnp.sum(ys.astype(jnp.float32) * gs, axis=-1)
            at = jnp.where((dest >= p * rp) & (dest < (p + 1) * rp),
                           dest - p * rp, rp)
            d_pairs += _rows(rowdot, at)
            dys = (_rows(w_flat, rows)[:, None] * gs).astype(g.dtype)
        with jax.named_scope('experts'):
            dact = _gmm(dys, w2, groups, tiles, transpose_w=True).astype(
                jnp.float32)
            h1f, h3f = h1.astype(jnp.float32), h3.astype(jnp.float32)
            sig = jax.nn.sigmoid(h1f)
            dh1 = (dact * h3f * sig * (1.0 + h1f * (1.0 - sig))).astype(
                g.dtype)
            dh3 = (dact * h1f * sig).astype(g.dtype)
            dxs = _gmm(dh1, w1, groups, tiles, transpose_w=True) \
                .astype(jnp.float32) \
                + _gmm(dh3, w3, groups, tiles, transpose_w=True) \
                .astype(jnp.float32)
        with jax.named_scope('gather'):
            dx = _rows_to_tokens(dxs, token, tiles, nth, dx)
        # the weight products add into the sums in place; a group without
        # a tile in this pass keeps what it has
        with jax.named_scope('dw_sum'):
            dw1 = _gmm_dw(xs, dh1, groups, tiles, nth, dw1)
            dw3 = _gmm_dw(xs, dh3, groups, tiles, nth, dw3)
            dw2 = _gmm_dw(act, dys, groups, tiles, nth, dw2)
        return dx, d_pairs, dw1, dw3, dw2

    dx, d_pairs, dw1, dw3, dw2 = jax.lax.fori_loop(
        0, _num_passes(rp, n_tiles), one,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(w_pairs.shape, jnp.float32),
         jnp.zeros(w1.shape, jnp.float32), jnp.zeros(w3.shape, jnp.float32),
         jnp.zeros(w2.shape, jnp.float32)))
    return (dx.astype(g.dtype), d_pairs.astype(w_pairs.dtype),
            dw1.astype(w1.dtype), dw3.astype(w3.dtype), dw2.astype(w2.dtype),
            None, None, None, None)


_experts.defvjp(_experts_fwd, _experts_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(x, k):
    """``jax.lax.top_k`` on the last axis of x [T, n], with a backward
    rule that reads the indices as a mirrored stage keeps them
    (``top_k``'s own reads them as the sort made them, unnamed, and would
    run the sort again for it)."""
    return tuple(jax.lax.top_k(x, k))


def _top_k_fwd(x, k):
    w, idx = jax.lax.top_k(x, k)
    idx = dear(idx, 'moe_route')
    # a zero-row array tells the backward rule x's width and dtype
    return (w, idx), (idx, jnp.zeros((0, x.shape[1]), x.dtype))


def _top_k_bwd(k, res, g):
    idx, like = res
    rows = jnp.arange(idx.shape[0])[:, None]
    return (jnp.zeros((idx.shape[0], like.shape[1]), like.dtype)
            .at[rows, idx].add(g[0]),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def _route(attrs, x2, router, select_bias, k):
    """(w_pairs [T, k] float32, idx [T, k]): each token's k experts and
    their weights, as :func:`_moe` describes. The choice and the chosen
    scores are named for a mirrored stage (``registry.dear``): what the
    backward pass reads of the top-k and the gather."""
    if _sigmoid_scoring(attrs):
        scores = jax.nn.sigmoid(_matmul(x2, router))
        bias = jax.lax.stop_gradient(select_bias).astype(jnp.float32)
        _, idx = jax.lax.top_k(scores + bias.reshape(1, -1), k)
        idx = dear(idx, 'moe_route')
        w_pairs = dear(jnp.take_along_axis(scores, idx, axis=-1),
                       'moe_route')
        if attrs.get('norm_topk_prob', True):
            w_pairs = w_pairs / (jnp.sum(w_pairs, axis=-1, keepdims=True)
                                 + float(attrs.get('norm_eps', 1e-20)))
    else:
        probs = jax.nn.softmax(_matmul(x2, router), axis=-1)
        w_pairs, idx = _top_k(probs, k)
        w_pairs = dear(w_pairs, 'moe_route')
        if attrs.get('norm_topk_prob', True):
            w_pairs = w_pairs / jnp.sum(w_pairs, axis=-1, keepdims=True)
    w_pairs = w_pairs * float(attrs.get('routed_scaling', 1.0))
    return dear(w_pairs, 'moe_route'), idx


@register('MoE',
          input_names=['data', 'router_weight', 'experts_w1_weight',
                       'experts_w3_weight', 'experts_w2_weight',
                       'shared_w1_weight', 'shared_w3_weight',
                       'shared_w2_weight', 'stats', 'select_bias'],
          param_defaults={'num_experts': 0, 'num_experts_per_tok': 1,
                          'experts_held': 0, 'expert_offset': 0,
                          'norm_topk_prob': True, 'routed_scaling': 1.0,
                          'hidden': 0, 'shared_hidden': 0,
                          'scoring': 'softmax', 'norm_eps': 1e-20},
          num_outputs=2, num_visible_outputs=1, mutate_inputs={8: 1},
          aux_inputs=('stats',),
          optional_inputs={'shared_w1_weight': _has_shared,
                           'shared_w3_weight': _has_shared,
                           'shared_w2_weight': _has_shared,
                           'select_bias': _sigmoid_scoring})
def _moe(attrs, x, router, w1, w3, w2, *rest):
    """A routed expert layer that holds ``experts_held`` of ``num_experts``
    experts, those from ``expert_offset`` on, and, where ``shared_hidden``
    is not 0, a shared expert of that width. With ``shared_hidden`` 0 the
    node has no ``shared_*`` inputs and nothing is added to the routed
    experts' sum (called with arrays, the layer has a shared expert where
    its three weights are handed to it).

    The router scores every token over all ``num_experts`` (softmax,
    float32), takes the ``num_experts_per_tok`` largest, divides their
    weights by their sum (``norm_topk_prob``) and multiplies by
    ``routed_scaling``. With ``scoring`` 'sigmoid' the scores are each
    logit's sigmoid, the choice is of the largest ``score + select_bias``
    (``select_bias`` (1, num_experts), one more input; it takes no
    gradient: a trainer balances the load with it from outside) and the
    weights are the chosen experts' bare scores. Of a token's pairs only
    those on an expert held here are computed: a plan sorts them by expert
    into rows, each expert's rows padded to whole tiles. The plan (int32
    vectors) has the static worst-case length, every token on every
    expert held; the activations do not: the rows are walked in passes of
    a bounded length (:func:`_pass_rows`: twice an even router's share),
    as many as the rows present need, and in each a grouped product runs
    the gated MLP of each expert on its rows. One pass as a rule; an
    imbalance costs further passes, never a pair. What the experts
    held elsewhere would add is left out; the shared expert, where there is
    one, is added once. ``norm_eps`` is what is added to the sum of the
    chosen sigmoid scores before the division (1e-20 unless given; a family
    publishes its own).

    Weights of the experts held: w1, w3 (held, in, hidden), w2 (held,
    hidden, in). ``stats`` is an auxiliary state that receives this step's
    MOE_STATS: pairs computed here, tokens routed, pairs dropped (the
    pairs routed here less the rows placed: 0), the fullest expert's rows,
    that over the mean, and the passes made over the rows.
    """
    held, offset = int(attrs['experts_held']), int(attrs['expert_offset'])
    k = int(attrs['num_experts_per_tok'])
    # after the experts' weights: [shared w1, w3, w2,] stats [, select_bias]
    shared = list(rest)
    select_bias = shared.pop() if _sigmoid_scoring(attrs) else None
    stats = shared.pop()
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d)
    # trace-time names below the node's own, for the compiled program's
    # scope map (telemetry/programs.py): router, plan, then per pass
    # gather, experts, combine and dw_sum, and shared
    with jax.named_scope('router'):
        w_pairs, idx = _route(attrs, x2, router, select_bias, k)
    with jax.named_scope('plan'):
        dest, row_pair, tile_group, n_tiles, counts = _dispatch_plan(
            idx, held, offset)
        rp = _pass_rows(row_pair.shape[0], x2.shape[0], k, held,
                        router.shape[0])
        # what _experts carries to its backward pass (with w_pairs, named
        # by _route): int32 vectors behind scans, scatters and a search
        plan = [dear(v, 'moe_plan') for v in _whole_passes(
            rp, dest, row_pair, tile_group) + (n_tiles,)]
    out = _experts(rp, x2, w_pairs, w1, w3, w2, *plan)
    if shared:
        with jax.named_scope('shared'):
            out = out + _gated_mlp(x2, *shared)
    pairs = jnp.sum(counts).astype(jnp.float32)
    placed = jnp.sum(row_pair < dest.size).astype(jnp.float32)
    load_max = jnp.max(counts).astype(jnp.float32)
    new_stats = jnp.stack([
        pairs, jnp.float32(x2.shape[0]), pairs - placed, load_max,
        load_max * held / jnp.maximum(pairs, 1.0),
        _num_passes(rp, n_tiles).astype(jnp.float32)]).astype(stats.dtype)
    return out.reshape(lead + (d,)), jax.lax.stop_gradient(new_stats)
