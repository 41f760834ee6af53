"""Pallas TPU kernels for the hot ops.

The reference hand-writes CUDA for its hot paths (softmax.cu, im2col,
cudnn wrappers — SURVEY.md N6); on TPU, XLA's fusion already covers
most of that, and these kernels target what XLA does NOT schedule
optimally on the MXU/VMEM hierarchy:

- :func:`blockwise_attention` — attention, forward and backward, over
  the blocks a mask leaves (a :class:`Walk`): online softmax over K/V
  blocks streamed through VMEM; no [Tq, Tk] score matrix in HBM.
- :func:`fused_rmsnorm` / :func:`fused_layernorm` — one pass over the
  feature dim in VMEM (XLA emits separate reduce+scale passes).
- :func:`fused_rmsnorm_bwd` — RMSNorm's backward rule: one pass over x
  and dy that writes dx and sums the gain's gradient in float32 (XLA's
  form writes float32 copies of the rows and reduces them again).
- :func:`softmax_xent` — fused logsumexp + gather loss for LM heads,
  avoiding the [N, V] softmax materialization.

Where a kernel runs is decided by where its operands live, never by the
process: concrete arrays are asked for their device, and under a trace
``jax.lax.platform_dependent`` leaves the choice to the lowering, which
knows the platform it compiles for. Operands on a TPU get the compiled
kernel; anywhere else the same kernel body runs through the Pallas
interpreter (tests/unittest/test_pallas.py on the CPU mesh). A kernel the
chip's compiler would refuse raises here, with its shapes, where it is
bound for a TPU: at once for operands on one, and under a trace when the
program is lowered for one (a program lowered for the CPU never meets
the limit, and the interpreter has none). Nothing gives way to the jnp
formulation quietly. The row kernels (softmax, cross-entropy, LayerNorm)
are forward-only: their backward passes are jax.custom_vjp rules in plain
jnp; RMSNorm, which every decoder block runs, has a backward kernel too.
Attention has kernels in both directions (``attention_forward``,
``attention_backward``): no [Tq, Tk] array exists in either, and
``flash_attention`` / ``flash_attention_lse`` are the same kernels behind
[B, T, H, D] operands.
"""
import functools
import types
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

from .registry import dear

__all__ = ['flash_attention', 'flash_attention_lse', 'fused_rmsnorm',
           'fused_rmsnorm_bwd', 'fused_layernorm', 'fused_softmax',
           'softmax_xent']


_NEG = -1e30

# Mosaic's default scoped-VMEM limit on v5e is 16 MiB of the core's 128
# (the rehearsal compiles of tests/unittest/test_tpu_compile.py hold every
# kernel to it but one: the one backward kernel of blockwise attention asks
# for what _bwd_vmem counts, 32 MiB at 8192 tokens).
# Row kernels keep one f32 [blk, D] working tile under _ROW_TILE_BYTES:
# the pipeline double-buffers the input and output blocks and the body
# holds a few f32 temporaries of the same shape. At 2 MiB every row
# kernel still compiles at the widest row that leaves the 8-row minimum
# (65536 elements, f32 and bf16); a 50k-word LM head fits with room.
_ROW_TILE_BYTES = 2 << 20
# RMSNorm's two kernels take their rows by bytes alone, so that a head's
# narrow row gets a tall block (2048 rows of 128, 128 of 2048: a grid step
# costs half a microsecond whatever it moves): half the tile, because the
# backward kernel reads two arrays and holds twice the temporaries
_RMS_TILE_BYTES = 1 << 20
# rows narrower than this take the backward kernel (a head's 64 or 128, a
# latent's 512 or 1536); from here on XLA's form of the backward pass runs
# at the bandwidth alone on the chip (2048: 0.096 ms where the kernel takes
# 0.108; 3072: 0.151 and 0.163) and inside a training step its reductions
# fuse into the neighbouring products, which a kernel's operands cannot
# (lfm2_fit_8k lost 1.9% with kernels as its block norms: PERF.md, PR 47)
_RMS_BWD_WIDTHS = 2048
# Mosaic's default scope, and what the one backward kernel of blockwise
# attention may hold beside it for a whole sequence (_bwd_vmem): three
# eighths of a v5e core's 128 MiB of VMEM (jax pallas/mosaic/tpu_info.py)
_VMEM_SCOPE = 16 << 20
_BWD_RESIDENT_BYTES = 48 << 20


def _by_platform(operands, on_tpu, elsewhere):
    """``on_tpu(*operands)`` where the operands live on a TPU,
    ``elsewhere(*operands)`` on any other platform."""
    for x in operands:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            tpu = all(d.platform == 'tpu' for d in x.devices())
            return (on_tpu if tpu else elsewhere)(*operands)
    return jax.lax.platform_dependent(*operands, tpu=on_tpu,
                                      default=elsewhere)


def dispatch(fused, plain, *operands):
    """Policy for the registry ops (ops/nn.py): the fused kernel for
    operands on a TPU, the plain jnp formulation elsewhere (faster there
    than interpreted Pallas) unless MXTPU_FORCE_PALLAS=1, the tests'
    explicit switch, routes every platform through the kernel."""
    from ..config import flags as _flags
    _flags.reload('MXTPU_FORCE_PALLAS')  # tests toggle it per-case
    if _flags.get('MXTPU_FORCE_PALLAS'):
        return fused(*operands)
    return _by_platform(operands, fused, plain)


def run_kernel(build, *operands, too_big=None):
    """Run ``build(interpret)(*operands)``: compiled for operands on a
    TPU, interpreted elsewhere. ``too_big`` is the error for a kernel
    whose blocks the chip's compiler would refuse: it takes the compiled
    kernel's place, so only a TPU ever meets it."""
    interp = build(True)
    on_tpu = (build(False) if too_big is None
              else functools.partial(_refused, interp, too_big))
    return _by_platform(operands, on_tpu, interp)


def _refused(interp, msg, *operands):
    """Stands where a compiled kernel would: raises ``msg`` for concrete
    operands, and under a trace stages a primitive that raises it when
    (and only when) the branch is lowered, which platform_dependent does
    for a TPU alone."""
    if not any(isinstance(x, jax.core.Tracer) for x in operands):
        raise ValueError(msg)
    outs, tree = jax.tree.flatten(jax.eval_shape(interp, *operands))
    return jax.tree.unflatten(tree, _refuse_p.bind(
        *operands, msg=msg,
        outs=tuple((o.shape, o.dtype) for o in outs)))


_refuse_p = Primitive('mxtpu_kernel_refused')
_refuse_p.multiple_results = True
_refuse_p.def_abstract_eval(lambda *_, msg, outs: [
    jax.core.ShapedArray(shape, dtype) for shape, dtype in outs])


def _refuse_lowering(ctx, *_, msg, outs):
    raise ValueError(msg)


def _refuse_batch(args, dims, *, msg, outs):
    n = next(a.shape[d] for a, d in zip(args, dims) if d is not None)
    res = _refuse_p.bind(*args, msg=msg, outs=tuple(
        ((n,) + shape, dtype) for shape, dtype in outs))
    return res, [0] * len(res)


mlir.register_lowering(_refuse_p, _refuse_lowering)
batching.primitive_batchers[_refuse_p] = _refuse_batch


def _block_ok(blk, dim):
    """Mosaic's second-to-minor block rule (jax pallas/mosaic/lowering.py
    _check_block_mappings): a second-to-minor block dim is legal iff it
    equals the array dim or is a multiple of 8. (Minor dims and rank-1
    blocks need %128 or equality instead — here every minor dim and
    every rank-1 block equals its array dim: full feature rows, full
    (D,) params, and the [.., blk, 1] columns that carry per-row
    outputs.) Interpret mode (the CPU test mesh) does NOT enforce any
    of this, so every block-size choice goes through these helpers to
    keep CPU-green == TPU-lowerable."""
    return blk == dim or blk % 8 == 0


def _pick_block(want, n):
    """Largest Mosaic-legal divisor of ``n`` that is <= want. Falls back
    to the whole axis (always legal, but only sensible when the full
    block fits VMEM — the row kernels pre-pad ``n`` to a multiple of 8
    via :func:`_pad_and_block` so they never take the fallback on awkward
    sizes)."""
    for b in range(min(want, n), 0, -1):
        if n % b == 0 and _block_ok(b, n):
            return b
    return n


def _pad_and_block(want, n):
    """(pad, blk) for tiling ``n`` rows at ~``want``: pad rows up to the
    next multiple of 8 when ``n`` has no Mosaic-legal divisor <= want,
    then pick the largest legal divisor of ``n + pad``. Keeps wide row
    kernels (e.g. a [N, vocab] xent) from falling back to a whole-array
    block that cannot fit VMEM when N has no small legal divisor
    (N = 2 * prime, ...). ``want`` is clamped to >= 8 internally so
    that once padded to a multiple of 8, blk=8 always qualifies — the
    fallback is only reachable for n <= want (small full blocks)."""
    want = max(want, 8)
    pad = (-n) % 8 if (n > want and _pick_block(want, n) == n) else 0
    return pad, _pick_block(want, n + pad)


def _row_block(name, want, x2):
    """(rows per block, too_big) for a row kernel over ``x2`` [N, D]:
    ``want`` rows, cut down until one f32 [blk, D] tile fits
    _ROW_TILE_BYTES (a [128, 32000] f32 xent block is 16 MB before double
    buffering — all of VMEM). For a row so wide that even the 8-row
    minimum does not fit, ``too_big`` is run_kernel's error."""
    D = x2.shape[-1]
    rows = _ROW_TILE_BYTES // (4 * D)
    if rows < 8:
        return 8, (
            '%s: rows of %d elements (operand %s %s) do not fit VMEM even '
            'at the minimum block of 8 rows (%d bytes against %d)'
            % (name, D, tuple(x2.shape), x2.dtype.name, 8 * 4 * D,
               _ROW_TILE_BYTES))
    return min(want, rows - rows % 8), None


# ---------------------------------------------------------------------------
# Fused normalization
# ---------------------------------------------------------------------------

def _rmsnorm_kernel(x_ref, g_ref, o_ref, eps):
    x = x_ref[:].astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[:] = (x * inv * g_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _layernorm_kernel(x_ref, g_ref, b_ref, o_ref, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * g_ref[:].astype(jnp.float32) +
                b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _norm_call(name, kernel, arrs, x, block_rows=256):
    lead = x.shape[:-1]
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    if N == 0:                       # empty batch: nothing to launch
        return x2.reshape(lead + (D,))
    want, too_big = _row_block(name, block_rows, x2)
    pad, blk = _pad_and_block(want, N)
    if pad:
        x2 = jnp.concatenate([x2, jnp.zeros((pad, D), x2.dtype)])
    out = run_kernel(lambda interpret: pl.pallas_call(
        kernel,
        grid=((N + pad) // blk,),
        in_specs=[pl.BlockSpec((blk, D), lambda i: (i, 0))] +
                 [pl.BlockSpec((D,), lambda i: (0,))] * len(arrs),
        out_specs=pl.BlockSpec((blk, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N + pad, D), x.dtype),
        interpret=interpret, name=name), x2, *arrs, too_big=too_big)
    return out[:N].reshape(lead + (D,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_rmsnorm(x, gamma, eps=1e-6):
    """RMSNorm in one VMEM pass over the feature dim."""
    def kern(x_ref, g_ref, o_ref):
        _rmsnorm_kernel(x_ref, g_ref, o_ref, eps)
    return _norm_call('fused_rmsnorm', kern, (gamma,), x,
                      block_rows=_RMS_TILE_BYTES // (4 * x.shape[-1]))


def _rms_ref(x, gamma, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * inv * gamma.astype(jnp.float32)).astype(x.dtype)


def _rms_fwd(x, gamma, eps):
    return fused_rmsnorm(x, gamma, eps), (x, gamma)


def _rmsnorm_bwd_kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, eps):
    """One block of rows: dx, and the block's part of the gain's gradient
    added into ``dg_ref`` [8, D], which every grid step revisits (eight
    partial rows, so that the sum over a block's rows is whole-tile adds;
    the grid runs in order, so the same inputs give the same bits)."""
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    xh = x * inv
    t = dy * g_ref[:].astype(jnp.float32)
    dx_ref[:] = (inv * (t - xh * jnp.mean(t * xh, axis=-1, keepdims=True))
                 ).astype(dx_ref.dtype)
    part = (dy * xh).reshape(-1, 8, x.shape[-1]).sum(axis=0)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dg_ref[:] = part

    @pl.when(pl.program_id(0) > 0)
    def _():
        dg_ref[:] += part


def fused_rmsnorm_bwd(x, gamma, dy, eps=1e-6):
    """(dx, dgamma) of :func:`fused_rmsnorm` in one pass over x and dy:
    statistics, row sums and dgamma in float32, dx in x's dtype, dgamma
    float32 [D]. Rows go by bytes (a narrow row gets a tall block); rows
    padded up to a block are zeros and add nothing to dgamma."""
    D = x.shape[-1]
    x2, dy2 = x.reshape(-1, D), dy.reshape(-1, D)
    N = x2.shape[0]
    if N == 0:                       # empty batch: nothing to launch
        return jnp.zeros_like(x), jnp.zeros((D,), jnp.float32)
    name = 'fused_rmsnorm_bwd'
    want, too_big = _row_block(name, max(8, _RMS_TILE_BYTES // (4 * D)), x2)
    pad = (-N) % 8                   # whole tiles for the eight partial rows
    blk = _pick_block(want, N + pad)
    if pad:
        x2, dy2 = _pad0(x2, pad), _pad0(dy2, pad)

    def kern(x_ref, dy_ref, g_ref, dx_ref, dg_ref):
        _rmsnorm_bwd_kernel(x_ref, dy_ref, g_ref, dx_ref, dg_ref, eps)
    rows = pl.BlockSpec((blk, D), lambda i: (i, 0))
    dx, dg = run_kernel(lambda interpret: pl.pallas_call(
        kern,
        grid=((N + pad) // blk,),
        in_specs=[rows, rows, pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=[rows, pl.BlockSpec((8, D), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N + pad, D), x.dtype),
                   jax.ShapeDtypeStruct((8, D), jnp.float32)],
        interpret=interpret, name=name), x2, dy2, gamma, too_big=too_big)
    return dx[:N].reshape(x.shape), dg.sum(axis=0)


def _rms_bwd(eps, res, g):
    x, gamma = res
    if x.shape[-1] >= _RMS_BWD_WIDTHS:
        _, vjp = jax.vjp(lambda x, gm: _rms_ref(x, gm, eps), x, gamma)
        return vjp(g)
    dx, dg = fused_rmsnorm_bwd(x, gamma, g, eps)
    return dx, dg.astype(gamma.dtype)


fused_rmsnorm.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layernorm(x, gamma, beta, eps=1e-5):
    """LayerNorm in one VMEM pass over the feature dim."""
    def kern(x_ref, g_ref, b_ref, o_ref):
        _layernorm_kernel(x_ref, g_ref, b_ref, o_ref, eps)
    return _norm_call('fused_layernorm', kern, (gamma, beta), x)


def _ln_ref(x, gamma, beta, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) +
            beta.astype(jnp.float32)).astype(x.dtype)


def _ln_fwd(x, gamma, beta, eps):
    return fused_layernorm(x, gamma, beta, eps), (x, gamma, beta)


def _ln_bwd(eps, res, g):
    x, gamma, beta = res
    _, vjp = jax.vjp(lambda x, gm, b: _ln_ref(x, gm, b, eps), x, gamma, beta)
    return vjp(g)


fused_layernorm.defvjp(_ln_fwd, _ln_bwd)


# ---------------------------------------------------------------------------
# Fused row softmax
# ---------------------------------------------------------------------------

def _softmax_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    m = x.max(axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[:] = (e / e.sum(axis=-1, keepdims=True)).astype(o_ref.dtype)


@jax.custom_vjp
def fused_softmax(x):
    """Last-axis softmax in one VMEM pass (max+exp+sum+div fused)."""
    return _norm_call('fused_softmax', _softmax_kernel, (), x)


def _softmax_fwd(x):
    y = fused_softmax(x)
    return y, y


def _softmax_bwd(y, g):
    # d/dx softmax = y * (g - sum(g*y)) along the row
    return (y * (g - jnp.sum(g * y, axis=-1, keepdims=True)),)


fused_softmax.defvjp(_softmax_fwd, _softmax_bwd)


# ---------------------------------------------------------------------------
# Fused softmax cross-entropy
# ---------------------------------------------------------------------------

def _xent_kernel(logits_ref, labels_ref, loss_ref):
    # labels/loss ride as [blk, 1] columns: rank-1 blocks would need
    # blk % 128 == 0 on real TPU (Mosaic's rank-1 rule); a [blk, 1]
    # block only needs blk % 8 with its minor dim equal to the array's
    x = logits_ref[:].astype(jnp.float32)          # [blk, V]
    m = x.max(axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1)) + m[:, 0]
    n = x.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = cols == labels_ref[:].reshape(n, 1)
    gold = jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)
    loss_ref[:] = (lse - gold).astype(loss_ref.dtype)[:, None]


@jax.custom_vjp
def softmax_xent(logits, labels):
    """Per-example CE loss [N] from logits [N, V] + int labels [N],
    without materializing softmax in HBM."""
    N, V = logits.shape
    if N == 0:                       # empty batch: nothing to launch
        return jnp.zeros((0,), jnp.float32)
    want, too_big = _row_block('softmax_xent', 128, logits)
    pad, blk = _pad_and_block(want, N)
    if pad:
        logits = jnp.concatenate([logits, jnp.zeros((pad, V), logits.dtype)])
        labels = jnp.concatenate([labels, jnp.zeros((pad,), labels.dtype)])
    return run_kernel(lambda interpret: pl.pallas_call(
        _xent_kernel,
        grid=((N + pad) // blk,),
        in_specs=[pl.BlockSpec((blk, V), lambda i: (i, 0)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N + pad, 1), jnp.float32),
        interpret=interpret, name='softmax_xent'),
        logits, labels[:, None], too_big=too_big)[:N, 0]


def _xent_fwd(logits, labels):
    return softmax_xent(logits, labels), (logits, labels)


def _xent_bwd(res, g):
    logits, labels = res
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=p.dtype)
    return ((p - onehot) * g[:, None]).astype(logits.dtype), None


softmax_xent.defvjp(_xent_fwd, _xent_bwd)


# ---------------------------------------------------------------------------
# Blockwise attention: forward and backward, grouped-query, under any mask
# that is a Walk
# ---------------------------------------------------------------------------
# q is [B, Tq, H * D] and k, v are [B, Tk, KV * D], the layout a projection
# leaves them in: a block (1, blk, D) of head h is the column block h, so
# no transpose is made and query head h reads key/value head h // (H / KV).
# The grid's last axis walks only the key blocks a query block can see (the
# mask's `Walk`, below): a key block outside is neither fetched (the index
# map stays on the last block that was) nor computed. The backward is one
# kernel: for each visible pair of a query block and a key block it makes
# the scores, the mask, p, dp and ds once (keys first, [blk_k, blk_q], so
# that dk and dv take them as they lie and only dq's product transposes ds)
# and adds to all three gradients. Its grid walks, inside one key/value
# head's cell, the group's query heads, their query blocks and each block's
# key blocks, with dq's block in scratch; dk and dv of the head's whole
# sequence stay in VMEM, added into by a row slice and written when the cell
# ends. Whether they fit is a function of the shapes (`_bwd_vmem`); for a
# sequence past it the backward is the two kernels it was, one per side: dq
# over the key blocks of a query block, dk/dv over the query blocks (and the
# query heads of the group) of a key block, each making the scores for
# itself. Nothing of size Tq x Tk exists in either direction.

class Walk(typing.NamedTuple):
    """What a mask means to a blockwise kernel: which blocks of the other
    side a block touches, in what order, and the mask of a pair.

    ``key_block(i, s)`` is (the s-th key block that query block i touches,
    held on the last one once the walk has ended; whether it has not), and
    ``query_block(j, s)`` the same of the query blocks of key block j, as
    functions of traced or Python indices. `key_steps` and `query_steps` are
    the static lengths of the two walks (a grid's walking axis), `nq` and
    `nk` the blocks a side. ``seen(qi, kj, keys_first=False)`` is the mask
    of query block qi against key block kj, [blk_q, blk_k] ([blk_k, blk_q]
    with `keys_first`).

    An index map reads the held block, so that a step past a walk's end
    fetches nothing; a kernel body takes both and works under
    ``pl.when(live)``. Every block pair that holds a seen element is walked
    once from either side (tests/unittest/test_transformer_ops.py holds every
    walk to it). A new mask is one more function that returns this record:
    the kernels, their wrappers and the latent family read nothing else of
    a mask."""
    nq: int
    nk: int
    key_block: typing.Callable
    query_block: typing.Callable
    key_steps: int
    query_steps: int
    seen: typing.Callable


def _imax(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _imin(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _iwhere(c, a, b):
    return (a if c else b) if isinstance(c, (bool, int)) else \
        jnp.where(c, a, b)


def _visible(qi, kj, blk_q, blk_k, Tk, offset, causal, window,
             keys_first=False):
    """[blk_q, blk_k] mask of query block qi against key block kj
    ([blk_k, blk_q] with `keys_first`). Every block pays for it: taking
    the mask only where the diagonal or the window's edge crosses a block
    (``lax.cond``) made the kernels a quarter slower on the chip, not
    faster (PERF.md, PR 27)."""
    shape, q_axis = ((blk_k, blk_q), 1) if keys_first else ((blk_q, blk_k), 0)
    rows = qi * blk_q + offset + jax.lax.broadcasted_iota(
        jnp.int32, shape, q_axis)
    cols = kj * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - q_axis)
    seen = cols < Tk            # keys past the end are padding
    if causal:
        seen &= cols <= rows
    if window:
        seen &= cols > rows - window
    return seen


def _causal_walk(Tq, Tk, pad_q, pad_k, blk_q, blk_k, causal, window):
    """The :class:`Walk` of Tq queries over Tk keys, the mask aligned bottom
    right: with `causal` a query sees the keys up to its own position, with
    `window` w > 0 only the last w of them, and a block walks the one run of
    blocks between the first and the last it touches. Where an axis had to
    be padded (a length that is no multiple of 8: tests and odd shapes)
    every block walks every block of the other side; the mask, which keys
    off the true lengths, stays exact."""
    offset = Tk - Tq
    nq, nk = (Tq + pad_q) // blk_q, (Tk + pad_k) // blk_k

    if pad_q or pad_k:
        keys_of, queries_of = lambda i: (0, nk - 1), lambda j: (0, nq - 1)
    else:
        def keys_of(i):       # (first, last) key block of query block i
            hi = ((i + 1) * blk_q - 1 + offset) // blk_k if causal else nk - 1
            lo = (i * blk_q + offset - window + 1) if window else 0
            lo = _imax(lo, 0) // blk_k
            return lo, _imin(_imax(hi, 0), nk - 1)

        def queries_of(j):    # (first, last) query block of key block j
            lo = _imax(j * blk_k - offset, 0) // blk_q if causal else 0
            hi = ((j + 1) * blk_k - 1 - offset + window - 1) // blk_q \
                if window else nq - 1
            return _imin(lo, nq - 1), _imin(_imax(hi, 0), nq - 1)

    def one_run(blocks_of, n):
        def block_of(i, s):
            lo, hi = blocks_of(i)
            at = lo + s
            return _imin(at, hi), at <= hi
        spans = (blocks_of(i) for i in range(n))
        return block_of, max(1, max(hi - lo + 1 for lo, hi in spans))

    key_block, key_steps = one_run(keys_of, nq)
    query_block, query_steps = one_run(queries_of, nk)
    return Walk(nq, nk, key_block, query_block, key_steps, query_steps,
                functools.partial(_visible, blk_q=blk_q, blk_k=blk_k, Tk=Tk,
                                  offset=offset, causal=causal,
                                  window=window))


def _attn_blocks(Tq, Tk, block_q, block_k):
    """(blk_q, blk_k, pad_q, pad_k): advisory sizes coerced to Mosaic-legal
    ones; an axis with no legal divisor near the request is padded to a
    multiple of its block (padded keys are masked, padded queries sliced
    off)."""
    def one(want, n):
        blk = max(8, min(want, -(-n // 8) * 8))
        blk -= blk % 8
        return blk, (-n) % blk
    blk_q, pad_q = one(block_q, Tq)
    blk_k, pad_k = one(block_k, Tk)
    return blk_q, blk_k, pad_q, pad_k


# -- the block-diffusion mask --------------------------------------------------
# A sequence of two halves of L rows, [noisy ; clean], each in blocks of B
# positions (arXiv:2503.09573): a noisy row sees its own noisy block, both
# ways, and the clean blocks strictly before it; a clean row sees the clean
# blocks up to and including its own; nothing sees another block's noise.
# Seen from either side a kernel block therefore touches up to TWO runs of
# the other side's kernel blocks (a noisy query block: its own noisy key
# blocks, and the clean ones from the half's start; a clean key block: the
# clean query blocks from its own on, and the noisy ones after it), and
# the walk takes the first run and then the second.

def _diffusion_geometry(L, B, blk):
    """For kernel blocks of `blk` rows that tile a half (blk divides L, so
    2 L / blk blocks a side): ``keys_of(i)`` and ``queries_of(j)``, each
    the two runs ``(first, count, first, count)`` of the other side's
    blocks that block i (j) touches, the second possibly empty, as functions
    of a (traced or Python) block index; and the static length of each
    side's walk."""
    n = L // blk

    def half(i):            # (clean?, first and last diffusion block)
        clean = i >= n
        j = i - _iwhere(clean, n, 0)
        return clean, (j * blk) // B, ((j + 1) * blk - 1) // B

    def run(p0, p1):        # kernel blocks of positions p0..p1 of a half
        lo = p0 // blk
        return lo, _imin(p1 // blk, n - 1) - lo + 1

    def keys_of(i):
        clean, b0, b1 = half(i)
        own = run(b0 * B, (b1 + 1) * B - 1)
        # clean keys: up to the last row's block, strictly before it for
        # a noisy row (none at all before block 0)
        lo, count = run(0, _iwhere(clean, b1 + 1, b1) * B - 1)
        return (_iwhere(clean, n + lo, own[0]), _iwhere(clean, count, own[1]),
                n + lo, _iwhere(clean, 0, count))

    def queries_of(j):
        clean, b0, b1 = half(j)
        own = run(b0 * B, (b1 + 1) * B - 1)
        # of a clean key block: the clean rows from its first block on,
        # the noisy rows of the blocks after that one
        lo, after = (b0 * B) // blk, _imin(((b0 + 1) * B) // blk, n)
        return (_iwhere(clean, n + lo, own[0]),
                _iwhere(clean, n - lo, own[1]),
                after, _iwhere(clean, n - after, 0))

    steps = lambda f: max(  # noqa: E731
        f(i)[1] + f(i)[3] for i in range(2 * n))
    return keys_of, queries_of, steps(keys_of), steps(queries_of)


def _diffusion_walk(L, B, blk, pad):
    """The :class:`Walk` of the block-diffusion mask over two halves of L
    rows in blocks of B positions, in kernel blocks of `blk` rows. Where a
    half is not whole blocks (tests and odd lengths) every block walks every
    block; the mask, which keys off the true rows, stays exact."""
    seen = functools.partial(_diffusion_visible, blk=blk, L=L, B=B)
    if L % blk:
        n = (2 * L + pad) // blk
        every = lambda i, s: (s, s < n)    # noqa: E731
        return Walk(n, n, every, every, n, n, seen)
    keys_of, queries_of, ksteps, qsteps = _diffusion_geometry(L, B, blk)

    def two_runs(runs_of):
        def block_of(i, s):
            lo1, n1, lo2, n2 = runs_of(i)
            second = _iwhere(n2 > 0, lo2 + _imin(s - n1, n2 - 1),
                             lo1 + n1 - 1)
            return _iwhere(s < n1, lo1 + s, second), s < n1 + n2
        return block_of

    n = 2 * L // blk
    return Walk(n, n, two_runs(keys_of), two_runs(queries_of), ksteps,
                qsteps, seen)


def _diffusion_blocks(L, block):
    """(rows of a kernel block, rows of padding after 2 L): a block that
    tiles a half where the half's length allows one, else the blocks of
    `_attn_blocks` over both halves and every block walking every block."""
    blk, pad = _attn_blocks(L, L, block, block)[::2]
    if not pad:
        return blk, 0
    return _attn_blocks(2 * L, 2 * L, block, block)[::2]


def _diffusion_visible(qi, kj, blk, L, B, keys_first=False):
    """`_visible` under the block-diffusion mask. A key's block number is
    made one comparable number, clean blocks below `past` and noisy ones
    from it on, so that a tile costs two comparisons of a column of row
    numbers against a row of key numbers: a clean key is seen up to the
    row's bound, a noisy key where it is the row's own block."""
    q_shape, k_shape = ((1, blk), (blk, 1)) if keys_first \
        else ((blk, 1), (1, blk))

    def rows_of(block, shape):
        r = block * blk + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0 if shape[1] == 1 else 1)
        clean = r >= L
        pos = r - jnp.where(clean, L, 0)
        if B & (B - 1):
            return r, clean, jax.lax.div(pos, jnp.int32(B))
        return r, clean, jax.lax.shift_right_logical(
            pos, jnp.int32(B.bit_length() - 1))

    _, q_clean, qb = rows_of(qi, q_shape)
    cols, k_clean, kb = rows_of(kj, k_shape)
    past = L // B + 1
    key = jnp.where(cols < 2 * L, jnp.where(k_clean, kb, past + kb),
                    4 * past)           # keys past the end are padding
    upto = qb - jnp.where(q_clean, 0, 1)
    own = jnp.where(q_clean, -1, past + qb)
    return (key <= upto) | (key == own)


def _walk_of(Tq, Tk, block_q, block_k, causal=True, window=0,
             block_length=0):
    """(blk_q, blk_k, pad_q, pad_k, the :class:`Walk`) of one attention
    call: the one place where a mask is chosen. `block_length` > 0 is the
    block-diffusion mask over two halves of Tq / 2 rows (`causal`, `window`
    and `block_k` are not read), else the causal and windowed one."""
    if block_length:
        blk, pad = _diffusion_blocks(Tq // 2, block_q)
        return blk, blk, pad, pad, _diffusion_walk(Tq // 2, block_length,
                                                   blk, pad)
    blk_q, blk_k, pad_q, pad_k = _attn_blocks(Tq, Tk, block_q, block_k)
    return blk_q, blk_k, pad_q, pad_k, _causal_walk(
        Tq, Tk, pad_q, pad_k, blk_q, blk_k, causal, window)


def block_diffusion_pairs(L, block_length, block=512):
    """(query-key pairs a head that the mask leaves, pairs in the kernel
    blocks the forward walk visits) for two halves of L rows."""
    blk, _, _, _, walk = _walk_of(2 * L, 2 * L, block, block,
                                  block_length=block_length)
    visited = sum(bool(walk.key_block(i, s)[1])
                  for i in range(walk.nq) for s in range(walk.key_steps))
    blocks = L // block_length
    return block_length ** 2 * blocks * (blocks + 1), visited * blk * blk


# -- the four bodies ----------------------------------------------------------
# `geo` is (block_of, seen, steps[, blk]) of the side a grid walks: a
# Walk's key_block (query_block for dk/dv), its mask, the walk's static
# length and, for the one backward kernel, the rows of a block of the side
# whose gradients stay resident.

def _dot(a, b, contract):
    # the operands' own precision (one bf16 pass for bf16), whatever
    # default the process has set: Mosaic refuses 'highest' on bf16
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                     geo, scale):
    key_block, seen_of, steps = geo
    qi, step = pl.program_id(2), pl.program_id(3)
    kj, live = key_block(qi, step)

    @pl.when(step == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        seen = seen_of(qi, kj)
        s = jnp.where(seen, _dot(q, k, ((1,), (1,))) * scale, _NEG)
        m = m_s[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_s[...] = l_s[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + _dot(p.astype(v.dtype), v,
                                              ((1,), (0,)))
        m_s[...] = m_new

    @pl.when(step == steps - 1)
    def _():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)


def _attn_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                    acc_s, *, geo, scale):
    key_block, seen_of, steps = geo
    qi, step = pl.program_id(2), pl.program_id(3)
    kj, live = key_block(qi, step)

    @pl.when(step == 0)
    def _():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _dot(q, k, ((1,), (1,))) * scale
        p = jnp.where(seen_of(qi, kj), jnp.exp(s - lse_ref[0, 0]), 0.0)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta_ref[0, 0])
        acc_s[...] += _dot(ds.astype(k.dtype), k, ((1,), (0,))) * scale

    @pl.when(step == steps - 1)
    def _():
        dq_ref[0] = acc_s[...].astype(dq_ref.dtype)


def _attn_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_s, dv_s, *, geo, scale, group):
    query_block, seen_of, steps = geo
    kj, step = pl.program_id(2), pl.program_id(3)
    qi, live = query_block(kj, step % steps)

    @pl.when(step == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _dot(q, k, ((1,), (1,))) * scale
        p = jnp.where(seen_of(qi, kj), jnp.exp(s - lse_ref[0, 0]), 0.0)
        dv_s[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta_ref[0, 0])
        dk_s[...] += _dot(ds.astype(q.dtype), q, ((0,), (0,))) * scale

    @pl.when(step == group * steps - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd_pair(s, seen, lse, delta, do, v):
    """(p, ds) [blk_k, blk_q] of one block pair from its scaled scores, in
    the operands' precision: the part of a backward step that dq, dk and dv
    share."""
    p = jnp.where(seen, jnp.exp(s - lse), 0.0)
    dp = _dot(v, do, ((1,), (1,)))
    return p.astype(do.dtype), (p * (dp - delta)).astype(do.dtype)


def _block_rows(block, blk):
    return pl.ds(pl.multiple_of(block * blk, blk), blk)


def _attn_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     dk_ref, dv_ref, dq_s, dk_s, dv_s, *, geo, scale):
    """One key/value head's cell of the grid walks its group's query heads,
    their query blocks and each block's visible key blocks; dk and dv of
    the whole sequence stay in VMEM until the cell ends."""
    key_block, seen_of, steps, blk_k = geo
    member, qi, step = (pl.program_id(axis) for axis in (2, 3, 4))
    kj, live = key_block(qi, step)
    first = (member == 0) & (qi == 0) & (step == 0)
    last = (member == pl.num_programs(2) - 1) \
        & (qi == pl.num_programs(3) - 1) & (step == steps - 1)

    @pl.when(first)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(step == 0)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        s = _dot(k, q, ((1,), (1,))) * scale
        p, ds = _bwd_pair(s, seen_of(qi, kj, keys_first=True),
                          lse_ref[0, 0, 0], delta_ref[0, 0, 0], do, v_ref[0])
        rows = _block_rows(kj, blk_k)
        dv_s[rows, :] += _dot(p, do, ((1,), (0,)))
        dk_s[rows, :] += _dot(ds, q, ((1,), (0,)))
        dq_s[...] += _dot(ds, k, ((0,), (0,)))

    @pl.when(step == steps - 1)
    def _():
        dq_ref[0] = (dq_s[...] * scale).astype(dq_ref.dtype)

    @pl.when(last)
    def _():
        dk_ref[0] = (dk_s[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd_vmem(rows, widths, dtype):
    """What decides between the one backward kernel and the two: the VMEM
    limit the one kernel needs, or None where it does not fit. Its grid
    walks one side of the block pairs; the other side's gradients (`widths`
    columns each, of a whole sequence of `rows`) stay in VMEM until every
    pair of a head has added to them: a float32 accumulator and the
    output's two pipeline buffers each, lanes padded to 128. They may take
    _BWD_RESIDENT_BYTES; the blocks in flight and the [blk, blk] score
    tiles keep Mosaic's default scope beside them."""
    lanes = sum(-(-w // 128) * 128 for w in widths)
    resident = rows * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)
    return resident + _VMEM_SCOPE if resident <= _BWD_RESIDENT_BYTES else None


def _pad_rows(x, pad):
    return x if not pad else jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


def _cols(x, pad):
    """A per-row statistic [B, H, T] as float32 columns [B, H, T + pad, 1]
    (a block of it lies along the scores' rows)."""
    return jnp.pad(x.astype(jnp.float32),
                   ((0, 0), (0, 0), (0, pad)))[..., None]


def _rows(x, pad, blk):
    """The same as float32 rows [B, H, blocks, 1, blk], a block of it along
    the columns of scores that come keys first; the last two axes are a
    block's whole, which is legal at any block size."""
    B, H, T = x.shape
    return _cols(x, pad).reshape(B, H, (T + pad) // blk, 1, blk)


def _attn_params(n_parallel, n_arbitrary=1, vmem=None):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(
        ('parallel',) * n_parallel + ('arbitrary',) * n_arbitrary),
        vmem_limit_bytes=vmem)


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


# A head's D columns of [B, T, H * D] are a legal block on the chip only
# where D is a multiple of 128 lanes. Narrower heads (64) cross the compiled
# kernels as [B, H, T, D], where D is the whole minor axis: a transpose each
# way around the call, the kernels and their grids as they are. The
# interpreter has no such rule and keeps the projections' layout (and the
# text it lowered to) unless a test sets this.
_BY_HEAD_INTERPRETED = False


def _crosses_by_head(D, interpret):
    return D % 128 != 0 and (not interpret or _BY_HEAD_INTERPRETED)


def _head_blocks(D, interpret):
    """(whether heads of D columns cross by head in this build, spec): with
    ``spec(rows, index)`` the block of `rows` rows of one head, `index`
    giving (batch, row block, head) from the grid's indices."""
    by_head = _crosses_by_head(D, interpret)

    def spec(rows, index):
        if not by_head:
            return pl.BlockSpec((1, rows, D), index)

        def head_first(*grid):
            b, r, h = index(*grid)
            return b, h, r, 0

        return pl.BlockSpec((1, None, rows, D), head_first)

    return by_head, spec


def _from_heads(x):
    """[B, H, T, D] as [B, T, H * D]."""
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _head_layout(call, by_head, heads, n_in, n_out):
    """`call` on operands [B, T, H * D], whose first `n_in` it reads, and
    whose first `n_out` results it writes, as [B, H, T, D] (`heads`: the
    heads of each such operand and result)."""
    if not by_head:
        return call

    def crossed(*operands):
        with jax.named_scope('heads'):
            operands = [_by_head(x, h) for x, h in zip(operands, heads)] \
                + list(operands[n_in:])
        results = call(*operands)
        with jax.named_scope('heads'):
            return type(results)(
                _from_heads(x) if i < n_out else x
                for i, x in enumerate(results))

    return crossed


def attention_forward(q, k, v, heads, kv_heads, causal=True, window=0,
                      scale=None, block_q=512, block_k=512,
                      name='attention', block_length=0):
    """(out [B, Tq, H * D], lse [B, H, Tq]) of grouped-query attention;
    q [B, Tq, H * D], k and v [B, Tk, KV * D]. `window` w > 0: a query sees
    only the w keys up to its own position. `block_length` > 0: the
    block-diffusion mask over a sequence of two halves in blocks of that
    many positions (`_walk_of`). The kernel is named ``<name>_fwd`` in a
    device trace."""
    B, Tq, HD = q.shape
    Tk, D, group = k.shape[1], HD // heads, heads // kv_heads
    scale = D ** -0.5 if scale is None else scale
    blk_q, blk_k, pad_q, pad_k, walk = _walk_of(
        Tq, Tk, block_q, block_k, causal, window, block_length)
    with jax.named_scope('heads'):      # as in latent_attention_forward
        q, k, v = (_pad_rows(q, pad_q), _pad_rows(k, pad_k),
                   _pad_rows(v, pad_k))

    def kv_index(b, h, i, s):
        return b, walk.key_block(i, s)[0], h // group

    kernel = functools.partial(
        _attn_fwd_kernel, scale=scale,
        geo=(walk.key_block, walk.seen, walk.key_steps))

    def build(interpret):
        by_head, spec = _head_blocks(D, interpret)
        q_spec = spec(blk_q, lambda b, h, i, s: (b, i, h))
        return _head_layout(pl.pallas_call(
            kernel,
            grid=(B, heads, walk.nq, walk.key_steps),
            in_specs=[q_spec, spec(blk_k, kv_index), spec(blk_k, kv_index)],
            out_specs=[q_spec,
                       pl.BlockSpec((1, 1, blk_q, 1),
                                    lambda b, h, i, s: (b, h, i, 0))],
            out_shape=[jax.ShapeDtypeStruct(
                (B, heads, Tq + pad_q, D) if by_head else q.shape, q.dtype),
                jax.ShapeDtypeStruct((B, heads, Tq + pad_q, 1),
                                     jnp.float32)],
            scratch_shapes=[_vmem((blk_q, 1)), _vmem((blk_q, 1)),
                            _vmem((blk_q, D))],
            compiler_params=_attn_params(3),
            interpret=interpret, name=name + '_fwd'),
            by_head, (heads, kv_heads, kv_heads), 3, 1)

    out, lse = run_kernel(build, q, k, v)
    with jax.named_scope('heads'):
        return out[:, :Tq], lse[:, :, :Tq, 0]


def attention_backward(q, k, v, out, lse, g_out, heads, kv_heads,
                       causal=True, window=0, scale=None, block_q=512,
                       block_k=512, g_lse=None, name='attention',
                       block_length=0):
    """(dq, dk, dv) of :func:`attention_forward` from its output, its
    log-sum-exp [B, H, Tq] and the output's cotangent; `g_lse` is the
    log-sum-exp's own cotangent, where it has one (it enters as a shift of
    the rows' ``delta``: d lse_i / d s_ij = p_ij). One kernel, named
    ``<name>_bwd`` in a device trace, where dk and dv of a whole sequence
    fit VMEM (:func:`_bwd_vmem`); past that two, ``<name>_dq`` and
    ``<name>_dkv``."""
    B, Tq, HD = q.shape
    Tk, D, group = k.shape[1], HD // heads, heads // kv_heads
    scale = D ** -0.5 if scale is None else scale
    blk_q, blk_k, pad_q, pad_k, walk = _walk_of(
        Tq, Tk, block_q, block_k, causal, window, block_length)
    nq, nk, ksteps, qsteps = (walk.nq, walk.nk, walk.key_steps,
                              walk.query_steps)
    # delta_i = sum_d dO_id O_id, per head: the softmax's own term
    with jax.named_scope('delta'):
        delta = jnp.sum(
            (g_out.astype(jnp.float32) * out.astype(jnp.float32))
            .reshape(B, Tq, heads, D), axis=-1).transpose(0, 2, 1)
        if g_lse is not None:
            delta = delta - g_lse.astype(jnp.float32)
    with jax.named_scope('heads'):
        q, g_out = _pad_rows(q, pad_q), _pad_rows(g_out, pad_q)
        k, v = _pad_rows(k, pad_k), _pad_rows(v, pad_k)
    k_block = lambda i, s: walk.key_block(i, s)[0]              # noqa: E731
    q_block = lambda j, s: walk.query_block(j, s % qsteps)[0]   # noqa: E731
    keys = (walk.key_block, walk.seen, ksteps)
    vmem = _bwd_vmem(Tk + pad_k, (D, D), k.dtype)
    crossing = ((heads, kv_heads, kv_heads, heads), 4)

    def shapes(by_head, *like):
        return [jax.ShapeDtypeStruct(
            (B, x.shape[2] // D, x.shape[1], D) if by_head else x.shape,
            x.dtype) for x in like]

    if vmem is not None:
        # grid (batch, key/value head g, its m-th query head, i, s)
        row_spec = pl.BlockSpec((1, 1, 1, 1, blk_q), lambda b, g, m, i, s: (
            b, g * group + m, i, 0, 0))

        def build(interpret):
            by_head, spec = _head_blocks(D, interpret)
            q_spec = spec(blk_q, lambda b, g, m, i, s: (b, i, g * group + m))
            k_spec = spec(blk_k, lambda b, g, m, i, s: (b, k_block(i, s), g))
            whole = spec(Tk + pad_k, lambda b, g, m, i, s: (b, 0, g))
            return _head_layout(pl.pallas_call(
                functools.partial(_attn_bwd_kernel, geo=keys + (blk_k,),
                                  scale=scale),
                grid=(B, kv_heads, group, nq, ksteps),
                in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
                out_specs=[q_spec, whole, whole],
                out_shape=shapes(by_head, q, k, v),
                scratch_shapes=[_vmem((blk_q, D)), _vmem((Tk + pad_k, D)),
                                _vmem((Tk + pad_k, D))],
                compiler_params=_attn_params(2, 3, vmem),
                interpret=interpret, name=name + '_bwd'),
                by_head, *crossing, 3)

        dq, dk, dv = run_kernel(build, q, k, v, g_out,
                                _rows(lse, pad_q, blk_q),
                                _rows(delta, pad_q, blk_q))
        with jax.named_scope('heads'):
            return dq[:, :Tq], dk[:, :Tk], dv[:, :Tk]
    lse, delta = _cols(lse, pad_q), _cols(delta, pad_q)

    def kv_index(b, h, i, s):
        return b, k_block(i, s), h // group

    col_spec = pl.BlockSpec((1, 1, blk_q, 1), lambda b, h, i, s: (b, h, i, 0))

    def build_dq(interpret):
        by_head, spec = _head_blocks(D, interpret)
        q_spec = spec(blk_q, lambda b, h, i, s: (b, i, h))
        return _head_layout(pl.pallas_call(
            functools.partial(_attn_dq_kernel, geo=keys, scale=scale),
            grid=(B, heads, nq, ksteps),
            in_specs=[q_spec, spec(blk_k, kv_index), spec(blk_k, kv_index),
                      q_spec, col_spec, col_spec],
            out_specs=[q_spec],
            out_shape=shapes(by_head, q),
            scratch_shapes=[_vmem((blk_q, D))],
            compiler_params=_attn_params(3),
            interpret=interpret, name=name + '_dq'),
            by_head, *crossing, 1)

    dq, = run_kernel(build_dq, q, k, v, g_out, lse, delta)

    def q_index(b, g, j, s):
        return b, q_block(j, s), g * group + s // qsteps

    def qcol_index(b, g, j, s):
        return b, g * group + s // qsteps, q_block(j, s), 0

    qcol_spec = pl.BlockSpec((1, 1, blk_q, 1), qcol_index)

    def build_dkv(interpret):
        by_head, spec = _head_blocks(D, interpret)
        k_spec = spec(blk_k, lambda b, g, j, s: (b, j, g))
        qw_spec = spec(blk_q, q_index)
        return _head_layout(pl.pallas_call(
            functools.partial(
                _attn_dkv_kernel, scale=scale, group=group,
                geo=(walk.query_block, walk.seen, qsteps)),
            grid=(B, kv_heads, nk, group * qsteps),
            in_specs=[qw_spec, k_spec, k_spec, qw_spec, qcol_spec, qcol_spec],
            out_specs=[k_spec, k_spec],
            out_shape=shapes(by_head, k, v),
            scratch_shapes=[_vmem((blk_k, D)), _vmem((blk_k, D))],
            compiler_params=_attn_params(3),
            interpret=interpret, name=name + '_dkv'),
            by_head, *crossing, 2)

    dk, dv = run_kernel(build_dkv, q, k, v, g_out, lse, delta)
    with jax.named_scope('heads'):
        return dq[:, :Tq], dk[:, :Tk], dv[:, :Tk]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def blockwise_attention(q, k, v, heads, kv_heads, causal=True, window=0,
                        scale=None, block_q=512, block_k=512,
                        name='attention', block_length=0):
    """Grouped-query attention, forward and backward by the blockwise
    kernels above: causal and optionally windowed, or with `block_length`
    > 0 under the block-diffusion mask (q, k and v then hold a noisy and a
    clean copy of Tq / 2 positions, in blocks of that many). q
    [B, Tq, H * D], k and v [B, Tk, KV * D]; returns [B, Tq, H * D]."""
    return attention_forward(q, k, v, heads, kv_heads, causal, window,
                             scale, block_q, block_k, name, block_length)[0]


def _blockwise_fwd(q, k, v, heads, kv_heads, causal, window, scale, block_q,
                   block_k, name, block_length):
    out, lse = attention_forward(q, k, v, heads, kv_heads, causal, window,
                                 scale, block_q, block_k, name, block_length)
    # what attention_backward reads that is made here: a mirrored stage
    # keeps the two, so the kernel runs once. q, k and v are made outside,
    # where the policy judges them: the op that calls names them
    # (ops/transformer.py)
    out, lse = dear(out, name + '_out'), dear(lse, name + '_lse')
    return out, (q, k, v, out, lse)


def _blockwise_bwd(heads, kv_heads, causal, window, scale, block_q, block_k,
                   name, block_length, res, g):
    q, k, v, out, lse = res
    return attention_backward(q, k, v, out, lse, g, heads, kv_heads, causal,
                              window, scale, block_q, block_k, name=name,
                              block_length=block_length)


blockwise_attention.defvjp(_blockwise_fwd, _blockwise_bwd)


# -- the [B, T, H, D] entry points --------------------------------------------
# ring attention's (parallel/ring_attention.py), chip_smoke.py's and the
# tests': every head its own key/value head, causal or not, Tq <= Tk, the
# mask aligned bottom right (decode convention). The kernels are the ones
# above under the name ``flash_attention``; [B, T, H, D] is [B, T, H * D]
# by a reshape.

def _flash_lse_ref(q, k, v, causal, scale):
    """(out, lse) in plain jnp: the dense oracle of the tests."""
    s = jnp.einsum('bqhd,bkhd->bhqk', q * scale, k)
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        s = jnp.where(mask, s, _NEG)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    return jnp.einsum('bhqk,bkhd->bqhd', p, v), lse


def _flash_ref(q, k, v, causal, scale):
    s = jnp.einsum('bqhd,bkhd->bhqk', q * scale, k)
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v)


def _merged_heads(x):
    B, T, H, D = x.shape
    return x.reshape(B, T, H * D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lse(q, k, v, causal=False, scale=None, block_q=128,
                        block_k=128):
    """(out [B, Tq, H, D], lse [B, H, Tq]) of attention over q
    [B, Tq, H, D], k and v [B, Tk, H, D], shapes like
    ring_attention.attention_reference (its numeric oracle). The per-row
    log-sum-exp is the merge statistic ring attention needs to combine
    normalized chunk outputs exactly; its cotangent is taken in the
    backward pass (the merge weights depend on it). ``block_q``/``block_k``
    are advisory tile sizes (`_attn_blocks`)."""
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k)[0]


def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128):
    """:func:`flash_attention_lse` without the log-sum-exp."""
    return flash_attention_lse(q, k, v, causal, scale, block_q, block_k)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if causal and Tq > Tk:
        # bottom-right alignment gives the first Tq-Tk query rows zero
        # visible keys (softmax over empty set — NaN in the oracle);
        # reject rather than return silently-wrong finite values
        raise ValueError('causal attention requires Tq <= Tk '
                         '(got Tq=%d, Tk=%d)' % (Tq, Tk))
    if Tk == 0:
        # softmax over an empty key set is undefined (NaN in the
        # oracle); fail loudly instead of tracing a 0-size block
        raise ValueError('attention requires at least one key (Tk=0)')
    if q.size == 0:                  # empty batch/seq: nothing to launch
        out, lse = jnp.zeros(q.shape, q.dtype), \
            jnp.zeros((B, H, Tq), jnp.float32)
    else:
        out, lse = attention_forward(
            _merged_heads(q), _merged_heads(k), _merged_heads(v), H, H,
            causal, 0, scale, block_q, block_k, 'flash_attention')
        out = out.reshape(q.shape)
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if q.size == 0:
        return jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
    H = q.shape[2]
    grads = attention_backward(
        *map(_merged_heads, (q, k, v, out)), lse, _merged_heads(g[0]), H, H,
        causal, 0, scale, block_q, block_k, g_lse=g[1],
        name='flash_attention')
    return tuple(dx.reshape(x.shape) for dx, x in zip(grads, (q, k, v)))


flash_attention_lse.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Grouped matrix product: rows sorted by expert, one weight per expert
# ---------------------------------------------------------------------------
# The rows of x come sorted by group, each group padded to whole tiles of
# GROUP_TILE rows (at least one), in a buffer of a static worst-case length.
# `tile_group[t]` is tile t's group and `n_tiles[0]` how many tiles hold
# rows: the tiles past them are neither fetched nor computed (their index
# maps stay on the last tile that was), so the work follows the rows
# present, not the buffer. Their rows of the output are not written; of the
# weight gradient, which adds into the array it is given, a group without a
# tile among those present keeps what the array held.

GROUP_TILE = 128


def _gmm_kernel(tile_group, n_tiles, x_ref, w_ref, o_ref, *, transpose_w):
    @pl.when(pl.program_id(1) < n_tiles[0])
    def _():
        contract = ((1,), (1,)) if transpose_w else ((1,), (0,))
        o_ref[...] = _dot(x_ref[...], w_ref[0], contract).astype(o_ref.dtype)


_ZERO_ROWS = 256


def _zero(o_ref):
    """Zeros into a block, _ZERO_ROWS of its leading axis at a time where
    they are many: one store over [8192, 1024] unrolls into 8192 stores,
    most of such a kernel's code and of its compile."""
    rows = o_ref.shape[0]
    if rows % _ZERO_ROWS or rows == _ZERO_ROWS:
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        return

    def some(i, carry):
        at = pl.multiple_of(i * _ZERO_ROWS, _ZERO_ROWS)
        o_ref[pl.ds(at, _ZERO_ROWS)] = jnp.zeros(
            (_ZERO_ROWS,) + o_ref.shape[1:], o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // _ZERO_ROWS, some, 0)


def _start_from(acc_block, o_ref, first, pass_index):
    """Where `first`, the output block starts from the accumulator's, which
    is the output's own array and holds zeros in pass 0: not worth a read
    then."""
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(first & (pass_index[0] == 0))
    def _():
        _zero(o_ref)

    @pl.when(first & (pass_index[0] > 0))
    def _():
        pltpu.sync_copy(acc_block, o_ref)


def _tgmm_kernel(tile_group, n_tiles, pass_index, x_ref, y_ref, acc_ref,
                 o_ref):
    n, t = pl.program_id(0), pl.program_id(1)
    live = t < n_tiles[0]
    g = tile_group[t]
    first = live & ((t == 0) | (g != tile_group[jnp.maximum(t - 1, 0)]))
    tn = o_ref.shape[2]
    _start_from(acc_ref.at[pl.ds(g, 1), :, pl.ds(n * tn, tn)], o_ref, first,
                pass_index)

    @pl.when(live)
    def _():
        o_ref[0] += _dot(x_ref[...], y_ref[...], ((0,), (0,)))


def _col_block(n, want=512):
    for b in (want, 256, 128):
        if n % b == 0:
            return b
    return n


def _gmm_grid(num_scalars, grid, in_specs, out_specs, scratch=(),
              vmem=None):
    from jax.experimental.pallas import tpu as pltpu
    return dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_scalars, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=vmem))


def _last_tile(t, n_tiles):
    """Tile t, or the last present for a tile past it (not fetched again)."""
    return jnp.minimum(t, n_tiles[0] - 1)


def grouped_matmul(x, w, tile_group, n_tiles, transpose_w=False,
                   name='grouped_matmul'):
    """out[r] = x[r] @ w[group of r's tile] (or its transpose): x [R, K],
    w [G, K, N] ([G, N, K] with `transpose_w`), out [R, N] in x's dtype.
    Rows of tiles past ``n_tiles[0]`` are left unwritten."""
    R, K = x.shape
    N = w.shape[1] if transpose_w else w.shape[2]
    tm, tn = GROUP_TILE, _col_block(N)
    w_block = (1, tn, K) if transpose_w else (1, K, tn)

    def w_index(n, t, tile_group, n_tiles):
        g = tile_group[_last_tile(t, n_tiles)]
        return (g, n, 0) if transpose_w else (g, 0, n)

    kernel = functools.partial(_gmm_kernel, transpose_w=transpose_w)
    return run_kernel(lambda interpret: pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        interpret=interpret, name=name,
        **_gmm_grid(2, (N // tn, R // tm), [
            pl.BlockSpec((tm, K),
                         lambda n, t, tg, nt: (_last_tile(t, nt), 0)),
            pl.BlockSpec(w_block, w_index)],
            pl.BlockSpec((tm, tn),
                         lambda n, t, tg, nt: (_last_tile(t, nt), n)))),
        tile_group, n_tiles, x, w)


def grouped_matmul_dw(x, y, tile_group, n_tiles, pass_index, acc,
                      name='grouped_matmul_dw'):
    """out[g] = acc[g] + sum over the rows r of group g among the tiles
    present of x[r]^T y[r]: x [R, K], y [R, N], acc and out [G, K, N]
    float32 in one array (the kernel adds into `acc` in place). A group
    without a tile among the ``n_tiles[0]`` present is not visited and keeps
    acc's values. ``pass_index[0]`` is the caller's count of calls into this
    acc so far: at 0 acc holds zeros, by contract, and is not read."""
    R, K = x.shape
    N = y.shape[1]
    tm, tn = GROUP_TILE, _col_block(N, 256)
    return run_kernel(lambda interpret: pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={5: 0},
        interpret=interpret, name=name,
        **_gmm_grid(3, (N // tn, R // tm), [
            pl.BlockSpec((tm, K),
                         lambda n, t, tg, nt, p: (_last_tile(t, nt), 0)),
            pl.BlockSpec((tm, tn),
                         lambda n, t, tg, nt, p: (_last_tile(t, nt), n)),
            pl.BlockSpec(memory_space=pl.ANY)],
            pl.BlockSpec((1, K, tn), lambda n, t, tg, nt, p:
                         (tg[_last_tile(t, nt)], 0, n)))),
        tile_group, n_tiles, pass_index, x, y, acc)


# The way back from the sorted rows to the tokens: each row of the tiles
# present is added into its token's row of a float32 [T, d] sum. A block of
# columns of the sum is resident over the walk of the row tiles, and a row is
# one load, one add and one store of a dynamic row of it, row after row, so
# that rows of one token add up in the order they come. A padding row adds
# zero to the last token.

_ROWS_TO_TOKENS_BYTES = 32 << 20    # of the resident block of the sum
_ROWS_UNROLLED = 8


def _rows_to_tokens_kernel(token, n_tiles, pass_index, *refs, scaled):
    scale = refs[0] if scaled else None
    src_ref, acc_ref, o_ref, *wide = refs[1:] if scaled else refs
    n, t = pl.program_id(0), pl.program_id(1)
    tm, td = src_ref.shape
    T = o_ref.shape[0]
    _start_from(acc_ref.at[:, pl.ds(n * td, td)], o_ref, t == 0, pass_index)

    @pl.when(t < n_tiles[0])
    def _():
        rows_ref = src_ref
        if wide:    # one row of a packed dtype is no whole sublane
            rows_ref, = wide
            rows_ref[...] = src_ref[...].astype(jnp.float32)

        def some(i, carry):
            for j in range(_ROWS_UNROLLED):
                r = i * _ROWS_UNROLLED + j
                at = token[t * tm + r]
                row = rows_ref[pl.ds(r, 1), :]
                if scaled:
                    row = row * scale[t * tm + r]
                o_ref[pl.ds(jnp.minimum(at, T - 1), 1), :] += jnp.where(
                    at < T, row, 0.0)
            return carry

        jax.lax.fori_loop(0, tm // _ROWS_UNROLLED, some, 0)


def rows_to_tokens(src, token, n_tiles, pass_index, acc, scale=None,
                   name='rows_to_tokens'):
    """out[token[r]] = acc[token[r]] + scale[r] * src[r] summed over the
    real rows r (token[r] < T) of the ``n_tiles[0]`` tiles present: src
    [R, d] sorted rows, token [R] int32 (T for a padding row, which adds
    nothing whatever it holds), scale [R] float32 or None for 1, acc and out
    [T, d] float32 in one array (the kernel adds into `acc` in place). Rows
    of tiles past the last present are not read. ``pass_index[0]`` is the
    caller's count of calls into this acc so far: at 0 acc holds zeros, by
    contract, and is not read."""
    R, d = src.shape
    T = acc.shape[0]
    tm = GROUP_TILE
    # the widest block of columns, whole lanes, that the sum's rows fit
    td = next((b for b in range(d - d % 128, 0, -128)
               if d % b == 0 and T * b * 4 <= _ROWS_TO_TOKENS_BYTES), d)
    scaled = scale is not None
    prefetched = (token, n_tiles, pass_index) + ((scale,) if scaled else ())
    wide = [] if src.dtype == jnp.float32 else [_vmem((tm, td))]
    kernel = functools.partial(_rows_to_tokens_kernel, scaled=scaled)
    return run_kernel(lambda interpret: pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={len(prefetched) + 1: 0},
        interpret=interpret, name=name,
        # the resident block is written back while the next is walked
        **_gmm_grid(len(prefetched), (d // td, R // tm), [
            pl.BlockSpec((tm, td), lambda n, t, tok, nt, *_:
                         (_last_tile(t, nt), n)),
            pl.BlockSpec(memory_space=pl.ANY)],
            pl.BlockSpec((T, td), lambda n, t, *_: (0, n)),
            scratch=wide, vmem=2 * T * td * 4 + (16 << 20))),
        *prefetched, src, acc)


# ---------------------------------------------------------------------------
# Latent attention: keys wider than values, one rotary key head for all heads
# ---------------------------------------------------------------------------
# A head's score is the sum of two products: its own ``q_nope k_nope^T`` and
# ``q_rope k_rope^T`` against the one rotary key head that every head shares
# (k_rope [B, T, Dr], never broadcast). Values are narrower than keys
# (Dn + Dr against Dv), so the kernels above, which take one head size for
# all three, do not fit; these are a family of their own that reads the same
# record of a mask (`Walk`, from `_walk_of`) in the same form (`geo`), and
# the grouped-query kernels stay as they were. q_nope, k_nope and v keep the
# projections' layout [B, T, H * D]; a head's 64 rotary query columns are no
# legal block of [B, T, H * 64] (the minor block is 128 lanes or the whole
# axis), so q_rope and its gradient cross as [B, H, T, Dr]. The shared key's
# gradient is a sum over heads: the backward kernel writes each head's part
# in float32 and the sum is one reduction outside. The one backward kernel
# walks the other way round from the grouped-query one: a head's cell walks
# its key blocks and each one's query blocks with dk_nope, dk_rope and dv in
# scratch, and holds the head's dq_nope and dq_rope of the whole sequence
# (6 MiB of float32 at 8192 tokens, where dk and dv would be 10).

def _latent_scores(qn, qr, kn, kr, scale):
    return (_dot(qn, kn, ((1,), (1,))) + _dot(qr, kr, ((1,), (1,)))) * scale


def _latent_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                       m_s, l_s, acc_s, *, geo, scale):
    key_block, seen_of, steps = geo
    qi, step = pl.program_id(2), pl.program_id(3)
    kj, live = key_block(qi, step)

    @pl.when(step == 0)
    def _():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        v = v_ref[0]
        seen = seen_of(qi, kj)
        s = jnp.where(seen, _latent_scores(qn_ref[0], qr_ref[0, 0],
                                           kn_ref[0], kr_ref[0], scale), _NEG)
        m = m_s[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_s[...] = l_s[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + _dot(p.astype(v.dtype), v,
                                              ((1,), (0,)))
        m_s[...] = m_new

    @pl.when(step == steps - 1)
    def _():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)


def _latent_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dqn_ref, dqr_ref, dqn_s, dqr_s, *, geo,
                      scale):
    key_block, seen_of, steps = geo
    qi, step = pl.program_id(2), pl.program_id(3)
    kj, live = key_block(qi, step)

    @pl.when(step == 0)
    def _():
        dqn_s[...] = jnp.zeros(dqn_s.shape, jnp.float32)
        dqr_s[...] = jnp.zeros(dqr_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        kn, kr, v, do = kn_ref[0], kr_ref[0], v_ref[0], do_ref[0]
        s = _latent_scores(qn_ref[0], qr_ref[0, 0], kn, kr, scale)
        p = jnp.where(seen_of(qi, kj), jnp.exp(s - lse_ref[0, 0]), 0.0)
        dp = _dot(do, v, ((1,), (1,)))
        ds = (p * (dp - delta_ref[0, 0])).astype(kn.dtype)
        dqn_s[...] += _dot(ds, kn, ((1,), (0,))) * scale
        dqr_s[...] += _dot(ds, kr, ((1,), (0,))) * scale

    @pl.when(step == steps - 1)
    def _():
        dqn_ref[0] = dqn_s[...].astype(dqn_ref.dtype)
        dqr_ref[0, 0] = dqr_s[...].astype(dqr_ref.dtype)


def _latent_dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dkn_ref, dkr_ref, dv_ref, dkn_s,
                       dkr_s, dv_s, *, geo, scale):
    query_block, seen_of, steps = geo
    kj, step = pl.program_id(2), pl.program_id(3)
    qi, live = query_block(kj, step)

    @pl.when(step == 0)
    def _():
        dkn_s[...] = jnp.zeros(dkn_s.shape, jnp.float32)
        dkr_s[...] = jnp.zeros(dkr_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        qn, qr, do = qn_ref[0], qr_ref[0, 0], do_ref[0]
        s = _latent_scores(qn, qr, kn_ref[0], kr_ref[0], scale)
        p = jnp.where(seen_of(qi, kj), jnp.exp(s - lse_ref[0, 0]), 0.0)
        dv_s[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot(do, v_ref[0], ((1,), (1,)))
        ds = (p * (dp - delta_ref[0, 0])).astype(qn.dtype)
        dkn_s[...] += _dot(ds, qn, ((0,), (0,))) * scale
        dkr_s[...] += _dot(ds, qr, ((0,), (0,))) * scale

    @pl.when(step == steps - 1)
    def _():
        dkn_ref[0] = dkn_s[...].astype(dkn_ref.dtype)
        dkr_ref[0, 0] = dkr_s[...]
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _latent_bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                       dqn_s, dqr_s, dkn_s, dkr_s, dv_s, *, geo, scale):
    """A head's cell of the grid walks its key blocks and each one's visible
    query blocks; the head's dq_nope and dq_rope of the whole sequence stay
    in VMEM until the cell ends."""
    query_block, seen_of, steps, blk_q = geo
    kj, step = pl.program_id(2), pl.program_id(3)
    qi, live = query_block(kj, step)

    @pl.when((kj == 0) & (step == 0))
    def _():
        dqn_s[...] = jnp.zeros(dqn_s.shape, jnp.float32)
        dqr_s[...] = jnp.zeros(dqr_s.shape, jnp.float32)

    @pl.when(step == 0)
    def _():
        dkn_s[...] = jnp.zeros(dkn_s.shape, jnp.float32)
        dkr_s[...] = jnp.zeros(dkr_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(live)
    def _():
        qn, qr, kn, kr, do = qn_ref[0], qr_ref[0, 0], kn_ref[0], kr_ref[0], \
            do_ref[0]
        s = _latent_scores(kn, kr, qn, qr, scale)
        p, ds = _bwd_pair(s, seen_of(qi, kj, keys_first=True),
                          lse_ref[0, 0, 0], delta_ref[0, 0, 0], do, v_ref[0])
        rows = _block_rows(qi, blk_q)
        dv_s[...] += _dot(p, do, ((1,), (0,)))
        dkn_s[...] += _dot(ds, qn, ((1,), (0,)))
        dkr_s[...] += _dot(ds, qr, ((1,), (0,)))
        dqn_s[rows, :] += _dot(ds, kn, ((0,), (0,)))
        dqr_s[rows, :] += _dot(ds, kr, ((0,), (0,)))

    @pl.when(step == steps - 1)
    def _():
        dkn_ref[0] = (dkn_s[...] * scale).astype(dkn_ref.dtype)
        dkr_ref[0, 0] = dkr_s[...] * scale
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)

    @pl.when((kj == pl.num_programs(2) - 1) & (step == steps - 1))
    def _():
        dqn_ref[0] = (dqn_s[...] * scale).astype(dqn_ref.dtype)
        dqr_ref[0, 0] = (dqr_s[...] * scale).astype(dqr_ref.dtype)


def _by_head(x, heads):
    """[B, T, H * D] as [B, H, T, D]."""
    B, T, HD = x.shape
    return x.reshape(B, T, heads, HD // heads).transpose(0, 2, 1, 3)


def _latent_dims(q_nope, k_rope, v, heads, scale=None):
    """(Dn, Dr, Dv, scale) from the operands' widths; the scores' scale is
    ``1 / sqrt(Dn + Dr)`` unless one is given."""
    Dn, Dr, Dv = q_nope.shape[2] // heads, k_rope.shape[2], \
        v.shape[2] // heads
    return Dn, Dr, Dv, scale or (Dn + Dr) ** -0.5


def _latent_specs(blk_q, blk_k, q_block, k_block):
    """Block specs of a grid (batch, head, i, s) whose last two axes pick
    the query block ``q_block(i, s)`` and the key block ``k_block(i, s)``:
    (q, k) of width D for [B, T, H * D], (qh, kh) for [B, H, T, D], kr for
    the shared rotary key [B, T, D]."""
    def q(D):
        return pl.BlockSpec((1, blk_q, D),
                            lambda b, h, i, s: (b, q_block(i, s), h))

    def k(D):
        return pl.BlockSpec((1, blk_k, D),
                            lambda b, h, i, s: (b, k_block(i, s), h))

    def qh(D):
        return pl.BlockSpec((1, 1, blk_q, D),
                            lambda b, h, i, s: (b, h, q_block(i, s), 0))

    def kh(D):
        return pl.BlockSpec((1, 1, blk_k, D),
                            lambda b, h, i, s: (b, h, k_block(i, s), 0))

    def kr(D):
        return pl.BlockSpec((1, blk_k, D),
                            lambda b, h, i, s: (b, k_block(i, s), 0))

    return q, k, qh, kh, kr


def latent_attention_forward(q_nope, q_rope, k_nope, k_rope, v, heads,
                             block_q=512, block_k=512,
                             name='attention_latent', scale=None):
    """(out [B, T, H * Dv], lse [B, H, T]) of causal attention whose score
    is ``(q_nope k_nope^T + q_rope k_rope^T) / sqrt(Dn + Dr)``: q_nope and
    k_nope [B, T, H * Dn], q_rope [B, T, H * Dr], k_rope [B, T, Dr] (one
    head, read by all), v [B, T, H * Dv]. The kernel is named
    ``<name>_fwd`` in a device trace."""
    B, T, _ = q_nope.shape
    Dn, Dr, Dv, scale = _latent_dims(q_nope, k_rope, v, heads, scale)
    blk_q, blk_k, pad_q, pad_k, walk = _walk_of(T, T, block_q, block_k)
    geo = (walk.key_block, walk.seen, walk.key_steps)
    q, k, qh, _, kr = _latent_specs(
        blk_q, blk_k, lambda i, s: i, lambda i, s: walk.key_block(i, s)[0])
    # 'heads', 'delta': trace-time names for the work around a kernel
    # (pads, per-head layouts, the softmax's own term), for the compiled
    # program's scope map (telemetry/programs.py); the call itself stands
    # under the kernel's name
    with jax.named_scope('heads'):
        operands = (_pad_rows(q_nope, pad_q),
                    _by_head(_pad_rows(q_rope, pad_q), heads),
                    _pad_rows(k_nope, pad_k), _pad_rows(k_rope, pad_k),
                    _pad_rows(v, pad_k))
    out, lse = run_kernel(lambda interpret: pl.pallas_call(
        functools.partial(_latent_fwd_kernel, geo=geo, scale=scale),
        grid=(B, heads, walk.nq, walk.key_steps),
        in_specs=[q(Dn), qh(Dr), k(Dn), kr(Dr), k(Dv)],
        out_specs=[q(Dv), qh(1)],
        out_shape=[jax.ShapeDtypeStruct((B, T + pad_q, heads * Dv), v.dtype),
                   jax.ShapeDtypeStruct((B, heads, T + pad_q, 1),
                                        jnp.float32)],
        scratch_shapes=[_vmem((blk_q, 1)), _vmem((blk_q, 1)),
                        _vmem((blk_q, Dv))],
        compiler_params=_attn_params(3),
        interpret=interpret, name=name + '_fwd'), *operands)
    with jax.named_scope('heads'):
        return out[:, :T], lse[:, :, :T, 0]


def latent_attention_backward(q_nope, q_rope, k_nope, k_rope, v, out, lse,
                              g_out, heads, block_q=512, block_k=512,
                              name='attention_latent', scale=None):
    """(dq_nope, dq_rope, dk_nope, dk_rope, dv) of
    :func:`latent_attention_forward` from its output, its log-sum-exp and
    the output's cotangent. One kernel, ``<name>_bwd``, over the query
    blocks of a key block and head, where a head's dq_nope and dq_rope of
    the whole sequence fit VMEM (:func:`_bwd_vmem`); past that two:
    ``<name>_dq`` over the key blocks of a query block, ``<name>_dkv`` as
    the one. The shared rotary key's gradient is the sum of the heads'
    parts."""
    B, T, _ = q_nope.shape
    Dn, Dr, Dv, scale = _latent_dims(q_nope, k_rope, v, heads, scale)
    blk_q, blk_k, pad_q, pad_k, walk = _walk_of(T, T, block_q, block_k)
    Tq, Tk = T + pad_q, T + pad_k
    queries = (walk.query_block, walk.seen, walk.query_steps)
    # delta_i = sum_d dO_id O_id, per head: the softmax's own term
    with jax.named_scope('delta'):
        delta = jnp.sum(
            (g_out.astype(jnp.float32) * out.astype(jnp.float32))
            .reshape(B, T, heads, Dv), axis=-1).transpose(0, 2, 1)
    with jax.named_scope('heads'):
        operands = (_pad_rows(q_nope, pad_q),
                    _by_head(_pad_rows(q_rope, pad_q), heads),
                    _pad_rows(k_nope, pad_k), _pad_rows(k_rope, pad_k),
                    _pad_rows(v, pad_k), _pad_rows(g_out, pad_q))
    dk_shapes = [jax.ShapeDtypeStruct((B, Tk, heads * Dn), k_nope.dtype),
                 jax.ShapeDtypeStruct((B, heads, Tk, Dr), jnp.float32),
                 jax.ShapeDtypeStruct((B, Tk, heads * Dv), v.dtype)]
    dq_shapes = [jax.ShapeDtypeStruct((B, Tq, heads * Dn), q_nope.dtype),
                 jax.ShapeDtypeStruct((B, heads, Tq, Dr), q_rope.dtype)]
    dk_scratch = [_vmem((blk_k, Dn)), _vmem((blk_k, Dr)), _vmem((blk_k, Dv))]

    def in_specs(q, k, qh, kr, stat):
        return [q(Dn), qh(Dr), k(Dn), kr(Dr), k(Dv), q(Dv), stat, stat]

    vmem = _bwd_vmem(Tq, (Dn, Dr), q_nope.dtype)
    q_block = lambda j, s: walk.query_block(j, s)[0]    # noqa: E731
    q, k, qh, kh, kr = _latent_specs(blk_q, blk_k, q_block, lambda j, s: j)
    if vmem is not None:
        row = pl.BlockSpec((1, 1, 1, 1, blk_q), lambda b, h, j, s: (
            b, h, q_block(j, s), 0, 0))
        whole = [pl.BlockSpec((1, Tq, Dn), lambda b, h, j, s: (b, 0, h)),
                 pl.BlockSpec((1, 1, Tq, Dr), lambda b, h, j, s: (b, h, 0, 0))]
        dqn, dqr, dkn, dkr, dv = run_kernel(lambda interpret: pl.pallas_call(
            functools.partial(_latent_bwd_kernel, geo=queries + (blk_q,),
                              scale=scale),
            grid=(B, heads, walk.nk, walk.query_steps),
            in_specs=in_specs(q, k, qh, kr, row),
            out_specs=whole + [k(Dn), kh(Dr), k(Dv)],
            out_shape=dq_shapes + dk_shapes,
            scratch_shapes=[_vmem((Tq, Dn)), _vmem((Tq, Dr))] + dk_scratch,
            compiler_params=_attn_params(2, 2, vmem),
            interpret=interpret, name=name + '_bwd'),
            *operands, _rows(lse, pad_q, blk_q), _rows(delta, pad_q, blk_q))
    else:
        operands += (_cols(lse, pad_q), _cols(delta, pad_q))
        dkn, dkr, dv = run_kernel(lambda interpret: pl.pallas_call(
            functools.partial(_latent_dkv_kernel, geo=queries, scale=scale),
            grid=(B, heads, walk.nk, walk.query_steps),
            in_specs=in_specs(q, k, qh, kr, qh(1)),
            out_specs=[k(Dn), kh(Dr), k(Dv)], out_shape=dk_shapes,
            scratch_shapes=dk_scratch, compiler_params=_attn_params(3),
            interpret=interpret, name=name + '_dkv'), *operands)
        q, k, qh, _, kr = _latent_specs(
            blk_q, blk_k, lambda i, s: i,
            lambda i, s: walk.key_block(i, s)[0])
        dqn, dqr = run_kernel(lambda interpret: pl.pallas_call(
            functools.partial(
                _latent_dq_kernel, scale=scale,
                geo=(walk.key_block, walk.seen, walk.key_steps)),
            grid=(B, heads, walk.nq, walk.key_steps),
            in_specs=in_specs(q, k, qh, kr, qh(1)),
            out_specs=[q(Dn), qh(Dr)], out_shape=dq_shapes,
            scratch_shapes=[_vmem((blk_q, Dn)), _vmem((blk_q, Dr))],
            compiler_params=_attn_params(3),
            interpret=interpret, name=name + '_dq'), *operands)
    with jax.named_scope('heads'):
        dqr = dqr.transpose(0, 2, 1, 3).reshape(B, Tq, heads * Dr)
        dkr = jnp.sum(dkr, axis=1).astype(k_rope.dtype)
        return dqn[:, :T], dqr[:, :T], dkn[:, :T], dkr[:, :T], dv[:, :T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def latent_attention(q_nope, q_rope, k_nope, k_rope, v, heads, block_q=512,
                     block_k=512, name='attention_latent', scale=None):
    """Causal latent attention in its expanded (training) form, forward
    and backward by the kernels above; returns [B, T, H * Dv]."""
    return latent_attention_forward(q_nope, q_rope, k_nope, k_rope, v, heads,
                                    block_q, block_k, name, scale)[0]


def _latent_fwd(q_nope, q_rope, k_nope, k_rope, v, heads, block_q, block_k,
                name, scale):
    out, lse = latent_attention_forward(q_nope, q_rope, k_nope, k_rope, v,
                                        heads, block_q, block_k, name, scale)
    # as _blockwise_fwd: a mirrored stage keeps the kernel's two outputs;
    # of the operands the op names the queries and the rotary key
    out, lse = dear(out, name + '_out'), dear(lse, name + '_lse')
    return out, (q_nope, q_rope, k_nope, k_rope, v, out, lse)


def _latent_bwd(heads, block_q, block_k, name, scale, res, g):
    return latent_attention_backward(*res, g, heads, block_q, block_k, name,
                                     scale)


latent_attention.defvjp(_latent_fwd, _latent_bwd)


# ---------------------------------------------------------------------------
# Hyper-connections: n residual streams, read and written by per-token
# coefficients
# ---------------------------------------------------------------------------
# The residual of a token is X [n, d], carried as one row of [rows, n * d]
# (stream j its columns [j * d, (j + 1) * d)). A sublayer reads
# ``y = sum_j H_pre[j] X[j]`` and writes ``X'[i] = H_post[i] z + sum_j
# M[i, j] X[j]``; the coefficients come from the row itself,
# ``c = alpha * (x W^T) / rms(x) + bias``. Both passes are bound by the
# bytes of X: each kernel reads a block of rows once, in the operands' own
# precision, works stream by stream in float32 and writes its result; no
# float32 [rows, n * d] array exists. Coefficients cross as float32
# [rows, HYPER_COLS] arrays whose unused columns are zero; what lies
# between the two kernels (two sigmoids, the exponential, the projection
# onto the doubly stochastic matrices) is 24 numbers a token and XLA's.

HYPER_COLS = 32                 # columns of a coefficient array
_HYPER_BLOCK_BYTES = 6 << 20    # of row blocks in flight, per buffer set


def _dot32(a, b, contract):
    """float32 operands at full precision (the default would round them
    to bfloat16 on the chip)."""
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _hyper_params():
    from jax.experimental.pallas import tpu as pltpu
    # a block of rows of X is megabytes wide; the scoped default of 16 MiB
    # holds two in flight and little else
    return pltpu.CompilerParams(dimension_semantics=('arbitrary',),
                                vmem_limit_bytes=64 << 20)


def _hyper_rows(rows, width, itemsize, wide_operands):
    """(pad, block) of the row axis: blocks of `wide_operands` arrays
    [block, width] stay under _HYPER_BLOCK_BYTES together."""
    want = _HYPER_BLOCK_BYTES // (width * itemsize * wide_operands)
    want = max(16, min(256, want - want % 16))
    return _pad_and_block(want, rows)


def _pad0(x, pad):
    return x if not pad else jnp.pad(x, ((0, pad), (0, 0)))


def _stream(ref, j, d):
    return ref[:, j * d:(j + 1) * d]


def _put_col(c, k, value):
    """c with column k replaced by value [blk, 1]."""
    col = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
    return jnp.where(col == k, value, c)


def _hyper_u(x_ref, w_ref, n, d):
    """(x W^T [blk, HYPER_COLS], sum of squares [blk, 1]) of a block."""
    u = jnp.zeros((x_ref.shape[0], HYPER_COLS), jnp.float32)
    ss = jnp.zeros((x_ref.shape[0], 1), jnp.float32)
    for j in range(n):
        xj = _stream(x_ref, j, d)
        u += _dot(xj, _stream(w_ref, j, d), ((1,), (1,)))
        x32 = xj.astype(jnp.float32)
        ss += jnp.sum(x32 * x32, axis=-1, keepdims=True)
    return u, ss


def _hyper_pre_fwd_kernel(x_ref, w_ref, a_ref, b_ref, y_ref, c_ref, *, n, d,
                          eps):
    u, ss = _hyper_u(x_ref, w_ref, n, d)
    r = jax.lax.rsqrt(ss / (n * d) + eps)
    c = u * r * a_ref[...] + b_ref[...]
    hp = jax.nn.sigmoid(c)
    y = hp[:, 0:1] * _stream(x_ref, 0, d).astype(jnp.float32)
    for j in range(1, n):
        y += hp[:, j:j + 1] * _stream(x_ref, j, d).astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    # the last column is free: it carries 1 / rms to the backward pass
    c_ref[...] = _put_col(c, HYPER_COLS - 1, r)


def _hyper_pre_bwd_kernel(x_ref, w_ref, a_ref, c_ref, dy_ref, dc_ref, gx_ref,
                          dx_ref, dcf_ref, dcm_ref, dw_ref, *, n, d):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    c = c_ref[...]
    r = c[:, HYPER_COLS - 1:HYPER_COLS]
    hp = jax.nn.sigmoid(c)
    dy = dy_ref[...].astype(jnp.float32)
    u, _ = _hyper_u(x_ref, w_ref, n, d)
    dc = _put_col(dc_ref[...], HYPER_COLS - 1, 0.0)
    for j in range(n):      # y = sum_j sigmoid(c_j) x_j
        hj = hp[:, j:j + 1]
        dh = jnp.sum(dy * _stream(x_ref, j, d).astype(jnp.float32), axis=-1,
                     keepdims=True)
        dc = _put_col(dc, j, dc[:, j:j + 1] + dh * hj * (1.0 - hj))
    dm = dc * a_ref[...]                        # c = a (u r) + b
    du = dm * r
    # r = (ss / nd + eps)^-1/2:  dr/dx = -r^3 x / nd
    s = jnp.sum(dm * u, axis=-1, keepdims=True) * (-(r * r * r) / (n * d))
    dcf_ref[...] = dc
    dcm_ref[...] = dc * (u * r)
    for j in range(n):
        x32 = _stream(x_ref, j, d).astype(jnp.float32)
        w32 = _stream(w_ref, j, d).astype(jnp.float32)
        dxj = (_dot32(du, w32, ((1,), (0,))) + s * x32
               + hp[:, j:j + 1] * dy
               + _stream(gx_ref, j, d).astype(jnp.float32))
        dx_ref[:, j * d:(j + 1) * d] = dxj.astype(dx_ref.dtype)
        dw_ref[:, j * d:(j + 1) * d] += _dot32(du, x32, ((0,), (0,)))


def _hyper_post_fwd_kernel(x_ref, z_ref, c_ref, o_ref, *, n, d):
    c = c_ref[...]
    z = z_ref[...].astype(jnp.float32)
    for i in range(n):
        acc = c[:, i:i + 1] * z
        for j in range(n):
            k = n + i * n + j
            acc += c[:, k:k + 1] * _stream(x_ref, j, d).astype(jnp.float32)
        o_ref[:, i * d:(i + 1) * d] = acc.astype(o_ref.dtype)


def _hyper_post_bwd_kernel(g_ref, x_ref, z_ref, c_ref, dx_ref, dz_ref, dc_ref,
                           *, n, d):
    c = c_ref[...]
    z = z_ref[...].astype(jnp.float32)
    dc = jnp.zeros(c.shape, jnp.float32)
    dz = jnp.zeros(z.shape, jnp.float32)
    for i in range(n):
        gi = _stream(g_ref, i, d).astype(jnp.float32)
        dz += c[:, i:i + 1] * gi
        dc = _put_col(dc, i, jnp.sum(z * gi, axis=-1, keepdims=True))
        for j in range(n):
            xj = _stream(x_ref, j, d).astype(jnp.float32)
            dc = _put_col(dc, n + i * n + j,
                          jnp.sum(gi * xj, axis=-1, keepdims=True))
    dz_ref[...] = dz.astype(dz_ref.dtype)
    dc_ref[...] = dc
    for j in range(n):
        acc = c[:, n + j:n + j + 1] * _stream(g_ref, 0, d).astype(
            jnp.float32)
        for i in range(1, n):
            k = n + i * n + j
            acc += c[:, k:k + 1] * _stream(g_ref, i, d).astype(jnp.float32)
        dx_ref[:, j * d:(j + 1) * d] = acc.astype(dx_ref.dtype)


def _hyper_call(kernel, name, rows, blk, ins, outs, **static):
    """One pass over the row blocks. `ins` / `outs`: (array or
    ShapeDtypeStruct, 'rows' for an operand cut in row blocks or 'whole'
    for one every block reads)."""
    def spec(a, how):
        if how == 'rows':
            return pl.BlockSpec((blk, a.shape[1]), lambda i: (i, 0))
        return pl.BlockSpec(a.shape, lambda i: (0, 0))

    return run_kernel(lambda interpret: pl.pallas_call(
        functools.partial(kernel, **static), grid=(rows // blk,),
        in_specs=[spec(a, how) for a, how in ins],
        out_specs=[spec(a, how) for a, how in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a, _ in outs],
        compiler_params=_hyper_params(), interpret=interpret, name=name),
        *[a for a, _ in ins])


def hyper_pre_forward(x, w, a_row, b_row, n, eps, name='hyper_pre'):
    """(y [R, d], c [R, HYPER_COLS] float32) of rows x [R, n * d]:
    ``c = a_row * (x w^T) / rms(x) + b_row`` (its last column holds
    1 / rms instead) and ``y = sum_j sigmoid(c[:, j]) x_j``; w
    [HYPER_COLS, n * d], a_row and b_row [1, HYPER_COLS] float32. The
    kernel is named ``<name>_fwd`` in a device trace."""
    R, nd = x.shape
    d = nd // n
    pad, blk = _hyper_rows(R, nd, x.dtype.itemsize, 2)
    f32 = jnp.float32
    y, c = _hyper_call(
        _hyper_pre_fwd_kernel, name + '_fwd', R + pad, blk,
        [(_pad0(x, pad), 'rows'), (w, 'whole'), (a_row, 'whole'),
         (b_row, 'whole')],
        [(jax.ShapeDtypeStruct((R + pad, d), x.dtype), 'rows'),
         (jax.ShapeDtypeStruct((R + pad, HYPER_COLS), f32), 'rows')],
        n=n, d=d, eps=eps)
    return y[:R], c[:R]


def hyper_pre_backward(x, w, a_row, c, dy, dc, gx, n, name='hyper_pre'):
    """(dx, dw float32, da_row, db_row) of :func:`hyper_pre_forward`, with
    gx, the cotangent that reaches x by other ways (the sublayer's own
    write), added into dx in the same pass. ``<name>_bwd``."""
    R, nd = x.shape
    d = nd // n
    pad, blk = _hyper_rows(R, nd, x.dtype.itemsize, 6)
    f32 = jnp.float32
    rows = lambda width, dt: (  # noqa: E731
        jax.ShapeDtypeStruct((R + pad, width), dt), 'rows')
    dx, dcf, dcm, dw = _hyper_call(
        _hyper_pre_bwd_kernel, name + '_bwd', R + pad, blk,
        [(_pad0(x, pad), 'rows'), (w, 'whole'), (a_row, 'whole'),
         (_pad0(c, pad), 'rows'), (_pad0(dy, pad), 'rows'),
         (_pad0(dc.astype(f32), pad), 'rows'), (_pad0(gx, pad), 'rows')],
        [rows(nd, x.dtype), rows(HYPER_COLS, f32), rows(HYPER_COLS, f32),
         (jax.ShapeDtypeStruct(w.shape, f32), 'whole')],
        n=n, d=d)
    return (dx[:R], dw, jnp.sum(dcm[:R], axis=0, keepdims=True),
            jnp.sum(dcf[:R], axis=0, keepdims=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def hyper_pre(x, w, a_row, b_row, n, eps):
    """(y, c, x) of :func:`hyper_pre_forward`: x is handed on untouched so
    that what reads it next (:func:`hyper_post`) sends its cotangent back
    through here, where the backward kernel adds it in its one pass."""
    y, c = hyper_pre_forward(x, w, a_row, b_row, n, eps)
    return y, c, x


def _hyper_pre_fwd(x, w, a_row, b_row, n, eps):
    y, c = hyper_pre_forward(x, w, a_row, b_row, n, eps)
    return (y, c, x), (x, w, a_row, c)


def _hyper_pre_bwd(n, eps, res, g):
    x, w, a_row, c = res
    dy, dc, gx = g
    dx, dw, da, db = hyper_pre_backward(x, w, a_row, c, dy, dc, gx, n)
    return dx, dw.astype(w.dtype), da, db


hyper_pre.defvjp(_hyper_pre_fwd, _hyper_pre_bwd)


def hyper_post_forward(x, z, coef, n, name='hyper_post'):
    """X' [R, n * d]: ``X'_i = coef[:, i] z + sum_j coef[:, n + i n + j]
    x_j``; x [R, n * d], z [R, d], coef [R, HYPER_COLS] float32.
    ``<name>_fwd``."""
    R, nd = x.shape
    pad, blk = _hyper_rows(R, nd, x.dtype.itemsize, 4)
    out, = _hyper_call(
        _hyper_post_fwd_kernel, name + '_fwd', R + pad, blk,
        [(_pad0(x, pad), 'rows'), (_pad0(z, pad), 'rows'),
         (_pad0(coef, pad), 'rows')],
        [(jax.ShapeDtypeStruct((R + pad, nd), x.dtype), 'rows')],
        n=n, d=nd // n)
    return out[:R]


def hyper_post_backward(g, x, z, coef, n, name='hyper_post'):
    """(dx, dz, dcoef) of :func:`hyper_post_forward`. ``<name>_bwd``."""
    R, nd = x.shape
    d = nd // n
    pad, blk = _hyper_rows(R, nd, x.dtype.itemsize, 6)
    dx, dz, dc = _hyper_call(
        _hyper_post_bwd_kernel, name + '_bwd', R + pad, blk,
        [(_pad0(g, pad), 'rows'), (_pad0(x, pad), 'rows'),
         (_pad0(z, pad), 'rows'), (_pad0(coef, pad), 'rows')],
        [(jax.ShapeDtypeStruct((R + pad, nd), x.dtype), 'rows'),
         (jax.ShapeDtypeStruct((R + pad, d), z.dtype), 'rows'),
         (jax.ShapeDtypeStruct((R + pad, HYPER_COLS), jnp.float32), 'rows')],
        n=n, d=d)
    return dx[:R], dz[:R], dc[:R]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def hyper_post(x, z, coef, n):
    return hyper_post_forward(x, z, coef, n)


def _hyper_post_fwd(x, z, coef, n):
    return hyper_post_forward(x, z, coef, n), (x, z, coef)


def _hyper_post_bwd(n, res, g):
    return hyper_post_backward(g, *res, n)


hyper_post.defvjp(_hyper_post_fwd, _hyper_post_bwd)


# ---------------------------------------------------------------------------
# Gated short convolution: y = C * conv(B * x), causal, depthwise, L taps
# ---------------------------------------------------------------------------
# The operand is a projection's output [B, T, 3 C], its thirds B, C and x in
# this order; the taps come as rows, [SHORT_CONV_ROWS, C] float32 (row j is
# tap j, which weighs u_{t - (L - 1 - j)}; rows from L on are 0). A grid
# step takes a block of rows at its whole width, so every array crosses
# once a pass; the L - 1 rows of u = B * x before the block (and, backward,
# the rows of dc = dy * C after it) come with one more block of 8 rows of
# the same arrays, 3% of a block of 256. Inside, the columns are walked in
# chunks, in float32, and written back in the operands' precision: no
# float32 [T, C] array exists. With their gates off (``gated=False``) the
# same two bodies run y = silu(conv(x)) over one array [B, T, C], a
# linear-attention layer's convolution (:func:`silu_conv`): the halo block
# of 8 rows covers its 3 rows back, and backward the next block's first
# rows of dc = dy silu'(c) are made from that block's rows and this one's
# last.

SHORT_CONV_ROWS = 8     # rows of the taps' array and of a halo block
_SHORT_CONV_BLOCK = 256


def _short_conv_chunk(C):
    return next((c for c in (512, 256, 128) if C % c == 0), C)


def _third_cols(third, c0, chunk, C):
    """Columns [c0, c0 + chunk) of a [.., 3 C] block's `third`."""
    return slice(third * C + c0, third * C + c0 + chunk)


def _third(ref, third, c0, chunk, C):
    return ref[0, :, _third_cols(third, c0, chunk, C)].astype(jnp.float32)


def _moved_down(before, u, k):
    """Row t holds u_{t-k}, the first k rows `before`'s last."""
    at = SHORT_CONV_ROWS - k
    return jnp.concatenate([before, u], axis=0)[at:at + u.shape[0]]


def _moved_up(u, after, k):
    """Row t holds u_{t+k}, the last k rows `after`'s first."""
    return jnp.concatenate([u, after], axis=0)[k:k + u.shape[0]]


def _taps_sum(w, before, u, L):
    """c_t = sum_j w[j] u_{t - (L - 1 - j)}, the rows before the block
    `before`'s last."""
    c = w[L - 1:L] * u
    for k in range(1, L):
        c += w[L - 1 - k:L - k] * _moved_down(before, u, k)
    return c


def _short_conv_fwd_kernel(x_ref, prev_ref, w_ref, o_ref, *, C, L, chunk,
                           gated=True):
    first = pl.program_id(1) == 0
    for c0 in range(0, C, chunk):
        part = lambda ref, third: _third(ref, third, c0, chunk, C)  # noqa
        if gated:
            u = part(x_ref, 0) * part(x_ref, 2)
            before = jnp.where(first, 0.0,
                               part(prev_ref, 0) * part(prev_ref, 2))
        else:               # y = silu(conv(x)): one array in, no gates
            u, before = part(x_ref, 0), jnp.where(first, 0.0,
                                                  part(prev_ref, 0))
        c = _taps_sum(w_ref[:, c0:c0 + chunk], before, u, L)
        y = part(x_ref, 1) * c if gated else jax.nn.silu(c)
        o_ref[0, :, c0:c0 + chunk] = y.astype(o_ref.dtype)


def _silu_slope(c):
    s = jax.nn.sigmoid(c)
    return s * (1.0 + c * (1.0 - s))


def _short_conv_bwd_kernel(x_ref, prev_ref, next_ref, g_ref, gnext_ref, w_ref,
                           dx_ref, dw_ref, *, C, L, chunk, gated=True):
    b, i = pl.program_id(0), pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1

    @pl.when((b == 0) & first)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    for c0 in range(0, C, chunk):
        part = lambda ref, third: _third(ref, third, c0, chunk, C)  # noqa
        cols = lambda third: _third_cols(third, c0, chunk, C)  # noqa: E731
        own = lambda ref: ref[0, :, c0:c0 + chunk].astype(  # noqa: E731
            jnp.float32)
        if gated:
            xb, xc, xx = part(x_ref, 0), part(x_ref, 1), part(x_ref, 2)
            u = xb * xx
            before = jnp.where(first, 0.0,
                               part(prev_ref, 0) * part(prev_ref, 2))
            dy = own(g_ref)
            dc = dy * xc
            after = jnp.where(last, 0.0, own(gnext_ref) * part(next_ref, 1))
            w = w_ref[:, c0:c0 + chunk]
        else:
            # y = silu(conv(x)): dc = dy silu'(c) with c made again, for
            # the block and for the next block's first rows (their c reads
            # this block's last)
            w = w_ref[:, c0:c0 + chunk]
            u = own(x_ref)
            before = jnp.where(first, 0.0, own(prev_ref))
            dc = own(g_ref) * _silu_slope(_taps_sum(w, before, u, L))
            after = jnp.where(last, 0.0, own(gnext_ref) * _silu_slope(
                _taps_sum(w, u[-SHORT_CONV_ROWS:], own(next_ref), L)))
        c = w[L - 1:L] * u if gated else None
        du = w[L - 1:L] * dc
        dw_ref[L - 1:L, c0:c0 + chunk] += jnp.sum(dc * u, axis=0,
                                                  keepdims=True)
        for k in range(1, L):
            u_k = _moved_down(before, u, k)
            if gated:
                c += w[L - 1 - k:L - k] * u_k
            du += w[L - 1 - k:L - k] * _moved_up(dc, after, k)
            dw_ref[L - 1 - k:L - k, c0:c0 + chunk] += jnp.sum(
                dc * u_k, axis=0, keepdims=True)
        if gated:
            dx_ref[0, :, cols(0)] = (du * xx).astype(dx_ref.dtype)
            dx_ref[0, :, cols(1)] = (dy * c).astype(dx_ref.dtype)
            dx_ref[0, :, cols(2)] = (du * xb).astype(dx_ref.dtype)
        else:
            dx_ref[0, :, c0:c0 + chunk] = du.astype(dx_ref.dtype)


def _short_conv_rows(T):
    """(block, pad) of the row axis: whole blocks of SHORT_CONV_ROWS's
    multiple; rows added at the end are zeros that nothing reads back."""
    blk = min(_SHORT_CONV_BLOCK, -(-T // SHORT_CONV_ROWS) * SHORT_CONV_ROWS)
    return blk, -T % blk


def _short_conv_setup(bcx, w, name, gated=True):
    B, T, C3 = bcx.shape
    C, L = w.shape
    if C3 != (3 * C if gated else C) or not 1 <= L <= SHORT_CONV_ROWS:
        raise ValueError('%s: operand %s against taps %s'
                         % (name, tuple(bcx.shape), tuple(w.shape)))
    blk, pad = _short_conv_rows(T)
    halo = blk // SHORT_CONV_ROWS           # halo blocks a row block
    n_halo = (T + pad) // SHORT_CONV_ROWS

    def rows(width):
        return pl.BlockSpec((1, blk, width), lambda b, i: (b, i, 0))

    def before(width):
        return pl.BlockSpec((1, SHORT_CONV_ROWS, width), lambda b, i: (
            b, jnp.maximum(i * halo - 1, 0), 0))

    def after(width):
        return pl.BlockSpec((1, SHORT_CONV_ROWS, width), lambda b, i: (
            b, jnp.minimum((i + 1) * halo, n_halo - 1), 0))

    taps = jnp.pad(w.astype(jnp.float32).T, ((0, SHORT_CONV_ROWS - L), (0, 0)))
    whole = pl.BlockSpec((SHORT_CONV_ROWS, C), lambda b, i: (0, 0))
    # a lane offset of C or 2 C into a block has to be a whole tile's
    too_big = None if C % 128 == 0 else (
        '%s: %d channels (operand %s %s) are no multiple of 128 lanes'
        % (name, C, tuple(bcx.shape), bcx.dtype.name))
    return types.SimpleNamespace(
        x=_pad_rows(bcx, pad), taps=taps, grid=(B, (T + pad) // blk),
        rows=rows, before=before, after=after, whole=whole,
        too_big=too_big)


def _short_conv_params():
    from jax.experimental.pallas import tpu as pltpu
    # blocks of [256, 3 C] in flight twice over, in and out
    return pltpu.CompilerParams(
        dimension_semantics=('arbitrary', 'arbitrary'),
        vmem_limit_bytes=64 << 20)


def short_conv_forward(bcx, w, name='short_conv', gated=True):
    """y [B, T, C] = C * (sum_j w[:, j] u_{t - (L - 1 - j)}) with u = B * x
    (zero before the sequence's start) for bcx [B, T, 3 C] = [B | C | x] and
    taps w [C, L]; with `gated` false, y = silu(sum_j w[:, j] x_{t - (L - 1
    - j)}) for bcx = x [B, T, C]. The kernel is named ``<name>_fwd`` in a
    device trace."""
    B, T, width = bcx.shape
    C, L = w.shape
    cut = _short_conv_setup(bcx, w, name, gated)
    static = {} if gated else {'gated': False}
    out = run_kernel(lambda interpret: pl.pallas_call(
        functools.partial(_short_conv_fwd_kernel, C=C, L=L,
                          chunk=_short_conv_chunk(C), **static),
        grid=cut.grid,
        in_specs=[cut.rows(width), cut.before(width), cut.whole],
        out_specs=cut.rows(C),
        out_shape=jax.ShapeDtypeStruct((B, cut.x.shape[1], C), bcx.dtype),
        compiler_params=_short_conv_params(), interpret=interpret,
        name=name + '_fwd'), cut.x, cut.x, cut.taps, too_big=cut.too_big)
    return out[:, :T]


def short_conv_backward(bcx, w, g, name='short_conv', gated=True):
    """(d_bcx, as bcx, and dw [C, L] float32) of :func:`short_conv_forward`
    from the output's cotangent g [B, T, C]. ``<name>_bwd``."""
    B, T, width = bcx.shape
    C, L = w.shape
    cut = _short_conv_setup(bcx, w, name, gated)
    static = {} if gated else {'gated': False}
    g = _pad_rows(g, cut.x.shape[1] - T)
    dx, dw = run_kernel(lambda interpret: pl.pallas_call(
        functools.partial(_short_conv_bwd_kernel, C=C, L=L,
                          chunk=_short_conv_chunk(C), **static),
        grid=cut.grid,
        in_specs=[cut.rows(width), cut.before(width), cut.after(width),
                  cut.rows(C), cut.after(C), cut.whole],
        out_specs=[cut.rows(width), cut.whole],
        out_shape=[jax.ShapeDtypeStruct(cut.x.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((SHORT_CONV_ROWS, C), jnp.float32)],
        compiler_params=_short_conv_params(), interpret=interpret,
        name=name + '_bwd'), cut.x, cut.x, cut.x, g, g, cut.taps,
        too_big=cut.too_big)
    return dx[:, :T], dw[:L].T


@jax.custom_vjp
def short_conv(bcx, w):
    """The gated short convolution by the two kernels above."""
    return short_conv_forward(bcx, w)


def _short_conv_fwd(bcx, w):
    return short_conv_forward(bcx, w), (bcx, w)


def _short_conv_bwd(res, g):
    bcx, w = res
    dx, dw = short_conv_backward(bcx, w, g)
    return dx, dw.astype(w.dtype)


short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


@jax.custom_vjp
def silu_conv(x, w):
    """silu of the causal depthwise convolution of x [B, T, C] with taps w
    [C, L]: the two kernels above with their gates off."""
    return short_conv_forward(x, w, gated=False)


def _silu_conv_fwd(x, w):
    return silu_conv(x, w), (x, w)


def _silu_conv_bwd(res, g):
    x, w = res
    dx, dw = short_conv_backward(x, w, g, gated=False)
    return dx, dw.astype(w.dtype)


silu_conv.defvjp(_silu_conv_fwd, _silu_conv_bwd)


# ---------------------------------------------------------------------------
# Gated delta rule: a scan whose grid carries state along the sequence
# ---------------------------------------------------------------------------
# Per head, with S in R^{dk x dv} and S_0 = 0:
#   S_t = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S_t^T k_t);
#   S_t = S_t + k_t u_t^T;   o_t = S_t^T q_t.
# In chunks of C rows (gamma: the running sum of g inside a chunk, Gamma_ij
# = exp(gamma_i - gamma_j) for i >= j) this is, exactly,
#   A = strict_lower(diag(beta) (K K^T * Gamma));  T = (I + A)^-1 diag(beta)
#   W = T (K * exp(gamma));  U = T V;  U' = U - W S_0
#   O = (Q * exp(gamma)) S_0 + lower((Q K^T) * Gamma) U'
#   S_C = exp(gamma_C) S_0 + (K * exp(gamma_C - gamma))^T U'.
# What does not depend on S_0 is the same work for every chunk, and three
# kernels whose grid is parallel over chunks do it, a chunk of up to ten
# heads in VMEM a grid step: ``delta_rule_solve`` makes A and the inverse
# X = (I + A)^-1 (Gamma from the difference of gamma, every exp and the
# solve's arithmetic in float32), ``delta_rule_chunk_fwd`` makes from X
# the chain's six operands (the products take the operands' dtype), and
# ``delta_rule_chunk_bwd`` takes their cotangents back to q, k, v, gamma
# and beta, the inverse's own (-X^T dX X^T) among them. Only gamma, the
# running sum of g, is XLA's. X is named dear: a mirrored stage solves
# once a step and makes W, U and P again from the X it kept. What does
# depend on S_0 is the chain of chunks:
# the two kernels walk it, forward and from the end, with the state (or its
# cotangent) of `heads` heads in VMEM across the grid steps of one
# sequence. Their operands are by head, [B, H, T, D], because neither 96
# nor 192 columns are a multiple of the 128 lanes. The forward kernel
# writes the state at each chunk's start, which is what the backward
# kernel needs of it.

DELTA_CHUNK = 64
# heads walked by one grid step: their chains are independent, so the
# scheduler has one's products to issue while another's drain
_DELTA_HEADS = 2


def _delta_dot(dtype):
    return _dot32 if dtype == jnp.float32 else _dot


def _delta_fwd_kernel(qg_ref, kd_ref, w_ref, u_ref, p_ref, decay_ref, o_ref,
                      s0_ref, smax_ref, s_s, *, heads):
    c = pl.program_id(2)
    dot = _delta_dot(w_ref.dtype)

    @pl.when(c == 0)
    def _():
        s_s[...] = jnp.zeros(s_s.shape, jnp.float32)

    for j in range(heads):
        S = s_s[j]
        s0_ref[j] = S
        Sd = S.astype(w_ref.dtype)
        u1 = u_ref[j].astype(jnp.float32) - dot(w_ref[j], Sd, ((1,), (0,)))
        u1d = u1.astype(u_ref.dtype)
        o_ref[j] = (dot(qg_ref[j], Sd, ((1,), (0,)))
                    + dot(p_ref[j], u1d, ((1,), (0,)))).astype(o_ref.dtype)
        s_s[j] = decay_ref[j] * S + dot(kd_ref[j], u1d, ((0,), (0,)))

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        for j in range(heads):
            smax_ref[j] = jnp.max(jnp.abs(s_s[j]), axis=0, keepdims=True)


def _delta_bwd_kernel(qg_ref, kd_ref, w_ref, u_ref, p_ref, decay_ref, s0_ref,
                      do_ref, dqg_ref, dkd_ref, dw_ref, du_ref, dp_ref,
                      ddecay_ref, ds_s, *, heads):
    dtype = w_ref.dtype
    dot = _delta_dot(dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_s[...] = jnp.zeros(ds_s.shape, jnp.float32)

    for j in range(heads):
        S, dS1 = s0_ref[j], ds_s[j]      # state before, cotangent after
        Sd, dS1d, do = S.astype(dtype), dS1.astype(dtype), do_ref[j]
        u1d = (u_ref[j].astype(jnp.float32)
               - dot(w_ref[j], Sd, ((1,), (0,)))).astype(dtype)
        du1 = dot(p_ref[j], do, ((0,), (0,))) \
            + dot(kd_ref[j], dS1d, ((1,), (0,)))
        du1d = du1.astype(dtype)
        dp_ref[j] = dot(do, u1d, ((1,), (1,))).astype(dp_ref.dtype)
        dqg_ref[j] = dot(do, Sd, ((1,), (1,))).astype(dqg_ref.dtype)
        dkd_ref[j] = dot(u1d, dS1d, ((1,), (1,))).astype(dkd_ref.dtype)
        dw_ref[j] = (-dot(du1d, Sd, ((1,), (1,)))).astype(dw_ref.dtype)
        du_ref[j] = du1d
        ddecay_ref[j] = jnp.sum(S * dS1, axis=0, keepdims=True)
        ds_s[j] = dot(qg_ref[j], do, ((0,), (0,))) + decay_ref[j] * dS1 \
            - dot(w_ref[j], du1d, ((0,), (0,)))


def _delta_specs(C, heads, at):
    """Block specs of the delta rule's arrays for `heads` heads a grid
    step, the chunk taken being ``at(c)``: (C rows of width D of
    [B, H, T, D], a chunk's own [r, c] of [B, H, n, r, c])."""
    def rows(D):
        return pl.BlockSpec((None, heads, C, D),
                            lambda b, h, c: (b, h, at(c), 0))

    def tile(r, c):
        return pl.BlockSpec((None, heads, None, r, c),
                            lambda b, h, c_: (b, h, at(c_), 0, 0))
    return rows, tile


def _delta_heads(H, most=_DELTA_HEADS):
    return next(h for h in range(most, 0, -1) if H % h == 0)


def delta_scan_forward(qg, kd, w, u, p, decay, name='delta_rule'):
    """(O [B, H, T, dv], the state at each chunk's start [B, H, n, dk, dv]
    float32, the last state's largest magnitudes by column [B, H, 1, dv])
    of the chain ``U' = U - W S; O = Qg S + P U'; S = decay S + Kd^T U'``
    over the n chunks of T rows, S = 0 before the first: qg, kd, w
    [B, H, T, dk], u [B, H, T, dv], p [B, H, T, C] (a chunk's rows against
    its own), decay [B, H, n, 1, dv] float32. ``<name>_fwd``."""
    B, H, T, dk = qg.shape
    dv, C = u.shape[3], p.shape[3]
    n, heads = T // C, _delta_heads(H)
    rows, tile = _delta_specs(C, heads, lambda c: c)
    row, state = tile(1, dv), tile(dk, dv)
    last = pl.BlockSpec((None, heads, 1, dv), lambda b, h, c: (b, h, 0, 0))
    return run_kernel(lambda interpret: pl.pallas_call(
        functools.partial(_delta_fwd_kernel, heads=heads),
        grid=(B, H // heads, n),
        in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(C), row],
        out_specs=[rows(dv), state, last],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), u.dtype),
                   jax.ShapeDtypeStruct((B, H, n, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1, dv), jnp.float32)],
        scratch_shapes=[_vmem((heads, dk, dv))],
        compiler_params=_attn_params(2), interpret=interpret,
        name=name + '_fwd'), qg, kd, w, u, p, decay)


def delta_scan_backward(qg, kd, w, u, p, decay, states, do,
                        name='delta_rule'):
    """The cotangents of :func:`delta_scan_forward`'s six operands from
    O's, the chunks walked from the end with the state's cotangent
    carried; `states` as the forward kernel wrote them. ``<name>_bwd``."""
    B, H, T, dk = qg.shape
    dv, C = u.shape[3], p.shape[3]
    n, heads = T // C, _delta_heads(H)
    rows, tile = _delta_specs(C, heads, lambda c: n - 1 - c)
    row, state = tile(1, dv), tile(dk, dv)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)     # noqa: E731
    return run_kernel(lambda interpret: pl.pallas_call(
        functools.partial(_delta_bwd_kernel, heads=heads),
        grid=(B, H // heads, n),
        in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(C), row,
                  state, rows(dv)],
        out_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(C), row],
        out_shape=[like(qg), like(kd), like(w), like(u), like(p),
                   like(decay)],
        scratch_shapes=[_vmem((heads, dk, dv))],
        compiler_params=_attn_params(2), interpret=interpret,
        name=name + '_bwd'), qg, kd, w, u, p, decay, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def delta_scan(qg, kd, w, u, p, decay, name='delta_rule'):
    """(O, the last state's largest magnitudes by column) by the two
    kernels above; the second takes no cotangent."""
    o, _, smax = delta_scan_forward(qg, kd, w, u, p, decay, name)
    return o, smax


def _delta_scan_fwd(qg, kd, w, u, p, decay, name):
    o, states, smax = delta_scan_forward(qg, kd, w, u, p, decay, name)
    # the chain is the one part of the op that cannot be made again in
    # parallel: a mirrored stage keeps what it gave, so it runs once
    o, states = dear(o, name + '_out'), dear(states, name + '_states')
    return (o, smax), (qg, kd, w, u, p, decay, states)


def _delta_scan_bwd(name, res, g):
    return delta_scan_backward(*res, g[0], name=name)


delta_scan.defvjp(_delta_scan_fwd, _delta_scan_bwd)


# rows of the diagonal blocks that the solve clears by forward substitution
# on the VPU; from there up it merges blocks by pairs on the MXU, two
# six-pass products a level that wait for one another (a layer's solves at
# olmo_hybrid_fit_4k: 2.03 ms from 8 rows, 1.68 from 16, 1.42 from 32)
_DELTA_SOLVE_ROWS = 32
# heads a grid step of the kernels that carry nothing from chunk to chunk
# (at most: a divisor of H), where the chain takes _DELTA_HEADS: their
# steps cost as much to start as to run (chunk_fwd 0.79 ms a layer at 2
# heads a step, 0.53 at 6, 0.51 at 10)
_DELTA_CHUNK_HEADS = 10
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _grid_of(C):
    """Row and column numbers of a [C, C] block."""
    return (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0),
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _as_col(row, eye):
    """A [1, C] row as a [C, 1] column."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _as_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _decay_between(seen, col, gamma):
    """Gamma: exp(gamma_i - gamma_j) where `seen` and 0 elsewhere, from the
    difference (exp(gamma_i) exp(-gamma_j) would be inf times 0 at a fast
    decay); `col` is the row `gamma` as a column."""
    return jnp.exp(jnp.where(seen, col - gamma, -jnp.inf))


def _unit_lower_inverse(a, i, j):
    """(I + a)^-1 of a [C, C] strictly lower triangular float32 block, C a
    power of two, i and j its row and column numbers. The diagonal blocks
    of _DELTA_SOLVE_ROWS rows by forward substitution, all at once and a
    column a step (row s of a block is final when its column s is
    cleared; a strictly lower `a` leaves the rows above it alone); then
    the blocks of twice the rows from the two of half their size
    (``[[X1, 0], [-X2 A21 X1, X2]]``), which is forward substitution by
    blocks and as stable."""
    C = a.shape[0]
    rows = min(_DELTA_SOLVE_ROWS, C)
    x = [(i == j).astype(jnp.float32)[r:r + 8] for r in range(0, C, 8)]
    for s in range(rows - 1):
        for b in range(0, C, rows):
            at = b + s
            row = x[at // 8][at % 8:at % 8 + 1]
            for t in range((at + 1) // 8, (b + rows) // 8):
                x[t] = x[t] - a[8 * t:8 * t + 8, at:at + 1] * row
    x = jnp.concatenate(x, axis=0)
    while rows < C:
        lower_left = (((i ^ j) < 2 * rows) & ((i & rows) != 0)
                      & ((j & rows) == 0))
        x = x - _dot32(_dot32(x, jnp.where(lower_left, a, 0.0), _NN), x, _NN)
        rows *= 2
    return x


def _each_head(heads, body):
    """``body(h)`` for each of a grid step's heads: a loop over pairs, so
    that the scheduler has one head's products to issue while the other's
    drain and the body is written out twice, not `heads` times (ten heads
    unrolled took 20 s more to trace and lower than the two they are)."""
    pair = 2 - heads % 2

    def pairs(at, _):
        for h in range(pair):
            body(pair * at + h)

    jax.lax.fori_loop(0, heads // pair, pairs, None)


def _delta_solve_kernel(k_ref, gamma_ref, beta_ref, x_ref, *, heads, dtype):
    i, j = _grid_of(x_ref.shape[-1])

    def head(h):
        k = k_ref[h].astype(dtype)
        gamma = gamma_ref[h]
        a = _as_col(beta_ref[h], i == j) * _delta_dot(dtype)(k, k, _NT) \
            * _decay_between(i > j, _as_col(gamma, i == j), gamma)
        x_ref[h] = _unit_lower_inverse(a, i, j)

    _each_head(heads, head)


def _delta_chunk_fwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, x_ref,
                            qg_ref, kd_ref, w_ref, u_ref, p_ref, decay_ref,
                            *, heads):
    C, dtype = x_ref.shape[-1], v_ref.dtype
    dot = _delta_dot(dtype)
    i, j = _grid_of(C)

    def head(h):
        q, k, gamma = q_ref[h], k_ref[h], gamma_ref[h]
        col, last = _as_col(gamma, i == j), gamma[:, C - 1:]
        e = jnp.exp(col)
        t = (x_ref[h] * beta_ref[h]).astype(dtype)
        w_ref[h] = dot(t, (k * e).astype(dtype), _NN).astype(dtype)
        u_ref[h] = dot(t, v_ref[h], _NN).astype(dtype)
        p_ref[h] = (dot(q.astype(dtype), k.astype(dtype), _NT)
                    * _decay_between(i >= j, col, gamma)).astype(dtype)
        qg_ref[h] = (q * e).astype(dtype)
        kd_ref[h] = (k * jnp.exp(last - col)).astype(dtype)
        decay_ref[h] = jnp.broadcast_to(jnp.exp(last), decay_ref.shape[1:])

    _each_head(heads, head)


def _delta_chunk_bwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, x_ref,
                            dqg_ref, dkd_ref, dw_ref, du_ref, dp_ref,
                            ddecay_ref, dq_ref, dk_ref, dv_ref, dgamma_ref,
                            dbeta_ref, *, heads):
    C, dtype = x_ref.shape[-1], v_ref.dtype
    dot = _delta_dot(dtype)
    i, j = _grid_of(C)
    eye = i == j
    at_last = j[:1] == C - 1

    def head(h):
        q, k, gamma, beta, x = (q_ref[h], k_ref[h], gamma_ref[h],
                                beta_ref[h], x_ref[h])
        col, last = _as_col(gamma, eye), gamma[:, C - 1:]
        e, f = jnp.exp(col), jnp.exp(last - col)
        qc, kc, t = q.astype(dtype), k.astype(dtype), \
            (x * beta).astype(dtype)
        below = _decay_between(i > j, col, gamma)
        kk, qk = dot(kc, kc, _NT), dot(qc, kc, _NT)
        dw, du = dw_ref[h], du_ref[h]
        dqg, dkd, dp = (r[h].astype(jnp.float32)
                        for r in (dqg_ref, dkd_ref, dp_ref))
        # P = Q K^T * Gamma (1 on the diagonal); W = T (K * e); U = T V
        dqk = dp * jnp.where(eye, 1.0, below)
        dke = dot(t, dw, _TN)
        dv_ref[h] = dot(t, du, _TN).astype(dv_ref.dtype)
        dt = dot(dw, (k * e).astype(dtype), _NT) + dot(du, v_ref[h], _NT)
        # T = X diag(beta), and the inverse's own cotangent, -X^T dX X^T,
        # of which A takes what lies below the diagonal
        da = -_dot32(_dot32(x, dt * beta, _TN), x, _NT) * below
        dkk = da * _as_col(beta, eye)
        dqk_d, dkk_d = dqk.astype(dtype), dkk.astype(dtype)
        dq_ref[h] = dqg * e + dot(dqk_d, kc, _NN)
        dk_ref[h] = dke * e + dkd * f + dot(dqk_d, qc, _TN) \
            + dot(dkk_d, kc, _NN) + dot(dkk_d, kc, _TN)
        dbeta_ref[h] = jnp.sum(dt * x, axis=0, keepdims=True) \
            + _as_row(jnp.sum(da * kk, axis=1, keepdims=True), eye)
        # gamma: in e, in the decay to the chunk's end, and row less column
        # in both Gammas (A's and P's cotangents times A and P themselves)
        to_end = jnp.sum(dkd * k, axis=1, keepdims=True) * f
        d_diff = dkk * kk + dqk * qk
        d_col = jnp.sum(dqg * q + dke * k, axis=1, keepdims=True) * e \
            - to_end + jnp.sum(d_diff, axis=1, keepdims=True)
        d_last = jnp.sum(to_end, axis=0, keepdims=True) + jnp.exp(last) \
            * jnp.sum(ddecay_ref[h], axis=1, keepdims=True)
        dgamma_ref[h] = _as_row(d_col, eye) + jnp.where(at_last, d_last, 0.0) \
            - jnp.sum(d_diff, axis=0, keepdims=True)

    _each_head(heads, head)


def _delta_chunk_call(kernel, arrays, outs, name):
    """The results (a tuple) of `kernel` over the grid of chunks, `heads`
    heads a step: each array and each of `outs` (ShapeDtypeStruct) is by
    row, [B, H, T, D], or by chunk, [B, H, n, r, c]."""
    B, H, n = next(x.shape[:3] for x in arrays if x.ndim == 5)
    C = arrays[0].shape[2] // n
    heads = _delta_heads(H, _DELTA_CHUNK_HEADS)
    rows, tile = _delta_specs(C, heads, lambda c: c)
    specs = lambda xs: [rows(x.shape[3]) if x.ndim == 4         # noqa: E731
                        else tile(*x.shape[3:]) for x in xs]
    return tuple(run_kernel(lambda interpret: pl.pallas_call(
        functools.partial(kernel, heads=heads), grid=(B, H // heads, n),
        in_specs=specs(arrays), out_specs=specs(outs), out_shape=outs,
        compiler_params=_attn_params(3, 0), interpret=interpret, name=name),
        *arrays))


def delta_solve(k, gamma, beta, dtype, name='delta_rule'):
    """X = (I + A)^-1 [B, H, n, C, C] float32 of every chunk, A =
    strict_lower(diag(beta) (K K^T * Gamma)): k [B, H, T, dk] float32,
    gamma (the running sum of g inside a chunk) and beta [B, H, n, 1, C];
    K K^T takes operands of `dtype`. ``<name>_solve``."""
    B, H, n, _, C = gamma.shape
    return _delta_chunk_call(
        functools.partial(_delta_solve_kernel, dtype=dtype),
        (k, gamma, beta),
        [jax.ShapeDtypeStruct((B, H, n, C, C), jnp.float32)],
        name + '_solve')[0]


def delta_chunk_forward(q, k, v, gamma, beta, x, name='delta_rule'):
    """:func:`delta_scan`'s six operands from the op's, by head (q and k
    [B, H, T, dk] float32 as the scan takes them, normalised, q scaled; v
    [B, H, T, dv]), and the chunks' inverses. ``<name>_chunk_fwd``."""
    B, H, n, _, C = gamma.shape
    like = lambda x: jax.ShapeDtypeStruct(x.shape, v.dtype)     # noqa: E731
    return _delta_chunk_call(
        _delta_chunk_fwd_kernel, (q, k, v, gamma, beta, x),
        [like(q), like(k), like(k), like(v),
         jax.ShapeDtypeStruct(q.shape[:3] + (C,), v.dtype),
         jax.ShapeDtypeStruct((B, H, n, 1, v.shape[3]), jnp.float32)],
        name + '_chunk_fwd')


def delta_chunk_backward(q, k, v, gamma, beta, x, dqg, dkd, dw, du, dp,
                         ddecay, name='delta_rule'):
    """The cotangents of q, k, v, gamma and beta from those of
    :func:`delta_chunk_forward`'s six results, through the inverse too.
    ``<name>_chunk_bwd``."""
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)     # noqa: E731
    return _delta_chunk_call(
        _delta_chunk_bwd_kernel,
        (q, k, v, gamma, beta, x, dqg, dkd, dw, du, dp, ddecay),
        [like(q), like(k), like(v), like(gamma), like(beta)],
        name + '_chunk_bwd')


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def delta_chunks(q, k, v, gamma, beta, name='delta_rule'):
    """What a chunk needs that does not depend on the carried state: the
    solve, then :func:`delta_chunk_forward`."""
    x = delta_solve(k, gamma, beta, v.dtype, name)
    return delta_chunk_forward(q, k, v, gamma, beta, x, name)


def _delta_chunks_fwd(q, k, v, gamma, beta, name):
    # 4 C^2 bytes a chunk behind the one sequential part of a chunk's
    # work: a mirrored stage keeps the inverse, and its second forward is
    # the products alone
    x = dear(delta_solve(k, gamma, beta, v.dtype, name), name + '_inverse')
    return delta_chunk_forward(q, k, v, gamma, beta, x, name), \
        (q, k, v, gamma, beta, x)


def _delta_chunks_bwd(name, res, g):
    return delta_chunk_backward(*res, *g, name=name)


delta_chunks.defvjp(_delta_chunks_fwd, _delta_chunks_bwd)


def delta_rule(q, k, v, g, beta, chunk=DELTA_CHUNK, name='delta_rule'):
    """(o [B, H, T, dv] in v's dtype, the largest magnitude of a state
    after the last row [B] float32) of the gated delta rule for q and k
    [B, H, T, dk] float32 (normalised, q scaled), v [B, H, T, dv], g (the
    log of the decay, <= 0) and beta [B, H, T] float32. A sequence that
    is no whole number of chunks is padded with g = 0, beta = 0, k = 0,
    which leaves the state as it is."""
    T = q.shape[2]
    pad = -T % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for x in (q, k, v))
    with jax.named_scope('chunks'):
        operands = delta_chunks(
            q, k, v, jnp.cumsum(_rows(g, pad, chunk), axis=-1),
            _rows(beta, pad, chunk), name)
    o, smax = delta_scan(*operands, name)
    return o[:, :, :T], jnp.max(smax, axis=(1, 2, 3))
