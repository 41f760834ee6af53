"""Neural-network layer ops.

Reference: src/operator/{fully_connected,convolution,deconvolution,
batch_norm,pooling,activation,leaky_relu,dropout,lrn,l2_normalization,
instance_norm,softmax_output,make_loss,regression_output,sequence_*}-inl.h
and src/operator/nn/softmax.cc.

TPU-first notes: Convolution/FullyConnected lower to lax.conv_general_dilated
/ dot_general so XLA tiles them on the MXU; layouts stay NCHW at the API (the
reference's convention) and XLA's layout assignment re-tiles internally.
BatchNorm follows the aux-state protocol: it RETURNS updated moving stats as
extra outputs and the invoke layer writes them back (op_attr_types.h
FMutateInputs analog).
"""
import functools

import numpy as _np

import jax
import jax.numpy as jnp

from .registry import dear, register, register_alias


# ---------------------------------------------------------------------------
# FullyConnected — reference fully_connected-inl.h:29-52 (linalg_gemm)
# ---------------------------------------------------------------------------
@register('FullyConnected', input_names=['data', 'weight', 'bias'],
          param_defaults={'num_hidden': 0, 'no_bias': False, 'flatten': True})
def _fully_connected(attrs, data, weight, bias=None):
    if attrs.get('flatten', True):
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    y = jax.lax.dot_general(x, weight, (((x.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y.astype(x.dtype)
    if bias is not None and not attrs.get('no_bias', False):
        y = y + bias
    if weight.shape[0] <= weight.shape[1]:
        # a contraction: no larger than what it was made from, behind a
        # product at least as deep as it is wide, so a mirrored stage
        # keeps it (an attention's output, key, value or latent
        # projection; not a query's, an MLP's or a head's)
        y = dear(y, 'fully_connected_out')
    return y


def _fc_arg_names(attrs):
    if attrs and attrs.get('no_bias', False):
        return ['data', 'weight']
    return ['data', 'weight', 'bias']


# ---------------------------------------------------------------------------
# Convolution — reference convolution-inl.h (im2col+gemm) / cudnn. Here:
# one lax.conv_general_dilated call == the whole MXU-tiled conv.
# ---------------------------------------------------------------------------
def _pair(v, n=2):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    return v if len(v) == n else v * n


@register('Convolution', input_names=['data', 'weight', 'bias'],
          param_defaults={'kernel': (), 'stride': (), 'dilate': (), 'pad': (),
                          'num_filter': 0, 'num_group': 1, 'no_bias': False,
                          'workspace': 1024, 'cudnn_tune': None,
                          'cudnn_off': False, 'layout': None})
def _convolution(attrs, data, weight, bias=None):
    kernel = tuple(attrs['kernel'])
    nd = len(kernel)
    stride = tuple(attrs.get('stride') or (1,) * nd)
    dilate = tuple(attrs.get('dilate') or (1,) * nd)
    pad = tuple(attrs.get('pad') or (0,) * nd)
    groups = int(attrs.get('num_group', 1))

    if nd == 1:  # lift 1D conv to 2D (reference does the same via mshadow)
        data2 = data[:, :, None, :]
        w2 = weight[:, :, None, :]
        out = _conv_nd(data2, w2, (1,) + stride, (1,) + dilate, (0,) + pad, groups)
        out = out[:, :, 0, :]
    else:
        out = _conv_nd(data, weight, stride, dilate, pad, groups)
    if bias is not None and not attrs.get('no_bias', False):
        out = out + bias.reshape((1, -1) + (1,) * (out.ndim - 2))
    return out


def _channels_last_conv(data, weight, w_layout, **conv_kwargs):
    """Run a conv with channels-last compute behind the NCHW API (the
    reference's convention). Measured 1.3x faster fwd+bwd than
    logical-NCHW dimension_numbers on v5e: XLA's layout assignment
    handles the NHWC gradient convs far better, and the boundary
    transposes are pushed/cancelled between adjacent convs
    (elementwise/broadcast ops commute with them).

    ``w_layout`` is the weight's leading-dims order, 'OI' (Convolution)
    or 'IO' (Deconvolution). No preferred_element_type anywhere: jax's
    conv transpose rule can't mix an f32 cotangent with bf16 operands,
    and XLA:TPU accumulates bf16 convs in f32 on the MXU regardless."""
    nd = data.ndim - 2
    # NCHW -> NHWC / NCDHW -> NDHWC
    to_last = (0,) + tuple(range(2, nd + 2)) + (1,)
    to_first = (0, nd + 1) + tuple(range(1, nd + 1))
    io = (1, 0) if w_layout == 'OI' else (0, 1)      # -> <sp>IO
    w_last = tuple(range(2, nd + 2)) + io
    dn = ('NHWC', 'HWIO', 'NHWC') if nd == 2 else ('NDHWC', 'DHWIO', 'NDHWC')
    out = jax.lax.conv_general_dilated(
        jnp.transpose(data, to_last), jnp.transpose(weight, w_last),
        dimension_numbers=dn, **conv_kwargs).astype(data.dtype)
    return jnp.transpose(out, to_first)


def _bn_onepass():
    from ..config import flags as _flags
    _flags.reload('MXTPU_BN_ONEPASS')  # read at trace time only; the
    # parity tests flip it between fresh program builds in one process
    return _flags.get('MXTPU_BN_ONEPASS')


def _conv_nd(data, weight, stride, dilate, pad, groups):
    from ..config import flags as _flags
    if (_flags.get('MXTPU_CONV_STEM_S2D') and groups == 1
            and data.ndim == 4 and data.shape[1] <= 4
            and min(stride) > 1 and dilate == (1,) * len(dilate)):
        return _conv2d_stem_s2d(data, weight, stride, pad)
    if (_flags.get('MXTPU_CONV_BWD_PATCHES') and groups == 1
            and data.ndim == 4):
        return _conv2d_patches_bwd(data, weight, stride, dilate, pad)
    return _channels_last_conv(
        data, weight, 'OI', window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        feature_group_count=groups)


def _conv2d_stem_s2d(data, weight, stride, pad):
    """Thin-input strided conv as space-to-depth + stride-1 conv.

    The image-network stem (ResNet 7x7/s2, AlexNet 11x11/s4,
    Inception 3x3/s2 — all cin=3) is the worst conv shape on the MXU:
    3 input channels leave the 128x128 systolic array ~98% idle and the
    stride-2 footprint defeats XLA's tiling. Re-expressing it over
    the s-strided phase decomposition x2[qh, qw, c*s^2 + rh*s + rw] =
    x[s*qh+rh, s*qw+rw] turns it into a dense stride-1 conv with
    cin*s^2 channels — exactly the MLPerf-ResNet space-to-depth trick,
    derived here as a pure reparametrization (no train-recipe change):

      y[p] = sum_j w[j] x[s*p + j - p0]          (original, per dim)

    Shift the kernel by d = (-p0) mod s so p0+d = s*P, split the tap
    index j+d = s*t + r; then y[p] = sum_{t,r} w'[s*t+r] x2[p+t-P, r]
    — a T-tap stride-1 conv over q with T = ceil((k+d)/s). Zero-padded
    taps add (T*s/k)^2-fold nominal FLOPs on a shape whose utilization
    improves by much more (A/B'd on chip; opt-in MXTPU_CONV_STEM_S2D).
    Backward needs no custom rule: the transforms are linear jnp ops,
    and the weight gradient of the stride-1 conv flows back through
    their transpose onto the original 7x7 layout.
    """
    N, C, H, W = data.shape
    O = weight.shape[0]
    sh, sw = stride
    kh, kw = int(weight.shape[2]), int(weight.shape[3])
    ph, pw = pad
    out_h = (H + 2 * ph - kh) // sh + 1
    out_w = (W + 2 * pw - kw) // sw + 1

    def _geom(k, s, p, size, out):
        d = (-p) % s                  # kernel left-shift to align phases
        P = (p + d) // s              # q-space left margin
        T = -((k + d) // -s)          # taps over q (ceil)
        lo = s * P                    # input left pad
        hi = s * (out - 1 + T - P) - size  # right pad to cover last tap
        hi = max(hi, 0)
        hi += (s - (lo + size + hi) % s) % s  # phase split needs s | len
        return d, T, lo, hi

    dh, Th, lo_h, hi_h = _geom(kh, sh, ph, H, out_h)
    dw, Tw, lo_w, hi_w = _geom(kw, sw, pw, W, out_w)

    x = jnp.pad(data, ((0, 0), (0, 0), (lo_h, hi_h), (lo_w, hi_w)))
    qh, qw = x.shape[2] // sh, x.shape[3] // sw
    x = x.reshape(N, C, qh, sh, qw, sw)
    x = jnp.transpose(x, (0, 1, 3, 5, 2, 4)).reshape(N, C * sh * sw, qh, qw)

    w = jnp.pad(weight, ((0, 0), (0, 0),
                         (dh, Th * sh - kh - dh), (dw, Tw * sw - kw - dw)))
    w = w.reshape(O, C, Th, sh, Tw, sw)
    w = jnp.transpose(w, (0, 1, 3, 5, 2, 4)).reshape(O, C * sh * sw, Th, Tw)

    out = _channels_last_conv(
        x, w, 'OI', window_strides=(1, 1), padding=[(0, 0), (0, 0)],
        rhs_dilation=(1, 1), feature_group_count=1)
    return out[:, :, :out_h, :out_w]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv2d_patches_bwd(data, weight, stride, dilate, pad):
    """Conv2d whose WEIGHT gradient is an explicit patches-matmul.

    The measured MFU gap is XLA's grad-weight conv at
    small spatial sizes: conv_backprop_filter becomes a long skinny
    contraction the MXU tiles poorly. im2col + dot_general instead
    turns it into one large (C*kh*kw, N*H'*W') x (N*H'*W', O) matmul —
    the shape the MXU is built for. Data gradient stays the standard
    transposed conv (XLA is already good at it). Opt-in via
    MXTPU_CONV_BWD_PATCHES=1; numerics parity-tested vs the plain path
    (tests/unittest/test_conv_patches.py)."""
    return _channels_last_conv(
        data, weight, 'OI', window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        feature_group_count=1)


def _conv2d_patches_fwd(data, weight, stride, dilate, pad):
    out = _conv2d_patches_bwd(data, weight, stride, dilate, pad)
    return out, (data, weight)


def _conv2d_patches_rev(stride, dilate, pad, res, gout):
    data, weight = res
    padding = [(p, p) for p in pad]

    # grad wrt data: transposed conv, same as the default rule
    def fwd_data(d):
        return _channels_last_conv(
            d, weight, 'OI', window_strides=stride, padding=padding,
            rhs_dilation=dilate, feature_group_count=1)
    g_data = jax.vjp(fwd_data, data)[1](gout)[0]

    # grad wrt weight: im2col patches, one big MXU matmul.
    # patches: (N, C*kh*kw, H', W') with feature dim ordered (C, kh, kw)
    kh, kw = int(weight.shape[2]), int(weight.shape[3])
    patches = jax.lax.conv_general_dilated_patches(
        data, filter_shape=(kh, kw), window_strides=stride,
        padding=padding, rhs_dilation=dilate,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    # contract batch+space of patches (N,CKK,H',W') with gout (N,O,H',W')
    g_w = jax.lax.dot_general(
        patches, gout,
        dimension_numbers=(((0, 2, 3), (0, 2, 3)), ((), ())),
        preferred_element_type=jnp.float32)          # (CKK, O)
    c = int(weight.shape[1])
    g_w = g_w.reshape(c, kh, kw, g_w.shape[-1])      # (C,kh,kw,O)
    g_w = jnp.transpose(g_w, (3, 0, 1, 2)).astype(weight.dtype)
    return g_data, g_w


_conv2d_patches_bwd.defvjp(_conv2d_patches_fwd, _conv2d_patches_rev)


@register('Deconvolution', input_names=['data', 'weight', 'bias'],
          param_defaults={'kernel': (), 'stride': (), 'dilate': (), 'pad': (),
                          'adj': (), 'target_shape': (), 'num_filter': 0,
                          'num_group': 1, 'no_bias': True, 'workspace': 512})
def _deconvolution(attrs, data, weight, bias=None):
    """Reference deconvolution-inl.h — conv transpose = gradient of conv."""
    kernel = tuple(attrs['kernel'])
    nd = len(kernel)
    stride = tuple(attrs.get('stride') or (1,) * nd)
    dilate = tuple(attrs.get('dilate') or (1,) * nd)
    pad = tuple(attrs.get('pad') or (0,) * nd)
    groups = int(attrs.get('num_group', 1))
    adj = tuple(attrs.get('adj') or (0,) * nd)

    # weight layout is (in_ch, out_ch/g, *kernel) in MXNet deconv; the
    # kernel must be spatially flipped: deconv is the input-gradient of
    # the (correlation-style) forward conv, which correlates against the
    # reversed kernel (deconvolution-inl.h pack_col2im == conv backward)
    weight = weight[(slice(None), slice(None)) +
                    (slice(None, None, -1),) * nd]
    if groups > 1:
        # jax wants rhs (C/g, F, *k) with the O dim group-major; mxnet
        # stores (C, F/g, *k) with groups stacked along C
        C = weight.shape[0]
        fpg = weight.shape[1]
        w = weight.reshape((groups, C // groups, fpg) + kernel)
        w = jnp.moveaxis(w, 0, 1)  # (C/g, g, F/g, *k)
        weight = w.reshape((C // groups, groups * fpg) + kernel)
    pads = []
    for k, s, p, d, a in zip(kernel, stride, pad, dilate, adj):
        eff_k = (k - 1) * d + 1
        pads.append((eff_k - 1 - p, eff_k - 1 - p + a))
    out = _channels_last_conv(
        data, weight, 'IO', window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate,
        feature_group_count=groups)
    if bias is not None and not attrs.get('no_bias', True):
        out = out + bias.reshape((1, -1) + (1,) * (out.ndim - 2))
    return out


# ---------------------------------------------------------------------------
# Pooling — reference pooling-inl.h; lax.reduce_window == the pool kernel
# ---------------------------------------------------------------------------
@register('Pooling',
          param_defaults={'kernel': (), 'pool_type': 'max', 'stride': (),
                          'pad': (), 'global_pool': False,
                          'pooling_convention': 'valid', 'cudnn_off': False})
def _pooling(attrs, data):
    nd = data.ndim - 2
    if attrs.get('global_pool', False):
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = tuple(attrs['kernel'])
        stride = tuple(attrs.get('stride') or (1,) * nd)
        pad = tuple(attrs.get('pad') or (0,) * nd)
    ptype = attrs.get('pool_type', 'max')
    full = attrs.get('pooling_convention', 'valid') == 'full'

    window = (1, 1) + kernel
    strides = (1, 1) + stride
    pads = [(0, 0), (0, 0)]
    for i, p in enumerate(pad):
        hi = p
        if full:
            # ceil-mode: add extra padding on the high side if needed
            size = data.shape[2 + i] + 2 * p
            rem = (size - kernel[i]) % stride[i]
            if rem:
                hi = p + (stride[i] - rem)
        pads.append((p, hi))

    if ptype == 'max':
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return jax.lax.reduce_window(data, _np.asarray(init, data.dtype),
                                     jax.lax.max, window, strides, pads)
    if ptype in ('avg', 'sum'):
        s = jax.lax.reduce_window(data, _np.asarray(0, data.dtype),
                                  jax.lax.add, window, strides, pads)
        if ptype == 'sum':
            return s
        # count_include_pad=True (the reference default for avg pooling)
        return s / _np.prod(kernel)
    raise ValueError('unknown pool_type ' + ptype)


register_alias('Pooling_v1', 'Pooling')


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
@register('Activation', param_defaults={'act_type': 'relu'})
def _activation(attrs, x):
    act = attrs.get('act_type', 'relu')
    if act == 'relu':
        return jax.nn.relu(x)
    if act == 'sigmoid':
        return jax.nn.sigmoid(x)
    if act == 'tanh':
        return jnp.tanh(x)
    if act == 'softrelu':
        return jax.nn.softplus(x)
    if act == 'softsign':
        return x / (1 + jnp.abs(x))
    raise ValueError('unknown act_type ' + act)


@register('LeakyReLU', input_names=['data', 'gamma'],
          param_defaults={'act_type': 'leaky', 'slope': 0.25,
                          'lower_bound': 0.125, 'upper_bound': 0.334},
          needs_rng=True, train_aware=True)
def _leaky_relu(attrs, x, *rest):
    """Reference leaky_relu-inl.h: leaky/prelu/elu/rrelu."""
    act = attrs.get('act_type', 'leaky')
    key = rest[-1]
    if act == 'leaky':
        return jnp.where(x > 0, x, attrs.get('slope', 0.25) * x)
    if act == 'elu':
        s = attrs.get('slope', 0.25)
        return jnp.where(x > 0, x, s * (jnp.exp(x) - 1))
    if act == 'prelu':
        gamma = rest[0]
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else gamma
        return jnp.where(x > 0, x, g * x)
    if act == 'rrelu':
        lo, hi = attrs.get('lower_bound', 0.125), attrs.get('upper_bound', 0.334)
        if attrs.get('__is_train__', False):
            slope = jax.random.uniform(key, (x.shape[1] if x.ndim > 1 else 1,),
                                       minval=lo, maxval=hi, dtype=x.dtype)
            s = slope.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else slope
        else:
            s = (lo + hi) / 2.0
        return jnp.where(x > 0, x, s * x)
    raise ValueError('unknown act_type ' + act)


# ---------------------------------------------------------------------------
# BatchNorm — reference batch_norm-inl.h. Aux protocol: returns
# (y, updated_moving_mean, updated_moving_var); invoke writes the extra
# outputs back into the moving_mean/moving_var input NDArrays.
# ---------------------------------------------------------------------------
@register('BatchNorm',
          input_names=['data', 'gamma', 'beta', 'moving_mean', 'moving_var'],
          param_defaults={'eps': 1e-3, 'momentum': 0.9, 'fix_gamma': True,
                          'use_global_stats': False, 'output_mean_var': False,
                          'axis': 1, 'cudnn_off': False},
          aux_inputs=('moving_mean', 'moving_var'),
          mutate_inputs={3: 1, 4: 2}, num_visible_outputs=1,
          num_outputs=3, train_aware=True)
def _batch_norm(attrs, data, gamma, beta, moving_mean, moving_var):
    eps = attrs.get('eps', 1e-3)
    momentum = attrs.get('momentum', 0.9)
    axis = int(attrs.get('axis', 1)) % data.ndim
    fix_gamma = attrs.get('fix_gamma', True)
    use_global = attrs.get('use_global_stats', False) or not attrs.get('__is_train__', False)

    reduce_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1 for i in range(data.ndim))

    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if use_global:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    else:
        x32 = data.astype(jnp.float32)
        if _bn_onepass():
            # one-pass SHIFTED moments: sum and sum-of-squares of
            # (x - pivot) reduce over the SAME read of x, so XLA's
            # multi-output fusion computes the stats in ONE HBM pass of
            # the activation instead of jnp.var's two (mean, then
            # (x-mean)^2 — a data dependency no compiler can
            # single-pass). The per-channel pivot (x's first element)
            # centers the accumulation near the mean, so the
            # E[x^2]-E[x]^2 cancellation operates at std-scale — no
            # precision loss even for large-mean f32 activations; var
            # is clamped at 0. Role of the reference's single-pass
            # CUDA stats kernel (src/operator/batch_norm.cu
            # BatchNormalizationUpdateOutput).
            n = x32.size // x32.shape[axis]
            pivot_idx = tuple(slice(None) if i == axis else 0
                              for i in range(x32.ndim))
            pivot = jax.lax.stop_gradient(x32[pivot_idx])
            xc = x32 - pivot.reshape(bshape)
            s1 = jnp.sum(xc, axis=reduce_axes)
            s2 = jnp.sum(xc * xc, axis=reduce_axes)
            m0 = s1 / n
            mean = pivot + m0
            var = jnp.maximum(s2 / n - m0 * m0, 0.0)
        else:               # MXTPU_BN_ONEPASS=0: the two-pass escape
            # hatch — byte-identical to the pre-flip default lowering
            # (pinned by test_bn_onepass.py), kept for A/B evidence
            mean = jnp.mean(x32, axis=reduce_axes)
            var = jnp.var(x32, axis=reduce_axes)
        new_mm = momentum * moving_mean + (1 - momentum) * mean.astype(moving_mean.dtype)
        new_mv = momentum * moving_var + (1 - momentum) * var.astype(moving_var.dtype)
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps).astype(data.dtype)
    y = (data - mean.astype(data.dtype).reshape(bshape)) * \
        (g * inv).reshape(bshape) + beta.reshape(bshape)
    return y, jax.lax.stop_gradient(new_mm), jax.lax.stop_gradient(new_mv)


register_alias('BatchNorm_v1', 'BatchNorm')


@register('InstanceNorm', input_names=['data', 'gamma', 'beta'],
          param_defaults={'eps': 1e-3})
def _instance_norm(attrs, x, gamma, beta):
    """Reference instance_norm-inl.h (normalize over spatial dims per sample/channel)."""
    eps = attrs.get('eps', 1e-3)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(bshape) + \
        beta.reshape(bshape)


@register('LayerNorm', input_names=['data', 'gamma', 'beta'],
          param_defaults={'axis': -1, 'eps': 1e-5})
def _layer_norm(attrs, x, gamma, beta):
    ax = int(attrs.get('axis', -1)) % x.ndim
    eps = attrs.get('eps', 1e-5)

    def plain(x, gamma, beta):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=ax, keepdims=True)
        var = jnp.var(x32, axis=ax, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + eps)
        bshape = tuple(x.shape[ax] if i == ax else 1 for i in range(x.ndim))
        return (y.astype(x.dtype) * gamma.reshape(bshape)
                + beta.reshape(bshape)).astype(x.dtype)

    if ax == x.ndim - 1:
        from . import pallas_kernels as pk
        return pk.dispatch(
            lambda x, g, b: pk.fused_layernorm(x, g, b, eps), plain,
            x, gamma, beta)
    return plain(x, gamma, beta)


@register('L2Normalization', param_defaults={'eps': 1e-10, 'mode': 'instance'})
def _l2_normalization(attrs, x):
    """Reference l2_normalization-inl.h."""
    eps = attrs.get('eps', 1e-10)
    mode = attrs.get('mode', 'instance')
    if mode == 'instance':
        axes = tuple(range(1, x.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    elif mode == 'channel':
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
    else:  # spatial
        axes = tuple(range(2, x.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / n


@register('LRN', param_defaults={'alpha': 1e-4, 'beta': 0.75, 'knorm': 2.0,
                                 'nsize': 5})
def _lrn(attrs, x):
    """Reference lrn-inl.h (cross-channel local response normalization)."""
    alpha, beta = attrs.get('alpha', 1e-4), attrs.get('beta', 0.75)
    knorm, nsize = attrs.get('knorm', 2.0), int(attrs.get('nsize', 5))
    sq = jnp.square(x)
    half = nsize // 2
    sq_pad = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2))
    window = (1, nsize) + (1,) * (x.ndim - 2)
    ssum = jax.lax.reduce_window(sq_pad, _np.asarray(0, x.dtype), jax.lax.add,
                                 window, (1,) * x.ndim,
                                 [(0, 0)] * x.ndim)
    return x * jnp.power(knorm + alpha / nsize * ssum, -beta)


# ---------------------------------------------------------------------------
# Dropout — reference dropout-inl.h; RNG key comes in as trailing arg
# ---------------------------------------------------------------------------
@register('Dropout', param_defaults={'p': 0.5, 'mode': 'training'},
          needs_rng=True, train_aware=True)
def _dropout(attrs, x, key):
    p = attrs.get('p', 0.5)
    training = attrs.get('__is_train__', False) or attrs.get('mode') == 'always'
    if not training or p <= 0:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


# ---------------------------------------------------------------------------
# Softmax family — reference nn/softmax.cc + softmax_output-inl.h
# ---------------------------------------------------------------------------
@register('softmax', param_defaults={'axis': -1, 'temperature': None})
def _softmax(attrs, x):
    t = attrs.get('temperature', None)
    if t:
        x = x / t
    ax = int(attrs.get('axis', -1)) % x.ndim
    if ax == x.ndim - 1:
        from . import pallas_kernels as pk
        return pk.dispatch(pk.fused_softmax,
                           lambda x: jax.nn.softmax(x, axis=-1), x)
    return jax.nn.softmax(x, axis=ax)


@register('log_softmax', param_defaults={'axis': -1, 'temperature': None})
def _log_softmax(attrs, x):
    t = attrs.get('temperature', None)
    if t:
        x = x / t
    return jax.nn.log_softmax(x, axis=int(attrs.get('axis', -1)))


@register('SoftmaxActivation', param_defaults={'mode': 'instance'})
def _softmax_activation(attrs, x):
    if attrs.get('mode', 'instance') == 'channel':
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


@register('softmax_cross_entropy', input_names=['data', 'label'])
def _softmax_cross_entropy(attrs, data, label):
    from . import pallas_kernels as pk

    def fused(data, lab):
        # fused logsumexp+gather — never materializes softmax in HBM
        return pk.softmax_xent(data, lab).sum().astype(data.dtype)

    def plain(data, lab):
        logp = jax.nn.log_softmax(data, axis=-1)
        picked = jnp.take_along_axis(logp, lab[:, None], axis=-1)
        return -jnp.sum(picked)

    return pk.dispatch(fused, plain, data, label.astype(jnp.int32))


@register('SoftmaxOutput', input_names=['data', 'label'],
          param_defaults={'grad_scale': 1.0, 'ignore_label': -1.0,
                          'multi_output': False, 'use_ignore': False,
                          'preserve_shape': False, 'normalization': 'null',
                          'out_grad': False, 'smooth_alpha': 0.0})
def _softmax_output(attrs, data, label):
    """Reference softmax_output-inl.h.

    Forward = softmax(data). The custom gradient (softmax - one_hot(label),
    scaled/masked per attrs) is wired via jax.custom_vjp so the imperative
    tape and the symbolic executor both get the reference's exact backward.
    """
    return _softmax_output_cvjp(data, label, _SoftmaxOutputCfg(attrs))


class _SoftmaxOutputCfg:
    """Hashable static config for the custom_vjp."""

    def __init__(self, attrs):
        self.grad_scale = attrs.get('grad_scale', 1.0)
        self.ignore_label = attrs.get('ignore_label', -1.0)
        self.use_ignore = attrs.get('use_ignore', False)
        self.multi_output = attrs.get('multi_output', False)
        self.normalization = attrs.get('normalization', 'null')
        self.smooth_alpha = attrs.get('smooth_alpha', 0.0)
        self._k = (self.grad_scale, self.ignore_label, self.use_ignore,
                   self.multi_output, self.normalization, self.smooth_alpha)

    def __hash__(self):
        return hash(self._k)

    def __eq__(self, other):
        return isinstance(other, _SoftmaxOutputCfg) and self._k == other._k


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_output_cvjp(data, label, cfg):
    return _softmax_fwd_impl(data, cfg)


def _softmax_fwd_impl(data, cfg):
    if cfg.multi_output:
        return jax.nn.softmax(data, axis=1)
    if data.ndim > 2:
        return jax.nn.softmax(data, axis=-1)
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(data, label, cfg):
    out = _softmax_fwd_impl(data, cfg)
    return out, (out, label)


def _softmax_output_bwd(cfg, res, g):
    out, label = res
    axis = 1 if cfg.multi_output else -1
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, out.shape[axis], axis=axis, dtype=out.dtype)
    smooth = cfg.smooth_alpha
    if smooth:
        k = out.shape[axis]
        onehot = onehot * (1 - smooth) + smooth / (k - 1) * (1 - onehot)
    grad = out - onehot
    if cfg.use_ignore:
        mask = (label != cfg.ignore_label).astype(out.dtype)
        mask = jnp.expand_dims(mask, axis if axis >= 0 else out.ndim - 1)
        grad = grad * mask
    scale = cfg.grad_scale
    if cfg.normalization == 'batch':
        scale = scale / out.shape[0]
    elif cfg.normalization == 'valid':
        if cfg.use_ignore:
            valid = jnp.maximum(jnp.sum((label != cfg.ignore_label)), 1)
        else:
            valid = label.size
        scale = scale / valid
    return (grad * scale, None)


_softmax_output_cvjp.defvjp(_softmax_output_fwd, _softmax_output_bwd)
register_alias('Softmax', 'SoftmaxOutput')


@register('WeightedSoftmaxOutput', input_names=['data', 'label', 'weight'],
          param_defaults={'grad_scale': 1.0, 'ignore_label': -1.0,
                          'normalization': 'batch'})
def _weighted_softmax_output(attrs, data, label, weight):
    """``SoftmaxOutput`` whose rows carry a weight that arrives as an
    input: data [N, classes], label and weight [N]. Forward = softmax(data),
    so metrics read it as they read ``SoftmaxOutput``. The gradient of row
    i is ``weight_i [label_i != ignore_label] (softmax - one_hot(label_i))
    grad_scale / N`` (``normalization='batch'``: a fixed row count, N, so
    that the objective is ``(1 / N) sum_i weight_i CE_i`` over the rows
    that carry a label; ``'null'``: no division). Label and weight take
    no gradient."""
    scale = float(attrs.get('grad_scale', 1.0))
    if attrs.get('normalization', 'batch') == 'batch':
        scale /= data.shape[0]
    return _weighted_softmax_cvjp(data, label, weight,
                                  (scale, float(attrs.get('ignore_label',
                                                          -1.0))))


@_partial(jax.custom_vjp, nondiff_argnums=(3,))
def _weighted_softmax_cvjp(data, label, weight, cfg):
    return jax.nn.softmax(data, axis=-1)


def _weighted_softmax_fwd(data, label, weight, cfg):
    out = jax.nn.softmax(data, axis=-1)
    return out, (out, label, weight)


def _weighted_softmax_bwd(cfg, res, g):
    scale, ignore = cfg
    out, label, weight = res
    kept = label != ignore
    # an ignored label may be no class at all: it takes class 0's place
    # and a weight of zero
    onehot = jax.nn.one_hot(jnp.where(kept, label, 0).astype(jnp.int32),
                            out.shape[-1], dtype=out.dtype)
    w = jnp.where(kept, weight.astype(out.dtype), 0) * scale
    return (out - onehot) * w[:, None], None, None


_weighted_softmax_cvjp.defvjp(_weighted_softmax_fwd, _weighted_softmax_bwd)


# ---------------------------------------------------------------------------
# Regression outputs & MakeLoss — reference regression_output-inl.h,
# make_loss-inl.h. Same custom-gradient trick.
# ---------------------------------------------------------------------------
def _make_regression(name, fwd, bwd):
    @_partial(jax.custom_vjp, nondiff_argnums=(2,))
    def op(data, label, grad_scale):
        return fwd(data)

    def op_fwd(data, label, grad_scale):
        return fwd(data), (fwd(data), label)

    def op_bwd(grad_scale, res, g):
        out, label = res
        n = out.shape[0]
        return (bwd(out, label) * (grad_scale / n), None)

    op.defvjp(op_fwd, op_bwd)

    @register(name, input_names=['data', 'label'],
              param_defaults={'grad_scale': 1.0})
    def wrapper(attrs, data, label):
        return op(data, label.reshape(data.shape), attrs.get('grad_scale', 1.0))
    return wrapper


_make_regression('LinearRegressionOutput', lambda x: x, lambda o, l: (o - l))
_make_regression('LogisticRegressionOutput', jax.nn.sigmoid, lambda o, l: (o - l))
_make_regression('MAERegressionOutput', lambda x: x, lambda o, l: jnp.sign(o - l))


@register('MakeLoss', param_defaults={'grad_scale': 1.0,
                                      'normalization': 'null',
                                      'valid_thresh': 0.0})
def _make_loss(attrs, x):
    """Reference make_loss-inl.h: forward=identity, backward=grad_scale."""
    scale = attrs.get('grad_scale', 1.0)
    if attrs.get('normalization') == 'batch':
        scale = scale / x.shape[0]
    elif attrs.get('normalization') == 'valid':
        scale = scale / jnp.maximum((x > attrs.get('valid_thresh', 0.0)).sum(), 1)
    return _make_loss_cvjp(x, scale)


@_partial(jax.custom_vjp, nondiff_argnums=())
def _make_loss_cvjp(x, scale):
    return x


def _make_loss_fwd(x, scale):
    # residual must be a jax pytree: carry the broadcast gradient itself
    # (shape/dtype objects are not valid leaves)
    return x, jnp.broadcast_to(jnp.asarray(scale, x.dtype), x.shape)


def _make_loss_bwd(res, g):
    return (res, None)


_make_loss_cvjp.defvjp(_make_loss_fwd, _make_loss_bwd)
register_alias('make_loss', 'MakeLoss')


@register('SVMOutput', input_names=['data', 'label'],
          param_defaults={'margin': 1.0, 'regularization_coefficient': 1.0,
                          'use_linear': False})
def _svm_output(attrs, data, label):
    """Reference svm_output-inl.h: forward is identity (scores)."""
    return _svm_cvjp(data, label, (attrs.get('margin', 1.0),
                                   attrs.get('regularization_coefficient', 1.0),
                                   attrs.get('use_linear', False)))


@_partial(jax.custom_vjp, nondiff_argnums=(2,))
def _svm_cvjp(data, label, cfg):
    return data


def _svm_fwd(data, label, cfg):
    return data, (data, label)


def _svm_bwd(cfg, res, g):
    margin, reg, linear = cfg
    data, label = res
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, data.shape[1], dtype=data.dtype)
    score_correct = jnp.take_along_axis(data, lab[:, None], axis=1)
    viol = (margin - (score_correct - data)) > 0
    if linear:
        gdata = jnp.where(viol, reg * jnp.ones_like(data), 0.0)
    else:
        gdata = jnp.where(viol, 2 * reg * (margin - (score_correct - data)), 0.0)
    gdata = gdata * (1 - onehot)
    gcorrect = -jnp.sum(gdata, axis=1, keepdims=True)
    gdata = gdata + gcorrect * onehot
    return (gdata, None)


_svm_cvjp.defvjp(_svm_fwd, _svm_bwd)


# ---------------------------------------------------------------------------
# Sequence ops — reference sequence_last/mask/reverse-inl.h
# ---------------------------------------------------------------------------
@register('SequenceLast', input_names=['data', 'sequence_length'],
          optional_inputs={'sequence_length': 'use_sequence_length'},
          param_defaults={'use_sequence_length': False, 'axis': 0})
def _sequence_last(attrs, data, seq_len=None):
    if not attrs.get('use_sequence_length', False) or seq_len is None:
        return data[-1]
    idx = (seq_len.astype(jnp.int32) - 1)
    batch = jnp.arange(data.shape[1])
    return data[idx, batch]


@register('SequenceMask', input_names=['data', 'sequence_length'],
          optional_inputs={'sequence_length': 'use_sequence_length'},
          param_defaults={'use_sequence_length': False, 'value': 0.0,
                          'axis': 0})
def _sequence_mask(attrs, data, seq_len=None):
    if not attrs.get('use_sequence_length', False) or seq_len is None:
        return data
    T = data.shape[0]
    mask = jnp.arange(T)[:, None] < seq_len.astype(jnp.int32)[None, :]
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(attrs.get('value', 0.0), data.dtype))


@register('SequenceReverse', input_names=['data', 'sequence_length'],
          optional_inputs={'sequence_length': 'use_sequence_length'},
          param_defaults={'use_sequence_length': False, 'axis': 0})
def _sequence_reverse(attrs, data, seq_len=None):
    if not attrs.get('use_sequence_length', False) or seq_len is None:
        return jnp.flip(data, 0)
    T = data.shape[0]
    sl = seq_len.astype(jnp.int32)
    t = jnp.arange(T)[:, None]
    src = jnp.where(t < sl[None, :], sl[None, :] - 1 - t, t)
    batch = jnp.arange(data.shape[1])[None, :]
    return data[src, batch]
