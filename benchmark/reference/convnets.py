"""Plain references for the convolutional configurations.

ResNet-50 and Inception-v3: forward pass, mean cross-entropy and its
gradient, and the multi-precision SGD-with-momentum update, in plain
``jax.numpy`` / ``lax.conv_general_dilated`` / ``lax.reduce_window``,
float32, under ``jax.default_matmul_precision('highest')`` (a float32
matmul on a TPU is one bf16 pass unless asked). No kernels, no import of
``mxnet_tpu``: only parameter *names* are shared with the program.

What is followed, and each departure:

* ``resnet50``: stage widths and unit counts of He et al.,
  arXiv:1512.03385 Table 1 (50-layer column: 3/4/6/3 bottleneck units of
  256/512/1024/2048 channels, 7x7/2 stem, 3x3/2 max pool, global average
  pool, 1000-way FC). The unit is the *pre-activation* form of He et al.,
  arXiv:1603.05027 (BN-ReLU-conv three times, stride on the 3x3, the
  projection shortcut taken from the first ReLU), with a final BN-ReLU
  before the pool, because that is what the configuration's builder
  (``examples/image-classification/symbols/resnet.py``, like upstream
  MXNet's) builds under the name ResNet-50.
* ``inception_v3``: Szegedy et al., arXiv:1512.00567, in the layout of
  the torchvision / Gluon model zoo (stem, 3xA, B, 4xC, D, 2xE, 8x8
  average pool, FC; every convolution followed by BN(eps 1e-3)-ReLU, no
  bias). No auxiliary head, and no dropout: the configuration's builder
  sets the dropout rate to 0, since a reference cannot draw the
  program's mask.
* BatchNorm: batch statistics with the biased variance when training,
  moving statistics when not; eps 1e-3 (the operator's default, which
  both builders keep).
* Average pools with padding divide by the whole window (MXNet's
  ``Pooling`` counts the padding).

``quant`` (the control of the benchmark's comparison) rounds both
operands of every convolution and of the FC to float8 e4m3 with one scale
per tensor, straight-through in the backward pass: the nearest precision
below the bfloat16 the configurations state.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-3
_DN = ('NCHW', 'OIHW', 'NCHW')


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def _fp8(x):
    """x rounded to float8 e4m3 with a per-tensor scale; identity
    gradient."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def conv(x, w, stride=(1, 1), pad=(0, 0), quant=False):
    if quant:
        x, w = _fp8(x), _fp8(w)
    return lax.conv_general_dilated(
        x, w, window_strides=stride,
        padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=_DN)


def dense(x, w, b, quant=False):
    if quant:
        x, w = _fp8(x), _fp8(w)
    return x @ w.T + b


def batch_norm(x, p, name, train, names=('moving_mean', 'moving_var')):
    if train:
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(x - mean[None, :, None, None]),
                       axis=(0, 2, 3))
    else:
        mean, var = p[name + '_' + names[0]], p[name + '_' + names[1]]
    inv = lax.rsqrt(var + EPS) * p[name + '_gamma']
    return (x - mean[None, :, None, None]) * inv[None, :, None, None] \
        + p[name + '_beta'][None, :, None, None]


def max_pool(x, k, s, pad=0):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, s, s),
        [(0, 0), (0, 0), (pad, pad), (pad, pad)])


def avg_pool(x, k, s, pad=0):
    total = lax.reduce_window(
        x, 0.0, lax.add, (1, 1, k, k), (1, 1, s, s),
        [(0, 0), (0, 0), (pad, pad), (pad, pad)])
    return total / float(k * k)


def relu(x):
    return jnp.maximum(x, 0.0)


# ---------------------------------------------------------------------------
# ResNet-50
# ---------------------------------------------------------------------------

_RESNET50_UNITS = (3, 4, 6, 3)
_RESNET50_WIDTHS = (256, 512, 1024, 2048)


def _resnet_unit(x, p, name, stride, dim_match, train, quant):
    a1 = relu(batch_norm(x, p, name + '_bn1', train))
    y = conv(a1, p[name + '_conv1_weight'], quant=quant)
    y = relu(batch_norm(y, p, name + '_bn2', train))
    y = conv(y, p[name + '_conv2_weight'], (stride, stride), (1, 1), quant)
    y = relu(batch_norm(y, p, name + '_bn3', train))
    y = conv(y, p[name + '_conv3_weight'], quant=quant)
    if dim_match:
        return y + x
    return y + conv(a1, p[name + '_sc_weight'], (stride, stride),
                    quant=quant)


def resnet50(p, x, train, quant=False, remat=True):
    """Logits (N, classes) of images x (N, 3, H, W). ``remat`` recomputes
    each unit in the backward pass: the same mathematics in less memory,
    so that batch 128 in float32 fits one chip."""
    unit = _resnet_unit
    if remat:
        unit = jax.checkpoint(_resnet_unit, static_argnums=(2, 3, 4, 5, 6))
    y = conv(x, p['conv0_weight'], (2, 2), (3, 3), quant)
    y = relu(batch_norm(y, p, 'bn0', train))
    y = max_pool(y, 3, 2, 1)
    for s, n_units in enumerate(_RESNET50_UNITS):
        for u in range(n_units):
            name = 'stage%d_unit%d' % (s + 1, u + 1)
            stride = 2 if (u == 0 and s > 0) else 1
            y = unit(y, p, name, stride, u > 0, train, quant)
    y = relu(batch_norm(y, p, 'bn1', train))
    y = jnp.mean(y, axis=(2, 3))
    return dense(y, p['fc1_weight'], p['fc1_bias'], quant)


# ---------------------------------------------------------------------------
# Inception-v3
# ---------------------------------------------------------------------------
# One token is 'CHxKH[.KW][sS][pPH[.PW]]' (a conv-BN-ReLU unit), 'avg'
# (3x3/1 pad 1 average pool), 'max' (3x3/2 max pool) or 'a|b' (two units
# on the same input, concatenated). Copied from the published
# architecture, not imported from the program.

_STEM = ('32x3s2', '32x3', '64x3p1', 'M', '80x1', '192x3', 'M')


def _a(pool):
    return ('64x1', '48x1,64x5p2', '64x1,96x3p1,96x3p1', 'avg,%dx1' % pool)


def _c(c):
    return ('192x1',
            '{0}x1,{0}x1.7p0.3,192x7.1p3.0'.format(c),
            '{0}x1,{0}x7.1p3.0,{0}x1.7p0.3,{0}x7.1p3.0,192x1.7p0.3'
            .format(c),
            'avg,192x1')


_SPLIT = '384x1.3p0.1|384x3.1p1.0'
_E = ('320x1', '384x1,' + _SPLIT, '448x1,384x3p1,' + _SPLIT, 'avg,192x1')
_CELLS = (
    ('A1_', _a(32)), ('A2_', _a(64)), ('A3_', _a(64)),
    ('B_', ('384x3s2', '64x1,96x3p1,96x3s2', 'max')),
    ('C1_', _c(128)), ('C2_', _c(160)), ('C3_', _c(160)), ('C4_', _c(192)),
    ('D_', ('192x1,320x3s2',
            '192x1,192x1.7p0.3,192x7.1p3.0,192x3s2', 'max')),
    ('E1_', _E), ('E2_', _E),
)


def _parse(tok):
    """(kernel, stride, pad) of one conv token; channels come from the
    weight."""
    rest = tok.split('x', 1)[1]
    pad = stride = None
    if 'p' in rest:
        rest, pad = rest.split('p')
    if 's' in rest:
        rest, stride = rest.split('s')

    def pair(v, default):
        if v is None:
            return default
        v = [int(t) for t in v.split('.')]
        return (v[0], v[-1])
    return pair(rest, None), pair(stride, (1, 1)), pair(pad, (0, 0))


class _Names:
    """Parameter names in creation order: '<prefix>conv2d<i>' and
    '<prefix>batchnorm<i>', i counted per prefix."""

    def __init__(self, root):
        self.root = root
        self.count = {}

    def unit(self, prefix):
        i = self.count.get(prefix, 0)
        self.count[prefix] = i + 1
        return ('%s%sconv2d%d' % (self.root, prefix, i),
                '%s%sbatchnorm%d' % (self.root, prefix, i))


def _cbr(x, p, names, prefix, tok, train, quant):
    cname, bname = names.unit(prefix)
    _, stride, pad = _parse(tok)
    y = conv(x, p[cname + '_weight'], stride, pad, quant)
    return relu(batch_norm(y, p, bname, train,
                           ('running_mean', 'running_var')))


def _branch(x, p, names, prefix, spec, train, quant):
    for tok in spec.split(','):
        if tok == 'avg':
            x = avg_pool(x, 3, 1, 1)
        elif tok == 'max':
            x = max_pool(x, 3, 2)
        elif '|' in tok:
            x = jnp.concatenate(
                [_cbr(x, p, names, prefix, t, train, quant)
                 for t in tok.split('|')], axis=1)
        else:
            x = _cbr(x, p, names, prefix, tok, train, quant)
    return x


def inception_v3(p, x, train, quant=False, remat=True, root='inception3_'):
    names = _Names(root)
    y = x
    for tok in _STEM:
        y = max_pool(y, 3, 2) if tok == 'M' \
            else _cbr(y, p, names, '', tok, train, quant)

    def cell(y, p, prefix, specs):
        return jnp.concatenate(
            [_branch(y, p, names, prefix, s, train, quant) for s in specs],
            axis=1)
    if remat:
        cell = jax.checkpoint(cell, static_argnums=(2, 3))
    for prefix, specs in _CELLS:
        names.count.pop(prefix, None)
        y = cell(y, p, prefix, specs)
    y = avg_pool(y, 8, 8)
    y = y.reshape(y.shape[0], -1)
    return dense(y, p[root + 'dense0_weight'], p[root + 'dense0_bias'],
                 quant)


MODELS = {'resnet50': resnet50, 'inception_v3': inception_v3}


# ---------------------------------------------------------------------------
# loss, gradient, update
# ---------------------------------------------------------------------------

def log_probs(model, p, x, train, quant=False):
    with jax.default_matmul_precision('highest'):
        logits = MODELS[model](p, x.astype(jnp.float32), train, quant)
    return jax.nn.log_softmax(logits, axis=-1)


def mean_loss(model, p, x, y, quant=False):
    """Mean cross-entropy of a training-mode forward pass."""
    lp = log_probs(model, p, x, True, quant)
    return -jnp.mean(jnp.take_along_axis(lp, y[:, None].astype(jnp.int32),
                                         axis=1))


@functools.partial(jax.jit, static_argnums=(0, 4))
def loss_and_grad(model, p, x, y, quant=False):
    return jax.value_and_grad(
        lambda q: mean_loss(model, q, x, y, quant))(p)


@functools.partial(jax.jit, static_argnums=(0, 3))
def predict_log_probs(model, p, x, quant=False):
    """Inference-mode log-probabilities (moving statistics)."""
    return log_probs(model, p, x, False, quant)


def weight_decay_of(name, wd):
    """MXNet's rule: decay applies to ``*_weight`` and ``*_gamma`` only."""
    return wd if name.endswith(('_weight', '_gamma')) else 0.0


def sgd_momentum_step(w, mom, g, lr, momentum, wd):
    """One update of every leaf, all float32 (the masters):
    mom = momentum * mom - lr * (g + wd * w);  w = w + mom."""
    new_w, new_m = {}, {}
    for n in g:
        d = g[n] + weight_decay_of(n, wd) * w[n]
        new_m[n] = momentum * mom[n] - lr * d
        new_w[n] = w[n] + new_m[n]
    return new_w, new_m
