"""Operations a configuration *requires*, counted from shapes.

The count comes from an abstract trace (``jax.eval_shape``) of the plain
reference: every convolution and fully-connected layer it reaches records
its operand shapes, and the multiply-adds follow from those. Nothing is
read from the program or from XLA's cost analysis, which counts
recomputation and fusion artefacts. 2 operations per multiply-add; a
trained layer needs its forward pass, the gradient of its weight and the
gradient of its input (each as many multiply-adds as the forward pass),
except a layer fed by the network's input, which needs no input gradient.
Elementwise, pooling and normalisation work is not counted: it is under
1% of a convolutional network's operations.
"""
import contextlib

import jax
import jax.numpy as jnp

from benchmark.reference import convnets


@contextlib.contextmanager
def _recording(log):
    real_conv, real_dense = convnets.conv, convnets.dense

    def conv(x, w, stride=(1, 1), pad=(0, 0), quant=False):
        out = real_conv(x, w, stride, pad, quant)
        macs = (out.shape[0] * out.shape[1] * out.shape[2] * out.shape[3]
                * w.shape[1] * w.shape[2] * w.shape[3])
        log.append({'kind': 'conv', 'x': tuple(x.shape),
                    'w': tuple(w.shape), 'out': tuple(out.shape),
                    'macs': macs})
        return out

    def dense(x, w, b, quant=False):
        out = real_dense(x, w, b, quant)
        log.append({'kind': 'fc', 'x': tuple(x.shape), 'w': tuple(w.shape),
                    'out': tuple(out.shape),
                    'macs': x.shape[0] * w.shape[0] * w.shape[1]})
        return out

    convnets.conv, convnets.dense = conv, dense
    try:
        yield
    finally:
        convnets.conv, convnets.dense = real_conv, real_dense


_LAYERS = {}    # one abstract trace per (model, shapes) in a process


def layers(model, param_shapes, image_shape):
    """One record per convolution / FC layer of `model` for ONE image:
    shapes and forward multiply-adds, in execution order."""
    key = (model, tuple(image_shape),
           tuple(sorted((n, tuple(s)) for n, s in param_shapes.items())))
    if key not in _LAYERS:
        _LAYERS[key] = _trace_layers(model, param_shapes, image_shape)
    return _LAYERS[key]


def _trace_layers(model, param_shapes, image_shape):
    params = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
              for n, s in param_shapes.items()}
    x = jax.ShapeDtypeStruct((1,) + tuple(image_shape), jnp.float32)
    log = []
    with _recording(log):
        jax.eval_shape(
            lambda p, v: convnets.MODELS[model](p, v, True, False, False),
            params, x)
    return log


def required_flops(model, param_shapes, image_shape):
    """Operations per sample: {'forward', 'train', 'conv_forward',
    'conv_train'} (train = forward + backward, no recomputation)."""
    recs = layers(model, param_shapes, image_shape)
    out = {'forward': 0, 'train': 0, 'conv_forward': 0, 'conv_train': 0}
    for i, r in enumerate(recs):
        fwd = 2 * r['macs']
        train = fwd * (2 if i == 0 else 3)   # the first layer eats the input
        out['forward'] += fwd
        out['train'] += train
        if r['kind'] == 'conv':
            out['conv_forward'] += fwd
            out['conv_train'] += train
    return out
