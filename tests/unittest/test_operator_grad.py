"""Numeric-gradient sweep over the heavier op families.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import check_numeric_gradient

RNG = np.random.RandomState
KW = dict(numeric_eps=1e-3, rtol=0.06, atol=2e-2)


def test_conv_variants_grad():
    rng = RNG(0)
    x = rng.randn(2, 3, 7, 7).astype(np.float32) * 0.5
    for kwargs in [dict(kernel=(3, 3), num_filter=4),
                   dict(kernel=(3, 3), num_filter=4, stride=(2, 2)),
                   dict(kernel=(3, 3), num_filter=4, pad=(1, 1)),
                   dict(kernel=(3, 3), num_filter=6, num_group=3),
                   dict(kernel=(3, 3), num_filter=4, dilate=(2, 2))]:
        s = mx.sym.Convolution(mx.sym.Variable('data'), name='c',
                               no_bias=True, **kwargs)
        w_shape = s.infer_shape(data=x.shape)[0][1]
        w = (rng.randn(*w_shape) * 0.3).astype(np.float32)
        check_numeric_gradient(s, {'data': x, 'c_weight': w}, **KW)


def test_deconv_grad():
    rng = RNG(1)
    x = rng.randn(2, 3, 5, 5).astype(np.float32) * 0.5
    s = mx.sym.Deconvolution(mx.sym.Variable('data'), name='d',
                             kernel=(3, 3), num_filter=4, stride=(2, 2),
                             no_bias=True)
    w_shape = s.infer_shape(data=x.shape)[0][1]
    w = (rng.randn(*w_shape) * 0.3).astype(np.float32)
    check_numeric_gradient(s, {'data': x, 'd_weight': w}, **KW)


@pytest.mark.parametrize('pool_type', ['max', 'avg', 'sum'])
def test_pooling_modes_grad(pool_type):
    rng = RNG(2)
    x = rng.randn(2, 2, 6, 6).astype(np.float32)
    s = mx.sym.Pooling(mx.sym.Variable('data'), kernel=(2, 2),
                       stride=(2, 2), pool_type=pool_type)
    check_numeric_gradient(s, {'data': x}, **KW)
    sg = mx.sym.Pooling(mx.sym.Variable('data'), global_pool=True,
                        pool_type=pool_type, kernel=(1, 1))
    check_numeric_gradient(sg, {'data': x}, **KW)


@pytest.mark.parametrize('op,kw', [
    ('sum', {'axis': 1}), ('mean', {'axis': (0, 2)}),
    ('prod', {'axis': 1}), ('max', {'axis': 1}), ('min', {'axis': 2}),
    ('norm', {}),
])
def test_reduce_family_grad(op, kw):
    rng = RNG(3)
    # offsets keep max/min argmax unique so the subgradient is stable
    x = (rng.randn(3, 4, 5) + np.arange(60).reshape(3, 4, 5) * 0.01) \
        .astype(np.float32)
    s = getattr(mx.sym, op)(mx.sym.Variable('data'), **kw)
    check_numeric_gradient(s, {'data': x}, **KW)


def test_take_and_pick_grad():
    rng = RNG(4)
    w = rng.randn(6, 4).astype(np.float32)
    idx = np.array([0, 3, 5], np.float32)
    s = mx.sym.take(mx.sym.Variable('w'), mx.sym.Variable('idx'))
    check_numeric_gradient(s, {'w': w, 'idx': idx},
                           grad_nodes=['w'], **KW)
    p = mx.sym.pick(mx.sym.Variable('data'), mx.sym.Variable('pidx'),
                    axis=1)
    check_numeric_gradient(
        p, {'data': rng.randn(3, 4).astype(np.float32),
            'pidx': np.array([1, 0, 3], np.float32)},
        grad_nodes=['data'], **KW)


def test_norm_layers_grad():
    rng = RNG(5)
    x = rng.randn(3, 4).astype(np.float32)
    ln = mx.sym.LayerNorm(mx.sym.Variable('data'), name='ln')
    check_numeric_gradient(
        ln, {'data': x, 'ln_gamma': np.ones(4, np.float32),
             'ln_beta': np.zeros(4, np.float32)}, **KW)
    l2 = mx.sym.L2Normalization(mx.sym.Variable('data'))
    check_numeric_gradient(l2, {'data': x + 1.0}, **KW)


def test_linalg_grad():
    rng = RNG(6)
    a = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(4, 2).astype(np.float32)
    s = mx.sym.linalg.gemm2(mx.sym.Variable('a'), mx.sym.Variable('b'))
    check_numeric_gradient(s, {'a': a, 'b': b}, **KW)
    spd = (a @ a.T + 4 * np.eye(3)).astype(np.float32)
    chol = mx.sym.linalg.potrf(mx.sym.Variable('m'))
    check_numeric_gradient(chol, {'m': spd}, **KW)


def test_rnn_op_grad():
    rng = RNG(7)
    T, B, D, H = 3, 2, 4, 5
    x = rng.randn(T, B, D).astype(np.float32) * 0.5
    s = mx.sym.RNN(mx.sym.Variable('data'), state_size=H, num_layers=1,
                   mode='lstm', name='r')
    shapes = dict(zip(s.list_arguments(),
                      s.infer_shape(data=x.shape)[0]))
    params = (rng.randn(*shapes['r_parameters']) * 0.2).astype(np.float32)
    state = np.zeros(shapes['r_state'], np.float32)
    cell = np.zeros(shapes['r_state_cell'], np.float32)
    check_numeric_gradient(
        s, {'data': x, 'r_parameters': params, 'r_state': state,
            'r_state_cell': cell},
        grad_nodes=['data', 'r_parameters'], **KW)


def test_batch_dot_and_topk_backward():
    rng = RNG(8)
    a = rng.randn(2, 3, 4).astype(np.float32)
    b = rng.randn(2, 4, 5).astype(np.float32)
    s = mx.sym.batch_dot(mx.sym.Variable('a'), mx.sym.Variable('b'))
    check_numeric_gradient(s, {'a': a, 'b': b}, **KW)
    # topk ret_typ='value' backprops to the selected entries
    x = (rng.randn(3, 6) + np.arange(18).reshape(3, 6) * 0.05) \
        .astype(np.float32)
    t = mx.sym.topk(mx.sym.Variable('data'), k=2, ret_typ='value')
    check_numeric_gradient(t, {'data': x}, **KW)


def test_unary_family_numeric_grad():
    """Numeric-gradient sweep over the differentiable unary family
    (reference test_operator.py's check_numeric_gradient pattern)."""
    cases = {
        'tanh': (-2, 2), 'sigmoid': (-3, 3), 'exp': (-1, 1),
        'log': (0.2, 3), 'sqrt': (0.2, 4), 'rsqrt': (0.3, 3),
        'square': (-2, 2), 'cbrt': (0.2, 3), 'expm1': (-1, 1),
        'log1p': (-0.5, 2), 'arctan': (-2, 2), 'sinh': (-1.5, 1.5),
        'cosh': (-1.5, 1.5), 'softsign': (-2, 2), 'erf': (-2, 2),
        'gamma': (1.2, 3), 'gammaln': (1.2, 3),
    }
    rng = np.random.RandomState(0)
    for name, (lo, hi) in cases.items():
        data = mx.sym.Variable('data')
        s = mx.sym.sum(getattr(mx.sym, name)(data))
        x = rng.uniform(lo, hi, (3, 4)).astype(np.float32)
        check_numeric_gradient(s, {'data': x}, **KW)


def test_binary_broadcast_numeric_grad():
    rng = np.random.RandomState(1)
    a = rng.uniform(0.5, 2.0, (3, 1, 4)).astype(np.float32)
    b = rng.uniform(0.5, 2.0, (1, 2, 4)).astype(np.float32)
    for op in ['broadcast_add', 'broadcast_mul', 'broadcast_div',
               'broadcast_power', 'broadcast_hypot']:
        lhs, rhs = mx.sym.Variable('lhs'), mx.sym.Variable('rhs')
        s = mx.sym.sum(getattr(mx.sym, op)(lhs, rhs))
        check_numeric_gradient(s, {'lhs': a, 'rhs': b}, **KW)
    # maximum: operands separated beyond the fd eps so the subgradient
    # is stable (both winner directions exercised)
    lhs, rhs = mx.sym.Variable('lhs'), mx.sym.Variable('rhs')
    s = mx.sym.sum(mx.sym.broadcast_maximum(lhs, rhs))
    check_numeric_gradient(s, {'lhs': a, 'rhs': b + 1.5}, **KW)
    check_numeric_gradient(s, {'lhs': a + 3.0, 'rhs': b}, **KW)


def test_layer_ops_numeric_grad():
    """Composite layers against finite differences: conv+bias, FC
    no-flatten, LeakyReLU modes, Embedding, SequenceMask."""
    rng = np.random.RandomState(2)

    data = mx.sym.Variable('data')
    w = mx.sym.Variable('w')
    b = mx.sym.Variable('b')
    conv = mx.sym.sum(mx.sym.Convolution(
        data, w, b, kernel=(3, 3), num_filter=4, pad=(1, 1), stride=(2, 2)))
    check_numeric_gradient(conv, {
        'data': rng.randn(2, 3, 7, 7).astype(np.float32),
        'w': rng.randn(4, 3, 3, 3).astype(np.float32) * 0.5,
        'b': rng.randn(4).astype(np.float32) * 0.1}, **KW)

    fc = mx.sym.sum(mx.sym.FullyConnected(
        data, w, b, num_hidden=5, flatten=False))
    check_numeric_gradient(fc, {
        'data': rng.randn(2, 3, 4).astype(np.float32),
        'w': rng.randn(5, 4).astype(np.float32) * 0.5,
        'b': rng.randn(5).astype(np.float32) * 0.1}, **KW)

    for act in ['leaky', 'elu']:
        s = mx.sym.sum(mx.sym.LeakyReLU(data, act_type=act, slope=0.3))
        check_numeric_gradient(
            s, {'data': rng.randn(3, 4).astype(np.float32) + 0.1}, **KW)

    emb_w = mx.sym.Variable('emb_w')
    emb = mx.sym.sum(mx.sym.Embedding(data, emb_w, input_dim=6,
                                      output_dim=3))
    # gradient flows to the table, not the (integer) indices
    ex = emb.bind(mx.cpu(),
                  {'data': mx.nd.array([[1., 4.], [2., 5.]]),
                   'emb_w': mx.nd.array(rng.randn(6, 3).astype(np.float32))},
                  args_grad={'emb_w': mx.nd.zeros((6, 3))},
                  grad_req={'data': 'null', 'emb_w': 'write'})
    ex.forward(is_train=True)
    ex.backward(mx.nd.ones((1,)))  # full-reduce sum outputs (1,), Shape1(1)
    g = ex.grad_dict['emb_w'].asnumpy()
    want = np.zeros((6, 3))
    for idx in [1, 4, 2, 5]:
        want[idx] += 1
    np.testing.assert_allclose(g, want, rtol=1e-5)

    # SequenceMask: gradient passes only inside each sequence's length
    sm = mx.sym.sum(mx.sym.SequenceMask(
        data, mx.sym.Variable('len'), use_sequence_length=True))
    x = rng.randn(4, 2, 3).astype(np.float32)   # (T, B, D)
    check_numeric_gradient(sm, {'data': x,
                                'len': np.array([2., 4.], np.float32)},
                           grad_nodes=['data'], **KW)


def test_softmax_family_numeric_grad():
    rng = np.random.RandomState(3)
    x = rng.randn(4, 6).astype(np.float32)
    w = rng.randn(4, 6).astype(np.float32)
    data = mx.sym.Variable('data')
    wsym = mx.sym.Variable('w')
    for fn in ['softmax', 'log_softmax']:
        # fixed weights give a non-trivial cotangent; only data is
        # perturbed numerically (grad_nodes)
        s = mx.sym.sum(getattr(mx.sym, fn)(data) * wsym)
        check_numeric_gradient(s, {'data': x, 'w': w},
                               grad_nodes=['data'], **KW)
