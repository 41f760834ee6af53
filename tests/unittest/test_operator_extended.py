"""Extended operator coverage.

Reference: tests/python/unittest/test_operator.py (4,010 LoC) — the
numeric-gradient + numpy-oracle pattern applied across the registered
surface: unary/binary math, broadcast/reduce, index/gather, shape
manipulation, conv/pool variants, norm layers, linalg, sequence ops.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
import mxnet_tpu.autograd as ag
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_forward,
                                  check_symbolic_backward)

RNG = np.random.RandomState


# ---------------------------------------------------------------------------
# unary math vs numpy oracles (reference test_operator.py unary family)
# ---------------------------------------------------------------------------
UNARY_CASES = [
    # (op, numpy fn, domain lo, hi, grad?)
    ('abs', np.abs, -2, 2, True),
    ('exp', np.exp, -2, 2, True),
    ('expm1', np.expm1, -1, 1, True),
    ('log', np.log, 0.1, 4, True),
    ('log1p', np.log1p, -0.5, 2, True),
    ('log2', np.log2, 0.1, 4, True),
    ('log10', np.log10, 0.1, 4, True),
    ('sqrt', np.sqrt, 0.1, 4, True),
    ('rsqrt', lambda x: 1 / np.sqrt(x), 0.1, 4, True),
    ('cbrt', np.cbrt, 0.1, 4, True),
    ('rcbrt', lambda x: 1 / np.cbrt(x), 0.1, 4, True),
    ('square', np.square, -2, 2, True),
    ('reciprocal', lambda x: 1 / x, 0.2, 3, True),
    ('sin', np.sin, -3, 3, True),
    ('cos', np.cos, -3, 3, True),
    ('tan', np.tan, -1, 1, True),
    ('arcsin', np.arcsin, -0.9, 0.9, True),
    ('arccos', np.arccos, -0.9, 0.9, True),
    ('arctan', np.arctan, -3, 3, True),
    ('sinh', np.sinh, -2, 2, True),
    ('cosh', np.cosh, -2, 2, True),
    ('tanh', np.tanh, -2, 2, True),
    ('arcsinh', np.arcsinh, -2, 2, True),
    ('arccosh', np.arccosh, 1.1, 4, True),
    ('arctanh', np.arctanh, -0.9, 0.9, True),
    ('sigmoid', lambda x: 1 / (1 + np.exp(-x)), -3, 3, True),
    ('softsign', lambda x: x / (1 + np.abs(x)), -3, 3, True),
    ('relu', lambda x: np.maximum(x, 0), -2, 2, False),
    ('floor', np.floor, -3, 3, False),
    ('ceil', np.ceil, -3, 3, False),
    ('trunc', np.trunc, -3, 3, False),
    ('rint', np.rint, -3, 3, False),
    ('fix', np.fix, -3, 3, False),
    ('sign', np.sign, -3, 3, False),
    ('negative', np.negative, -3, 3, True),
    ('degrees', np.degrees, -3, 3, True),
    ('radians', np.radians, -180, 180, True),
    ('gamma', lambda x: np.vectorize(__import__('math').gamma)(x), 0.5, 4, True),
    ('gammaln', lambda x: np.vectorize(__import__('math').lgamma)(x), 0.5, 4, True),
    ('erf', lambda x: np.vectorize(__import__('math').erf)(x), -2, 2, True),
]


@pytest.mark.parametrize('op,ref,lo,hi,grad', UNARY_CASES,
                         ids=[c[0] for c in UNARY_CASES])
def test_unary_vs_numpy(op, ref, lo, hi, grad):
    rng = RNG(hash(op) % (2 ** 31))
    x = rng.uniform(lo, hi, (3, 4)).astype(np.float32)
    got = getattr(nd, op)(nd.array(x)).asnumpy()
    assert_almost_equal(got, ref(x).astype(np.float32), rtol=1e-4, atol=1e-5)
    if grad:
        data = mx.sym.Variable('data')
        sym = getattr(mx.sym, op)(data)
        check_numeric_gradient(sym, [x], numeric_eps=1e-3, rtol=0.05,
                               atol=1e-2)


# ---------------------------------------------------------------------------
# binary + scalar arithmetic
# ---------------------------------------------------------------------------
def test_binary_elemwise_vs_numpy():
    rng = RNG(0)
    a = rng.uniform(0.5, 2, (3, 4)).astype(np.float32)
    b = rng.uniform(0.5, 2, (3, 4)).astype(np.float32)
    na, nb = nd.array(a), nd.array(b)
    assert_almost_equal((na + nb).asnumpy(), a + b)
    assert_almost_equal((na - nb).asnumpy(), a - b)
    assert_almost_equal((na * nb).asnumpy(), a * b)
    assert_almost_equal((na / nb).asnumpy(), a / b, rtol=1e-5)
    assert_almost_equal((na ** nb).asnumpy(), a ** b, rtol=1e-4)
    assert_almost_equal((na % nb).asnumpy(), a % b, rtol=1e-5)
    assert_almost_equal(nd.maximum(na, nb).asnumpy(), np.maximum(a, b))
    assert_almost_equal(nd.minimum(na, nb).asnumpy(), np.minimum(a, b))


def test_scalar_arithmetic_all_orders():
    x = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    n = nd.array(x)
    assert_almost_equal((n + 2).asnumpy(), x + 2)
    assert_almost_equal((2 + n).asnumpy(), 2 + x)
    assert_almost_equal((n - 2).asnumpy(), x - 2)
    assert_almost_equal((2 - n).asnumpy(), 2 - x)
    assert_almost_equal((n * 3).asnumpy(), x * 3)
    assert_almost_equal((n / 2).asnumpy(), x / 2)
    assert_almost_equal((2 / n).asnumpy(), 2 / x, rtol=1e-6)
    assert_almost_equal((n ** 2).asnumpy(), x ** 2)
    assert_almost_equal((2 ** n).asnumpy(), 2 ** x, rtol=1e-6)
    assert_almost_equal((n % 2).asnumpy(), x % 2)
    assert_almost_equal((7 % n).asnumpy(), 7 % x)


def test_comparison_scalar_ops():
    x = np.array([1.0, 2.0, 3.0], np.float32)
    n = nd.array(x)
    assert ((n > 2).asnumpy() == (x > 2)).all()
    assert ((n >= 2).asnumpy() == (x >= 2)).all()
    assert ((n < 2).asnumpy() == (x < 2)).all()
    assert ((n <= 2).asnumpy() == (x <= 2)).all()
    assert ((n == 2).asnumpy() == (x == 2)).all()
    assert ((n != 2).asnumpy() == (x != 2)).all()


def test_smooth_l1():
    x = np.array([-2.0, -0.5, 0.0, 0.5, 2.0], np.float32)
    got = nd.smooth_l1(nd.array(x), scalar=1.0).asnumpy()
    want = np.where(np.abs(x) < 1, 0.5 * x * x, np.abs(x) - 0.5)
    assert_almost_equal(got, want.astype(np.float32))


def test_add_n():
    rng = RNG(1)
    arrs = [rng.randn(2, 3).astype(np.float32) for _ in range(4)]
    got = nd.add_n(*[nd.array(a) for a in arrs]).asnumpy()
    assert_almost_equal(got, sum(arrs))


# ---------------------------------------------------------------------------
# broadcast family
# ---------------------------------------------------------------------------
BCAST_OPS = [
    ('broadcast_add', np.add), ('broadcast_sub', np.subtract),
    ('broadcast_mul', np.multiply), ('broadcast_div', np.divide),
    ('broadcast_maximum', np.maximum), ('broadcast_minimum', np.minimum),
    ('broadcast_power', np.power), ('broadcast_mod', np.mod),
    ('broadcast_hypot', np.hypot),
]


@pytest.mark.parametrize('op,ref', BCAST_OPS, ids=[c[0] for c in BCAST_OPS])
def test_broadcast_binary(op, ref):
    rng = RNG(2)
    a = rng.uniform(0.5, 2, (2, 3, 4)).astype(np.float32)
    b = rng.uniform(0.5, 2, (2, 1, 4)).astype(np.float32)
    got = getattr(nd, op)(nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(got, ref(a, b).astype(np.float32), rtol=1e-5)


def test_broadcast_comparisons():
    a = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    b = np.array([[2.0], [3.0]], np.float32)
    for op, ref in [('broadcast_equal', np.equal),
                    ('broadcast_not_equal', np.not_equal),
                    ('broadcast_greater', np.greater),
                    ('broadcast_greater_equal', np.greater_equal),
                    ('broadcast_lesser', np.less),
                    ('broadcast_lesser_equal', np.less_equal)]:
        got = getattr(nd, op)(nd.array(a), nd.array(b)).asnumpy()
        assert (got == ref(a, b).astype(np.float32)).all(), op


def test_broadcast_logical():
    a = np.array([0.0, 1.0, 2.0, 0.0], np.float32)
    b = np.array([0.0, 0.0, 1.0, 3.0], np.float32)
    assert_almost_equal(
        nd.broadcast_logical_and(nd.array(a), nd.array(b)).asnumpy(),
        np.logical_and(a, b).astype(np.float32))
    assert_almost_equal(
        nd.broadcast_logical_or(nd.array(a), nd.array(b)).asnumpy(),
        np.logical_or(a, b).astype(np.float32))
    assert_almost_equal(
        nd.broadcast_logical_xor(nd.array(a), nd.array(b)).asnumpy(),
        np.logical_xor(a, b).astype(np.float32))


def test_broadcast_to_and_axes():
    x = np.arange(4, dtype=np.float32).reshape(1, 4)
    got = nd.broadcast_to(nd.array(x), shape=(3, 4)).asnumpy()
    assert_almost_equal(got, np.broadcast_to(x, (3, 4)))
    got2 = nd.broadcast_axis(nd.array(x.reshape(1, 4)), axis=0, size=5)
    assert got2.shape == (5, 4)
    like = nd.zeros((3, 4))
    got3 = nd.broadcast_like(nd.array(x), like)
    assert got3.shape == (3, 4)


def test_broadcast_grad_reduces_correctly():
    data = mx.sym.Variable('a')
    b = mx.sym.Variable('b')
    out = mx.sym.broadcast_mul(data, b)
    rng = RNG(3)
    a_np = rng.randn(2, 3).astype(np.float32)
    b_np = rng.randn(1, 3).astype(np.float32)
    og = rng.randn(2, 3).astype(np.float32)
    check_symbolic_backward(out, [a_np, b_np], [og],
                            [og * b_np, (og * a_np).sum(0, keepdims=True)])


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
REDUCE_CASES = [
    ('sum', np.sum), ('mean', np.mean), ('prod', np.prod),
    ('max', np.max), ('min', np.min),
    ('nansum', np.nansum), ('nanprod', np.nanprod),
]


@pytest.mark.parametrize('op,ref', REDUCE_CASES,
                         ids=[c[0] for c in REDUCE_CASES])
def test_reduce_vs_numpy(op, ref):
    rng = RNG(4)
    x = rng.uniform(0.5, 2, (2, 3, 4)).astype(np.float32)
    if op.startswith('nan'):
        x[0, 0, 0] = np.nan
    for axis in [None, 0, 1, 2, (0, 2)]:
        kwargs = {} if axis is None else {'axis': axis}
        got = getattr(nd, op)(nd.array(x), **kwargs).asnumpy()
        want = ref(x, axis=axis).astype(np.float32)
        assert_almost_equal(got.squeeze(), np.asarray(want).squeeze(),
                            rtol=1e-4, atol=1e-5)


def test_reduce_keepdims():
    x = RNG(5).randn(2, 3, 4).astype(np.float32)
    got = nd.sum(nd.array(x), axis=1, keepdims=True)
    assert got.shape == (2, 1, 4)
    assert_almost_equal(got.asnumpy(), x.sum(1, keepdims=True), rtol=1e-5)


def test_norm():
    x = RNG(6).randn(3, 4).astype(np.float32)
    got = nd.norm(nd.array(x)).asnumpy()
    assert_almost_equal(np.asarray(got).squeeze(), np.linalg.norm(x),
                        rtol=1e-5)


def test_sum_grad():
    data = mx.sym.Variable('data')
    sym = mx.sym.sum(data, axis=1)
    x = RNG(7).randn(3, 4).astype(np.float32)
    check_numeric_gradient(sym, [x], numeric_eps=1e-3, rtol=0.05, atol=1e-2)


def test_argmax_argmin():
    x = RNG(8).randn(3, 4).astype(np.float32)
    assert (nd.argmax(nd.array(x), axis=1).asnumpy() ==
            np.argmax(x, 1)).all()
    assert (nd.argmin(nd.array(x), axis=0).asnumpy() ==
            np.argmin(x, 0)).all()
    assert (nd.argmax_channel(nd.array(x)).asnumpy() == np.argmax(x, 1)).all()


# ---------------------------------------------------------------------------
# index / gather / scatter
# ---------------------------------------------------------------------------
def test_take_modes():
    w = np.arange(12, dtype=np.float32).reshape(4, 3)
    idx = np.array([0, 3, 1], np.float32)
    got = nd.take(nd.array(w), nd.array(idx)).asnumpy()
    assert_almost_equal(got, w[[0, 3, 1]])
    # clip mode on out-of-range
    idx2 = np.array([5, -1], np.float32)
    got2 = nd.take(nd.array(w), nd.array(idx2), mode='clip').asnumpy()
    assert_almost_equal(got2, w[[3, 0]])


def test_take_grad_scatters():
    data = mx.sym.Variable('data')
    idx = mx.sym.Variable('idx')
    sym = mx.sym.take(data, idx)
    w = RNG(9).randn(4, 3).astype(np.float32)
    i = np.array([1, 1, 2], np.float32)
    og = np.ones((3, 3), np.float32)
    want = np.zeros_like(w)
    np.add.at(want, [1, 1, 2], og)
    ex = sym.bind(mx.cpu(), {'data': nd.array(w), 'idx': nd.array(i)},
                  args_grad={'data': nd.zeros(w.shape)}, grad_req={'data': 'write', 'idx': 'null'})
    ex.forward(is_train=True)
    ex.backward(out_grads=nd.array(og))
    assert_almost_equal(ex.grad_dict['data'].asnumpy(), want)


def test_batch_take_and_pick():
    x = np.arange(12, dtype=np.float32).reshape(4, 3)
    idx = np.array([0, 2, 1, 0], np.float32)
    got = nd.pick(nd.array(x), nd.array(idx), axis=1).asnumpy()
    assert_almost_equal(got, x[np.arange(4), idx.astype(int)])
    got2 = nd.batch_take(nd.array(x), nd.array(idx)).asnumpy()
    assert_almost_equal(got2, x[np.arange(4), idx.astype(int)])


def test_gather_nd_scatter_nd():
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    indices = np.array([[0, 2], [1, 3]], np.float32)  # rows: dims
    got = nd.gather_nd(nd.array(x), nd.array(indices)).asnumpy()
    assert_almost_equal(got, x[[0, 2], [1, 3]])
    data = np.array([9.0, 8.0], np.float32)
    got2 = nd.scatter_nd(nd.array(data), nd.array(indices),
                         shape=(3, 4)).asnumpy()
    want = np.zeros((3, 4), np.float32)
    want[0, 1] = 9
    want[2, 3] = 8
    assert_almost_equal(got2, want)


def test_one_hot():
    idx = np.array([0, 2, 1], np.float32)
    got = nd.one_hot(nd.array(idx), depth=4).asnumpy()
    want = np.eye(4, dtype=np.float32)[[0, 2, 1]]
    assert_almost_equal(got, want)
    got2 = nd.one_hot(nd.array(idx), depth=4, on_value=5, off_value=-1)
    assert got2.asnumpy()[0, 0] == 5 and got2.asnumpy()[0, 1] == -1


def test_where_op():
    cond = np.array([1.0, 0.0, 1.0], np.float32)
    a = np.array([1.0, 2.0, 3.0], np.float32)
    b = np.array([9.0, 8.0, 7.0], np.float32)
    got = nd.where(nd.array(cond), nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(got, np.where(cond > 0, a, b))


# ---------------------------------------------------------------------------
# sort / topk
# ---------------------------------------------------------------------------
def test_sort_argsort():
    x = RNG(10).randn(3, 5).astype(np.float32)
    assert_almost_equal(nd.sort(nd.array(x), axis=1).asnumpy(), np.sort(x, 1))
    assert_almost_equal(nd.sort(nd.array(x), axis=1, is_ascend=False).asnumpy(),
                        -np.sort(-x, 1))
    assert (nd.argsort(nd.array(x), axis=1).asnumpy() ==
            np.argsort(x, 1, kind='stable')).all()


def test_topk_modes():
    x = RNG(11).randn(2, 6).astype(np.float32)
    # indices mode (default)
    got = nd.topk(nd.array(x), k=3, axis=1).asnumpy()
    want = np.argsort(-x, 1)[:, :3]
    assert (got == want).all()
    # value mode
    got_v = nd.topk(nd.array(x), k=3, axis=1, ret_typ='value').asnumpy()
    assert_almost_equal(got_v, -np.sort(-x, 1)[:, :3])
    # both
    vals, idxs = nd.topk(nd.array(x), k=2, axis=1, ret_typ='both')
    assert_almost_equal(vals.asnumpy(), -np.sort(-x, 1)[:, :2])
    # smallest
    got_s = nd.topk(nd.array(x), k=2, axis=1, is_ascend=True,
                    ret_typ='value').asnumpy()
    assert_almost_equal(got_s, np.sort(x, 1)[:, :2])


# ---------------------------------------------------------------------------
# shape manipulation
# ---------------------------------------------------------------------------
def test_reshape_special_codes():
    x = nd.zeros((2, 3, 4))
    assert nd.reshape(x, shape=(-1,)).shape == (24,)
    assert nd.reshape(x, shape=(0, -1)).shape == (2, 12)
    assert nd.reshape(x, shape=(-2,)).shape == (2, 3, 4)
    assert nd.reshape(x, shape=(-3, 4)).shape == (6, 4)
    assert nd.reshape(x, shape=(0, 0, 2, 2)).shape == (2, 3, 2, 2)
    assert nd.reshape_like(x, nd.zeros((6, 4))).shape == (6, 4)


def test_transpose_swapaxes_flip():
    x = RNG(12).randn(2, 3, 4).astype(np.float32)
    assert_almost_equal(nd.transpose(nd.array(x)).asnumpy(),
                        x.transpose())
    assert_almost_equal(
        nd.transpose(nd.array(x), axes=(1, 0, 2)).asnumpy(),
        x.transpose(1, 0, 2))
    assert_almost_equal(nd.swapaxes(nd.array(x), dim1=0, dim2=2).asnumpy(),
                        x.swapaxes(0, 2))
    assert_almost_equal(nd.flip(nd.array(x), axis=1).asnumpy(),
                        x[:, ::-1])
    assert_almost_equal(nd.reverse(nd.array(x), axis=2).asnumpy(),
                        x[:, :, ::-1])


def test_tile_repeat():
    x = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    assert_almost_equal(nd.tile(nd.array(x), reps=(2, 3)).asnumpy(),
                        np.tile(x, (2, 3)))
    assert_almost_equal(nd.repeat(nd.array(x), repeats=2, axis=1).asnumpy(),
                        np.repeat(x, 2, 1))
    assert_almost_equal(nd.repeat(nd.array(x), repeats=2).asnumpy(),
                        np.repeat(x, 2))


def test_expand_squeeze():
    x = nd.zeros((2, 1, 3))
    assert nd.expand_dims(x, axis=0).shape == (1, 2, 1, 3)
    assert nd.squeeze(x).shape == (2, 3)
    assert nd.squeeze(x, axis=1).shape == (2, 3)


def test_stack_concat_split():
    a = np.ones((2, 3), np.float32)
    b = 2 * np.ones((2, 3), np.float32)
    got = nd.stack(nd.array(a), nd.array(b), axis=1)
    assert got.shape == (2, 2, 3)
    got2 = nd.concat(nd.array(a), nd.array(b), dim=0)
    assert got2.shape == (4, 3)
    parts = nd.split(nd.array(np.arange(12, np.float32).reshape(2, 6)
                              if False else
                              np.arange(12, dtype=np.float32).reshape(2, 6)),
                     num_outputs=3, axis=1)
    assert len(parts) == 3 and parts[0].shape == (2, 2)
    assert_almost_equal(parts[1].asnumpy(),
                        np.arange(12, dtype=np.float32).reshape(2, 6)[:, 2:4])
    # squeeze_axis
    p2 = nd.split(nd.array(a), num_outputs=2, axis=0, squeeze_axis=True)
    assert p2[0].shape == (3,)


def test_slice_family():
    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    got = nd.slice(nd.array(x), begin=(0, 1, 1), end=(2, 3, 3)).asnumpy()
    assert_almost_equal(got, x[0:2, 1:3, 1:3])
    got2 = nd.slice_axis(nd.array(x), axis=2, begin=1, end=3).asnumpy()
    assert_almost_equal(got2, x[:, :, 1:3])
    like = nd.zeros((2, 2, 2))
    got3 = nd.slice_like(nd.array(x), like).asnumpy()
    assert_almost_equal(got3, x[:2, :2, :2])
    got4 = nd.slice_like(nd.array(x), like, axes=(1,)).asnumpy()
    assert_almost_equal(got4, x[:, :2])
    # stepped slice
    got5 = nd.slice(nd.array(x), begin=(None, None, None),
                    end=(None, None, None), step=(1, 2, 1)).asnumpy()
    assert_almost_equal(got5, x[:, ::2])


def test_space_depth_roundtrip():
    x = RNG(13).randn(1, 4, 2, 2).astype(np.float32)
    y = nd.depth_to_space(nd.array(x), block_size=2)
    assert y.shape == (1, 1, 4, 4)
    z = nd.space_to_depth(y, block_size=2)
    assert_almost_equal(z.asnumpy(), x)


def test_pad_modes():
    x = RNG(14).randn(1, 1, 3, 3).astype(np.float32)
    w = (0, 0, 0, 0, 1, 1, 1, 1)
    got = nd.pad(nd.array(x), mode='constant', pad_width=w,
                 constant_value=5).asnumpy()
    want = np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)), 'constant',
                  constant_values=5)
    assert_almost_equal(got, want)
    got_e = nd.pad(nd.array(x), mode='edge', pad_width=w).asnumpy()
    assert_almost_equal(got_e, np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)),
                                      'edge'))
    got_r = nd.pad(nd.array(x), mode='reflect', pad_width=w).asnumpy()
    assert_almost_equal(got_r, np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)),
                                      'reflect'))


def test_clip_op():
    x = np.array([-2.0, 0.5, 3.0], np.float32)
    got = nd.clip(nd.array(x), a_min=-1, a_max=1).asnumpy()
    assert_almost_equal(got, np.clip(x, -1, 1))


# ---------------------------------------------------------------------------
# dot family
# ---------------------------------------------------------------------------
def test_dot_variants():
    rng = RNG(15)
    a = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(4, 5).astype(np.float32)
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)).asnumpy(), a @ b,
                        rtol=1e-4)
    assert_almost_equal(
        nd.dot(nd.array(a), nd.array(b.T), transpose_b=True).asnumpy(),
        a @ b, rtol=1e-4)
    assert_almost_equal(
        nd.dot(nd.array(a.T), nd.array(b), transpose_a=True).asnumpy(),
        a @ b, rtol=1e-4)


def test_batch_dot():
    rng = RNG(16)
    a = rng.randn(2, 3, 4).astype(np.float32)
    b = rng.randn(2, 4, 5).astype(np.float32)
    got = nd.batch_dot(nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(got, a @ b, rtol=1e-4)
    got_t = nd.batch_dot(nd.array(a), nd.array(b.transpose(0, 2, 1)),
                         transpose_b=True).asnumpy()
    assert_almost_equal(got_t, a @ b, rtol=1e-4)


def test_dot_grad():
    a = mx.sym.Variable('a')
    b = mx.sym.Variable('b')
    sym = mx.sym.dot(a, b)
    rng = RNG(17)
    check_numeric_gradient(sym, [rng.randn(3, 4).astype(np.float32),
                                 rng.randn(4, 2).astype(np.float32)],
                           numeric_eps=1e-3, rtol=0.05, atol=1e-2)


def test_khatri_rao():
    a = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    b = np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]], np.float32)
    got = nd.khatri_rao(nd.array(a), nd.array(b)).asnumpy()
    want = np.vstack([np.kron(a[:, i], b[:, i]).reshape(-1)
                      for i in range(2)]).T.reshape(6, 2)
    # column-wise kron: check one column explicitly
    assert got.shape == (6, 2)
    assert_almost_equal(got[:, 0], np.kron(a[:, 0], b[:, 0]))


# ---------------------------------------------------------------------------
# linalg family
# ---------------------------------------------------------------------------
def test_linalg_gemm():
    rng = RNG(18)
    a = rng.randn(3, 4).astype(np.float32)
    b = rng.randn(4, 5).astype(np.float32)
    c = rng.randn(3, 5).astype(np.float32)
    got = nd.linalg_gemm(nd.array(a), nd.array(b), nd.array(c),
                         alpha=2.0, beta=0.5).asnumpy()
    assert_almost_equal(got, 2.0 * (a @ b) + 0.5 * c, rtol=1e-4)
    got2 = nd.linalg_gemm2(nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(got2, a @ b, rtol=1e-4)


def test_linalg_potrf_potri():
    rng = RNG(19)
    m = rng.randn(4, 4).astype(np.float32)
    spd = m @ m.T + 4 * np.eye(4, dtype=np.float32)
    l = nd.linalg_potrf(nd.array(spd)).asnumpy()
    assert_almost_equal(l @ l.T, spd, rtol=1e-3, atol=1e-3)
    assert_almost_equal(l, np.tril(l))  # lower triangular
    inv = nd.linalg_potri(nd.array(l)).asnumpy()
    assert_almost_equal(inv @ spd, np.eye(4), rtol=1e-2, atol=1e-2)


def test_linalg_trmm_trsm():
    rng = RNG(20)
    l = np.tril(rng.randn(3, 3).astype(np.float32)) + 3 * np.eye(3, dtype=np.float32)
    b = rng.randn(3, 4).astype(np.float32)
    got = nd.linalg_trmm(nd.array(l), nd.array(b)).asnumpy()
    assert_almost_equal(got, l @ b, rtol=1e-4)
    x = nd.linalg_trsm(nd.array(l), nd.array(b)).asnumpy()
    assert_almost_equal(l @ x, b, rtol=1e-3, atol=1e-3)


def test_linalg_syrk_sumlogdiag():
    rng = RNG(21)
    a = rng.randn(3, 4).astype(np.float32)
    got = nd.linalg_syrk(nd.array(a)).asnumpy()
    assert_almost_equal(got, a @ a.T, rtol=1e-4)
    m = np.diag(np.array([1.0, 2.0, 3.0], np.float32)) + \
        np.triu(0.1 * np.ones((3, 3), np.float32), 1)
    got2 = nd.linalg_sumlogdiag(nd.array(m)).asnumpy()
    assert_almost_equal(np.asarray(got2).squeeze(),
                        np.log(np.array([1.0, 2.0, 3.0])).sum(), rtol=1e-5)


# ---------------------------------------------------------------------------
# conv/pool/deconv variants (beyond test_operator.py basics)
# ---------------------------------------------------------------------------
def test_convolution_dilate_group():
    rng = RNG(22)
    x = rng.randn(1, 4, 8, 8).astype(np.float32)
    w = rng.randn(4, 2, 3, 3).astype(np.float32)
    out = nd.Convolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                         num_filter=4, num_group=2, dilate=(2, 2),
                         no_bias=True)
    assert out.shape == (1, 4, 4, 4)
    # group semantics: each half of filters sees half of channels
    out_full = out.asnumpy()
    x_lo = x[:, :2]
    w_lo = w[:2]
    out_lo = nd.Convolution(nd.array(x_lo), nd.array(w_lo), None,
                            kernel=(3, 3), num_filter=2, dilate=(2, 2),
                            no_bias=True).asnumpy()
    assert_almost_equal(out_full[:, :2], out_lo, rtol=1e-4)


def test_convolution_1d_3d():
    rng = RNG(23)
    x1 = rng.randn(2, 3, 10).astype(np.float32)
    w1 = rng.randn(4, 3, 3).astype(np.float32)
    out1 = nd.Convolution(nd.array(x1), nd.array(w1), None, kernel=(3,),
                          num_filter=4, no_bias=True)
    assert out1.shape == (2, 4, 8)
    x3 = rng.randn(1, 2, 4, 4, 4).astype(np.float32)
    w3 = rng.randn(2, 2, 2, 2, 2).astype(np.float32)
    out3 = nd.Convolution(nd.array(x3), nd.array(w3), None, kernel=(2, 2, 2),
                          num_filter=2, no_bias=True)
    assert out3.shape == (1, 2, 3, 3, 3)


def test_deconvolution_inverts_shapes():
    rng = RNG(24)
    x = rng.randn(1, 3, 5, 5).astype(np.float32)
    w = rng.randn(3, 4, 3, 3).astype(np.float32)
    out = nd.Deconvolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                           num_filter=4, stride=(2, 2), no_bias=True)
    assert out.shape == (1, 4, 11, 11)
    # adj pads the output
    out2 = nd.Deconvolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                            num_filter=4, stride=(2, 2), adj=(1, 1),
                            no_bias=True)
    assert out2.shape == (1, 4, 12, 12)


def test_deconv_is_conv_transpose():
    """deconv(x, w) forward == gradient of conv w.r.t. its input."""
    rng = RNG(25)
    x = rng.randn(1, 2, 4, 4).astype(np.float32)
    w = rng.randn(2, 3, 3, 3).astype(np.float32)
    dec = nd.Deconvolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                           num_filter=3, no_bias=True).asnumpy()
    data = mx.sym.Variable('data')
    wsym = mx.sym.Variable('weight')
    conv = mx.sym.Convolution(data, wsym, kernel=(3, 3), num_filter=2,
                              no_bias=True)
    big = np.zeros((1, 3, 6, 6), np.float32)
    ex = conv.bind(mx.cpu(), {'data': nd.array(big), 'weight': nd.array(w)},
                   args_grad={'data': nd.zeros(big.shape)},
                   grad_req={'data': 'write', 'weight': 'null'})
    ex.forward(is_train=True)
    ex.backward(out_grads=nd.array(x))
    # conv input-grad with flipped/transposed weights == deconv output
    assert_almost_equal(ex.grad_dict['data'].asnumpy(), dec, rtol=1e-3,
                        atol=1e-4)


def test_pooling_variants():
    rng = RNG(26)
    x = rng.randn(1, 2, 6, 6).astype(np.float32)
    # sum pooling
    got = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type='sum').asnumpy()
    want = x.reshape(1, 2, 3, 2, 3, 2).sum(axis=(3, 5))
    assert_almost_equal(got, want, rtol=1e-5)
    # global pooling
    got_g = nd.Pooling(nd.array(x), kernel=(1, 1), global_pool=True,
                       pool_type='max').asnumpy()
    assert_almost_equal(got_g.squeeze(), x.max(axis=(2, 3)).squeeze())
    # full convention rounds up
    got_f = nd.Pooling(nd.array(x), kernel=(4, 4), stride=(4, 4),
                       pool_type='max', pooling_convention='full')
    assert got_f.shape == (1, 2, 2, 2)
    # 1d pooling
    x1 = rng.randn(1, 2, 8).astype(np.float32)
    got1 = nd.Pooling(nd.array(x1), kernel=(2,), stride=(2,),
                      pool_type='avg')
    assert got1.shape == (1, 2, 4)


def test_lrn():
    rng = RNG(27)
    x = rng.uniform(0.1, 1, (1, 4, 3, 3)).astype(np.float32)
    got = nd.LRN(nd.array(x), nsize=3, alpha=1e-4, beta=0.75, knorm=2.0)
    assert got.shape == x.shape
    # oracle for channel 0 (window covers channels 0..1)
    sq = x ** 2
    denom = (2.0 + 1e-4 / 3 * (sq[0, 0] + sq[0, 1])) ** 0.75
    assert_almost_equal(got.asnumpy()[0, 0], x[0, 0] / denom, rtol=1e-4)


def test_l2_normalization_modes():
    rng = RNG(28)
    x = rng.randn(2, 3, 4).astype(np.float32)
    got = nd.L2Normalization(nd.array(x), mode='instance').asnumpy()
    want = x / np.sqrt((x ** 2).sum(axis=(1, 2), keepdims=True) + 1e-10)
    assert_almost_equal(got, want, rtol=1e-4)
    got_c = nd.L2Normalization(nd.array(x), mode='channel').asnumpy()
    want_c = x / np.sqrt((x ** 2).sum(axis=1, keepdims=True) + 1e-10)
    assert_almost_equal(got_c, want_c, rtol=1e-4)


def test_instance_norm():
    rng = RNG(29)
    x = rng.randn(2, 3, 4, 4).astype(np.float32)
    gamma = np.ones(3, np.float32)
    beta = np.zeros(3, np.float32)
    got = nd.InstanceNorm(nd.array(x), nd.array(gamma), nd.array(beta),
                          eps=1e-5).asnumpy()
    mean = x.mean(axis=(2, 3), keepdims=True)
    var = x.var(axis=(2, 3), keepdims=True)
    want = (x - mean) / np.sqrt(var + 1e-5)
    assert_almost_equal(got, want, rtol=1e-4, atol=1e-5)


def test_softmax_temperature_axis():
    rng = RNG(30)
    x = rng.randn(2, 5).astype(np.float32)
    got = nd.softmax(nd.array(x), temperature=2.0).asnumpy()
    e = np.exp(x / 2.0 - (x / 2.0).max(1, keepdims=True))
    assert_almost_equal(got, e / e.sum(1, keepdims=True), rtol=1e-5)
    x3 = rng.randn(2, 3, 4).astype(np.float32)
    got_ax = nd.softmax(nd.array(x3), axis=1).asnumpy()
    e3 = np.exp(x3 - x3.max(1, keepdims=True))
    assert_almost_equal(got_ax, e3 / e3.sum(1, keepdims=True), rtol=1e-5)


def test_log_softmax_matches_log_of_softmax():
    x = RNG(31).randn(3, 6).astype(np.float32)
    got = nd.log_softmax(nd.array(x)).asnumpy()
    assert_almost_equal(got, np.log(nd.softmax(nd.array(x)).asnumpy()),
                        rtol=1e-4, atol=1e-5)


def test_softmax_cross_entropy():
    rng = RNG(32)
    x = rng.randn(4, 5).astype(np.float32)
    label = np.array([0, 2, 4, 1], np.float32)
    got = nd.softmax_cross_entropy(nd.array(x), nd.array(label)).asnumpy()
    p = np.exp(x - x.max(1, keepdims=True))
    p = p / p.sum(1, keepdims=True)
    want = -np.log(p[np.arange(4), label.astype(int)]).sum()
    assert_almost_equal(np.asarray(got).squeeze(), want, rtol=1e-4)


def test_blockgrad_stops_gradient():
    x = nd.array(np.array([1.0, 2.0], np.float32))
    x.attach_grad()
    with ag.record():
        y = nd.BlockGrad(x * 2) * 3 + x
        loss = y.sum()
    loss.backward()
    assert_almost_equal(x.grad.asnumpy(), np.ones(2, np.float32))


def test_custom_op_roundtrip():
    import mxnet_tpu.operator as op_mod

    class Double(op_mod.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            self.assign(out_data[0], req[0], in_data[0] * 2)

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            self.assign(in_grad[0], req[0], out_grad[0] * 2)

    @op_mod.register('double_ext')
    class DoubleProp(op_mod.CustomOpProp):
        def list_arguments(self):
            return ['data']

        def list_outputs(self):
            return ['output']

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            return Double()

    x = nd.array(np.array([1.0, 2.0, 3.0], np.float32))
    got = nd.Custom(x, op_type='double_ext')
    assert_almost_equal(got.asnumpy(), np.array([2.0, 4.0, 6.0], np.float32))


# ---------------------------------------------------------------------------
# sequence + misc layers
# ---------------------------------------------------------------------------
def test_sequence_mask_value():
    x = np.ones((4, 2, 3), np.float32)  # (T, N, ...)
    lens = np.array([2, 4], np.float32)
    got = nd.SequenceMask(nd.array(x), nd.array(lens),
                          use_sequence_length=True, value=-1).asnumpy()
    assert (got[:2, 0] == 1).all() and (got[2:, 0] == -1).all()
    assert (got[:, 1] == 1).all()


def test_sequence_last_reverse():
    x = np.arange(24, dtype=np.float32).reshape(4, 2, 3)
    lens = np.array([2, 4], np.float32)
    last = nd.SequenceLast(nd.array(x), nd.array(lens),
                           use_sequence_length=True).asnumpy()
    assert_almost_equal(last[0], x[1, 0])
    assert_almost_equal(last[1], x[3, 1])
    rev = nd.SequenceReverse(nd.array(x), nd.array(lens),
                             use_sequence_length=True).asnumpy()
    assert_almost_equal(rev[0, 0], x[1, 0])
    assert_almost_equal(rev[1, 0], x[0, 0])
    assert_almost_equal(rev[2, 0], x[2, 0])  # beyond len: untouched
    assert_almost_equal(rev[0, 1], x[3, 1])


def test_crop_op():
    x = np.arange(36, dtype=np.float32).reshape(1, 1, 6, 6)
    got = nd.Crop(nd.array(x), h_w=(3, 3), center_crop=True).asnumpy()
    assert got.shape == (1, 1, 3, 3)
    # center 3x3 block of a 6x6 starts at offset 1 (floor((6-3)/2))
    assert_almost_equal(got[0, 0], x[0, 0, 1:4, 1:4])


def test_svm_output_forward_identity():
    x = RNG(33).randn(3, 4).astype(np.float32)
    label = np.array([0, 1, 2], np.float32)
    got = nd.SVMOutput(nd.array(x), nd.array(label)).asnumpy()
    assert_almost_equal(got, x)


def test_makeloss_grad_is_output_scaled():
    data = mx.sym.Variable('data')
    loss = mx.sym.MakeLoss(mx.sym.sum(data * data), grad_scale=2.0)
    x = np.array([[1.0, 2.0]], np.float32)
    ex = loss.bind(mx.cpu(), {'data': nd.array(x)},
                   args_grad={'data': nd.zeros((1, 2))})
    ex.forward(is_train=True)
    ex.backward()
    assert_almost_equal(ex.grad_dict['data'].asnumpy(), 4 * x)


def test_identity_ops():
    x = RNG(34).randn(2, 3).astype(np.float32)
    assert_almost_equal(nd.identity(nd.array(x)).asnumpy(), x)
    assert_almost_equal(nd.stop_gradient(nd.array(x)).asnumpy(), x)
    assert_almost_equal(nd.zeros_like(nd.array(x)).asnumpy(),
                        np.zeros_like(x))
    assert_almost_equal(nd.ones_like(nd.array(x)).asnumpy(),
                        np.ones_like(x))


def test_cast_dtypes():
    x = np.array([1.5, 2.7], np.float32)
    # float64 omitted: jax x64 mode is off by default on TPU
    for dt in ['int32', 'uint8', 'float16']:
        got = nd.cast(nd.array(x), dtype=dt)
        assert str(got.dtype) == dt
    assert (nd.cast(nd.array(x), dtype='int32').asnumpy() ==
            np.array([1, 2])).all()


def test_arange_zeros_ones():
    got = nd.arange(2, 10, step=2)
    assert_almost_equal(got.asnumpy(), np.arange(2, 10, 2, dtype=np.float32))
    got_r = nd.arange(0, 4, repeat=2)
    assert_almost_equal(got_r.asnumpy(),
                        np.repeat(np.arange(4, dtype=np.float32), 2))
    assert nd.zeros((2, 2)).asnumpy().sum() == 0
    assert nd.ones((2, 2)).asnumpy().sum() == 4


# ---------------------------------------------------------------------------
# random samplers: moment checks (reference test_random.py pattern)
# ---------------------------------------------------------------------------
def test_random_uniform_moments():
    mx.random.seed(42)
    x = nd.random_uniform(low=2, high=4, shape=(50000,)).asnumpy()
    assert abs(x.mean() - 3.0) < 0.05
    assert x.min() >= 2 and x.max() <= 4


def test_random_normal_moments():
    mx.random.seed(43)
    x = nd.random_normal(loc=1.0, scale=2.0, shape=(50000,)).asnumpy()
    assert abs(x.mean() - 1.0) < 0.05
    assert abs(x.std() - 2.0) < 0.05


def test_random_poisson_gamma_exponential():
    mx.random.seed(44)
    p = nd.random_poisson(lam=4.0, shape=(20000,)).asnumpy()
    assert abs(p.mean() - 4.0) < 0.15
    g = nd.random_gamma(alpha=3.0, beta=2.0, shape=(20000,)).asnumpy()
    assert abs(g.mean() - 6.0) < 0.25
    e = nd.random_exponential(lam=2.0, shape=(20000,)).asnumpy()
    assert abs(e.mean() - 0.5) < 0.05


def test_sample_multinomial_distribution():
    mx.random.seed(45)
    probs = nd.array(np.array([[0.2, 0.8]], np.float32))
    s = nd.sample_multinomial(probs, shape=10000).asnumpy()
    assert abs((s == 1).mean() - 0.8) < 0.05


def test_shuffle_is_permutation():
    mx.random.seed(46)
    x = np.arange(100, dtype=np.float32)
    got = nd.shuffle(nd.array(x)).asnumpy()
    assert sorted(got.tolist()) == x.tolist()
    assert not (got == x).all()


def test_seed_reproducibility():
    mx.random.seed(7)
    a = nd.random_normal(shape=(10,)).asnumpy()
    mx.random.seed(7)
    b = nd.random_normal(shape=(10,)).asnumpy()
    assert_almost_equal(a, b)


# ---------------------------------------------------------------------------
# numeric gradients across key layers (reference check_numeric_gradient use)
# ---------------------------------------------------------------------------
def test_conv_numeric_gradient():
    data = mx.sym.Variable('data')
    sym = mx.sym.Convolution(data, kernel=(3, 3), num_filter=2, pad=(1, 1),
                             name='c')
    rng = RNG(35)
    check_numeric_gradient(
        sym, [rng.randn(1, 2, 5, 5).astype(np.float32),
              rng.randn(2, 2, 3, 3).astype(np.float32),
              rng.randn(2).astype(np.float32)],
        numeric_eps=1e-2, rtol=0.1, atol=5e-2)


def test_pooling_numeric_gradient():
    data = mx.sym.Variable('data')
    for pool_type in ['avg', 'sum']:
        sym = mx.sym.Pooling(data, kernel=(2, 2), stride=(2, 2),
                             pool_type=pool_type)
        rng = RNG(36)
        check_numeric_gradient(sym, [rng.randn(1, 1, 4, 4).astype(np.float32)],
                               numeric_eps=1e-2, rtol=0.05, atol=1e-2)


def test_batchnorm_numeric_gradient():
    data = mx.sym.Variable('data')
    sym = mx.sym.BatchNorm(data, fix_gamma=False, use_global_stats=False,
                           name='bn')
    rng = RNG(37)
    check_numeric_gradient(
        sym, [rng.randn(4, 3).astype(np.float32),
              np.abs(rng.randn(3)).astype(np.float32) + 0.5,
              rng.randn(3).astype(np.float32)],
        aux_states=[np.zeros(3, np.float32), np.ones(3, np.float32)],
        numeric_eps=1e-2, rtol=0.1, atol=5e-2)


def test_broadcast_ops_numeric_gradient():
    a = mx.sym.Variable('a')
    b = mx.sym.Variable('b')
    rng = RNG(38)
    for op in [mx.sym.broadcast_add, mx.sym.broadcast_mul]:
        sym = op(a, b)
        check_numeric_gradient(sym, [rng.randn(2, 3).astype(np.float32),
                                     rng.randn(1, 3).astype(np.float32)],
                               numeric_eps=1e-3, rtol=0.05, atol=1e-2)


def test_embedding_numeric_gradient_weight():
    data = mx.sym.Variable('data')
    weight = mx.sym.Variable('weight')
    sym = mx.sym.Embedding(data, weight, input_dim=5, output_dim=3)
    idx = np.array([[0, 2], [4, 2]], np.float32)
    rng = RNG(39)
    w = rng.randn(5, 3).astype(np.float32)
    # only the weight is differentiable
    ex = sym.bind(mx.cpu(), {'data': nd.array(idx), 'weight': nd.array(w)},
                  args_grad={'weight': nd.zeros((5, 3))},
                  grad_req={'data': 'null', 'weight': 'write'})
    ex.forward(is_train=True)
    og = np.ones((2, 2, 3), np.float32)
    ex.backward(out_grads=nd.array(og))
    want = np.zeros((5, 3), np.float32)
    np.add.at(want, idx.astype(int).ravel(),
              og.reshape(-1, 3))
    assert_almost_equal(ex.grad_dict['weight'].asnumpy(), want)


def test_grad_req_add_accumulates():
    data = mx.sym.Variable('data')
    sym = mx.sym.sum(data * data)
    x = np.array([1.0, 2.0], np.float32)
    g = nd.zeros((2,))
    ex = sym.bind(mx.cpu(), {'data': nd.array(x)}, args_grad={'data': g},
                  grad_req='add')
    for _ in range(3):
        ex.forward(is_train=True)
        ex.backward()
    assert_almost_equal(ex.grad_dict['data'].asnumpy(), 3 * 2 * x)


def test_grouped_deconv_is_grouped_conv_transpose():
    """Grouped deconv forward == input-gradient of the grouped conv
    (the group-major weight relayout for XLA must preserve semantics)."""
    rng = RNG(40)
    x = rng.randn(1, 4, 5, 5).astype(np.float32)
    w = rng.randn(4, 3, 3, 3).astype(np.float32)  # (C=4, F/g=3), g=2
    dec = nd.Deconvolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                           num_filter=6, num_group=2, no_bias=True).asnumpy()
    data = mx.sym.Variable('data')
    wsym = mx.sym.Variable('weight')
    conv = mx.sym.Convolution(data, wsym, kernel=(3, 3), num_filter=4,
                              num_group=2, no_bias=True)
    big = np.zeros((1, 6, 7, 7), np.float32)
    ex = conv.bind(mx.cpu(), {'data': nd.array(big), 'weight': nd.array(w)},
                   args_grad={'data': nd.zeros(big.shape)},
                   grad_req={'data': 'write', 'weight': 'null'})
    ex.forward(is_train=True)
    ex.backward(out_grads=nd.array(x))
    assert_almost_equal(dec, ex.grad_dict['data'].asnumpy(), rtol=1e-4,
                        atol=1e-5)


def test_ndarray_pickle_roundtrip():
    import pickle
    x = nd.array(RNG(41).randn(3, 4).astype(np.float32))
    y = pickle.loads(pickle.dumps(x))
    assert_almost_equal(y.asnumpy(), x.asnumpy())
    # the unpickled array must be fully functional (jax-backed)
    y[0] = 7.0
    assert (y.asnumpy()[0] == 7.0).all()
    z = (y * 2).asnumpy()
    assert_almost_equal(z[1], 2 * x.asnumpy()[1])
    # bf16 payloads survive
    b = nd.array(np.ones((2, 2), np.float32)).astype('bfloat16')
    b2 = pickle.loads(pickle.dumps(b))
    assert str(b2.dtype) == 'bfloat16'


def test_linalg_gelqf():
    rng = RNG(42)
    a = rng.randn(3, 5).astype(np.float32)
    q, l = nd.linalg_gelqf(nd.array(a))
    assert q.shape == (3, 5) and l.shape == (3, 3)
    assert_almost_equal(l.asnumpy() @ q.asnumpy(), a, rtol=1e-4, atol=1e-5)
    assert_almost_equal(q.asnumpy() @ q.asnumpy().T, np.eye(3),
                        rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# elemwise comparison/mod/hypot registrations (reference
# elemwise_binary_op_logic.cc / _extended.cc _equal.._lesser_equal, _mod,
# _hypot, _grad_add) and slice assignment (matrix_op.cc _slice_assign)
# ---------------------------------------------------------------------------
ELEM_BINARY_CASES = [
    ('_equal', lambda a, b: (a == b).astype(np.float32)),
    ('_not_equal', lambda a, b: (a != b).astype(np.float32)),
    ('_greater', lambda a, b: (a > b).astype(np.float32)),
    ('_greater_equal', lambda a, b: (a >= b).astype(np.float32)),
    ('_lesser', lambda a, b: (a < b).astype(np.float32)),
    ('_lesser_equal', lambda a, b: (a <= b).astype(np.float32)),
    ('_mod', np.mod),
    ('_hypot', np.hypot),
    ('_grad_add', np.add),
]


@pytest.mark.parametrize('op,ref', ELEM_BINARY_CASES,
                         ids=[c[0] for c in ELEM_BINARY_CASES])
def test_elemwise_binary_registrations(op, ref):
    rng = RNG(7)
    a = np.round(rng.uniform(-3, 3, (4, 5))).astype(np.float32)
    b = np.round(rng.uniform(-3, 3, (4, 5))).astype(np.float32)
    b[b == 0] = 1.0  # keep _mod defined
    got = getattr(nd, op)(nd.array(a), nd.array(b)).asnumpy()
    assert_almost_equal(got, ref(a, b), rtol=1e-5, atol=1e-6)


def test_slice_assign():
    rng = RNG(8)
    a = rng.randn(4, 5).astype(np.float32)
    r = rng.randn(2, 3).astype(np.float32)
    lhs = nd.array(a)
    out = nd._slice_assign(lhs, nd.array(r),
                           begin=(1, 1), end=(3, 4)).asnumpy()
    want = a.copy()
    want[1:3, 1:4] = r
    assert_almost_equal(out, want)
    # original untouched (functional form)
    assert_almost_equal(lhs.asnumpy(), a)
    # _crop_assign is the legacy alias
    out2 = nd._crop_assign(nd.array(a), nd.array(r),
                           begin=(1, 1), end=(3, 4)).asnumpy()
    assert_almost_equal(out2, want)


def test_slice_assign_scalar():
    a = np.arange(20, dtype=np.float32).reshape(4, 5)
    out = nd._slice_assign_scalar(nd.array(a), scalar=-1.0,
                                  begin=(0, 2), end=(4, 5)).asnumpy()
    want = a.copy()
    want[:, 2:] = -1.0
    assert_almost_equal(out, want)
    out2 = nd._crop_assign_scalar(nd.array(a), scalar=3.0,
                                  begin=(1,), end=(2,)).asnumpy()
    want2 = a.copy()
    want2[1:2] = 3.0
    assert_almost_equal(out2, want2)


def test_sparse_retain_registry_op():
    a = RNG(9).randn(5, 3).astype(np.float32)
    idx = np.array([0, 3], np.int64)
    out = nd._sparse_retain(nd.array(a), nd.array(idx)).asnumpy()
    want = np.zeros_like(a)
    want[[0, 3]] = a[[0, 3]]
    assert_almost_equal(out, want)
    # gradient is the same row mask applied to ograd
    # (reference _backward_sparse_retain)
    x = nd.array(a)
    x.attach_grad()
    with ag.record():
        y = nd._sparse_retain(x, nd.array(idx))
        loss = nd.sum(y)
    loss.backward()
    gmask = np.zeros_like(a)
    gmask[[0, 3]] = 1.0
    assert_almost_equal(x.grad.asnumpy(), gmask)
    # the public nd.sparse_retain name accepts the reference's
    # row_sparse input type and returns a row_sparse result
    dense = np.zeros((4, 2), np.float32)
    dense[[1, 3]] = [[1, 2], [3, 4]]
    rsp = nd.array(dense).tostype('row_sparse')
    kept = nd.sparse_retain(rsp, nd.array(np.array([3], np.int64)))
    assert kept.stype == 'row_sparse'
    want2 = np.zeros_like(dense)
    want2[3] = dense[3]
    assert_almost_equal(kept.tostype('default').asnumpy(), want2)


def test_cast_storage_and_square_sum_registry_ops():
    a = RNG(10).randn(3, 4).astype(np.float32)
    # eager nd.cast_storage performs the real container conversion
    rsp = nd.cast_storage(nd.array(a), stype='row_sparse')
    assert rsp.stype == 'row_sparse'
    assert_almost_equal(rsp.tostype('default').asnumpy(), a)
    assert_almost_equal(nd.cast_storage(nd.array(a),
                                        stype='default').asnumpy(), a)
    # symbol-world cast_storage is a value-identity annotation
    s = mx.sym.cast_storage(mx.sym.Variable('x'), stype='row_sparse')
    ex = s.bind(mx.cpu(), {'x': nd.array(a)})
    assert_almost_equal(ex.forward()[0].asnumpy(), a)
    got = nd._square_sum(nd.array(a), axis=1).asnumpy()
    assert_almost_equal(got, (a ** 2).sum(1), rtol=1e-5, atol=1e-6)
    got0 = nd._square_sum(nd.array(a)).asnumpy()
    assert_almost_equal(got0, (a ** 2).sum(), rtol=1e-5, atol=1e-6)


def test_slice_assign_symbolic():
    lhs = mx.sym.Variable('lhs')
    rhs = mx.sym.Variable('rhs')
    s = mx.sym._slice_assign(lhs, rhs, begin=(0,), end=(1,))
    a = np.ones((2, 3), np.float32)
    r = np.full((1, 3), 5.0, np.float32)
    ex = s.bind(mx.cpu(), {'lhs': nd.array(a), 'rhs': nd.array(r)})
    out = ex.forward()[0].asnumpy()
    want = a.copy()
    want[0:1] = r
    assert_almost_equal(out, want)


def test_copy_make_border():
    from mxnet_tpu.image.image import copyMakeBorder
    img = np.arange(12, dtype=np.float32).reshape(2, 2, 3)
    out = copyMakeBorder(img, 1, 2, 3, 4, type=0, values=9.0)
    assert out.shape == (5, 9, 3)
    assert (out[0] == 9.0).all() and (out[:, 0] == 9.0).all()
    assert_almost_equal(out[1, 3], img[0, 0])
    rep = copyMakeBorder(img, 1, 0, 0, 0, type=1)
    assert_almost_equal(rep[0], img[0])
    # cv2 border codes: 2 reflect (edge doubled), 3 wrap, 4 reflect_101
    refl = copyMakeBorder(img, 1, 0, 0, 0, type=2)
    assert_almost_equal(refl[0], img[0])
    wrap = copyMakeBorder(img, 1, 0, 0, 0, type=3)
    assert_almost_equal(wrap[0], img[-1])
    r101 = copyMakeBorder(img, 1, 0, 0, 0, type=4)
    assert_almost_equal(r101[0], img[1])
    with pytest.raises(ValueError):
        copyMakeBorder(img, 1, 0, 0, 0, type=7)


def test_deconvolution_bf16_backward():
    """Regression: bf16 Deconvolution under record() must not crash in
    the conv vjp (f32 cotangent vs bf16 operands)."""
    rng = RNG(11)
    x = nd.array(rng.randn(2, 3, 5, 5).astype(np.float32)).astype('bfloat16')
    w = nd.array((rng.randn(3, 4, 3, 3) * 0.1).astype(np.float32)).astype(
        'bfloat16')
    x.attach_grad()
    w.attach_grad()
    with ag.record():
        y = nd.Deconvolution(x, w, kernel=(3, 3), num_filter=4,
                             no_bias=True)
        loss = nd.sum(y * y)
    loss.backward()
    assert str(x.grad.dtype) == 'bfloat16'
    assert x.grad.shape == x.shape and w.grad.shape == w.shape
    assert float(nd.sum(nd.abs(w.grad)).asnumpy()) > 0


def test_reshape_legacy_target_shape():
    """Deprecated Reshape(target_shape=, keep_highest=) params
    (matrix_op-inl.h:159-182): 0 marks the one inferred dim;
    keep_highest pins dim0 to the input's. 2017-era scripts
    (bi-lstm-sort lstm.py:117) still use them."""
    x = nd.array(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
    # (0,) -> fully flattened
    flat = nd.Reshape(x, target_shape=(0,))
    assert flat.shape == (24,)
    np.testing.assert_allclose(flat.asnumpy(), np.arange(24))
    # explicit dims with one inferred
    r = nd.Reshape(x, target_shape=(6, 0))
    assert r.shape == (6, 4)
    # keep_highest: dim0 from input, trailing inferred
    k = nd.Reshape(x, target_shape=(7, 0), keep_highest=True)
    assert k.shape == (2, 12)
    # symbolic path: shape inference must agree
    s = mx.sym.Variable('a')
    out = mx.sym.Reshape(s, target_shape=(0,))
    _, oshape, _ = out.infer_shape(a=(2, 3, 4))
    assert tuple(oshape[0]) == (24,)


def test_batchnorm_onepass_matches_twopass():
    """MXTPU_BN_ONEPASS (one fused HBM read for sum/sumsq) must be a
    pure scheduling change: training-mode outputs, moving-stat updates,
    and input/param gradients match the two-pass jnp.var form."""
    import subprocess
    import sys
    import os as _os
    code = r'''
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'
import jax; jax.config.update('jax_platforms', 'cpu')
import numpy as np
import json
import mxnet_tpu as mx
from mxnet_tpu import autograd as ag

np.random.seed(0)
import sys as _sys
_mean = float(_sys.argv[1]) if len(_sys.argv) > 1 else 7.0
x = mx.nd.array((np.random.randn(4, 6, 5, 5) * 3 + _mean).astype('float32'))
g = mx.nd.array(np.random.rand(6).astype('float32') + 0.5)
b = mx.nd.array(np.random.randn(6).astype('float32'))
mm = mx.nd.zeros(6)
mv = mx.nd.ones(6)
x.attach_grad(); g.attach_grad()
with ag.record():
    y = mx.nd.BatchNorm(x, g, b, mm, mv, fix_gamma=False, eps=1e-3)
    loss = (y * y).sum()
loss.backward()
out = {'y': y.asnumpy().tolist(), 'dx': x.grad.asnumpy().tolist(),
       'dg': g.grad.asnumpy().tolist()}
print(json.dumps(out))
'''
    def run(flag, mean):
        env = dict(_os.environ)
        env['MXTPU_BN_ONEPASS'] = flag
        env['JAX_PLATFORMS'] = 'cpu'
        r = subprocess.run([sys.executable, '-c', code, mean], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        import json
        return json.loads(r.stdout.strip().splitlines()[-1])

    # BN-typical regime: the two forms agree to float tolerance
    outs = {flag: run(flag, '7') for flag in ('0', '1')}
    for k in ('y', 'dx', 'dg'):
        np.testing.assert_allclose(np.array(outs['1'][k]),
                                   np.array(outs['0'][k]),
                                   rtol=2e-5, atol=2e-5, err_msg=k)

    # catastrophic-cancellation regime (mean >> std): BOTH f32 forms
    # carry rounding error vs a float64 oracle here — the shifted-pivot
    # one-pass must be at least as accurate as the two-pass jnp.var
    np.random.seed(0)
    x64 = (np.random.randn(4, 6, 5, 5) * 3 + 10000).astype(np.float32) \
        .astype(np.float64)
    g64 = (np.random.rand(6).astype(np.float32) + 0.5).astype(np.float64)
    b64 = np.random.randn(6).astype(np.float32).astype(np.float64)
    mean64 = x64.mean(axis=(0, 2, 3))
    var64 = x64.var(axis=(0, 2, 3))
    y64 = (x64 - mean64[None, :, None, None]) * \
        (g64 / np.sqrt(var64 + 1e-3))[None, :, None, None] + \
        b64[None, :, None, None]
    outs = {flag: run(flag, '10000') for flag in ('0', '1')}
    err1 = np.abs(np.array(outs['1']['y']) - y64).max()
    err0 = np.abs(np.array(outs['0']['y']) - y64).max()
    assert err1 <= err0 * 1.5 + 1e-6, (err1, err0)
