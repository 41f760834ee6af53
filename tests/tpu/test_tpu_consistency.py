"""TPU-vs-CPU consistency tier (reference tests/python/gpu/
test_operator_gpu.py pattern: run one symbol on both backends and
cross-compare outputs and gradients via check_consistency).

Needs the chip beside the host CPU: MXTPU_TEST_TPU=1 makes tests/conftest.py
leave both platforms visible, and the ``chip`` fixture below (used by every
test of this file) skips when a TPU device is not there. The device is asked
for inside that fixture, never while this file is imported, so every xdist
worker collects the same tests. Run on the machine with the chip:

    MXTPU_TEST_TPU=1 python -m pytest tests/tpu -q -p no:cacheprovider
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import check_consistency


@pytest.fixture(scope='module')
def chip():
    if os.environ.get('MXTPU_TEST_TPU') != '1':
        pytest.skip('TPU consistency tier: set MXTPU_TEST_TPU=1 on the '
                    'machine with the chip')
    import jax
    try:
        return jax.devices('tpu')[0]
    except RuntimeError as e:
        pytest.skip('no TPU device: %s' % e)


def _ctxs(shapes, dtype=np.float32):
    """cpu + tpu ctx specs for a dict of input shapes (fp32 on both)."""
    td = {k: dtype for k in shapes}
    return [dict(ctx=mx.cpu(), type_dict=dict(td), **shapes),
            dict(ctx=mx.tpu(), type_dict=dict(td), **shapes)]


def _v(name='data'):
    return mx.sym.Variable(name)


# (id, symbol builder, input shapes, kwargs for check_consistency)
SWEEP = [
    ('fc', lambda: mx.sym.FullyConnected(_v(), num_hidden=8, name='fc'),
     {'data': (4, 16)}, {}),
    ('fc_no_bias', lambda: mx.sym.FullyConnected(_v(), num_hidden=8,
                                                 no_bias=True, name='fc'),
     {'data': (4, 16)}, {}),
    ('conv_bn_relu', lambda: mx.sym.Activation(
        mx.sym.BatchNorm(mx.sym.Convolution(
            _v(), kernel=(3, 3), num_filter=8, pad=(1, 1), name='c'),
            name='bn'), act_type='relu'),
     {'data': (2, 4, 8, 8)}, {}),
    ('conv_strided', lambda: mx.sym.Convolution(
        _v(), kernel=(3, 3), num_filter=8, stride=(2, 2), name='c'),
     {'data': (2, 4, 9, 9)}, {}),
    ('conv_dilated', lambda: mx.sym.Convolution(
        _v(), kernel=(3, 3), num_filter=8, dilate=(2, 2), pad=(2, 2),
        name='c'),
     {'data': (2, 4, 8, 8)}, {}),
    ('conv_grouped', lambda: mx.sym.Convolution(
        _v(), kernel=(3, 3), num_filter=8, num_group=4, pad=(1, 1),
        name='c'),
     {'data': (2, 8, 8, 8)}, {}),
    ('conv1d', lambda: mx.sym.Convolution(
        _v(), kernel=(3,), num_filter=8, pad=(1,), name='c'),
     {'data': (2, 4, 16)}, {}),
    ('deconv', lambda: mx.sym.Deconvolution(
        _v(), kernel=(4, 4), num_filter=6, stride=(2, 2), pad=(1, 1),
        name='dc'),
     {'data': (2, 4, 7, 7)}, {}),
    ('pool_max', lambda: mx.sym.Pooling(
        _v(), kernel=(2, 2), stride=(2, 2), pool_type='max'),
     {'data': (2, 3, 8, 8)}, {}),
    ('pool_avg', lambda: mx.sym.Pooling(
        _v(), kernel=(3, 3), stride=(2, 2), pad=(1, 1), pool_type='avg'),
     {'data': (2, 3, 9, 9)}, {}),
    ('pool_global', lambda: mx.sym.Pooling(
        _v(), kernel=(1, 1), global_pool=True, pool_type='avg'),
     {'data': (2, 3, 8, 8)}, {}),
    ('softmax_out', lambda: mx.sym.SoftmaxOutput(
        mx.sym.flatten(_v()), name='sm'),
     {'data': (2, 3, 8, 8)}, {}),
    ('log_softmax', lambda: mx.sym.log_softmax(_v(), axis=-1),
     {'data': (4, 10)}, {}),
    ('layernorm', lambda: mx.sym.LayerNorm(_v(), name='ln'),
     {'data': (4, 16)}, {}),
    ('instancenorm', lambda: mx.sym.InstanceNorm(_v(), name='in'),
     {'data': (2, 4, 6, 6)}, {}),
    ('l2norm', lambda: mx.sym.L2Normalization(_v()),
     {'data': (4, 16)}, {}),
    ('leaky_elu', lambda: mx.sym.LeakyReLU(_v(), act_type='elu'),
     {'data': (4, 16)}, {}),
    ('act_tanh_sigmoid', lambda: mx.sym.Activation(
        mx.sym.Activation(_v(), act_type='tanh'), act_type='sigmoid'),
     {'data': (4, 16)}, {}),
    ('embedding', lambda: mx.sym.Embedding(
        _v(), input_dim=20, output_dim=8, name='emb'),
     {'data': (4, 6)}, {'grad_req': 'null'}),
    ('batch_dot', lambda: mx.sym.batch_dot(
        mx.sym.slice_axis(_v(), axis=1, begin=0, end=4),
        mx.sym.slice_axis(_v(), axis=1, begin=4, end=8),
        transpose_b=True),
     {'data': (2, 8, 5)}, {}),
    ('reduce_mix', lambda: mx.sym.sum(
        mx.sym.mean(_v(), axis=2, keepdims=True), axis=1),
     {'data': (3, 4, 5, 6)}, {}),
    ('transpose_reshape', lambda: mx.sym.reshape(
        mx.sym.transpose(_v(), axes=(0, 2, 3, 1)), shape=(0, -1)),
     {'data': (2, 3, 4, 5)}, {}),
    ('upsampling', lambda: mx.sym.UpSampling(
        _v(), scale=2, sample_type='nearest'),
     {'data': (2, 3, 5, 5)}, {}),
    ('clip_abs', lambda: mx.sym.clip(mx.sym.abs(_v()), 0.1, 0.8),
     {'data': (5, 3, 4)}, {}),
    ('seq_mask', lambda: mx.sym.SequenceMask(
        _v(), use_sequence_length=False, value=0.0),
     {'data': (5, 3, 4)}, {}),
    ('ctc', lambda: mx.sym.contrib.CTCLoss(
        _v(), mx.sym.slice_axis(mx.sym.slice_axis(mx.sym.clip(
            mx.sym.reshape(mx.sym.Variable('data'), shape=(12, 5)),
            0, 3), axis=0, begin=0, end=2), axis=1, begin=0, end=2),
        name='ctc'),
     {'data': (6, 2, 5)}, {'grad_req': 'null'}),
    ('smooth_l1', lambda: mx.sym.smooth_l1(_v(), scalar=1.0),
     {'data': (4, 9)}, {}),
    ('topk_argmax', lambda: mx.sym.topk(_v(), k=3, axis=-1),
     {'data': (4, 10)}, {'grad_req': 'null'}),
    ('rnn_lstm', lambda: mx.sym.RNN(
        _v(), state_size=8, num_layers=1, mode='lstm', name='rnn'),
     {'data': (5, 2, 6)}, {'tol': {np.float32: 2e-3}}),
    ('dot', lambda: mx.sym.dot(
        mx.sym.slice_axis(_v(), axis=0, begin=0, end=4),
        mx.sym.slice_axis(_v(), axis=0, begin=4, end=8),
        transpose_b=True),
     {'data': (8, 12)}, {}),
]


@pytest.mark.parametrize('name,build,shapes,kw',
                         SWEEP, ids=[c[0] for c in SWEEP])
def test_op_consistency(chip, name, build, shapes, kw):
    check_consistency(build(), _ctxs(shapes), **kw)


# bf16-on-TPU vs fp32-on-CPU: the production mixed-precision numerics.
BF16_SWEEP = ['fc', 'conv_bn_relu', 'pool_avg', 'layernorm', 'log_softmax']


@pytest.mark.parametrize('name', BF16_SWEEP)
def test_bf16_tpu_vs_fp32_cpu(chip, name):
    case = {c[0]: c for c in SWEEP}[name]
    _, build, shapes, kw = case
    import jax
    import jax.numpy as jnp
    ctxs = [dict(ctx=mx.cpu(),
                 type_dict={k: np.float32 for k in shapes}, **shapes),
            dict(ctx=mx.tpu(),
                 type_dict={k: jnp.bfloat16 for k in shapes}, **shapes)]
    kw = dict(kw)
    kw.pop('tol', None)
    # production bench/serving runs MXU-rate bf16 matmuls; the harness
    # conftest forces full-f32 matmul precision for finite-difference
    # tests, so undo it here to compare the real production numerics
    with jax.default_matmul_precision('bfloat16'):
        check_consistency(build(), ctxs, **kw)


# ---------------------------------------------------------------------------
# Pallas kernels compiled FOR REAL on the chip vs their jnp oracles.
# Interpret mode on the CPU mesh does not enforce Mosaic's block rules
# (a transformer bench once failed lowering on a CPU-green kernel), so these
# cases make every tier capture a hardware-lowering proof — including
# the awkward shapes that take the _pad_and_block padding paths.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('Tq,blk', [(128, 128), (28, 8)],
                         ids=['aligned', 'padded_q'])
def test_pallas_flash_attention_on_chip(chip, Tq, blk):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention, _flash_ref
    rng = np.random.RandomState(0)
    mk = lambda: jax.device_put(  # noqa: E731
        jnp.asarray(rng.randn(2, Tq, 2, 16), jnp.float32),
        mx.tpu().jax_device())
    q, k, v = (mk() for _ in range(3))
    for causal in (False, True):
        out = flash_attention(q, k, v, causal, None, blk, blk)
        ref = _flash_ref(q, k, v, causal, 16 ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize('kernel', ['rmsnorm', 'layernorm', 'softmax',
                                    'xent'])
def test_pallas_row_kernels_on_chip(chip, kernel):
    """fused row kernels at N=1006 (= 2*503, the row-padding path)
    compiled on hardware vs jnp oracles — one verdict per kernel."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    rng = np.random.RandomState(1)
    dev = mx.tpu().jax_device()
    x = jax.device_put(jnp.asarray(rng.randn(1006, 128), jnp.float32), dev)
    x32 = np.asarray(x)
    e = np.exp(x32 - x32.max(-1, keepdims=True))

    if kernel == 'rmsnorm':
        g = jax.device_put(jnp.ones((128,), jnp.float32), dev)
        got = np.asarray(pk.fused_rmsnorm(x, g))
        want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6)
    elif kernel == 'layernorm':
        g = jax.device_put(jnp.ones((128,), jnp.float32), dev)
        b = jax.device_put(jnp.zeros((128,), jnp.float32), dev)
        got = np.asarray(pk.fused_layernorm(x, g, b))
        mu = x32.mean(-1, keepdims=True)
        want = (x32 - mu) / np.sqrt(
            ((x32 - mu) ** 2).mean(-1, keepdims=True) + 1e-5)
    elif kernel == 'softmax':
        got = np.asarray(pk.fused_softmax(x))
        want = e / e.sum(-1, keepdims=True)
    else:
        labels = jax.device_put(
            jnp.asarray(rng.randint(0, 128, (1006,)), jnp.int32), dev)
        got = np.asarray(pk.softmax_xent(x, labels))
        lse = np.log(e.sum(-1)) + x32.max(-1)
        want = lse - x32[np.arange(1006), np.asarray(labels)]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_stem_s2d_on_chip(chip):
    """The space-to-depth stem rewrite (ops/nn.py _conv2d_stem_s2d)
    lowers and matches the plain strided conv ON HARDWARE — bf16, the
    ResNet/AlexNet/Inception stem geometries. Calls the kernels
    directly so no process-level flag flip is needed."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _conv2d_stem_s2d, _channels_last_conv

    tpu = [d for d in jax.devices() if d.platform == 'tpu'][0]
    rng = np.random.RandomState(0)
    cases = [((2, 3, 64, 64), (8, 3, 7, 7), (2, 2), (3, 3)),
             ((2, 3, 67, 67), (8, 3, 11, 11), (4, 4), (2, 2)),
             ((2, 3, 65, 65), (8, 3, 3, 3), (2, 2), (0, 0))]
    for ishape, wshape, stride, pad in cases:
        x = jax.device_put(
            jnp.asarray(rng.randn(*ishape), jnp.bfloat16), tpu)
        w = jax.device_put(
            jnp.asarray(rng.randn(*wshape) * 0.1, jnp.bfloat16), tpu)

        def plain(x, w):
            return jnp.sum(_channels_last_conv(
                x, w, 'OI', window_strides=stride,
                padding=[(p, p) for p in pad], rhs_dilation=(1, 1),
                feature_group_count=1).astype(jnp.float32))

        def s2d(x, w):
            return jnp.sum(
                _conv2d_stem_s2d(x, w, stride, pad).astype(jnp.float32))

        va, (gxa, gwa) = jax.jit(jax.value_and_grad(plain, (0, 1)))(x, w)
        vb, (gxb, gwb) = jax.jit(jax.value_and_grad(s2d, (0, 1)))(x, w)
        # the host fetch is the barrier
        va, vb = float(np.asarray(va)), float(np.asarray(vb))
        np.testing.assert_allclose(va, vb, rtol=2e-2,
                                   err_msg=str((ishape, wshape)))
        np.testing.assert_allclose(
            np.asarray(gxa, np.float32), np.asarray(gxb, np.float32),
            rtol=0.1, atol=0.05, err_msg=str((ishape, wshape)))
        np.testing.assert_allclose(
            np.asarray(gwa, np.float32), np.asarray(gwb, np.float32),
            rtol=0.1, atol=0.5, err_msg=str((ishape, wshape)))


def test_device_augment_on_chip(chip, tmp_path):
    """Round-5 device-augment upload path on the real chip: uint8 batch
    ships to the TPU, the jitted crop/mirror/normalize runs there, and
    the result matches the host-augmented CPU pipeline exactly with
    randomness off (same .rec, same math, different execution site)."""
    import jax
    from mxnet_tpu.recordio import MXRecordIO, IRHeader, pack_img
    rng = np.random.RandomState(0)
    p = str(tmp_path / 'aug.rec')
    rec = MXRecordIO(p, 'w')
    for i in range(16):
        img = (rng.rand(40, 40, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img,
                           img_fmt='.raw'))
    rec.close()
    kw = dict(data_shape=(3, 32, 32), batch_size=8, preprocess_threads=2,
              prefetch_buffer=2, mean_r=11, mean_g=17, mean_b=23,
              std_r=2, std_g=3, std_b=4, scale=0.5, label_name='l')
    host = mx.io.ImageRecordIter(p, **kw, device_augment=0)
    host.reset()
    want = host.next().data[0].asnumpy()
    with mx.gpu():   # maps to the TPU device in this build
        dev = mx.io.ImageRecordIter(p, **kw, device_augment=1)
        dev.reset()
        got_nd = dev.next().data[0]
    assert got_nd._data.devices() == {jax.devices('tpu')[0]}, \
        got_nd._data.devices()
    np.testing.assert_allclose(got_nd.asnumpy(), want,
                               rtol=1e-3, atol=1e-3)

    # randomized mode runs on-chip without error and stays in range
    with mx.gpu():
        it = mx.io.ImageRecordIter(p, **kw, device_augment=1,
                                   rand_crop=1, rand_mirror=1)
        it.reset()
        arr = it.next().data[0].asnumpy()
    assert np.isfinite(arr).all()
