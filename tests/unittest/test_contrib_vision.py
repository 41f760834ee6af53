"""Contrib vision/quantization ops.

Reference: tests/python/unittest/test_operator.py (deformable conv /
PSROIPooling entries), tests/python/unittest/test_contrib_operator.py
(proposal/multibox), and the quantize pair from
src/operator/contrib/quantize-inl.h.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
import mxnet_tpu.autograd as ag


class TestDeformableConvolution:
    def test_zero_offset_matches_convolution(self):
        rng = np.random.RandomState(0)
        data = nd.array(rng.randn(2, 4, 8, 8).astype(np.float32))
        weight = nd.array(rng.randn(6, 4, 3, 3).astype(np.float32))
        bias = nd.array(rng.randn(6).astype(np.float32))
        offset = nd.zeros((2, 18, 8, 8))
        out_def = nd.contrib.DeformableConvolution(
            data, offset, weight, bias, kernel=(3, 3), pad=(1, 1),
            num_filter=6)
        out_conv = nd.Convolution(data, weight, bias, kernel=(3, 3),
                                  pad=(1, 1), num_filter=6)
        np.testing.assert_allclose(out_def.asnumpy(), out_conv.asnumpy(),
                                   atol=1e-4)

    def test_integer_offset_shifts_sampling(self):
        """Offset (+1, +1) at every tap == conv over the shifted image."""
        rng = np.random.RandomState(1)
        data_np = rng.randn(1, 2, 8, 8).astype(np.float32)
        weight = nd.array(rng.randn(3, 2, 3, 3).astype(np.float32))
        off = np.ones((1, 18, 8, 8), np.float32)  # dy=dx=1 everywhere
        out_def = nd.contrib.DeformableConvolution(
            nd.array(data_np), nd.array(off), weight, None, kernel=(3, 3),
            pad=(1, 1), num_filter=3, no_bias=True)
        shifted = np.zeros_like(data_np)
        shifted[:, :, :-1, :-1] = data_np[:, :, 1:, 1:]
        out_ref = nd.Convolution(nd.array(shifted), weight, None,
                                 kernel=(3, 3), pad=(1, 1), num_filter=3,
                                 no_bias=True)
        # away from the top/left border the two agree exactly; at that
        # border the shifted-conv sees conv zero-padding where deformable
        # sampling still reads real row/col 0
        np.testing.assert_allclose(out_def.asnumpy()[:, :, 1:, 1:],
                                   out_ref.asnumpy()[:, :, 1:, 1:],
                                   atol=1e-4)

    def test_stride_and_groups(self):
        rng = np.random.RandomState(2)
        data = nd.array(rng.randn(1, 4, 9, 9).astype(np.float32))
        weight = nd.array(rng.randn(4, 2, 3, 3).astype(np.float32))
        offset = nd.zeros((1, 18, 4, 4))
        out = nd.contrib.DeformableConvolution(
            data, offset, weight, None, kernel=(3, 3), stride=(2, 2),
            num_filter=4, num_group=2, no_bias=True)
        ref = nd.Convolution(data, weight, None, kernel=(3, 3),
                             stride=(2, 2), num_filter=4, num_group=2,
                             no_bias=True)
        np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(), atol=1e-4)

    def test_gradients_flow(self):
        rng = np.random.RandomState(3)
        data = nd.array(rng.randn(1, 2, 6, 6).astype(np.float32))
        weight = nd.array(rng.randn(2, 2, 3, 3).astype(np.float32))
        offset = nd.array(0.3 * rng.randn(1, 18, 6, 6).astype(np.float32))
        for v in (data, weight, offset):
            v.attach_grad()
        with ag.record():
            y = nd.contrib.DeformableConvolution(
                data, offset, weight, None, kernel=(3, 3), pad=(1, 1),
                num_filter=2, no_bias=True)
            loss = (y * y).sum()
        loss.backward()
        for v in (data, weight, offset):
            assert float((v.grad ** 2).sum().asnumpy()) > 0

    def test_deformable_groups(self):
        rng = np.random.RandomState(4)
        data = nd.array(rng.randn(1, 4, 6, 6).astype(np.float32))
        weight = nd.array(rng.randn(2, 4, 3, 3).astype(np.float32))
        offset = nd.zeros((1, 2 * 18, 6, 6))  # num_deformable_group=2
        out = nd.contrib.DeformableConvolution(
            data, offset, weight, None, kernel=(3, 3), pad=(1, 1),
            num_filter=2, num_deformable_group=2, no_bias=True)
        ref = nd.Convolution(data, weight, None, kernel=(3, 3), pad=(1, 1),
                             num_filter=2, no_bias=True)
        np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(), atol=1e-4)


class TestDeformablePSROIPooling:
    def test_constant_map_pools_constant(self):
        # each position-sensitive channel constant → output equals that
        # channel's constant for the matching bin
        out_dim, gs, ps = 2, 2, 2
        C = out_dim * gs * gs
        data = np.zeros((1, C, 8, 8), np.float32)
        for c in range(C):
            data[0, c] = float(c)
        rois = nd.array(np.array([[0, 0, 0, 7, 7]], np.float32))
        out = nd.contrib.DeformablePSROIPooling(
            nd.array(data), rois, nd.zeros((1, 2, ps, ps)),
            spatial_scale=1.0, output_dim=out_dim, group_size=gs,
            pooled_size=ps, no_trans=True)
        got = out.asnumpy()[0]
        assert got.shape == (out_dim, ps, ps)
        # channel layout: (c*gs + gy)*gs + gx
        for c in range(out_dim):
            for gy in range(gs):
                for gx in range(gs):
                    assert got[c, gy, gx] == pytest.approx(
                        (c * gs + gy) * gs + gx, abs=1e-5)

    def test_trans_offsets_move_sampling(self):
        out_dim, gs, ps = 1, 1, 2
        data = np.zeros((1, 1, 8, 8), np.float32)
        data[0, 0, :, 4:] = 1.0  # right half ones
        rois = nd.array(np.array([[0, 0, 0, 7, 7]], np.float32))
        base = nd.contrib.DeformablePSROIPooling(
            nd.array(data), rois, nd.zeros((1, 2, ps, ps)),
            spatial_scale=1.0, output_dim=out_dim, group_size=gs,
            pooled_size=ps, no_trans=True).asnumpy()
        # push sampling right: x-offset (channel 1) positive → the left
        # bins (over the zero half) now reach into the ones region
        trans = np.zeros((1, 2, ps, ps), np.float32)
        trans[0, 1] = 1.0
        moved = nd.contrib.DeformablePSROIPooling(
            nd.array(data), rois, nd.array(trans),
            spatial_scale=1.0, output_dim=out_dim, group_size=gs,
            pooled_size=ps, sample_per_part=2, trans_std=0.25,
            no_trans=False).asnumpy()
        assert moved[0, 0, 0, 0] > base[0, 0, 0, 0]
        assert moved[0, 0, 1, 0] > base[0, 0, 1, 0]


class TestMultiProposal:
    def _inputs(self, N=2, FH=4, FW=4, A=12, seed=0):
        rng = np.random.RandomState(seed)
        cls = rng.rand(N, 2 * A, FH, FW).astype(np.float32)
        bbox = (0.1 * rng.randn(N, 4 * A, FH, FW)).astype(np.float32)
        info = np.tile(np.array([64, 64, 1.0], np.float32), (N, 1))
        return nd.array(cls), nd.array(bbox), nd.array(info)

    def test_output_shape_and_batch_index(self):
        cls, bbox, info = self._inputs()
        rois = nd.contrib.MultiProposal(cls, bbox, info,
                                        rpn_pre_nms_top_n=50,
                                        rpn_post_nms_top_n=10,
                                        rpn_min_size=4)
        out = rois.asnumpy()
        assert out.shape == (20, 5)
        assert (out[:10, 0] == 0).all() and (out[10:, 0] == 1).all()

    def test_boxes_clipped_to_image(self):
        cls, bbox, info = self._inputs(seed=1)
        out = nd.contrib.MultiProposal(cls, bbox, info,
                                       rpn_pre_nms_top_n=50,
                                       rpn_post_nms_top_n=10,
                                       rpn_min_size=4).asnumpy()
        boxes = out[:, 1:]
        assert (boxes >= 0).all() and (boxes <= 63).all()
        # non-degenerate: coordinates ordered for filled rows
        filled = boxes.sum(axis=1) > 0
        assert (boxes[filled, 2] >= boxes[filled, 0]).all()
        assert (boxes[filled, 3] >= boxes[filled, 1]).all()

    def test_output_score(self):
        cls, bbox, info = self._inputs(seed=2)
        rois, scores = nd.contrib.MultiProposal(
            cls, bbox, info, rpn_pre_nms_top_n=50, rpn_post_nms_top_n=10,
            rpn_min_size=4, output_score=True)
        assert rois.shape == (20, 5)
        assert scores.shape == (20, 1)
        # scores come out sorted (descending) per image among filled slots
        s = scores.asnumpy().reshape(2, 10)
        for i in range(2):
            filled = s[i] > 0
            vals = s[i][filled]
            assert (np.diff(vals) <= 1e-6).all()

    def test_nms_suppresses_duplicates(self):
        # identical anchors decoding to identical boxes: only one survives
        A = 12
        cls = np.zeros((1, 2 * A, 2, 2), np.float32)
        cls[0, A:] = 0.9  # all fg scores equal
        bbox = np.zeros((1, 4 * A, 2, 2), np.float32)
        info = np.array([[64, 64, 1.0]], np.float32)
        out = nd.contrib.MultiProposal(
            nd.array(cls), nd.array(bbox), nd.array(info),
            rpn_pre_nms_top_n=48, rpn_post_nms_top_n=48, rpn_min_size=1,
            threshold=0.7).asnumpy()
        filled = out[:, 1:].sum(axis=1) > 0
        # 48 anchors over a 2x2 grid with many duplicates/IoU>0.7 overlaps:
        # NMS must cut the survivor count well below pre-NMS count
        assert 0 < filled.sum() < 48


class TestQuantize:
    def test_uint8_roundtrip(self):
        rng = np.random.RandomState(0)
        x = rng.uniform(-1, 1, (3, 4)).astype(np.float32)
        mn = nd.array(np.array([-1.0], np.float32))
        mx_ = nd.array(np.array([1.0], np.float32))
        q, qmin, qmax = nd.contrib.quantize(nd.array(x), mn, mx_,
                                            out_type='uint8')
        assert q.dtype == np.uint8
        assert float(qmin.asnumpy()) == -1.0
        assert float(qmax.asnumpy()) == 1.0
        deq = nd.contrib.dequantize(q, qmin, qmax, out_type='float32')
        np.testing.assert_allclose(deq.asnumpy(), x, atol=2.0 / 255 + 1e-6)

    def test_int8(self):
        x = nd.array(np.array([[-1.0, 0.0, 1.0]], np.float32))
        mn = nd.array(np.array([-1.0], np.float32))
        mx_ = nd.array(np.array([1.0], np.float32))
        q, _, _ = nd.contrib.quantize(x, mn, mx_, out_type='int8')
        assert q.dtype == np.int8
        got = q.asnumpy().ravel()
        assert got[0] == -128 and got[2] == 127

    def test_extremes_map_to_limits(self):
        x = nd.array(np.array([0.0, 255.0], np.float32))
        mn = nd.array(np.array([0.0], np.float32))
        mx_ = nd.array(np.array([255.0], np.float32))
        q, _, _ = nd.contrib.quantize(x, mn, mx_)
        got = q.asnumpy()
        assert got[0] == 0 and got[1] == 255


class TestSymbolIntegration:
    def test_deformable_conv_in_symbol_graph(self):
        data = mx.sym.Variable('data')
        offset = mx.sym.Variable('offset')
        out = mx.sym.contrib.DeformableConvolution(
            data=data, offset=offset, kernel=(3, 3), pad=(1, 1),
            num_filter=4, name='dconv')
        args = sorted(out.list_arguments())
        assert 'dconv_weight' in args and 'dconv_bias' in args
        arg_shapes, out_shapes, _ = out.infer_shape(data=(1, 2, 8, 8),
                                                    offset=(1, 18, 8, 8))
        assert out_shapes[0] == (1, 4, 8, 8)


class TestPSROIPooling:
    def test_position_sensitive_channel_selection(self):
        out_dim, gs, ps = 2, 2, 2
        C = out_dim * gs * gs
        data = np.zeros((1, C, 8, 8), np.float32)
        for c in range(C):
            data[0, c] = float(c)
        rois = nd.array(np.array([[0, 0, 0, 7, 7]], np.float32))
        out = nd.contrib.PSROIPooling(nd.array(data), rois,
                                      spatial_scale=1.0, output_dim=out_dim,
                                      pooled_size=ps, group_size=gs)
        np.testing.assert_allclose(out.asnumpy().ravel(),
                                   np.arange(C, dtype=np.float32))

    def test_bin_averages_pixels(self):
        # one channel, known values: top-left bin of a 4x4 roi over an
        # 4x4 image with ps=2 averages the top-left 2x2 block
        data = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
        rois = nd.array(np.array([[0, 0, 0, 3, 3]], np.float32))
        out = nd.contrib.PSROIPooling(nd.array(data), rois,
                                      spatial_scale=1.0, output_dim=1,
                                      pooled_size=2, group_size=1).asnumpy()
        assert out[0, 0, 0, 0] == pytest.approx(np.mean([0, 1, 4, 5]))
        assert out[0, 0, 1, 1] == pytest.approx(np.mean([10, 11, 14, 15]))


class TestIdentityAttachKLSparseReg:
    def test_forward_identity_backward_penalty(self):
        import mxnet_tpu.autograd as ag2
        x = nd.array(np.random.RandomState(1).rand(4, 3).astype(np.float32)
                     * 0.5)
        moving = nd.array(np.full(3, 0.2, np.float32))
        x.attach_grad()
        with ag2.record():
            y = nd.IdentityAttachKLSparseReg(
                x, moving, sparseness_target=0.1, penalty=0.01, momentum=0.9)
            loss = y.sum()
        loss.backward()
        np.testing.assert_allclose(y.asnumpy(), x.asnumpy())
        m = moving.asnumpy()  # updated in-place via the aux protocol
        want = 1 + 0.01 * (-0.1 / m + 0.9 / (1 - m))
        np.testing.assert_allclose(x.grad.asnumpy(),
                                   np.broadcast_to(want, (4, 3)), rtol=1e-5)

    def test_moving_average_momentum(self):
        import mxnet_tpu.autograd as ag2
        x = nd.array(np.full((4, 3), 0.5, np.float32))
        moving = nd.array(np.full(3, 0.2, np.float32))
        x.attach_grad()
        with ag2.record():
            y = nd.IdentityAttachKLSparseReg(x, moving, momentum=0.9)
        np.testing.assert_allclose(moving.asnumpy(),
                                   0.9 * 0.2 + 0.1 * 0.5, rtol=1e-6)
