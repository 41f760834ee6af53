"""Streaming ImageRecordIter (io/image_record.py).

Reference behaviors under test (src/io/iter_image_recordio_2.cc +
image_aug_default.cc + iter_prefetcher.h): per-image rand_crop /
rand_mirror (not per-batch), honored preprocess_threads, bounded
prefetch (dataset never resident), shuffle-is-permutation, round_batch
padding, num_parts sharding, and reproducibility under mx.random.seed.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mio
from mxnet_tpu.recordio import MXRecordIO, IRHeader, pack_img
from mxnet_tpu.io.image_record import scan_record_offsets


def _write_rec(path, n, hw=12, seed=0, encode='.raw', labeler=None):
    rng = np.random.RandomState(seed)
    rec = MXRecordIO(path, 'w')
    imgs = []
    for i in range(n):
        img = (rng.rand(hw, hw, 3) * 255).astype(np.uint8)
        imgs.append(img)
        lab = float(labeler(i) if labeler else i % 7)
        rec.write(pack_img(IRHeader(0, lab, i, 0), img, img_fmt=encode))
    rec.close()
    return imgs


def test_offset_scan_counts_records(tmp_path):
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 17)
    assert len(scan_record_offsets(p)) == 17


def test_sequential_batches_and_values(tmp_path):
    """No shuffle/augment: batches reproduce the packed pixels exactly
    (scale/mean/std applied)."""
    p = str(tmp_path / 'a.rec')
    imgs = _write_rec(p, 8, hw=6)
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                             batch_size=4, scale=1.0 / 255)
    batches = list(it)
    assert len(batches) == 2
    got = batches[0].data[0].asnumpy()
    want = np.stack([im.transpose(2, 0, 1) for im in imgs[:4]]) / 255.0
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(batches[0].label[0].asnumpy(),
                               [0, 1, 2, 3], atol=0)


def test_round_batch_pad_wraps(tmp_path):
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 10, hw=6)
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                             batch_size=4, round_batch=True)
    batches = list(it)
    assert [b.pad for b in batches] == [0, 0, 2]
    # padded tail wraps to the head records
    np.testing.assert_allclose(batches[2].label[0].asnumpy()[-2:], [0, 1])
    it2 = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                              batch_size=4, round_batch=False)
    assert len(list(it2)) == 2


def test_rand_mirror_is_per_image(tmp_path):
    """The round-3 gap: one coin per BATCH is wrong; each image flips
    independently (image_aug_default.cc). With 32 images the chance of
    a uniform batch is 2^-31."""
    p = str(tmp_path / 'a.rec')
    imgs = _write_rec(p, 32, hw=6)
    mx.random.seed(5)
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                             batch_size=32, rand_mirror=True)
    got = next(iter(it)).data[0].asnumpy()
    flipped = []
    for i, im in enumerate(imgs):
        chw = im.transpose(2, 0, 1).astype(np.float32)
        if np.allclose(got[i], chw):
            flipped.append(False)
        elif np.allclose(got[i], chw[:, :, ::-1]):
            flipped.append(True)
        else:
            raise AssertionError('image %d is neither original nor '
                                 'mirrored' % i)
    assert any(flipped) and not all(flipped)


def test_rand_crop_is_per_image(tmp_path):
    """Each image draws its own crop offset: crops of a coordinate ramp
    differ across the batch."""
    p = str(tmp_path / 'a.rec')
    rec = MXRecordIO(p, 'w')
    ramp = np.tile(np.arange(16, dtype=np.uint8)[None, :, None] * 10,
                   (16, 1, 3))
    for i in range(16):
        rec.write(pack_img(IRHeader(0, float(i), i, 0), ramp,
                           img_fmt='.raw'))
    rec.close()
    mx.random.seed(11)
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 8, 8),
                             batch_size=16, rand_crop=True)
    got = next(iter(it)).data[0].asnumpy()
    # the x-offset of each crop is its first column value / 10
    offs = {int(round(got[i, 0, 0, 0] / 10)) for i in range(16)}
    assert len(offs) > 1, 'all crops identical — per-batch, not per-image'
    # without rand_crop: center crop for every image
    it2 = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 8, 8),
                              batch_size=16)
    got2 = next(iter(it2)).data[0].asnumpy()
    assert {int(round(got2[i, 0, 0, 0] / 10)) for i in range(16)} == {4}


def test_shuffle_is_seeded_permutation(tmp_path):
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 24, hw=6)
    mx.random.seed(3)
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                             batch_size=8, shuffle=True)
    labs = np.concatenate([b.label[0].asnumpy() for b in it])
    full = np.arange(24) % 7
    assert sorted(labs.tolist()) == sorted(full.tolist())
    assert not np.array_equal(labs, full)   # actually shuffled
    mx.random.seed(3)
    it2 = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                              batch_size=8, shuffle=True)
    labs2 = np.concatenate([b.label[0].asnumpy() for b in it2])
    np.testing.assert_allclose(labs, labs2)   # seed-reproducible


def test_num_parts_sharding(tmp_path):
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 12, hw=6, labeler=lambda i: i)
    seen = []
    for part in range(3):
        it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                                 batch_size=4, num_parts=3,
                                 part_index=part)
        seen.append(np.concatenate([b.label[0].asnumpy() for b in it]))
    allsee = sorted(np.concatenate(seen).tolist())
    assert allsee == list(range(12))
    assert seen[0].tolist() == [0, 3, 6, 9]


def test_reset_mid_epoch_and_reuse(tmp_path):
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 16, hw=6, labeler=lambda i: i)
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                             batch_size=4)
    next(it)
    it.reset()   # abandon a running producer mid-epoch
    labs = np.concatenate([b.label[0].asnumpy() for b in it])
    np.testing.assert_allclose(labs, np.arange(16))
    it.reset()
    assert len(list(it)) == 4


def test_preprocess_threads_honored_and_equal(tmp_path):
    """Thread count changes execution, not results."""
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 20, hw=6)
    outs = []
    for t in (1, 4):
        it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                                 batch_size=5, preprocess_threads=t)
        outs.append(np.concatenate([b.data[0].asnumpy() for b in it]))
    np.testing.assert_allclose(outs[0], outs[1])


def test_pad_and_fill_value(tmp_path):
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 4, hw=6)
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 10, 10),
                             batch_size=4, pad=2, fill_value=9)
    got = next(iter(it)).data[0].asnumpy()
    assert got.shape == (4, 3, 10, 10)
    np.testing.assert_allclose(got[:, :, 0, 0], 9.0)   # padded corner


def test_unsupported_augmenter_warns_once(tmp_path):
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 4, hw=6)
    with pytest.warns(UserWarning, match='max_rotate_angle'):
        mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                            batch_size=4, max_rotate_angle=10)


def test_jpeg_stream(tmp_path):
    pytest.importorskip('PIL')
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 6, hw=8, encode='.jpg')
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 8, 8),
                             batch_size=3)
    bs = list(it)
    assert len(bs) == 2 and bs[0].data[0].shape == (3, 3, 8, 8)


def test_decode_error_surfaces_to_consumer(tmp_path):
    """A corrupt record raises in the consumer thread, not silently in
    the producer."""
    p = str(tmp_path / 'a.rec')
    rec = MXRecordIO(p, 'w')
    rec.write(b'not-an-image-record')
    rec.close()
    it = mio.ImageRecordIter(path_imgrec=p, data_shape=(3, 6, 6),
                             batch_size=1)
    with pytest.raises(Exception):
        next(it)


# ---- device-augment mode (round 5: feed the chip) -------------------------

def _iter_kw(hw, batch, **kw):
    base = dict(data_shape=(3, hw, hw), batch_size=batch,
                preprocess_threads=2, prefetch_buffer=2)
    base.update(kw)
    return base


def test_device_augment_matches_host_path_deterministic(tmp_path):
    """With randomness off, the device path (uint8 ship + on-device
    center crop / normalize) must produce the host path's exact
    values — same math, different execution site."""
    import mxnet_tpu as mx
    p = str(tmp_path / 'a.rec')
    _write_rec(p, 8, hw=10)
    kw = dict(mean_r=11, mean_g=17, mean_b=23, std_r=2, std_g=3, std_b=4,
              scale=0.7, resize=8, label_name='l')
    host = mx.io.ImageRecordIter(
        p, **_iter_kw(6, 4, **kw), device_augment=0)
    dev = mx.io.ImageRecordIter(
        p, **_iter_kw(6, 4, **kw), device_augment=1)
    host.reset(); dev.reset()
    for _ in range(2):
        bh, bd = host.next(), dev.next()
        np.testing.assert_allclose(bd.data[0].asnumpy(),
                                   bh.data[0].asnumpy(),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(bd.label[0].asnumpy(),
                                      bh.label[0].asnumpy())
        assert bd.data[0].shape == (4, 3, 6, 6)
        assert str(bd.data[0].dtype) == 'float32'


def test_device_augment_rand_crop_mirror_properties(tmp_path):
    """Random crop/mirror on device: per-image variation, values drawn
    from the source image set, deterministic under mx.random.seed."""
    import mxnet_tpu as mx
    p = str(tmp_path / 'b.rec')
    _write_rec(p, 16, hw=12)
    kw = _iter_kw(8, 8, rand_crop=1, rand_mirror=1, resize=12,
                  label_name='l')

    def run():
        mx.random.seed(5)
        it = mx.io.ImageRecordIter(p, **kw, device_augment=1)
        it.reset()
        return it.next().data[0].asnumpy()

    a, b = run(), run()
    np.testing.assert_array_equal(a, b)   # seeded determinism
    # different crops across a batch of distinct random images: the 8
    # outputs must not all be identical slices of one another
    assert a.shape == (8, 3, 8, 8)
    assert len({arr.tobytes() for arr in a}) > 1


def test_device_augment_raw_fixed_records_no_resize(tmp_path):
    """RAW0 fixed-size records need no host resize: uniform sizes pass
    straight through; a non-uniform file errors with guidance."""
    import mxnet_tpu as mx
    p = str(tmp_path / 'c.rec')
    _write_rec(p, 8, hw=9)
    it = mx.io.ImageRecordIter(p, **_iter_kw(7, 4, label_name='l'),
                               device_augment=1)
    it.reset()
    b = it.next()
    assert b.data[0].shape == (4, 3, 7, 7)


def test_device_augment_feeds_module_fit(tmp_path):
    """End-to-end: ImageRecordIter(device_augment=1) drives Module.fit
    (the fused window when eligible) and the loss is finite."""
    import mxnet_tpu as mx
    p = str(tmp_path / 'd.rec')
    _write_rec(p, 32, hw=10, labeler=lambda i: i % 4)
    it = mx.io.ImageRecordIter(
        p, **_iter_kw(8, 8, rand_crop=1, rand_mirror=1, resize=10,
                      label_name='softmax_label'), device_augment=1)
    data = mx.sym.Variable('data')
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), name='c')
    net = mx.sym.Activation(net, act_type='relu')
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=4, name='fc')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    mod = mx.mod.Module(net, context=mx.cpu())
    accs = []
    mod.fit(it, num_epoch=2, optimizer='sgd',
            optimizer_params=(('learning_rate', 0.05),),
            eval_metric='acc',
            batch_end_callback=lambda prm: accs.append(
                prm.eval_metric.get_name_value()[0][1]))
    assert accs and all(np.isfinite(v) for v in accs)


def test_device_augment_nonsquare_and_undersized(tmp_path):
    """Non-square uniform records crop over each axis independently;
    undersized records are padded up to the crop like the host path."""
    import mxnet_tpu as mx
    from mxnet_tpu.recordio import MXRecordIO, IRHeader, pack_img
    # non-square 8x12 records, crop 7x7: x offsets must reach col 5
    p = str(tmp_path / 'ns.rec')
    rng = np.random.RandomState(0)
    rec = MXRecordIO(p, 'w')
    for i in range(16):
        img = (rng.rand(8, 12, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img,
                           img_fmt='.raw'))
    rec.close()
    mx.random.seed(3)
    it = mx.io.ImageRecordIter(p, **_iter_kw(7, 8, rand_crop=1,
                                             label_name='l'),
                               device_augment=1)
    it.reset()
    assert it.next().data[0].shape == (8, 3, 7, 7)

    # undersized 5x5 records, crop 7x7: padded with fill_value like the
    # host path (not an opaque dynamic_slice failure)
    q = str(tmp_path / 'small.rec')
    rec = MXRecordIO(q, 'w')
    for i in range(8):
        img = (rng.rand(5, 5, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img,
                           img_fmt='.raw'))
    rec.close()
    host = mx.io.ImageRecordIter(q, **_iter_kw(7, 4, label_name='l'),
                                 device_augment=0)
    dev = mx.io.ImageRecordIter(q, **_iter_kw(7, 4, label_name='l'),
                                device_augment=1)
    host.reset(); dev.reset()
    np.testing.assert_allclose(dev.next().data[0].asnumpy(),
                               host.next().data[0].asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_device_augment_grayscale_and_odd_parity_center_crop(tmp_path):
    """C=1 targets use only the first channel's mean/std (no 3-channel
    broadcast), and the composed host-square + device-center crop lands
    on the host path's exact pixels even at odd parities."""
    import mxnet_tpu as mx
    from mxnet_tpu.recordio import MXRecordIO, IRHeader, pack_img
    rng = np.random.RandomState(1)
    # odd-parity geometry: source 13x21 resized-short handled via
    # resize=13 -> S=13, crop 10: (21-13)//2=4 vs (21-10)//2 - (13-10)//2
    # = 5-1 = 4... pick sizes where naive differs: source h=13,w=20,
    # resize... use raw fixed-size path with resize set
    p = str(tmp_path / 'odd.rec')
    rec = MXRecordIO(p, 'w')
    for i in range(8):
        img = (rng.rand(15, 21, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img,
                           img_fmt='.raw'))
    rec.close()
    kw = dict(data_shape=(3, 10, 10), batch_size=4, preprocess_threads=2,
              prefetch_buffer=2, resize=13, mean_r=3, std_r=2,
              label_name='l')
    host = mx.io.ImageRecordIter(p, **kw, device_augment=0)
    dev = mx.io.ImageRecordIter(p, **kw, device_augment=1)
    host.reset(); dev.reset()
    np.testing.assert_allclose(dev.next().data[0].asnumpy(),
                               host.next().data[0].asnumpy(),
                               rtol=1e-5, atol=1e-5)

    # grayscale target: output must be (B, 1, H, W), matching host
    q = str(tmp_path / 'gray.rec')
    rec = MXRecordIO(q, 'w')
    for i in range(8):
        img = (rng.rand(9, 9, 3) * 255).astype(np.uint8)
        rec.write(pack_img(IRHeader(0, float(i), i, 0), img,
                           img_fmt='.raw'))
    rec.close()
    kw = dict(data_shape=(1, 8, 8), batch_size=4, preprocess_threads=2,
              prefetch_buffer=2, mean_r=7, std_r=3, label_name='l')
    host = mx.io.ImageRecordIter(q, **kw, device_augment=0)
    dev = mx.io.ImageRecordIter(q, **kw, device_augment=1)
    host.reset(); dev.reset()
    bh, bd = host.next(), dev.next()
    assert bd.data[0].shape == (4, 1, 8, 8)
    np.testing.assert_allclose(bd.data[0].asnumpy(), bh.data[0].asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_device_augment_spmd_fused_fit(tmp_path):
    """device_augment batches (device-resident f32) must stack and
    dp-shard correctly into the fused Module.fit window on a multi-
    device SPMD group, matching the host-augment path's training
    trajectory with randomness off."""
    import os
    import mxnet_tpu as mx
    from mxnet_tpu.module.executor_group import SPMDExecutorGroup
    from mxnet_tpu.module.fused_fit import FusedFitLoop

    p = str(tmp_path / 'spmd.rec')
    _write_rec(p, 64, hw=8, labeler=lambda i: i % 4)

    def run(device_augment):
        mx.random.seed(9)
        np.random.seed(9)
        it = mx.io.ImageRecordIter(
            p, **_iter_kw(8, 16, label_name='softmax_label'),
            device_augment=device_augment)
        data = mx.sym.Variable('data')
        net = mx.sym.Flatten(data)
        net = mx.sym.FullyConnected(net, num_hidden=4, name='fc')
        net = mx.sym.SoftmaxOutput(net, name='softmax')
        mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(8)])
        os.environ['MXTPU_FUSED_FIT'] = '1'
        try:
            mod.fit(it, num_epoch=2, optimizer='sgd',
                    optimizer_params=(('learning_rate', 0.1),),
                    kvstore='device', eval_metric='acc')
            # the behaviors under test must actually have engaged — a
            # silent eligibility fallback would test the reference loop
            assert isinstance(mod._exec_group, SPMDExecutorGroup)
            assert FusedFitLoop.build(
                mod, mx.metric.create('acc')) is not None
        finally:
            os.environ.pop('MXTPU_FUSED_FIT', None)
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    a_dev = run(1)
    a_host = run(0)
    for k in a_dev:
        np.testing.assert_allclose(a_dev[k], a_host[k], rtol=1e-5,
                                   atol=1e-5, err_msg=k)


def test_device_augment_deferred_into_fused_window(tmp_path):
    """When the fused fit loop drives a device-augment iterator, the
    augmentation is traced INSIDE the window program (defer mode: raw
    uint8 batches, zero per-batch aug dispatches). With
    randomness off the trajectory equals the unfused eager path
    exactly; tail batches (< window) materialize eagerly; the
    iterator's defer switch is always restored."""
    import os
    import mxnet_tpu as mx
    from mxnet_tpu.module.fused_fit import FusedFitLoop
    import mxnet_tpu.module.fused_fit as ff

    p = str(tmp_path / 'defer.rec')
    # 40 imgs / batch 4 = 10 batches: W=4 on cpu -> 2 windows + 2 tail
    _write_rec(p, 40, hw=8, labeler=lambda i: i % 4)

    def run(fused):
        mx.random.seed(11)
        np.random.seed(11)
        it = mx.io.ImageRecordIter(
            p, **_iter_kw(8, 4, label_name='softmax_label'),
            device_augment=1)
        data = mx.sym.Variable('data')
        net = mx.sym.Flatten(data)
        net = mx.sym.FullyConnected(net, num_hidden=4, name='fc')
        net = mx.sym.SoftmaxOutput(net, name='softmax')
        mod = mx.mod.Module(net, context=mx.cpu())
        os.environ['MXTPU_FUSED_FIT'] = '1' if fused else '0'
        try:
            mod.fit(it, num_epoch=2, optimizer='sgd',
                    optimizer_params=(('learning_rate', 0.1),),
                    kvstore='local', eval_metric='acc')
        finally:
            os.environ.pop('MXTPU_FUSED_FIT', None)
        return mod, it

    mod_f, it_f = run(True)
    # defer engaged: the cached loop compiled a defer-mode program
    _, loop = mod_f.__dict__['_fused_fit_cache']
    assert any(k[2] for k in loop._programs), list(loop._programs)
    # ...exactly one program across both epochs (reuse, no retrace)
    assert len(loop._programs) == 1
    # switch restored for other consumers of the iterator
    assert it_f._defer_aug is False
    # eager batches augment again after the fit (f32 CHW, not uint8)
    it_f.reset()
    b = next(iter(it_f))
    assert str(b.data[0].dtype) == 'float32'
    assert b.data[0].shape[1:] == (3, 8, 8)

    mod_u, _ = run(False)
    a_f = {k: v.asnumpy() for k, v in mod_f.get_params()[0].items()}
    a_u = {k: v.asnumpy() for k, v in mod_u.get_params()[0].items()}
    assert a_f.keys() == a_u.keys()
    for k in a_f:
        np.testing.assert_allclose(a_f[k], a_u[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_defer_program_keyed_by_aug_config(tmp_path):
    """Two device-augment iterators with EQUAL batch shapes but
    different normalization must not share a compiled defer window:
    the aug math is baked into the program, so the program key carries
    device_aug_signature()."""
    import os
    import mxnet_tpu as mx

    p = str(tmp_path / 'sig.rec')
    _write_rec(p, 32, hw=8, labeler=lambda i: i % 4)

    def build_mod():
        data = mx.sym.Variable('data')
        net = mx.sym.Flatten(data)
        net = mx.sym.FullyConnected(net, num_hidden=4, name='fc')
        net = mx.sym.SoftmaxOutput(net, name='softmax')
        return mx.mod.Module(net, context=mx.cpu())

    os.environ['MXTPU_FUSED_FIT'] = '1'
    try:
        mod = build_mod()
        kw = dict(_iter_kw(8, 8, label_name='softmax_label'),
                  device_augment=1)
        it_a = mx.io.ImageRecordIter(p, **kw)
        it_b = mx.io.ImageRecordIter(p, mean_r=100., std_r=7., **kw)
        assert it_a.device_aug_signature() != it_b.device_aug_signature()
        fit_kw = dict(optimizer='sgd',
                      optimizer_params=(('learning_rate', 0.1),),
                      kvstore='local', eval_metric='acc')
        mod.fit(it_a, num_epoch=1, **fit_kw)
        _, loop = mod.__dict__['_fused_fit_cache']
        assert len(loop._programs) == 1
        mod.fit(it_b, num_epoch=2, begin_epoch=1, **fit_kw)
        _, loop2 = mod.__dict__['_fused_fit_cache']
        assert loop2 is loop            # loop reused (module unchanged)
        assert len(loop._programs) == 2  # ...but a FRESH aug program
        keys = list(loop._programs)
        assert keys[0][2] != keys[1][2]
    finally:
        os.environ.pop('MXTPU_FUSED_FIT', None)


def test_host_crop_matches_device_crop_deterministic(tmp_path):
    """host_crop=1 (workers crop to HxW before handover — 23% fewer
    upload bytes for 224^2-from-256^2) must produce the device-crop
    path's exact values with randomness off: the center-crop formulas
    are shared, only the execution site moves."""
    import mxnet_tpu as mx
    p = str(tmp_path / 'hc.rec')
    _write_rec(p, 8, hw=10)
    kw = dict(mean_r=3, mean_g=5, mean_b=7, std_r=2, std_g=3, std_b=4,
              scale=0.5, label_name='l')
    a = mx.io.ImageRecordIter(p, **_iter_kw(6, 4, **kw),
                              device_augment=1, host_crop=1)
    b = mx.io.ImageRecordIter(p, **_iter_kw(6, 4, **kw),
                              device_augment=1, host_crop=0)
    a.reset(); b.reset()
    for _ in range(2):
        ba, bb = a.next(), b.next()
        np.testing.assert_allclose(ba.data[0].asnumpy(),
                                   bb.data[0].asnumpy(),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(ba.label[0].asnumpy(),
                                      bb.label[0].asnumpy())


def test_host_crop_defer_ships_cropped_uint8(tmp_path):
    """In fused-fit defer mode a host-crop iterator hands over
    (B, H, W, C) uint8 — the crop already applied — and its
    device_aug_signature differs from the device-crop one, so the two
    modes never share a compiled window."""
    import mxnet_tpu as mx
    p = str(tmp_path / 'hcd.rec')
    _write_rec(p, 16, hw=10)
    it = mx.io.ImageRecordIter(p, **_iter_kw(6, 4, label_name='l'),
                               device_augment=1, host_crop=1)
    it2 = mx.io.ImageRecordIter(p, **_iter_kw(6, 4, label_name='l'),
                                device_augment=1, host_crop=0)
    assert it.device_aug_signature() != it2.device_aug_signature()
    assert it.defer_device_aug(True)
    try:
        b = next(iter(it))
        d = b.data[0]
        assert d.shape == (4, 6, 6, 3), d.shape      # pre-cropped HWC
        assert str(d.dtype) == 'uint8'
        # the pure fn consumes the pre-cropped batch directly
        import jax
        out = jax.jit(it.device_aug_pure())(
            d.asnumpy(), jax.random.PRNGKey(0))
        assert out.shape == (4, 3, 6, 6)
    finally:
        it.defer_device_aug(False)


def test_host_crop_rand_crop_varies_and_is_seeded(tmp_path):
    """Random host crops: per-image variation within a batch,
    deterministic under mx.random.seed (offsets ride the producer's
    per-batch RandomState, like the host-augment path)."""
    import mxnet_tpu as mx
    p = str(tmp_path / 'hcr.rec')
    _write_rec(p, 16, hw=12)
    kw = _iter_kw(8, 8, rand_crop=1, rand_mirror=1, label_name='l')

    def run():
        mx.random.seed(5)
        it = mx.io.ImageRecordIter(p, **kw, device_augment=1,
                                   host_crop=1)
        it.reset()
        return it.next().data[0].asnumpy()

    a, b = run(), run()
    np.testing.assert_array_equal(a, b)
    assert a.shape == (8, 3, 8, 8)
    assert len({arr.tobytes() for arr in a}) > 1
