"""im2rec tool + ImageDetRecordIter.

Reference: tools/im2rec.{py,cc} + src/io/iter_image_det_recordio.cc +
tests/python/unittest/test_io.py patterns.
"""
import os
import sys

import numpy as np
import pytest

from mxnet_tpu import io as mio

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, 'tools'))
import im2rec  # noqa: E402

PIL = pytest.importorskip('PIL')
from PIL import Image  # noqa: E402


@pytest.fixture
def image_tree(tmp_path):
    rng = np.random.RandomState(0)
    for cls in ['cat', 'dog']:
        d = tmp_path / cls
        d.mkdir()
        for i in range(4):
            arr = (rng.rand(12, 12, 3) * 255).astype(np.uint8)
            Image.fromarray(arr).save(str(d / ('%s%d.png' % (cls, i))))
    return tmp_path


def test_make_list(image_tree):
    prefix = str(image_tree / 'data')
    im2rec.main([prefix, str(image_tree), '--make-list'])
    lines = open(prefix + '.lst').read().strip().split('\n')
    assert len(lines) == 8
    for line in lines:
        idx, label, rel = line.split('\t')
        int(idx)
        assert float(label) in (0.0, 1.0)
        assert rel.endswith('.png')


def test_pack_and_read_classification(image_tree):
    prefix = str(image_tree / 'data')
    im2rec.main([prefix, str(image_tree), '--make-list'])
    im2rec.main([prefix, str(image_tree), '--resize', '8', '--center-crop',
                 '--encoding', 'raw'])
    it = mio.ImageRecordIter(path_imgrec=prefix + '.rec',
                             data_shape=(3, 8, 8), batch_size=4)
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (4, 3, 8, 8)
    assert batches[0].label[0].shape == (4,)


def test_jpeg_encoding_roundtrip(image_tree):
    prefix = str(image_tree / 'jdata')
    im2rec.main([prefix, str(image_tree), '--make-list'])
    im2rec.main([prefix, str(image_tree), '--resize', '8', '--center-crop',
                 '--encoding', '.png'])
    it = mio.ImageRecordIter(path_imgrec=prefix + '.rec',
                             data_shape=(3, 8, 8), batch_size=8)
    b = next(iter(it))
    assert b.data[0].shape == (8, 3, 8, 8)


def _write_det_list(image_tree, prefix):
    im2rec.main([str(image_tree / 'data'), str(image_tree), '--make-list'])
    files = [ln.split('\t')[-1].strip()
             for ln in open(str(image_tree / 'data') + '.lst')]
    with open(prefix + '.lst', 'w') as f:
        for i, rel in enumerate(files):
            if i % 2 == 0:  # one object
                lab = [2, 5, 0, 0.1, 0.1, 0.5, 0.5]
            else:           # two objects
                lab = [2, 5, 1, 0.2, 0.2, 0.6, 0.6, 0, 0.0, 0.0, 0.3, 0.3]
            f.write('%d\t%s\t%s\n' % (i, '\t'.join(map(str, lab)), rel))


def test_det_record_iter(image_tree):
    prefix = str(image_tree / 'det')
    _write_det_list(image_tree, prefix)
    im2rec.main([prefix, str(image_tree), '--lst', prefix + '.lst',
                 '--resize', '8', '--center-crop', '--encoding', 'raw',
                 '--pack-label'])
    it = mio.ImageDetRecordIter(path_imgrec=prefix + '.rec',
                                data_shape=(3, 8, 8), batch_size=4)
    b = next(iter(it))
    lab = b.label[0].asnumpy()
    # header [2, 5] + 2 objects x 5, padded with -1
    assert lab.shape == (4, 12)
    assert (lab[:, 0] == 2).all() and (lab[:, 1] == 5).all()
    one_obj = lab[lab[:, 7] == -1]
    assert (one_obj[:, 7:] == -1).all()
    assert it.label_object_width == 5
    assert it.max_objects == 2


def test_det_label_pad_width(image_tree):
    prefix = str(image_tree / 'det2')
    _write_det_list(image_tree, prefix)
    im2rec.main([prefix, str(image_tree), '--lst', prefix + '.lst',
                 '--resize', '8', '--center-crop', '--encoding', 'raw',
                 '--pack-label'])
    it = mio.ImageDetRecordIter(path_imgrec=prefix + '.rec',
                                data_shape=(3, 8, 8), batch_size=4,
                                label_pad_width=2 + 4 * 5)
    b = next(iter(it))
    assert b.label[0].shape == (4, 2 + 4 * 5)


def test_det_rand_mirror_flips_labels(image_tree):
    prefix = str(image_tree / 'det3')
    _write_det_list(image_tree, prefix)
    im2rec.main([prefix, str(image_tree), '--lst', prefix + '.lst',
                 '--resize', '8', '--center-crop', '--encoding', 'raw',
                 '--pack-label'])
    it = mio.ImageDetRecordIter(path_imgrec=prefix + '.rec',
                                data_shape=(3, 8, 8), batch_size=4,
                                rand_mirror=True)
    plain = next(iter(it))
    mirrored = it._mirror_batch(plain)
    # image flipped along width
    np.testing.assert_allclose(mirrored.data[0].asnumpy(),
                               plain.data[0].asnumpy()[:, :, :, ::-1])
    # label x-coords flipped: xmin' = 1-xmax, xmax' = 1-xmin; pads untouched
    p = plain.label[0].asnumpy()
    m = mirrored.label[0].asnumpy()
    ow = it.label_object_width
    po = p[:, 2:].reshape(p.shape[0], -1, ow)
    mo = m[:, 2:].reshape(m.shape[0], -1, ow)
    valid = po[:, :, 0] != -1
    np.testing.assert_allclose(mo[:, :, 1][valid], 1.0 - po[:, :, 3][valid],
                               rtol=1e-6)
    np.testing.assert_allclose(mo[:, :, 3][valid], 1.0 - po[:, :, 1][valid],
                               rtol=1e-6)
    assert (mo[:, :, 0][~valid] == -1).all()


def test_det_plain_multilabel_not_misparsed(image_tree):
    # a [3.0, 7.0] classification-style label must NOT be read as a
    # detection header (3 would 'look like' hdr_w)
    prefix = str(image_tree / 'det4')
    im2rec.main([str(image_tree / 'data'), str(image_tree), '--make-list'])
    files = [ln.split('\t')[-1].strip()
             for ln in open(str(image_tree / 'data') + '.lst')]
    with open(prefix + '.lst', 'w') as f:
        for i, rel in enumerate(files):
            f.write('%d\t3.0\t7.0\t%s\n' % (i, rel))
    im2rec.main([prefix, str(image_tree), '--lst', prefix + '.lst',
                 '--resize', '8', '--center-crop', '--encoding', 'raw',
                 '--pack-label'])
    it = mio.ImageDetRecordIter(path_imgrec=prefix + '.rec',
                                data_shape=(3, 8, 8), batch_size=4)
    b = next(iter(it))
    lab = b.label[0].asnumpy()
    # promoted to one object row of width 2, values preserved
    assert it.label_object_width == 2
    assert (lab[:, 2] == 3.0).all() and (lab[:, 3] == 7.0).all()


def test_default_jpg_encoding(image_tree):
    # the tool's default --encoding .jpg must work (PIL wants 'JPEG')
    prefix = str(image_tree / 'jpgdata')
    im2rec.main([prefix, str(image_tree), '--make-list'])
    im2rec.main([prefix, str(image_tree), '--resize', '8', '--center-crop'])
    it = mio.ImageRecordIter(path_imgrec=prefix + '.rec',
                             data_shape=(3, 8, 8), batch_size=8)
    b = next(iter(it))
    assert b.data[0].shape == (8, 3, 8, 8)


def test_det_label_pad_width_exact(image_tree):
    # width not a multiple of obj_w still pads to EXACTLY the request
    prefix = str(image_tree / 'det5')
    _write_det_list(image_tree, prefix)
    im2rec.main([prefix, str(image_tree), '--lst', prefix + '.lst',
                 '--resize', '8', '--center-crop', '--encoding', 'raw',
                 '--pack-label'])
    it = mio.ImageDetRecordIter(path_imgrec=prefix + '.rec',
                                data_shape=(3, 8, 8), batch_size=4,
                                label_pad_width=15)  # (15-2) % 5 != 0
    b = next(iter(it))
    assert b.label[0].shape == (4, 15)
