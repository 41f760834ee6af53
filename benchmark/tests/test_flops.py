"""The FLOP counter against hand counts (2 operations per multiply-add)."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.reduce import flops  # noqa: E402


def resnet50_shapes():
    s = {'conv0_weight': (64, 3, 7, 7), 'fc1_weight': (1000, 2048),
         'fc1_bias': (1000,)}
    for name in ('bn0', 'bn1'):
        c = 64 if name == 'bn0' else 2048
        s[name + '_gamma'] = s[name + '_beta'] = (c,)
    widths, units, cin = (256, 512, 1024, 2048), (3, 4, 6, 3), 64
    for st, (w, n) in enumerate(zip(widths, units)):
        for u in range(n):
            p = 'stage%d_unit%d' % (st + 1, u + 1)
            s[p + '_conv1_weight'] = (w // 4, cin, 1, 1)
            s[p + '_conv2_weight'] = (w // 4, w // 4, 3, 3)
            s[p + '_conv3_weight'] = (w, w // 4, 1, 1)
            if u == 0:
                s[p + '_sc_weight'] = (w, cin, 1, 1)
            for b, c in (('bn1', cin), ('bn2', w // 4), ('bn3', w // 4)):
                s['%s_%s_gamma' % (p, b)] = s['%s_%s_beta' % (p, b)] = (c,)
            cin = w
    return s


def test_resnet50_stem_bottleneck_and_fc_by_hand():
    recs = flops.layers('resnet50', resnet50_shapes(), (3, 224, 224))
    # stem: 64 maps of 112x112, each a 3x7x7 window
    assert recs[0]['macs'] == 64 * 112 * 112 * 3 * 7 * 7 == 118013952
    # stage1_unit1 at 56x56: 1x1 64->64, 3x3 64->64, 1x1 64->256,
    # projection 1x1 64->256
    hw = 56 * 56
    assert [r['macs'] for r in recs[1:5]] == [
        hw * 64 * 64, hw * 64 * 64 * 9, hw * 256 * 64, hw * 256 * 64]
    # the classifier
    assert recs[-1]['kind'] == 'fc' and recs[-1]['macs'] == 2048 * 1000
    assert len(recs) == 1 + 16 * 3 + 4 + 1


def test_resnet50_totals():
    need = flops.required_flops('resnet50', resnet50_shapes(),
                                (3, 224, 224))
    # He et al. quote 3.8e9 multiply-adds for the v1 net; the stride on the
    # 3x3 (v1.5) and this layout give 4.09e9
    assert need['forward'] == 8178368512
    # every layer trains at 3x forward but the stem (no input gradient)
    assert need['train'] == 3 * need['forward'] - 2 * 118013952
    assert need['conv_train'] == need['train'] - 3 * 2 * 2048 * 1000


def test_layers_scale_with_the_image():
    small = flops.required_flops('resnet50', resnet50_shapes(), (3, 64, 64))
    assert small['forward'] < 8178368512 / 10
