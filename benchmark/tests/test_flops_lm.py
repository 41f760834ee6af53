"""``reduce/flops_lm.py`` against numbers worked out by hand from the
published widths, and ``reduce/kernel_times.py`` on the recorded trace."""
import json
import os

from benchmark.reduce import flops_lm, kernel_times, trace

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = json.load(open(os.path.join(HERE, '..', 'configs',
                                  'laguna_s_2_1.json')))


def test_visible_pairs():
    assert flops_lm.visible_pairs(4, 0) == 10
    assert flops_lm.visible_pairs(4, 2) == 1 + 2 + 2 + 2
    assert flops_lm.visible_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512


def test_required_flops_of_the_cut_model():
    need = flops_lm.required_flops(CFG, 8192)
    parts = need['parts']
    # a full layer's projections: 2 * 3072 * (48*128 + 2*1024 + 48) + out
    full = 2 * 3072 * (6144 + 2048 + 48) + 2 * 6144 * 3072
    slide = 2 * 3072 * (9216 + 2048 + 72) + 2 * 9216 * 3072
    assert parts['projections'] == 2 * full + 3 * slide
    assert parts['dense_mlp'] == 6 * 3072 * 12288
    assert parts['head'] == 2 * 3072 * 12544
    assert abs(parts['attention_full'] / 2 - 100.7e6) < 0.1e6
    assert abs(need['forward'] - 1.2207e9) < 1e6
    assert need['train'] == 3 * need['forward']


def test_kernel_work_is_positive_and_split_by_kind():
    full = flops_lm.attention_work(CFG, 8192, 1, windowed=False)
    window = flops_lm.attention_work(CFG, 8192, 1, windowed=True)
    assert full[0] > window[0] > 0 and full[1] > 0
    # forward two products of the visible pairs, backward five
    assert full[0] == 7 * 2 * 128 * 48 * 2 * flops_lm.visible_pairs(8192, 0)


def test_kernel_times_on_the_recorded_trace():
    """The small recorded trace holds no Pallas kernel: every group reads
    zero and the busy time is the trace reduction's own."""
    from jax.profiler import ProfileData
    path = os.path.join(HERE, 'small_v5e.xplane.pb')
    lines = trace.device_lines(ProfileData.from_file(path))[0][1]
    got = kernel_times.reduce_lines(lines)
    want = trace.reduce_device(lines)
    assert abs(got['busy'] - want['busy_s']) < 1e-9
    assert all(got[g] == 0.0 for g, _ in kernel_times.GROUPS)
