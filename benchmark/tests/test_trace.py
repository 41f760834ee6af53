"""The trace reduction: on made-up events whose answers are known by
hand, and on a small capture recorded on the v5e and kept beside this
file (``small_v5e.xplane.pb``: a jitted scan of a convolution, a tanh and
a reduction, four calls)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.reduce import trace  # noqa: E402

RECORDED = os.path.join(HERE, 'small_v5e.xplane.pb')


def ev(name, start, dur, **stats):
    return (name, start, dur, stats)


def test_union_and_leaf_times():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    # a while loop of 10 s around two 3 s children: 4 s are its own
    leaves = trace.leaf_times([ev('while', 0, 10), ev('a', 1, 3),
                               ev('b', 5, 3)])
    assert {n: d for n, _, d, _ in leaves} == {'while': 4, 'a': 3, 'b': 3}


def test_busy_idle_classes_and_gaps():
    lines = {'XLA Ops': [
        ev('fusion.1', 0.0, 1.0, hlo_category='convolution fusion'),
        ev('fusion.2', 1.0, 0.5, hlo_category='loop fusion'),
        ev('convolution.3', 3.0, 1.0),
        ev('all-reduce.4', 4.0, 0.5),
    ], 'XLA Modules': [ev('jit_f', 0.0, 4.5)]}
    r = trace.reduce_device(lines, window_s=5.0)
    assert r['busy_s'] == pytest.approx(3.0)
    assert r['window_s'] == 5.0
    assert r['by_class'] == pytest.approx(
        {'conv': 2.0, 'other': 0.5, 'collective': 0.5})
    # the one gap: 1.5 s after fusion.2, before convolution.3
    assert r['gaps'][0][1] == pytest.approx(1.5)
    assert 'fusion.2' in r['gaps'][0][0] and 'convolution.3' in r['gaps'][0][0]
    assert r['modules'] == [('jit_f', 4.5)]


def test_collective_overlap():
    # an asynchronous all-reduce of 2 s on a line of its own, 1.5 s of it
    # under compute: 0.5 s exposed
    lines = {'XLA Ops': [ev('fusion.1', 0.0, 1.5)],
             'Async': [ev('all-reduce-start.2', 0.0, 2.0)]}
    r = trace.reduce_device(lines)
    assert r['collective_s'] == pytest.approx(2.0)
    assert r['collective_exposed_s'] == pytest.approx(0.5)
    # a synchronous one among the ops is exposed in full
    lines = {'XLA Ops': [ev('fusion.1', 0.0, 1.0), ev('all-gather.2', 1.0, 0.5)]}
    r = trace.reduce_device(lines)
    assert r['collective_exposed_s'] == pytest.approx(0.5)


def test_breakdown_is_short_and_sorted():
    red = {'by_name': {'op%d' % i: float(i) for i in range(30)},
           'gaps': [['g', 1.0]] * 30}
    b = trace.breakdown(red)
    assert len(b['device_ops']) == 10 and len(b['idle_gaps']) == 10
    assert b['device_ops'][0] == ['op29', 29.0]


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason='no recorded capture beside this file')
def test_recorded_capture_of_the_v5e():
    r = trace.reduce_file(RECORDED, devices=1)
    assert r['devices'] == 1
    assert 0 < r['busy_s'] <= r['window_s']
    # leaf times add up to the busy time (nothing counted twice)
    assert sum(r['by_class'].values()) == pytest.approx(r['busy_s'],
                                                        rel=0.02)
    assert r['by_class']['conv'] > 0 and r['by_class']['other'] > 0
    assert r['by_class']['collective'] == 0
    assert len(r['modules']) >= 4
    assert trace.breakdown(r)['device_ops']


def test_whole_periods_between_the_longest_programs():
    # three 1 s programs starting at 0, 4 and 8: two periods of 4 s, each
    # with 1 s of work; the tail of the third is cut off
    ops = [ev('fusion.%d' % i, t, 1.0) for i, t in enumerate((0, 4, 8))]
    mods = [ev('jit_step', t, 1.0) for t in (0, 4, 8)] + [ev('tiny', 2, .01)]
    r = trace.reduce_device({'XLA Ops': ops, 'XLA Modules': mods},
                            whole_periods_of=3)
    assert r['window_s'] == pytest.approx(8.0)
    assert r['busy_s'] == pytest.approx(2.0)
