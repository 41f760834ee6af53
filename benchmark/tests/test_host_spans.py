"""The attribution of the device's idle time to the program's host spans,
on hand-made intervals whose answers are known by hand."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.reduce import host_spans, window_spans  # noqa: E402


def ev(name, start, end, **stats):
    return (name, start, end, stats)


def test_innermost_is_flat_and_in_order():
    events = [ev('f.draw', 0.0, 4.0), ev('f.next', 0.5, 1.5),
              ev('f.next', 2.0, 3.0), ev('f.put', 5.0, 6.0)]
    assert host_spans.innermost(events) == [
        (0.0, 0.5, 'f.draw'), (0.5, 1.5, 'f.next'), (1.5, 2.0, 'f.draw'),
        (2.0, 3.0, 'f.next'), (3.0, 4.0, 'f.draw'), (5.0, 6.0, 'f.put')]
    # a child that a clock lets end after its parent ends with it
    assert host_spans.innermost([ev('a', 0.0, 1.0), ev('b', 0.5, 1.2)]) \
        == [(0.0, 0.5, 'a'), (0.5, 1.0, 'b')]


def test_idle_intervals_of_whole_periods():
    # three windows start at 1, 5 and 9; the slice is [1, 9], busy 1-2.5,
    # 5-6.5 in it: idle 2.5-5 and 6.5-9
    lines = {'XLA Modules': [('jit_w', 1.0, 1.5, {}), ('jit_w', 5.0, 1.5, {}),
                             ('jit_w', 9.0, 1.5, {}), ('jit_s', 0.2, 0.1, {})],
             'XLA Ops': [('a', 0.2, 0.1, {}), ('a', 1.0, 1.0, {}),
                         ('b', 2.0, 0.5, {}), ('a', 5.0, 1.5, {}),
                         ('a', 9.0, 1.5, {})]}
    t0, t1, idle = host_spans.idle_intervals(lines, whole_periods_of=3)
    assert (t0, t1) == (1.0, 9.0)
    assert idle == [(2.5, 5.0), (6.5, 9.0)]
    # without periods: from the first operation to the last
    t0, t1, idle = host_spans.idle_intervals(lines)
    assert (t0, t1) == (0.2, 10.5)
    assert sum(e - s for s, e in idle) == pytest.approx(10.3 - 4.6)


def test_put_is_handed_to_the_side_thread_and_the_rest_is_unnamed():
    # the device is idle from 0 to 10. The loop draws for 3 s (2 s of it
    # inside the iterator), waits in .put from 3 to 8, dispatches until
    # 8.5, and no span covers 8.5 to 10. The side thread stacks from 2 to
    # 6 and uploads from 6 to 7.
    loop = [ev('fused_fit.draw', 0.0, 3.0, win=4),
            ev('fused_fit.next', 0.5, 1.5, win=4),
            ev('fused_fit.next', 1.5, 2.5, win=4),
            ev('fused_fit.put', 3.0, 8.0, win=4),
            ev('fused_fit.dispatch', 8.0, 8.5, win=4)]
    side = [ev('fused_fit.stack', 2.0, 6.0, win=4),
            ev('fused_fit.upload', 6.0, 7.0, win=4)]
    got = host_spans.attribute([(0.0, 10.0)], loop, [side])
    assert got == pytest.approx({
        'fused_fit.draw': 1.0, 'fused_fit.next': 2.0,
        'fused_fit.stack': 3.0,         # 3 to 6: the side thread's share
        'fused_fit.upload': 1.0, 'fused_fit.put': 1.0,   # 7 to 8
        'fused_fit.dispatch': 0.5, '': 1.5})
    assert sum(got.values()) == pytest.approx(10.0)
    # only idle seconds are handed out: busy from 4 to 9
    got = host_spans.attribute([(0.0, 4.0), (9.0, 10.0)], loop, [side])
    assert got == pytest.approx({
        'fused_fit.draw': 1.0, 'fused_fit.next': 2.0,
        'fused_fit.stack': 1.0, '': 1.0})
    # no pool: the loop's own .stack keeps its seconds
    loop = [ev('fused_fit.stack', 0.0, 2.0), ev('fused_fit.upload', 2.0, 2.5),
            ev('fused_fit.put', 2.5, 2.6), ev('fused_fit.dispatch', 2.6, 3.0)]
    got = host_spans.attribute([(0.0, 3.0)], loop)
    assert got == pytest.approx({
        'fused_fit.stack': 2.0, 'fused_fit.upload': 0.5,
        'fused_fit.put': 0.1, 'fused_fit.dispatch': 0.4})


def test_the_loop_thread_is_the_one_that_dispatches():
    loop = [ev('fused_fit.put', 0.0, 1.0), ev('fused_fit.dispatch', 1.0, 2.0)]
    side = [ev('fused_fit.stack', 0.0, 0.5)]
    assert host_spans.loop_and_sides([side, loop]) == (loop, [side])
    assert host_spans.loop_and_sides([side]) == (None, [])
    assert host_spans.loop_and_sides([]) == (None, [])


def test_a_capture_without_program_spans_reads_nothing():
    # the capture recorded on the v5e for test_trace.py: device events, no
    # span of the program
    recorded = os.path.join(HERE, 'small_v5e.xplane.pb')
    assert host_spans.reduce_file(recorded, 1, 3) is None
    assert host_spans.idle_share({'trace': None}, ('',)) is None


def test_median_over_dispatched_windows():
    spans = [{'name': 'fused_fit.draw', 'dur_ms': 30.0, 'win': 0},
             {'name': 'fused_fit.dispatch', 'dur_ms': 1.0, 'win': 0},
             {'name': 'fused_fit.draw', 'dur_ms': 50.0, 'win': 1},
             {'name': 'fused_fit.dispatch', 'dur_ms': 1.0, 'win': 1},
             {'name': 'fused_fit.draw', 'dur_ms': 0.1, 'win': 2}]
    assert window_spans.median_ms({'spans': spans}, 'fused_fit.draw') == 40.0
    assert window_spans.median_ms({'spans': spans}, 'fused_fit.stack') is None
    # records of a program that does not number its windows: all of them
    old = [{'name': 'fused_fit.draw', 'dur_ms': d} for d in (1.0, 2.0, 9.0)]
    assert window_spans.median_ms({'spans': old}, 'fused_fit.draw') == 2.0
    assert window_spans.median_ms({}, 'fused_fit.draw') is None
