"""What the host was doing while the device was idle.

With telemetry on, every ``telemetry.span`` of the program opens a
``jax.profiler.TraceAnnotation``, so a capture holds the program's spans
as events of its ``/host:CPU`` plane: on the capture's clock, which is the
device events' clock too, on one line per thread, with the span's
attributes (``win``) among the statistics. This file takes the idle
intervals of the device in the slice that ``trace.reduce_device`` cuts and
hands each second of them to the span that was innermost on the loop's
thread at that time:

* the loop's thread is the line that holds the ``.dispatch`` spans;
* a second in which the loop waited in a ``.put`` span goes to what a
  side thread was in at that time (``.stack``, ``.upload``), and stays
  with ``.put`` where no side thread was in a span;
* a second that no span of the loop's thread covers goes to ``''``: what
  the measurement still cannot see.

The seconds handed out sum to the slice's idle time, ``window_s - busy_s``
of ``trace.reduce_file`` (mean over the cell's chips). A capture of a
program without such spans (an older one, telemetry off) has no such
events and :func:`reduce_file` returns None.
"""
import os

from benchmark.reduce import trace

PREFIXES = ('fused_fit.', 'fit.')


def host_lines(profile, prefixes=PREFIXES):
    """[[(name, start_s, end_s, stats)]]: for every line of the host's
    planes that holds events of the program's spans, those events."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith('/host:'):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9, trace._stats(e))
                   for e in line.events if e.name.startswith(prefixes)]
            if evs:
                out.append(evs)
    return out


def innermost(events):
    """[(start, end, name)], flat and in order: over the union of the
    `events` of one thread (which nest or follow one another), the name
    of the innermost one at every instant."""
    out, stack = [], []
    cursor = None

    def emit(end, name):
        if end > cursor:
            out.append((cursor, end, name))

    for name, start, end, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            top = stack.pop()
            emit(top[1], top[0])
            cursor = max(cursor, top[1])
        if stack:
            emit(start, stack[-1][0])
            end = min(end, stack[-1][1])    # a child ends with its parent
        stack.append((name, end))
        cursor = start
    while stack:
        top = stack.pop()
        emit(top[1], top[0])
        cursor = max(cursor, top[1])
    return out


def idle_intervals(lines, whole_periods_of=0):
    """(t0, t1, [(start, end)]) of one chip: the slice as
    ``trace.reduce_device`` cuts it (the whole periods between the starts
    of the `whole_periods_of` longest program executions; else from the
    first operation to the last) and the stretches of it in which no
    operation ran."""
    modules = lines.get('XLA Modules', ())
    ops = lines.get('XLA Ops')
    if ops is None:
        ops = [e for evs in lines.values() for e in evs]
    busy = trace.union([(s, s + d) for _, s, d, _ in ops])
    if not busy:
        return 0.0, 0.0, []
    if whole_periods_of > 1 and len(modules) >= whole_periods_of:
        longest = sorted(modules, key=lambda e: -e[2])[:whole_periods_of]
        starts = sorted(e[1] for e in longest)
        t0, t1 = starts[0], starts[-1]
    else:
        t0, t1 = busy[0][0], busy[-1][1]
    idle, at = [], t0
    for s, e in busy:
        if e <= t0 or s >= t1:
            continue
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if t1 > at:
        idle.append((at, t1))
    return t0, t1, idle


def _split(intervals, segments):
    """Cut merged `intervals` by flat, ordered `segments` [(start, end,
    name)]: [(start, end, name or None)], None where no segment lies."""
    out, j = [], 0
    for s, e in intervals:
        at = s
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b = max(segments[k][0], s), min(segments[k][1], e)
            if a > at:
                out.append((at, a, None))
            if b > a:
                out.append((a, b, segments[k][2]))
                at = b
            k += 1
        if e > at:
            out.append((at, e, None))
    return out


def attribute(idle, loop, sides=()):
    """{span name: seconds} of the merged `idle` intervals: each second to
    the innermost span of the loop's thread (`loop`, events of one
    thread), a wait in ``.put`` handed on to what the side threads
    (`sides`, events per thread) were in, the rest under ``''``."""
    side = sorted(seg for evs in sides for seg in innermost(evs))
    out = {}

    def add(name, seconds):
        out[name] = out.get(name, 0.0) + seconds

    for s, e, name in _split(idle, innermost(loop)):
        if name is None:
            add('', e - s)
        elif name.endswith('.put'):
            for a, b, handed in _split([(s, e)], side):
                add(handed or name, b - a)
        else:
            add(name, e - s)
    return out


def loop_and_sides(lines):
    """(events of the loop's thread, [events of each other thread]): the
    loop's thread is the one with most ``.dispatch`` spans."""
    def dispatches(evs):
        return sum(1 for e in evs if e[0].endswith('.dispatch'))
    if not lines or not max(map(dispatches, lines)):
        return None, []
    loop = max(lines, key=dispatches)
    return loop, [evs for evs in lines if evs is not loop]


def reduce_profile(profile, devices=None, whole_periods_of=0):
    """{'idle_s': the slice's idle seconds (mean over the chips),
    'by_span': {span name: seconds of them}, 'windows': the ``win`` of
    the loop's spans that lie in the slice}, or None where the capture
    holds no span of the program."""
    loop, sides = loop_and_sides(host_lines(profile))
    if loop is None:
        return None
    per = [idle_intervals(lines, whole_periods_of)
           for _, lines in sorted(trace.device_lines(profile),
                                  key=lambda plane: plane[0])
           if any(lines.values())]
    if devices:
        per = per[:devices]
    if not per:
        return None
    by_span, wins = {}, set()
    for t0, t1, idle in per:
        for name, seconds in attribute(idle, loop, sides).items():
            by_span[name] = by_span.get(name, 0.0) + seconds / len(per)
        wins.update(st['win'] for _, s, e, st in loop
                    if 'win' in st and s < t1 and e > t0)
    return {'idle_s': sum(by_span.values()), 'by_span': by_span,
            'windows': sorted(wins)}


def reduce_file(path, devices=None, whole_periods_of=0):
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), devices,
                          whole_periods_of)


def of_run(run):
    """The reduction for a driver's `run`, made once and kept in it. The
    capture is the newest under ``trace/`` beside the program's telemetry
    log, in the run's work directory, which still stands when the readers
    are called; the slice is the one the driver's own reduction took."""
    if 'host_spans' not in run:
        run['host_spans'] = None
        log = os.environ.get('MXTPU_TELEMETRY_PATH')
        if log and run.get('trace'):
            try:
                path = trace.newest_xplane(
                    os.path.join(os.path.dirname(log), 'trace'))
            except FileNotFoundError:
                return None
            steps = run.get('trace_steps', 0) // max(
                run.get('steps_per_window', 1), 1)
            run['host_spans'] = reduce_file(
                path, run.get('chips'), steps + 1 if steps else 0)
    return run['host_spans']


def idle_share(run, names):
    """Per cent of the slice's idle time handed to the spans `names`."""
    reduced = of_run(run)
    if not reduced or not reduced['idle_s']:
        return None
    return 100.0 * sum(reduced['by_span'].get(n, 0.0)
                       for n in names) / reduced['idle_s']
