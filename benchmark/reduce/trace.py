"""From a profiler capture (``.xplane.pb``) to the numbers the per-layer
metrics read. Uses nothing but ``jax.profiler.ProfileData``.

What a TPU capture holds (seen on the v5e, PR 24): one plane per chip,
``/device:TPU:<i>``, with a line ``XLA Ops`` (one event per executed HLO
instruction, named by the instruction's whole text, with no category and
no ``jax.named_scope`` path among its statistics), a line ``Async XLA
Ops`` (copy-start/done pairs and other asynchronous halves) and a line
``XLA Modules`` (one event per program execution, ``jit_<name>(<hash>)``).

The reduction works on *leaf* time, a parent's duration minus what its
nested children cover, so that nothing is counted twice, and on the union
of intervals for busy time.

Classes of device time:

* ``collective``: the instruction's name is an all-reduce, all-gather,
  reduce-scatter, collective-permute or all-to-all (their ``-start`` /
  ``-done`` halves included);
* ``conv``: a convolution or dot instruction, or an output fusion (XLA's
  fusion kind for a convolution or dot with what is fused around it: the
  epilogue's bias, BatchNorm scale or ReLU counts as the convolution's
  time). Where an event is not named by HLO text, the profiler's
  ``hlo_category`` statistic or the name's own prefix decides;
* ``other``: everything else (normalisation statistics, elementwise,
  pooling, the update, copies).
"""
import glob
import os
import re

_COLLECTIVE = re.compile(
    r'(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)')


def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    if not files:
        raise FileNotFoundError('no .xplane.pb under %s' % trace_dir)
    return max(files, key=os.path.getmtime)


def _stats(event):
    try:
        return {str(k): v for k, v in event.stats}
    except Exception:  # noqa: BLE001 - a capture without statistics
        return {}


def short_name(name):
    """An event's name is the whole HLO instruction on this chip
    ('%fusion.2 = bf16[8,64,112,112]{...} fusion(...), kind=kOutput, ...'):
    keep the instruction's own name, its result's type and its kind."""
    if ' = ' not in name:
        return name
    head, rest = name.split(' = ', 1)
    shape = rest.split('{', 1)[0].split(' ', 1)[0]
    kind = re.search(r'kind=(k\w+)', rest)
    return ' '.join(filter(None, (head.lstrip('%'), shape,
                                  kind.group(1) if kind else '')))


def classify(name, stats):
    """'collective', 'conv' or 'other' for one device event."""
    head = name.split(' = ', 1)[0]
    if _COLLECTIVE.search(head):
        return 'collective'
    if ' = ' in name:
        # the v5e's captures name an event by its HLO text and carry no
        # category or scope: a convolution or matmul is an instruction of
        # that opcode or an output fusion (XLA's fusion kind for a
        # convolution or dot with what is fused around it)
        rest = name.split(' = ', 1)[1]
        if 'kind=kOutput' in rest or re.search(
                r'\} (convolution|dot)\(', rest) \
                or head.lstrip('%').startswith(('convolution', 'dot')):
            return 'conv'
        return 'other'
    cat = str(stats.get('hlo_category', '')).lower()
    if 'convolution' in cat or name.lower().startswith(('convolution',
                                                        'dot')):
        return 'conv'
    return 'other'


def device_lines(profile):
    """[(plane name, {line name: [(name, start_s, dur_s, stats)]})] for
    every accelerator plane."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith('/device:'):
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9, _stats(e))
                for e in line.events]
        out.append((plane.name, lines))
    return out


def leaf_times(events):
    """[(name, start, self_seconds, stats)]: each event's duration less
    the part its nested children cover. Events of one line nest or follow
    one another, never cross."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack = []      # indices into out of the open parents
    for name, start, dur, stats in evs:
        end = start + dur
        while stack and out[stack[-1]][4] <= start + 1e-12:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[2] -= min(dur, max(0.0, parent[4] - start))
        out.append([name, start, dur, stats, end])
        stack.append(len(out) - 1)
    return [(n, s, max(d, 0.0), st) for n, s, d, st, _ in out]


def union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _covered(intervals, cover):
    """Seconds of `intervals` (merged) that `cover` (merged) overlaps."""
    total, j = 0.0, 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total += max(0.0, min(e, cover[k][1]) - max(s, cover[k][0]))
            k += 1
    return total


def _clip(events, t0, t1):
    out = []
    for name, start, dur, stats in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e - s, stats))
    return out


def reduce_device(lines, window_s=None, whole_periods_of=0):
    """The reduction for one chip's lines.

    `whole_periods_of` n > 1: the slice runs from the start of the first
    of the n longest program executions (``XLA Modules``) to the start of
    the last, so that it holds n - 1 whole periods of a loop that runs one
    long program per period, whether the host or the device paces it;
    events are clipped to it."""
    if whole_periods_of > 1 and len(lines.get('XLA Modules', ())) \
            >= whole_periods_of:
        longest = sorted(lines['XLA Modules'], key=lambda e: -e[2])
        starts = sorted(e[1] for e in longest[:whole_periods_of])
        t0, t1 = starts[0], starts[-1]
        lines = {k: _clip(v, t0, t1) for k, v in lines.items()}
        window_s = t1 - t0
    ops = lines.get('XLA Ops')
    if ops is None:     # a capture of another backend: take every line
        ops = [e for evs in lines.values() for e in evs]
    leaves = leaf_times(ops)
    busy = union([(s, s + d) for _, s, d, _ in ops])
    busy_s = sum(e - s for s, e in busy)
    span = (busy[-1][1] - busy[0][0]) if busy else 0.0
    window = window_s if window_s and window_s >= span else span

    by_name, by_class = {}, {'conv': 0.0, 'collective': 0.0, 'other': 0.0}
    coll, compute = [], []
    for name, start, self_s, stats in leaves:
        if self_s <= 0.0:
            continue
        cls = classify(name, stats)
        by_class[cls] += self_s
        key = short_name(name)
        by_name[key] = by_name.get(key, 0.0) + self_s
        (coll if cls == 'collective' else compute).append(
            (start, start + self_s))
    # collectives on lines of their own (asynchronous halves)
    for lname, evs in lines.items():
        if lname in ('XLA Ops', 'XLA Modules', 'Steps'):
            continue
        for name, start, dur, _ in evs:
            if _COLLECTIVE.search(name.split(' = ', 1)[0]):
                coll.append((start, start + dur))
    coll_u, comp_u = union(coll), union(compute)
    coll_s = sum(e - s for s, e in coll_u)
    exposed_s = coll_s - _covered(coll_u, comp_u)

    gaps = []
    for (s0, e0), (s1, _e1) in zip(busy, busy[1:]):
        gaps.append((s1 - e0, e0))
    gaps.sort(reverse=True)
    ends = {round(s + d, 9): short_name(n) for n, s, d, _ in ops}
    starts = {round(s, 9): short_name(n) for n, s, d, _ in ops}
    named_gaps = []
    for dur, at in gaps[:10]:
        named_gaps.append(['after %s before %s' % (
            ends.get(round(at, 9), '?'),
            starts.get(round(at + dur, 9), '?')), dur])

    modules = [(n, d) for n, _s, d, _ in lines.get('XLA Modules', [])]
    return {'busy_s': busy_s, 'window_s': window, 'span_s': span,
            'by_name': by_name, 'by_class': by_class,
            'collective_s': coll_s, 'collective_exposed_s': exposed_s,
            'gaps': named_gaps, 'modules': modules,
            'n_events': len(ops)}


def reduce_file(path, window_s=None, devices=None, whole_periods_of=0):
    """The whole capture: per-chip reductions and their mean.

    `devices`: how many chips the cell used; planes beyond it (idle chips
    of a larger host) are left out."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    per = [(name, reduce_device(lines, window_s, whole_periods_of))
           for name, lines in device_lines(profile)]
    per = [p for p in per if p[1]['n_events']]
    per.sort(key=lambda p: p[0])
    if devices:
        per = per[:devices]
    if not per:
        return {'devices': 0, 'busy_s': 0.0, 'window_s': window_s or 0.0,
                'by_name': {}, 'by_class': {}, 'collective_s': 0.0,
                'collective_exposed_s': 0.0, 'gaps': [], 'modules': [],
                'per_device': []}
    n = float(len(per))
    by_name, by_class = {}, {}
    for _, r in per:
        for k, v in r['by_name'].items():
            by_name[k] = by_name.get(k, 0.0) + v / n
        for k, v in r['by_class'].items():
            by_class[k] = by_class.get(k, 0.0) + v / n
    first = per[0][1]
    return {'devices': len(per),
            'busy_s': sum(r['busy_s'] for _, r in per) / n,
            'window_s': max(r['window_s'] for _, r in per),
            'by_name': by_name, 'by_class': by_class,
            'collective_s': first['collective_s'],
            'collective_exposed_s': first['collective_exposed_s'],
            'gaps': first['gaps'], 'modules': first['modules'],
            'per_device': [{'plane': name, 'busy_s': r['busy_s'],
                            'span_s': r['span_s']} for name, r in per]}


def breakdown(reduced, top=10):
    """The contract's ``breakdown``: the device operations that took most
    time and the longest idle gaps, at most `top` of each."""
    ops = sorted(reduced['by_name'].items(), key=lambda kv: -kv[1])[:top]
    return {'device_ops': [[k, v] for k, v in ops],
            'idle_gaps': [list(g) for g in reduced['gaps'][:top]]}


def describe(path, limit=6):
    """A few lines on what a capture holds: planes, lines, the statistic
    keys of the first events. For reading a trace by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append('plane %s' % plane.name)
        for line in plane.lines:
            evs = list(line.events)
            out.append('  line %s: %d events' % (line.name, len(evs)))
            for e in evs[:limit]:
                out.append('    %s %.1fus %s' % (
                    e.name, e.duration_ns * 1e-3,
                    {k: (v if not isinstance(v, str) else v[:80])
                     for k, v in _stats(e).items()}))
    return '\n'.join(out)
