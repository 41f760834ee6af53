"""Device seconds of the program's named kernels in a traced slice.

``reduce/trace.py`` keys its per-operation times by an event's *short*
name, which for a custom call keeps the instruction's own name and drops
the rest of its text. A Pallas kernel carries the name the program gave
it (``pl.pallas_call(..., name=...)``) somewhere in the event's name, its
HLO text or its statistics, so this reduction matches the whole of those,
on the same leaf times and the same slice (the whole periods between the
starts of the longest program executions) as ``trace.reduce_device``.

`GROUPS` maps a group to the substrings that put an event into it. An
event is counted once, in the first group that matches.
"""
from benchmark.reduce import trace

GROUPS = (
    ('attention_window', ('attention_window',)),
    ('attention_full', ('attention_full',)),
    ('moe_expert', ('moe_expert_matmul',)),
    # what XLA runs around the expert product (routing, gathers, the
    # shared expert): tellable only where the capture carries the
    # symbol's scope names
    ('moe_other', ('_moe/', '_moe"', '_moe.')),
)


def _text(name, stats):
    return name + ' ' + ' '.join(str(v) for v in stats.values())


def reduce_lines(lines, whole_periods_of=0):
    """{group: device seconds, 'busy': busy seconds} for one chip's
    lines."""
    if whole_periods_of > 1 and len(lines.get('XLA Modules', ())) \
            >= whole_periods_of:
        longest = sorted(lines['XLA Modules'], key=lambda e: -e[2])
        starts = sorted(e[1] for e in longest[:whole_periods_of])
        lines = {k: trace._clip(v, starts[0], starts[-1])
                 for k, v in lines.items()}
    ops = lines.get('XLA Ops')
    if ops is None:
        ops = [e for evs in lines.values() for e in evs]
    out = {g: 0.0 for g, _ in GROUPS}
    out['busy'] = sum(e - s for s, e in trace.union(
        [(s, s + d) for _, s, d, _ in ops]))
    for name, _start, self_s, stats in trace.leaf_times(ops):
        if self_s <= 0.0:
            continue
        text = _text(name, stats)
        for group, needles in GROUPS:
            if any(n in text for n in needles):
                out[group] += self_s
                break
    return out


def reduce_capture(trace_dir, whole_periods_of=0):
    """The first chip's reduction of the newest capture under
    `trace_dir`; {} where the capture holds no device plane."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(trace.newest_xplane(trace_dir))
    planes = sorted(trace.device_lines(profile), key=lambda p: p[0])
    for _, lines in planes:
        if any(lines.values()):
            return reduce_lines(lines, whole_periods_of)
    return {}
