"""Median device time of one bucket program call: the program executions
on the trace's ``XLA Modules`` line, less the microsecond helpers around
each dispatch (key split, unstack), which are told apart by lasting under
a hundredth of the longest call."""
from benchmark import harness


def read(run):
    mods = (run.get('trace') or {}).get('modules')
    if not mods:
        return None
    longest = max(d for _, d in mods)
    return 1e3 * harness.median([d for _, d in mods if d >= longest / 100])
