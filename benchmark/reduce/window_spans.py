"""The window pipeline's spans among ``run['spans']``, the span records of
the timed ``fit``: ``name``, ``t``, ``dur_ms`` and, from a program that
writes them, ``tid`` and ``win``, the window's number."""
from statistics import median


def median_ms(run, name):
    """Median duration of the spans `name` over the dispatched windows (the
    draw that only finds the iterator at its end has a ``win`` that no
    ``.dispatch`` carries); None where the program wrote none."""
    spans = run.get('spans', ())
    dispatched = {s['win'] for s in spans
                  if s['name'].endswith('.dispatch') and 'win' in s}
    ms = [s['dur_ms'] for s in spans if s['name'] == name
          and (not dispatched or s.get('win') in dispatched)]
    return median(ms) if ms else None
