"""Host time per window after the dispatch: the median ``fused_fit.fetch``
span (the window's one device-to-host fetch of its metric statistics) plus
the metric and callback spans (``fit.metric``, ``fit.callback``) shared
out over the windows."""
from benchmark import harness


def read(run):
    spans = run.get('spans', ())
    fetch = [s['dur_ms'] for s in spans if s['name'] == 'fused_fit.fetch']
    if not fetch:
        return None
    rest = sum(s['dur_ms'] for s in spans
               if s['name'] in ('fit.metric', 'fit.callback'))
    return harness.median(fetch) + rest / len(fetch)
