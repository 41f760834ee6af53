"""Median host time of one window's dispatch (span ``fused_fit.dispatch``:
the call that enqueues the compiled window and writes its outputs back)."""
from benchmark import harness


def read(run):
    ms = [s['dur_ms'] for s in run.get('spans', ())
          if s['name'] == 'fused_fit.dispatch']
    return harness.median(ms) if ms else None
