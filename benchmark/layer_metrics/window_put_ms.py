"""Median time the fit loop waits for a window's batches to be on the
device (span ``fused_fit.put``: what is left of the host stack and the one
``device_put`` per window after the side thread's head start)."""
from benchmark import harness


def read(run):
    ms = [s['dur_ms'] for s in run.get('spans', ())
          if s['name'] == 'fused_fit.put']
    return harness.median(ms) if ms else None
