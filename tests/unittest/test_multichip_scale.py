"""Multi-chip dryrun at width: n=16 and n=32 virtual
meshes light up sp/ep in the PRIMARY round-robin mesh (16 → dp2.tp2.pp2.sp2,
32 → all five axes at 2), and every parity assert inside
__graft_entry__.dryrun_multichip must hold — the n-device loss
trajectory equals a 1-device run of the same model/data, so "ok" means
*correct*, not just *ran* (reference analogue: the exact-arithmetic
style of tests/nightly/dist_sync_kvstore.py:28-80).

Each width needs its own process: the virtual device count is fixed at
backend init by --xla_force_host_platform_device_count.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))

@pytest.mark.parametrize('n', [16, 32])
def test_dryrun_multichip_at_width(n):
    env = dict(os.environ)
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=%d' % n
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = os.pathsep.join(
        p for p in [REPO, env.get('PYTHONPATH', '')] if p)
    code = ("from __graft_entry__ import dryrun_multichip;"
            "dryrun_multichip(%d)" % n)
    proc = subprocess.run([sys.executable, '-c', code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    # the primary mesh at this width must include the wide axes...
    if n == 16:
        assert "'sp': 2" in out, out[-2000:]
    else:
        assert "'sp': 2" in out and "'ep': 2" in out, out[-2000:]
    # ...and every parity assert must have fired and passed
    assert out.count('parity') >= 1, out[-2000:]
    assert 'OK' in out, out[-2000:]
