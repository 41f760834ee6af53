"""Run one example script as a child process on the pinned 8-device CPU
mesh (shared by the test_examples_*.py files: one file per example family,
so that `--dist loadfile` can share the families out over the workers).

Success = exit 0: each script asserts its own training behaviour. The
child gets the CPU platform from its environment and its own time limit;
it never needs the chip.
"""
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))

# seconds one example may take; the slowest take 100-115 s here when six
# workers share the machine and passed 150 when the machine was busier
# still (PR 45's second whole run: two examples ended by the limit, nothing
# of theirs having changed). tests/conftest.py ends any test after 420 s
LIMIT_S = 300


def run_example(script, args, limit_s=LIMIT_S):
    env = dict(os.environ)
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = ROOT
    code = ("import sys, runpy; sys.argv=[%r]+%r;"
            "runpy.run_path(%r, run_name='__main__')"
            % (script, list(args), os.path.join(ROOT, 'examples', script)))
    proc = subprocess.run([sys.executable, '-c', code], env=env,
                          capture_output=True, text=True, timeout=limit_s,
                          cwd=os.path.join(ROOT, 'examples',
                                           os.path.dirname(script)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc
