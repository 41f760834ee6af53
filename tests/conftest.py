"""Test harness config: run on a virtual 8-device CPU mesh.

This is the TPU-world analog of the reference's multiple-cpu-context testing
(tests/python/unittest/test_multi_device_exec.py uses mx.cpu(1), mx.cpu(2));
XLA_FLAGS=--xla_force_host_platform_device_count=8 gives 8 independent CPU
devices so sharding/mesh/kvstore paths are exercised without TPU hardware.

The platform list is pinned to ``cpu`` here, by the caller and in the open:
that is context.py's CPU-mesh mode, in which mx.tpu(i)/mx.gpu(i) name the
i-th virtual CPU device. MXTPU_TEST_TPU=1 (the tests/tpu consistency tier,
run on the machine with the chip) leaves the chip visible beside the host
CPU instead. Nothing here touches a device while it is imported.
"""
import faulthandler
import os
import re

import pytest

_flags = os.environ.get('XLA_FLAGS', '')
_flags = re.sub(r'--xla_force_host_platform_device_count=\d+', '', _flags)
os.environ['XLA_FLAGS'] = (_flags + ' --xla_force_host_platform_device_count=8').strip()

_platforms = 'tpu,cpu' if os.environ.get('MXTPU_TEST_TPU') == '1' else 'cpu'
os.environ['JAX_PLATFORMS'] = _platforms

import jax  # noqa: E402

jax.config.update('jax_platforms', _platforms)
# full-f32 matmul/conv so finite-difference gradient checks are meaningful
# (the default bf16-grade MXU precision is what bench/production uses)
jax.config.update('jax_default_matmul_precision', 'float32')


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'slow: multi-process / long-running integration test')
    config.addinivalue_line(
        'markers', 'convergence: example/compat convergence run '
        '(minutes-scale subprocess); deselect with -m "not convergence" '
        'for the fast correctness tier')
    config.addinivalue_line(
        'markers', 'chaos: fault-injection / recovery test '
        '(MXTPU_FAULT_INJECT harness; tier-1-safe, CPU-only, each '
        'under 30s) — select with -m chaos to drill the restart paths')


# A test that hangs (a deadlocked thread, a child that never answers) must
# cost the run one failure, not its whole time limit: under `--dist
# loadfile` a stuck worker is otherwise only ended by the driver's clock,
# and every test queued behind it is lost. Past this many seconds the
# worker dumps every thread's stack to stderr and exits; xdist reports the
# test as failed and hands the rest of the file to a new worker. Above
# every limit a test sets itself (the longest is 300 s), far above the
# slowest test under six workers (about 100 s).
TEST_HARD_LIMIT_S = 420


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    faulthandler.dump_traceback_later(TEST_HARD_LIMIT_S, exit=True)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


def pytest_sessionstart(session):
    """Truncate the coverage accumulation file at session START so
    stale lines from a previous run can never mask a newly-uncovered
    op; subprocesses spawned during THIS session still append."""
    assert len(jax.devices('cpu')) == 8, \
        'virtual 8-device CPU mesh failed to come up'
    path = os.environ.get('MXTPU_OP_COVERAGE_FILE', '')
    if path:
        open(path, 'w').close()


def op_coverage_missing():
    """Registered-but-never-invoked ops: the union of this process's
    recorded invocations and the MXTPU_OP_COVERAGE_FILE accumulation
    (subprocess test cases append there at exit), grouped by OpDef so
    aliases count for each other. Pure-host codec ops with
    data-dependent shapes still execute via nd.* (recorded in
    _jitted_impl/host paths), so no exemptions are needed."""
    from mxnet_tpu.ops import registry
    invoked = set(registry.invoked_names())
    path = os.environ.get('MXTPU_OP_COVERAGE_FILE', '')
    if path and os.path.exists(path):
        with open(path) as f:
            invoked.update(ln.strip() for ln in f if ln.strip())
    missing = []
    for names in registry.op_alias_groups():
        if not any(n in invoked for n in names):
            missing.append(min(names, key=len))
    return sorted(missing)


def pytest_sessionfinish(session, exitstatus):
    """Execution-based op-coverage gate: with
    MXTPU_OP_COVERAGE_FILE set, the full suite must INVOKE every
    registered op — a registered-but-broken op whose name only appears
    in a comment now fails the session. Opt-in (a partial run would
    fail spuriously); the grep gate in test_op_sweep.py remains as the
    always-on fallback."""
    if not os.environ.get('MXTPU_OP_COVERAGE_FILE'):
        return
    if exitstatus != 0:
        return      # don't mask real failures with the coverage report
    missing = op_coverage_missing()
    if missing:
        import sys as _sys
        _sys.stderr.write(
            '\n[op-coverage gate] %d registered ops were never INVOKED '
            'during this session:\n  %s\n'
            % (len(missing), '\n  '.join(missing)))
        session.exitstatus = 1
