"""Rehearsal of chip_smoke.py on the CPU mesh at tiny size: the script's own
phase functions, called with a small symbol under the pinned CPU platform
(context.py's CPU-mesh mode), so that a wrong path, argument or assertion
is found here and not on the chip. The script grows no option for this."""
import importlib.util
import os
import subprocess
import sys

import pytest

import jax
import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.config import flags

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope='module')
def smoke():
    spec = importlib.util.spec_from_file_location(
        'chip_smoke', os.path.join(ROOT, 'chip_smoke.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tele_on(tmp_path, monkeypatch):
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    for f in ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH'):
        flags.reload(f)
    telemetry._reset_for_tests()
    yield
    monkeypatch.undo()
    for f in ('MXTPU_TELEMETRY', 'MXTPU_TELEMETRY_PATH'):
        flags.reload(f)
    telemetry._reset_for_tests()


def _small_net(num_classes=10):
    data = mx.sym.Variable('data')
    body = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                              no_bias=True, name='conv0')
    body = mx.sym.BatchNorm(body, fix_gamma=False, name='bn0')
    body = mx.sym.Activation(body, act_type='relu', name='relu0')
    body = mx.sym.Pooling(body, global_pool=True, kernel=(2, 2),
                          pool_type='avg', name='pool0')
    fc = mx.sym.FullyConnected(mx.sym.Flatten(body), num_hidden=num_classes,
                               name='fc1')
    return mx.sym.SoftmaxOutput(fc, name='softmax')


def test_fit_then_serve_rehearsal(smoke, tele_on, tmp_path):
    mod, losses, W = smoke.phase_fit(
        mx, _small_net(), mx.tpu(0), (3, 8, 8), 10, 8, 3, 0, 'cpu')
    assert W == 4 and len(losses) == 3
    smoke.phase_serve(mx, mod, mx.tpu(0), str(tmp_path), (3, 8, 8), 8,
                      (1, 3, 8), 0, 'cpu')


def test_four_chip_rehearsal_on_virtual_devices(smoke, tele_on):
    smoke.phase_four_chips(mx, _small_net(), (3, 8, 8), 10, 16, 4, 2, 0,
                           'cpu')


def test_kernel_and_cache_phases_rehearsal(smoke, tele_on):
    dev = jax.devices('cpu')[0]
    smoke.phase_kernels(dev, full=False, compiled=False)
    assert smoke.phase_cache(dev) == jax.config.jax_compilation_cache_dir


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    for argv in ([], ['--four-chips']):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, 'chip_smoke.py')] + argv,
            env=env, capture_output=True, text=True, timeout=120)
        assert r.returncode != 0, r.stdout[-2000:]
        assert '"ok"' not in r.stdout, r.stdout[-2000:]
        assert 'TPU' in r.stderr
