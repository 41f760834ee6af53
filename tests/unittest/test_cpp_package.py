"""cpp-package (N20): compile and run the pure-C++ MLP example.

Reference: cpp-package/example/mlp.cpp + tests/cpp — a C++ consumer
building symbols, binding an executor, and training with manual SGD,
entirely through the C ABI.
"""
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def _clean_env():
    """Subprocess env for the embedded-interpreter binaries: the CPU
    platform, pinned, and the repo on the path."""
    env = dict(os.environ)
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    env['JAX_PLATFORMS'] = 'cpu'
    return env



def _build_and_run(example, marker, tmp_path):
    """Build the lib, compile one cpp-package example, run it, check
    its success marker."""
    subprocess.run(['make', '-C', os.path.join(REPO, 'src'),
                    os.path.join('..', 'lib', 'libmxnet_tpu.so')],
                   check=True, capture_output=True, text=True, timeout=300)
    exe = str(tmp_path / os.path.splitext(example)[0])
    subprocess.run(
        ['g++', '-std=c++17', '-o', exe,
         os.path.join(REPO, 'cpp-package', 'example', example),
         '-I' + os.path.join(REPO, 'cpp-package', 'include'),
         '-L' + os.path.join(REPO, 'lib'), '-lmxnet_tpu',
         '-Wl,-rpath,' + os.path.join(REPO, 'lib')],
        check=True, capture_output=True, text=True, timeout=300)
    r = subprocess.run([exe], env=_clean_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, '%s failed:\n%s\n%s' % (example, r.stdout,
                                                      r.stderr)
    assert marker in r.stdout


@pytest.mark.slow
def test_cpp_mlp_example(tmp_path):
    _build_and_run('mlp.cpp', 'cpp-package mlp ok', tmp_path)


@pytest.mark.slow
def test_cpp_lenet_example(tmp_path):
    """LeNet built from the GENERATED op.h factories, fed by
    MXDataIter(MNISTIter), trained with OptimizerRegistry SGD — the
    reference cpp-package/example/lenet.cpp workflow."""
    _build_and_run('lenet.cpp', 'cpp-package lenet ok', tmp_path)


def test_op_h_is_up_to_date(tmp_path):
    """The committed generated header matches a fresh generator run."""
    out = str(tmp_path / 'op.h')
    gen = subprocess.run(
        ['python', os.path.join(REPO, 'cpp-package', 'OpWrapperGenerator.py'),
         out], capture_output=True, text=True, env=_clean_env(), timeout=300)
    assert gen.returncode == 0, gen.stderr
    committed = open(os.path.join(REPO, 'cpp-package', 'include',
                                  'mxnet-cpp', 'op.h')).read()
    assert open(out).read() == committed, \
        'op.h is stale: rerun python cpp-package/OpWrapperGenerator.py'


@pytest.mark.slow
def test_cpp_train_api_example(tmp_path):
    """Xavier initializer + OptimizerRegistry adagrad/adadelta +
    Accuracy/LogLoss metrics + FactorScheduler, pure C++ (the
    initializer.h/metric.h surfaces of the reference cpp-package)."""
    _build_and_run('train_api.cpp', 'TRAIN_API_OK', tmp_path)
