"""C ABI tests (N13 + N19): build lib/libmxnet_tpu.so, compile the pure-C
driver, and run it in a subprocess (the binary embeds its own interpreter).

Reference test strategy: the C API is exercised indirectly by every
frontend in the reference; here the standalone C driver plays the role
of an amalgamation/cpp-package consumer (tests/cpp + amalgamation demo).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def _clean_env():
    """Subprocess env for the embedded-interpreter binaries: the CPU
    platform, pinned, and the repo on the path."""
    env = dict(os.environ)
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    env['JAX_PLATFORMS'] = 'cpu'
    return env

LIB = os.path.join(REPO, 'lib', 'libmxnet_tpu.so')
SRC = os.path.join(REPO, 'tests', 'capi', 'test_capi.c')


def _build_lib():
    subprocess.run(['make', '-C', os.path.join(REPO, 'src'),
                    os.path.join('..', 'lib', 'libmxnet_tpu.so')],
                   check=True, capture_output=True, text=True, timeout=300)


def _build_driver(tmp_path):
    exe = str(tmp_path / 'test_capi')
    subprocess.run(['gcc', '-o', exe, SRC, '-L' + os.path.join(REPO, 'lib'),
                    '-lmxnet_tpu', '-Wl,-rpath,' + os.path.join(REPO, 'lib'),
                    '-lm'], check=True, capture_output=True, text=True, timeout=300)
    return exe


@pytest.mark.slow
def test_c_api_driver(tmp_path):
    _build_lib()
    exe = _build_driver(tmp_path)
    env = _clean_env()
    r = subprocess.run([exe], env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, 'c api driver failed:\n%s\n%s' % (r.stdout, r.stderr)
    assert 'ALL C API TESTS PASSED' in r.stdout


def test_bridge_helpers_roundtrip():
    """The bridge module is plain Python — exercise it in-process too so
    failures localize without the C layer."""
    import numpy as np
    from mxnet_tpu import _c_api_impl as impl

    h = impl.nd_create((2, 3), 1, 0, 0, 0)
    impl.nd_sync_copy_from_bytes(h, np.arange(6, dtype=np.float32).tobytes(), 0)
    assert impl.nd_shape(h) == (2, 3)
    assert impl.nd_dtype(h) == 0
    outs = impl.imperative_invoke('_plus', [h, h], [], [], 0, [])
    np.testing.assert_allclose(outs[0].asnumpy().ravel(),
                               2 * np.arange(6, dtype=np.float32))

    # symbol compose-in-place semantics (what MXSymbolCompose relies on)
    atom = impl.symbol_create_atomic('FullyConnected', ['num_hidden'], ['4'])
    x = impl.symbol_create_variable('x')
    impl.symbol_compose_inplace(atom, 'fc1', ['data'], [x])
    assert impl.symbol_list_arguments(atom) == ['x', 'fc1_weight', 'fc1_bias']
    ash, osh, _ = impl.symbol_infer_shape(atom, ['x'], [0, 2], [2, 3], 0)
    assert osh == [(2, 4)]
    impl.symbol_free(atom)

    # raw bytes roundtrip
    blob = impl.nd_save_raw_bytes(h)
    h2 = impl.nd_load_from_raw_bytes(blob)
    np.testing.assert_allclose(h2.asnumpy(), h.asnumpy())


REF_HEADER = '/root/reference/include/mxnet/c_api.h'


REF_PRED_HEADER = '/root/reference/include/mxnet/c_predict_api.h'


@pytest.mark.skipif(not os.path.exists(REF_HEADER),
                    reason='reference tree not present')
@pytest.mark.parametrize('ref_header,our_header', [
    (REF_HEADER, 'c_api.h'),
    (REF_PRED_HEADER, 'c_predict_api.h'),
])
def test_c_api_name_parity(ref_header, our_header):
    """Every MX* function the reference headers declare exists in ours
    (156/156 across c_api.h + c_predict_api.h) and is exported by the
    built library. Covers BOTH headers so a predict-ABI hole like the
    round-4 MXPredPartialForward miss cannot recur."""
    import re
    ref = open(ref_header).read()
    ours = open(os.path.join(REPO, 'include', 'mxnet_tpu', our_header)).read()
    ref_names = set(re.findall(r'MXNET_DLL\s+\w+\s+(MX\w+)\(', ref))
    our_names = set(re.findall(r'\b(MX\w+)\(', ours))
    missing = sorted(ref_names - our_names)
    assert not missing, 'header missing: %s' % missing
    _build_lib()
    r = subprocess.run(['nm', '-D', LIB], capture_output=True, text=True, timeout=300)
    exported = set(l.split()[-1] for l in r.stdout.splitlines()
                   if ' T MX' in l)
    unexported = sorted(n for n in ref_names if n not in exported)
    assert not unexported, 'not exported: %s' % unexported
