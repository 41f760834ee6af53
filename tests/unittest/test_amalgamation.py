"""Amalgamation (N19): single-file build of the C ABI + predict API.

Reference: `amalgamation/` concatenates a predict-only MXNet into one
.cc for embedding targets. Here `amalgamation/amalgamate.py` emits one
translation unit carrying the full ABI (the predict API's bridge lives
in c_api.cc), and the SAME 146-function C driver that gates the normal
build (tests/capi/test_capi.c) must pass against the amalgamated lib.
"""
import os
import subprocess

import pytest

from test_c_api import REPO, SRC, _clean_env

AMALG = os.path.join(REPO, 'amalgamation')


@pytest.mark.slow
def test_amalgamated_lib_passes_c_driver(tmp_path):
    gen = str(tmp_path / 'mxnet_tpu_predict-all.cc')
    r = subprocess.run(
        ['python3', os.path.join(AMALG, 'amalgamate.py'), '-o', gen],
        check=True, capture_output=True, text=True, timeout=300)
    assert 'wrote' in r.stdout
    # single TU: no other .cc may be needed
    lib = str(tmp_path / 'libmxnet_tpu_predict.so')
    inc = subprocess.run(['python3-config', '--includes'],
                         capture_output=True, text=True, timeout=300).stdout.split()
    ld = subprocess.run(['python3-config', '--ldflags', '--embed'],
                        capture_output=True, text=True, timeout=300).stdout.split()
    subprocess.run(['g++', '-std=c++17', '-O2', '-fPIC', '-Wall',
                    '-pthread'] + inc + ['-shared', '-o', lib, gen] + ld,
                   check=True, capture_output=True, text=True, timeout=300)
    exe = str(tmp_path / 'test_capi_amalg')
    subprocess.run(['gcc', '-o', exe, SRC, lib,
                    '-Wl,-rpath,' + str(tmp_path), '-lm'],
                   check=True, capture_output=True, text=True, timeout=300)
    r = subprocess.run([exe], env=_clean_env(), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, 'amalgamated driver failed:\n%s\n%s' % (
        r.stdout, r.stderr)
    assert 'ALL C API TESTS PASSED' in r.stdout
    assert 'predict ok' in r.stdout
