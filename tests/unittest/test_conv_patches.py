"""MXTPU_CONV_BWD_PATCHES=1 parity: the patches-matmul weight gradient
equals the default conv_backprop_filter to numerical precision
(ops/nn.py _conv2d_patches_bwd).

The flag is parsed once per process, so each mode runs in ONE fresh
subprocess computing every case (2 jax startups total)."""
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CASES = [
    # (in_shape, w_shape, stride, dilate, pad)
    ((2, 3, 12, 12), (8, 3, 3, 3), (1, 1), (1, 1), (1, 1)),
    ((2, 4, 9, 9), (6, 4, 3, 3), (2, 2), (1, 1), (0, 0)),
    ((1, 2, 14, 14), (5, 2, 5, 5), (2, 2), (1, 1), (2, 2)),
    ((2, 3, 11, 11), (4, 3, 3, 3), (1, 1), (2, 2), (2, 2)),
    ((4, 8, 7, 7), (16, 8, 1, 1), (1, 1), (1, 1), (0, 0)),
]

_PROBE = r'''
import os, sys, json
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import jax.numpy as jnp
from mxnet_tpu.ops.nn import _conv_nd

results = []
for (ishape, wshape, stride, dilate, pad) in json.loads(sys.argv[1]):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*ishape), jnp.float32)
    w = jnp.asarray(rng.randn(*wshape), jnp.float32)

    def loss(x, w):
        return jnp.sum(jnp.tanh(_conv_nd(x, w, tuple(stride), tuple(dilate),
                                         tuple(pad), 1)))

    val, (gx, gw) = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
    results.append(dict(val=float(val),
                        gx=np.asarray(gx).ravel().tolist(),
                        gw=np.asarray(gw).ravel().tolist()))
print(json.dumps(results))
'''


def _run_probe(patches):
    env = dict(os.environ)
    env['PYTHONPATH'] = REPO
    env['JAX_PLATFORMS'] = 'cpu'
    if patches:
        env['MXTPU_CONV_BWD_PATCHES'] = '1'
    else:
        env.pop('MXTPU_CONV_BWD_PATCHES', None)
    r = subprocess.run([sys.executable, '-c', _PROBE, json.dumps(_CASES)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_patches_bwd_matches_default():
    default = _run_probe(patches=False)
    patched = _run_probe(patches=True)
    for case, a, b in zip(_CASES, default, patched):
        np.testing.assert_allclose(a['val'], b['val'], rtol=1e-5,
                                   err_msg=str(case))
        # FULL-array parity: any reshape/transpose slip must fail
        np.testing.assert_allclose(a['gx'], b['gx'], rtol=1e-4, atol=1e-5,
                                   err_msg=str(case))
        np.testing.assert_allclose(a['gw'], b['gw'], rtol=1e-4, atol=1e-5,
                                   err_msg=str(case))
