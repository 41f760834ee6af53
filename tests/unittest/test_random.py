"""Random samplers: determinism under seed, distribution moments.

Reference: tests/python/unittest/test_random.py (seeded reproducibility
+ moment checks per sampler) over src/operator/random/.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

N = (50, 50)  # 2500 samples: loose moment checks


def test_seed_reproducibility():
    mx.random.seed(42)
    a = nd.random.uniform(0, 1, shape=(4, 4)).asnumpy()
    b = nd.random.uniform(0, 1, shape=(4, 4)).asnumpy()
    assert not np.allclose(a, b)          # stream advances
    mx.random.seed(42)
    a2 = nd.random.uniform(0, 1, shape=(4, 4)).asnumpy()
    b2 = nd.random.uniform(0, 1, shape=(4, 4)).asnumpy()
    np.testing.assert_allclose(a, a2)
    np.testing.assert_allclose(b, b2)
    mx.random.seed(43)
    c = nd.random.uniform(0, 1, shape=(4, 4)).asnumpy()
    assert not np.allclose(a, c)


def test_uniform_moments_and_range():
    mx.random.seed(0)
    x = nd.random.uniform(-2, 3, shape=N).asnumpy()
    assert x.min() >= -2 and x.max() <= 3
    assert abs(x.mean() - 0.5) < 0.15
    assert abs(x.std() - np.sqrt(25 / 12.0)) < 0.15


def test_normal_moments():
    mx.random.seed(0)
    x = nd.random.normal(1.5, 2.0, shape=N).asnumpy()
    assert abs(x.mean() - 1.5) < 0.2
    assert abs(x.std() - 2.0) < 0.2


def test_gamma_moments():
    mx.random.seed(0)
    x = nd.random.gamma(3.0, 2.0, shape=N).asnumpy()
    # mean = alpha*beta, var = alpha*beta^2
    assert abs(x.mean() - 6.0) < 0.5
    assert abs(x.var() - 12.0) < 2.5
    assert (x > 0).all()


def test_exponential_moments():
    mx.random.seed(0)
    x = nd.random.exponential(0.5, shape=N).asnumpy()
    assert abs(x.mean() - 0.5) < 0.1
    assert (x >= 0).all()


def test_poisson_moments():
    mx.random.seed(0)
    x = nd.random.poisson(4.0, shape=N).asnumpy()
    assert abs(x.mean() - 4.0) < 0.3
    assert abs(x.var() - 4.0) < 0.8
    assert np.allclose(x, np.round(x))


def test_negative_binomial():
    mx.random.seed(0)
    x = nd.random.negative_binomial(5, 0.5, shape=N).asnumpy()
    # mean = k(1-p)/p = 5
    assert abs(x.mean() - 5.0) < 0.6
    assert (x >= 0).all()


def test_multinomial():
    mx.random.seed(0)
    probs = nd.array(np.array([[0.0, 0.1, 0.9]] * 4, np.float32))
    s = nd.random.multinomial(probs, shape=(100,)).asnumpy()
    assert s.shape == (4, 100)
    assert (s >= 1).all() and (s <= 2).all()
    assert (s == 2).mean() > 0.75


def test_shuffle_is_permutation():
    mx.random.seed(0)
    x = nd.array(np.arange(20, dtype=np.float32))
    y = nd.random.shuffle(x).asnumpy()
    assert sorted(y.tolist()) == list(range(20))


def test_nd_level_samplers():
    mx.random.seed(0)
    u = nd.random_uniform(low=0, high=1, shape=(3, 3))
    n = nd.random_normal(loc=0, scale=1, shape=(3, 3))
    assert u.shape == (3, 3) and n.shape == (3, 3)


def test_symbol_random_ops_in_graph():
    """Samplers compose into symbolic graphs (reference random ops are
    normal NNVM ops with a resource request)."""
    s = mx.sym.random_uniform(low=0, high=1, shape=(2, 2))
    out = s * 2
    ex = out.bind(mx.cpu(), {})
    mx.random.seed(7)
    a = ex.forward()[0].asnumpy()
    assert a.shape == (2, 2)
    assert (a >= 0).all() and (a <= 2).all()


def test_env_seed_matches_explicit_seed():
    """MXTPU_SEED=N must behave exactly as if the process began with
    mx.random.seed(N): same device key stream (no extra host draw) and
    same host-stream state (docs/env_vars.md)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    body = (
        "import jax; jax.config.update('jax_platforms', 'cpu');"
        "import sys; sys.path.insert(0, %r);"
        "{pre}"
        "import mxnet_tpu as mx; from mxnet_tpu import nd;"
        "{seed}"
        "u = nd.random.uniform(shape=(4,)).asnumpy().tolist();"
        "h = mx.random.host_rng().randint(0, 10**9);"
        "print('OUT', u, h)" % repo)

    def run(pre_env, body_):
        env = {k: v for k, v in os.environ.items() if k != 'MXTPU_SEED'}
        env['JAX_PLATFORMS'] = 'cpu'
        env.update(pre_env)
        out = subprocess.run([sys.executable, '-c', body_], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return [ln for ln in out.stdout.splitlines()
                if ln.startswith('OUT')][0]

    via_env = run({'MXTPU_SEED': '11'},
                  body.format(pre='', seed=''))
    via_call = run({}, body.format(pre='', seed='mx.random.seed(11);'))
    assert via_env == via_call
    # malformed values must not break import
    bad = run({'MXTPU_SEED': 'auto'},
              body.format(pre='import warnings;'
                          'warnings.simplefilter("ignore");', seed=''))
    assert bad.startswith('OUT')


def test_module_level_samplers():
    """Reference random.py:25-31 re-exports the sampling ops at module
    level; scripts call mx.random.uniform(low, high, shape=..., ctx=...)
    (example/profiler/profiler_executor.py:117)."""
    u = mx.random.uniform(-1.0, 1.0, shape=(64,), ctx=mx.cpu())
    a = u.asnumpy()
    assert a.shape == (64,) and a.min() >= -1.0 and a.max() <= 1.0
    n = mx.random.normal(0.0, 1.0, shape=(3, 4))
    assert n.shape == (3, 4)
    g = mx.random.gamma(2.0, 1.0, shape=(8,))
    assert (g.asnumpy() > 0).all()
    e = mx.random.exponential(1.0, shape=(8,))
    assert (e.asnumpy() >= 0).all()
    p = mx.random.poisson(3.0, shape=(8,))
    assert (p.asnumpy() >= 0).all()
    nb = mx.random.negative_binomial(2, 0.4, shape=(8,))
    gnb = mx.random.generalized_negative_binomial(2.0, 0.3, shape=(8,))
    assert nb.shape == (8,) and gnb.shape == (8,)
