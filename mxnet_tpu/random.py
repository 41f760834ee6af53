"""Global PRNG state — stateful seed API over JAX's stateless keys.

Reference: python/mxnet/random.py (mx.random.seed) + src/resource.cc:84
(per-device seedable mshadow PRNG pools). TPU-native: a process-global
counter-split key; every random op consumes one fresh subkey, passed to the
op as a trailing array argument so the op itself stays pure/jittable.
"""
import os as _os
import random as _pyrandom
import threading

import jax
import numpy as _np

__all__ = ['seed', 'next_key', 'get_state', 'set_state',
           'host_rng', 'host_pyrng',
           'uniform', 'normal', 'gamma', 'exponential', 'poisson',
           'negative_binomial', 'generalized_negative_binomial']

_lock = threading.Lock()
# lazy: creating a key initializes the jax backend, which must not happen
# at import time (a process that only imports the package must not take
# the chip)
_key = None
# MXTPU_SEED: seed every framework stream at import, exactly as if the
# process's first statement were mx.random.seed(N) — lets unmodified
# scripts (which never call seed) run hermetically, e.g. in CI. The
# device key stream honors it too (next_key's lazy init uses PRNGKey(N)
# directly, with no extra host draw).
_env_seed = None
_env_raw = _os.environ.get('MXTPU_SEED', '').strip()
if _env_raw:
    try:
        _env_seed = int(_env_raw)
    except ValueError:
        import warnings as _warnings
        _warnings.warn('MXTPU_SEED=%r is not an integer; ignoring it'
                       % _env_raw)
# framework-private host-side stream for initializers / iterator shuffles.
# Private so mx.random.seed is hermetic WITHOUT clobbering the user's
# process-global numpy state (the reference's mx.random.seed doesn't
# touch numpy either).
_host_rng = _np.random.RandomState(
    _env_seed % (2 ** 32) if _env_seed is not None else None)
_host_pyrng = _pyrandom.Random(_env_seed)


def host_rng():
    """The framework's host-side numpy stream (initializers, shuffles)."""
    return _host_rng


def host_pyrng():
    """The framework's host-side stdlib stream (augmenter gates etc.)."""
    return _host_pyrng


def seed(seed_state):
    """Seed all framework RNG streams (reference random.py:30
    mx.random.seed): the device key stream AND the framework's host-side
    stream that initializers / iterator shuffles draw from — without
    the latter, suite ordering leaks into init and `seed` is not
    hermetic."""
    global _key
    with _lock:
        _key = jax.random.PRNGKey(int(seed_state))
        _host_rng.seed(int(seed_state) % (2 ** 32))
        _host_pyrng.seed(int(seed_state))


def get_state():
    """Snapshot every framework RNG stream for checkpointing
    (module/checkpointing.py): the device key (numpy uint32 array, or
    None while the stream is still lazily uninitialized), the host
    numpy stream and the host stdlib stream. The host states come back
    as JSON-serializable nested lists so they can ride a checkpoint's
    metadata record."""
    with _lock:
        key = None if _key is None else _np.asarray(_key).copy()
    st = _host_rng.get_state()
    np_state = [st[0], _np.asarray(st[1]).tolist(), int(st[2]),
                int(st[3]), float(st[4])]

    def _listify(obj):
        if isinstance(obj, tuple):
            return [_listify(x) for x in obj]
        return obj

    return {'key': key, 'numpy': np_state,
            'python': _listify(_host_pyrng.getstate())}


def set_state(state):
    """Restore a :func:`get_state` snapshot — the checkpoint-resume
    path: after this, the key/shuffle/augment streams continue exactly
    where the saved run left them."""
    global _key
    import jax.numpy as jnp

    def _tupleize(obj):
        if isinstance(obj, list):
            return tuple(_tupleize(x) for x in obj)
        return obj

    with _lock:
        key = state.get('key')
        _key = None if key is None else jnp.asarray(_np.asarray(key))
    np_state = state.get('numpy')
    if np_state is not None:
        _host_rng.set_state((np_state[0],
                             _np.asarray(np_state[1], _np.uint32),
                             int(np_state[2]), int(np_state[3]),
                             float(np_state[4])))
    py_state = state.get('python')
    if py_state is not None:
        _host_pyrng.setstate(_tupleize(py_state))


def next_key():
    """Split one subkey off the global stream."""
    global _key
    with _lock:
        if _key is None:
            # MXTPU_SEED path: PRNGKey(N) directly, exactly what
            # mx.random.seed(N) would have set — and no host draw, so
            # host-stream consumers stay aligned with the seed() path
            _key = jax.random.PRNGKey(
                _env_seed if _env_seed is not None
                else _host_rng.randint(0, 2**31 - 1))
        _key, sub = jax.random.split(_key)
        return sub


def _sampler(op_name):
    # reference random.py:25-31 re-exports the sampling ops at module
    # level (uniform/normal/... — in 0.11 these are the scalar-param
    # SampleUniformParam family); resolved lazily so importing
    # mx.random never forces the op registry/backend up
    def fn(*args, **kwargs):
        from . import ndarray as _nd
        return getattr(_nd, op_name)(*args, **kwargs)
    fn.__name__ = op_name
    fn.__doc__ = ('mx.random.%s — alias of nd.%s (reference '
                  'random.py:25-31)' % (op_name, op_name))
    return fn


uniform = _sampler('uniform')
normal = _sampler('normal')
gamma = _sampler('random_gamma')
exponential = _sampler('random_exponential')
poisson = _sampler('random_poisson')
negative_binomial = _sampler('random_negative_binomial')
generalized_negative_binomial = _sampler(
    'random_generalized_negative_binomial')
