"""Seeded parameters, made once and handed to the program and to the plain
reference alike: the reference takes nothing the program has made.

All leaves come out of ONE jitted call on the device. Rules, by the
parameter's name: ``*_weight`` is He-normal (std sqrt(2 / fan_in), fan_in
the product of every axis but the first), ``*_bias`` and ``*_beta`` are 0,
``*_gamma`` is 1 unless the configuration's ``init.gamma`` names a suffix
with another value, ``*_mean`` is 0 and ``*_var`` is 1. Where the
configuration computes in bfloat16, values are rounded to bfloat16 (and
kept in float32), so that the program's float32 masters and the
reference start from the same numbers.
"""
import math

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A key from any whole number, also past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7fffffff)


def make_params(shapes, seed, init=None, round_bf16=True):
    """{name: float32 array} for {name: shape}."""
    gamma = (init or {}).get('gamma', {})
    names = sorted(shapes)

    def build(key):
        out = {}
        for i, n in enumerate(names):
            shape = tuple(shapes[n])
            if n.endswith('_weight'):
                fan_in = max(1, math.prod(shape[1:]))
                v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * math.sqrt(2.0 / fan_in)
            elif n.endswith('_gamma'):
                value = 1.0
                for suffix, g in gamma.items():
                    if n.endswith(suffix):
                        value = g
                v = jnp.full(shape, value, jnp.float32)
            elif n.endswith('_var'):
                v = jnp.ones(shape, jnp.float32)
            elif n.endswith(('_bias', '_beta', '_mean')):
                v = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError('no initialisation rule for %r' % n)
            if round_bf16:
                v = v.astype(jnp.bfloat16).astype(jnp.float32)
            out[n] = v
        return out

    return jax.jit(build)(seed_key(seed))
