"""Seeded parameters of the decoder configurations, made once and handed to
the program and to the plain reference alike (as ``weights.py`` does for the
convolutional ones, whose rules do not fit: a fan-in there is every axis but
the first).

Made on the host with numpy, every leaf from a generator of its own
(Philox keyed by the seed and the leaf's place among the sorted names), the
leaves spread over a few threads: 811 M values take seconds, touch no
device and wait for no compilation (the first form drew them in one jitted
call on the chip and fetched 3.2 GB back: 25 s of every run's set-up).
Rules, by the parameter's name and shape: ``*_weight`` is normal with std
1/sqrt(fan_in), where fan_in is the contracted axis: axis 1, both for a
projection's ``(out, in)`` and for the experts' ``(experts_held, in,
out)``; ``embed_weight`` is normal with std 1 (its rows are looked up, not
contracted, and the first RMSNorm rescales them); ``*_gamma`` is 1 (gains
1); ``*_stats`` (the expert layers' auxiliary state) is 0. Values are
rounded to bfloat16 (to nearest, ties to even) and kept in float32, so
that the program's float32 masters and the reference start from the same
numbers.
"""
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def round_to_bf16(v):
    """float32 array `v` rounded in place to the nearest bfloat16 value,
    ties to even (what ``lax.reduce_precision(v, 8, 7)`` gives for finite
    values)."""
    u = v.view(np.uint32)
    u += ((u >> 16) & 1) + np.uint32(0x7FFF)
    u &= np.uint32(0xFFFF0000)
    return v


def make_leaf(name, shape, seed, place, round_bf16=True):
    shape = tuple(shape)
    if name.endswith('_gamma'):
        return np.ones(shape, np.float32)
    if name.endswith('_stats'):
        return np.zeros(shape, np.float32)
    if not name.endswith('_weight'):
        raise ValueError('no initialisation rule for %r' % name)
    rng = np.random.Generator(np.random.Philox(key=[int(seed), place]))
    v = rng.standard_normal(shape, dtype=np.float32)
    if name != 'embed_weight':
        v *= np.float32(1.0 / math.sqrt(shape[1]))
    return round_to_bf16(v) if round_bf16 else v


def make_params(shapes, seed, round_bf16=True, threads=None):
    """{name: float32 numpy array} for {name: shape}."""
    names = sorted(shapes)
    threads = threads or max(1, min(8, (os.cpu_count() or 2) - 1))
    # the largest leaves first, so that no thread ends with one alone
    order = sorted(range(len(names)),
                   key=lambda i: -int(np.prod(shapes[names[i]])))
    with ThreadPoolExecutor(threads) as pool:
        made = dict(pool.map(
            lambda i: (names[i], make_leaf(names[i], shapes[names[i]], seed,
                                           i, round_bf16)), order))
    return {n: made[n] for n in names}
