"""5-axis parallel train step: parallel == serial, and it learns.

The reference establishes multi-device correctness by running the same
graph on multiple cpu() contexts (tests/python/unittest/
test_multi_device_exec.py); here the analog is: the SAME program on an
8-device mesh (pp*dp*tp or sp splits) must produce the same loss and
learning curve as on a trivial 1-device mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel.five_d import (TransformerConfig, full_mesh,
                                       init_params, make_loss_fn,
                                       make_5d_train_step)

CFG = TransformerConfig(vocab=61, d_model=16, n_heads=4, ffn=16, experts=2)

def _data(n_micro=3, batch=4, seq=8, seed=0):
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, CFG.vocab, (n_micro, batch, seq)).astype(np.int32)
    tgts = rng.randint(0, CFG.vocab, (n_micro, batch, seq)).astype(np.int32)
    return jnp.asarray(toks), jnp.asarray(tgts)


def _loss_on(axes):
    mesh = full_mesh(axes)
    params = init_params(CFG, mesh, seed=7)
    toks, tgts = _data()
    return float(make_loss_fn(CFG, mesh)(params, toks, tgts))


def test_parallel_matches_serial():
    serial = _loss_on({'dp': 1})
    for axes in ({'dp': 2, 'tp': 2}, {'sp': 2, 'dp': 2},
                 {'ep': 2, 'tp': 2}, {'dp': 2, 'sp': 2, 'tp': 2}):
        par = _loss_on(axes)
        assert np.isclose(serial, par, rtol=2e-4), (axes, serial, par)


def test_pipeline_matches_serial():
    # pp>1 runs the same math through the GPipe schedule
    serial = _loss_on({'dp': 1})
    # pp=1 vs pp alone vs pp composed with other axes
    for axes in ({'pp': 2}, {'pp': 2, 'dp': 2}, {'pp': 2, 'tp': 2, 'sp': 2}):
        par = _loss_on(axes)
        assert np.isclose(serial, par, rtol=2e-4), (axes, serial, par)


def test_train_step_learns_and_syncs():
    mesh = full_mesh({'pp': 2, 'dp': 2, 'tp': 2})
    init_state, step = make_5d_train_step(CFG, mesh, lr=0.5)
    state = init_state(seed=3)
    toks, tgts = _data(seed=1)
    # learn the (fixed) random mapping: loss must drop monotonically-ish
    losses = []
    for _ in range(8):
        state, loss = step(state, toks, tgts)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0] - 0.5, losses

    # gradient flows to every parameter group (incl. pipeline stage 1,
    # both experts, and the embedding behind the schedule masking)
    mesh1 = full_mesh({'dp': 1})
    params1 = init_params(CFG, mesh1, seed=3)
    grads = jax.jit(jax.grad(make_loss_fn(CFG, mesh1)))(params1, toks, tgts)
    for name, g in grads.items():
        assert float(jnp.max(jnp.abs(g))) > 0, name
