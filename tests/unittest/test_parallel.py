"""parallel/ package tests on the virtual 8-device CPU mesh.

Strategy mirrors the reference's multi-device testing
(tests/python/unittest/test_multi_device_exec.py uses multiple cpu
contexts): every parallel kernel is checked numerically against its
single-device oracle.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import functools

from mxnet_tpu.parallel import shard_map

import mxnet_tpu as mx
from mxnet_tpu.parallel import (
    make_mesh, local_mesh, DeviceMesh, ShardingPlan, shard_params,
    make_train_step, ShardedTrainer, ring_attention, blockwise_attention,
    ulysses_attention, make_ring_attention, attention_reference,
    pipeline_apply, stack_stage_params)
from mxnet_tpu.parallel.data_parallel import sgd_rule, adam_rule


def test_mesh_construction():
    m = make_mesh({'dp': 4, 'tp': 2})
    assert m.size == 8
    assert m.axis_size('dp') == 4 and m.axis_size('tp') == 2
    # tp must be the innermost axis (adjacent device ids)
    assert m.axis_names[-1] == 'tp'
    m1 = local_mesh(8)
    assert m1.axis_size('dp') == 8


def test_collectives_inside_shard_map():
    from mxnet_tpu.parallel import collectives as C
    mesh = local_mesh(8)
    x = jnp.arange(8.0)

    @functools.partial(shard_map, mesh=mesh.mesh, in_specs=P('dp'),
                       out_specs=P('dp'), check_vma=False)
    def f(v):
        total = C.allreduce(v, 'dp')
        rank = C.axis_index('dp')
        return total + 0 * v + rank

    out = np.asarray(f(x))
    assert np.allclose(out, 28.0 + np.arange(8))


def test_reduce_scatter_allgather_roundtrip():
    from mxnet_tpu.parallel import collectives as C
    mesh = local_mesh(8)
    x = jnp.arange(64.0).reshape(8, 8)

    @functools.partial(shard_map, mesh=mesh.mesh, in_specs=P(None, None),
                       out_specs=P('dp', None), check_vma=False)
    def f(v):
        shard = C.reduce_scatter(v, 'dp')        # each device: 8 * its row
        assert shard.shape == (1, 8)
        return shard

    out = np.asarray(f(x))
    assert np.allclose(out, np.asarray(x) * 8)


def test_data_parallel_matches_single_device():
    """The sharded jitted step must equal the plain single-device step."""
    rng = np.random.RandomState(0)
    w = rng.randn(16, 4).astype(np.float32)
    b = np.zeros(4, np.float32)
    X = rng.randn(64, 16).astype(np.float32)
    Y = rng.randn(64, 4).astype(np.float32)

    def loss_fn(params, batch, key):
        x, y = batch
        pred = x @ params['w'] + params['b']
        return jnp.mean((pred - y) ** 2)

    mesh = local_mesh(8)
    trainer = ShardedTrainer(loss_fn, {'w': w, 'b': b}, mesh,
                             optimizer=sgd_rule(lr=0.1))
    # reference: pure numpy GD on the same loss
    w_ref, b_ref = w.copy(), b.copy()
    for _ in range(5):
        loss = trainer.step((jnp.asarray(X), jnp.asarray(Y)))
        pred = X @ w_ref + b_ref
        gw = 2 * X.T @ (pred - Y) / (64 * 4)
        gb = 2 * (pred - Y).sum(0) / (64 * 4)
        w_ref -= 0.1 * gw
        b_ref -= 0.1 * gb
    assert np.allclose(np.asarray(trainer.params['w']), w_ref, atol=1e-4)
    assert np.allclose(np.asarray(trainer.params['b']), b_ref, atol=1e-4)
    assert float(loss) > 0


def test_tensor_parallel_dense():
    """Megatron column+row split matmul chain == unsharded chain."""
    rng = np.random.RandomState(1)
    x = rng.randn(8, 32).astype(np.float32)
    w1 = rng.randn(32, 64).astype(np.float32)   # column-split on tp
    w2 = rng.randn(64, 32).astype(np.float32)   # row-split on tp
    mesh = make_mesh({'dp': 2, 'tp': 4})
    plan = ShardingPlan([
        (r'w1', P(None, 'tp')),
        (r'w2', P('tp', None)),
    ])
    params = shard_params({'w1': jnp.asarray(w1), 'w2': jnp.asarray(w2)},
                          mesh, plan)

    @jax.jit
    def f(p, x):
        h = jax.nn.relu(x @ p['w1'])
        return h @ p['w2']

    out = np.asarray(f(params, jnp.asarray(x)))
    ref = np.maximum(x @ w1, 0) @ w2
    assert np.allclose(out, ref, atol=1e-4)


@pytest.mark.parametrize('causal', [False, True])
def test_blockwise_attention(causal):
    rng = np.random.RandomState(2)
    q = rng.randn(2, 32, 4, 8).astype(np.float32)
    k = rng.randn(2, 32, 4, 8).astype(np.float32)
    v = rng.randn(2, 32, 4, 8).astype(np.float32)
    ref = np.asarray(attention_reference(*map(jnp.asarray, (q, k, v)), causal=causal))
    out = np.asarray(blockwise_attention(*map(jnp.asarray, (q, k, v)),
                                         block_size=8, causal=causal))
    assert np.allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize('impl', ['ring', 'ulysses'])
@pytest.mark.parametrize('causal', [False, True])
def test_ring_attention_matches_reference(impl, causal):
    rng = np.random.RandomState(3)
    B, T, H, D = 2, 32, 4, 8
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    mesh = make_mesh({'sp': 4})
    apply = make_ring_attention(mesh, axis='sp', causal=causal, impl=impl)
    out = np.asarray(apply(q, k, v))
    ref = np.asarray(attention_reference(q, k, v, causal=causal))
    assert np.allclose(out, ref, atol=1e-4)


def test_pipeline_matches_sequential():
    rng = np.random.RandomState(4)
    n_stages, n_micro, mb, dim = 4, 8, 2, 16
    stage_params = [{'w': jnp.asarray(rng.randn(dim, dim) * 0.3, jnp.float32)}
                    for _ in range(n_stages)]
    xs = jnp.asarray(rng.randn(n_micro, mb, dim), jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p['w'])

    mesh = make_mesh({'pp': 4})
    stacked = stack_stage_params(stage_params)
    out = np.asarray(pipeline_apply(stage_fn, stacked, xs, mesh))

    ref = np.asarray(xs)
    for p in stage_params:
        ref = np.tanh(ref @ np.asarray(p['w']))
    assert out.shape == (n_micro, mb, dim)
    assert np.allclose(out, ref, atol=1e-5)


def test_size1_axis_kept_for_topology_agnostic_plans():
    """A plan naming 'tp' must degrade to replicated on a tp=1 mesh."""
    mesh = make_mesh({'dp': 8, 'tp': 1})
    assert 'tp' in mesh.axis_names
    plan = ShardingPlan([('w', P(None, 'tp'))])
    out = shard_params({'w': jnp.zeros((4, 4))}, mesh, plan)
    assert out['w'].shape == (4, 4)


def test_blockwise_causal_decode_alignment():
    """Tq=1, Tk=32 decode step: queries align to the END of the keys."""
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 1, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)
    ref = np.asarray(attention_reference(q, k, v, causal=True))
    out = np.asarray(blockwise_attention(q, k, v, block_size=8, causal=True))
    assert np.allclose(out, ref, atol=1e-5)


def test_ring_attention_scale_passthrough():
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(1, 16, 2, 8), jnp.float32)
    mesh = make_mesh({'sp': 4})
    apply = make_ring_attention(mesh, scale=0.5)
    out = np.asarray(apply(x, x, x))
    ref = np.asarray(attention_reference(x, x, x, scale=0.5))
    assert np.allclose(out, ref, atol=1e-5)


def test_adam_rule_step():
    init, update = adam_rule(lr=0.1)
    p = jnp.ones(3)
    g = jnp.ones(3)
    s = init(p)
    p2, s2 = update(p, g, s, jnp.zeros((), jnp.int32))
    # first adam step with bias correction moves by ~lr
    assert np.allclose(np.asarray(p2), 1.0 - 0.1, atol=1e-3)


def test_ring_attention_gradients_match_reference():
    """Long-context backward: grads through the sp-ring (ppermute chain)
    must match the single-device oracle's (the training path of
    sequence parallelism, not just inference)."""
    import jax
    import jax.numpy as jnp
    mesh = make_mesh({'sp': 4})
    B, T, H, D = 2, 256, 2, 16
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
               for _ in range(3))

    apply = make_ring_attention(mesh, axis='sp', causal=True)

    def ring_loss(q, k, v):
        return (apply(q, k, v).astype(jnp.float32) ** 2).mean()

    def ref_loss(q, k, v):
        return (attention_reference(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).mean()

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ring, g_ref, 'qkv'):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=2e-2, atol=2e-3, err_msg=name)


@pytest.mark.parametrize('causal', [False, True])
def test_ulysses_attention_gradients_match_reference(causal):
    """Ulysses backward parity: grads through the two
    all_to_alls (heads<->seq transposes) must match the single-device
    oracle — an SP mode you cannot backprop through is inference-only."""
    import jax
    import jax.numpy as jnp
    mesh = make_mesh({'sp': 4})
    B, T, H, D = 2, 128, 4, 16     # H % sp == 0, the Ulysses contract
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
               for _ in range(3))
    apply = make_ring_attention(mesh, axis='sp', causal=causal,
                                impl='ulysses')

    def uly_loss(q, k, v):
        return (apply(q, k, v).astype(jnp.float32) ** 2).mean()

    def ref_loss(q, k, v):
        return (attention_reference(q, k, v, causal=causal)
                .astype(jnp.float32) ** 2).mean()

    g_uly = jax.grad(uly_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for gu, gf, name in zip(g_uly, g_ref, 'qkv'):
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gf),
                                   rtol=2e-2, atol=2e-3, err_msg=name)


def test_shard_updates_matches_unsharded():
    """ZeRO-style weight-update sharding (arXiv:2004.13336): identical
    training trajectory, optimizer states physically dp-sharded."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.data_parallel import (make_train_step,
                                                  adam_rule)
    mesh = make_mesh({'dp': 8})
    rng = np.random.RandomState(0)
    W0 = rng.randn(16, 4).astype(np.float32)
    X = jnp.asarray(rng.randn(32, 16).astype(np.float32))
    Y = jnp.asarray(rng.randn(32, 4).astype(np.float32))

    def loss_fn(params, batch, key):
        x, y = batch
        return jnp.mean((x @ params['w'] - y) ** 2)

    traj = []
    for shard in (False, True):
        init, step = make_train_step(loss_fn, mesh,
                                     optimizer=adam_rule(lr=0.05),
                                     shard_updates=shard)
        state = init({'w': jnp.asarray(W0)})  # fresh: step donates state
        key = jax.random.PRNGKey(0)
        with mesh.mesh if hasattr(mesh, 'mesh') else mesh:
            for _ in range(5):
                state, loss = step(state, (X, Y), key)
        traj.append((float(np.asarray(loss)),
                     np.asarray(state['params']['w'])))
        if shard:
            m_state = state['opt']['w'][0]   # adam m
            spec = str(getattr(m_state.sharding, 'spec', ''))
            assert 'dp' in spec, spec        # the SPEC, not the mesh repr
            pspec = str(getattr(state['params']['w'].sharding, 'spec', ''))
            assert 'dp' not in pspec, pspec  # params stay plan-replicated
    np.testing.assert_allclose(traj[0][1], traj[1][1], rtol=1e-5,
                               atol=1e-6)
    assert abs(traj[0][0] - traj[1][0]) < 1e-6


def test_striped_attention_parity_and_layout():
    """Striped ring attention (arXiv:2311.09431): round-robin layout
    balances the causal ring; outputs and gradients must match the
    dense oracle exactly."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.ring_attention import (
        attention_reference, make_ring_attention, stripe_layout,
        unstripe_layout)

    mesh = mx.parallel.make_mesh({'sp': 4})
    rng = np.random.RandomState(0)
    B, T, H, D = 2, 32, 2, 8
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
               for _ in range(3))

    x = jnp.arange(T, dtype=jnp.float32).reshape(1, T, 1, 1)
    np.testing.assert_allclose(unstripe_layout(stripe_layout(x, 4), 4), x)

    apply = make_ring_attention(mesh, axis='sp', causal=True,
                                impl='striped')

    def run(q_, k_, v_):
        return unstripe_layout(apply(stripe_layout(q_, 4),
                                     stripe_layout(k_, 4),
                                     stripe_layout(v_, 4)), 4)

    out = run(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)

    g1 = jax.grad(lambda *a: (run(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(
        lambda *a: (attention_reference(*a, causal=True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """Sharded SPMD checkpointing (parallel.checkpoint over orbax):
    shard-parallel save, restore onto the template's shardings,
    max_to_keep retention, and bitwise training-state resume."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import checkpoint as ckpt

    mesh = mx.parallel.make_mesh({'dp': 2, 'tp': 4})
    sh_w = NamedSharding(mesh.mesh, P('tp', None))
    sh_r = NamedSharding(mesh.mesh, P())
    state = {'w': jax.device_put(jnp.arange(32.0).reshape(8, 4), sh_w),
             'scale': jax.device_put(jnp.float32(0.5), sh_r),
             'opt': {'m': jax.device_put(jnp.ones((8, 4)), sh_w)}}
    m = ckpt.manager(str(tmp_path), max_to_keep=2)
    ckpt.save(m, 1, state)
    ckpt.save(m, 2, jax.tree_util.tree_map(lambda x: x * 2, state))
    assert ckpt.latest_step(m) == 2

    template = jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x, device=x.sharding), state)
    restored = ckpt.restore(m, template)
    np.testing.assert_allclose(np.asarray(restored['w']),
                               np.arange(32.).reshape(8, 4) * 2)
    assert restored['w'].sharding == sh_w
    old = ckpt.restore(m, template, step=1)
    np.testing.assert_allclose(np.asarray(old['opt']['m']),
                               np.ones((8, 4)))

    # resume equivalence: continue-from-restore == continue-straight
    @jax.jit
    def step(s):
        return {'w': s['w'] * 0.9 + 1.0, 'scale': s['scale'],
                'opt': {'m': s['opt']['m'] * 0.5}}

    s_direct = step(step(restored))
    s_resumed = step(step(ckpt.restore(m, template)))
    np.testing.assert_array_equal(np.asarray(s_direct['w']),
                                  np.asarray(s_resumed['w']))

    with pytest.raises(FileNotFoundError):
        empty = ckpt.manager(str(tmp_path / 'fresh'))
        ckpt.restore(empty, template)
