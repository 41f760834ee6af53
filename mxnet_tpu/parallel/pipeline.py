"""Pipeline parallelism — a real micro-batch schedule over the ``pp`` axis.

The reference only has *manual layer placement* (AttrScope(ctx_group) +
group2ctx, symbol.py:1250; example/model-parallel-lstm) — devices idle
while their stage is inactive, and overlap is whatever the async engine
happens to find. This module implements an explicit GPipe-style schedule
as ONE compiled computation: every device runs the same scanned program
(SPMD), activations hop stages via ``lax.ppermute``, and the bubble is
the schedule's (stages-1)/(microbatches+stages-1) — not luck.

Layout contract: each stage's parameters are stacked on a leading
``n_stages`` dim and sharded over ``pp``; micro-batches are a leading
``n_micro`` dim, replicated.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map

__all__ = ['PipelineStage', 'pipeline_apply', 'stack_stage_params']


class PipelineStage:
    """A (fn, params) pair; helper for building homogeneous stage stacks."""

    def __init__(self, fn, params):
        self.fn = fn
        self.params = params


def stack_stage_params(stage_params_list):
    """[{name: arr}, ...] per stage → {name: arr[n_stages, ...]} stacked.

    All stages must share one parameter structure (homogeneous pipeline —
    the transformer-block case)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stage_params_list)


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh, axis='pp'):
    """Run the GPipe schedule; returns outputs [n_micro, ...].

    stage_fn(params, x) -> y with y.shape == x.shape (homogeneous
    stages). ``microbatches``: [n_micro, micro_batch, ...]. One
    shard_map + lax.scan; n_micro + n_stages - 1 ticks.
    """
    n_micro = microbatches.shape[0]
    pspec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)

    @functools.partial(
        shard_map, mesh=mesh.mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False)
    def run(params, mbs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)  # [1,...]→[...]
        n = lax.psum(1, axis)
        idx = lax.axis_index(axis)
        steps = n_micro + n - 1
        fwd = [(i, i + 1) for i in range(n - 1)]      # stage i → i+1

        x_shape = mbs.shape[1:]

        def body(carry, t):
            buf_in, outs = carry
            # stage 0 injects microbatch t (clamped; masked out when t ≥ n_micro)
            feed = lax.dynamic_index_in_dim(mbs, jnp.minimum(t, n_micro - 1),
                                            axis=0, keepdims=False)
            x = jnp.where(idx == 0, feed, buf_in)
            y = stage_fn(params, x)
            # the tick at which the LAST stage finishes microbatch m is
            # t = m + n - 1 → write slot t-(n-1) when we are that stage
            slot = jnp.clip(t - (n - 1), 0, n_micro - 1)
            valid = (idx == n - 1) & (t >= n - 1)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(valid, y, lax.dynamic_index_in_dim(outs, slot, 0, keepdims=False)),
                slot, 0)
            buf_next = lax.ppermute(y, axis, fwd)     # non-receivers get 0
            return (buf_next, outs), None

        init = (jnp.zeros(x_shape, mbs.dtype),
                jnp.zeros((n_micro,) + x_shape, mbs.dtype))
        (_, outs), _ = lax.scan(body, init, jnp.arange(steps))
        # only the last stage holds real outputs; share them with every
        # device so out_specs can be replicated
        outs = jnp.where(idx == n - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, axis)

    return run(stacked_params, microbatches)
