"""5-axis parallel transformer training step — dp/tp/pp/sp/ep in ONE program.

The reference's parallelism inventory (SURVEY.md §2.3) stops at data
parallelism (module/executor_group.py decide_slices + kvstore reduce)
and manual layer placement (AttrScope(ctx_group), symbol.py group2ctx).
This module is the TPU-native superset: a decoder-only transformer LM
whose full training step — forward, GPipe pipeline schedule, ring
attention, Megatron tensor-parallel matmuls, expert-parallel MoE,
backward, gradient sync, SGD update — compiles to ONE XLA computation
over a named 5-axis mesh:

- ``dp``: batch sharded; grad psum inserted by the shard_map transpose.
- ``tp``: attention heads + MoE hidden dim sharded (column-parallel
  w_up / row-parallel w_down with a single psum, Megatron-style).
- ``pp``: layers stacked on a leading stage dim; GPipe micro-batch
  schedule via lax.scan + lax.ppermute stage hand-off (pipeline.py).
- ``sp``: sequence sharded; ring attention streams K/V chunks around
  the ring with ppermute (ring_attention.py).
- ``ep``: experts sharded; every shard evaluates its local experts on
  all tokens (dense dispatch), combined with one psum over ``ep``.

Any axis may have size 1 — the same program degrades gracefully, so one
code path covers 1 chip through a v5e-64 pod. This file is also what
``__graft_entry__.dryrun_multichip`` compiles.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import DeviceMesh
from .ring_attention import ring_attention

__all__ = ['TransformerConfig', 'param_specs', 'init_params',
           'make_loss_fn', 'make_5d_train_step']


class TransformerConfig:
    """Tiny bag of hyperparameters for the 5-axis LM.

    Divisibility contract (checked in init_params): n_heads and ffn by
    the tp axis, experts by ep, vocab/d_model free.
    """

    def __init__(self, vocab=256, d_model=64, n_heads=4, head_dim=None,
                 ffn=128, experts=2, n_layers=2, dtype=jnp.float32):
        self.vocab = vocab
        self.d_model = d_model
        self.n_heads = n_heads
        self.head_dim = head_dim or d_model // n_heads
        self.ffn = ffn
        self.experts = experts
        self.n_layers = n_layers
        self.dtype = dtype


def param_specs(cfg=None):
    """PartitionSpec per parameter. Layer-stacked tensors lead with a
    [n_layers] dim sharded over pp — each stage owns n_layers/pp blocks."""
    return {
        'embed':  P(),                              # [V, D]
        'ln1':    P('pp', None),                    # [L, D]
        'ln2':    P('pp', None),                    # [L, D]
        'wqkv':   P('pp', None, None, 'tp', None),  # [L, D, 3, H, Dh]
        'wo':     P('pp', 'tp', None, None),        # [L, H, Dh, D]
        'gate':   P('pp', None, None),              # [L, D, E] (replicated/ep)
        'w_up':   P('pp', 'ep', None, 'tp'),        # [L, E, D, F]
        'w_down': P('pp', 'ep', 'tp', None),        # [L, E, F, D]
        'head':   P(),                              # [D, V]
    }


def param_shapes(cfg):
    """Shape per parameter (single source of truth with init_params)."""
    D, H, Dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    F, E, V, L = cfg.ffn, cfg.experts, cfg.vocab, cfg.n_layers
    return {
        'embed':  (V, D),
        'ln1':    (L, D),
        'ln2':    (L, D),
        'wqkv':   (L, D, 3, H, Dh),
        'wo':     (L, H, Dh, D),
        'gate':   (L, D, E),
        'w_up':   (L, E, D, F),
        'w_down': (L, E, F, D),
        'head':   (D, V),
    }


def _zero_spec(spec, shape, dp):
    """ZeRO layout for optimizer state / weight update over the dp axis
    (arXiv:2004.13336): place 'dp' on the first spec-free dim it
    divides, so each replica owns 1/dp of the momentum and update math.
    The grad all-reduce + shard slice is the form XLA's TPU
    reduce-scatter-creation rewrites into one reduce-scatter; on
    backends without that pass the program carries the all-reduce plus
    a param all-gather (memory/compute win intact, comm neutral at
    best). No free dividing dim (or dp=1) → unchanged."""
    if dp <= 1:
        return spec
    s = list(tuple(spec)) + [None] * (len(shape) - len(tuple(spec)))
    for i, ax in enumerate(s):
        if ax is None and shape[i] % dp == 0:
            s[i] = 'dp'
            return P(*s)
    return spec


AXES = ('pp', 'dp', 'ep', 'sp', 'tp')


def full_mesh(axes=None, devices=None):
    """A mesh naming all five axes; unspecified ones get size 1 (the same
    program then runs anywhere from 1 chip to a pod)."""
    from .mesh import make_mesh
    axes = dict(axes or {})
    for ax in AXES:
        axes.setdefault(ax, 1)
    return make_mesh(axes, devices)


def _check_mesh(mesh):
    missing = [ax for ax in AXES if ax not in mesh.axis_names]
    if missing:
        raise ValueError(
            'five_d needs all of %s on the mesh (size 1 is fine; use '
            'full_mesh()); missing %s' % (AXES, missing))


def init_params(cfg, mesh, seed=0):
    """Host-init then device_put onto the mesh per param_specs."""
    _check_mesh(mesh)
    S = mesh.axis_size('pp')
    tp, ep = mesh.axis_size('tp'), mesh.axis_size('ep')
    if cfg.n_heads % tp or cfg.ffn % tp:
        raise ValueError('tp=%d must divide n_heads and ffn' % tp)
    if cfg.experts % ep:
        raise ValueError('ep=%d must divide experts' % ep)
    if cfg.n_layers % S:
        raise ValueError('pp=%d must divide n_layers' % S)
    rng = np.random.RandomState(seed)
    D, H, Dh, F = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ffn

    def mk(shape, scale):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    shapes = param_shapes(cfg)
    scales = {'embed': 0.02, 'wqkv': D ** -0.5, 'wo': (H * Dh) ** -0.5,
              'gate': D ** -0.5, 'w_up': D ** -0.5, 'w_down': F ** -0.5,
              'head': D ** -0.5}
    host = {k: (np.ones(shapes[k], np.float32) if k in ('ln1', 'ln2')
                else mk(shapes[k], scales[k])) for k in shapes}
    specs = param_specs(cfg)
    return {k: jax.device_put(v.astype(cfg.dtype),
                              NamedSharding(mesh.mesh, specs[k]))
            for k, v in host.items()}


def make_loss_fn(cfg, mesh):
    """shard_map'ed loss(params, tokens, targets) -> scalar mean CE.

    tokens/targets: int32 [n_micro, batch, seq], batch sharded dp, seq
    sharded sp, micro-batch dim replicated (it is the pipeline schedule).
    Differentiable from outside; the shard_map transpose plants the dp/sp
    grad psums exactly where the reference pushed grads to the KVStore
    (§3.3) — compiled, overlapped collectives instead.
    """
    _check_mesh(mesh)
    specs = param_specs(cfg)
    data_spec = P(None, 'dp', 'sp')

    @functools.partial(shard_map, mesh=mesh.mesh,
                       in_specs=(specs, data_spec, data_spec),
                       out_specs=P(), check_vma=False)
    def loss_fn(params, tokens, targets):
        S = lax.psum(1, 'pp')               # static axis sizes
        dp = lax.psum(1, 'dp')
        sp = lax.psum(1, 'sp')
        stage = lax.axis_index('pp')
        ep_rank = lax.axis_index('ep')
        n_micro, b, t = tokens.shape
        embed, head = params['embed'], params['head']
        # local layer stack: leading [n_layers/pp] slice per stage
        stk = {k: v for k, v in params.items()
               if k not in ('embed', 'head')}
        L_local = stk['ln1'].shape[0]
        E_local = stk['w_up'].shape[1]

        def rms(x, g):
            return x * lax.rsqrt(
                jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6) * g

        def block(x, stg):                   # x: [b, t_local, D]
            h = rms(x, stg['ln1'])
            qkv = jnp.einsum('btd,dchk->cbthk', h, stg['wqkv'])
            att = ring_attention(qkv[0], qkv[1], qkv[2],
                                 axis='sp', causal=True)
            o = jnp.einsum('bthk,hkd->btd', att, stg['wo'])
            x = x + lax.psum(o, 'tp')        # row-parallel wo
            h2 = rms(x, stg['ln2'])
            glog = jnp.einsum('btd,de->bte', h2, stg['gate'])
            probs = jax.nn.softmax(glog, -1)
            assign = jnp.argmax(glog, -1)    # top-1 routing, dense dispatch
            y = jnp.zeros_like(h2)
            for e in range(E_local):
                ge = ep_rank * E_local + e
                w = probs[..., ge] * (assign == ge)
                u = jax.nn.gelu(jnp.einsum('btd,df->btf', h2, stg['w_up'][e]))
                y = y + w[..., None] * jnp.einsum('btf,fd->btd',
                                                  u, stg['w_down'][e])
            return x + lax.psum(y, ('tp', 'ep'))

        def stage_fn(x):                     # all this stage's layers
            for i in range(L_local):
                x = block(x, {k: v[i] for k, v in stk.items()})
            return x

        def ce_sum(logits, tgt):
            lse = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
            return jnp.sum(lse - gold)

        # GPipe: n_micro + S - 1 ticks; stage 0 injects, last stage scores
        steps = n_micro + S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, tt):
            buf, acc = carry
            mb = jnp.minimum(tt, n_micro - 1)
            feed = embed[lax.dynamic_index_in_dim(tokens, mb, 0,
                                                  keepdims=False)]
            x = jnp.where(stage == 0, feed, buf)
            y = stage_fn(x)
            slot = jnp.clip(tt - (S - 1), 0, n_micro - 1)
            logits = jnp.einsum('btd,dv->btv', y, head)
            tgt = lax.dynamic_index_in_dim(targets, slot, 0, keepdims=False)
            valid = (stage == S - 1) & (tt >= S - 1)
            acc = acc + jnp.where(valid, ce_sum(logits, tgt),
                                  jnp.zeros((), logits.dtype))
            buf = lax.ppermute(y, 'pp', fwd_perm)
            return (buf, acc), None

        init = (jnp.zeros((b, t, cfg.d_model), embed.dtype),
                jnp.zeros((), embed.dtype))
        (_, acc), _ = lax.scan(tick, init, jnp.arange(steps))
        total = n_micro * b * t * dp * sp    # global token count
        return lax.psum(acc, ('pp', 'dp', 'sp')) / total

    return loss_fn


def make_5d_train_step(cfg, mesh, lr=0.1, momentum=0.9):
    """(init_state, step): the full fused train step, jitted over the mesh.

    step(state, tokens, targets) -> (state, loss). State (params +
    momentum) is donated so weights update in place in HBM — the
    functional form of the reference's kWriteInplace optimizer ops.
    """
    loss_fn = make_loss_fn(cfg, mesh)
    specs = param_specs(cfg)
    shapes = param_shapes(cfg)
    dp = mesh.axis_size('dp')
    shardings = {k: NamedSharding(mesh.mesh, s) for k, s in specs.items()}
    # ZeRO over dp (arXiv:2004.13336): momentum lives dp-sharded at
    # rest, grads are constrained to the same layout, the update runs
    # on 1/dp shards, and only the params re-gather (their
    # out_shardings) for the next forward. See _zero_spec for the
    # backend-dependent comm story.
    vel_shardings = {k: NamedSharding(mesh.mesh,
                                      _zero_spec(specs[k], shapes[k], dp))
                     for k in specs}
    state_sh = {'params': shardings, 'vel': vel_shardings}
    data_sh = NamedSharding(mesh.mesh, P(None, 'dp', 'sp'))

    def init_state(seed=0):
        params = init_params(cfg, mesh, seed)
        # allocate vel DIRECTLY into its sharded layout — a dense
        # zeros-then-reshard would spike full-size buffers on one device
        vel = {k: jnp.zeros(shapes[k], v.dtype, device=vel_shardings[k])
               for k, v in params.items()}
        return {'params': params, 'vel': vel}

    def step(state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(state['params'],
                                                  tokens, targets)
        grads = {k: jax.lax.with_sharding_constraint(g, vel_shardings[k])
                 for k, g in grads.items()}
        vel = {k: momentum * state['vel'][k] - lr * grads[k]
               for k in grads}
        params = {k: jax.lax.with_sharding_constraint(
                      state['params'][k], vel_shardings[k]) + vel[k]
                  for k in grads}
        return {'params': params, 'vel': vel}, loss

    jstep = jax.jit(step, in_shardings=(state_sh, data_sh, data_sh),
                    out_shardings=(state_sh, None), donate_argnums=(0,))
    return init_state, jstep
