"""Plain reference for decoders of the ``lfm2_moe`` family, for training:
gated short convolutions in most layers, grouped-query attention with a
per-head RMSNorm on q and k in the others, a sigmoid router with an expert
bias over experts without a shared one, a head tied to the embedding. The
configuration the benchmark runs is LiquidAI's LFM2-24B-A2B
(``benchmark/configs/lfm2_24b_a2b.json``; its ``assumed`` lists what the
published keys leave open and this file settles).

Plain ``jax.numpy``, float32, under
``jax.default_matmul_precision('highest')``; no kernels, no import of
``mxnet_tpu``; only parameter *names* are shared with the program
(``examples/transformer/symbols/lfm2_moe.py``). The gated MLP, the float8
control's rounding and the update are ``reference/deepseek_v3.py``'s,
beside this file.

The equations (``d`` is ``hidden_size``, ``H`` ``num_attention_heads``,
``KV`` ``num_key_value_heads``, ``D = d / H``, ``L`` ``conv_L_cache``,
``eps`` ``norm_eps``; no bias anywhere):

* ``h_0 = Emb[ids]``. Block l: ``a = RMSNorm(h)``, ``h = h + Op_l(a)`` by
  ``layer_types[l]``; ``b = RMSNorm(h)``, ``h = h + MLP(b)`` for ``l <
  num_dense_layers``, else ``h = h + MoE(b)``. ``logits = RMSNorm(h_L)
  Emb^T``: the head is the embedding (tied), mean cross-entropy.
* ``conv``: ``[B | C | x] = a W_in`` (the three thirds of ``3 d`` columns
  in this order); ``u = B * x``; ``c_t = sum_j w[:, j] u_{t - (L - 1 -
  j)}`` per channel, ``u`` zero before the sequence's start; ``Op(a) = (C *
  c) W_out``. No activation inside.
* ``full_attention``: ``q = a W_q`` as ``(T, H, D)``, ``k = a W_k`` and
  ``v = a W_v`` as ``(T, KV, D)``; q and k each through an RMSNorm over the
  ``D`` columns of a head (one gain of ``D`` for all heads of q, one for
  k) before the rotary turn; rotary on all ``D`` dimensions, dimension
  ``i`` against ``i + D / 2``, ``rope_theta``, no scaling; scores ``q k^T
  / sqrt(D)``, causal, query head ``i`` on key/value head ``i // (H /
  KV)``; softmax in float32; ``Op(a) = Attn W_o``.
* ``MoE``: ``s = sigmoid(b W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + bias`` are chosen
  (``use_expert_bias``); their weights are the bare ``s`` over their sum +
  1e-6 (``norm_topk_prob``) times ``routed_scaling_factor``; every expert
  ``w2(silu(w1 b) * w3 b)`` of width ``moe_intermediate_size``; only the
  pairs on the experts *held here* (``experts_held`` from
  ``expert_offset``) are computed; nothing else is added. ``MLP``: the
  same gated form at ``intermediate_size``.

``loss_and_grad`` gives (loss, the pairs computed by the held experts per
sparse layer, the gradient, 0.0 where another family has a second loss).
With ``at_masters`` it is handed the float32 masters and computes with
their bfloat16 roundings (``working_weights``'s values), rounded where they
are used, a block at a time, the gradient passing the rounding unchanged:
the same numbers as rounding first, without a second copy of the
parameters beside masters, momentum and gradient on the chip.

``quant`` (the control of the benchmark's comparison) rounds both operands
of every matrix product (and of the attention's two) to float8 e4m3 with
one scale per tensor, straight-through in the backward pass; the
convolution's elementwise arithmetic stays float32.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import deepseek_v3 as base
from benchmark.reference.xing4_0 import rounded_in_passing

matmul, rms_norm, gated_mlp = base.matmul, base.rms_norm, base.gated_mlp
hashable, working_weights = base.hashable, base.working_weights
sgd_momentum_step, layer_name = base.sgd_momentum_step, base.layer_name
Q_BLOCK, ROW_BLOCK = base.Q_BLOCK, base.ROW_BLOCK
NORM_EPS = 1e-6     # on the sum of the chosen scores, as published
TIED = 'tied_embed_weight'


# ---------------------------------------------------------------------------
# the operators
# ---------------------------------------------------------------------------

def short_conv(u, w):
    """c_t = sum_j w[:, j] u_{t - (L - 1 - j)}: u (T, C), taps w (C, L),
    causal, depthwise, u zero before the start."""
    T, L = u.shape[0], w.shape[1]
    c = w[:, L - 1] * u
    for k in range(1, L):
        c = c + w[:, L - 1 - k] * jnp.pad(u, ((k, 0), (0, 0)))[:T]
    return c


def conv_block(p, name, a, quant=False):
    """The conv operator on the normed input a (T, d)."""
    gate_in, gate_out, x = jnp.split(
        matmul(a, p[name + '_in_weight'].T, quant), 3, axis=-1)
    c = short_conv(gate_in * x, p[name + '_taps_weight'])
    return matmul(gate_out * c, p[name + '_out_weight'].T, quant)


def apply_rope_halves(x, cos, sin):
    """x (T, heads, D): dimension i is rotated against i + D / 2."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, quant=False, q_block=Q_BLOCK):
    """Causal grouped-query attention of one sequence: q (T, H, D), k and
    v (T, KV, D). Dense masked products, a block of queries at a time
    against every key."""
    T, H, D = q.shape
    KV = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    if quant:
        q, k, v = base._fp8(q), base._fp8(k), base._fp8(v)
    q_block = min(q_block, T)
    pad = (-T) % q_block
    q5 = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(-1, q_block, KV, H // KV, D)
    starts = jnp.arange((T + pad) // q_block) * q_block

    @jax.checkpoint
    def one(args):
        qb, start = args
        s = jnp.einsum('qkgd,skd->kgqs', qb, k) * scale
        # rows of the padding look where the last token looks
        rows = jnp.minimum(start + jnp.arange(q_block), T - 1)
        seen = jnp.arange(T)[None, :] <= rows[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        if quant:
            pr = base._fp8(pr)
        return jnp.einsum('kgqs,skd->qkgd', pr, v)

    out = lax.map(one, (q5, starts))
    return out.reshape(-1, H, D)[:T]


def head_dim(cfg):
    return int(cfg.get('head_dim') or int(cfg['hidden_size'])
               // int(cfg['num_attention_heads']))


def attention_block(p, name, a, cfg, cos, sin, quant=False):
    """The attention operator on the normed input a (T, d)."""
    T = a.shape[0]
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    D, eps = head_dim(cfg), float(cfg['norm_eps'])
    q = matmul(a, p[name + '_q_weight'].T, quant).reshape(T, H, D)
    k = matmul(a, p[name + '_k_weight'].T, quant).reshape(T, KV, D)
    v = matmul(a, p[name + '_v_weight'].T, quant).reshape(T, KV, D)
    q = apply_rope_halves(rms_norm(q, p[name + '_q_norm_gamma'], eps),
                          cos, sin)
    k = apply_rope_halves(rms_norm(k, p[name + '_k_norm_gamma'], eps),
                          cos, sin)
    o = attention(q, k, v, quant)
    return matmul(o.reshape(T, H * D), p[name + '_o_weight'].T, quant)


def route(b, wr, bias, top_k, scaling, norm=True, quant=False):
    """(experts (T, top_k), weights (T, top_k)): sigmoid scores over all
    experts, the top_k largest of score + bias chosen, the bare scores of
    the chosen over their sum + NORM_EPS, times `scaling`."""
    scores = jax.nn.sigmoid(matmul(b, wr.T, quant))
    _, idx = lax.top_k(scores + lax.stop_gradient(bias).reshape(1, -1),
                       top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + NORM_EPS)
    return idx, w * scaling


def moe_layer(p, name, b, cfg, held, offset, quant=False):
    """What the experts [offset, offset + held) add for the normed input b
    (T, d). Returns (sum, number of token-expert pairs that landed on the
    experts held)."""
    idx, w = route(b, p[name + '_router_weight'],
                   p[name + '_select_bias_weight'],
                   int(cfg['num_experts_per_tok']),
                   float(cfg.get('routed_scaling_factor', 1.0)),
                   bool(cfg.get('norm_topk_prob', True)), quant)

    @jax.checkpoint
    def expert(carry, held_here):      # a loop over the experts held
        out, pairs = carry
        e, w1, w3, w2 = held_here
        hit = idx == (offset + e)
        weight = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)
        y = gated_mlp(b, w1, w3, w2, quant)
        return (out + weight[:, None] * y, pairs + jnp.sum(hit)), None

    (out, pairs), _ = lax.scan(
        expert, (jnp.zeros_like(b), jnp.zeros((), jnp.int32)),
        (jnp.arange(held), p[name + '_experts_w1_weight'][:held],
         p[name + '_experts_w3_weight'][:held],
         p[name + '_experts_w2_weight'][:held]))
    return out, pairs


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def is_sparse(cfg, i):
    return i >= int(cfg.get('num_dense_layers', 0))


def experts_held(cfg):
    return int(cfg.get('experts_held', cfg['num_experts']))


def block(p, name, h, cfg, kind, sparse, cos, sin, quant=False):
    """One decoder block on h (T, d); (h', pairs on the experts held)."""
    eps = float(cfg['norm_eps'])
    a = rms_norm(h, p[name + '_op_norm_gamma'], eps)
    if kind == 'conv':
        h = h + conv_block(p, name + '_conv', a, quant)
    elif kind == 'full_attention':
        h = h + attention_block(p, name + '_attn', a, cfg, cos, sin, quant)
    else:
        raise ValueError('lfm2_moe: layer type %r' % (kind,))
    b = rms_norm(h, p[name + '_ffn_norm_gamma'], eps)
    if not sparse:
        return h + gated_mlp(b, p[name + '_mlp_w1_weight'].T,
                             p[name + '_mlp_w3_weight'].T,
                             p[name + '_mlp_w2_weight'].T, quant), \
            jnp.zeros((), jnp.int32)
    y, n = moe_layer(p, name + '_moe', b, cfg, experts_held(cfg),
                     int(cfg.get('expert_offset', 0)), quant)
    return h + y, n


def cross_entropy(head, h, labels, quant=False):
    """Sum of -log softmax(h head^T)[label], a block of rows at a time."""
    T = h.shape[0]
    blk = min(ROW_BLOCK, T)
    pad = (-T) % blk
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, blk, h.shape[1])
    yb = jnp.pad(labels, (0, pad)).reshape(-1, blk)
    mb = (jnp.arange(T + pad) < T).reshape(-1, blk)

    @jax.checkpoint
    def rows(args):
        hx, yx, mx = args
        logp = jax.nn.log_softmax(matmul(hx, head.T, quant), axis=-1)
        picked = jnp.take_along_axis(logp, yx[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(mx, picked, 0.0))

    return jnp.sum(lax.map(rows, (hb, yb, mb)))


def forward(p, tokens, labels, cfg, quant=False, remat=True,
            at_masters=False):
    """(sum of the cross-entropies, pairs per sparse layer) for one
    sequence: tokens, labels (T,). `at_masters`: p holds float32 masters,
    used through :func:`rounded_in_passing` (a block's inside its stage,
    the others' here)."""
    use = rounded_in_passing if at_masters else (lambda tree: tree)
    top = use({k: v for k, v in p.items() if not k.startswith('layer')})
    T = tokens.shape[0]
    cos, sin = base.rope_tables(cfg['rope_parameters']['rope_theta'],
                                head_dim(cfg), T)
    h = top[TIED][tokens]
    pairs = []
    for i in range(int(cfg['num_hidden_layers'])):
        name = layer_name(i)
        sub = {k: v for k, v in p.items() if k.startswith(name + '_')}
        kind, sparse = cfg['layer_types'][i], is_sparse(cfg, i)

        def stage(sub, h, name=name, kind=kind, sparse=sparse):
            return block(use(sub), name, h, cfg, kind, sparse, cos, sin,
                         quant)

        h, n = (jax.checkpoint(stage) if remat else stage)(sub, h)
        if sparse:
            pairs.append(n)
    h = rms_norm(h, top['final_norm_gamma'], float(cfg['norm_eps']))
    return cross_entropy(top[TIED], h, labels, quant), pairs


def mean_loss(p, tokens, labels, cfg, quant=False, remat=True,
              at_masters=False):
    """(mean cross-entropy over every token of the step, pairs per sparse
    layer summed over the sequences). tokens, labels (B, T) integer."""
    total, pairs = 0.0, None
    for b in range(tokens.shape[0]):
        loss, n = forward(p, tokens[b], labels[b], cfg, quant, remat,
                          at_masters)
        total = total + loss
        pairs = n if pairs is None else [x + y for x, y in zip(pairs, n)]
    return total / tokens.size, \
        jnp.stack(pairs) if pairs else jnp.zeros((0,))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _loss_and_grad(p, tokens, labels, cfg_json, quant, at_masters=False):
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision('highest'):
        (loss, pairs), g = jax.value_and_grad(
            lambda q: mean_loss(q, tokens, labels, cfg, quant,
                                at_masters=at_masters), has_aux=True)(p)
    # the fourth is a second head's loss in a family that has one
    return loss, pairs, g, jnp.zeros(())


def loss_and_grad(p, tokens, labels, cfg, quant=False, at_masters=False):
    """(loss, pairs per sparse layer, gradient of every leaf)."""
    return _loss_and_grad(p, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(labels, jnp.int32), hashable(cfg),
                          bool(quant), bool(at_masters))[:3]


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def param_shapes(cfg):
    """{name: shape} of every parameter, as the program's builder names and
    shapes them (2-D weights as (out, in); the taps as (channels, taps);
    the experts held as one array per projection, (experts_held, in, out);
    the expert bias as (1, num_experts); the embedding, which is also the
    head, once)."""
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    H, KV, D = int(cfg['num_attention_heads']), \
        int(cfg['num_key_value_heads']), head_dim(cfg)
    experts, held = int(cfg.get('num_experts', 0)), experts_held(cfg)
    wide, narrow = int(cfg['intermediate_size']), \
        int(cfg['moe_intermediate_size'])
    out = {TIED: (V, d), 'final_norm_gamma': (d,)}
    for i in range(int(cfg['num_hidden_layers'])):
        n = layer_name(i)
        out.update({n + '_op_norm_gamma': (d,), n + '_ffn_norm_gamma': (d,)})
        if cfg['layer_types'][i] == 'conv':
            out.update({
                n + '_conv_in_weight': (3 * d, d),
                n + '_conv_taps_weight': (d, int(cfg['conv_L_cache'])),
                n + '_conv_out_weight': (d, d)})
        else:
            out.update({
                n + '_attn_q_weight': (H * D, d),
                n + '_attn_k_weight': (KV * D, d),
                n + '_attn_v_weight': (KV * D, d),
                n + '_attn_o_weight': (d, H * D),
                n + '_attn_q_norm_gamma': (D,),
                n + '_attn_k_norm_gamma': (D,)})
        if is_sparse(cfg, i):
            out.update({
                n + '_moe_router_weight': (experts, d),
                n + '_moe_select_bias_weight': (1, experts),
                n + '_moe_experts_w1_weight': (held, d, narrow),
                n + '_moe_experts_w3_weight': (held, d, narrow),
                n + '_moe_experts_w2_weight': (held, narrow, d)})
        else:
            out.update({n + '_mlp_w1_weight': (wide, d),
                        n + '_mlp_w3_weight': (wide, d),
                        n + '_mlp_w2_weight': (d, wide)})
    return out
