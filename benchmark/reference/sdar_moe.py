"""Plain reference for decoders of the ``sdar_moe`` family, trained as
block-diffusion models: a noisy and a clean copy of the sequence under a
block mask, a weighted loss on the masked tokens. The configuration the
benchmark runs is JetLM's SDAR-30B-A3B-Chat
(``benchmark/configs/sdar_30b_a3b_chat.json``; its ``assumed`` lists what
the published keys leave open and this file settles).

Plain ``jax.numpy``, float32, under
``jax.default_matmul_precision('highest')``; no kernels, no import of
``mxnet_tpu``; only parameter *names* are shared with the program
(``examples/transformer/symbols/sdar_moe.py``). The matrix product, the
norm, the gated MLP, the rotary tables, the float8 control's rounding and
the update are ``reference/deepseek_v3.py``'s, the paired-halves rotary
turn ``reference/lfm2_moe.py``'s, beside this file.

The equations (``d`` ``hidden_size``, ``H`` ``num_attention_heads``,
``KV`` ``num_key_value_heads``, ``D`` ``head_dim``, ``eps``
``rms_norm_eps``, ``B`` ``block_length``; ``L`` clean tokens a sequence;
no bias anywhere):

* A step's input is the ``2 L`` ids ``[xt ; x0]``: ``xt_i`` is the mask id
  where position i was masked (``m_i`` = 1), else ``x0_i``. The label of
  noisy row i is ``x0_i`` where ``m_i`` = 1 and -1 elsewhere (no shift:
  row i predicts token i); its weight ``w_i = 1 / t`` of its block. The
  clean half has no label.
* ``h = Emb[ids]`` (2 L, d). Block l: ``a = RMSNorm(h)``; ``q = a W_q`` as
  (2 L, H, D), ``k = a W_k``, ``v = a W_v`` as (2 L, KV, D); q and k each
  through an RMSNorm over the D columns of a head (one gain of D for all
  heads of q, one for k) before the rotary turn; rotary on all D
  dimensions, dimension j against j + D / 2, ``rope_theta``, no scaling,
  at position ``r mod L``: both halves carry positions 0..L-1. Scores
  ``q k^T / sqrt(D)``, query head i on key/value head ``i // (H / KV)``.
  With ``seg(r)`` 0 for r < L (noisy) and 1 (clean) and ``blk(r) = (r mod
  L) // B``, key c is visible to query r iff ``(seg(c) = 1 and blk(c) <
  blk(r)) or (seg(c) = seg(r) and blk(c) = blk(r))`` (:func:`mask_rows`).
  Softmax over the visible keys, ``h = h + (P v) W_o``. Then ``b =
  RMSNorm(h)``; ``p = softmax(b W_r)`` over all ``num_experts``, the
  ``num_experts_per_tok`` largest, their weights ``p_e`` over the sum of
  the chosen (``norm_topk_prob``); every expert ``w2(silu(w1 b) * w3 b)``
  of width ``moe_intermediate_size``; only the pairs on the experts *held
  here* (``experts_held`` from ``expert_offset``) are computed, and what
  the absent experts would add is left out; no shared expert.
* Only the noisy half goes on: ``z = RMSNorm(h[:L]) W_head^T``. The
  objective is ``J = (1 / (batch L)) sum_i m_i w_i CE(z_i, x0_i)``; the
  loss that is reported is the plain mean over the masked rows, ``sum_i
  m_i CE_i / sum_i m_i``: the gradient is the objective's, the loss the
  metric's (``Perplexity(ignore_label=-1)``'s logarithm).

Departures from the published description: none that the config's keys
state; what they leave open (the per-head norms, the layout, the mask, the
schedule, the mask id) is the configuration file's ``assumed``.

``loss_and_grad`` gives (loss, the pairs computed by the held experts per
layer, the gradient, 0.0 where another family has a second loss). With
``at_masters`` it is handed the float32 masters and computes with their
bfloat16 roundings, rounded where they are used, a block at a time
(``reference/xing4_0.rounded_in_passing``). ``quant`` (the control of the
benchmark's comparison) rounds both operands of every matrix product (and
of the attention's two) to float8 e4m3 with one scale per tensor,
straight-through in the backward pass.

The driver's entry, ``_loss_and_grad``, takes two integer arrays of ``(batch,
2 L)``: the ids, and ``[labels ; the weights' float32 bits]``
(:func:`pack`), which is how a step's three arrays pass through
``drivers/fit_tokens_heads.follow``'s two.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import deepseek_v3 as base
from benchmark.reference.lfm2_moe import apply_rope_halves
from benchmark.reference.xing4_0 import rounded_in_passing

matmul, rms_norm, gated_mlp = base.matmul, base.rms_norm, base.gated_mlp
hashable, working_weights = base.hashable, base.working_weights
sgd_momentum_step, layer_name = base.sgd_momentum_step, base.layer_name
Q_BLOCK = base.Q_BLOCK
IGNORE = -1


def experts_held(cfg):
    return int(cfg.get('experts_held', cfg['num_experts']))


def mask_rows(rows, L, B):
    """[len(rows), 2 L] of the block-diffusion mask: which of the 2 L keys
    [noisy ; clean] each query row (numbered in 0..2L-1) sees."""
    cols = jnp.arange(2 * L)
    seg_r, seg_c = rows >= L, cols >= L
    blk_r, blk_c = (rows % L) // B, (cols % L) // B
    return (seg_c[None, :] & (blk_c[None, :] < blk_r[:, None])) \
        | ((seg_c[None, :] == seg_r[:, None])
           & (blk_c[None, :] == blk_r[:, None]))


def attention(q, k, v, B, quant=False, q_block=Q_BLOCK):
    """Grouped-query attention of one step under the block-diffusion mask:
    q (2 L, H, D), k and v (2 L, KV, D). Dense masked products, a block of
    queries at a time against every key, so that the mask is [q_block, 2 L]
    at a time."""
    T, H, D = q.shape
    KV, L = k.shape[1], T // 2
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
        # rows of the padding look where the last row looks
        rows = jnp.minimum(start + jnp.arange(q_block), T - 1)
        seen = mask_rows(rows, L, B)
        pr = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        if quant:
            pr = base._fp8(pr)
        return jnp.einsum('kgqs,skd->qkgd', pr, v)

    out = lax.map(one, (q5, starts))
    return out.reshape(-1, H, D)[:T]


def attention_block(p, name, a, cfg, cos, sin, quant=False):
    """The attention operator on the normed input a (2 L, d)."""
    T = a.shape[0]
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    D, eps = int(cfg['head_dim']), float(cfg['rms_norm_eps'])
    q = matmul(a, p[name + '_q_weight'].T, quant).reshape(T, H, D)
    k = matmul(a, p[name + '_k_weight'].T, quant).reshape(T, KV, D)
    v = matmul(a, p[name + '_v_weight'].T, quant).reshape(T, KV, D)
    q = apply_rope_halves(rms_norm(q, p[name + '_q_norm_gamma'], eps),
                          cos, sin)
    k = apply_rope_halves(rms_norm(k, p[name + '_k_norm_gamma'], eps),
                          cos, sin)
    o = attention(q, k, v, int(cfg['block_length']), quant)
    return matmul(o.reshape(T, H * D), p[name + '_o_weight'].T, quant)


def route(b, wr, top_k, norm=True, quant=False):
    """(experts (T, top_k), weights (T, top_k)): softmax over all experts,
    the top_k largest, over their sum."""
    probs = jax.nn.softmax(matmul(b, wr.T, quant), axis=-1)
    w, idx = lax.top_k(probs, top_k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) if norm else w


def moe_layer(p, name, b, cfg, held, offset, quant=False):
    """What the experts [offset, offset + held) add for the normed input b
    (T, d). Returns (sum, number of token-expert pairs that landed on the
    experts held)."""
    idx, w = route(b, p[name + '_router_weight'],
                   int(cfg['num_experts_per_tok']),
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


def block(p, name, h, cfg, cos, sin, quant=False):
    """One decoder block on h (2 L, d); (h', pairs on the experts held)."""
    eps = float(cfg['rms_norm_eps'])
    a = rms_norm(h, p[name + '_input_norm_gamma'], eps)
    h = h + attention_block(p, name + '_attn', a, cfg, cos, sin, quant)
    b = rms_norm(h, p[name + '_post_attn_norm_gamma'], eps)
    y, n = moe_layer(p, name + '_moe', b, cfg, experts_held(cfg),
                     int(cfg.get('expert_offset', 0)), quant)
    return h + y, n


def forward(p, ids, labels, weights, cfg, quant=False, remat=True,
            at_masters=False):
    """(sum of w_i CE_i over the masked rows, sum of CE_i over them, pairs
    per layer) for one sequence: ids (2 L,), labels (L,) with -1 where no
    loss is taken, weights (L,)."""
    use = rounded_in_passing if at_masters else (lambda tree: tree)
    top = use({k: v for k, v in p.items() if not k.startswith('layer')})
    L = ids.shape[0] // 2
    cos, sin = base.rope_tables(cfg['rope_theta'], int(cfg['head_dim']), L)
    cos, sin = jnp.tile(cos, (2, 1)), jnp.tile(sin, (2, 1))
    h = top['embed_weight'][ids]
    pairs = []
    for i in range(int(cfg['num_hidden_layers'])):
        name = layer_name(i)
        sub = {k: v for k, v in p.items() if k.startswith(name + '_')}

        def stage(sub, h, name=name):
            return block(use(sub), name, h, cfg, cos, sin, quant)

        h, n = (jax.checkpoint(stage) if remat else stage)(sub, h)
        pairs.append(n)
    z = rms_norm(h[:L], top['final_norm_gamma'], float(cfg['rms_norm_eps']))
    masked = labels != IGNORE
    ce = row_losses(top['head_weight'], z, jnp.where(masked, labels, 0),
                    quant)
    return (jnp.sum(jnp.where(masked, weights * ce, 0.0)),
            jnp.sum(jnp.where(masked, ce, 0.0)), pairs)


def row_losses(head, h, labels, quant=False):
    """-log softmax(h head^T)[label] of every row, a block of rows at a
    time (:func:`lfm2_moe.cross_entropy` sums them; here each row is
    weighted afterwards)."""
    T = h.shape[0]
    blk = min(base.ROW_BLOCK, T)
    pad = (-T) % blk
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, blk, h.shape[1])
    yb = jnp.pad(labels, (0, pad)).reshape(-1, blk)

    @jax.checkpoint
    def rows(args):
        hx, yx = args
        logp = jax.nn.log_softmax(matmul(hx, head.T, quant), axis=-1)
        return -jnp.take_along_axis(logp, yx[:, None], axis=-1)[:, 0]

    return lax.map(rows, (hb, yb)).reshape(-1)[:T]


def objective(p, ids, labels, weights, cfg, quant=False, remat=True,
              at_masters=False):
    """(J, (the mean cross-entropy over the masked rows, pairs per layer
    summed over the sequences)). ids (batch, 2 L), labels and weights
    (batch, L)."""
    weighted, plain, pairs = 0.0, 0.0, None
    for b in range(ids.shape[0]):
        w, c, n = forward(p, ids[b], labels[b], weights[b], cfg, quant,
                          remat, at_masters)
        weighted, plain = weighted + w, plain + c
        pairs = n if pairs is None else [x + y for x, y in zip(pairs, n)]
    masked = jnp.maximum(jnp.sum(labels != IGNORE), 1)
    return weighted / labels.size, (plain / masked, jnp.stack(pairs))


def pack(labels, weights):
    """[labels ; the weights' float32 bits] as int32 (batch, 2 L)."""
    return np.concatenate(
        [np.asarray(labels).astype(np.int32),
         np.ascontiguousarray(weights, np.float32).view(np.int32)], axis=1)


def unpack(packed):
    L = packed.shape[1] // 2
    return packed[:, :L], lax.bitcast_convert_type(packed[:, L:],
                                                   jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _loss_and_grad(p, ids, packed, cfg_json, quant, at_masters=False):
    cfg = json.loads(cfg_json)
    labels, weights = unpack(packed)
    with jax.default_matmul_precision('highest'):
        (_, (loss, pairs)), g = jax.value_and_grad(
            lambda q: objective(q, ids, labels, weights, cfg, quant,
                                at_masters=at_masters), has_aux=True)(p)
    # the fourth is a second head's loss in a family that has one
    return loss, pairs, g, jnp.zeros(())


def loss_and_grad(p, ids, labels, weights, cfg, quant=False,
                  at_masters=False):
    """(the mean cross-entropy over the masked rows, pairs per layer, the
    objective's gradient of every leaf)."""
    return _loss_and_grad(p, jnp.asarray(ids, jnp.int32),
                          jnp.asarray(pack(labels, weights)), hashable(cfg),
                          bool(quant), bool(at_masters))[:3]


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def param_shapes(cfg):
    """{name: shape} of every parameter, as the program's builder names and
    shapes them (2-D weights as (out, in); the experts held as one array
    per projection, (experts_held, in, out))."""
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    H, KV, D = int(cfg['num_attention_heads']), \
        int(cfg['num_key_value_heads']), int(cfg['head_dim'])
    experts, held = int(cfg['num_experts']), experts_held(cfg)
    narrow = int(cfg['moe_intermediate_size'])
    out = {'embed_weight': (V, d), 'final_norm_gamma': (d,),
           'head_weight': (V, d)}
    for i in range(int(cfg['num_hidden_layers'])):
        n = layer_name(i)
        out.update({
            n + '_input_norm_gamma': (d,),
            n + '_post_attn_norm_gamma': (d,),
            n + '_attn_q_weight': (H * D, d),
            n + '_attn_k_weight': (KV * D, d),
            n + '_attn_v_weight': (KV * D, d),
            n + '_attn_o_weight': (d, H * D),
            n + '_attn_q_norm_gamma': (D,),
            n + '_attn_k_norm_gamma': (D,),
            n + '_moe_router_weight': (experts, d),
            n + '_moe_experts_w1_weight': (held, d, narrow),
            n + '_moe_experts_w3_weight': (held, d, narrow),
            n + '_moe_experts_w2_weight': (held, narrow, d)})
    return out
