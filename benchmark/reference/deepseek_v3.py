"""Plain reference for decoders of the ``deepseek_v3`` family, for training:
latent attention (MLA) in its expanded form, a sigmoid router with a
selection bias, shared experts. The configuration the benchmark runs is
kakaocorp's kanana-2-30b-a3b-instruct-2601
(``benchmark/configs/kanana_2_30b_a3b.json``).

Forward pass, mean cross-entropy over the tokens of the step, its gradient
and the multi-precision SGD-with-momentum update in plain ``jax.numpy``,
float32, under ``jax.default_matmul_precision('highest')`` (a step reads
the masters rounded to bfloat16, ``working_weights``; all arithmetic is
float32). No kernels, no import of ``mxnet_tpu``: dense masks, a loop over
the experts held (a ``lax.scan``: unrolled, the sixteen experts of four
layers made the chip's compiler take 270 s for this program where it now
takes 92, on a run's path). Only parameter *names* are shared with the program
(``examples/transformer/symbols/deepseek_v3.py``).

The equations, from the published ``config.json`` (``cfg`` is that file's
content as the benchmark's configuration file holds it; ``d`` is
``hidden_size``, ``H`` ``num_attention_heads``, ``Dn``
``qk_nope_head_dim``, ``Dr`` ``qk_rope_head_dim``, ``Dv`` ``v_head_dim``,
``r`` ``kv_lora_rank``; no bias anywhere):

* ``a = RMSNorm(h)``. ``q = a Wq`` as ``(T, H, Dn + Dr)``, each head
  ``[q_nope | q_rope]`` (``q_lora_rank`` null: no low-rank query path).
  ``c = a Wa`` of width ``r + Dr``, ``[c_kv | k_rope]``;
  ``RMSNorm(c_kv) Wb`` as ``(T, H, Dn + Dv)``, each head ``[k_nope | v]``.
* Rotary positions on ``q_rope`` and on the one ``k_rope`` that all heads
  share: ``rope_theta``, no scaling (``rope_scaling`` null), all ``Dr``
  dimensions, dimension ``2i`` paired with ``2i + 1``
  (``rope_interleave``).
* ``s_h[t, u] = (q_nope_h[t] . k_nope_h[u] + q_rope_h[t] . k_rope[u])
  / sqrt(Dn + Dr)`` for ``u <= t``; softmax in float32, times ``v_h``;
  ``h = h + concat(heads) Wo``. No gate, no window.
* ``b = RMSNorm(h)``. Layers before ``first_k_dense_replace``:
  ``h = h + (silu(b W1) * (b W3)) W2`` of width ``intermediate_size``.
* Every other layer: ``s = sigmoid(b Wr)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + bias`` are chosen
  (``e_score_correction_bias``; ``n_group`` 1 and ``topk_group`` 1 make
  the group limit the identity, so none is built); their weights are the
  bare ``s``, divided by their sum + 1e-20 (``norm_topk_prob``) and
  multiplied by ``routed_scaling_factor``; every expert is a gated MLP of
  width ``moe_intermediate_size``, the ``n_shared_experts`` shared ones
  one gated MLP of ``n_shared_experts`` times that width, added without a
  gate: ``h = h + sum_k w_k E_k(b) + E_shared(b)``. Of the routed experts
  only those *held here* (``experts_held`` from ``expert_offset``) are
  computed, as one chip of the deployment leaves the rest to the others.
* A last RMSNorm, logits ``h Whead`` (untied), mean cross-entropy.

Departures from the published code (transformers' ``modeling_deepseek_v3``):

* it rotates interleaved pairs by first moving the even dimensions to
  the front half and the odd to the back (of ``q_rope`` and ``k_rope``
  alike) and then rotating half against half; here a pair is rotated in
  place. Both sides of every dot product are permuted alike there, so the
  scores are the same.
* the selection bias is a buffer there, updated by the trainer's load
  balancing and never by a gradient; here it is a leaf,
  ``layerN_moe_select_bias_weight`` of shape ``(1, n_routed_experts)``,
  whose gradient is exactly zero (it enters only the choice), so that with
  no weight decay it stays as seeded.
* only the experts held are computed (the share of one chip).

``quant`` (the control of the benchmark's comparison) rounds both operands
of every matrix product to float8 e4m3 with one scale per tensor,
straight-through in the backward pass.

For the chip at the published widths the work is cut in blocks so that it
fits: every layer is a ``jax.checkpoint`` and so is every held expert,
attention runs over blocks of queries and the loss over blocks of rows of
the logits.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 256       # queries per block of the attention
ROW_BLOCK = 1024    # rows of the logits per block of the loss


def _fp8(x):
    """x rounded to float8 e4m3 with a per-tensor scale; identity
    gradient."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def matmul(x, w, quant=False):
    """x @ w (w as (in, out))."""
    if quant:
        x, w = _fp8(x), _fp8(w)
    return x @ w


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def rope_tables(theta, dim, length):
    """(cos, sin), each (length, dim / 2), float32."""
    inv_freq = 1.0 / float(theta) ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope_interleaved(x, cos, sin):
    """x (T, heads, Dr): dimension 2i is rotated against 2i + 1."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def attention(q_nope, q_rope, k_nope, k_rope, v, quant=False,
              q_block=Q_BLOCK):
    """Causal latent attention of one sequence, expanded form. q_nope and
    k_nope (T, H, Dn), q_rope (T, H, Dr), k_rope (T, Dr) shared by the
    heads, v (T, H, Dv). Dense masked products, a block of queries at a
    time against every key."""
    T, H, Dn = q_nope.shape
    scale = 1.0 / math.sqrt(Dn + q_rope.shape[-1])
    if quant:
        q_nope, q_rope, k_nope, k_rope, v = (
            _fp8(x) for x in (q_nope, q_rope, k_nope, k_rope, v))
    q_block = min(q_block, T)
    pad = (-T) % q_block

    def blocks(x):
        return jnp.pad(x, ((0, pad), (0, 0), (0, 0))) \
            .reshape((-1, q_block) + x.shape[1:])

    starts = jnp.arange((T + pad) // q_block) * q_block

    @jax.checkpoint
    def one(args):
        qn, qr, start = args
        s = (jnp.einsum('qhd,shd->hqs', qn, k_nope)
             + jnp.einsum('qhd,sd->hqs', qr, k_rope)) * scale
        # rows of the padding look where the last token looks
        rows = jnp.minimum(start + jnp.arange(q_block), T - 1)
        seen = jnp.arange(T)[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        if quant:
            p = _fp8(p)
        return jnp.einsum('hqs,shd->qhd', p, v)

    out = lax.map(one, (blocks(q_nope), blocks(q_rope), starts))
    return out.reshape((-1,) + v.shape[1:])[:T]


def attention_block(p, name, a, cfg, cos, sin, quant=False):
    """The attention sub-layer on the normed input a (T, d)."""
    T = a.shape[0]
    H = int(cfg['num_attention_heads'])
    Dn, Dr = int(cfg['qk_nope_head_dim']), int(cfg['qk_rope_head_dim'])
    Dv, r = int(cfg['v_head_dim']), int(cfg['kv_lora_rank'])
    q = matmul(a, p[name + '_q_weight'].T, quant).reshape(T, H, Dn + Dr)
    c = matmul(a, p[name + '_kv_a_weight'].T, quant)
    c_kv = rms_norm(c[:, :r], p[name + '_kv_norm_gamma'],
                    float(cfg['rms_norm_eps']))
    kv = matmul(c_kv, p[name + '_kv_b_weight'].T, quant) \
        .reshape(T, H, Dn + Dv)
    q_rope = apply_rope_interleaved(q[..., Dn:], cos, sin)
    k_rope = apply_rope_interleaved(c[:, None, r:], cos, sin)[:, 0]
    o = attention(q[..., :Dn], q_rope, kv[..., :Dn], k_rope, kv[..., Dn:],
                  quant)
    return matmul(o.reshape(T, H * Dv), p[name + '_o_weight'].T, quant)


def gated_mlp(x, w1, w3, w2, quant=False):
    """(silu(x w1) * (x w3)) w2, weights as (in, out)."""
    return matmul(jax.nn.silu(matmul(x, w1, quant)) * matmul(x, w3, quant),
                  w2, quant)


def route(b, wr, bias, top_k, scaling, norm=True, quant=False):
    """(experts (T, top_k), weights (T, top_k)) of the router: sigmoid
    scores over all experts, the top_k largest of score + bias chosen,
    the bare scores of the chosen normalised and times `scaling`."""
    scores = jax.nn.sigmoid(matmul(b, wr.T, quant))
    _, idx = lax.top_k(scores + lax.stop_gradient(bias).reshape(1, -1),
                       top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scaling


def moe_layer(p, name, b, cfg, held, offset, quant=False, shared=True):
    """What the experts [offset, offset + held) and (if `shared`) the
    shared experts add for the normed input b (T, d). Returns (sum, number
    of token-expert pairs that landed on the experts held)."""
    idx, w = route(b, p[name + '_router_weight'],
                   p[name + '_select_bias_weight'],
                   int(cfg['num_experts_per_tok']),
                   float(cfg['routed_scaling_factor']),
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
    if shared:
        out = out + gated_mlp(b, p[name + '_shared_w1_weight'].T,
                              p[name + '_shared_w3_weight'].T,
                              p[name + '_shared_w2_weight'].T, quant)
    return out, pairs


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer_name(i):
    return 'layer%d' % i


def is_sparse(cfg, i):
    return i >= int(cfg['first_k_dense_replace']) \
        and i % int(cfg.get('moe_layer_freq', 1)) == 0


def experts_held(cfg):
    return int(cfg.get('experts_held', cfg['n_routed_experts']))


def forward(p, tokens, cfg, quant=False, remat=True):
    """(hidden states after the last norm (T, d), pairs computed by the
    held experts per sparse layer) for one sequence of token ids (T,)."""
    T = tokens.shape[0]
    eps = float(cfg['rms_norm_eps'])
    cos, sin = rope_tables(cfg['rope_theta'], int(cfg['qk_rope_head_dim']), T)
    h = p['embed_weight'][tokens]
    pairs = []
    for i in range(int(cfg['num_hidden_layers'])):
        name = layer_name(i)
        sub = {k: v for k, v in p.items() if k.startswith(name + '_')}

        def layer(sub, h, i=i, name=name):
            a = rms_norm(h, sub[name + '_attn_norm_gamma'], eps)
            h = h + attention_block(sub, name + '_attn', a, cfg, cos, sin,
                                    quant)
            b = rms_norm(h, sub[name + '_mlp_norm_gamma'], eps)
            if is_sparse(cfg, i):
                y, n = moe_layer(sub, name + '_moe', b, cfg,
                                 experts_held(cfg),
                                 int(cfg.get('expert_offset', 0)), quant)
            else:
                y = gated_mlp(b, sub[name + '_mlp_w1_weight'].T,
                              sub[name + '_mlp_w3_weight'].T,
                              sub[name + '_mlp_w2_weight'].T, quant)
                n = jnp.zeros((), jnp.int32)
            return h + y, n

        h, n = (jax.checkpoint(layer) if remat else layer)(sub, h)
        if is_sparse(cfg, i):
            pairs.append(n)
    return rms_norm(h, p['final_norm_gamma'], eps), pairs


def mean_loss(p, tokens, labels, cfg, quant=False, remat=True):
    """(mean cross-entropy over every token of the step, pairs per sparse
    layer summed over the sequences). tokens, labels (B, T) integer."""
    total = 0.0
    pairs = None
    for b in range(tokens.shape[0]):
        h, n = forward(p, tokens[b], cfg, quant, remat)
        pairs = n if pairs is None else [x + y for x, y in zip(pairs, n)]
        T = h.shape[0]
        block = min(ROW_BLOCK, T)
        pad = (-T) % block
        hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, h.shape[1])
        yb = jnp.pad(labels[b], (0, pad)).reshape(-1, block)
        mb = (jnp.arange(T + pad) < T).reshape(-1, block)

        @jax.checkpoint
        def rows(args):
            hx, yx, mx = args
            logp = jax.nn.log_softmax(
                matmul(hx, p['head_weight'].T, quant), axis=-1)
            picked = jnp.take_along_axis(logp, yx[:, None], axis=-1)[:, 0]
            return -jnp.sum(jnp.where(mx, picked, 0.0))

        total = total + jnp.sum(lax.map(rows, (hb, yb, mb)))
    return total / tokens.size, jnp.stack(pairs) if pairs else jnp.zeros((0,))


def hashable(cfg):
    """The configuration as something ``jax.jit`` takes as static."""
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _loss_and_grad(p, tokens, labels, cfg_json, quant):
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision('highest'):
        (loss, pairs), g = jax.value_and_grad(
            lambda q: mean_loss(q, tokens, labels, cfg, quant),
            has_aux=True)(p)
    return loss, pairs, g


def loss_and_grad(p, tokens, labels, cfg, quant=False):
    """(loss, pairs per sparse layer, gradient of every leaf)."""
    return _loss_and_grad(p, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(labels, jnp.int32), hashable(cfg),
                          bool(quant))


def working_weights(masters):
    """The weights a multi-precision step computes with: the float32
    masters rounded to bfloat16 and held in float32."""
    # reduce_precision, not a pair of casts: under jit XLA may drop a cast
    # to bfloat16 and back as excess precision it is allowed to keep
    return {k: lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
            for k, v in masters.items()}


def sgd_momentum_step(w, mom, g, lr, momentum, wd=0.0):
    """One update of every leaf, all float32 (the masters):
    mom = momentum * mom - lr * (g + wd * w);  w = w + mom."""
    new_w, new_m = {}, {}
    for n in g:
        new_m[n] = momentum * mom[n] - lr * (g[n] + wd * w[n])
        new_w[n] = w[n] + new_m[n]
    return new_w, new_m


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def param_shapes(cfg):
    """{name: shape} of every parameter, as the program's builder names
    and shapes them (2-D weights as (out, in); the experts held as one
    array per projection, (experts_held, in, out); the selection bias as
    (1, n_routed_experts))."""
    d, V, H = int(cfg['hidden_size']), int(cfg['vocab_size']), \
        int(cfg['num_attention_heads'])
    Dn, Dr = int(cfg['qk_nope_head_dim']), int(cfg['qk_rope_head_dim'])
    Dv, r = int(cfg['v_head_dim']), int(cfg['kv_lora_rank'])
    experts, held = int(cfg['n_routed_experts']), experts_held(cfg)
    wide, narrow = int(cfg['intermediate_size']), \
        int(cfg['moe_intermediate_size'])
    shared = narrow * int(cfg['n_shared_experts'])
    out = {'embed_weight': (V, d), 'final_norm_gamma': (d,),
           'head_weight': (V, d)}
    for i in range(int(cfg['num_hidden_layers'])):
        n = layer_name(i)
        out.update({
            n + '_attn_norm_gamma': (d,), n + '_mlp_norm_gamma': (d,),
            n + '_attn_q_weight': (H * (Dn + Dr), d),
            n + '_attn_kv_a_weight': (r + Dr, d),
            n + '_attn_kv_norm_gamma': (r,),
            n + '_attn_kv_b_weight': (H * (Dn + Dv), r),
            n + '_attn_o_weight': (d, H * Dv)})
        if is_sparse(cfg, i):
            out.update({
                n + '_moe_router_weight': (experts, d),
                n + '_moe_select_bias_weight': (1, experts),
                n + '_moe_experts_w1_weight': (held, d, narrow),
                n + '_moe_experts_w3_weight': (held, d, narrow),
                n + '_moe_experts_w2_weight': (held, narrow, d),
                n + '_moe_shared_w1_weight': (shared, d),
                n + '_moe_shared_w3_weight': (shared, d),
                n + '_moe_shared_w2_weight': (d, shared)})
        else:
            out.update({n + '_mlp_w1_weight': (wide, d),
                        n + '_mlp_w3_weight': (wide, d),
                        n + '_mlp_w2_weight': (d, wide)})
    return out
