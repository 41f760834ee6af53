"""Plain reference for the Laguna-S-2.1 decoder (poolside), for training.

Forward pass, mean cross-entropy over the tokens of the step, its
gradient and the multi-precision SGD-with-momentum update in plain
``jax.numpy``, float32, under ``jax.default_matmul_precision('highest')``
(the step's definition includes that its forward and backward pass read the
masters rounded to bfloat16, ``working_weights``; all arithmetic is
float32). No kernels, no import of ``mxnet_tpu``: dense masks, a loop over the
experts held. Only parameter *names* are shared with the program
(``examples/transformer/symbols/laguna.py``).

The equations, from the published ``config.json`` (``cfg`` below is that
file's content as ``benchmark/configs/laguna_s_2_1.json`` holds it; ``d``
is ``hidden_size``, ``D`` is ``head_dim``, layer ``l`` has
``H = num_attention_heads_per_layer[l]`` query heads,
``num_key_value_heads`` key/value heads and kind ``layer_types[l]``):

* ``a = RMSNorm(h)``; ``q = a Wq`` as ``(T, H, D)``, ``k = a Wk`` and
  ``v = a Wv`` as ``(T, KV, D)``; no bias.
* Rotary positions on ``q`` and ``k`` with the settings of the layer's
  kind in ``rope_parameters``: ``default`` is the plain form on
  ``partial_rotary_factor * D`` dimensions; ``yarn`` blends interpolated
  and extrapolated frequencies between the ``beta_fast`` and ``beta_slow``
  correction dimensions and multiplies cosine and sine by
  ``attention_factor``. The rotated dimensions come first and are paired
  half against half (``rotate_half``), the convention of the published
  implementations of this family; the rest pass through.
* Query head ``i`` reads key/value head ``i // (H / KV)``. Scores
  ``q k^T / sqrt(D)``; position ``t`` sees ``s <= t`` and, on
  ``sliding_attention`` layers, ``s > t - sliding_window``. Softmax in
  float32, times ``v``.
* Per-head gate: ``g = sigmoid(a Wg)``, ``Wg`` of ``d x H``; head ``i``'s
  output is multiplied by ``g[:, i]``. ``h = h + concat(heads) Wo``.
* ``b = RMSNorm(h)``. A ``dense`` layer: ``h = h + (silu(b W1) * (b W3)) W2``.
* A ``sparse`` layer: ``p = softmax(b Wr)`` over all ``num_experts``, the
  ``num_experts_per_tok`` largest taken, their weights divided by their
  sum (``norm_topk_prob``) and multiplied by
  ``moe_routed_scaling_factor``; every expert and the shared expert is
  the same gated MLP; ``h = h + sum_k w_k E_k(b) + E_shared(b)``. Of the
  routed experts only those *held here* (``experts_held`` from
  ``expert_offset``) are computed: what the absent ones would add is left
  out, as one chip of the deployment leaves it to the others.
* A last RMSNorm, logits ``h Whead`` (untied), mean cross-entropy.

What the config does not say is listed, with the reason for each choice,
under ``assumed`` in the configuration's file.

``quant`` (the control of the benchmark's comparison) rounds both operands
of every matrix product (projections, scores, values, experts, router,
head) to float8 e4m3 with one scale per tensor, straight-through in the
backward pass: the nearest precision below the bfloat16 the configuration
states.

For the chip at the published widths the work is cut in blocks so that it
fits: every layer is a ``jax.checkpoint``, attention runs over blocks of
queries (each a dense masked product against every key, or on a windowed
layer against the span of keys the block's mask can let through), and the
loss over blocks of rows of the logits.
"""
import functools
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


def rope_inv_freq(rope, head_dim):
    """(inverse frequencies of the rotated pairs, factor on cos and sin)
    for one entry of ``rope_parameters``."""
    dim = int(head_dim * float(rope.get('partial_rotary_factor', 1)))
    base = float(rope['rope_theta'])
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rope['rope_type'] == 'default':
        return 1.0 / pos_freqs, 1.0
    if rope['rope_type'] != 'yarn':
        raise ValueError('rope_type %r' % (rope['rope_type'],))
    factor = float(rope['factor'])
    original = float(rope['original_max_position_embeddings'])

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rope['beta_fast']))), 0)
    high = min(math.ceil(correction_dim(float(rope['beta_slow']))), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    extrapolated, interpolated = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    inv_freq = interpolated * ramp + extrapolated * (1.0 - ramp)
    attention_factor = rope.get('attention_factor')
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, float(attention_factor)


def rope_tables(rope, head_dim, length):
    """(cos, sin), each (length, rotated dims / 2), float32."""
    inv_freq, scale = rope_inv_freq(rope, head_dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def apply_rope(x, cos, sin):
    """x (..., T, heads, D); the first 2 * cos.shape[-1] dimensions of D
    are rotated, half against half."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def attention(q, k, v, window, quant=False, q_block=Q_BLOCK):
    """Causal grouped-query attention of one sequence. q (T, H, D), k and
    v (T, KV, D); `window` 0 for full attention. Dense masked products, a
    block of queries at a time: against every key, or, on a windowed layer
    longer than one span, against the `window + q_block` keys that end with
    the block (the only ones its mask can let through)."""
    T, H, D = q.shape
    KV = k.shape[1]
    group = H // KV
    scale = 1.0 / math.sqrt(D)
    if quant:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    q_block = min(q_block, T)
    pad = (-T) % q_block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    blocks = qp.reshape(-1, q_block, KV, group, D)
    starts = jnp.arange(blocks.shape[0]) * q_block
    span = window + q_block if window and window + q_block < T else 0
    if span:
        # key position c sits at row c + window of the padded arrays, so
        # the span of the block that starts at `start` begins at row `start`
        kp = jnp.pad(k, ((window, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((window, pad), (0, 0), (0, 0)))

    @jax.checkpoint
    def one(args):
        qb, start = args
        if span:
            kb = lax.dynamic_slice_in_dim(kp, start, span)
            vb = lax.dynamic_slice_in_dim(vp, start, span)
            cols = start - window + jnp.arange(span)
        else:
            kb, vb, cols = k, v, jnp.arange(T)
        s = jnp.einsum('qkgd,skd->kgqs', qb, kb) * scale
        # rows of the padding look where the last token looks
        rows = jnp.minimum(start + jnp.arange(q_block), T - 1)
        seen = (cols[None, :] <= rows[:, None]) & (cols[None, :] >= 0) \
            & (cols[None, :] < T)
        if window:
            seen &= cols[None, :] > rows[:, None] - window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if quant:
            p = _fp8(p)
        return jnp.einsum('kgqs,skd->qkgd', p, vb)

    out = lax.map(one, (blocks, starts))
    return out.reshape(-1, H, D)[:T]


def attention_block(p, name, a, heads, kv_heads, head_dim, window, cos, sin,
                    quant=False):
    """The attention sub-layer on the normed input a (T, d): projections,
    rotary positions, attention, the per-head gate and the output
    projection."""
    T = a.shape[0]
    q = matmul(a, p[name + '_q_weight'].T, quant).reshape(T, heads, head_dim)
    k = matmul(a, p[name + '_k_weight'].T, quant).reshape(T, kv_heads,
                                                        head_dim)
    v = matmul(a, p[name + '_v_weight'].T, quant).reshape(T, kv_heads,
                                                        head_dim)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    o = attention(q, k, v, window, quant)
    gate = jax.nn.sigmoid(matmul(a, p[name + '_g_weight'].T, quant))
    o = o * gate[:, :, None]
    return matmul(o.reshape(T, heads * head_dim), p[name + '_o_weight'].T,
                  quant)


def gated_mlp(x, w1, w3, w2, quant=False):
    """(silu(x w1) * (x w3)) w2, weights as (in, out)."""
    return matmul(jax.nn.silu(matmul(x, w1, quant)) * matmul(x, w3, quant),
                  w2, quant)


def route(b, wr, top_k, scaling, quant=False):
    """(experts (T, top_k), weights (T, top_k)) of the router: softmax over
    all experts, the top_k largest, normalised to sum 1, times `scaling`."""
    probs = jax.nn.softmax(matmul(b, wr.T, quant), axis=-1)
    w, idx = lax.top_k(probs, top_k)
    return idx, w / jnp.sum(w, axis=-1, keepdims=True) * scaling


def moe_layer(p, name, b, cfg, held, offset, quant=False, shared=True):
    """What the experts [offset, offset + held) and (if `shared`) the
    shared expert add for the normed input b (T, d). Returns (sum, number
    of token-expert pairs that landed on the experts held)."""
    idx, w = route(b, p[name + '_router_weight'],
                   int(cfg['num_experts_per_tok']),
                   float(cfg['moe_routed_scaling_factor']), quant)
    out = jnp.zeros_like(b)
    pairs = 0
    for e in range(held):       # a loop over the experts held
        hit = idx == (offset + e)
        weight = jnp.sum(jnp.where(hit, w, 0.0), axis=-1)
        pairs = pairs + jnp.sum(hit)
        y = gated_mlp(b, p[name + '_experts_w1_weight'][e],
                      p[name + '_experts_w3_weight'][e],
                      p[name + '_experts_w2_weight'][e], quant)
        out = out + weight[:, None] * y
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


def heads_of(cfg, i):
    per = cfg.get('num_attention_heads_per_layer')
    return int(per[i]) if per else int(cfg['num_attention_heads'])


def is_sparse(cfg, i):
    return cfg['mlp_layer_types'][i] == 'sparse'


def forward(p, tokens, cfg, quant=False, remat=True):
    """(hidden states after the last norm (T, d), pairs computed by the
    held experts per sparse layer) for one sequence of token ids (T,)."""
    T = tokens.shape[0]
    eps = float(cfg['rms_norm_eps'])
    D, KV = int(cfg['head_dim']), int(cfg['num_key_value_heads'])
    tables = {kind: rope_tables(rope, D, T)
              for kind, rope in cfg['rope_parameters'].items()}
    h = p['embed_weight'][tokens]
    pairs = []
    for i in range(int(cfg['num_hidden_layers'])):
        name = layer_name(i)
        kind = cfg['layer_types'][i]
        window = int(cfg['sliding_window']) \
            if kind == 'sliding_attention' else 0
        sub = {k: v for k, v in p.items() if k.startswith(name + '_')}

        def layer(sub, h, i=i, name=name, kind=kind, window=window):
            a = rms_norm(h, sub[name + '_attn_norm_gamma'], eps)
            h = h + attention_block(sub, name + '_attn', a, heads_of(cfg, i),
                                    KV, D, window, *tables[kind],
                                    quant=quant)
            b = rms_norm(h, sub[name + '_mlp_norm_gamma'], eps)
            if is_sparse(cfg, i):
                y, n = moe_layer(sub, name + '_moe', b, cfg,
                                 int(cfg['experts_held']),
                                 int(cfg['expert_offset']), quant)
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
    import json
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _loss_and_grad(p, tokens, labels, cfg_json, quant):
    import json
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
    masters rounded to the bfloat16 the configuration keeps its parameters
    in (and held in float32: the arithmetic stays float32). The forward
    pass of such a step never sees a change of a master below bfloat16's
    resolution; a reference that did would follow another algorithm."""
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
    array per projection, (experts_held, in, out))."""
    d, D = int(cfg['hidden_size']), int(cfg['head_dim'])
    KV, V = int(cfg['num_key_value_heads']), int(cfg['vocab_size'])
    held = int(cfg['experts_held'])
    wide, narrow = int(cfg['intermediate_size']), \
        int(cfg['moe_intermediate_size'])
    shared = int(cfg['shared_expert_intermediate_size'])
    out = {'embed_weight': (V, d), 'final_norm_gamma': (d,),
           'head_weight': (V, d)}
    for i in range(int(cfg['num_hidden_layers'])):
        n, H = layer_name(i), heads_of(cfg, i)
        out.update({
            n + '_attn_norm_gamma': (d,), n + '_mlp_norm_gamma': (d,),
            n + '_attn_q_weight': (H * D, d),
            n + '_attn_k_weight': (KV * D, d),
            n + '_attn_v_weight': (KV * D, d),
            n + '_attn_g_weight': (H, d),
            n + '_attn_o_weight': (d, H * D)})
        if is_sparse(cfg, i):
            out.update({
                n + '_moe_router_weight': (int(cfg['num_experts']), d),
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
