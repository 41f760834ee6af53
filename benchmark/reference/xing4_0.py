"""Plain reference for decoders of the ``xing4_0`` family, for training:
the ``deepseek_v3`` sublayers (``reference/deepseek_v3.py`` beside this
file: latent attention in its expanded form, the sigmoid router with a
selection bias, the shared expert) with a low-rank query path and YaRN
rotary scaling, under a residual of ``hc_mult`` streams mixed by
manifold-constrained hyper-connections (arXiv:2512.24880), and one
multi-token-prediction module as a second loss (DeepSeek-V3 report, section
2.2). The configuration the benchmark runs is XingChen-AGI's
Xing4.0-29B-A4B (``benchmark/configs/xing4_0_29b_a4b.json``; its
``assumed`` lists what the published keys leave open and this file
settles).

Plain ``jax.numpy``, float32, under
``jax.default_matmul_precision('highest')``; no kernels, no import of
``mxnet_tpu``; only parameter *names* are shared with the program
(``examples/transformer/symbols/xing4_0.py``).

The equations (``n`` is ``hc_mult``, ``d`` ``hidden_size``; per token the
residual is X [n, d]; ``eps`` is ``rms_norm_eps``):

* ``X_0`` = the embedding copied into all n streams.
* Every sublayer F (attention; dense MLP or expert layer) has W
  [2n + n^2, n d], a bias [2n + n^2] and three scalars ``a_pre, a_post,
  a_res``. With ``x = vec(X)`` and ``m = (W x) / sqrt(mean(x^2) + eps)``:
  ``H_pre = sigmoid(a_pre m[:n] + b)``, ``H_post = 2 sigmoid(a_post
  m[n:2n] + b)``, ``M_0 = exp(clip(a_res mat(m[2n:]) + b,
  mhc_h_res_clamp_min, mhc_h_res_clamp_max))`` (row-major) and, for
  ``hc_sinkhorn_iters`` rounds, ``M = M / (column sums + hc_eps)`` then
  ``M = M / (row sums + hc_eps)``; ``y = sum_j H_pre[j] X[j]``, ``z =
  F(RMSNorm(y))``, ``X'[i] = H_post[i] z + sum_j M[i, j] X[j]``. The
  gradient runs through every round.
* Attention: ``c_q = RMSNorm(a W_qa)``, ``q = c_q W_qb`` (H heads of
  ``Dn + Dr``); keys and values as in ``deepseek_v3``; rotary pairs
  interleaved, YaRN frequencies, cos and sin times ``mscale /
  mscale_all_dim`` (1 here); scores times ``(Dn + Dr)^-0.5 (0.1
  mscale_all_dim ln factor + 1)^2``.
* After the last block ``h = sum_j sigmoid(a (W_head x) / rms + b)[j]
  X[j]``, the last RMSNorm, the untied head, the mean cross-entropy
  ``L_main``.
* The prediction module: ``h'_t = [RMSNorm(h_t) ; RMSNorm(Emb(label_t))]
  W_eh``; one sparse block on ``h'`` copied into n streams, its own
  collapse and last norm, the shared embedding and head; ``L_mtp`` is the
  mean cross-entropy against ``label_{t+1}`` over all positions but the
  last. The objective is ``L_main + mtp_loss_weight L_mtp``.

``loss_and_grad`` gives (``L_main``, the pairs computed by the held experts
per expert layer, the module's last, the gradient of the objective,
``L_mtp``). With ``at_masters`` it is handed the float32 masters and
computes with their bfloat16 roundings (``working_weights``'s values),
rounded where they are used, a block at a time, the gradient passing the
rounding unchanged: the same numbers as rounding first, without a second
copy of 913.5 M parameters beside masters, momentum and gradient, which
the chip does not hold.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import deepseek_v3 as base

matmul, rms_norm = base.matmul, base.rms_norm
hashable, working_weights = base.hashable, base.working_weights
sgd_momentum_step = base.sgd_momentum_step
ROW_BLOCK = base.ROW_BLOCK
MTP_WEIGHT = 0.3


# ---------------------------------------------------------------------------
# rotary positions with YaRN
# ---------------------------------------------------------------------------

def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(cfg, length):
    """(cos, sin, the scores' factor beside 1 / sqrt(Dn + Dr)); cos and
    sin (length, Dr / 2) float32."""
    dim, theta = int(cfg['qk_rope_head_dim']), float(cfg['rope_theta'])
    pos = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    s = cfg.get('rope_scaling')
    if s is None:
        inv, on_tables, on_scores = 1.0 / pos, 1.0, 1.0
    else:
        factor = float(s['factor'])
        original = float(s['original_max_position_embeddings'])

        def correction(rotations):
            return dim * math.log(original / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(correction(float(s.get('beta_fast', 32)))), 0)
        high = min(math.ceil(correction(float(s.get('beta_slow', 1)))),
                   dim - 1)
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        # interpolated where the ramp is 1, extrapolated where it is 0
        inv = ramp / (factor * pos) + (1.0 - ramp) / pos
        all_dim = float(s.get('mscale_all_dim', 0) or 0)
        on_tables = yarn_mscale(factor, float(s.get('mscale', 1))) \
            / yarn_mscale(factor, all_dim)
        on_scores = yarn_mscale(factor, all_dim) ** 2 if all_dim else 1.0
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None]
    return jnp.cos(angle) * on_tables, jnp.sin(angle) * on_tables, on_scores


def attention_block(p, name, a, cfg, tables, quant=False):
    """The attention sub-layer on the normed input a (T, d)."""
    cos, sin, on_scores = tables
    T = a.shape[0]
    H = int(cfg['num_attention_heads'])
    Dn, Dr = int(cfg['qk_nope_head_dim']), int(cfg['qk_rope_head_dim'])
    Dv, r = int(cfg['v_head_dim']), int(cfg['kv_lora_rank'])
    eps = float(cfg['rms_norm_eps'])
    if cfg.get('q_lora_rank') is None:
        q = matmul(a, p[name + '_q_weight'].T, quant)
    else:
        c_q = rms_norm(matmul(a, p[name + '_q_a_weight'].T, quant),
                       p[name + '_q_a_norm_gamma'], eps)
        q = matmul(c_q, p[name + '_q_b_weight'].T, quant)
    # base.attention scales by 1 / sqrt(Dn + Dr): the rest rides on q
    q = q.reshape(T, H, Dn + Dr) * on_scores
    c = matmul(a, p[name + '_kv_a_weight'].T, quant)
    c_kv = rms_norm(c[:, :r], p[name + '_kv_norm_gamma'], eps)
    kv = matmul(c_kv, p[name + '_kv_b_weight'].T, quant) \
        .reshape(T, H, Dn + Dv)
    q_rope = base.apply_rope_interleaved(q[..., Dn:], cos, sin)
    k_rope = base.apply_rope_interleaved(c[:, None, r:], cos, sin)[:, 0]
    o = base.attention(q[..., :Dn], q_rope, kv[..., :Dn], k_rope,
                       kv[..., Dn:], quant)
    return matmul(o.reshape(T, H * Dv), p[name + '_o_weight'].T, quant)


# ---------------------------------------------------------------------------
# the residual streams
# ---------------------------------------------------------------------------

def mixing_arguments(X, w, bias, eps, quant=False):
    """m W-projected and normalised, plus the bias not yet scaled:
    ((T, K) projections over the row's rms, bias (K,))."""
    x = X.reshape(X.shape[0], -1)
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return matmul(x, w.T, quant) / rms, bias.reshape(-1)


def sinkhorn(M, iters, eps):
    """M (T, n, n): `iters` rounds of columns, then rows (a ``lax.scan``
    of one round: this program's compilation is on a run's path, and 20
    rounds of 13 nodes unrolled, forward and backward, lengthen it)."""
    def one(M, _):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
        return M / (jnp.sum(M, axis=-1, keepdims=True) + eps), None

    return lax.scan(one, M, None, length=iters)[0]


def coefficients(p, name, X, cfg, quant=False):
    """(H_pre (T, n), H_post (T, n), M (T, n, n)) of the sublayer whose
    mixing parameters are ``<name>_hc_*``."""
    n = int(cfg['hc_mult'])
    m, b = mixing_arguments(X, p[name + '_hc_weight'],
                            p[name + '_hc_bias_weight'],
                            float(cfg['rms_norm_eps']), quant)
    a = p[name + '_hc_alpha_gamma']
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:],
                         float(cfg['mhc_h_res_clamp_min']),
                         float(cfg['mhc_h_res_clamp_max'])))
    M = sinkhorn(M.reshape(-1, n, n), int(cfg['hc_sinkhorn_iters']),
                 float(cfg['hc_eps']))
    return h_pre, h_post, M


def sublayer(p, name, X, cfg, f, quant=False):
    """X (T, n, d) after the sublayer f (normed input (T, d) -> update)."""
    h_pre, h_post, M = coefficients(p, name, X, cfg, quant)
    y = jnp.einsum('tj,tjd->td', h_pre, X)
    z = f(rms_norm(y, p[name + '_norm_gamma'], float(cfg['rms_norm_eps'])))
    return h_post[:, :, None] * z[:, None, :] \
        + jnp.einsum('tij,tjd->tid', M, X)


def collapse(p, name, X, cfg, quant=False):
    """The streams read once more: (T, d)."""
    m, b = mixing_arguments(X, p[name + '_weight'], p[name + '_bias_weight'],
                            float(cfg['rms_norm_eps']), quant)
    return jnp.einsum('tj,tjd->td', jax.nn.sigmoid(
        p[name + '_alpha_gamma'][0] * m + b), X)


def streams(h, cfg):
    return jnp.repeat(h[:, None, :], int(cfg['hc_mult']), axis=1)


def block(p, name, X, cfg, tables, sparse, quant=False):
    """One decoder block on the streams X; (X', pairs on the experts
    held)."""
    X = sublayer(p, name + '_attn', X, cfg,
                 lambda a: attention_block(p, name + '_attn', a, cfg, tables,
                                           quant), quant)
    pairs = []

    def feed_forward(b):
        if not sparse:
            pairs.append(jnp.zeros((), jnp.int32))
            return base.gated_mlp(b, p[name + '_mlp_w1_weight'].T,
                                  p[name + '_mlp_w3_weight'].T,
                                  p[name + '_mlp_w2_weight'].T, quant)
        y, n = base.moe_layer(p, name + '_moe', b, cfg,
                              base.experts_held(cfg),
                              int(cfg.get('expert_offset', 0)), quant)
        pairs.append(n)
        return y

    X = sublayer(p, name + '_mlp', X, cfg, feed_forward, quant)
    return X, pairs[0]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def cross_entropy(p, h, labels, valid, quant=False):
    """Sum over the rows where `valid` of -log softmax(h W_head)[label],
    a block of rows at a time."""
    T = h.shape[0]
    blk = min(ROW_BLOCK, T)
    pad = (-T) % blk
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, blk, h.shape[1])
    yb = jnp.pad(labels, (0, pad)).reshape(-1, blk)
    mb = jnp.pad(valid, (0, pad)).reshape(-1, blk)

    @jax.checkpoint
    def rows(args):
        hx, yx, mx = args
        logp = jax.nn.log_softmax(matmul(hx, p['head_weight'].T, quant),
                                  axis=-1)
        picked = jnp.take_along_axis(logp, yx[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(mx, picked, 0.0))

    return jnp.sum(lax.map(rows, (hb, yb, mb)))


def _stage(f, remat):
    return jax.checkpoint(f) if remat else f


def rounded_in_passing(tree):
    """``working_weights(tree)`` as a function of the masters whose
    gradient is the gradient at the rounded values: ``v + (round(v) - v)``
    is ``round(v)`` exactly (the difference of two neighbours is exact)."""
    return {k: v + lax.stop_gradient(
        lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7) - v)
        for k, v in tree.items()}


def _is_block_leaf(k):
    return k.startswith('layer') or k.startswith(
        ('mtp_attn_', 'mtp_mlp_', 'mtp_moe_'))


def forward(p, tokens, labels, cfg, quant=False, remat=True,
            at_masters=False):
    """(sum of the main head's cross-entropies, sum of the prediction
    module's over its T - 1 positions (0.0 without a module), pairs per
    expert layer) for one sequence: tokens, labels (T,). `at_masters`: p
    holds float32 masters, used through :func:`rounded_in_passing` (a
    block's inside its stage, the others' here)."""
    use = rounded_in_passing if at_masters else (lambda tree: tree)
    p = dict(p, **use({k: v for k, v in p.items() if not _is_block_leaf(k)}))
    T = tokens.shape[0]
    eps = float(cfg['rms_norm_eps'])
    tables = rope_tables(cfg, T)
    X = streams(p['embed_weight'][tokens], cfg)
    pairs = []
    for i in range(int(cfg['num_hidden_layers'])):
        name = base.layer_name(i)
        sub = {k: v for k, v in p.items() if k.startswith(name + '_')}
        sparse = base.is_sparse(cfg, i)
        X, n = _stage(lambda sub, X, name=name, sparse=sparse: block(
            use(sub), name, X, cfg, tables, sparse, quant), remat)(sub, X)
        if sparse:
            pairs.append(n)
    h = collapse(p, 'head_hc', X, cfg, quant)
    main = cross_entropy(p, rms_norm(h, p['final_norm_gamma'], eps), labels,
                         jnp.ones((T,), bool), quant)
    if not int(cfg.get('num_nextn_predict_layers', 0)):
        return main, jnp.zeros(()), pairs
    joined = jnp.concatenate(
        [rms_norm(h, p['mtp_h_norm_gamma'], eps),
         rms_norm(p['embed_weight'][labels], p['mtp_e_norm_gamma'], eps)],
        axis=-1)
    sub = {k: v for k, v in p.items()
           if k.startswith('mtp_') and _is_block_leaf(k)}
    X, n = _stage(lambda sub, h1: block(
        use(sub), 'mtp', streams(h1, cfg), cfg, tables, True, quant), remat)(
        sub, matmul(joined, p['mtp_eh_weight'].T, quant))
    pairs.append(n)
    g = rms_norm(collapse(p, 'mtp_head_hc', X, cfg, quant),
                 p['mtp_final_norm_gamma'], eps)
    # position t predicts label[t + 1]; the last has nothing to predict
    ahead = jnp.concatenate([labels[1:], labels[:1]])
    return main, cross_entropy(p, g, ahead, jnp.arange(T) < T - 1,
                               quant), pairs


def losses(p, tokens, labels, cfg, quant=False, remat=True,
           at_masters=False):
    """(L_main, L_mtp, pairs per expert layer summed over the sequences):
    tokens, labels (B, T) integer."""
    main = mtp = 0.0
    pairs = None
    for b in range(tokens.shape[0]):
        m, t, n = forward(p, tokens[b], labels[b], cfg, quant, remat,
                          at_masters)
        main, mtp = main + m, mtp + t
        pairs = n if pairs is None else [x + y for x, y in zip(pairs, n)]
    B, T = tokens.shape
    return main / (B * T), mtp / max(B * (T - 1), 1), \
        jnp.stack(pairs) if pairs else jnp.zeros((0,))


def mtp_weight(cfg):
    return float(cfg.get('mtp_loss_weight', MTP_WEIGHT))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _loss_and_grad(p, tokens, labels, cfg_json, quant, at_masters=False):
    cfg = json.loads(cfg_json)

    def objective(q):
        main, mtp, pairs = losses(q, tokens, labels, cfg, quant,
                                  at_masters=at_masters)
        return main + mtp_weight(cfg) * mtp, (main, mtp, pairs)

    with jax.default_matmul_precision('highest'):
        (_, (main, mtp, pairs)), g = jax.value_and_grad(
            objective, has_aux=True)(p)
    return main, pairs, g, mtp


def loss_and_grad(p, tokens, labels, cfg, quant=False, at_masters=False):
    """(L_main, pairs per expert layer, gradient of L_main +
    mtp_loss_weight L_mtp for every leaf, L_mtp)."""
    return _loss_and_grad(p, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(labels, jnp.int32), hashable(cfg),
                          bool(quant), bool(at_masters))


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def _block_shapes(cfg, n, sparse):
    d, H = int(cfg['hidden_size']), int(cfg['num_attention_heads'])
    Dn, Dr = int(cfg['qk_nope_head_dim']), int(cfg['qk_rope_head_dim'])
    Dv, r = int(cfg['v_head_dim']), int(cfg['kv_lora_rank'])
    streams_ = int(cfg['hc_mult'])
    k = 2 * streams_ + streams_ ** 2
    out = {}
    for sub in ('_attn', '_mlp'):
        out.update({n + sub + '_hc_weight': (k, streams_ * d),
                    n + sub + '_hc_bias_weight': (1, k),
                    n + sub + '_hc_alpha_gamma': (3,),
                    n + sub + '_norm_gamma': (d,)})
    if cfg.get('q_lora_rank') is None:
        out[n + '_attn_q_weight'] = (H * (Dn + Dr), d)
    else:
        rq = int(cfg['q_lora_rank'])
        out.update({n + '_attn_q_a_weight': (rq, d),
                    n + '_attn_q_a_norm_gamma': (rq,),
                    n + '_attn_q_b_weight': (H * (Dn + Dr), rq)})
    out.update({n + '_attn_kv_a_weight': (r + Dr, d),
                n + '_attn_kv_norm_gamma': (r,),
                n + '_attn_kv_b_weight': (H * (Dn + Dv), r),
                n + '_attn_o_weight': (d, H * Dv)})
    experts, held = int(cfg['n_routed_experts']), base.experts_held(cfg)
    wide, narrow = int(cfg['intermediate_size']), \
        int(cfg['moe_intermediate_size'])
    shared = narrow * int(cfg['n_shared_experts'])
    if sparse:
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


def _collapse_shapes(cfg, n):
    streams_, d = int(cfg['hc_mult']), int(cfg['hidden_size'])
    return {n + '_weight': (streams_, streams_ * d),
            n + '_bias_weight': (1, streams_), n + '_alpha_gamma': (1,)}


def param_shapes(cfg):
    """{name: shape} of every parameter, as the program's builder names and
    shapes them (``reference/deepseek_v3.py``'s rules; a sublayer's mixing
    map as (2n + n^2, n d), its biases as (1, 2n + n^2), its three scalars
    as (3,))."""
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    out = {'embed_weight': (V, d), 'final_norm_gamma': (d,),
           'head_weight': (V, d)}
    out.update(_collapse_shapes(cfg, 'head_hc'))
    for i in range(int(cfg['num_hidden_layers'])):
        out.update(_block_shapes(cfg, base.layer_name(i),
                                 base.is_sparse(cfg, i)))
    if int(cfg.get('num_nextn_predict_layers', 0)):
        out.update({'mtp_h_norm_gamma': (d,), 'mtp_e_norm_gamma': (d,),
                    'mtp_eh_weight': (d, 2 * d),
                    'mtp_final_norm_gamma': (d,)})
        out.update(_block_shapes(cfg, 'mtp', True))
        out.update(_collapse_shapes(cfg, 'mtp_head_hc'))
    return out
