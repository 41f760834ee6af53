"""Plain reference for decoders of the ``olmo_hybrid`` family, for
training: gated delta-rule linear attention in most layers, full attention
without positions in the others, a dense gated MLP in every layer, the
norm after each sub-layer, an untied head. The configuration the benchmark
runs is allenai's Olmo-Hybrid-7B (``benchmark/configs/olmo_hybrid_7b.json``;
its ``assumed`` lists what the published keys leave open and this file
settles).

Plain ``jax.numpy``, float32, under
``jax.default_matmul_precision('highest')``; no kernels, no import of
``mxnet_tpu``; only parameter *names* are shared with the program
(``examples/transformer/symbols/olmo_hybrid.py``). The gated MLP, the
float8 control's rounding and the update are ``reference/deepseek_v3.py``'s,
the dense masked attention and the blocked loss ``reference/lfm2_moe.py``'s,
beside this file.

The equations (``d`` is ``hidden_size``, ``eps`` ``rms_norm_eps``; no bias
anywhere; every sum float32):

* ``h_0 = Emb[ids]``. Block l: ``h = h + RMSNorm(Op_l(h))`` by
  ``layer_types[l]``, then ``h = h + RMSNorm(MLP(h))``: the norm follows
  the sub-layer, none precedes it. ``logits = RMSNorm(h_L) W_head^T``,
  mean cross-entropy of the next token.
* ``linear_attention`` (``H = linear_num_value_heads`` heads, ``dk =
  linear_key_head_dim``, ``dv = linear_value_head_dim``):
  ``[q | k | v] = silu(conv([h Wq | h Wk | h Wv]))`` with a causal
  depthwise convolution of ``linear_conv_kernel_dim`` taps a channel, zero
  before the sequence's start (tap j weighs the row ``taps - 1 - j``
  back); per head ``q = q / sqrt(|q|^2 + 1e-6) / sqrt(dk)``, ``k = k /
  sqrt(|k|^2 + 1e-6)``; ``beta = 2 sigmoid(h Wb)``
  (``linear_allow_neg_eigval``; 1 without), ``g = -exp(A_log) softplus(h
  Wa + dt_bias)`` with ``A_log = leaf + linear_A_log_offset`` and
  ``dt_bias = leaf + linear_dt_bias_offset``; then ROW BY ROW, with ``S``
  in ``R^{dk x dv}`` and ``S = 0`` before the first row,

      S = exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S = S + k_t u^T;
      o_t = S^T q_t

  and ``Op(h) = (RMSNorm_dv(o) * silu(h Wg)) Wo`` with one gain of ``dv``
  for all heads.
* ``full_attention``: ``q = RMSNorm(h Wq)``, ``k = RMSNorm(h Wk)`` over all
  their columns, ``v = h Wv``, split into heads of ``d /
  num_attention_heads`` columns; no rotary turn; scores ``q k^T /
  sqrt(D)``, causal, softmax in float32; ``Op(h) = Attn Wo``.

Departures from the published description: none that is known. The model's
code is not on this machine; the equations are those of ISSUE 49 and the
points they settle are the configuration's ``assumed``.

The recurrence's backward pass through T rows would keep T states (9 GB a
layer at 4096 rows of 30 heads): it runs under ``jax.checkpoint`` by
segments of `SEGMENT` rows, so that one state a segment is kept and one
segment's rows are walked again.

``loss_and_grad`` gives (loss, an empty array where another family counts
its experts' pairs, the gradient, 0.0 where another family has a second
loss). With ``at_masters`` it is handed the float32 masters and computes
with their bfloat16 roundings (``working_weights``'s values), rounded where
they are used, a block at a time, the gradient passing the rounding
unchanged.

``quant`` (the control of the benchmark's comparison) rounds both operands
of every matrix product, of the attention's two and of the recurrence's (q,
k and v as it reads them) to float8 e4m3 with one scale per tensor,
straight-through in the backward pass; the convolution's and the gates'
elementwise arithmetic stays float32.
"""
import functools
import json

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import deepseek_v3 as base
from benchmark.reference import lfm2_moe
from benchmark.reference.xing4_0 import rounded_in_passing

matmul, rms_norm, gated_mlp = base.matmul, base.rms_norm, base.gated_mlp
hashable, working_weights = base.hashable, base.working_weights
sgd_momentum_step, layer_name = base.sgd_momentum_step, base.layer_name
short_conv, attention = lfm2_moe.short_conv, lfm2_moe.attention
cross_entropy = lfm2_moe.cross_entropy
SEGMENT = 64        # rows of the recurrence between two kept states
NORM_EPS = 1e-6     # under the root of q's and k's squared length


# ---------------------------------------------------------------------------
# the operators
# ---------------------------------------------------------------------------

def delta_rule(q, k, v, g, beta, segment=SEGMENT, keep_states=False):
    """o (T, H, dv) of the recurrence for q, k (T, H, dk), v (T, H, dv), g
    and beta (T, H), row by row; with `keep_states` also the state after
    every row (T, H, dk, dv), for tests."""
    T, H, dk = q.shape
    pad = -T % segment      # rows that leave the state as it is

    def rows(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, segment) + x.shape[1:])

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[:, None, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum('hkv,hk->hv', S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, (jnp.einsum('hkv,hk->hv', S, q_t),
                   S if keep_states else None)

    @jax.checkpoint
    def walk(S, seg):
        return lax.scan(step, S, seg)

    S, (o, states) = lax.scan(
        walk, jnp.zeros((H, dk, v.shape[-1]), jnp.float32),
        tuple(rows(x) for x in (q, k, v, g, beta)))
    o = o.reshape((-1,) + o.shape[2:])[:T]
    if keep_states:
        return o, states.reshape((-1,) + states.shape[2:])[:T]
    return o


def unit(x, scale=1.0):
    return x * (lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + NORM_EPS)
                * scale)


def decay_and_beta(p, name, h, cfg, quant=False):
    """(g, beta), each (T, H), of the block's input h (T, d)."""
    a_log = p[name + '_A_log_weight'][0] \
        + float(cfg.get('linear_A_log_offset', 0.0))
    dt_bias = p[name + '_dt_bias_weight'][0] \
        + float(cfg.get('linear_dt_bias_offset', 0.0))
    g = -jnp.exp(a_log) * jax.nn.softplus(
        matmul(h, p[name + '_a_weight'].T, quant) + dt_bias)
    top = 2.0 if cfg.get('linear_allow_neg_eigval', False) else 1.0
    return g, top * jax.nn.sigmoid(matmul(h, p[name + '_b_weight'].T, quant))


def linear_attention_block(p, name, h, cfg, quant=False):
    """The linear-attention operator on the block's input h (T, d)."""
    T = h.shape[0]
    H = int(cfg['linear_num_value_heads'])
    dk, dv = int(cfg['linear_key_head_dim']), int(cfg['linear_value_head_dim'])
    qkv = jnp.concatenate([matmul(h, p[name + '_%s_weight' % x].T, quant)
                           for x in 'qkv'], axis=-1)
    qkv = jax.nn.silu(short_conv(qkv, p[name + '_taps_weight']))
    q, k, v = jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1)
    q = unit(q.reshape(T, H, dk), dk ** -0.5)
    k, v = unit(k.reshape(T, H, dk)), v.reshape(T, H, dv)
    if quant:
        q, k, v = base._fp8(q), base._fp8(k), base._fp8(v)
    g, beta = decay_and_beta(p, name, h, cfg, quant)
    o = delta_rule(q, k, v, g, beta)
    o = rms_norm(o, p[name + '_o_norm_gamma'], float(cfg['rms_norm_eps']))
    gate = jax.nn.silu(matmul(h, p[name + '_gate_weight'].T, quant))
    return matmul(o.reshape(T, H * dv) * gate, p[name + '_o_weight'].T, quant)


def head_dim(cfg):
    return int(cfg.get('head_dim') or int(cfg['hidden_size'])
               // int(cfg['num_attention_heads']))


def attention_block(p, name, h, cfg, quant=False):
    """The attention operator on the block's input h (T, d)."""
    T = h.shape[0]
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    D, eps = head_dim(cfg), float(cfg['rms_norm_eps'])
    q = rms_norm(matmul(h, p[name + '_q_weight'].T, quant),
                 p[name + '_q_norm_gamma'], eps).reshape(T, H, D)
    k = rms_norm(matmul(h, p[name + '_k_weight'].T, quant),
                 p[name + '_k_norm_gamma'], eps).reshape(T, KV, D)
    v = matmul(h, p[name + '_v_weight'].T, quant).reshape(T, KV, D)
    o = attention(q, k, v, quant)
    return matmul(o.reshape(T, H * D), p[name + '_o_weight'].T, quant)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def block(p, name, h, cfg, kind, quant=False):
    """One decoder block on h (T, d)."""
    eps = float(cfg['rms_norm_eps'])
    if kind == 'linear_attention':
        op = linear_attention_block(p, name + '_lin', h, cfg, quant)
    elif kind == 'full_attention':
        op = attention_block(p, name + '_attn', h, cfg, quant)
    else:
        raise ValueError('olmo_hybrid: layer type %r' % (kind,))
    h = h + rms_norm(op, p[name + '_op_norm_gamma'], eps)
    mlp = gated_mlp(h, p[name + '_mlp_w1_weight'].T,
                    p[name + '_mlp_w3_weight'].T,
                    p[name + '_mlp_w2_weight'].T, quant)
    return h + rms_norm(mlp, p[name + '_ffn_norm_gamma'], eps)


def forward(p, tokens, labels, cfg, quant=False, remat=True,
            at_masters=False):
    """The sum of the cross-entropies of one sequence: tokens, labels
    (T,). `at_masters`: p holds float32 masters, used through
    :func:`rounded_in_passing` (a block's inside its stage, the others'
    here)."""
    use = rounded_in_passing if at_masters else (lambda tree: tree)
    top = use({k: v for k, v in p.items() if not k.startswith('layer')})
    h = top['embed_weight'][tokens]
    for i in range(int(cfg['num_hidden_layers'])):
        name = layer_name(i)
        sub = {k: v for k, v in p.items() if k.startswith(name + '_')}
        kind = cfg['layer_types'][i]

        def stage(sub, h, name=name, kind=kind):
            return block(use(sub), name, h, cfg, kind, quant)

        h = (jax.checkpoint(stage) if remat else stage)(sub, h)
    h = rms_norm(h, top['final_norm_gamma'], float(cfg['rms_norm_eps']))
    return cross_entropy(top['head_weight'], h, labels, quant)


def mean_loss(p, tokens, labels, cfg, quant=False, remat=True,
              at_masters=False):
    """Mean cross-entropy over every token of the step. tokens, labels
    (B, T) integer."""
    total = 0.0
    for b in range(tokens.shape[0]):
        total = total + forward(p, tokens[b], labels[b], cfg, quant, remat,
                                at_masters)
    return total / tokens.size


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _loss_and_grad(p, tokens, labels, cfg_json, quant, at_masters=False):
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision('highest'):
        loss, g = jax.value_and_grad(
            lambda q: mean_loss(q, tokens, labels, cfg, quant,
                                at_masters=at_masters))(p)
    # no expert layer: no pairs; no second head: 0.0
    return loss, jnp.zeros((0,)), g, jnp.zeros(())


def loss_and_grad(p, tokens, labels, cfg, quant=False, at_masters=False):
    """(loss, an empty array, gradient of every leaf)."""
    return _loss_and_grad(p, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(labels, jnp.int32), hashable(cfg),
                          bool(quant), bool(at_masters))[:3]


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def param_shapes(cfg):
    """{name: shape} of every parameter, as the program's builder names and
    shapes them (2-D weights as (out, in); the taps as (channels, taps);
    the decay's two leaves as (1, heads))."""
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    H, KV, D = int(cfg['num_attention_heads']), \
        int(cfg['num_key_value_heads']), head_dim(cfg)
    LH = int(cfg['linear_num_value_heads'])
    dk, dv = int(cfg['linear_key_head_dim']), int(cfg['linear_value_head_dim'])
    wide = int(cfg['intermediate_size'])
    out = {'embed_weight': (V, d), 'head_weight': (V, d),
           'final_norm_gamma': (d,)}
    for i in range(int(cfg['num_hidden_layers'])):
        n = layer_name(i)
        out.update({n + '_op_norm_gamma': (d,), n + '_ffn_norm_gamma': (d,),
                    n + '_mlp_w1_weight': (wide, d),
                    n + '_mlp_w3_weight': (wide, d),
                    n + '_mlp_w2_weight': (d, wide)})
        if cfg['layer_types'][i] == 'linear_attention':
            out.update({
                n + '_lin_q_weight': (LH * dk, d),
                n + '_lin_k_weight': (LH * dk, d),
                n + '_lin_v_weight': (LH * dv, d),
                n + '_lin_taps_weight': (
                    LH * (2 * dk + dv), int(cfg['linear_conv_kernel_dim'])),
                n + '_lin_a_weight': (LH, d), n + '_lin_b_weight': (LH, d),
                n + '_lin_A_log_weight': (1, LH),
                n + '_lin_dt_bias_weight': (1, LH),
                n + '_lin_gate_weight': (LH * dv, d),
                n + '_lin_o_norm_gamma': (dv,),
                n + '_lin_o_weight': (d, LH * dv)})
        else:
            out.update({
                n + '_attn_q_weight': (H * D, d),
                n + '_attn_k_weight': (KV * D, d),
                n + '_attn_v_weight': (KV * D, d),
                n + '_attn_o_weight': (d, H * D),
                n + '_attn_q_norm_gamma': (H * D,),
                n + '_attn_k_norm_gamma': (KV * D,)})
    return out
