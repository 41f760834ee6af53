"""Laguna-S-2.1 (poolside), the decoder as a Symbol for ``Module.fit``.

``get_symbol(config)`` builds the network from the keys of the model's
published ``config.json`` (https://huggingface.co/poolside/Laguna-S-2.1):
an embedding, ``num_hidden_layers`` blocks, a last RMSNorm and an untied
head, trained on the mean cross-entropy of the next token. A block is

    a = RMSNorm(h);  q, k, v, g = a Wq, a Wk, a Wv, a Wg
    h = h + (gate(g) * Attention(rope(q), rope(k), v)) Wo
    b = RMSNorm(h);  h = h + MLP(b)          (layer kinds 'dense')
                     h = h + MoE(b)          (layer kinds 'sparse')

with ``num_attention_heads_per_layer[l]`` query heads against
``num_key_value_heads`` key/value heads, causal attention that is full or
windowed (``sliding_window``) by ``layer_types[l]``, rotary positions from
``rope_parameters[layer_types[l]]``, a per-head sigmoid gate, the SwiGLU
MLP, and ``num_experts`` routed experts of which this program holds
``experts_held`` from ``expert_offset`` on (all of them by default) beside
the shared expert. The ops are ``mxnet_tpu/ops/transformer.py``; the plain
reference that the tests and the benchmark compare with is
``benchmark/reference/laguna.py``, which also lists what the config leaves
open. This family has a shared expert in every sparse layer and heads of
128 columns; neither is the ops' limit: ``MoE`` with ``shared_hidden`` 0
has no shared expert and no inputs for one, and heads narrower than 128
lanes are handled below ``GroupedQueryAttention``, where the compiled
attention kernels cross them as ``[B, H, T, D]``
(``ops/pallas_kernels.py``; ``lfm2_moe.py`` beside this file builds both).

``data`` and ``softmax_label`` are ``(batch, seq_len)`` token ids; the one
output is ``(batch * seq_len, vocab_size)`` probabilities, which is what
``Module.fit``'s in-graph ``ce``/``acc`` statistics take.

``dtype='float16'``: parameters are float16 variables (bfloat16 under
MXTPU_F16_AS_BF16) and a ``multi_precision`` optimizer keeps float32
masters, as for the image-classification symbols. ``remat``: each block is
one mirrored stage (``__force_mirroring__``), recomputed in the backward
pass, so that a step keeps one block's activations and not all of them.
Of a block a stage keeps what it reads, what leaves it, and what an op
inside named as dear to recompute: the attention kernel's output and
log-sum-exp (``ops/pallas_kernels.py``), so the kernel's forward pass
runs once a step; projections, rotary positions, norms, the MLP and the
expert layer are computed again.
"""
import mxnet_tpu as mx


def _rope_attrs(rope, head_dim):
    attrs = {'base': float(rope['rope_theta']),
             'rotary_dim': int(head_dim
                               * float(rope.get('partial_rotary_factor', 1))),
             'scaling': rope.get('rope_type', 'default')}
    if attrs['scaling'] == 'yarn':
        attrs.update(
            factor=float(rope['factor']),
            original_max_position=int(
                rope['original_max_position_embeddings']),
            beta_fast=float(rope.get('beta_fast', 32)),
            beta_slow=float(rope.get('beta_slow', 1)),
            attention_factor=float(rope.get('attention_factor') or 0.0))
    return attrs


def get_symbol(config, dtype='float32', remat=True, **kwargs):
    cfg = config
    d, D = int(cfg['hidden_size']), int(cfg['head_dim'])
    KV, V = int(cfg['num_key_value_heads']), int(cfg['vocab_size'])
    layers = int(cfg['num_hidden_layers'])
    experts = int(cfg.get('num_experts', 0))
    held = int(cfg.get('experts_held', experts))
    offset = int(cfg.get('expert_offset', 0))
    eps = float(cfg.get('rms_norm_eps', 1e-6))
    heads = cfg.get('num_attention_heads_per_layer') \
        or [int(cfg['num_attention_heads'])] * layers
    kinds = cfg.get('layer_types') or ['full_attention'] * layers
    mlps = cfg.get('mlp_layer_types') or ['dense'] * layers

    def var(name, **kw):
        return mx.sym.Variable(name, dtype=dtype, **kw)

    def linear(x, name, out):
        return mx.sym.FullyConnected(
            data=x, weight=var(name + '_weight'), num_hidden=out,
            no_bias=True, flatten=False, name=name)

    def norm(x, name):
        return mx.sym.RMSNorm(data=x, gamma=var(name + '_gamma'), eps=eps,
                              name=name)

    def block(h, i):
        name, H, kind = 'layer%d' % i, int(heads[i]), kinds[i]
        rope = _rope_attrs(cfg['rope_parameters'][kind], D)
        a = norm(h, name + '_attn_norm')
        q = mx.sym.RotaryEmbedding(linear(a, name + '_attn_q', H * D),
                                   num_heads=H, name=name + '_attn_q_rope',
                                   **rope)
        k = mx.sym.RotaryEmbedding(linear(a, name + '_attn_k', KV * D),
                                   num_heads=KV, name=name + '_attn_k_rope',
                                   **rope)
        o = mx.sym.GroupedQueryAttention(
            query=q, key=k, value=linear(a, name + '_attn_v', KV * D),
            gate=linear(a, name + '_attn_g', H), gated=True, num_heads=H,
            num_kv_heads=KV, name=name + '_attn',
            window=int(cfg['sliding_window'])
            if kind == 'sliding_attention' else 0)
        h = h + linear(o, name + '_attn_o', d)
        b = norm(h, name + '_mlp_norm')
        if mlps[i] == 'sparse':
            p = name + '_moe'
            y = mx.sym.MoE(
                data=b, router_weight=var(p + '_router_weight'),
                experts_w1_weight=var(p + '_experts_w1_weight'),
                experts_w3_weight=var(p + '_experts_w3_weight'),
                experts_w2_weight=var(p + '_experts_w2_weight'),
                shared_w1_weight=var(p + '_shared_w1_weight'),
                shared_w3_weight=var(p + '_shared_w3_weight'),
                shared_w2_weight=var(p + '_shared_w2_weight'),
                stats=mx.sym.Variable(p + '_stats', dtype='float32',
                                      init=mx.init.Zero()),
                num_experts=experts, experts_held=held, expert_offset=offset,
                num_experts_per_tok=int(cfg['num_experts_per_tok']),
                norm_topk_prob=bool(cfg.get('norm_topk_prob', True)),
                routed_scaling=float(
                    cfg.get('moe_routed_scaling_factor', 1.0)),
                hidden=int(cfg['moe_intermediate_size']),
                shared_hidden=int(cfg['shared_expert_intermediate_size']),
                name=p)
        else:
            p = name + '_mlp'
            y = mx.sym.GatedMLP(
                data=b, w1_weight=var(p + '_w1_weight'),
                w3_weight=var(p + '_w3_weight'),
                w2_weight=var(p + '_w2_weight'),
                hidden=int(cfg['intermediate_size']), name=p)
        return h + y

    data = mx.sym.Variable('data', dtype='float32')
    label = mx.sym.Variable('softmax_label', dtype='float32')
    h = mx.sym.Embedding(data=data, weight=var('embed_weight'), input_dim=V,
                         output_dim=d, name='embed')
    for i in range(layers):
        if remat:
            with mx.AttrScope(__force_mirroring__='layer%d' % i):
                h = block(h, i)
        else:
            h = block(h, i)
    logits = linear(norm(h, 'final_norm'), 'head', V)
    if dtype == 'float16':
        logits = mx.sym.Cast(data=logits, dtype='float32')
    return mx.sym.SoftmaxOutput(
        data=mx.sym.Reshape(logits, shape=(-1, V)),
        label=mx.sym.Reshape(label, shape=(-1,)), normalization='valid',
        name='softmax')
