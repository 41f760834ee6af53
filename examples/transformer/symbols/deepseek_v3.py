"""Decoders of the ``deepseek_v3`` family as a Symbol for ``Module.fit``:
latent attention (MLA), a sigmoid router with a selection bias, shared
experts. Written for kakaocorp's kanana-2-30b-a3b-instruct-2601
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601).

``get_symbol(config)`` builds the network from the keys of the model's
published ``config.json``: an embedding, ``num_hidden_layers`` blocks, a
last RMSNorm and an untied head, trained on the mean cross-entropy of the
next token. A block is

    a = RMSNorm(h);  q = a Wq;  [c_kv | k_rope] = a Wa
    [k_nope | v] = RMSNorm(c_kv) Wb        (per head)
    h = h + LatentAttention(q_nope, rope(q_rope), k_nope, rope(k_rope), v) Wo
    b = RMSNorm(h);  h = h + MLP(b)        (layers before first_k_dense_replace)
                     h = h + MoE(b)        (the others)

with ``num_attention_heads`` heads whose keys are ``qk_nope_head_dim +
qk_rope_head_dim`` wide and whose values ``v_head_dim``, the keys and values
expanded from a latent of ``kv_lora_rank`` (training computes this form;
the absorbed one is a decode path), one rotary key head shared by all
heads, interleaved rotary pairs (``rope_interleave``), and
``n_routed_experts`` routed experts scored by a sigmoid, chosen by score
plus selection bias, of which this program holds ``experts_held`` from
``expert_offset`` on (all of them by default) beside ``n_shared_experts``
shared ones, which are one gated MLP of that many times the width. Only
what this family's published configs of this kind set is built:
``q_lora_rank`` null (no low-rank query path), ``n_group`` 1 (no group
limit), ``rope_scaling`` null; anything else raises. The ops are
``mxnet_tpu/ops/transformer.py``; the plain reference that the tests and
the benchmark compare with is ``benchmark/reference/deepseek_v3.py``.

The selection bias is the argument ``layerN_moe_select_bias_weight`` of
shape ``(1, n_routed_experts)``; it takes no gradient (``MoE`` stops it),
so an optimizer without weight decay leaves it as given.

``data``, ``softmax_label``, the output, ``dtype`` and ``remat`` are as in
``laguna.py`` beside this file: each block is one mirrored stage that
keeps the attention kernel's output and log-sum-exp.
"""
import mxnet_tpu as mx


def _check(cfg):
    if cfg.get('q_lora_rank') is not None:
        raise ValueError('deepseek_v3: q_lora_rank %r: no low-rank query '
                         'path is built' % (cfg['q_lora_rank'],))
    if int(cfg.get('n_group', 1)) != 1 or int(cfg.get('topk_group', 1)) != 1:
        raise ValueError('deepseek_v3: grouped routing (n_group %r, '
                         'topk_group %r) is not built'
                         % (cfg.get('n_group'), cfg.get('topk_group')))
    if cfg.get('rope_scaling') is not None:
        raise ValueError('deepseek_v3: rope_scaling %r is not built'
                         % (cfg['rope_scaling'],))
    if cfg.get('scoring_func', 'sigmoid') != 'sigmoid':
        raise ValueError('deepseek_v3: scoring_func %r'
                         % (cfg['scoring_func'],))


def get_symbol(config, dtype='float32', remat=True, **kwargs):
    cfg = config
    _check(cfg)
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    H = int(cfg['num_attention_heads'])
    Dn, Dr = int(cfg['qk_nope_head_dim']), int(cfg['qk_rope_head_dim'])
    Dv, rank = int(cfg['v_head_dim']), int(cfg['kv_lora_rank'])
    layers = int(cfg['num_hidden_layers'])
    experts = int(cfg['n_routed_experts'])
    held = int(cfg.get('experts_held', experts))
    offset = int(cfg.get('expert_offset', 0))
    eps = float(cfg.get('rms_norm_eps', 1e-6))
    dense_before = int(cfg.get('first_k_dense_replace', 0))
    every = int(cfg.get('moe_layer_freq', 1))
    rope = {'base': float(cfg['rope_theta']),
            'interleaved': bool(cfg.get('rope_interleave', False))}

    def var(name, **kw):
        return mx.sym.Variable(name, dtype=dtype, **kw)

    def linear(x, name, out):
        return mx.sym.FullyConnected(
            data=x, weight=var(name + '_weight'), num_hidden=out,
            no_bias=True, flatten=False, name=name)

    def norm(x, name):
        return mx.sym.RMSNorm(data=x, gamma=var(name + '_gamma'), eps=eps,
                              name=name)

    def columns(x, begin, end):
        return mx.sym.slice_axis(x, axis=-1, begin=begin, end=end)

    def per_head(x, width, begin, end):
        """Columns [begin, end) of every head of x [B, T, H * width]."""
        heads = mx.sym.Reshape(x, shape=(0, 0, H, width))
        return mx.sym.Reshape(columns(heads, begin, end), shape=(0, 0, -1))

    def block(h, i):
        name = 'layer%d' % i
        p = name + '_attn'
        a = norm(h, p + '_norm')
        q = linear(a, p + '_q', H * (Dn + Dr))
        c = linear(a, p + '_kv_a', rank + Dr)
        kv = linear(norm(columns(c, 0, rank), p + '_kv_norm'), p + '_kv_b',
                    H * (Dn + Dv))
        o = mx.sym.LatentAttention(
            q_nope=per_head(q, Dn + Dr, 0, Dn),
            q_rope=mx.sym.RotaryEmbedding(
                per_head(q, Dn + Dr, Dn, Dn + Dr), num_heads=H,
                name=p + '_q_rope', **rope),
            k_nope=per_head(kv, Dn + Dv, 0, Dn),
            k_rope=mx.sym.RotaryEmbedding(
                columns(c, rank, rank + Dr), num_heads=1,
                name=p + '_k_rope', **rope),
            value=per_head(kv, Dn + Dv, Dn, Dn + Dv),
            num_heads=H, name=p)
        h = h + linear(o, p + '_o', d)
        b = norm(h, name + '_mlp_norm')
        if i >= dense_before and i % every == 0:
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
                select_bias=var(p + '_select_bias_weight'),
                scoring='sigmoid', num_experts=experts, experts_held=held,
                expert_offset=offset,
                num_experts_per_tok=int(cfg['num_experts_per_tok']),
                norm_topk_prob=bool(cfg.get('norm_topk_prob', True)),
                routed_scaling=float(cfg.get('routed_scaling_factor', 1.0)),
                hidden=int(cfg['moe_intermediate_size']),
                shared_hidden=int(cfg['moe_intermediate_size'])
                * int(cfg['n_shared_experts']),
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
