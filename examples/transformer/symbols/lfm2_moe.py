"""Decoders of the ``lfm2_moe`` family as a Symbol for ``Module.fit``: gated
short convolutions where most layers of another decoder have attention,
grouped-query attention with narrow heads in the others, routed experts
without a shared one, a head tied to the embedding. Written for LiquidAI's
LFM2-24B-A2B (https://huggingface.co/LiquidAI/LFM2-24B-A2B).

``get_symbol(config)`` builds the network from the keys of the model's
published ``config.json``: an embedding, ``num_hidden_layers`` blocks, a
last RMSNorm and the embedding once more as the head, trained on the mean
cross-entropy of the next token. A block is

    a = RMSNorm(h);  h = h + Op(a)        by layer_types[l]
    b = RMSNorm(h);  h = h + MLP(b)       (l < num_dense_layers)
                     h = h + MoE(b)       (the others)

``conv``: ``[B | C | x] = a W_in`` (``W_in`` of 3 x hidden rows), ``Op(a) =
(C * conv(B * x)) W_out`` with a causal depthwise convolution of
``conv_L_cache`` taps a channel and no bias, no activation
(``GatedShortConv``). ``full_attention``: ``num_attention_heads`` query
heads on ``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads`` columns, q and k each through an RMSNorm over a
head's columns (one gain for all heads of q, one for k) before the rotary
turn (all of a head's dimensions, halves paired, ``rope_parameters``), no
gate, no bias. Heads narrower than 128 columns are handled below the op:
the attention kernels cross them as ``[B, H, T, D]``
(``ops/pallas_kernels.py``). ``MoE``: ``num_experts`` experts of width
``moe_intermediate_size`` scored by a sigmoid, the ``num_experts_per_tok``
largest of score plus the expert bias (``use_expert_bias``; a leaf
``layerN_moe_select_bias_weight`` of shape ``(1, num_experts)`` that takes
no gradient), weights the chosen scores over their sum plus 1e-6
(``norm_topk_prob``) times ``routed_scaling_factor``; this program holds
``experts_held`` of them from ``expert_offset`` on (all by default);
no shared expert (``MoE`` with ``shared_hidden`` 0). The dense layers'
MLP is ``intermediate_size`` wide.

The embedding and the head are ONE variable, ``tied_embed_weight`` of
``(vocab_size, hidden_size)``, read by ``Embedding`` and by the head's
``FullyConnected``: its gradient is the sum of the gather's scatter and
the product's, and the optimizer updates it once.

Not built, and refused: ``conv_bias`` true, a ``rope_type`` other than
``default``, a layer type other than the two, a router without the expert
bias. The ops are ``mxnet_tpu/ops/transformer.py``; the plain reference
that the tests and the benchmark compare with is
``benchmark/reference/lfm2_moe.py``, which also lists what the config
leaves open.

``data``, ``softmax_label``, the output, ``dtype`` and ``remat`` are as in
``laguna.py`` beside this file. Each block is one mirrored stage. Of a
conv block it keeps, by the rules of ``ops/registry.py``, the output
projection's result (2048 to 2048: it contracts nothing and expands
nothing) and nothing of the operator: the input projection expands (its
output is three times what it was made from) and is computed again, and
``GatedShortConv`` behind it with it, one pass over its bytes.
"""
import mxnet_tpu as mx

LAYER_TYPES = ('conv', 'full_attention')
# what the published code adds to the sum of the chosen scores
NORM_EPS = 1e-6


def _check(cfg):
    """Raises for what this file does not build."""
    if cfg.get('conv_bias', False):
        raise ValueError('lfm2_moe: conv_bias true is not built')
    rope = cfg.get('rope_parameters') or {}
    if rope.get('rope_type', 'default') != 'default':
        raise ValueError('lfm2_moe: rope_type %r is not built'
                         % (rope['rope_type'],))
    unknown = sorted(set(cfg['layer_types']) - set(LAYER_TYPES))
    if unknown:
        raise ValueError('lfm2_moe: layer types %s are not built' % unknown)
    if len(cfg['layer_types']) != int(cfg['num_hidden_layers']):
        raise ValueError('lfm2_moe: %d layer_types for %d layers'
                         % (len(cfg['layer_types']),
                            int(cfg['num_hidden_layers'])))
    if not cfg.get('use_expert_bias', True):
        raise ValueError('lfm2_moe: a router without the expert bias '
                         '(use_expert_bias false) is not built')


def get_symbol(config, dtype='float32', remat=True, **kwargs):
    cfg = config
    _check(cfg)
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    D = int(cfg.get('head_dim') or d // H)
    layers, dense = int(cfg['num_hidden_layers']), \
        int(cfg.get('num_dense_layers', 0))
    taps = int(cfg.get('conv_L_cache', 3))
    eps = float(cfg.get('norm_eps', 1e-5))
    theta = float(cfg['rope_parameters']['rope_theta'])
    experts = int(cfg.get('num_experts', 0))

    def var(name, **kw):
        return mx.sym.Variable(name, dtype=dtype, **kw)

    def linear(x, name, out, weight=None):
        return mx.sym.FullyConnected(
            data=x, weight=var(name + '_weight') if weight is None else weight,
            num_hidden=out, no_bias=True, flatten=False, name=name)

    def norm(x, name):
        return mx.sym.RMSNorm(data=x, gamma=var(name + '_gamma'), eps=eps,
                              name=name)

    def conv(a, p):
        bcx = linear(a, p + '_in', 3 * d)
        y = mx.sym.GatedShortConv(data=bcx, weight=var(p + '_taps_weight'),
                                  kernel=taps, name=p)
        return linear(y, p + '_out', d)

    def head_norm_rope(x, p, heads):
        """RMSNorm over each head's columns, one gain for all heads, then
        the rotary turn."""
        x = mx.sym.Reshape(norm(mx.sym.Reshape(x, shape=(0, -1, D)),
                                p + '_norm'), shape=(0, -1, heads * D))
        return mx.sym.RotaryEmbedding(x, num_heads=heads, base=theta,
                                      name=p + '_rope')

    def attention(a, p):
        o = mx.sym.GroupedQueryAttention(
            query=head_norm_rope(linear(a, p + '_q', H * D), p + '_q', H),
            key=head_norm_rope(linear(a, p + '_k', KV * D), p + '_k', KV),
            value=linear(a, p + '_v', KV * D), num_heads=H, num_kv_heads=KV,
            name=p)
        return linear(o, p + '_o', d)

    def feed_forward(b, name, sparse):
        if not sparse:
            p = name + '_mlp'
            return mx.sym.GatedMLP(
                data=b, w1_weight=var(p + '_w1_weight'),
                w3_weight=var(p + '_w3_weight'),
                w2_weight=var(p + '_w2_weight'),
                hidden=int(cfg['intermediate_size']), name=p)
        p = name + '_moe'
        return mx.sym.MoE(
            data=b, router_weight=var(p + '_router_weight'),
            experts_w1_weight=var(p + '_experts_w1_weight'),
            experts_w3_weight=var(p + '_experts_w3_weight'),
            experts_w2_weight=var(p + '_experts_w2_weight'),
            stats=mx.sym.Variable(p + '_stats', dtype='float32',
                                  init=mx.init.Zero()),
            select_bias=var(p + '_select_bias_weight'),
            scoring='sigmoid', num_experts=experts,
            experts_held=int(cfg.get('experts_held', experts)),
            expert_offset=int(cfg.get('expert_offset', 0)),
            num_experts_per_tok=int(cfg['num_experts_per_tok']),
            norm_topk_prob=bool(cfg.get('norm_topk_prob', True)),
            norm_eps=NORM_EPS,
            routed_scaling=float(cfg.get('routed_scaling_factor', 1.0)),
            hidden=int(cfg['moe_intermediate_size']), shared_hidden=0,
            name=p)

    def block(h, i):
        name = 'layer%d' % i
        a = norm(h, name + '_op_norm')
        if cfg['layer_types'][i] == 'conv':
            h = h + conv(a, name + '_conv')
        else:
            h = h + attention(a, name + '_attn')
        return h + feed_forward(norm(h, name + '_ffn_norm'), name,
                                i >= dense)

    data = mx.sym.Variable('data', dtype='float32')
    label = mx.sym.Variable('softmax_label', dtype='float32')
    tied = var('tied_embed_weight')
    h = mx.sym.Embedding(data=data, weight=tied, input_dim=V, output_dim=d,
                         name='embed')
    for i in range(layers):
        if remat:
            with mx.AttrScope(__force_mirroring__='layer%d' % i):
                h = block(h, i)
        else:
            h = block(h, i)
    logits = linear(norm(h, 'final_norm'), 'head', V, weight=tied)
    if dtype == 'float16':
        logits = mx.sym.Cast(data=logits, dtype='float32')
    return mx.sym.SoftmaxOutput(
        data=mx.sym.Reshape(logits, shape=(-1, V)),
        label=mx.sym.Reshape(label, shape=(-1,)), normalization='valid',
        name='softmax')
