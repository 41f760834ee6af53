"""Decoders of the ``olmo_hybrid`` family as a Symbol for ``Module.fit``:
gated delta-rule linear attention where most layers of another decoder
have softmax attention, full attention without positions in the others, a
dense gated MLP in every layer, the norm AFTER each sub-layer. Written for
allenai's Olmo-Hybrid-7B (https://huggingface.co/allenai/Olmo-Hybrid-7B).

``get_symbol(config)`` builds the network from the keys of the model's
published ``config.json``: an embedding, ``num_hidden_layers`` blocks, a
last RMSNorm and an untied head, trained on the mean cross-entropy of the
next token. A block is

    h = h + RMSNorm(Op(h))        by layer_types[l]
    h = h + RMSNorm(MLP(h))

(no norm before a sub-layer: it reads the residual stream as it is).

``linear_attention`` (``H = linear_num_value_heads`` heads of
``linear_key_head_dim`` = dk and ``linear_value_head_dim`` = dv columns):

    [q | k | v] = silu(conv([h Wq | h Wk | h Wv]))   ShortConv: causal,
                       depthwise, linear_conv_kernel_dim taps, no bias
    beta = 2 sigmoid(h Wb)                 (linear_allow_neg_eigval: the 2)
    g    = -exp(A_log) * softplus(h Wa + dt_bias)        a scalar a head
    o    = GatedDeltaRule(q, k, v, g, beta)   q, k divided by their length
                                              inside, q by sqrt(dk)
    Op(h) = (RMSNorm_dv(o) * silu(h Wg)) Wo   one gain of dv for all heads

``A_log`` and ``dt_bias`` are leaves of ``(1, H)`` that take gradient:
``layerN_lin_A_log_weight`` and ``layerN_lin_dt_bias_weight`` hold the
parameter LESS the constants ``linear_A_log_offset`` and
``linear_dt_bias_offset`` of the configuration (0 by default; the same
gradient), so that a seeded start draws the leaf small around the
constant. beta, g and the gated norm's product are float32.

``full_attention``: ``num_attention_heads`` query heads on
``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads`` columns, q and k each through an RMSNorm over ALL
their columns before the heads are split, no rotary turn and no other
position (``rope_parameters.rope_theta`` null: the linear layers carry
the order), no gate, no bias.

Not built, and refused: a layer type other than the two,
``linear_num_key_heads != linear_num_value_heads``, a ``rope_theta`` that
is not null, ``tie_word_embeddings`` true, ``attention_bias`` true, an
activation other than ``silu``. The ops are
``mxnet_tpu/ops/transformer.py``; the plain reference that the tests and
the benchmark compare with is ``benchmark/reference/olmo_hybrid.py``,
which also lists what the config leaves open.

``data``, ``softmax_label``, the output, ``dtype`` and ``remat`` are as in
``laguna.py`` beside this file. Each block is one mirrored stage. Of a
linear-attention block it keeps, by the rules of ``ops/registry.py``: the
contracting projections' results (q, k, the output projection, the MLP's
last), the chain of chunks' output and the states at the chunks' starts
(``GatedDeltaRule``); the expanding projections (v, the gate), the
convolution and what a chunk needs beside the state are computed again.
"""
import mxnet_tpu as mx

LAYER_TYPES = ('linear_attention', 'full_attention')


def _check(cfg):
    """Raises for what this file does not build."""
    unknown = sorted(set(cfg['layer_types']) - set(LAYER_TYPES))
    if unknown:
        raise ValueError('olmo_hybrid: layer types %s are not built'
                         % unknown)
    if len(cfg['layer_types']) != int(cfg['num_hidden_layers']):
        raise ValueError('olmo_hybrid: %d layer_types for %d layers'
                         % (len(cfg['layer_types']),
                            int(cfg['num_hidden_layers'])))
    if int(cfg['linear_num_key_heads']) != int(cfg['linear_num_value_heads']):
        raise ValueError('olmo_hybrid: %d key heads on %d value heads in a '
                         'linear-attention layer is not built'
                         % (int(cfg['linear_num_key_heads']),
                            int(cfg['linear_num_value_heads'])))
    if (cfg.get('rope_parameters') or {}).get('rope_theta') is not None:
        raise ValueError('olmo_hybrid: a rotary turn (rope_theta %r) is not '
                         'built' % (cfg['rope_parameters']['rope_theta'],))
    if cfg.get('tie_word_embeddings', False):
        raise ValueError('olmo_hybrid: a tied head is not built')
    if cfg.get('attention_bias', False):
        raise ValueError('olmo_hybrid: attention_bias true is not built')
    if cfg.get('hidden_act', 'silu') != 'silu':
        raise ValueError('olmo_hybrid: hidden_act %r is not built'
                         % (cfg['hidden_act'],))


def get_symbol(config, dtype='float32', remat=True, **kwargs):
    cfg = config
    _check(cfg)
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    D = int(cfg.get('head_dim') or d // H)
    LH = int(cfg['linear_num_value_heads'])
    dk, dv = int(cfg['linear_key_head_dim']), int(cfg['linear_value_head_dim'])
    taps = int(cfg['linear_conv_kernel_dim'])
    eps = float(cfg.get('rms_norm_eps', 1e-6))
    beta_max = 2.0 if cfg.get('linear_allow_neg_eigval', False) else 1.0
    layers = int(cfg['num_hidden_layers'])

    def var(name, **kw):
        return mx.sym.Variable(name, dtype=dtype, **kw)

    def linear(x, name, out):
        return mx.sym.FullyConnected(
            data=x, weight=var(name + '_weight'), num_hidden=out,
            no_bias=True, flatten=False, name=name)

    def norm(x, name):
        return mx.sym.RMSNorm(data=x, gamma=var(name + '_gamma'), eps=eps,
                              name=name)

    def f32(x):
        return mx.sym.Cast(data=x, dtype='float32')

    def silu32(x):
        x = f32(x)
        return x * mx.sym.sigmoid(x)

    def offset_leaf(name, key):
        """The float32 parameter: its leaf plus the configuration's
        constant."""
        return f32(var(name + '_weight', shape=(1, LH))) \
            + float(cfg.get(key, 0.0))

    def linear_attention(h, p):
        qkv = mx.sym.Concat(linear(h, p + '_q', LH * dk),
                            linear(h, p + '_k', LH * dk),
                            linear(h, p + '_v', LH * dv), dim=2)
        qkv = mx.sym.ShortConv(data=qkv, weight=var(p + '_taps_weight'),
                               kernel=taps, name=p + '_conv')
        q, k, v = (mx.sym.slice_axis(qkv, axis=2, begin=a, end=b)
                   for a, b in ((0, LH * dk), (LH * dk, 2 * LH * dk),
                                (2 * LH * dk, LH * (2 * dk + dv))))
        beta = mx.sym.sigmoid(f32(linear(h, p + '_b', LH))) * beta_max
        dt = mx.sym.Activation(
            mx.sym.broadcast_add(f32(linear(h, p + '_a', LH)),
                                 mx.sym.Reshape(offset_leaf(
                                     p + '_dt_bias', 'linear_dt_bias_offset'),
                                     shape=(1, 1, LH))),
            act_type='softrelu')
        g = mx.sym.broadcast_mul(
            dt, mx.sym.Reshape(
                0.0 - mx.sym.exp(offset_leaf(p + '_A_log',
                                             'linear_A_log_offset')),
                shape=(1, 1, LH)))
        o = mx.sym.GatedDeltaRule(
            query=q, key=k, value=v, g=g, beta=beta,
            stats=mx.sym.Variable(p + '_stats', dtype='float32',
                                  init=mx.init.Zero()),
            num_heads=LH, name=p)
        o = mx.sym.Reshape(norm(mx.sym.Reshape(o, shape=(0, -1, dv)),
                                p + '_o_norm'), shape=(0, -1, LH * dv))
        o = mx.sym.Cast(f32(o) * silu32(linear(h, p + '_gate', LH * dv)),
                        dtype=dtype)
        return linear(o, p + '_o', d)

    def attention(h, p):
        o = mx.sym.GroupedQueryAttention(
            query=norm(linear(h, p + '_q', H * D), p + '_q_norm'),
            key=norm(linear(h, p + '_k', KV * D), p + '_k_norm'),
            value=linear(h, p + '_v', KV * D), num_heads=H, num_kv_heads=KV,
            name=p)
        return linear(o, p + '_o', d)

    def block(h, i):
        name = 'layer%d' % i
        if cfg['layer_types'][i] == 'linear_attention':
            op = linear_attention(h, name + '_lin')
        else:
            op = attention(h, name + '_attn')
        h = h + norm(op, name + '_op_norm')
        p = name + '_mlp'
        mlp = mx.sym.GatedMLP(
            data=h, w1_weight=var(p + '_w1_weight'),
            w3_weight=var(p + '_w3_weight'), w2_weight=var(p + '_w2_weight'),
            hidden=int(cfg['intermediate_size']), name=p)
        return h + norm(mlp, name + '_ffn_norm')

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
