"""Decoders of the ``sdar_moe`` family as a Symbol for ``Module.fit``:
a sparse-expert decoder trained as a block-diffusion model. Written for
JetLM's SDAR-30B-A3B-Chat
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat).

A training step reads a noisy and a clean copy of one sequence at once.
From L clean tokens ``x0`` the iterator beside this file
(``examples/transformer/blockdiff_iter.py``) draws a noise level ``t`` for
each block of ``block_length`` positions, masks each position of the block
with probability ``t`` (the mask id takes its place: ``xt``) and yields

    data          (batch, 2 L)   [xt ; x0], the noisy half first
    softmax_label (batch, L)     x0 where masked, -1 elsewhere (no shift)
    loss_weight   (batch, L)     1 / t of the position's block

``get_symbol(config, seq_len=L)`` builds the network from the keys of the
model's published ``config.json`` plus ``block_length`` (and
``experts_held``, ``expert_offset``) for steps of L clean tokens (the
rotary period and the head's slice are part of the symbol): an embedding, ``num_hidden_layers`` blocks, a last
RMSNorm and an untied head over the NOISY half alone. A block is

    a = RMSNorm(h);  q, k, v = a Wq, a Wk, a Wv
    h = h + Attention(rope(norm(q)), rope(norm(k)), v) Wo
    b = RMSNorm(h);  h = h + MoE(b)

with ``num_attention_heads`` query heads on ``num_key_value_heads``
key/value heads of ``head_dim`` columns, q and k each through an RMSNorm
over a head's columns (one gain for all heads of q, one for k) before the
rotary turn (all of a head's dimensions, halves paired, ``rope_theta``),
whose positions restart at the clean half (``RotaryEmbedding(period=L)``:
both copies of position p sit at p). The attention is
``GroupedQueryAttention(block_length=B)``: a noisy
row sees its own noisy block in both directions and the clean blocks
strictly before it; a clean row the clean blocks up to and including its
own; nothing sees another block's noise (Block Diffusion,
arXiv:2503.09573, which the SDAR report, arXiv:2510.06303, trains by).
``MoE``: ``num_experts`` experts of width ``moe_intermediate_size``, a
softmax over all of them, the ``num_experts_per_tok`` largest, their
weights over their sum (``norm_topk_prob``), no shared expert and no
selection bias; this program holds ``experts_held`` of them from
``expert_offset`` on (all by default). Every layer is sparse
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []); others are refused,
as are a rotary scaling, a sliding window and a tied head.

The head is ``WeightedSoftmaxOutput``: the objective is ``(1 / (batch L))
sum_i m_i w_i CE_i`` over the masked rows, each weighted by its block's
``1 / t``; the output is the softmax of the L noisy rows, so
``Perplexity(ignore_label=-1)`` on it and ``softmax_label`` holds the
plain mean over the masked rows, inside the fused window. The clean half's
rows of the LAST block are computed though only their keys and values are
read; they cost what any other block's do.

Bind with both label-side inputs: ``mx.mod.Module(sym,
label_names=['softmax_label', 'loss_weight'])``. ``dtype`` and ``remat``
are as in ``laguna.py`` beside this file; each block is one mirrored
stage, which keeps the attention kernels' operands, output and
log-sum-exp, the contracting projections' outputs and the expert layer's
plan. The plain reference that the tests and the benchmark compare with
is ``benchmark/reference/sdar_moe.py``, which also lists what the config
leaves open.
"""
import mxnet_tpu as mx

LABEL_NAMES = ['softmax_label', 'loss_weight']
IGNORE = -1


def _check(cfg):
    """Raises for what this file does not build."""
    if int(cfg.get('decoder_sparse_step', 1)) != 1 \
            or cfg.get('mlp_only_layers'):
        raise ValueError('sdar_moe: a dense layer among the sparse ones '
                         '(decoder_sparse_step, mlp_only_layers) is not '
                         'built')
    if cfg.get('rope_scaling'):
        raise ValueError('sdar_moe: rope_scaling %r is not built'
                         % (cfg['rope_scaling'],))
    if cfg.get('use_sliding_window', False):
        raise ValueError('sdar_moe: a sliding window is not built')
    if cfg.get('tie_word_embeddings', False):
        raise ValueError('sdar_moe: a tied head is not built')
    if cfg.get('attention_bias', False):
        raise ValueError('sdar_moe: attention_bias true is not built')


def get_symbol(config, dtype='float32', remat=True, seq_len=None, **kwargs):
    cfg = config
    _check(cfg)
    d, V = int(cfg['hidden_size']), int(cfg['vocab_size'])
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    D = int(cfg.get('head_dim') or d // H)
    layers = int(cfg['num_hidden_layers'])
    eps = float(cfg.get('rms_norm_eps', 1e-6))
    theta = float(cfg['rope_theta'])
    experts = int(cfg['num_experts'])
    block_length = int(cfg['block_length'])

    def var(name, **kw):
        return mx.sym.Variable(name, dtype=dtype, **kw)

    def linear(x, name, out):
        return mx.sym.FullyConnected(
            data=x, weight=var(name + '_weight'), num_hidden=out,
            no_bias=True, flatten=False, name=name)

    def norm(x, name):
        return mx.sym.RMSNorm(data=x, gamma=var(name + '_gamma'), eps=eps,
                              name=name)

    def head_norm_rope(x, p, heads, L):
        """RMSNorm over each head's columns, one gain for all heads, then
        the rotary turn at positions that restart at the clean half."""
        x = mx.sym.Reshape(norm(mx.sym.Reshape(x, shape=(0, -1, D)),
                                p + '_norm'), shape=(0, -1, heads * D))
        return mx.sym.RotaryEmbedding(x, num_heads=heads, base=theta,
                                      period=L, name=p + '_rope')

    def attention(a, p, L):
        o = mx.sym.GroupedQueryAttention(
            query=head_norm_rope(linear(a, p + '_q', H * D), p + '_q', H, L),
            key=head_norm_rope(linear(a, p + '_k', KV * D), p + '_k', KV, L),
            value=linear(a, p + '_v', KV * D), num_heads=H, num_kv_heads=KV,
            block_length=block_length, name=p)
        return linear(o, p + '_o', d)

    def experts_layer(b, p):
        return mx.sym.MoE(
            data=b, router_weight=var(p + '_router_weight'),
            experts_w1_weight=var(p + '_experts_w1_weight'),
            experts_w3_weight=var(p + '_experts_w3_weight'),
            experts_w2_weight=var(p + '_experts_w2_weight'),
            stats=mx.sym.Variable(p + '_stats', dtype='float32',
                                  init=mx.init.Zero()),
            scoring='softmax', num_experts=experts,
            experts_held=int(cfg.get('experts_held', experts)),
            expert_offset=int(cfg.get('expert_offset', 0)),
            num_experts_per_tok=int(cfg['num_experts_per_tok']),
            norm_topk_prob=bool(cfg.get('norm_topk_prob', True)),
            hidden=int(cfg['moe_intermediate_size']), shared_hidden=0,
            name=p)

    def block(h, i, L):
        name = 'layer%d' % i
        h = h + attention(norm(h, name + '_input_norm'), name + '_attn', L)
        return h + experts_layer(norm(h, name + '_post_attn_norm'),
                                 name + '_moe')

    if not seq_len:
        raise ValueError('sdar_moe: the clean length of a step (seq_len: '
                         'the rotary period and the head\'s slice) is part '
                         'of the symbol')
    L = int(seq_len)
    data = mx.sym.Variable('data', dtype='float32')
    label = mx.sym.Variable('softmax_label', dtype='float32')
    weight = mx.sym.Variable('loss_weight', dtype='float32')
    h = mx.sym.Embedding(data=data, weight=var('embed_weight'), input_dim=V,
                         output_dim=d, name='embed')
    for i in range(layers):
        if remat:
            with mx.AttrScope(__force_mirroring__='layer%d' % i):
                h = block(h, i, L)
        else:
            h = block(h, i, L)
    noisy = mx.sym.slice_axis(h, axis=1, begin=0, end=L,
                              name='blockdiff_mask_noisy')
    logits = linear(norm(noisy, 'final_norm'), 'head', V)
    if dtype == 'float16':
        logits = mx.sym.Cast(data=logits, dtype='float32')
    return mx.sym.WeightedSoftmaxOutput(
        data=mx.sym.Reshape(logits, shape=(-1, V)),
        label=mx.sym.Reshape(label, shape=(-1,)),
        weight=mx.sym.Reshape(weight, shape=(-1,)),
        ignore_label=IGNORE, normalization='batch', name='softmax')
