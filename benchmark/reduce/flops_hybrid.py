"""Operations and bytes a decoder of the ``lfm2_moe`` family *requires*
(gated short convolutions in most layers, grouped-query attention in the
others, routed experts without a shared one, a head tied to the embedding),
counted from the configuration's shapes as ``flops_lm.py`` counts Laguna's:
2 operations per multiply-add, a trained token 3 times the forward count, no
recomputation, no elementwise work.

Per token and forward pass: a conv operator's two projections (``d x 3 d``
in, ``d x d`` out; its three taps a channel are elementwise work and are
counted as bytes, below), an attention operator's four (q and the output of
``H D``, k and v of ``KV D``) and its two products over the keys a query
sees, the dense MLP or, on a sparse layer, the router and the routed
experts held at the pairs that land on them (expected
``num_experts_per_tok * experts_held / num_experts`` a token, or a counted
number), the head (the embedding once more: the same product as an untied
one).

``conv_bytes``: what the conv operators have to move for one trained step,
each array once in the activations' 2 bytes, with v a step's [rows, d]:
forward the three thirds of the input projection's result in and the
result out, 4 v; backward those three and the result's cotangent in, the
three thirds' cotangents out, 7 v. The taps (``d x L`` numbers) are left
out. Whatever implements the operator is held to these.

``attention_work`` and ``expert_work`` are ``flops_lm``'s counts at this
family's keys (a head is ``hidden_size / num_attention_heads`` wide where
the file has no ``head_dim``); ``expert_least_seconds`` is the larger of
the products' time and the bytes' (rows and three readings of the weights
a layer and step): at 512 rows a held expert the products bound it.
"""
from benchmark.reduce import flops_lm

visible_pairs = flops_lm.visible_pairs
expert_work = flops_lm.expert_work
expert_weight_bytes = flops_lm.expert_weight_bytes


def head_dim(cfg):
    return int(cfg.get('head_dim') or int(cfg['hidden_size'])
               // int(cfg['num_attention_heads']))


def layers_of(cfg, kind):
    return sum(1 for k in cfg['layer_types'] if k == kind)


def sparse_layers(cfg):
    return int(cfg['num_hidden_layers']) - int(cfg.get('num_dense_layers', 0))


def forward_flops_per_token(cfg, seq_len, pairs_per_token=None):
    """{part: operations per token, forward}: 'conv_projections',
    'attention_projections', 'attention_full', 'dense_mlp', 'router',
    'experts', 'head'."""
    d, D = int(cfg['hidden_size']), head_dim(cfg)
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    experts = int(cfg['num_experts'])
    if pairs_per_token is None:
        pairs_per_token = (int(cfg['num_experts_per_tok'])
                           * int(cfg.get('experts_held', experts))
                           / float(experts))
    conv, attn = layers_of(cfg, 'conv'), layers_of(cfg, 'full_attention')
    sparse = sparse_layers(cfg)
    return {
        'conv_projections': conv * 2.0 * (d * 3 * d + d * d),
        'attention_projections': attn * 2.0 * (2 * d * H * D
                                               + 2 * d * KV * D),
        'attention_full': attn * 4.0 * D * H
        * visible_pairs(seq_len, 0) / seq_len,
        'dense_mlp': (int(cfg['num_hidden_layers']) - sparse) * 6.0 * d
        * int(cfg['intermediate_size']),
        'router': sparse * 2.0 * d * experts,
        'experts': sparse * pairs_per_token * 6.0 * d
        * int(cfg['moe_intermediate_size']),
        'head': 2.0 * d * int(cfg['vocab_size'])}


def required_flops(cfg, seq_len, pairs_per_token=None):
    """Operations per token: {'forward', 'train', 'parts'}."""
    parts = forward_flops_per_token(cfg, seq_len, pairs_per_token)
    fwd = sum(parts.values())
    return {'forward': fwd, 'train': 3 * fwd, 'parts': parts}


def conv_bytes(cfg, seq_len, batch, itemsize=2):
    """Bytes the conv operators must move for one trained step."""
    v = batch * seq_len * int(cfg['hidden_size']) * itemsize
    return layers_of(cfg, 'conv') * (4 + 7) * v


def attention_work(cfg, seq_len, batch):
    """(operations, bytes) that the attention kernels of every attention
    layer need for one trained step: ``flops_lm.attention_work``'s count
    (forward two products, backward five, over the visible pairs; q, k, v,
    the output, its cotangent and the three gradients each moved once, in
    bfloat16)."""
    D, H = head_dim(cfg), int(cfg['num_attention_heads'])
    KV = int(cfg['num_key_value_heads'])
    layers = layers_of(cfg, 'full_attention')
    pairs = visible_pairs(seq_len, 0) * batch
    rows = batch * seq_len
    return (layers * (2 + 5) * 2.0 * D * H * pairs,
            layers * 2.0 * rows * D * ((2 * H + 2 * KV) + (5 * H + 4 * KV)))


def expert_least_seconds(cfg, pairs, steps, peak):
    """The least time of the grouped expert products for `pairs`
    token-expert pairs over `steps` steps of every sparse layer: the
    products over the bf16 peak, or the rows' bytes and three readings of
    every held expert's weights a layer and step over the memory
    bandwidth, whichever is larger. `peak`: ``peaks.peaks_of``'s."""
    flops, bytes_ = expert_work(cfg, pairs)
    bytes_ += 3 * expert_weight_bytes(cfg) * sparse_layers(cfg) * steps
    return max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
