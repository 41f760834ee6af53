"""Operations and bytes a decoder of the ``olmo_hybrid`` family *requires*
(gated delta-rule linear attention in most layers, full attention without
positions in the others, a dense gated MLP in every layer, an untied
head), counted from the configuration's shapes as ``flops_lm.py`` counts
Laguna's: 2 operations per multiply-add, a trained token 3 times the
forward count, no recomputation, no elementwise work.

Per token and forward pass: a linear-attention operator's projections (q
and k of ``H dk``, v, the gate and the output of ``H dv``, the decay's and
beta's of ``H``) and its scan; an attention operator's four projections
and its two products over the keys a query sees; the MLP of every layer;
the head.

The scan is counted in its chunked form at a chunk of `CHUNK` rows,
whatever chunk the program takes: per head and chunk the products ``K
K^T``, ``Q K^T`` and ``T (K e^gamma)`` (``2 C^2 dk`` each), ``T V`` and
``P U'`` (``2 C^2 dv`` each), the triangular solve (``C^3``), and ``W S``,
``Q S`` and the state's update (``2 C dk dv`` each): ``2 C (3 dk + 2 dv) +
C^2 + 6 dk dv`` a token. (The recurrence row by row would need ``6 dk dv``
alone, on a chain of T steps; the chunked form buys a chain of T / C with
the rest.)

``delta_rule_work`` is what the op has to do for one trained step: the
operations above forward and twice that backward; the bytes of q, k, v, g,
beta and o each moved once forward, and those and their cotangents once
backward (q, k, v, o in the activations' 2 bytes, g and beta in float32's
4). ``delta_rule_least_seconds`` is the larger of the two times: at the
published shapes the bytes bound it. Whatever implements the op is held
to these.

``conv_bytes`` is what the convolutions over ``[q | k | v]`` have to move
for one trained step: the operand and the output forward, the operand, the
output's cotangent and the operand's backward, each ``[rows, H (2 dk +
dv)]`` once in the activations' 2 bytes; no recomputation (the backward
pass makes the convolution again for ``silu'``, from the operand it reads
anyway); memory bounds it.
"""
from benchmark.reduce import flops_lm

visible_pairs = flops_lm.visible_pairs
CHUNK = 64


def head_dim(cfg):
    return int(cfg.get('head_dim') or int(cfg['hidden_size'])
               // int(cfg['num_attention_heads']))


def layers_of(cfg, kind):
    return sum(1 for k in cfg['layer_types'] if k == kind)


def _linear_dims(cfg):
    return (int(cfg['linear_num_value_heads']),
            int(cfg['linear_key_head_dim']),
            int(cfg['linear_value_head_dim']))


def delta_rule_flops_per_token(cfg, chunk=CHUNK):
    """Operations of one layer's scan per token, forward."""
    H, dk, dv = _linear_dims(cfg)
    return H * (2.0 * chunk * (3 * dk + 2 * dv) + chunk ** 2
                + 6.0 * dk * dv)


def forward_flops_per_token(cfg, seq_len):
    """{part: operations per token, forward}: 'linear_projections',
    'delta_rule', 'attention_projections', 'attention_full', 'mlp',
    'head'."""
    d, D = int(cfg['hidden_size']), head_dim(cfg)
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    LH, dk, dv = _linear_dims(cfg)
    lin = layers_of(cfg, 'linear_attention')
    attn = layers_of(cfg, 'full_attention')
    return {
        'linear_projections': lin * 2.0 * d * (2 * LH * dk + 3 * LH * dv
                                               + 2 * LH),
        'delta_rule': lin * delta_rule_flops_per_token(cfg),
        'attention_projections': attn * 2.0 * (2 * d * H * D
                                               + 2 * d * KV * D),
        'attention_full': attn * 4.0 * D * H
        * visible_pairs(seq_len, 0) / seq_len,
        'mlp': int(cfg['num_hidden_layers']) * 6.0 * d
        * int(cfg['intermediate_size']),
        'head': 2.0 * d * int(cfg['vocab_size'])}


def required_flops(cfg, seq_len):
    """Operations per token: {'forward', 'train', 'parts'}."""
    parts = forward_flops_per_token(cfg, seq_len)
    fwd = sum(parts.values())
    return {'forward': fwd, 'train': 3 * fwd, 'parts': parts}


def delta_rule_work(cfg, seq_len, batch, itemsize=2):
    """(operations, bytes) that the scans of every linear-attention layer
    need for one trained step."""
    H, dk, dv = _linear_dims(cfg)
    rows = batch * seq_len
    layers = layers_of(cfg, 'linear_attention')
    forward = rows * H * (2 * (dk + dv) * itemsize + 2 * 4)
    return (layers * 3.0 * rows * delta_rule_flops_per_token(cfg),
            layers * 3.0 * forward)


def delta_rule_least_seconds(cfg, seq_len, batch, peak):
    """The least time of the scans of one trained step: the products over
    the bf16 peak or the bytes over the memory bandwidth, whichever is
    larger. `peak`: ``peaks.peaks_of``'s."""
    flops, bytes_ = delta_rule_work(cfg, seq_len, batch)
    return max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])


def conv_bytes(cfg, seq_len, batch, itemsize=2):
    """Bytes the convolutions of every linear-attention layer must move
    for one trained step."""
    H, dk, dv = _linear_dims(cfg)
    v = batch * seq_len * H * (2 * dk + dv) * itemsize
    return layers_of(cfg, 'linear_attention') * (2 + 3) * v


def attention_work(cfg, seq_len, batch):
    """(operations, bytes) that the attention kernels of every attention
    layer need for one trained step: ``flops_lm.attention_work``'s count
    (forward two products, backward five, over the visible pairs; q, k, v,
    the output, its cotangent and the three gradients each moved once, in
    bfloat16), here with as many key/value heads as query heads."""
    D, H = head_dim(cfg), int(cfg['num_attention_heads'])
    KV = int(cfg['num_key_value_heads'])
    layers = layers_of(cfg, 'full_attention')
    pairs = visible_pairs(seq_len, 0) * batch
    rows = batch * seq_len
    return (layers * (2 + 5) * 2.0 * D * H * pairs,
            layers * 2.0 * rows * D * ((2 * H + 2 * KV) + (5 * H + 4 * KV)))
