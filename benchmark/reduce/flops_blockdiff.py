"""Operations and bytes a decoder of the ``sdar_moe`` family *requires*
when it is trained as a block-diffusion model, counted from the
configuration's shapes as ``flops_lm.py`` counts Laguna's: 2 operations per
multiply-add, a trained sample 3 times the forward count, no recomputation,
no elementwise work.

A sample is a CLEAN token. A step of ``L`` clean tokens runs ``2 L`` rows
(the noisy and the clean copy) through the layers and ``L`` through the
head, so per clean token and forward pass: twice a layer's four projections
(q and the output of ``H D``, k and v of ``KV D``), the router and the
routed experts held (at the pairs that land on them: expected
``num_experts_per_tok * experts_held / num_experts`` a ROW, or a counted
number), the attention's two products over the pairs the mask leaves, and
the head once.

The mask's true query-key pairs a head (``mask_pairs``): with ``n = L /
B`` blocks of ``B`` positions a noisy row of block b sees its own ``B``
noisy keys and ``b B`` clean ones, a clean row ``(b + 1) B`` clean ones:
``B^2 n (n + 1)`` (16.79 M at L 4096, B 4), not the pairs of the kernel
blocks a walk visits (20.97 M in 80 blocks of 512 x 512).

``attention_work``: ``flops_lm``'s rule (forward two products, backward
five, over the true pairs; q, k, v, the output, its cotangent and the
three gradients each moved once, in bfloat16, over the ``2 L`` rows).
``expert_least_seconds`` is ``flops_hybrid``'s.
"""
from benchmark.reduce import flops_lm

expert_work = flops_lm.expert_work
expert_weight_bytes = flops_lm.expert_weight_bytes


def mask_pairs(seq_len, block_length):
    """Query-key pairs a head that the block-diffusion mask leaves over
    [noisy ; clean] of `seq_len` clean positions."""
    n = int(seq_len) // int(block_length)
    return int(block_length) ** 2 * n * (n + 1)


def forward_flops_per_token(cfg, seq_len, pairs_per_row=None):
    """{part: operations per clean token, forward}: 'projections',
    'attention_blockdiff', 'router', 'experts', 'head'."""
    d, D = int(cfg['hidden_size']), int(cfg['head_dim'])
    H, KV = int(cfg['num_attention_heads']), int(cfg['num_key_value_heads'])
    layers, experts = int(cfg['num_hidden_layers']), int(cfg['num_experts'])
    if pairs_per_row is None:
        pairs_per_row = (int(cfg['num_experts_per_tok'])
                         * int(cfg.get('experts_held', experts))
                         / float(experts))
    return {
        'projections': 2 * layers * 2.0 * (2 * d * H * D + 2 * d * KV * D),
        'attention_blockdiff': layers * 4.0 * D * H
        * mask_pairs(seq_len, cfg['block_length']) / seq_len,
        'router': 2 * layers * 2.0 * d * experts,
        'experts': 2 * layers * pairs_per_row * 6.0 * d
        * int(cfg['moe_intermediate_size']),
        'head': 2.0 * d * int(cfg['vocab_size'])}


def required_flops(cfg, seq_len, pairs_per_row=None):
    """Operations per clean token: {'forward', 'train', 'parts'}."""
    parts = forward_flops_per_token(cfg, seq_len, pairs_per_row)
    fwd = sum(parts.values())
    return {'forward': fwd, 'train': 3 * fwd, 'parts': parts}


def attention_work(cfg, seq_len, batch):
    """(operations, bytes) that the attention kernels of every layer need
    for one trained step of `batch` sequences of `seq_len` clean tokens."""
    D, H = int(cfg['head_dim']), int(cfg['num_attention_heads'])
    KV = int(cfg['num_key_value_heads'])
    layers = int(cfg['num_hidden_layers'])
    pairs = mask_pairs(seq_len, cfg['block_length']) * batch
    rows = batch * 2 * seq_len
    return (layers * (2 + 5) * 2.0 * D * H * pairs,
            layers * 2.0 * rows * D * ((2 * H + 2 * KV) + (5 * H + 4 * KV)))


def expert_least_seconds(cfg, pairs, steps, peak):
    """``flops_hybrid.expert_least_seconds`` with every layer sparse."""
    flops, bytes_ = expert_work(cfg, pairs)
    bytes_ += 3 * expert_weight_bytes(cfg) \
        * int(cfg['num_hidden_layers']) * steps
    return max(flops / peak['bf16_flops'], bytes_ / peak['hbm_bytes_s'])
