"""Operations and bytes a decoder of the ``deepseek_v3`` family *requires*
(latent attention in its expanded form, shared experts, routed experts
held here), counted from the configuration's shapes as ``flops_lm.py``
counts Laguna's: 2 operations per multiply-add, a trained token 3 times
the forward count, no recomputation, no elementwise work.

Per token and forward pass: attention's projections (``q`` of
``H (Dn + Dr)``, the latent and rotary key of ``r + Dr``, the expansion of
the latent to ``H (Dn + Dv)``, the output of ``H Dv``), its two products
over the keys a query sees (scores ``Dn + Dr`` wide, values ``Dv``), the
dense MLP or, on a sparse layer, the router, the shared experts (one MLP of
``n_shared_experts`` widths) and the routed experts held at the pairs that
land on them (expected ``num_experts_per_tok * experts_held /
n_routed_experts`` a token, or a counted number), the head.

The kernels' own counts: ``attention_work`` takes the backward pass as the
five products it cannot avoid (scores again, dP, dV, dK, dQ) against the
forward pass's two, each at its own width; ``expert_work`` is
``flops_lm``'s, whose keys (``hidden_size``, ``moe_intermediate_size``,
``experts_held``) this family shares.
"""
from benchmark.reduce import flops_lm

expert_work = flops_lm.expert_work
expert_weight_bytes = flops_lm.expert_weight_bytes
visible_pairs = flops_lm.visible_pairs


def dims(cfg):
    """(H, Dn, Dr, Dv, r)."""
    return (int(cfg['num_attention_heads']), int(cfg['qk_nope_head_dim']),
            int(cfg['qk_rope_head_dim']), int(cfg['v_head_dim']),
            int(cfg['kv_lora_rank']))


def is_sparse(cfg, i):
    return i >= int(cfg['first_k_dense_replace']) \
        and i % int(cfg.get('moe_layer_freq', 1)) == 0


def sparse_layers(cfg):
    return sum(1 for i in range(int(cfg['num_hidden_layers']))
               if is_sparse(cfg, i))


def forward_flops_per_token(cfg, seq_len, pairs_per_token=None):
    """{part: operations per token, forward}: 'projections',
    'attention_latent', 'dense_mlp', 'router', 'shared', 'experts',
    'head'."""
    d = int(cfg['hidden_size'])
    H, Dn, Dr, Dv, r = dims(cfg)
    experts = int(cfg['n_routed_experts'])
    narrow = int(cfg['moe_intermediate_size'])
    if pairs_per_token is None:
        pairs_per_token = (int(cfg['num_experts_per_tok'])
                           * int(cfg.get('experts_held', experts))
                           / float(experts))
    layers = int(cfg['num_hidden_layers'])
    sparse = sparse_layers(cfg)
    return {
        'projections': layers * 2.0 * (d * H * (Dn + Dr) + d * (r + Dr)
                                       + r * H * (Dn + Dv) + H * Dv * d),
        'attention_latent': layers * 2.0 * H * (Dn + Dr + Dv)
        * visible_pairs(seq_len, 0) / seq_len,
        'dense_mlp': (layers - sparse) * 6.0 * d
        * int(cfg['intermediate_size']),
        'router': sparse * 2.0 * d * experts,
        'shared': sparse * 6.0 * d * narrow * int(cfg['n_shared_experts']),
        'experts': sparse * pairs_per_token * 6.0 * d * narrow,
        'head': 2.0 * d * int(cfg['vocab_size'])}


def required_flops(cfg, seq_len, pairs_per_token=None):
    """Operations per token: {'forward', 'train', 'parts'}."""
    parts = forward_flops_per_token(cfg, seq_len, pairs_per_token)
    fwd = sum(parts.values())
    return {'forward': fwd, 'train': 3 * fwd, 'parts': parts}


def attention_work(cfg, seq_len, batch):
    """(operations, bytes) that the latent attention kernels of every layer
    need for one trained step. Operations over the visible pairs of every
    head: forward the scores (``Dn + Dr`` wide) and the values (``Dv``);
    backward the scores again, dP (``Dv``), dV (``Dv``), dK and dQ (each
    ``Dn + Dr``). Bytes, bfloat16, each array moved once: forward q_nope,
    q_rope, k_nope, v and the one k_rope in, the output out; backward
    those five and the output's cotangent in (the output itself enters the
    softmax's row term outside the kernels and is not counted), the five
    gradients out."""
    H, Dn, Dr, Dv, _ = dims(cfg)
    pairs = visible_pairs(seq_len, 0) * batch
    layers = int(cfg['num_hidden_layers'])
    flops = layers * 2.0 * H * pairs * (
        (Dn + Dr) + Dv                              # forward
        + (Dn + Dr) + Dv + Dv + 2 * (Dn + Dr))      # backward
    rows = batch * seq_len
    operands = H * (2 * Dn + Dr + Dv) + Dr
    bytes_ = layers * 2.0 * rows * (
        (operands + H * Dv) + (operands + H * Dv) + operands)
    return flops, bytes_
