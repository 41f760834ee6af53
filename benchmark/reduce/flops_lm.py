"""Operations and bytes a decoder configuration *requires*, counted from
the configuration's shapes (never from the program or from XLA's cost
analysis, which counts recomputation). 2 operations per multiply-add.

Per token and forward pass: the projections of attention (q, k, v, gate,
output), attention's two products over the keys a query sees (causal: the
positions up to its own; windowed: at most ``sliding_window`` of them), the
dense MLP or, on a sparse layer, the router, the shared expert and the
routed experts held here at the pairs that land on them (expected:
``num_experts_per_tok * experts_held / num_experts`` a token; or a counted
number), the head. A trained token needs the forward pass, the gradient of
every weight and the gradient of every input: 3 times the forward count.
Recomputation (the program mirrors its blocks) is not required work and is
not counted. Elementwise, norm, rotary and softmax work is not counted.

The kernels' own counts (``attention_work``, ``expert_work``) follow the
same rule with the backward pass of blockwise attention taken as the five
products it cannot avoid (scores again, dP, dV, dK, dQ) against the
forward pass's two.
"""


def heads_of(cfg, i):
    per = cfg.get('num_attention_heads_per_layer')
    return int(per[i]) if per else int(cfg['num_attention_heads'])


def visible_pairs(seq_len, window):
    """Query-key pairs of one causal sequence: position t sees
    min(t + 1, window) keys (all t + 1 with no window)."""
    T, w = int(seq_len), int(window)
    if not w or w >= T:
        return T * (T + 1) // 2
    return w * (w + 1) // 2 + (T - w) * w


def layer_kinds(cfg):
    n = int(cfg['num_hidden_layers'])
    return [(cfg['layer_types'][i], cfg['mlp_layer_types'][i],
             heads_of(cfg, i)) for i in range(n)]


def window_of(cfg, kind):
    return int(cfg['sliding_window']) if kind == 'sliding_attention' else 0


def forward_flops_per_token(cfg, seq_len, pairs_per_token=None):
    """{part: operations per token, forward}: 'projections',
    'attention_full', 'attention_window', 'dense_mlp', 'router', 'shared',
    'experts', 'head'."""
    d, D = int(cfg['hidden_size']), int(cfg['head_dim'])
    KV = int(cfg['num_key_value_heads'])
    if pairs_per_token is None:
        pairs_per_token = (int(cfg['num_experts_per_tok'])
                           * int(cfg['experts_held'])
                           / float(cfg['num_experts']))
    out = dict.fromkeys(('projections', 'attention_full', 'attention_window',
                         'dense_mlp', 'router', 'shared', 'experts', 'head'),
                        0.0)
    for kind, mlp, H in layer_kinds(cfg):
        out['projections'] += 2 * d * (H * D + 2 * KV * D + H) \
            + 2 * H * D * d
        window = window_of(cfg, kind)
        part = 'attention_window' if window else 'attention_full'
        out[part] += 4.0 * D * H * visible_pairs(seq_len, window) / seq_len
        if mlp == 'dense':
            out['dense_mlp'] += 6 * d * int(cfg['intermediate_size'])
        else:
            out['router'] += 2 * d * int(cfg['num_experts'])
            out['shared'] += 6 * d * int(
                cfg['shared_expert_intermediate_size'])
            out['experts'] += pairs_per_token * 6 * d * int(
                cfg['moe_intermediate_size'])
    out['head'] = 2 * d * int(cfg['vocab_size'])
    return out


def required_flops(cfg, seq_len, pairs_per_token=None):
    """Operations per token: {'forward', 'train', 'parts'}."""
    parts = forward_flops_per_token(cfg, seq_len, pairs_per_token)
    fwd = sum(parts.values())
    return {'forward': fwd, 'train': 3 * fwd, 'parts': parts}


def attention_work(cfg, seq_len, batch, windowed):
    """(operations, bytes) that the attention kernels of the layers of one
    kind need for one trained step: forward two products, backward five,
    over the visible pairs; bytes are q, k, v, the output, its cotangent
    and the three gradients, each moved once, in bfloat16."""
    D, KV = int(cfg['head_dim']), int(cfg['num_key_value_heads'])
    flops = bytes_ = 0.0
    for kind, _, H in layer_kinds(cfg):
        window = window_of(cfg, kind)
        if bool(window) != bool(windowed):
            continue
        pairs = visible_pairs(seq_len, window) * batch
        flops += (2 + 5) * 2.0 * D * H * pairs
        rows = batch * seq_len
        # forward: q, k, v in, o out; backward: q, k, v, o, do in,
        # dq, dk, dv out
        bytes_ += 2.0 * rows * D * ((2 * H + 2 * KV) + (5 * H + 4 * KV))
    return flops, bytes_


def expert_work(cfg, pairs):
    """(operations, bytes) of the grouped expert products for `pairs`
    token-expert pairs summed over the sparse layers and steps of a slice
    in which every held expert's weights are read `reads` times: forward
    three products, backward six (each product's two gradients). Bytes:
    a pair's rows in and out of each product; the weights' reading is
    added by the caller (``expert_weight_bytes``) per layer and step."""
    d, h = int(cfg['hidden_size']), int(cfg['moe_intermediate_size'])
    flops = pairs * 3 * 3 * 2.0 * d * h
    # per pair, bfloat16: forward x (d) twice in, h1 h3 out, act in, y (d)
    # out; backward about twice that
    bytes_ = pairs * 2.0 * 3 * (3 * d + 3 * h)
    return flops, bytes_


def expert_weight_bytes(cfg):
    """Bytes of the held experts' weights of one sparse layer, read once
    (bfloat16): a trained step reads them three times at least (forward,
    the inputs' gradient; the weights' gradient writes as much in
    float32)."""
    d, h = int(cfg['hidden_size']), int(cfg['moe_intermediate_size'])
    return int(cfg['experts_held']) * 3 * d * h * 2.0


def sparse_layers(cfg):
    return sum(1 for _, mlp, _ in layer_kinds(cfg) if mlp == 'sparse')
