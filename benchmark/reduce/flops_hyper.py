"""Operations and bytes a decoder of the ``xing4_0`` family *requires*:
``flops_latent.py``'s count for the ``deepseek_v3`` sublayers (2 operations
per multiply-add, a trained token 3 times the forward count, no
recomputation, no elementwise work), with a low-rank query path, the
multi-token-prediction module as one more sparse block with its own head
and join, and the mixing maps of the residual streams.

Per token and forward pass, beside ``flops_latent``'s parts: the queries
come from a latent (``d q_lora_rank + q_lora_rank H (Dn + Dr)`` for ``d H
(Dn + Dr)``); every sublayer of every block projects the whole row of
``n d`` onto ``2n + n^2`` coefficients, and each collapse onto ``n``
('mixing'; the sums over streams are elementwise and, like every
elementwise work, not counted as operations: they are counted as bytes);
the prediction module joins two states (``2 d x d``) and has a head of its
own.

``mixing_bytes``: what the ``hyper_*`` kernels have to move for one trained
step, each array once in the activations' 2 bytes, with X a step's
[rows, n d] and v its [rows, d]: a sublayer's forward reads X and writes y
(pre), reads X and z and writes X' (post): 3 X + 2 v; its backward reads
X', X, z and writes dX and dz (post), reads X, dy and the other cotangent
of X and writes dX (pre): 6 X + 3 v; a collapse reads X and writes h, then
reads X and dh and writes dX: 3 X + 2 v. The coefficients (32 float32 a
token and kernel) are counted too.
"""
from benchmark.reduce import flops_latent

expert_work = flops_latent.expert_work
expert_weight_bytes = flops_latent.expert_weight_bytes
visible_pairs = flops_latent.visible_pairs
dims = flops_latent.dims
COEF_BYTES = 32 * 4     # a token's coefficient row, float32


def modules(cfg):
    return int(cfg.get('num_nextn_predict_layers', 0))


def blocks(cfg):
    """Decoder blocks computed: the layers and the prediction module's."""
    return int(cfg['num_hidden_layers']) + modules(cfg)


def sparse_layers(cfg):
    """Expert layers computed: the module's block is one."""
    return flops_latent.sparse_layers(cfg) + modules(cfg)


def coefficients(cfg):
    n = int(cfg['hc_mult'])
    return 2 * n + n * n


def forward_flops_per_token(cfg, seq_len, pairs_per_token=None):
    """{part: operations per token, forward}."""
    d, n = int(cfg['hidden_size']), int(cfg['hc_mult'])
    H, Dn, Dr, Dv, r = dims(cfg)
    experts = int(cfg['n_routed_experts'])
    narrow = int(cfg['moe_intermediate_size'])
    if pairs_per_token is None:
        pairs_per_token = (int(cfg['num_experts_per_tok'])
                           * int(cfg.get('experts_held', experts))
                           / float(experts))
    rq = cfg.get('q_lora_rank')
    queries = d * H * (Dn + Dr) if rq is None \
        else d * int(rq) + int(rq) * H * (Dn + Dr)
    B, sparse = blocks(cfg), sparse_layers(cfg)
    return {
        'projections': B * 2.0 * (queries + d * (r + Dr)
                                  + r * H * (Dn + Dv) + H * Dv * d),
        'attention_latent': B * 2.0 * H * (Dn + Dr + Dv)
        * visible_pairs(seq_len, 0) / seq_len,
        'dense_mlp': (B - sparse) * 6.0 * d * int(cfg['intermediate_size']),
        'router': sparse * 2.0 * d * experts,
        'shared': sparse * 6.0 * d * narrow * int(cfg['n_shared_experts']),
        'experts': sparse * pairs_per_token * 6.0 * d * narrow,
        'mixing': 2.0 * n * d * (2 * B * coefficients(cfg)
                                 + (1 + modules(cfg)) * n),
        'mtp_join': modules(cfg) * 2.0 * 2 * d * d,
        'head': (1 + modules(cfg)) * 2.0 * d * int(cfg['vocab_size'])}


def required_flops(cfg, seq_len, pairs_per_token=None):
    """Operations per token: {'forward', 'train', 'parts'}."""
    parts = forward_flops_per_token(cfg, seq_len, pairs_per_token)
    fwd = sum(parts.values())
    return {'forward': fwd, 'train': 3 * fwd, 'parts': parts}


def attention_work(cfg, seq_len, batch):
    """``flops_latent.attention_work`` over every block computed."""
    flops, bytes_ = flops_latent.attention_work(cfg, seq_len, batch)
    scale = blocks(cfg) / float(cfg['num_hidden_layers'])
    return flops * scale, bytes_ * scale


def mixing_bytes(cfg, seq_len, batch, itemsize=2):
    """Bytes the ``hyper_*`` kernels must move for one trained step."""
    rows = batch * seq_len
    d, n = int(cfg['hidden_size']), int(cfg['hc_mult'])
    X, v = rows * n * d * itemsize, rows * d * itemsize
    coef = rows * COEF_BYTES
    sublayer = (3 * X + 2 * v + 2 * coef) + (6 * X + 3 * v + 5 * coef)
    collapse = 3 * X + 2 * v + 4 * coef
    return 2 * blocks(cfg) * sublayer + (1 + modules(cfg)) * collapse
