"""``reduce/flops_hyper.py`` against counts by hand."""
import json
import os

from benchmark.reduce import flops_hyper, flops_latent

HERE = os.path.dirname(os.path.abspath(__file__))


def _config():
    with open(os.path.join(os.path.dirname(HERE), 'configs',
                           'xing4_0_29b_a4b.json')) as f:
        return json.load(f)


def test_required_flops_of_the_cut_model():
    """The published widths at 4096 tokens, part by part, in millions of
    operations a token, forward."""
    cfg = _config()
    parts = flops_hyper.forward_flops_per_token(cfg, 4096)
    # six blocks (five layers and the module's): queries through a latent
    # of 768, keys and values through one of 512 + 64, the output
    attention = 2 * (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                     + 512 * 32 * 256 + 32 * 128 * 3584)
    assert parts['projections'] == 6 * attention
    # a query sees (4096 + 1) / 2 keys on average, 320 wide in and out
    assert parts['attention_latent'] == 6 * 2 * 32 * 320 * 4097 / 2
    assert parts['dense_mlp'] == 6 * 3584 * 9216
    assert parts['router'] == 5 * 2 * 3584 * 64
    assert parts['shared'] == 5 * 6 * 3584 * 1024
    # top 4 of 64 with 8 held: half a pair a token and expert layer
    assert parts['experts'] == 5 * 0.5 * 6 * 3584 * 1024
    # 12 sublayers project 14336 onto 24, two collapses onto 4
    assert parts['mixing'] == 2 * 14336 * (12 * 24 + 2 * 4)
    assert parts['mtp_join'] == 2 * 7168 * 3584
    assert parts['head'] == 2 * 2 * 3584 * 16384
    need = flops_hyper.required_flops(cfg, 4096)
    assert abs(need['forward'] / 1e9 - 1.253) < 1e-3
    assert need['train'] == 3 * need['forward']
    # the module's block is counted like a layer's
    flops, bytes_ = flops_hyper.attention_work(cfg, 4096, 1)
    base = flops_latent.attention_work(cfg, 4096, 1)
    assert flops == base[0] * 6 / 5 and bytes_ == base[1] * 6 / 5


def test_mixing_bytes_by_hand():
    cfg = _config()
    X, v, coef = 4096 * 14336 * 2, 4096 * 3584 * 2, 4096 * 128
    sublayer = 9 * X + 5 * v + 7 * coef
    collapse = 3 * X + 2 * v + 4 * coef
    assert flops_hyper.mixing_bytes(cfg, 4096, 1) \
        == 12 * sublayer + 2 * collapse
    assert abs(flops_hyper.mixing_bytes(cfg, 4096, 1) / 1e9 - 15.3) < 0.05


def test_small_config_by_hand():
    cfg = dict(hidden_size=8, hc_mult=2, num_hidden_layers=1,
               num_nextn_predict_layers=0, first_k_dense_replace=1,
               num_attention_heads=2, qk_nope_head_dim=4, qk_rope_head_dim=2,
               v_head_dim=4, kv_lora_rank=3, q_lora_rank=None,
               n_routed_experts=4, num_experts_per_tok=1,
               moe_intermediate_size=5, n_shared_experts=1,
               intermediate_size=6, vocab_size=10)
    parts = flops_hyper.forward_flops_per_token(cfg, 4)
    assert parts['projections'] == 2 * (8 * 12 + 8 * 5 + 3 * 16 + 8 * 8)
    assert parts['attention_latent'] == 2 * 2 * 10 * 2.5
    assert parts['dense_mlp'] == 6 * 8 * 6
    assert parts['router'] == parts['shared'] == parts['experts'] == 0
    # two sublayers onto 2 * 2 + 4 coefficients, one collapse onto 2
    assert parts['mixing'] == 2 * 16 * (2 * 8 + 2)
    assert parts['mtp_join'] == 0 and parts['head'] == 2 * 8 * 10
    assert flops_hyper.blocks(cfg) == 1
    assert flops_hyper.sparse_layers(cfg) == 0
