"""``reduce/flops_latent.py`` against numbers worked out by hand from
kanana-2-30b-a3b's published widths and from a small configuration. The
repo's tier-1 run takes the same cases through
``tests/unittest/test_latent_ops.py``."""
import json
import os

from benchmark.reduce import flops_latent, flops_lm

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = json.load(open(os.path.join(HERE, '..', 'configs',
                                  'kanana_2_30b_a3b.json')))


def test_required_flops_of_the_cut_model():
    need = flops_latent.required_flops(CFG, 8192)
    parts = need['parts']
    # a layer's projections: q 2048 x 6144, latent and rotary key 2048 x
    # 576, the expansion 512 x 8192, the output 4096 x 2048
    layer = 2 * (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert layer == 2 * 26345472
    assert parts['projections'] == 5 * layer
    # a query sees 4096.5 keys on average; scores 192 wide, values 128
    assert parts['attention_latent'] == 5 * 2 * 32 * 320 * 4096.5
    assert parts['dense_mlp'] == 6 * 2048 * 6144
    assert parts['shared'] == 4 * 6 * 2048 * 1536
    assert parts['router'] == 4 * 2 * 2048 * 128
    # 6 of 128 experts a token, 16 held: 0.75 pairs a token and layer
    assert parts['experts'] == 4 * 0.75 * 6 * 2048 * 768
    assert parts['head'] == 2 * 2048 * 16032
    assert abs(need['forward'] - 930.1e6) < 0.1e6
    assert need['train'] == 3 * need['forward']
    assert abs(parts['attention_latent'] / need['forward'] - 0.451) < 1e-3
    counted = flops_latent.required_flops(CFG, 8192, pairs_per_token=1.0)
    assert counted['parts']['experts'] == 4 * 6 * 2048 * 768


def test_attention_work_by_hand():
    flops, bytes_ = flops_latent.attention_work(CFG, 8192, 1)
    pairs = 8192 * 8193 // 2
    forward = 2 * 32 * pairs * (192 + 128)
    backward = 2 * 32 * pairs * (192 + 128 + 128 + 192 + 192)
    assert flops == 5 * (forward + backward)
    assert abs(forward / 1e12 - 0.687) < 1e-3
    assert abs(backward / 1e12 - 1.787) < 1e-3
    # q_nope, q_rope, k_nope, v per head and one shared rotary key
    operands = 32 * (128 + 64 + 128 + 128) + 64
    assert bytes_ == 5 * 2 * 8192 * (3 * operands + 2 * 32 * 128)
    # compute bounds it: 12.6 ms a layer at the peak against 0.7 ms
    assert flops / 197e12 > 10 * bytes_ / 819e9


def test_expert_work_is_shared():
    assert flops_latent.expert_work is flops_lm.expert_work
    flops, bytes_ = flops_latent.expert_work(CFG, 1000)
    assert flops == 1000 * 9 * 2 * 2048 * 768
    assert flops_latent.expert_weight_bytes(CFG) == 16 * 3 * 2048 * 768 * 2
    assert flops_latent.sparse_layers(CFG) == 4


def test_small_config_by_hand():
    cfg = dict(hidden_size=8, num_attention_heads=2, qk_nope_head_dim=4,
               qk_rope_head_dim=2, v_head_dim=4, kv_lora_rank=3,
               num_hidden_layers=3, first_k_dense_replace=2,
               moe_layer_freq=1, intermediate_size=16,
               moe_intermediate_size=5, n_shared_experts=2,
               n_routed_experts=4, num_experts_per_tok=2, vocab_size=10)
    parts = flops_latent.forward_flops_per_token(cfg, 4)
    assert parts['projections'] == 3 * 2 * (8 * 12 + 8 * 5 + 3 * 16 + 8 * 8)
    assert parts['attention_latent'] == 3 * 2 * 2 * 10 * 10 / 4
    assert parts['dense_mlp'] == 2 * 6 * 8 * 16
    assert parts['router'] == 2 * 8 * 4 and parts['shared'] == 6 * 8 * 10
    assert parts['experts'] == 2 * 6 * 8 * 5      # all four held
    assert parts['head'] == 2 * 8 * 10
    flops, _ = flops_latent.attention_work(cfg, 4, 3)
    assert flops == 3 * 2 * 2 * 30 * (6 + 4 + 6 + 4 + 4 + 12)
