"""``reduce/flops_hybrid.py`` against counts by hand."""
import json
import os

from benchmark.reduce import flops_hybrid

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = {'bf16_flops': 197e12, 'hbm_bytes_s': 819e9}


def _config():
    with open(os.path.join(os.path.dirname(HERE), 'configs',
                           'lfm2_24b_a2b.json')) as f:
        return json.load(f)


def test_required_flops_of_the_cut_model():
    """The published widths at 8192 tokens, part by part, operations a
    token, forward: four conv layers and one of attention, one dense MLP
    and four expert layers."""
    cfg = _config()
    parts = flops_hybrid.forward_flops_per_token(cfg, 8192)
    assert parts['conv_projections'] == 4 * 2 * (2048 * 6144 + 2048 * 2048)
    assert parts['attention_projections'] \
        == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    # a query sees (8192 + 1) / 2 keys on average, 64 wide in and out
    assert parts['attention_full'] == 4 * 64 * 32 * 8193 / 2
    assert parts['dense_mlp'] == 6 * 2048 * 11776
    assert parts['router'] == 4 * 2 * 2048 * 64
    # top 4 of 64 with 16 held: one pair a token and expert layer
    assert parts['experts'] == 4 * 1.0 * 6 * 2048 * 1536
    assert parts['head'] == 2 * 2048 * 16384
    need = flops_hybrid.required_flops(cfg, 8192)
    assert abs(need['forward'] / 1e6 - 477.1) < 0.1
    assert need['train'] == 3 * need['forward']
    # a counted number of pairs takes the expected one's place
    more = flops_hybrid.required_flops(cfg, 8192, pairs_per_token=2.0)
    assert more['parts']['experts'] == 2 * parts['experts']


def test_shares_of_the_required_operations():
    """What ISSUE 42 says of the cut: the conv operators' projections
    28%, the dense MLP 30%, the experts 16%, the head 14%, attention 7%
    and its projections 4%."""
    need = flops_hybrid.required_flops(_config(), 8192)
    share = {k: round(100 * v / need['forward'])
             for k, v in need['parts'].items()}
    assert share == {'conv_projections': 28, 'dense_mlp': 30, 'experts': 16,
                     'head': 14, 'attention_full': 7,
                     'attention_projections': 4, 'router': 0}


def test_conv_bytes_by_hand():
    cfg = _config()
    v = 8192 * 2048 * 2
    assert flops_hybrid.conv_bytes(cfg, 8192, 1) == 4 * 11 * v
    assert abs(flops_hybrid.conv_bytes(cfg, 8192, 1) / 1e9 - 1.476) < 1e-3
    # 1.8 ms a step at the chip's 819 GB/s
    assert abs(flops_hybrid.conv_bytes(cfg, 8192, 1) / 819e9 * 1e3 - 1.8) \
        < 0.01
    assert flops_hybrid.conv_bytes(cfg, 8192, 2) == 8 * 11 * v


def test_attention_work_by_hand():
    cfg = _config()
    flops, bytes_ = flops_hybrid.attention_work(cfg, 8192, 1)
    pairs = 8192 * 8193 // 2
    assert flops == 7 * 2 * 64 * 32 * pairs
    # q and the output 2048 wide, k and v 512: forward q k v o, backward
    # q k v o do in and dq dk dv out
    assert bytes_ == 2 * 8192 * ((2 * 2048 + 2 * 512)
                                 + (5 * 2048 + 4 * 512))
    # the products bound it: 4.9 ms against 0.35
    assert abs(flops / 197e12 * 1e3 - 4.88) < 0.01
    assert bytes_ / 819e9 < 0.1 * flops / 197e12
    assert flops_hybrid.head_dim(cfg) == 64
    assert flops_hybrid.head_dim(dict(cfg, head_dim=128)) == 128


def test_expert_least_time_by_hand():
    """8192 x 4 pairs over 64 experts, 16 held: 8192 pairs a layer and
    step, 512 a held expert, where the products take longer than the
    weights' bytes; at a tenth of the rows the bytes do."""
    cfg = _config()
    pairs = 4 * 8192
    flops = pairs * 9 * 2 * 2048 * 1536
    rows = pairs * 2 * 3 * (3 * 2048 + 3 * 1536)
    weights = 3 * 4 * 16 * 3 * 2048 * 1536 * 2
    assert flops_hybrid.expert_work(cfg, pairs) == (flops, rows)
    assert flops / 197e12 > (rows + weights) / 819e9
    assert flops_hybrid.expert_least_seconds(cfg, pairs, 1, V5E) \
        == flops / 197e12
    few = pairs // 10
    assert flops_hybrid.expert_least_seconds(cfg, few, 1, V5E) \
        == (few * 2 * 3 * (3 * 2048 + 3 * 1536) + weights) / 819e9
    assert flops_hybrid.sparse_layers(cfg) == 4


def test_small_config_by_hand():
    cfg = dict(hidden_size=8, num_hidden_layers=3, num_dense_layers=1,
               layer_types=['conv', 'full_attention', 'conv'],
               num_attention_heads=4, num_key_value_heads=2, num_experts=4,
               num_experts_per_tok=2, experts_held=2, intermediate_size=6,
               moe_intermediate_size=5, vocab_size=10, conv_L_cache=3)
    parts = flops_hybrid.forward_flops_per_token(cfg, 4)
    assert parts['conv_projections'] == 2 * 2 * (8 * 24 + 8 * 8)
    assert parts['attention_projections'] == 2 * (2 * 8 * 8 + 2 * 8 * 4)
    assert parts['attention_full'] == 4 * 2 * 4 * 2.5
    assert parts['dense_mlp'] == 6 * 8 * 6
    assert parts['router'] == 2 * 2 * 8 * 4
    assert parts['experts'] == 2 * 1.0 * 6 * 8 * 5
    assert parts['head'] == 2 * 8 * 10
    assert flops_hybrid.conv_bytes(cfg, 4, 1) == 2 * 11 * 4 * 8 * 2
    assert flops_hybrid.layers_of(cfg, 'conv') == 2
