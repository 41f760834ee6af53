"""``reduce/flops_linear.py`` against counts by hand."""
import json
import os

from benchmark.reduce import flops_linear

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = {'bf16_flops': 197e12, 'hbm_bytes_s': 819e9}


def _config():
    with open(os.path.join(os.path.dirname(HERE), 'configs',
                           'olmo_hybrid_7b.json')) as f:
        return json.load(f)


def test_required_flops_of_the_cut_model():
    """The published widths at 4096 tokens, part by part, operations a
    token, forward: three linear-attention layers and one of attention, an
    MLP in each."""
    cfg = _config()
    parts = flops_linear.forward_flops_per_token(cfg, 4096)
    # q and k of 2880, v, the gate and the output of 5760, a and b of 30
    assert parts['linear_projections'] \
        == 3 * 2 * 3840 * (2 * 2880 + 3 * 5760 + 2 * 30)
    assert parts['delta_rule'] == 3 * 6021120
    assert parts['attention_projections'] == 2 * 4 * 3840 * 3840
    # a query sees (4096 + 1) / 2 keys on average, 128 wide in and out
    assert parts['attention_full'] == 4 * 128 * 30 * 4097 / 2
    assert parts['mlp'] == 4 * 6 * 3840 * 11008
    assert parts['head'] == 2 * 3840 * 12544
    need = flops_linear.required_flops(cfg, 4096)
    assert abs(need['forward'] / 1e9 - 1.8106) < 1e-4
    assert need['train'] == 3 * need['forward']
    assert abs(need['train'] / 1e9 - 5.4317) < 1e-4


def test_shares_of_the_required_operations():
    """What ISSUE 49 says of the cut: the MLPs 56%, the linear layers'
    projections 29%, the full layer's 6.5%, the head 5.3%, full attention
    1.7%, the scan itself 1.0%."""
    need = flops_linear.required_flops(_config(), 4096)
    share = {k: round(100 * v / need['forward'], 1)
             for k, v in need['parts'].items()}
    assert share == {'mlp': 56.0, 'linear_projections': 29.4,
                     'attention_projections': 6.5, 'head': 5.3,
                     'attention_full': 1.7, 'delta_rule': 1.0}


def test_scan_by_hand():
    """One head's chunk of 64 rows at dk 96, dv 192: K K^T, Q K^T and T (K
    e^gamma) are 2 x 64 x 64 x 96 each, T V and P U' 2 x 64 x 64 x 192
    each, the solve 64^3, W S, Q S and the state's update 2 x 64 x 96 x
    192 each; over 64 rows and times 30 heads."""
    chunk = 3 * 2 * 64 * 64 * 96 + 2 * 2 * 64 * 64 * 192 + 64 ** 3 \
        + 3 * 2 * 64 * 96 * 192
    assert chunk == 12845056
    assert flops_linear.delta_rule_flops_per_token(_config()) \
        == 30 * chunk / 64 == 6021120
    # at another chunk the count is another: the metric holds 64
    assert flops_linear.delta_rule_flops_per_token(_config(), 128) \
        == 30 * (2 * 128 * (3 * 96 + 2 * 192) + 128 ** 2 + 6 * 96 * 192)


def test_delta_rule_least_time_by_hand():
    cfg = _config()
    flops, bytes_ = flops_linear.delta_rule_work(cfg, 4096, 1)
    assert flops == 3 * 3 * 4096 * 6021120
    # q, k of 96 and v, o of 192 columns in 2 bytes, g and beta in 4, a
    # head and row; forward once, backward twice; three layers
    forward = 4096 * 30 * (2 * (96 + 96 + 192 + 192) + 2 * 4)
    assert forward == 142540800 and bytes_ == 3 * 3 * forward
    least = flops_linear.delta_rule_least_seconds(cfg, 4096, 1, V5E)
    # the bytes bound it: 1.57 ms a step against the products' 1.13
    assert least == bytes_ / 819e9
    assert abs(least * 1e3 - 1.566) < 1e-3
    assert abs(flops / 197e12 * 1e3 - 1.127) < 1e-3


def test_conv_bytes_by_hand():
    """[q | k | v] is 30 x (96 + 96 + 192) = 11520 columns of 2 bytes a
    row: x and y forward, x, dy and dx backward, three layers."""
    v = 4096 * 11520 * 2
    assert v == 94371840
    bytes_ = flops_linear.conv_bytes(_config(), 4096, 1)
    assert bytes_ == 3 * 5 * v == 1415577600
    # 1.73 ms a step at the v5e's bandwidth
    assert abs(bytes_ / V5E['hbm_bytes_s'] * 1e3 - 1.728) < 1e-3
    assert flops_linear.conv_bytes(_config(), 4096, 2) == 2 * bytes_


def test_attention_work_by_hand():
    cfg = _config()
    flops, bytes_ = flops_linear.attention_work(cfg, 4096, 1)
    pairs = 4096 * 4097 / 2
    assert flops == 7 * 2 * 128 * 30 * pairs
    # q, k, v, o forward; those four, the cotangent and three gradients back
    assert bytes_ == 2 * 4096 * 128 * (4 * 30 + 9 * 30)


def test_small_config_by_hand():
    cfg = dict(hidden_size=8, intermediate_size=16, vocab_size=32,
               num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=2,
               layer_types=['linear_attention', 'full_attention'],
               linear_num_value_heads=2, linear_key_head_dim=3,
               linear_value_head_dim=5)
    parts = flops_linear.forward_flops_per_token(cfg, 4)
    assert parts == {
        'linear_projections': 2.0 * 8 * (2 * 6 + 3 * 10 + 4),
        'delta_rule': 2 * (2.0 * 64 * (9 + 10) + 4096 + 6 * 15),
        'attention_projections': 2.0 * 4 * 8 * 8,
        'attention_full': 4.0 * 4 * 2 * 10 / 4,
        'mlp': 2 * 6.0 * 8 * 16,
        'head': 2.0 * 8 * 32}
