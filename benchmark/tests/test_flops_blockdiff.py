"""``reduce/flops_blockdiff.py`` against counts by hand."""
import json
import os

from benchmark.reduce import flops_blockdiff

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = {'bf16_flops': 197e12, 'hbm_bytes_s': 819e9}


def _config():
    with open(os.path.join(os.path.dirname(HERE), 'configs',
                           'sdar_30b_a3b_chat.json')) as f:
        return json.load(f)


def test_the_masks_true_pairs():
    """A noisy row of block b sees B noisy and b B clean keys, a clean row
    (b + 1) B clean ones: by a loop over the blocks, and the cell's
    number."""
    for L, B in ((64, 4), (64, 16), (96, 32), (4096, 4)):
        n = L // B
        by_hand = sum(B * (B + b * B) + B * (b + 1) * B for b in range(n))
        assert flops_blockdiff.mask_pairs(L, B) == by_hand
    assert flops_blockdiff.mask_pairs(4096, 4) == 16793600
    # a row sees 2052 keys on average, half of what a causal row of the
    # 8192 would
    assert flops_blockdiff.mask_pairs(4096, 4) / 8192 == 2050.0


def test_required_flops_of_the_cut_model():
    """The published widths at 4096 clean tokens, part by part, operations
    a clean token, forward: five layers on twice the rows, the head
    once."""
    cfg = _config()
    parts = flops_blockdiff.forward_flops_per_token(cfg, 4096)
    assert parts['projections'] \
        == 2 * 5 * 2 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    assert parts['attention_blockdiff'] == 5 * 4 * 128 * 32 * 16793600 / 4096
    assert parts['router'] == 2 * 5 * 2 * 2048 * 128
    # top 8 of 128 with 16 held: one pair a row and layer
    assert parts['experts'] == 2 * 5 * 1.0 * 6 * 2048 * 768
    assert parts['head'] == 2 * 2048 * 18992
    need = flops_blockdiff.required_flops(cfg, 4096)
    assert abs(need['forward'] / 1e9 - 0.890) < 0.001
    assert abs(need['train'] / 1e9 - 2.67) < 0.005
    more = flops_blockdiff.required_flops(cfg, 4096, pairs_per_row=2.0)
    assert more['parts']['experts'] == 2 * parts['experts']


def test_shares_of_the_required_operations():
    """What ISSUE 45 says of the cut: attention under the mask 38%, the
    projections 42%, the experts 11%, the head 9%."""
    need = flops_blockdiff.required_flops(_config(), 4096)
    share = {k: round(100 * v / need['forward'])
             for k, v in need['parts'].items()}
    assert share == {'projections': 42, 'attention_blockdiff': 38,
                     'experts': 11, 'head': 9, 'router': 1}


def test_attention_work_by_hand():
    cfg = _config()
    flops, bytes_ = flops_blockdiff.attention_work(cfg, 4096, 1)
    assert flops == 5 * 7 * 2 * 128 * 32 * 16793600
    assert bytes_ == 5 * 2 * 8192 * 128 * ((64 + 8) + (160 + 16))
    # 24.4 ms a step at the peak against 3.2 ms of bytes: the products
    # bound it
    assert abs(flops / 197e12 * 1e3 - 24.44) < 0.01
    assert abs(bytes_ / 819e9 * 1e3 - 3.17) < 0.01


def test_expert_least_time_by_hand():
    cfg = _config()
    pairs, steps = 32 * 5 * 8192, 32     # a pair a row and layer
    flops = pairs * 9 * 2 * 2048 * 768
    weights = 3 * (16 * 3 * 2048 * 768 * 2) * 5 * steps
    rows = pairs * 2 * 3 * (3 * 2048 + 3 * 768)
    least = flops_blockdiff.expert_least_seconds(cfg, pairs, steps, V5E)
    assert least == max(flops / 197e12, (rows + weights) / 819e9)
    # at a pair a row the products bound it (0.188 s against 0.170 of
    # bytes), where Kanana's same shapes at 0.75 pairs a row are bound by
    # the weights' bytes
    assert least == flops / 197e12
