"""The join of a capture's per-instruction seconds with the compiled
window's scope map (``reduce/scopes.py``), on the capture recorded on the
v5e (``small_v5e.xplane.pb``: a jitted scan of a convolution, a tanh and a
reduction, four calls) under a map made by hand for its instructions, and
the nine readers on runs with and without a capture. The walk that makes a
real map is the program's (``tests/unittest/test_scope_map.py``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.reduce import scopes, trace  # noqa: E402

RECORDED = os.path.join(HERE, 'small_v5e.xplane.pb')
READERS = [m['name'] for m in harness.load_json(
    os.path.join(REPO, 'BENCHMARK.json'))['per_layer']
    if m['name'] in ('unscoped_device_pct', 'update_device_pct',
                     'window_loop_self_ms', 'recompute_device_pct',
                     'dense_device_pct', 'norm_rotary_device_pct',
                     'moe_glue_device_pct', 'attn_glue_device_pct',
                     'bn_device_pct')]


def reader(name):
    return harness.load_file_module(os.path.join(
        REPO, 'benchmark', 'layer_metrics', name + '.py'))


def hand_map(by_name):
    """A map for the recorded program: its fusions are the convolution
    node's (the output fusions forward, the others backward), the scan
    is the window's, copies have no scope, one fusion is left out."""
    instrs = {}
    for key in by_name:
        name, _, rest = key.partition(' ')
        if name.startswith('while'):
            instrs[name] = ['window', '-', None, 'while', 0, '']
        elif 'fusion' in name:
            phase = 'fwd' if rest.endswith('kOutput') else 'bwd'
            instrs[name] = ['conv0', phase, None, 'fusion', 1, '']
        else:
            instrs[name] = [None, None, None, name.split('.')[0], 0, '']
    left_out = sorted(n for n in instrs if 'fusion' in n)[0]
    del instrs[left_out]
    return {'program': 'fused_fit.window[recorded]', 'instrs': instrs,
            'nodes': {'conv0': 'Convolution'}}, left_out


def test_nine_readers_are_listed():
    assert len(READERS) == 9


def test_the_parts_sum_to_the_busy_time_of_a_recorded_capture():
    r = trace.reduce_file(RECORDED, devices=1)
    m, left_out = hand_map(r['by_name'])
    t = scopes.join(r['by_name'], m, steps=12)
    parts = sum(t['rows'].values()) + sum(t['loops'].values()) \
        + t['unscoped_s'] + t['unmapped_s']
    assert parts == pytest.approx(t['total_s'])
    # leaf times tile the busy time of a single chip's line of operations
    assert 12 * t['total_s'] == pytest.approx(r['busy_s'], rel=1e-3)
    assert set(t['rows']) == {('Convolution', 'fwd', ''),
                              ('Convolution', 'bwd', '')}
    assert t['loops']['while'] > 0
    assert t['unscoped_s'] > 0
    assert [k.split(' ')[0] for k in t['unmapped']] == [left_out]
    assert t['kernels'] == {} and t['mixed_s'] == 0 and t['lent_s'] == 0


def test_table_is_made_once_and_read_by_every_reader(tmp_path, monkeypatch,
                                                     capsys):
    r = trace.reduce_file(RECORDED, devices=1)
    m, _ = hand_map(r['by_name'])
    (tmp_path / 'telemetry.scopes.w.1.json').write_text(json.dumps(m))
    log = tmp_path / 'telemetry.jsonl'
    log.write_text(json.dumps(
        {'type': 'program', 'name': 'fused_fit.window[recorded]',
         'scopes': 'telemetry.scopes.w.1.json'}) + '\n')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(log))
    run = {'trace': r, 'trace_steps': 12}
    got = {n: reader(n).read(run) for n in READERS}
    assert all(v is not None for v in got.values()), got
    t = run['scopes']
    assert got['window_loop_self_ms'] == pytest.approx(
        1e3 * t['loops']['while'])
    assert got['unscoped_device_pct'] == pytest.approx(
        100 * (t['unscoped_s'] + t['unmapped_s']) / t['busy_s'])
    for name in ('update_device_pct', 'recompute_device_pct',
                 'dense_device_pct', 'bn_device_pct'):
        assert got[name] == 0.0
    assert capsys.readouterr().err.count('ms busy a step') == 1


@pytest.mark.parametrize('name', READERS)
def test_reader_returns_none_without_a_capture_or_a_map(name, tmp_path,
                                                        monkeypatch):
    monkeypatch.delenv('MXTPU_TELEMETRY_PATH', raising=False)
    assert reader(name).read({}) is None
    # a traced run of a program that writes no map (this PR's parent)
    log = tmp_path / 'telemetry.jsonl'
    log.write_text(json.dumps(
        {'type': 'program', 'name': 'fused_fit.window[softmax]'}) + '\n')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(log))
    run = {'trace': {'by_name': {'fusion.1 f32[8] kLoop': 1.0},
                     'busy_s': 1.0}, 'trace_steps': 1}
    assert reader(name).read(run) is None
