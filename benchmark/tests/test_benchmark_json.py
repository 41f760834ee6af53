"""``BENCHMARK.json`` against the contract's static rules, and every file
a cell names."""
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
BENCH = json.load(open(os.path.join(REPO, 'BENCHMARK.json')))
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


def test_keys_and_names():
    assert set(BENCH) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= BENCH['run_seconds'] <= 51
    names = [m['name'] for m in BENCH['end_to_end'] + BENCH['per_layer']]
    assert len(names) == len(set(names))
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        for e in BENCH[group]:
            assert NAME.match(e['name']), e['name']
    for m in BENCH['end_to_end'] + BENCH['per_layer']:
        assert UNIT.match(m['unit']), m
        assert m['better'] in ('lower', 'higher')
        assert m['source'] in SOURCES
    for m in BENCH['end_to_end']:
        assert 0 < m['bound'] <= 0.1
        assert m['source'] in ('host_clock', 'device_trace')
        assert set(m) <= {'name', 'unit', 'better', 'bound', 'source',
                          'workloads'}
    for m in BENCH['per_layer']:
        assert set(m) <= {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    assert any(m['name'] == 'setup_s' for m in BENCH['end_to_end'])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_cells_and_files():
    configs = {c['name']: c for c in BENCH['configs']}
    pairs = set()
    four = 0
    for w in BENCH['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert w['chips'] in (1, 4) and len(w['why']) <= 200
        assert (w['config'], w['traffic']) not in pairs
        pairs.add((w['config'], w['traffic']))
        four += w['chips'] == 4
        assert os.path.exists(os.path.join(REPO, configs[w['config']]['file']))
        traffic = os.path.join(REPO, 'benchmark', 'traffic',
                               w['traffic'] + '.json')
        driver = json.load(open(traffic))['driver']
        assert os.path.exists(os.path.join(REPO, 'benchmark', 'drivers',
                                           driver + '.py'))
    assert four <= max(1, len(BENCH['workloads']) // 4)
    used = {w['config'] for w in BENCH['workloads']}
    assert used == set(configs)
    for c in BENCH['configs']:
        assert c['file'].startswith('benchmark/')
        cfg = json.load(open(os.path.join(REPO, c['file'])))
        assert cfg['reduced'] == c['reduced']
        ref = cfg['reference'].split(':')[0]
        assert os.path.exists(os.path.join(REPO, 'benchmark', 'reference',
                                           ref + '.py'))


def test_every_layer_metric_moves_something_its_cells_report():
    from benchmark import harness
    e2e = {m['name']: m for m in BENCH['end_to_end']}
    cells = [w['name'] for w in BENCH['workloads']]
    for m in BENCH['per_layer']:
        assert m['moves'] in e2e and m['moves'] != 'setup_s', m
        assert os.path.exists(os.path.join(
            REPO, 'benchmark', 'layer_metrics', m['name'] + '.py')), m
        moved = e2e[m['moves']]
        for c in m.get('workloads', moved.get('workloads', cells)):
            assert c in cells
            assert c in moved.get('workloads', cells), (m['name'], c)
    for c in cells:
        cell = harness.Cell(c)
        assert any(cell.reports(m) for m in BENCH['per_layer'])
        assert sum(cell.reports(m) for m in BENCH['end_to_end']) >= 2
    layers = {}
    for m in BENCH['per_layer']:
        layers.setdefault(m['layer'].lower(), set()).add(m['layer'])
    assert all(len(v) == 1 for v in layers.values()), layers
