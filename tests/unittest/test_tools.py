"""Tooling tier: bandwidth measurement + the legacy
DataParallelExecutorManager (reference tools/bandwidth/measure.py,
python/mxnet/executor_manager.py).
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, 'tools'))


def test_telemetry_report_golden(tmp_path, capsys):
    """tools/telemetry_report renders a fixed JSONL byte-for-byte (the
    offline twin of the live end-of-run summary table)."""
    import json
    import telemetry_report
    recs = [
        {'type': 'start', 'pid': 1, 't': 100.0},
        {'type': 'span', 'name': 'fit.batch', 'path': 'fit.batch',
         't': 100.1, 'dur_ms': 2.0},
        {'type': 'summary', 't': 101.5, 'elapsed_s': 1.5,
         'snapshot': {
             'counters': {'fit.steps': 8},
             'gauges': {'fit.input_bound_pct': 0.125,
                        'program.p.flops': 1000.0},
             'histograms': {'fit.batch': {
                 'count': 1, 'sum': 2.0, 'mean': 2.0, 'min': 2.0,
                 'max': 2.0, 'p50': 2.0, 'p95': 2.0}}},
         'programs': {'p': {
             'name': 'p', 'compiles': 1, 'dispatches': 2,
             'flops': 1000.0, 'bytes_accessed': 2048.0,
             'temp_bytes': 1048576, 'argument_bytes': 2097152,
             'output_bytes': 524288, 'generated_code_bytes': 0}}},
    ]
    path = tmp_path / 'tele.jsonl'
    with open(path, 'w') as f:
        for r in recs:
            f.write(json.dumps(r) + '\n')
    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    golden = (
        '== telemetry summary (1.5s) ==\n'
        '-- counters --\n'
        '  fit.steps  8\n'
        '-- gauges --\n'
        '  fit.input_bound_pct  0.125\n'
        '-- programs --\n'
        '  name  compiles      calls      flops  bytes_acc  temp_MiB'
        '   arg_MiB   out_MiB\n'
        '  p            1          2   1000.000   2048.000       1.0'
        '       2.0       0.5\n'
        '-- where the time went --\n'
        '  step                    0.000s    0.0%\n'
        '  compile                 0.000s    0.0%\n'
        '  input_wait              0.000s    0.0%\n'
        '  checkpoint              0.000s    0.0%\n'
        '  eval                    0.000s    0.0%\n'
        '  comm                    0.000s    0.0%\n'
        '  rework                  0.000s    0.0%\n'
        '  overhead                1.500s  100.0%\n'
        '  wall                    1.500s\n'
        '  goodput           0.000% (top badput: overhead)\n'
        '-- histograms (ms) --\n'
        '  name          count       mean        p50        p95'
        '        max\n'
        '  fit.batch         1      2.000      2.000      2.000'
        '      2.000\n')
    assert out == golden
    # the program.p.* gauge is folded into the table, not repeated
    assert 'program.p.flops' not in out


def test_telemetry_report_reconstructs_without_summary(tmp_path, capsys):
    """A crashed run's log (no summary record) still renders: spans,
    compiles, program records AND the run-health story — the incidents
    plus the LAST anomaly before the crash — are reconstructed
    best-effort."""
    import json
    import telemetry_report
    recs = [
        {'type': 'start', 'pid': 1, 't': 10.0},
        {'type': 'span', 'name': 'fit.dispatch', 't': 10.1,
         'dur_ms': 5.0},
        {'type': 'span', 'name': 'fit.dispatch', 't': 10.2,
         'dur_ms': 7.0},
        {'type': 'compile', 't': 10.3, 'dur_s': 1.25},
        {'type': 'program', 'name': 'executor.fwd_bwd[softmax]',
         't': 10.4, 'flops': 5e6, 'bytes_accessed': 1e6,
         'temp_bytes': 4096, 'argument_bytes': 8192, 'output_bytes': 16,
         'generated_code_bytes': 0},
        {'type': 'anomaly', 'detector': 'step_time', 't': 10.5,
         'value': 912.4, 'baseline': 310.2, 'mad': 4.1, 'k': 8.0},
        {'type': 'anomaly', 'detector': 'loss', 't': 10.6,
         'value': 50.0, 'baseline': 2.0, 'mad': 0.1, 'k': 8.0},
        {'type': 'health', 'event': 'nonfinite', 't': 10.7,
         'source': 'fused_fit', 'step': 34, 'window_step': 2,
         'first_bad_layer': 'fc1_weight', 'outputs_nonfinite': [0]},
        {'type': 'health', 'event': 'input_bound', 't': 10.8,
         'input_bound_pct': 37.5},
    ]
    path = tmp_path / 'crashed.jsonl'
    with open(path, 'w') as f:
        for r in recs:
            f.write(json.dumps(r) + '\n')
    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert 'xla.compiles' in out and 'fit.dispatch' in out
    assert 'executor.fwd_bwd[softmax]' in out
    assert 'no summary record found' in out
    # crashed-run health reconstruction: the incident with its step
    # attribution and the LAST anomaly (loss, 10.6 > 10.5) survive
    assert '-- run health --' in out
    assert 'DEGRADED (1 non-finite step)' in out
    assert ('fused_fit step 34 (window step 2): '
            'first non-finite symbol fc1_weight') in out
    assert 'loss=1, step_time=1' in out
    assert 'last_anomaly      loss=50.000 (baseline 2.000)' in out
    assert 'input_bound_pct   37.500' in out


def test_telemetry_report_health_block_from_summary(tmp_path, capsys):
    """A summary record's 'health' key renders the same Run health
    block the live table logged."""
    import json
    import telemetry_report
    rec = {'type': 'summary', 't': 20.0, 'elapsed_s': 2.0,
           'snapshot': {'counters': {'health.steps': 8},
                        'gauges': {}, 'histograms': {}},
           'health': {'nonfinite_steps': 0, 'incidents': [],
                      'anomaly_counts': {'step_time': 2},
                      'last_anomaly': {'detector': 'step_time',
                                       'value': 912.4, 'baseline': 310.2},
                      'input_bound_pct': 41.5}}
    path = tmp_path / 'ok.jsonl'
    with open(path, 'w') as f:
        f.write(json.dumps(rec) + '\n')
    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert 'status            ok' in out
    assert 'anomalies         step_time=2' in out
    assert 'input_bound_pct   41.500' in out


def _host_jsonl(tmp_path, host, step_ms, io_ms, steps=64, nonfinite=0):
    """One host's telemetry log: a summary record whose histograms put
    the host at ``step_ms`` per step with ``io_ms`` of prefetch wait."""
    import json
    snap = {'counters': {'fit.steps': steps},
            'gauges': {'health.step_time_ms': step_ms},
            'histograms': {
                'fit.batch': {'count': steps, 'sum': step_ms * steps,
                              'mean': step_ms, 'min': step_ms,
                              'max': step_ms, 'p50': step_ms,
                              'p95': step_ms},
                'io.prefetch_wait': {'count': steps, 'sum': io_ms * steps,
                                     'mean': io_ms, 'min': io_ms,
                                     'max': io_ms, 'p50': io_ms,
                                     'p95': io_ms}}}
    rec = {'type': 'summary', 't': 50.0, 'host': host, 'elapsed_s': 5.0,
           'snapshot': snap}
    if nonfinite:
        rec['health'] = {'nonfinite_steps': nonfinite, 'incidents': [],
                         'anomaly_counts': {}, 'last_anomaly': None}
    path = tmp_path / ('host%d.jsonl' % host)
    with open(path, 'w') as f:
        f.write(json.dumps({'type': 'start', 'pid': 1, 't': 45.0,
                            'host': host}) + '\n')
        f.write(json.dumps(rec) + '\n')
    return str(path)


def test_telemetry_report_multi_host(tmp_path, capsys):
    """Multiple JSONL paths (one per host) merge on the host field and
    render the per-host comparison plus the straggler classification:
    the slow host with a dominant io-wait share reads input_bound."""
    import telemetry_report
    p0 = _host_jsonl(tmp_path, 0, step_ms=10.0, io_ms=0.5)
    p1 = _host_jsonl(tmp_path, 1, step_ms=20.0, io_ms=9.0, nonfinite=2)
    assert telemetry_report.main([p0, p1]) == 0
    out = capsys.readouterr().out
    assert '== per-host comparison (2 hosts) ==' in out
    assert '1*' in out                       # slowest host marked
    assert 'input_bound' in out              # 9/20 = 45% io-wait share
    assert 'host 1 straggles — input_bound' in out
    # both hosts' full tables follow the comparison
    assert '== host 0 ==' in out and '== host 1 ==' in out
    # a single path keeps the original single-run rendering
    assert telemetry_report.main([p0]) == 0
    out = capsys.readouterr().out
    assert 'per-host comparison' not in out
    assert 'telemetry summary' in out


def test_telemetry_report_multi_host_communication_bound(tmp_path,
                                                         capsys):
    """The offline classifier sees the same roofline comm share the
    live sync vector carried: a slow host that is not input-starved
    but spends >30%% of its step in collectives reads
    communication_bound offline too."""
    import json
    import telemetry_report
    p0 = _host_jsonl(tmp_path, 0, step_ms=10.0, io_ms=0.2)
    p1 = _host_jsonl(tmp_path, 1, step_ms=20.0, io_ms=0.4)
    roof = {'type': 'roofline', 't': 60.0, 'host': 1, 'program': 'p',
            'source': 'measured', 'device': 'tpu v5 lite',
            'peaks': 'table', 'peak_tflops': 197.0,
            'peak_hbm_gbs': 819.0, 'step_time_ms': 20.0,
            'layers': [],
            'comm': {'bytes': 1e6, 'time_ms': 9.0, 'overlap_pct': 10.0,
                     'pct_of_step': 45.0, 'ops': {}, 'source':
                     'measured'}}
    with open(p1, 'a') as f:
        f.write(json.dumps(roof) + '\n')
    assert telemetry_report.main([p0, p1]) == 0
    out = capsys.readouterr().out
    assert 'host 1 straggles — communication_bound' in out


def test_telemetry_watch_render():
    """The watch CLI's frame renderer (pure function): throughput,
    health and per-host spread all land in the frame."""
    import telemetry_watch
    summary = {
        'elapsed_s': 120.0, 'host': 0,
        'snapshot': {
            'counters': {'fit.steps': 640},
            'gauges': {'speedometer.samples_per_sec': 1234.5,
                       'fit.input_bound_pct': 12.5},
            'histograms': {'fit.batch': {
                'count': 640, 'sum': 6400.0, 'mean': 10.0, 'min': 9.0,
                'max': 30.0, 'p50': 10.0, 'p95': 12.0}}},
        'health': {'nonfinite_steps': 1, 'incidents': [],
                   'anomaly_counts': {'loss': 2},
                   'last_anomaly': {'detector': 'loss', 'value': 9.0,
                                    'baseline': 2.0}},
        'cluster': {'hosts': 2, 'spread_pct': 40.0,
                    'straggler': 'input_bound', 'slowest_host': 1,
                    'per_host': [
                        {'host': 0, 'step_time_ms': 10.0,
                         'io_wait_pct': 2.0, 'dispatch_ms': 8.0},
                        {'host': 1, 'step_time_ms': 20.0,
                         'io_wait_pct': 45.0, 'dispatch_ms': 18.0}]},
    }
    frame = '\n'.join(telemetry_watch.render(summary, steps_per_s=5.25))
    assert 'host 0' in frame and 'up 120s' in frame
    assert 'steps 640' in frame and '5.25 steps/s' in frame
    assert '1.23e+03 samples/s' in frame and 'mfu' not in frame
    assert 'p50 10 ms' in frame
    assert 'DEGRADED (1 non-finite steps)' in frame
    assert 'last_anomaly loss=9 (baseline 2)' in frame
    assert 'straggler: input_bound' in frame
    assert '1*' in frame


def test_telemetry_watch_fetch_jsonl(tmp_path):
    """File mode builds the same dashboard input the /summary endpoint
    serves, from the last summary record."""
    import telemetry_watch
    path = _host_jsonl(tmp_path, 0, step_ms=10.0, io_ms=0.5)
    summary = telemetry_watch.fetch(path)
    assert summary['snapshot']['counters']['fit.steps'] == 64
    assert summary['elapsed_s'] == 5.0
    lines = telemetry_watch.render(summary)
    assert any('throughput' in ln for ln in lines)


def test_telemetry_watch_renders_opt_state_line():
    """The watch frame shows the sharded-update engagement: per-device
    opt-state MiB, layout, dp, and the step's whole collective share
    (labeled as such)."""
    import telemetry_watch
    summary = {
        'elapsed_s': 10.0, 'host': 0,
        'snapshot': {
            'counters': {'fit.steps': 64},
            'gauges': {'update.opt_state_bytes_per_device': 13448.0,
                       'update.sharded': 1.0, 'update.dp': 8.0,
                       'roofline.comm_pct_of_step': 7.5},
            'histograms': {}}}
    frame = '\n'.join(telemetry_watch.render(summary))
    assert 'opt_state' in frame
    assert 'sharded dp=8' in frame
    assert 'step collectives 7.5%' in frame
    # replicated layout renders too (and says so)
    summary['snapshot']['gauges'].update({'update.sharded': 0.0})
    frame = '\n'.join(telemetry_watch.render(summary))
    assert 'replicated' in frame


def test_every_report_and_diff_cli_smokes(tmp_path):
    """CI floor: every tools/*_report.py and tools/*_diff.py answers
    --help (argparse wiring + imports) — a new CLI cannot land without
    at least this."""
    import glob
    import subprocess
    patterns = [os.path.join(REPO, 'tools', '*_report.py'),
                os.path.join(REPO, 'tools', '*_diff.py'),
                os.path.join(REPO, 'tools', 'run_compare.py'),
                os.path.join(REPO, 'tools', 'telemetry_watch.py')]
    clis = sorted(p for pat in patterns for p in glob.glob(pat))
    assert clis, 'no report/diff CLIs found'
    names = {os.path.basename(p) for p in clis}
    assert {'telemetry_report.py', 'roofline_report.py',
            'memory_report.py', 'run_compare.py',
            'telemetry_watch.py'} <= names
    for cli in clis:
        out = subprocess.run([sys.executable, cli, '--help'],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, (cli, out.stderr)
        assert 'usage' in out.stdout.lower(), cli


def test_roofline_report_golden(tmp_path, capsys):
    """tools/roofline_report renders a fixed roofline JSONL record
    byte-for-byte through the live renderer (the offline twin; the
    live-vs-CLI identity is pinned end-to-end in test_roofline.py)."""
    import json
    import roofline_report
    roof = {'program': 'bench.train_step', 'source': 'measured',
            'device': 'tpu v5 lite', 'peaks': 'table',
            'peak_tflops': 197.0, 'peak_hbm_gbs': 819.0,
            'step_time_ms': 12.5, 'trace_steps': 10,
            'layers': [
                {'layer': 'stage1_unit1_conv1', 'class': 'memory-bound',
                 'flops': 1e9, 'bytes': 5e8, 'time_ms': 3.0, 'ai': 2.0,
                 'achieved_flops_s': 3.3e11, 'achieved_bytes_s': 1.6e11,
                 'roof_pct': 20.3, 'headroom_ms': 2.39}],
            'comm': {'bytes': 1048576.0, 'time_ms': 0.84,
                     'overlap_pct': 40.0, 'pct_of_step': 6.7,
                     'ops': {'all-reduce': 1048576.0},
                     'source': 'measured'}}
    path = tmp_path / 'roof.jsonl'
    with open(path, 'w') as f:
        f.write(json.dumps({'type': 'start', 'pid': 1, 't': 1.0}) + '\n')
        f.write(json.dumps(dict(roof, type='roofline', t=2.0)) + '\n')
    assert roofline_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    golden = (
        '-- roofline: bench.train_step (measured) --\n'
        '  device            tpu v5 lite (table peaks: 197.000 TFLOP/s,'
        ' 819.000 GB/s)\n'
        '  step_time_ms      12.500\n'
        '  layer               class             roof%    time_ms'
        '  headroom_ms\n'
        '  stage1_unit1_conv1  memory-bound     20.300      3.000'
        '        2.390\n'
        '  comm              1.0 MiB/step, 0.840 ms = 6.700% of step,'
        ' overlap 40.000% (measured; all-reduce 1.0 MiB)\n')
    assert out == golden


def _roof_dict(step_ms, conv_ms, conv_head, fc_ms, fc_head,
               extra_layer=None):
    layers = [
        {'layer': 'conv1', 'class': 'memory-bound', 'flops': 1e9,
         'bytes': 5e8, 'time_ms': conv_ms, 'ai': 2.0,
         'achieved_flops_s': 1.0, 'achieved_bytes_s': 1.0,
         'roof_pct': 20.0, 'headroom_ms': conv_head},
        {'layer': 'fc1', 'class': 'compute-bound', 'flops': 2e9,
         'bytes': 1e8, 'time_ms': fc_ms, 'ai': 20.0,
         'achieved_flops_s': 1.0, 'achieved_bytes_s': 1.0,
         'roof_pct': 80.0, 'headroom_ms': fc_head}]
    if extra_layer:
        layers.append(dict(layers[0], layer=extra_layer))
    return {'program': 'fused_fit.window[softmax]', 'source': 'modeled',
            'device': 'cpu', 'peaks': 'nominal', 'peak_tflops': 0.1,
            'peak_hbm_gbs': 50.0, 'step_time_ms': step_ms,
            'layers': layers}


def test_roofline_diff_headroom_reclaimed(tmp_path, capsys):
    """tools/roofline_diff matches layers by name across two roofline
    records (the last one of each telemetry JSONL) and ranks headroom
    reclaimed; layers present on only one side are listed, never
    silently dropped."""
    import json
    import roofline_diff
    before = tmp_path / 'before.jsonl'
    with open(before, 'w') as f:
        f.write(json.dumps(dict(_roof_dict(10.0, 4.0, 3.0, 2.0, 0.5,
                                           extra_layer='bn1'),
                                type='roofline', t=1.0)) + '\n')
    after = tmp_path / 'after.jsonl'
    with open(after, 'w') as f:
        # an earlier record of the same log loses to the last one
        f.write(json.dumps(dict(_roof_dict(9.0, 3.0, 2.0, 2.0, 0.5),
                                type='roofline', t=1.0)) + '\n')
        f.write(json.dumps(dict(_roof_dict(7.0, 1.5, 0.5, 2.0, 0.5),
                                type='roofline', t=2.0)) + '\n')
    assert roofline_diff.main([str(before), str(after)]) == 0
    out = capsys.readouterr().out
    assert 'step_time_ms      10 -> 7' in out
    assert 'conv1' in out and '2.5' in out     # 3.0 - 0.5 reclaimed
    assert 'gone in new: bn1' in out
    assert 'total headroom reclaimed: 2.5 ms/step' in out
    # --json round-trips the diff dict
    assert roofline_diff.main([str(before), str(after), '--json']) == 0
    d = json.loads(capsys.readouterr().out)
    assert d['total_reclaimed_ms'] == 2.5
    assert d['layers'][0]['layer'] == 'conv1'
    assert d['layers'][0]['reclaimed_ms'] == 2.5
    assert d['only_old'] == ['bn1']
    # a record-less artifact is a loud error, not an empty diff
    empty = tmp_path / 'empty.jsonl'
    empty.write_text(json.dumps({'type': 'start', 'pid': 1}) + '\n')
    with pytest.raises(SystemExit, match='no roofline record'):
        roofline_diff.main([str(empty), str(after)])


def test_telemetry_report_renders_roofline_block(tmp_path, capsys):
    """A summary record's 'roofline' key lands in telemetry_report's
    table, same renderer as the live one."""
    import json
    import telemetry_report
    rec = {'type': 'summary', 't': 20.0, 'elapsed_s': 2.0,
           'snapshot': {'counters': {'fit.steps': 8}, 'gauges': {},
                        'histograms': {}},
           'roofline': {'program': 'p', 'source': 'modeled',
                        'device': 'cpu', 'peaks': 'nominal',
                        'peak_tflops': 0.1, 'peak_hbm_gbs': 50.0,
                        'step_time_ms': 5.0,
                        'layers': [{'layer': 'fc1',
                                    'class': 'compute-bound',
                                    'flops': 1.0, 'bytes': 1.0,
                                    'time_ms': 5.0, 'ai': 1.0,
                                    'achieved_flops_s': 1.0,
                                    'achieved_bytes_s': 1.0,
                                    'roof_pct': 1.0,
                                    'headroom_ms': 4.9}],
                        'comm': None}}
    path = tmp_path / 'roof_sum.jsonl'
    with open(path, 'w') as f:
        f.write(json.dumps(rec) + '\n')
    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert '-- roofline: p (modeled) --' in out
    assert 'fc1' in out and 'compute-bound' in out


def test_bandwidth_collectives_tiny():
    import bandwidth
    res = bandwidth.measure_collectives(sizes=[1024], iters=2)
    ops = {r['op'] for r in res}
    assert {'psum', 'all_gather', 'reduce_scatter'} <= ops
    for r in res:
        assert r['busbw_GBps'] > 0 and r['time_ms'] > 0


def test_bandwidth_kvstore_tiny():
    import bandwidth
    res = bandwidth.measure_kvstore(sizes=[1024], iters=2)
    assert res and res[0]['op'] == 'kv_push_pull'
    assert res[0]['bytes'] == 4096


def test_executor_manager_trains():
    """The legacy manager runs a full fwd/bwd/update cycle over multiple
    contexts (reference executor_manager.py DataParallelExecutorManager)."""
    from mxnet_tpu.executor_manager import DataParallelExecutorManager
    from mxnet_tpu.io import NDArrayIter

    rng = np.random.RandomState(0)
    X = rng.randn(32, 6).astype(np.float32)
    w_true = rng.randn(6).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32)

    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, num_hidden=2, name='fc')
    net = mx.sym.SoftmaxOutput(net, name='softmax')

    it = NDArrayIter(X, y, batch_size=8, label_name='softmax_label')
    arg_names = net.list_arguments()
    param_names = [n for n in arg_names
                   if n not in ('data', 'softmax_label')]
    mgr = DataParallelExecutorManager(
        symbol=net, ctx=[mx.cpu(0), mx.cpu(1)], train_data=it,
        arg_names=arg_names, param_names=param_names,
        aux_names=net.list_auxiliary_states())

    arg_params = {n: nd.array(rng.randn(*s).astype(np.float32) * 0.1)
                  for n, s in zip(
                      arg_names, net.infer_shape(data=(8, 6))[0])
                  if n in param_names}
    mgr.set_params(arg_params, {})

    opt = mx.optimizer.SGD(learning_rate=0.5)
    updater = mx.optimizer.get_updater(opt)

    losses = []
    for epoch in range(4):
        it.reset()
        correct = total = 0
        for batch in it:
            mgr.load_data_batch(batch)
            mgr.forward(is_train=True)
            mgr.backward()
            for idx, (ws, gs) in enumerate(zip(mgr.param_arrays,
                                               mgr.grad_arrays)):
                for k, (w, g) in enumerate(zip(ws, gs)):
                    updater(idx * 2 + k, g, w)
            for out, lab in zip(mgr.curr_execgrp.get_outputs()
                                if hasattr(mgr, 'curr_execgrp') else [],
                                []):
                pass
        # score with the trained params
        out_args, out_aux = {}, {}
        mgr.copy_to(out_args := {n: nd.zeros(a.shape) for n, a in
                                 arg_params.items()}, out_aux)
        ex = net.bind(mx.cpu(), dict(out_args,
                                     data=nd.array(X),
                                     softmax_label=nd.array(y)))
        pred = ex.forward()[0].asnumpy().argmax(1)
        losses.append((pred == y).mean())
    assert losses[-1] > 0.8, losses


# ---------------------------------------------------------------------------
# run ledger satellites (ISSUE 15)
# ---------------------------------------------------------------------------

def test_telemetry_watch_renders_dynamics_and_sparkline():
    """The watch frame shows the per-layer dynamics roll-up (worst
    layer, dead fraction, incident count) and a loss sparkline from
    the ledger's recent scalars; neither line renders without its
    data."""
    import telemetry_watch
    summary = {
        'snapshot': {
            'counters': {'fit.steps': 64,
                         'dynamics.layer_incidents': 2},
            'gauges': {'dynamics.worst_layer': 'fc2_weight',
                       'dynamics.worst_update_ratio': 0.0042,
                       'dynamics.dead_frac_max': 0.12},
            'histograms': {}},
        'ledger': {'recent': [{'step': 2, 'loss': 1.0},
                              {'step': 4, 'loss': 0.8},
                              {'step': 6, 'loss': 0.5}]},
    }
    lines = telemetry_watch.render(summary)
    dyn = [ln for ln in lines if ln.strip().startswith('dynamics')]
    assert dyn and 'fc2_weight' in dyn[0]
    assert 'dead 12%' in dyn[0]
    assert '2 layer incidents' in dyn[0]
    loss = [ln for ln in lines if ln.strip().startswith('loss')]
    assert loss
    # the sparkline descends with the loss series
    assert telemetry_watch._SPARK[0] in loss[0]
    assert telemetry_watch._SPARK[-1] in loss[0]
    # no dynamics gauges, no ledger: neither line
    lines = telemetry_watch.render({'snapshot': {'counters': {},
                                                 'gauges': {},
                                                 'histograms': {}}})
    assert not [ln for ln in lines
                if ln.strip().startswith(('dynamics', 'loss'))]


def test_telemetry_report_renders_ledger_block(tmp_path, capsys):
    """A crashed run's log (manifest + scalars, no summary record)
    reconstructs the run-ledger block offline; a summary-carrying log
    renders it from the summary's ledger key."""
    import json
    import telemetry_report
    recs = [
        {'type': 'start', 'pid': 1, 't': 1.0},
        {'type': 'manifest', 't': 1.0, 'jax_version': '0.4.37',
         'platform': 'cpu', 'device_kind': 'cpu', 'device_count': 8,
         'git_sha': 'abc1234', 'flags': {'MXTPU_TELEMETRY': True},
         'env_set': ['MXTPU_TELEMETRY']},
        {'type': 'scalars', 'step': 2, 't': 2.0, 'loss': 1.0},
        {'type': 'scalars', 'step': 4, 't': 3.0, 'loss': 0.5},
    ]
    path = tmp_path / 'crashed.jsonl'
    path.write_text(''.join(json.dumps(r) + '\n' for r in recs))
    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert '-- run ledger --' in out
    assert 'jax=0.4.37' in out and 'git=abc1234' in out
    assert 'scalars           4 steps, every 2' in out
    assert 'loss 0.500' in out
    assert 'no summary record found' in out
    # summary path: the ledger key renders directly
    recs.append({'type': 'summary', 't': 4.0, 'elapsed_s': 3.0,
                 'snapshot': {},
                 'ledger': {'steps': 4, 'every': 2,
                            'manifest': {'jax_version': '0.4.37'},
                            'recent': [{'step': 4, 'loss': 0.5}],
                            'last': {'step': 4, 'loss': 0.5},
                            'final_loss': 0.5}})
    path.write_text(''.join(json.dumps(r) + '\n' for r in recs))
    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert '-- run ledger --' in out
    assert 'no summary record found' not in out
