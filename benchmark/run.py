#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that takes the cell's chips (and exits non-zero, with no
result, where JAX finds no TPU or fewer chips than the cell asks for),
makes weights and data from ``--seed``, warms the cell's own shapes,
measures for ``--seconds`` and prints one JSON object as the last line of
its standard output. ``--trace 0`` gives the cell's end-to-end metrics
(telemetry off, no profiler); ``--trace 1`` is a run of its own with
``MXTPU_TELEMETRY=1`` and a profiler capture of a short steady slice, and
gives the per-layer metrics and ``breakdown``.

The cell, its configuration, its traffic mix, the driver that runs the
mix and every per-layer metric are files found by the names in
``BENCHMARK.json``; see ``benchmark/README.md``.

``--set traffic.<key>=<value>`` (or ``config.``) changes one size for a
sweep or a rehearsal; a run that sets any is marked ``overridden`` and is
no measurement of the cell.
"""
import time
T0 = time.perf_counter()        # process start, for setup_s

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402
import types                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import harness   # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--set', action='append', default=[],
                    metavar='traffic.key=value')
    ap.add_argument('--keep', metavar='DIR',
                    help='keep what is worth reading by hand here: a traced '
                         'run\'s profiler capture, a serving run\'s '
                         'per-request records')
    return ap.parse_args(argv)


def prepare_environment(trace, workdir):
    """The flags a run sets, before the program is imported: bfloat16 for
    'float16' (what ``train_imagenet.py --dtype float16`` means on a TPU)
    and, in a traced run only, the program's telemetry with its log inside
    the run's work directory. ``BENCH_RUN`` is the driver's and is not
    read."""
    os.environ['MXTPU_F16_AS_BF16'] = '1'
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    if trace:
        os.environ['MXTPU_TELEMETRY'] = '1'
        os.environ['MXTPU_TELEMETRY_PATH'] = os.path.join(
            workdir, 'telemetry.jsonl')
    else:
        os.environ.pop('MXTPU_TELEMETRY', None)


def take_devices(chips, require_chip):
    """The cell's chips, or exit: no fallback to the CPU."""
    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != 'tpu'
                         or len(devices) < chips):
        print('benchmark: the cell needs %d TPU chip(s); jax.devices() = %s'
              % (chips, devices), file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print('benchmark: %d device(s) needed, %d found'
              % (chips, len(devices)), file=sys.stderr)
        raise SystemExit(2)
    return devices


def layer_metrics(cell, run):
    """Every per-layer metric this cell reports: each from its own reader,
    ``benchmark/layer_metrics/<name>.py``. A reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in cell.bench['per_layer']:
        if not cell.reports(m):
            continue
        reader = harness.load_file_module(
            os.path.join(HERE, 'layer_metrics', m['name'] + '.py'))
        value = reader.read(run)
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def main(argv=None, require_chip=True):
    args = parse(argv)
    if not os.path.isdir(os.path.join(REPO, 'mxnet_tpu')):
        print('benchmark: %s holds no mxnet_tpu: nothing to measure'
              % REPO, file=sys.stderr)
        return 2
    cell = harness.Cell(args.workload, args.set)
    workdir = harness.make_workdir()
    try:
        prepare_environment(args.trace, workdir)
        devices = take_devices(cell.chips, require_chip)
        compiles = harness.Compiles()
        ctx = types.SimpleNamespace(
            cell=cell, config=cell.config, traffic=cell.traffic,
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            devices=devices[:cell.chips], t0=T0, workdir=workdir,
            checks=harness.Checks(), compiles=compiles, keep=args.keep,
            log=lambda msg: harness.log(msg, T0))
        ctx.log('cell %s: config %s, traffic %s, %d chip(s), seed %d, %gs, '
                'trace %d; devices %s'
                % (cell.name, cell.entry['config'], cell.entry['traffic'],
                   cell.chips, args.seed, args.seconds, args.trace,
                   ctx.devices))
        out = cell.driver().run(ctx)

        metrics = dict(out['end_to_end'])
        metrics['setup_s'] = out['setup_s']
        if args.trace:
            reported = layer_metrics(cell, out['run'])
        else:
            reported = {
                m['name']: {'value': float(metrics[m['name']]),
                            'unit': m['unit']}
                for m in cell.bench['end_to_end'] if cell.reports(m)}
        device = {'platform': devices[0].platform,
                  'kind': devices[0].device_kind, 'count': len(devices),
                  'memory_peak_bytes': out['memory_peak_bytes']}
        result = {'correct': ctx.checks.correct,
                  'attempted': int(out['attempted']),
                  'failed': int(out['failed']), 'metrics': reported,
                  'device': device}
        if args.trace:
            reduced = out['run'].get('trace')
            if reduced:
                from benchmark.reduce import trace as _trace
                device['busy_s'] = reduced['busy_s']
                device['window_s'] = reduced['window_s']
                result['breakdown'] = _trace.breakdown(reduced)
        if cell.overridden:
            result['overridden'] = list(args.set)
        ctx.log('end to end: %s' % json.dumps(metrics))
        ctx.log('compilations in this process: %d (%d served by the '
                'cache, %.1fs)' % (compiles.compiles, compiles.cache_hits,
                                   compiles.compile_s))
        if args.keep and args.trace:
            import shutil
            shutil.copytree(os.path.join(workdir, 'trace'), args.keep,
                            dirs_exist_ok=True)
    finally:
        harness.drop_workdir(workdir)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
