#!/usr/bin/env python3
"""The knee of a serving cell: one warm server, several offered rates.

    python3 benchmark/tools/sweep_serve.py --workload resnet50_serve_open \
        --rates 50 100 200 400 --seconds 12 [--seed 1]

Run once on the chip when a serving cell is defined (the cell itself
offers a fixed rate and searches for nothing). The first window offers
one-row requests only, far below capacity, and gives the unloaded one-row
median that the cell's latency limit is four times of. Then each rate is
offered for ``--seconds`` with the cell's own mix; printed per rate:
offered and answered rows per second, p50 and p95 from the due time, how
late the generator ran, mean rows per dispatch. The knee is the highest
rate at which the answered rate keeps up with the offered one and p95
stays within a few medians; past it the queue grows through the window.
"""
import argparse
import os
import sys
import time
import types

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import harness, run      # noqa: E402


def swap_generator(driver, ctx, s, traffic):
    """A generator child for another mix, against the same warm server."""
    driver.quit_generator(s['child'])
    s['child'] = driver.spawn_generator(ctx, traffic, s['image_shape'],
                                        s['classes'])
    driver.await_ready(s['child'])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', type=float, nargs='+', required=True)
    ap.add_argument('--seconds', type=float, default=12.0)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--cpu', action='store_true',
                    help='rehearse on the CPU; no measurement')
    ap.add_argument('--set', action='append', default=[])
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, args.set)
    workdir = harness.make_workdir()
    try:
        run.prepare_environment(0, workdir)
        devices = run.take_devices(cell.chips, not args.cpu)
        driver = cell.driver()
        ctx = types.SimpleNamespace(
            cell=cell, config=cell.config, traffic=cell.traffic,
            seed=args.seed, seconds=args.seconds, trace=False,
            devices=devices[:1], t0=T0, workdir=workdir, keep=None,
            log=lambda msg: harness.log(msg, T0))
        limit = float(cell.traffic['latency_limit_ms'])
        timeout = float(cell.traffic['timeout_s'])
        s = driver.setup(ctx)
        try:
            # unloaded: single rows, 20 a second
            swap_generator(driver, ctx, s, dict(cell.traffic,
                                                rows={'1': 1.0}))
            rec = driver.window(ctx, s, 20.0, min(args.seconds, 8.0))
            e = driver.summarise(rec, min(args.seconds, 8.0), limit, timeout)
            print('sweep %s on %s: unloaded one-row requests: p50 %.3f ms, '
                  'p95 %.3f ms over %d requests'
                  % (cell.name, devices[0].device_kind, e['serve_p50_ms'],
                     e['serve_p95_ms'], e['requests']), flush=True)
            swap_generator(driver, ctx, s, cell.traffic)
            for rate in args.rates:
                s['batcher'].dispatch_log.clear()
                rec = driver.window(ctx, s, rate, args.seconds)
                e = driver.summarise(rec, args.seconds, limit, timeout)
                log = list(s['batcher'].dispatch_log)
                answered = sum(r['rows'] for r in rec['requests']
                               if r['ok']) / max(
                    max(r['done'] for r in rec['requests']), args.seconds)
                print('sweep rate %7.1f rows/s: offered %7.1f answered '
                      '%7.1f within-limit %7.1f | p50 %8.2f ms p95 %8.2f ms'
                      ' | late p95 %6.2f ms | %5.2f rows/dispatch | '
                      '%d requests %d failed'
                      % (rate, e['offered_rows'] / args.seconds, answered,
                         e['serve_samples_s'], e['serve_p50_ms'],
                         e['serve_p95_ms'], e['late_p95_ms'],
                         sum(d[0] for d in log) / max(1, len(log)),
                         e['requests'], e['failed']), flush=True)
        finally:
            driver.finish(s)
    finally:
        harness.drop_workdir(workdir)
    return 0


if __name__ == '__main__':
    sys.exit(main())
