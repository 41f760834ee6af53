#!/usr/bin/env python3
"""The control of the comparisons, at a cell's own size, on the chip.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1 2 3

The control is the plain reference put in the program's place and computed
with float8 (e4m3) operands in every convolution and fully-connected
layer: the nearest precision below the bfloat16 the configurations state.
For each seed it prints the same numbers a run compares (and the same
function computes them), control against float32 reference. A limit has to
lie below the smallest of them and above the largest that sound runs of
the program print (PERF.md section 2). Run by hand when a limit is set or a
configuration is added; the benchmark's own runs do not run it.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

import numpy as np                                      # noqa: E402

from benchmark import compare_training, data, harness, loadgen, weights  # noqa: E402


def param_shapes(cfg, batch):
    os.environ['MXTPU_F16_AS_BF16'] = '1'
    params, aux, shapes = harness.symbol_shapes(
        harness.build_symbol(cfg), batch, cfg['input_shape'])
    return ({n: shapes[n] for n in params}, {n: shapes[n] for n in aux})


def training(cell, seed, devices):
    cfg, tr = cell.config, cell.traffic
    batch, W = int(tr['batch']), int(tr['steps_per_window'])
    params, _ = param_shapes(cfg, batch)
    start = {n: np.asarray(v) for n, v in weights.make_params(
        params, seed, cfg.get('init')).items()}
    pool, labels = data.image_pool(seed, int(tr['pool_rows']),
                                   tuple(cfg['input_shape']),
                                   int(cfg['classes']))
    offs = [data.batch_offset(k, len(pool), batch) for k in range(W + 3)]
    cut = lambda o: (pool[o:o + batch], labels[o:o + batch])   # noqa: E731
    batches = {'A': [cut(offs[0])], 'B': [cut(o) for o in offs[W:W + 3]]}
    model = cfg['reference'].split(':')[1]
    want = compare_training.follow(model, start, batches, W,
                                   cfg['optimizer'], devices)
    got = compare_training.follow(model, start, batches, W,
                                  cfg['optimizer'], devices, quant=True)
    return compare_training.gaps(got, want)[0]


def serving(cell, seed, devices):
    from benchmark.drivers import serve_http
    cfg, tr = cell.config, cell.traffic
    params, aux = param_shapes(cfg, int(tr['max_batch']))
    made = {n: np.asarray(v) for n, v in weights.make_params(
        dict(params, **aux), seed, cfg.get('init')).items()}
    pool = loadgen.body_pool(tr, seed, tuple(cfg['input_shape']))
    images = pool[:64]
    want = serve_http.reference_log_probs(cfg, made, images)
    got = serve_http.reference_log_probs(cfg, made, images, quant=True)
    return {'logits': serve_http.logit_gap(np.exp(got), want)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    ap.add_argument('--set', action='append', default=[])
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, args.set)
    import jax
    devices = jax.devices()[:cell.chips]
    kind = training if cell.traffic['driver'] == 'fit' else serving
    for seed in args.seeds:
        g = kind(cell, seed, devices)
        print('control %s seed %d on %s: %s'
              % (cell.name, seed, devices[0].device_kind,
                 ' '.join('%s %.6g' % kv for kv in sorted(g.items()))),
              flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
