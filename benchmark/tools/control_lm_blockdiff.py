#!/usr/bin/env python3
"""``control_lm_heads.py`` for a cell whose driver is
``fit_tokens_blockdiff``: the float8 control of the comparison at the
cell's own size, on the chip, with the plain reference the configuration's
file names, walked by ``fit_tokens_heads.follow`` on the steps the cell's
own iterator cuts (the noisy and the clean copy, the labels and the
weights from ``(seed, step)``).

    python3 benchmark/tools/control_lm_blockdiff.py --workload <cell> --seeds 1 2 3

Run by hand when a limit is set or checked; the benchmark's own runs do
not run it.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import compare_lm_training, data_lm, harness, weights_lm  # noqa: E402
from benchmark.drivers import fit_tokens_blockdiff  # noqa: E402
from benchmark.drivers import fit_tokens_heads as driver  # noqa: E402


def training(cell, seed, device):
    import mxnet_tpu as mx
    cfg, tr = cell.config, cell.traffic
    batch, rows = int(tr['batch']), 2 * int(tr['seq_len'])
    W = int(tr['steps_per_window'])
    ref = driver.bind(cfg)
    shapes = ref.param_shapes(cfg)
    start = weights_lm.make_params(shapes, seed)
    make_iter, _ = fit_tokens_blockdiff.iter_maker(seed, cfg, tr)
    it = make_iter(mx, data_lm.token_pool(seed, int(tr['pool_tokens']),
                                          int(cfg['vocab_size'])),
                   batch, rows, W)
    batches = {'A': [it.cut(0)], 'B': [it.cut(W + i) for i in range(3)]}

    def walk(quant, start):
        reference = driver.Reference(ref, cfg, shapes, (batch, rows),
                                     cfg['optimizer'], quant=quant)
        return driver.follow(reference, start, batches, W, cfg['optimizer'],
                             device)

    want = walk(False, dict(start))
    got = walk(True, start)
    g = compare_lm_training.gaps((got[0], got[2], got[3]),
                                 (want[0], want[2], want[3]))[0]
    g['pairs'] = compare_lm_training.pair_flips(got[1], want[1])[0]
    return g


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload)
    os.environ['MXTPU_F16_AS_BF16'] = '1'
    import jax
    device = jax.devices()[0]
    for seed in args.seeds:
        g = training(cell, seed, device)
        print('control %s seed %d on %s: %s'
              % (cell.name, seed, device.device_kind,
                 ' '.join('%s %.6g' % kv for kv in sorted(g.items()))),
              flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
