#!/usr/bin/env python3
"""The control of a ``fit_tokens`` cell's comparison, at the cell's own
size, on the chip (``control.py``'s way, for the decoder reference).

    python3 benchmark/tools/control_lm.py --workload <cell> --seeds 1 2 3

The control is the plain reference put in the program's place and computed
with float8 (e4m3) operands in every matrix product (projections, scores,
values, experts, router, head): the nearest precision below the bfloat16
the configuration states. For each seed it prints the numbers a run
compares (the same function computes them), control against float32
reference, and the routing flips of the control's router. A limit has to
lie below the smallest of them and above the largest that sound runs of
the program print (PERF.md section 2). Run by hand when a limit is set;
the benchmark's own runs do not run it.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

import numpy as np                                      # noqa: E402

from benchmark import compare_lm_training, data_lm, harness, weights_lm  # noqa: E402
from benchmark.reference import laguna                  # noqa: E402


def training(cell, seed, device):
    cfg, tr = cell.config, cell.traffic
    batch, seq_len = int(tr['batch']), int(tr['seq_len'])
    W = int(tr['steps_per_window'])
    made = weights_lm.make_params(laguna.param_shapes(cfg), seed)
    start = {n: np.asarray(v) for n, v in made.items()}
    del made
    pool = data_lm.token_pool(seed, int(tr['pool_tokens']),
                              int(cfg['vocab_size']))

    def cut(k):
        return data_lm.cut(pool, k, batch, seq_len)

    batches = {'A': [cut(0)], 'B': [cut(W + i) for i in range(3)]}
    want = compare_lm_training.follow(cfg, dict(start), batches, W,
                                      cfg['optimizer'], device)
    got = compare_lm_training.follow(cfg, start, batches, W,
                                     cfg['optimizer'], device, quant=True)
    g = compare_lm_training.gaps((got[0], got[2], got[3]),
                                 (want[0], want[2], want[3]))[0]
    g['pairs'] = compare_lm_training.pair_flips(got[1], want[1])[0]
    return g


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    ap.add_argument('--set', action='append', default=[])
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, args.set)
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
