#!/usr/bin/env python3
"""Per-window losses of a ``fit_tokens`` cell at several learning rates,
on the chip: how a configuration's rate is chosen.

    python3 benchmark/tools/sweep_lr_tokens.py --workload <cell> --seed 1 \
        --rates 0.05 0.25 1.0 --windows 4

For each rate a new ``Module`` is trained from the same seeded parameters
on the same stream through ``Module.fit`` (the rate enters the window as
an array, so one compiled program serves them all through the compile
cache). A rate is sound if every window's loss is finite and each is below
the one before. Run by hand; the benchmark's own runs do not run it.
"""
import argparse
import gc
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

import numpy as np                                      # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--rates', type=float, nargs='+', required=True)
    ap.add_argument('--windows', type=int, default=4)
    ap.add_argument('--set', action='append', default=[])
    args = ap.parse_args(argv)
    os.environ['MXTPU_F16_AS_BF16'] = '1'
    from benchmark import data_lm, harness, weights_lm
    from benchmark.drivers import fit_tokens
    import mxnet_tpu as mx
    cell = harness.Cell(args.workload, args.set)
    cfg, tr = cell.config, cell.traffic
    batch, seq_len = int(tr['batch']), int(tr['seq_len'])
    W, opt = int(tr['steps_per_window']), cfg['optimizer']
    sym = fit_tokens.build_symbol(cfg)
    params, aux, shapes = fit_tokens.symbol_shapes(sym, batch, seq_len)
    made = weights_lm.make_params(shapes, args.seed)
    start = {n: np.asarray(v) for n, v in made.items()}
    del made
    pool = data_lm.token_pool(args.seed, int(tr['pool_tokens']),
                              int(cfg['vocab_size']))
    for rate in args.rates:
        it = fit_tokens.make_iter(mx, pool, batch, seq_len, W)
        it.plan(windows=args.windows)
        sums = []

        def note(param, sums=sums):
            ce = param.eval_metric.metrics[0]
            sums.append((float(ce.sum_metric), int(ce.num_inst)))

        mod = mx.mod.Module(sym, context=mx.tpu(0))
        mod.fit(it, eval_metric=['ce', 'acc'], kvstore=tr['kvstore'],
                optimizer=opt['name'],
                optimizer_params={
                    'learning_rate': rate,
                    'momentum': float(opt['momentum']),
                    'wd': float(opt['wd']),
                    'multi_precision': bool(opt['multi_precision'])},
                arg_params={n: mx.nd.array(start[n]) for n in params},
                aux_params={n: mx.nd.array(start[n]) for n in aux},
                batch_end_callback=note, num_epoch=1)
        s = np.array([0.0] + [a for a, _ in sums])
        n = np.array([0] + [b for _, b in sums])
        per = np.diff(s) / np.maximum(np.diff(n), 1)
        print('rate %g: per-window loss %s' % (rate, [
            '%.4f' % per[i * W:(i + 1) * W].mean()
            for i in range(len(per) // W)]), flush=True)
        del mod, it
        gc.collect()
    return 0


if __name__ == '__main__':
    sys.exit(main())
