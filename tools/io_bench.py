"""ImageRecordIter streaming-scale bench .

Generates a synthetic JPEG .rec of the requested size, then streams it
through ImageRecordIter with full augmentation, reporting throughput
(img/s, MB/s) and the resident-set delta — which must stay flat (the
round-3 eager loader was O(dataset) host memory).

    python tools/io_bench.py --gb 2.5 --batch 32 --threads 4

Prints one JSON line. The 'rss_delta_mb' field is the peak RSS growth
between the first and last measurement window; 'passes' asserts it is
bounded by a few batch-queues, not the dataset.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rss_mb():
    with open('/proc/self/status') as f:
        for line in f:
            if line.startswith('VmRSS'):
                return int(line.split()[1]) / 1024.0
    return 0.0


def build_rec(path, target_bytes, hw):
    from mxnet_tpu.recordio import MXRecordIO, IRHeader, pack_img
    rng = np.random.RandomState(0)
    rec = MXRecordIO(path, 'w')
    # a handful of distinct JPEGs cycled with distinct headers: real
    # decode work per record without hours of synthesis
    protos = [(rng.rand(hw, hw, 3) * 255).astype(np.uint8)
              for _ in range(64)]
    from mxnet_tpu.recordio import pack  # noqa: F401 (doc pointer)
    payloads = [pack_img(IRHeader(0, float(i % 10), i, 0), protos[i],
                         quality=90, img_fmt='.jpg')
                for i in range(64)]
    n, written = 0, 0
    t0 = time.perf_counter()
    while written < target_bytes:
        rec.write(payloads[n % 64])
        written += len(payloads[n % 64]) + 12
        n += 1
    rec.close()
    print('[io_bench] wrote %d records, %.2f GB in %.1fs'
          % (n, written / 1e9, time.perf_counter() - t0), file=sys.stderr)
    return n, written


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--gb', type=float, default=2.0)
    ap.add_argument('--batch', type=int, default=32)
    ap.add_argument('--threads', type=int, default=4)
    ap.add_argument('--hw', type=int, default=256)
    ap.add_argument('--crop', type=int, default=224)
    ap.add_argument('--path', default='/tmp/io_bench.rec')
    ap.add_argument('--keep', action='store_true')
    args = ap.parse_args()

    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    from mxnet_tpu import io as mio

    if not os.path.exists(args.path):
        n, nbytes = build_rec(args.path, args.gb * 1e9, args.hw)
    else:
        nbytes = os.path.getsize(args.path)
        n = None
    rss0 = _rss_mb()
    it = mio.ImageRecordIter(
        path_imgrec=args.path, data_shape=(3, args.crop, args.crop),
        batch_size=args.batch, shuffle=True, rand_crop=True,
        rand_mirror=True, preprocess_threads=args.threads,
        scale=1.0 / 255, mean_r=0.5, mean_g=0.5, mean_b=0.5)
    rss_after_open = _rss_mb()
    imgs = 0
    peak = rss_after_open
    t0 = time.perf_counter()
    for b in it:
        imgs += args.batch
        if imgs % (args.batch * 64) == 0:
            peak = max(peak, _rss_mb())
    dt = time.perf_counter() - t0
    peak = max(peak, _rss_mb())
    out = {
        'metric': 'image_record_stream',
        'value': round(imgs / dt, 1),
        'unit': 'images/sec',
        'mb_per_s': round(nbytes / 1e6 / dt, 1),
        'images': imgs,
        'file_gb': round(nbytes / 1e9, 2),
        'threads': args.threads,
        'rss_open_mb': round(rss_after_open - rss0, 1),
        'rss_delta_mb': round(peak - rss_after_open, 1),
        'passes': bool(peak - rss_after_open < 2048),
    }
    print(json.dumps(out))
    if not args.keep:
        os.unlink(args.path)


if __name__ == '__main__':
    main()
