"""The open-loop load generator of the serving cells: one general
generator that reads a traffic file's parameters. It never imports JAX,
so it can run as a child of the process that holds the chip.

Traffic file keys it reads: ``rows`` ({rows per request: share}),
``rate_rows_s`` (offered rows per second), ``arrival_cv`` (coefficient of
variation of the gamma inter-arrival times; 1 is Poisson),
``arrival_seed`` (fixes the *set* of gaps), ``body_variants``,
``body_pool_rows``, ``client_threads``, ``timeout_s``.

Every seed gets the same cycle of (gap, size) pairs, fixed by the traffic
file's ``arrival_seed``, and starts at another point of it (and draws other
images): the offered work, and which bursts meet which sizes, do not change
from seed to seed, only the order in which they come.

As a module: ``schedule`` and ``bodies`` (the driver uses them to know
what was sent). As a program (the child): builds the bodies, prints
``READY``, waits for ``GO <port>`` on its standard input, sends the
schedule over loopback HTTP as ``application/x-npy`` float32 bodies, one
connection per request as ``tools/serve_model.py``'s clients do, times each
request from the instant it was *due*, checks every answer's shape and
that its rows sum to 1, and writes one JSON file of results.
"""
import argparse
import http.client
import io
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def schedule(traffic, seed, seconds):
    """[(due seconds, rows)] for a window of `seconds`."""
    sizes = sorted((int(r), float(p)) for r, p in traffic['rows'].items())
    mean_rows = sum(r * p for r, p in sizes)
    n = max(1, int(round(float(traffic['rate_rows_s']) / mean_rows
                         * seconds)))
    # the multiset of sizes: exact shares, the remainder to the commonest
    counts = [int(p * n) for _, p in sizes]
    counts[max(range(len(sizes)), key=lambda i: sizes[i][1])] += \
        n - sum(counts)
    rows = np.repeat([r for r, _ in sizes], counts)
    # the multiset of gaps: gamma with the stated cv, fixed by the
    # traffic's own seed, scaled to fill the window exactly
    cv = float(traffic['arrival_cv'])
    fixed = np.random.Generator(np.random.PCG64(int(traffic['arrival_seed'])))
    gaps = fixed.gamma(1.0 / cv ** 2, cv ** 2, n)
    gaps *= seconds / gaps.sum()
    fixed.shuffle(rows)
    # the run's seed only says where in that fixed cycle the window starts:
    # the same bursts meet the same sizes for every seed, in another order
    shift = int(seed) % n
    rows, gaps = np.roll(rows, -shift), np.roll(gaps, -shift)
    due = np.cumsum(gaps) - gaps[0]
    return [(float(t), int(r)) for t, r in zip(due, rows)]


def body_pool(traffic, seed, image_shape):
    rng = np.random.Generator(np.random.PCG64(int(seed) + 1))
    return rng.standard_normal(
        (int(traffic['body_pool_rows']),) + tuple(image_shape),
        dtype=np.float32)


def body_rows(pool, rows, variant):
    """The images of variant `variant` of a `rows`-row request."""
    off = (variant * rows) % (len(pool) - rows + 1)
    return pool[off:off + rows]


def variant_of(index, traffic):
    return index % int(traffic['body_variants'])


def bodies(traffic, seed, image_shape):
    """{(rows, variant): npy bytes}."""
    pool = body_pool(traffic, seed, image_shape)
    out = {}
    for r in traffic['rows']:
        for v in range(int(traffic['body_variants'])):
            buf = io.BytesIO()
            np.save(buf, body_rows(pool, int(r), v))
            out[(int(r), v)] = buf.getvalue()
    return out


def sample_ids(plan, seed, count):
    """Which requests' answers are kept for the comparison: a seeded
    sample with the largest request in it."""
    rng = np.random.Generator(np.random.PCG64(int(seed) + 2))
    ids = set(int(i) for i in rng.choice(len(plan), min(count, len(plan)),
                                         replace=False))
    ids.add(max(range(len(plan)), key=lambda i: plan[i][1]))
    return sorted(ids)


def send_one(port, i, rows, body, classes, timeout, keep):
    rec = {'id': str(i), 'rows': rows, 'ok': False, 'status': 0}
    rec['sent'] = time.perf_counter()
    try:
        conn = http.client.HTTPConnection('127.0.0.1', port, timeout=timeout)
        try:
            conn.request('POST', '/predict', body=body, headers={
                'Content-Type': 'application/x-npy',
                'X-Request-Id': str(i)})
            resp = conn.getresponse()
            raw = resp.read()
            rec['status'] = resp.status
        finally:
            conn.close()
        rec['done'] = time.perf_counter()
        if rec['status'] == 200:
            out = np.asarray(json.loads(raw)['outputs'][0], np.float32)
            rec['ok'] = bool(out.shape == (rows, classes)
                             and np.all(np.abs(out.sum(axis=1) - 1.0) < 1e-2))
            if keep:
                rec['answer'] = out.tolist()
    except (OSError, ValueError, KeyError, http.client.HTTPException) as e:
        rec['done'] = time.perf_counter()
        rec['error'] = repr(e)
    return rec


def offer(port, plan, made, traffic, classes, keep_ids):
    """Send `plan` on its schedule; returns the per-request records with
    times relative to the start."""
    timeout = float(traffic['timeout_s'])
    keep_ids = set(keep_ids)
    records = [None] * len(plan)
    lock = threading.Lock()

    def work(i, due, rows, t0):
        body = made[(rows, variant_of(i, traffic))]
        rec = send_one(port, i, rows, body, classes, timeout, i in keep_ids)
        rec['due'] = due
        rec['sent'] -= t0
        rec['done'] -= t0
        with lock:
            records[i] = rec

    with ThreadPoolExecutor(int(traffic['client_threads'])) as pool:
        t0 = time.perf_counter()
        futures = []
        for i, (due, rows) in enumerate(plan):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(work, i, due, rows, t0))
        for f in futures:
            f.result()
    return records


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--traffic', required=True,
                    help='JSON file: the traffic as the run uses it')
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--image-shape', required=True)
    ap.add_argument('--classes', type=int, required=True)
    ap.add_argument('--keep', type=int, default=12)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)
    shape = tuple(int(v) for v in args.image_shape.split(','))
    made = bodies(traffic, args.seed, shape)
    print('READY', flush=True)
    for line in sys.stdin:
        word = line.split()
        if not word or word[0] == 'QUIT':
            break
        # GO <port> <rate_rows_s> <seconds> <out file>: one window
        port, rate, seconds, out = (int(word[1]), float(word[2]),
                                    float(word[3]), word[4])
        window = dict(traffic, rate_rows_s=rate)
        plan = schedule(window, args.seed, seconds)
        keep = sample_ids(plan, args.seed, args.keep)
        records = offer(port, plan, made, window, args.classes, keep)
        with open(out, 'w') as f:
            json.dump({'requests': records, 'kept': keep}, f)
        print('DONE', flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
