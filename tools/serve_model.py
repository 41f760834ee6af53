#!/usr/bin/env python
"""Serve a saved checkpoint over HTTP with continuous batching.

The checkpoint -> endpoint path (docs/serving.md)::

    python tools/serve_model.py mymodel --epoch 3 --data-shape 3,224,224 \
        --context tpu:0
    python tools/serve_model.py mymodel --epoch 3 --data-shape 10 \
        --context cpu --port 8500 --max-batch 64 --max-wait-ms 3

Loads ``<prefix>-symbol.json`` + ``<prefix>-<epoch>.params``
(``Module.save_checkpoint`` artifacts) via ``Module.load``, binds for
inference, pre-compiles the bucket ladder (power-of-two batch shapes up
to --max-batch; warm across restarts through the persistent compile
cache, config.enable_compile_cache), and serves:

- ``POST /predict`` — JSON ``{"data": [[...], ...]}`` (or
  ``{"inputs": {...}}`` for multi-input graphs, or a raw .npy body);
  concurrent requests coalesce into shared padded device dispatches
  (queue -> coalesce -> dispatch -> split);
- ``GET /models`` / ``/healthz`` / ``/metrics`` — signature, probe,
  and the Prometheus ``serve.*`` family (latency p50/p99, queue depth,
  batch size, pad fraction, request/error counters).

Run with MXTPU_TELEMETRY=1 to light up the metrics; point
``tools/telemetry_watch.py`` at a telemetry endpoint (or this server's
/metrics via your scrape infra) to watch the serving line live.
"""
import argparse
import logging
import os
import signal
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _parse_shape(text):
    try:
        return tuple(int(d) for d in text.split(',') if d.strip())
    except ValueError:
        raise argparse.ArgumentTypeError(
            'shape must be comma-separated ints, e.g. 3,224,224')


def _parse_context(text):
    kind, _, dev_id = text.partition(':')
    if kind not in ('cpu', 'tpu') or (dev_id and not dev_id.isdigit()):
        raise argparse.ArgumentTypeError(
            'context must be cpu, cpu:N, tpu or tpu:N (got %r)' % text)
    return kind, int(dev_id or 0)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='Serve a Module checkpoint over HTTP with dynamic '
                    'batching over pre-compiled bucketed batch shapes '
                    '(docs/serving.md).')
    ap.add_argument('prefix', help='checkpoint prefix '
                    '(<prefix>-symbol.json, <prefix>-NNNN.params)')
    ap.add_argument('--epoch', type=int, default=0,
                    help='checkpoint epoch to load (default 0)')
    ap.add_argument('--data-shape', type=_parse_shape, required=True,
                    action='append', dest='data_shapes',
                    help='per-example input shape WITHOUT the batch dim, '
                         'e.g. 3,224,224 (repeat for multi-input graphs, '
                         'in --data-name order)')
    ap.add_argument('--data-name', action='append', dest='data_names',
                    help='input name(s), default "data"')
    ap.add_argument('--port', type=int, default=8500,
                    help='HTTP port (0 = OS-assigned ephemeral, printed '
                         'at startup; default 8500)')
    ap.add_argument('--max-batch', type=int, default=None,
                    help='largest batch bucket (default '
                         'MXTPU_SERVE_MAX_BATCH)')
    ap.add_argument('--max-wait-ms', type=float, default=None,
                    help='batcher coalescing deadline (default '
                         'MXTPU_SERVE_MAX_WAIT_MS)')
    ap.add_argument('--context', required=True, type=_parse_context,
                    help='device to serve from, named and not guessed: '
                         'tpu:0, tpu:1, ... or cpu (a device that is not '
                         'there is an error)')
    ap.add_argument('--no-warmup', action='store_true',
                    help='skip pre-compiling the bucket ladder (first '
                         'requests then pay the compiles)')
    args = ap.parse_args(argv)

    names = args.data_names or ['data']
    if len(names) != len(args.data_shapes):
        ap.error('--data-name count (%d) must match --data-shape count '
                 '(%d)' % (len(names), len(args.data_shapes)))

    logging.basicConfig(level=logging.INFO,
                        format='%(asctime)s %(message)s')
    import mxnet_tpu as mx
    from mxnet_tpu.serving import ServingEngine, DynamicBatcher
    from mxnet_tpu.serving.http import start_server

    ctx = mx.Context(*args.context)
    engine = ServingEngine.from_checkpoint(
        args.prefix, args.epoch,
        data_shapes=list(zip(names, args.data_shapes)),
        context=ctx, max_batch=args.max_batch)
    if not args.no_warmup:
        engine.warmup()
    server = start_server(engine,
                          DynamicBatcher(engine,
                                         max_wait_ms=args.max_wait_ms),
                          port=args.port)
    print('serving %s on port %d from %s (buckets %s)'
          % (engine.name, server.port, ctx.jax_device(), engine.buckets),
          flush=True)

    # an Event has no check-then-wait window: a SIGTERM landing at any
    # point sets it and wait() returns — never a signal consumed just
    # before a pause() that then blocks forever
    import threading
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


if __name__ == '__main__':
    sys.exit(main())
