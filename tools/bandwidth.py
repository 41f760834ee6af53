#!/usr/bin/env python3
"""Collective-bandwidth measurement tool.

Reference: tools/bandwidth/measure.py (times kvstore push+pull of
ResNet/VGG-sized parameter sets across devices and reports GB/s).

TPU-native: the data plane is XLA collectives over the device mesh, so
this measures what actually carries gradients here — psum (allreduce),
all_gather and reduce_scatter over a 1-D mesh axis — plus the
kvstore-level push+pull round for parity with the reference's number.

    python tools/bandwidth.py --sizes 1e6,1e7 --iters 20
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure_collectives(sizes, iters, dtype='float32'):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(devs, ('x',))
    results = []
    for size in sizes:
        size = int(size)
        # per-shard blocks must themselves split n ways (psum_scatter)
        per_dev = max(size // (n * n), 1) * n
        x = jnp.ones((n * per_dev,), dtype=dtype)

        def allreduce(v):
            return jax.lax.psum(v, 'x')

        def allgather(v):
            return jax.lax.all_gather(v, 'x', tiled=True)

        def reducescatter(v):
            return jax.lax.psum_scatter(v, 'x', tiled=True)

        cases = {
            # bus bytes factors per the standard ring-collective cost model
            'psum': (shard_map(allreduce, mesh=mesh, in_specs=P('x'),
                               out_specs=P('x')), 2 * (n - 1) / n),
            'all_gather': (shard_map(allgather, mesh=mesh, in_specs=P('x'),
                                     out_specs=P(), check_vma=False),
                           (n - 1) / n),
            'reduce_scatter': (shard_map(reducescatter, mesh=mesh,
                                         in_specs=P('x'), out_specs=P('x')),
                               (n - 1) / n),
        }
        nbytes = x.size * x.dtype.itemsize
        for name, (fn, bus_factor) in cases.items():
            jfn = jax.jit(fn)
            jfn(x).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                out = jfn(x)
            out.block_until_ready()
            dt = (time.perf_counter() - t0) / iters
            gbps = nbytes * bus_factor / dt / 1e9
            results.append({'op': name, 'bytes': nbytes, 'time_ms': dt * 1e3,
                            'busbw_GBps': gbps})
            print('%-15s %10d B  %8.3f ms  %8.2f GB/s (bus)' %
                  (name, nbytes, dt * 1e3, gbps))
    return results


def measure_kvstore(sizes, iters, kv_type='device', label='kv_push_pull'):
    """Reference measure.py's actual protocol: init + timed push/pull."""
    import numpy as np
    import mxnet_tpu as mx

    kv = mx.kv.create(kv_type)
    results = []
    for size in sizes:
        size = int(size)
        arr = mx.nd.array(np.ones(size, np.float32))
        out = mx.nd.zeros((size,))
        kv.init(0, arr)
        kv.push(0, arr)
        kv.pull(0, out=out)
        out.wait_to_read()
        t0 = time.perf_counter()
        for _ in range(iters):
            kv.push(0, arr)
            kv.pull(0, out=out)
        out.wait_to_read()
        dt = (time.perf_counter() - t0) / iters
        gbps = size * 4 * 2 / dt / 1e9  # push + pull
        results.append({'op': label, 'bytes': size * 4,
                        'time_ms': dt * 1e3, 'GBps': gbps})
        print('%-15s %10d B  %8.3f ms  %8.2f GB/s' %
              (label, size * 4, dt * 1e3, gbps))
    return results


def measure_dist(sizes, iters, n_servers=1, timeout_s=600):
    """PS-tier bandwidth: spawn a real 1-worker/N-server TCP cluster via
    tools/launch.py and time dist_sync push+pull (the reference
    measure.py against its parameter servers). The cluster runs in its
    own process group so a wedged server can be killed wholesale; the
    worker's printed rows are parsed back into result dicts."""
    import signal
    import subprocess
    env = dict(os.environ)
    env.pop('DMLC_ROLE', None)
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)
    here = os.path.abspath(__file__)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(here), 'launch.py'),
         '-n', '1', '-s', str(n_servers), sys.executable, here,
         '--dist-worker', '--sizes', ','.join(str(int(s)) for s in sizes),
         '--iters', str(iters)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        # kill the WHOLE group: orphaned scheduler/server processes hold
        # the inherited pipes open and would hang a plain kill+communicate
        os.killpg(proc.pid, signal.SIGKILL)
        out, err = proc.communicate()
        sys.stderr.write((err or '')[-3000:])
        raise SystemExit('dist bandwidth run timed out')
    sys.stdout.write(out)
    if proc.returncode != 0:
        sys.stderr.write((err or '')[-3000:])
        raise SystemExit('dist bandwidth run failed')
    results = []
    for line in out.splitlines():
        parts = line.split()
        if len(parts) == 7 and parts[0] == 'dist_push_pull':
            results.append({'op': parts[0], 'bytes': int(parts[1]),
                            'time_ms': float(parts[3]),
                            'GBps': float(parts[5])})
    if not results:
        # a format drift in measure_kvstore's print must not silently
        # drop the dist tier from the report
        raise SystemExit('no dist rows parsed from worker output:\n'
                         + out[-2000:])
    return results


def measure_dist_worker(sizes, iters):
    return measure_kvstore(sizes, iters, kv_type='dist_sync',
                           label='dist_push_pull')


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument('--sizes', default='1e6,1e7',
                   help='comma-separated element counts')
    p.add_argument('--iters', type=int, default=20)
    p.add_argument('--dtype', default='float32',
                   choices=['float32', 'bfloat16'])
    p.add_argument('--kvstore', action='store_true',
                   help='also time kvstore push+pull (reference protocol)')
    p.add_argument('--dist', action='store_true',
                   help='also time the TCP parameter-server tier '
                        '(spawns a local 1-worker/1-server cluster)')
    p.add_argument('--dist-worker', action='store_true',
                   help=argparse.SUPPRESS)
    p.add_argument('--cpu-devices', type=int, default=0,
                   help='force an N-device virtual CPU mesh (the container '
                        'pre-pins jax to the TPU backend; env vars alone '
                        'are too late)')
    args = p.parse_args(argv)
    sizes = [float(s) for s in args.sizes.split(',')]
    if args.dist_worker:
        import jax
        jax.config.update('jax_platforms', 'cpu')
        return measure_dist_worker(sizes, args.iters)
    if args.cpu_devices:
        os.environ['XLA_FLAGS'] = (
            os.environ.get('XLA_FLAGS', '') +
            ' --xla_force_host_platform_device_count=%d' % args.cpu_devices)
        import jax
        jax.config.update('jax_platforms', 'cpu')
    import jax
    print('devices: %d x %s' % (len(jax.devices()),
                                jax.devices()[0].device_kind))
    results = measure_collectives(sizes, args.iters, args.dtype)
    if args.kvstore:
        results += measure_kvstore(sizes, args.iters)
    if args.dist:
        results += measure_dist(sizes, args.iters)
    return results


if __name__ == '__main__':
    main()
