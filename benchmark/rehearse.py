#!/usr/bin/env python3
"""The rehearsals that cost no chip time (on-chip-measurement guide,
section 2), for every cell of ``BENCHMARK.json``. Run before a chip call.

    python3 benchmark/rehearse.py [--cells a b] [--compile]

1. Each cell end to end on the CPU at a tiny size (small images, batch 8,
   4-step windows, three seconds), through the same ``run.py`` with its
   look for a chip skipped. The result names the device as ``cpu``, carries
   ``overridden`` and is never a measurement. ``correct`` may be false in
   bfloat16 at these sizes (batch statistics of eight 1x1 maps are
   ill-conditioned); control flow, files and the result line are what is
   rehearsed.
2. A four-chip cell runs the same on four virtual CPU devices
   (``--xla_force_host_platform_device_count=4``), which finds wrong
   meshes and sharding rules.
3. ``--compile``: at the real size, for a described ``v5e:2x2``. The fit
   window is built inside ``Module`` from the devices it is bound to and
   cannot be handed described devices (PR 23 met the same limit), so what
   is compiled is the benchmark's own device program, the plain
   reference's loss-and-gradient at the cell's batch (the largest program
   the benchmark adds; it must fit the chip's memory beside nothing else).
   About two minutes a configuration.

Each rehearsal is a child process: the TPU's library and the CPU device
count are per process.
"""
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

TINY = {
    'fit': ['traffic.batch=8', 'traffic.steps_per_window=4',
            'traffic.pool_rows=32'],
    'serve_http': ['traffic.rate_rows_s=40', 'traffic.body_pool_rows=40'],
}
# configurations whose reference takes any image size get small images
SMALL_IMAGE = {'resnet50_v1': ['config.input_shape=[3,32,32]',
                               'config.builder.kwargs.image_shape=3,32,32']}
# a configuration that needs its full image size gets a smaller batch
TINY_BATCH = {'inception_v3': ['traffic.batch=2', 'traffic.pool_rows=8']}

CHILD = r'''
import sys
sys.path.insert(0, %r)
from benchmark import run
sys.exit(run.main(%r, require_chip=False))
'''

COMPILE = r'''
import os, sys, json
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, %(repo)r)
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
os.environ["MXTPU_F16_AS_BF16"] = "1"
from benchmark import harness
from benchmark.reference import convnets
cell = harness.Cell(%(cell)r)
cfg, batch = cell.config, int(cell.traffic["batch"])
shape = tuple(cfg["input_shape"])
names, _, shapes = harness.symbol_shapes(harness.build_symbol(cfg), batch,
                                         shape)
shapes = {n: shapes[n] for n in names}
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = Mesh(np.array(topo.devices[:cell.chips]), ("dp",))
whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
p = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32, sharding=whole)
     for n, s in shapes.items()}
x = jax.ShapeDtypeStruct((batch,) + shape, jnp.float32, sharding=rows)
y = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rows)
model = cfg["reference"].split(":")[1]
c = convnets.loss_and_grad.lower(model, p, x, y, False).compile()
m = c.memory_analysis()
print("compiled %%s reference at batch %%d for %%d described v5e chip(s): "
      "%%.2f GB of temporaries, %%.2f GB of arguments a chip"
      %% (model, batch, cell.chips, m.temp_size_in_bytes / 1e9,
         m.argument_size_in_bytes / 1e9))
'''


def rehearse_cpu(cell_name, entry, driver):
    sets = TINY[driver] + SMALL_IMAGE.get(entry['config'], []) \
        + (TINY_BATCH.get(entry['config'], []) if driver == 'fit' else [])
    if entry['chips'] == 4:
        sets = [s for s in sets if not s.startswith('traffic.pool_rows')] \
            + ['traffic.pool_rows=64']
    ok = True
    for trace in (0, 1):
        argv = ['--workload', cell_name, '--seed', str(2 ** 31 + 7),
                '--seconds', '3', '--trace', str(trace)]
        for s in sets:
            argv += ['--set', s]
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        if entry['chips'] == 4:
            env['XLA_FLAGS'] = (env.get('XLA_FLAGS', '') +
                                ' --xla_force_host_platform_device_count=4')
        out = subprocess.run([sys.executable, '-c', CHILD % (REPO, argv)],
                             env=env, capture_output=True, text=True)
        last = out.stdout.strip().splitlines()[-1] if out.stdout.strip() \
            else ''
        try:
            result = json.loads(last)
        except ValueError:
            print('%s trace %d: NO RESULT (exit %d)\n%s'
                  % (cell_name, trace, out.returncode, out.stderr[-3000:]))
            ok = False
            continue
        keys = {'correct', 'attempted', 'failed', 'metrics', 'device'}
        fine = (out.returncode == 0 and keys <= set(result)
                and result['device']['platform'] == 'cpu'
                and result['metrics'] and 'overridden' in result)
        print('%s trace %d on the CPU (no measurement): %s; correct %s, '
              '%d attempted, metrics %s'
              % (cell_name, trace, 'ran' if fine else 'FAULTY',
                 result['correct'], result['attempted'],
                 sorted(result['metrics'])))
        ok = ok and fine
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--cells', nargs='*')
    ap.add_argument('--compile', action='store_true')
    args = ap.parse_args(argv)
    bench = json.load(open(os.path.join(REPO, 'BENCHMARK.json')))
    ok = True
    for w in bench['workloads']:
        if args.cells and w['name'] not in args.cells:
            continue
        traffic = json.load(open(os.path.join(
            HERE, 'traffic', w['traffic'] + '.json')))
        ok = rehearse_cpu(w['name'], w, traffic['driver']) and ok
        if args.compile and traffic['driver'] == 'fit':
            env = dict(os.environ, JAX_PLATFORMS='cpu')
            out = subprocess.run(
                [sys.executable, '-c',
                 COMPILE % {'repo': REPO, 'cell': w['name']}],
                env=env, capture_output=True, text=True)
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith('compiled')]
            print(line[0] if line else '%s: the compile for a described '
                  'v5e FAILED\n%s' % (w['name'], out.stderr[-3000:]))
            ok = ok and bool(line)
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
