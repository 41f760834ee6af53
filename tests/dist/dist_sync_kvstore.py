"""Distributed KVStore sync-mode invariants, run as one of N workers.

Reference: tests/nightly/dist_sync_kvstore.py:28-80 — exact-arithmetic
push/pull checks across real worker/server processes (launched by
tools/launch.py), including big-array striping and row_sparse keys.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

# workers of the local launcher share one host: the CPU platform, pinned
os.environ['JAX_PLATFORMS'] = 'cpu'

import mxnet_tpu as mx  # noqa: E402

shape = (3, 3)
big_shape = (700, 700)  # > 1 MB of float32 → striped over all servers

keys = ['3', '5', '7']
big_key = '99'
rsp_key = '11'
rsp_shape = (40, 4)


def check(a, b, msg):
    if not np.allclose(a, b, rtol=1e-5, atol=1e-6):
        raise AssertionError('%s: max|diff|=%g'
                             % (msg, float(np.abs(a - b).max())))


def main():
    kv = mx.kv.create('dist_sync')
    nw = kv.num_workers
    my_rank = kv.rank

    for k in keys:
        kv.init(k, mx.nd.ones(shape))
    kv.init(big_key, mx.nd.ones(big_shape))
    kv.init(rsp_key, mx.nd.zeros(rsp_shape))

    # --- no-optimizer sync push: stored value becomes the merged sum ----
    for it in range(3):
        scale = it + 1
        for k in keys:
            kv.push(k, mx.nd.ones(shape) * scale)
        kv.push(big_key, mx.nd.ones(big_shape) * scale)
        out = mx.nd.zeros(shape)
        for k in keys:
            kv.pull(k, out=out)
            check(out.asnumpy(), np.full(shape, scale * nw, np.float32),
                  'sync merge key %s iter %d' % (k, it))
        big_out = mx.nd.zeros(big_shape)
        kv.pull(big_key, out=big_out)
        check(big_out.asnumpy(),
              np.full(big_shape, scale * nw, np.float32),
              'striped big key iter %d' % it)

    # --- server-side Test optimizer: weight += rescale * merged ---------
    rate = 2.0
    kv.set_optimizer(mx.optimizer.create('test', rescale_grad=rate))
    base = {}
    out = mx.nd.zeros(shape)
    for k in keys:
        kv.pull(k, out=out)
        base[k] = out.asnumpy().copy()
    kv.barrier()
    for k in keys:
        kv.push(k, mx.nd.ones(shape))
    for k in keys:
        kv.pull(k, out=out)
        check(out.asnumpy(), base[k] + rate * nw,
              'server optimizer key %s' % k)

    # --- row_sparse push/pull -------------------------------------------
    rows = np.array([1 + my_rank, 10, 30], np.int64)
    vals = np.ones((len(rows),) + rsp_shape[1:], np.float32)
    g = mx.nd.sparse.row_sparse_array((vals, rows), shape=rsp_shape)
    kv.push(rsp_key, g)
    expected = np.zeros(rsp_shape, np.float32)
    for r in range(nw):
        for row in (1 + r, 10, 30):
            expected[row] += rate  # Test optimizer applied to merged rows
    rid = mx.nd.array(np.arange(rsp_shape[0]))
    rsp_out = mx.nd.sparse.row_sparse_array(
        (np.zeros((1,) + rsp_shape[1:], np.float32),
         np.array([0], np.int64)), shape=rsp_shape)
    kv.row_sparse_pull(rsp_key, out=rsp_out, row_ids=rid)
    check(rsp_out.tostype('default').asnumpy(), expected, 'row_sparse')

    # --- failure detection (kvstore.h get_num_dead_node) ----------------
    # every node heartbeats; nothing is dead at a generous timeout
    assert kv.num_dead_node(node_id=6, timeout=60) == 0, \
        'live nodes reported dead'
    # a 0-second timeout marks anything without a *just-now* beat dead;
    # only assert it doesn't crash and stays within the node count
    n_dead = kv.num_dead_node(node_id=6, timeout=1e-9)
    assert 0 <= n_dead <= nw + int(os.environ.get('DMLC_NUM_SERVER', 1))

    kv.barrier()
    print('worker %d/%d: all dist_sync invariants passed' % (my_rank, nw),
          flush=True)


if __name__ == '__main__':
    main()
