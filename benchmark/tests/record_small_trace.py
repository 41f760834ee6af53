#!/usr/bin/env python3
"""Records ``small_v5e.xplane.pb``, the capture ``test_trace.py`` reads.
Run on the chip; writes to the directory given."""
import glob
import os
import shutil
import sys

import jax
import jax.numpy as jnp


def main(out_dir):
    def body(c, _):
        y = jax.lax.conv_general_dilated(
            c, jnp.ones((16, 16, 3, 3), jnp.bfloat16), (1, 1), 'SAME',
            dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
        return jnp.tanh(y) * 0.1, jnp.sum(y.astype(jnp.float32))

    f = jax.jit(lambda x: jax.lax.scan(body, x, None, length=3))
    x = jnp.ones((4, 16, 32, 32), jnp.bfloat16)
    jax.block_until_ready(f(x))
    tmp = os.path.join(out_dir, 'small_trace_tmp')
    jax.profiler.start_trace(tmp)
    for _ in range(4):
        jax.block_until_ready(f(x))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, 'plugins', 'profile', '*',
                                  '*.xplane.pb'))[0]
    shutil.copy(path, os.path.join(out_dir, 'small_v5e.xplane.pb'))
    shutil.rmtree(tmp)
    print('recorded', os.path.getsize(
        os.path.join(out_dir, 'small_v5e.xplane.pb')), 'bytes')


if __name__ == '__main__':
    main(sys.argv[1])
