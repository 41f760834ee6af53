"""Runtime kernel compilation — the TPU answer to mx.rtc.

Reference: python/mxnet/rtc.py (Rtc — user writes a CUDA kernel body in
a python string, NVRTC compiles it at runtime, the kernel runs on
NDArrays) over src/common/mxrtc.cc.

On TPU the runtime-compilation engine is XLA itself, and the
user-facing kernel language is Pallas. :class:`Rtc` keeps the
reference's shape — (name, inputs, outputs, kernel-source) in,
callable-on-NDArrays out — but the source is a python/Pallas kernel
body instead of CUDA C. Two source forms are accepted:

- a *jnp expression body*: python statements that read the input names
  and assign each output name, traced and jit-compiled by XLA
  (replaces the common "elementwise CUDA one-liner" use of mx.rtc);
- a *pallas kernel*: a ``def kernel(in_ref, ..., out_ref, ...)`` body
  using ``pl.load/pl.store``-style Ref ops, lowered by pallas_call
  (interpret mode off-TPU).

Security note: like the reference, this executes user-supplied source
in-process. It is a developer tool, not an untrusted-input boundary.
"""
import textwrap

import jax
import jax.numpy as jnp

from .ndarray.ndarray import NDArray

__all__ = ['Rtc']


class Rtc:
    """Compile a kernel from source at runtime and run it on NDArrays.

    Mirrors reference rtc.py:24 — ``name``/``inputs``/``outputs`` have
    the same meaning; ``kernel`` is python (jnp or pallas) source.
    """

    def __init__(self, name, inputs, outputs, kernel, mode='jnp'):
        if mode not in ('jnp', 'pallas'):
            raise ValueError("mode must be 'jnp' or 'pallas'")
        self.name = name
        self._in_names = [i[0] for i in inputs]
        self._out_names = [o[0] for o in outputs]
        self._out_shapes = [tuple(o[1].shape) for o in outputs]
        self._out_dtypes = [o[1].dtype for o in outputs]
        self._mode = mode
        self._source = kernel
        self._fn = self._compile(kernel)

    def _compile(self, kernel):
        src = textwrap.dedent(kernel)
        if self._mode == 'jnp':
            # wrap the body into a function of the declared inputs that
            # returns the declared outputs (the XLA analog of NVRTC
            # decorating the CUDA body with the kernel signature)
            body = textwrap.indent(src, '    ')
            fn_src = 'def %s(%s):\n%s\n    return (%s,)' % (
                self.name, ', '.join(self._in_names), body,
                ', '.join(self._out_names))
            env = {'jnp': jnp, 'jax': jax}
            exec(compile(fn_src, '<rtc:%s>' % self.name, 'exec'), env)
            return jax.jit(env[self.name])
        # pallas mode: source must define `def kernel(*refs)` over
        # input refs then output refs
        from jax.experimental import pallas as pl
        env = {'jnp': jnp, 'jax': jax, 'pl': pl}
        exec(compile(src, '<rtc:%s>' % self.name, 'exec'), env)
        if 'kernel' not in env:
            raise ValueError("pallas-mode source must define "
                             "'def kernel(...)'")
        kern = env['kernel']
        out_spec = [jax.ShapeDtypeStruct(s, d)
                    for s, d in zip(self._out_shapes, self._out_dtypes)]
        from .ops.pallas_kernels import run_kernel

        def run(*arrays):
            # compiled where the inputs live on a TPU, interpreted elsewhere
            outs = run_kernel(
                lambda interpret: pl.pallas_call(
                    kern, out_shape=out_spec, interpret=interpret),
                *arrays)
            return outs if isinstance(outs, (tuple, list)) else (outs,)
        return jax.jit(run)

    def push(self, inputs, outputs, grid_dims=None, block_dims=None):
        """Run the kernel (reference rtc.py push; grid/block dims are
        accepted for API compatibility — XLA/pallas choose the real
        launch geometry)."""
        if len(inputs) != len(self._in_names):
            raise ValueError('expected %d inputs' % len(self._in_names))
        if len(outputs) != len(self._out_names):
            raise ValueError('expected %d outputs' % len(self._out_names))
        arrays = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
                  for x in inputs]
        res = self._fn(*arrays)
        for out, r in zip(outputs, res):
            out._data = r.astype(out._data.dtype).reshape(out.shape)
        return outputs
