"""NDArray — the mutable, async-dispatch tensor every layer passes around.

Reference: include/mxnet/ndarray.h:93-1242 + src/ndarray/ndarray.cc +
python/mxnet/ndarray/ndarray.py:150.

TPU-native design (SURVEY.md §7): the reference's NDArray is a shared Chunk
(storage handle + engine Var); all mutation is an engine push and reads
synchronize via WaitToRead. Here the backing store is an immutable
``jax.Array`` and "mutation" rebinds ``_data`` — JAX's async dispatch gives
the same caller-returns-immediately pipelining the threaded engine provided,
and ``wait_to_read()`` maps to ``block_until_ready()``. Write-after-read
hazards cannot exist (buffers are immutable), which deletes the entire
ThreadedVar dependency-queue machinery (threaded_engine.h:111-213) with no
loss of semantics.
"""
import functools
import itertools
import math
import os
import re
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from .. import autograd as _ag
from .. import random as _random
from ..base import MXNetError, np_dtype, normalize_attrs, numeric_types
from ..context import Context, current_context
from ..ops import registry as _reg

__all__ = ['NDArray', 'array', 'zeros', 'ones', 'empty', 'full', 'arange',
           'invoke', 'waitall', 'concatenate', 'moveaxis', 'onehot_encode',
           'imperative_invoke', 'from_jax', 'stack']


def waitall():
    """Block until all dispatched computation is done — a real barrier.

    Reference: MXNDArrayWaitAll / Engine::WaitForAll (engine.h:180).
    XLA devices execute programs in submission order, so dispatching a
    trivial program on each local device and fetching its result to
    the host drains everything queued before it (the device→host copy
    is the fence)."""
    import numpy as _np
    for dev in jax.local_devices():
        try:
            fence = jax.device_put(_np.zeros((), _np.float32), dev)
            _np.asarray(fence + 1)
        except Exception:  # device gone/unreachable: nothing to drain
            pass


class NDArray:
    """Multi-dimensional, context-bound array (reference ndarray.py:150)."""

    __slots__ = ('_data', '_ctx', '_grad', '_leaf', '_node', '_out_idx',
                 '_fresh_grad', '__weakref__')

    def __init__(self, data, ctx=None):
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self._grad = None
        self._leaf = None
        self._node = None
        self._out_idx = 0
        self._fresh_grad = True

    # pickling carries values only (host numpy + context), never tape or
    # device state — same contract as the reference's NDArray __reduce__
    # (python/mxnet/ndarray.py save/load path)
    def __getstate__(self):
        npy = np.asarray(self._data)
        if npy.dtype.name == 'bfloat16':
            return {'data': npy.astype(np.float32), 'ctx': self._ctx,
                    'bf16': True}
        return {'data': npy, 'ctx': self._ctx, 'bf16': False}

    def __setstate__(self, state):
        ctx = state['ctx']
        try:
            dev = ctx.jax_device()
        except MXNetError:
            dev = jax.devices()[0]  # no such device in this process
        dtype = jnp.bfloat16 if state.get('bf16') else state['data'].dtype
        self.__init__(_build(dev, state['data'], _narrowed(dtype)), ctx=ctx)

    # -- basic properties -------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        # np.dtype handles bfloat16 via ml_dtypes and compares equal to
        # jnp.bfloat16, so one uniform return type (str() -> 'bfloat16')
        return np.dtype(self._data.dtype)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return 'default'

    @property
    def grad(self):
        return self._grad

    @property
    def handle(self):
        """Opaque-handle compat: the backing jax.Array."""
        return self._data

    def __repr__(self):
        return '\n%s\n<NDArray %s @%s>' % (
            str(self.asnumpy()), 'x'.join(str(s) for s in self.shape), self._ctx)

    def __len__(self):
        if not self.shape:
            raise TypeError('len() of unsized object')
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError('The truth value of an NDArray with multiple '
                             'elements is ambiguous.')
        return bool(self.asscalar())

    # -- synchronization (engine semantics) -------------------------------
    def wait_to_read(self):
        """Reference ndarray.h:336 WaitToRead ≙ block_until_ready."""
        jax.block_until_ready(self._data)

    def wait_to_write(self):
        jax.block_until_ready(self._data)

    # -- host transfer ----------------------------------------------------
    def asnumpy(self):
        arr = np.asarray(self._data)
        if arr.dtype == jnp.bfloat16:
            arr = arr.astype(np.float32)
        if not arr.flags.writeable:
            # reference asnumpy() copies device->host: callers own the
            # result and may mutate it (e.g. the CustomOp examples do
            # y[i, l] -= 1 on a forward output); np.asarray over a
            # jax.Array is a read-only view of the device buffer
            arr = arr.copy()
        return arr

    def asscalar(self):
        if self.size != 1:
            raise ValueError('The current array is not a scalar')
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # -- copies / context movement ----------------------------------------
    def copy(self):
        return self.copyto(self._ctx)

    def copyto(self, other):
        """Reference ndarray.cc:497 CopyFromTo (engine copy op)."""
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other._ctx.jax_device())
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()), other)
        raise TypeError('copyto does not support type ' + str(type(other)))

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    def astype(self, dtype, copy=True):
        d = np_dtype(dtype)
        if not copy and self._data.dtype == d:
            return self
        return invoke('Cast', [self], {'dtype': str(dtype)})

    def tostype(self, stype):
        """Reference cast_storage: dense → row_sparse / csr containers."""
        if stype == 'default':
            return self
        from .sparse import cast_storage
        return cast_storage(self, stype)

    # -- autograd ---------------------------------------------------------
    def attach_grad(self, grad_req='write', stype=None):
        """Reference ndarray.py attach_grad → MXAutogradMarkVariables."""
        grad = NDArray(jnp.zeros_like(self._data), self._ctx)
        _ag.mark_variables([self], [grad], grad_req)
        self._fresh_grad = True

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _ag.backward([self], [out_grad] if out_grad is not None else None,
                     retain_graph, train_mode)

    # -- mutation ---------------------------------------------------------
    def _set_data(self, new_data, node=None, out_idx=0):
        self._data = new_data
        self._node = node
        self._out_idx = out_idx

    def __setitem__(self, key, value):
        # the new value is built where this array lives (its device, or
        # its sharding over a mesh), never on the process's default
        # device, which beside an mx.cpu() array is the chip
        here, dtype = self._data.sharding, self._data.dtype
        whole = key is None or key == slice(None)
        if isinstance(value, NDArray):
            value = value._data
            if whole:
                value = jnp.broadcast_to(value, self.shape).astype(dtype)
        elif whole and isinstance(value, numeric_types):
            value = _build(here, functools.partial(
                jnp.full, self.shape, value, dtype))
        else:
            value = _host_copy(value, dtype, self.shape if whole else None)
        if whole:
            self._set_data(jax.device_put(value, here))
        else:
            # .at[] makes its index arrays on the default device too
            with jax.default_device(_first_device(here)):
                self._set_data(self._data.at[key].set(value))

    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data
        out = self._data[key]
        res = NDArray(out, self._ctx)
        if _ag.is_recording() and (self._node is not None or self._leaf is not None):
            # record the slice so gradients flow through indexing
            return invoke('_slice_like_getitem', [self], {'key': _freeze_key(key)})
        return res

    # -- operator overloads (dispatch to registered ops, reference
    #    ndarray.py __add__ etc → broadcast_add/_plus_scalar) -------------
    def __add__(self, other):
        return _binary(self, other, 'broadcast_add', '_plus_scalar')

    def __radd__(self, other):
        return self.__add__(other)

    def __iadd__(self, other):
        out = _binary(self, other, 'broadcast_add', '_plus_scalar')
        self._set_data(out._data, out._node, out._out_idx)
        return self

    def __sub__(self, other):
        return _binary(self, other, 'broadcast_sub', '_minus_scalar')

    def __rsub__(self, other):
        return _scalar(self, other, '_rminus_scalar')

    def __isub__(self, other):
        out = self.__sub__(other)
        self._set_data(out._data, out._node, out._out_idx)
        return self

    def __mul__(self, other):
        return _binary(self, other, 'broadcast_mul', '_mul_scalar')

    def __rmul__(self, other):
        return self.__mul__(other)

    def __imul__(self, other):
        out = self.__mul__(other)
        self._set_data(out._data, out._node, out._out_idx)
        return self

    def __truediv__(self, other):
        return _binary(self, other, 'broadcast_div', '_div_scalar')

    def __rtruediv__(self, other):
        return _scalar(self, other, '_rdiv_scalar')

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._set_data(out._data, out._node, out._out_idx)
        return self

    def __mod__(self, other):
        return _binary(self, other, 'broadcast_mod', '_mod_scalar')

    def __rmod__(self, other):
        return _scalar(self, other, '_rmod_scalar')

    def __pow__(self, other):
        return _binary(self, other, 'broadcast_power', '_power_scalar')

    def __rpow__(self, other):
        return _scalar(self, other, '_rpower_scalar')

    def __neg__(self):
        return invoke('negative', [self], {})

    def __abs__(self):
        return invoke('abs', [self], {})

    def __eq__(self, other):
        return _binary(self, other, 'broadcast_equal', '_equal_scalar')

    def __ne__(self, other):
        return _binary(self, other, 'broadcast_not_equal', '_not_equal_scalar')

    def __gt__(self, other):
        return _binary(self, other, 'broadcast_greater', '_greater_scalar')

    def __ge__(self, other):
        return _binary(self, other, 'broadcast_greater_equal', '_greater_equal_scalar')

    def __lt__(self, other):
        return _binary(self, other, 'broadcast_lesser', '_lesser_scalar')

    def __le__(self, other):
        return _binary(self, other, 'broadcast_lesser_equal', '_lesser_equal_scalar')

    def __hash__(self):
        return id(self)

    # -- common method forms of ops ---------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = kwargs.get('shape', shape)
        return invoke('Reshape', [self], {'shape': tuple(shape)})

    def reshape_like(self, other):
        return invoke('reshape_like', [self, other], {})

    def broadcast_to(self, shape):
        return invoke('broadcast_to', [self], {'shape': tuple(shape)})

    def broadcast_axes(self, axis=(), size=()):
        return invoke('broadcast_axes', [self],
                      {'axis': (axis,) if isinstance(axis, int) else
                       tuple(axis),
                       'size': (size,) if isinstance(size, int) else
                       tuple(size)})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke('transpose', [self], {'axes': axes} if axes else {})

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return invoke('Flatten', [self], {})

    def expand_dims(self, axis):
        return invoke('expand_dims', [self], {'axis': axis})

    def squeeze(self, axis=None):
        return invoke('squeeze', [self], {'axis': axis})

    def swapaxes(self, dim1, dim2):
        return invoke('SwapAxis', [self], {'dim1': dim1, 'dim2': dim2})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke('SliceChannel', [self],
                      {'num_outputs': num_outputs, 'axis': axis,
                       'squeeze_axis': squeeze_axis})

    def slice(self, begin, end, step=None):
        return invoke('slice', [self], {'begin': tuple(begin), 'end': tuple(end),
                                        'step': tuple(step) if step else None})

    def slice_axis(self, axis, begin, end):
        return invoke('slice_axis', [self], {'axis': axis, 'begin': begin, 'end': end})

    def take(self, indices, axis=0, mode='clip'):
        return invoke('take', [self, indices], {'axis': axis, 'mode': mode})

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype='float32'):
        return invoke('one_hot', [self], {'depth': depth, 'on_value': on_value,
                                          'off_value': off_value, 'dtype': dtype})

    def pick(self, index, axis=-1, keepdims=False):
        return invoke('pick', [self, index], {'axis': axis, 'keepdims': keepdims})

    def clip(self, a_min, a_max):
        return invoke('clip', [self], {'a_min': a_min, 'a_max': a_max})

    def tile(self, reps):
        return invoke('tile', [self], {'reps': tuple(reps)})

    def repeat(self, repeats, axis=None):
        return invoke('repeat', [self], {'repeats': repeats, 'axis': axis})

    def flip(self, axis):
        return invoke('reverse', [self], {'axis': (axis,) if isinstance(axis, int) else tuple(axis)})

    def pad(self, mode, pad_width, constant_value=0):
        return invoke('Pad', [self], {'mode': mode, 'pad_width': tuple(pad_width),
                                      'constant_value': constant_value})

    def sort(self, axis=-1, is_ascend=True):
        return invoke('sort', [self], {'axis': axis, 'is_ascend': is_ascend})

    def argsort(self, axis=-1, is_ascend=True, dtype='float32'):
        return invoke('argsort', [self], {'axis': axis, 'is_ascend': is_ascend,
                                          'dtype': dtype})

    def topk(self, axis=-1, k=1, ret_typ='indices', is_ascend=False):
        return invoke('topk', [self], {'axis': axis, 'k': k, 'ret_typ': ret_typ,
                                       'is_ascend': is_ascend})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return invoke('dot', [self, other], {'transpose_a': transpose_a,
                                             'transpose_b': transpose_b})

    def as_jax(self):
        """Escape hatch to the raw jax.Array (TPU-native extension)."""
        return self._data


def _reduce_method(name):
    def method(self, axis=None, keepdims=False, **kwargs):
        attrs = {'axis': axis if axis is None or isinstance(axis, int)
                 else tuple(axis), 'keepdims': keepdims}
        attrs.update(kwargs)
        return invoke(name, [self], attrs)
    method.__name__ = name
    return method


def _unary_method(name):
    def method(self, **kwargs):
        return invoke(name, [self], kwargs)
    method.__name__ = name
    return method


for _n in ['sum', 'nansum', 'prod', 'nanprod', 'mean', 'max', 'min', 'norm',
           'argmax', 'argmin']:
    setattr(NDArray, _n, _reduce_method(_n))
for _n in ['abs', 'sign', 'round', 'rint', 'fix', 'floor', 'ceil', 'trunc',
           'sin', 'cos', 'tan', 'arcsin', 'arccos', 'arctan', 'degrees',
           'radians', 'sinh', 'cosh', 'tanh', 'arcsinh', 'arccosh', 'arctanh',
           'exp', 'expm1', 'log', 'log10', 'log2', 'log1p', 'sqrt', 'rsqrt',
           'cbrt', 'square', 'reciprocal', 'relu', 'sigmoid', 'softmax',
           'log_softmax', 'zeros_like', 'ones_like', 'sign']:
    setattr(NDArray, _n, _unary_method(_n))


# ---------------------------------------------------------------------------
# invoke — the imperative call path
# ---------------------------------------------------------------------------

def _freeze_key(key):
    """Make an indexing key hashable for the attr dict."""
    if isinstance(key, tuple):
        return tuple(_freeze_key(k) for k in key)
    if isinstance(key, slice):
        return ('__slice__', key.start, key.stop, key.step)
    if isinstance(key, (jnp.ndarray, np.ndarray)):
        return ('__array__', tuple(np.asarray(key).ravel().tolist()),
                tuple(key.shape))
    return key


def _thaw_key(key):
    if isinstance(key, tuple):
        if len(key) == 4 and key[0] == '__slice__':
            return slice(key[1], key[2], key[3])
        if len(key) == 3 and key[0] == '__array__':
            return np.array(key[1]).reshape(key[2]).astype(np.int64)
        return tuple(_thaw_key(k) for k in key)
    return key


@_reg.register('_slice_like_getitem', differentiable=True)
def _slice_like_getitem(attrs, x):
    return x[_thaw_key(attrs['key'])]


def _parent_entry(arr):
    if arr._node is not None:
        return (arr._node, arr._out_idx)
    if arr._leaf is not None:
        return (arr._leaf, 0)
    return (None, 0)


def invoke(op_name, inputs, attrs=None, out=None):
    """Execute a registered op imperatively.

    Reference call stack (SURVEY.md §3.1): generated fn → _imperative_invoke →
    MXImperativeInvoke → SetShapeType/SetDependency → PushFCompute →
    Engine::PushAsync. Here: cached jit closure + (if recording) jax.vjp;
    JAX's async dispatch replaces the engine push.
    """
    from .. import profiler as _profiler
    with _profiler.maybe_span(op_name):
        return _invoke_impl(op_name, inputs, attrs, out)


def _invoke_impl(op_name, inputs, attrs=None, out=None):
    op = _reg.get(op_name)
    _reg.record(op)   # execution-based coverage gate (conftest)
    # ctx is an op kwarg in the reference (SampleUniformParam etc. carry
    # a ctx field): it directs placement, never reaches the kernel, and
    # must not key the jit cache
    req_ctx = None
    if attrs and 'ctx' in attrs:
        attrs = dict(attrs)  # don't mutate the caller's (reusable) dict
        req_ctx = attrs.pop('ctx')
        if req_ctx is not None and not isinstance(req_ctx, Context):
            # string spelling 'cpu(0)' / 'gpu(1)' (the C-API kwarg form)
            m = re.match(r'(\w+)\((\d+)\)', str(req_ctx))
            req_ctx = Context(m.group(1), int(m.group(2))) if m else None
    attrs = normalize_attrs(attrs or {})
    if op.train_aware:
        attrs['__is_train__'] = _ag.is_training()

    arrays = [i._data for i in inputs]
    n_real = len(arrays)
    if op.needs_rng:
        arrays.append(_random.next_key())

    ctx = inputs[0]._ctx if inputs else (req_ctx or current_context())

    recording = _ag.is_recording() and op.differentiable and any(
        i._node is not None or i._leaf is not None for i in inputs)

    if op.host:
        # host ops (image codecs, legacy callback bridges) run python on
        # concrete arrays. When the tape needs a vjp they go through the
        # pure_callback bridge (traceable, legacy-backward-aware);
        # otherwise they are applied directly.
        f = (_reg.host_bridge(op, attrs) if recording
             else functools.partial(op.fn, attrs))
    else:
        f = _reg.jitted(op_name, attrs)
    node = None
    if recording:
        outs, vjp_fn = jax.vjp(f, *arrays)
        single = not isinstance(outs, (tuple, list))
        outs_t = (outs,) if single else tuple(outs)
        parents = [_parent_entry(i) for i in inputs]
        if op.needs_rng:
            parents.append((None, 0))
        node = _ag.record_op(vjp_fn, parents, len(outs_t), n_real,
                             op_info=(op_name, dict(attrs)))
        node.head_ids = [(o.shape, o.dtype) for o in outs_t]
    else:
        outs = f(*arrays)
        single = not isinstance(outs, (tuple, list))
        outs_t = (outs,) if single else tuple(outs)

    # write mutated aux outputs back into their input NDArrays
    # (reference: FMutateInputs / aux states, op_attr_types.h)
    for in_idx, out_idx in op.mutated(attrs).items():
        if out_idx < len(outs_t):
            inputs[in_idx]._data = outs_t[out_idx]

    if req_ctx is not None and not inputs:
        # honor the requested device for source ops (zero-input
        # samplers/initializers): data must live where _ctx says it does
        dev = req_ctx.jax_device()
        outs_t = tuple(jax.device_put(o, dev) for o in outs_t)

    n_vis = op.n_visible_outputs(attrs)
    results = []
    for i in range(n_vis):
        r = NDArray(outs_t[i], ctx)
        r._node = node
        r._out_idx = i
        results.append(r)

    if out is not None:
        outs_list = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs_list, results):
            # the reference rejects a shape-mismatched out buffer at
            # shape-inference time (SetShapeType); rebinding would
            # silently change dst.shape for downstream holders
            if dst._data.shape != src._data.shape:
                raise ValueError(
                    'out has shape %s but %s produced %s'
                    % (dst._data.shape, op_name, src._data.shape))
            dst._set_data(src._data, src._node, src._out_idx)
        return out

    if n_vis == 1:
        return results[0]
    return results


def imperative_invoke(op_name, *inputs, **kwargs):
    out = kwargs.pop('out', None)
    return invoke(op_name, list(inputs), kwargs, out)


def _binary(lhs, rhs, op_broadcast, op_scalar):
    if isinstance(rhs, NDArray):
        return invoke(op_broadcast, [lhs, rhs], {})
    if isinstance(rhs, numeric_types):
        return invoke(op_scalar, [lhs], {'scalar': float(rhs)})
    from .sparse import BaseSparseNDArray
    if isinstance(rhs, BaseSparseNDArray):
        # dense (op) sparse emits dense, like the reference's elemwise
        # dense/sparse fallbacks
        return invoke(op_broadcast, [lhs, rhs.tostype('default')], {})
    raise TypeError('type %s not supported' % str(type(rhs)))


def _scalar(lhs, rhs, op_scalar):
    return invoke(op_scalar, [lhs], {'scalar': float(rhs)})


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------

def from_jax(data, ctx=None):
    return NDArray(data, ctx)


def _first_device(where):
    """`where` if it is a device, else the first device of that sharding."""
    if isinstance(where, jax.Device):
        return where
    return min(where.device_set, key=lambda d: d.id)


def _narrowed(dtype):
    """`dtype` as jax will hold it: it silently truncates 64-bit dtypes
    when x64 is off, so ask for the narrow one up front and keep the
    conversion warning-free."""
    d = np.dtype(np_dtype(dtype))
    if not jax.config.jax_enable_x64:
        if d == np.int64:
            d = np.dtype(np.int32)
        elif d == np.float64:
            d = np.dtype(np.float32)
    return d


# Host memory of dead host arrays, by size in bytes, kept to be written
# again: {bytes: [(stamp, buffer), ...]}, the one idle longest first. First
# touch of new memory, not the copy into it, is what a large host array
# costs (a 77 MB batch on the v5e's host: 70 ms of page faults, 4-30 ms of
# copying; a window's 2.47 GB stack: 2.7 s new, 0.12 s written before;
# PERF.md, PRs 26 and 28), and what the allocator gives back to the system
# it has to fault in again for the next batch. The reference pools its
# storage for the same reason (src/storage/pooled_storage_manager.h).
_idle_buffers = {}
_idle_stamp = itertools.count()
_POOLED_FROM = 1 << 20      # smaller buffers cost under a millisecond new


def _idle_bytes():
    return sum(n * len(bufs) for n, bufs in list(_idle_buffers.items()))


def _idle_limit(idle):
    """How many bytes the idle buffers may hold, `idle` of which they hold
    now: half of what the machine could hand out if they held nothing. It
    is read when a buffer comes back, so a machine that has filled up
    meanwhile keeps less. Like the reference's pool, which keeps what it
    has until the device runs short, and unlike a share of the installed
    memory, it fits what a job really cycles through: a fit holds two
    windows' batches and a window's stack or two (7.4-10 GB of the 47 GB
    beside one v5e), all of them idle at an epoch's end."""
    try:
        with open('/proc/meminfo', 'rb') as f:
            for line in f:
                if line.startswith(b'MemAvailable:'):
                    return (int(line.split()[1]) * 1024 + idle) // 2
    except OSError:
        pass
    return os.sysconf('SC_PHYS_PAGES') * os.sysconf('SC_PAGE_SIZE') // 8


def _keep_for_reuse(raw):
    """Called when the last user of `raw`'s memory has let go of it (on
    whichever thread that happens, inside any allocation: so no lock, and
    each step one list or dict operation that may find another thread has
    been there first). Over the limit the buffers idle longest go, one at
    a time: a size nobody asks for any more cannot hold the room for
    good, and a working set that fits is never let go of as a whole."""
    limit = _idle_limit(_idle_bytes())
    if raw.nbytes > limit:
        return
    while _idle_bytes() + raw.nbytes > limit:
        waiting = [(bufs[0][0], n) for n, bufs in list(_idle_buffers.items())
                   if bufs]
        if not waiting:
            break
        n = min(waiting)[1]
        try:
            bufs = _idle_buffers[n]
            bufs.pop(0)
            if not bufs:
                del _idle_buffers[n]
        except (KeyError, IndexError):
            pass                # another thread took it meanwhile
    _idle_buffers.setdefault(raw.nbytes, []).append((next(_idle_stamp), raw))


def _host_buffer(shape, dtype):
    """``(array, reused)``: an uninitialised host array of `shape` and
    `dtype` in memory that nothing else refers to, 64-byte aligned, and
    whether that memory has been written before (an idle buffer of its
    size) or is new.

    Aligned, because ``jax.device_put`` onto a cpu device then adopts it
    as the array's own memory without a second copy; for a chip it is what
    the one transfer reads, behind the call. So the memory may be written
    again only when nothing reads it any more, and that is decided by
    reference, never by counting: when the last view of a large buffer is
    gone (the caller's, a cpu-backed array's, a transfer's that has ended)
    it goes to the idle buffers for the next array of its size."""
    dtype = np.dtype(dtype)
    nbytes = math.prod(shape) * dtype.itemsize
    try:
        raw, reused = _idle_buffers[nbytes + 64].pop()[1], True
    except (KeyError, IndexError):
        raw, reused = np.empty(nbytes + 64, np.uint8), False
    off = -raw.ctypes.data % 64
    # numpy hands a view of a view the first array as its base, and stops
    # at one whose own base is no array: so whatever reads this memory,
    # through whichever view of the result, holds `flat`, and nothing but
    # the list of idle buffers holds `raw`
    flat = np.frombuffer(memoryview(raw)[off:off + nbytes], dtype)
    if nbytes >= _POOLED_FROM:
        weakref.finalize(flat, _keep_for_reuse, raw).atexit = False
    return flat.reshape(shape), reused


def _host_copy(src, dtype, shape=None):
    """A copy of host data `src` as `dtype` (broadcast to `shape`), taken
    now, in memory of :func:`_host_buffer`'s.

    The copy is ours because the backend's is not taken at the call:
    ``jax.device_put`` of a numpy array returns at once and reads the
    source afterwards, or on the CPU backend keeps a 64-byte-aligned
    source as the array's own memory."""
    src = np.asarray(src)
    out, _ = _host_buffer(src.shape if shape is None else tuple(shape), dtype)
    np.copyto(out, src, casting='unsafe')
    return out


def _build(where, value, dtype=None):
    """The backing ``jax.Array`` of an NDArray on `where` (a device, or
    the sharding of an array that is being refilled). Every constructor
    builds here, by one rule: building an NDArray touches no device but
    the one its context names.

    `value` from the host (anything numpy reads) is converted with numpy
    on the host and crosses once, straight to `where`; for a cpu device
    nothing leaves the host. `value` as a function generates the array
    with jnp on `where` itself. jnp without this puts its result on the
    process's default device, which on a machine with a chip is the chip
    whatever the context says."""
    if callable(value):
        with jax.default_device(_first_device(where)):
            data = value()
    else:
        data = _host_copy(value, dtype)
    # for a generated array this commits it where it is and moves nothing
    return jax.device_put(data, where)


def array(source_array, ctx=None, dtype=None):
    """Reference ndarray.py:1988 mx.nd.array.

    The data is converted on the host and put on `ctx`'s device in one
    transfer; for a cpu context it never leaves the host. The values are
    copied at the call (the reference's _sync_copyfrom): the source may
    be written to as soon as this returns."""
    ctx = ctx if ctx is not None else current_context()
    keep_dtype = isinstance(source_array, (np.ndarray, NDArray))
    if isinstance(source_array, NDArray):
        # by way of the host: a view of a cpu array, a fetch from a chip
        src = np.asarray(source_array._data)
        if src.dtype == jnp.bfloat16:
            src = src.astype(np.float32)
    else:
        src = np.asarray(source_array)
    if dtype is None:
        # reference ndarray.py: python lists default to float32; numpy
        # arrays keep their dtype (64-bit narrowed: x64 stays off for TPU)
        if not keep_dtype:
            dtype = np.float32
        else:
            dtype = src.dtype
            if dtype == np.float64:
                dtype = np.float32
            elif dtype == np.int64:
                dtype = np.int32
    return NDArray(_build(ctx.jax_device(), src, _narrowed(dtype)), ctx)


def empty(shape, ctx=None, dtype='float32'):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype='float32', **kwargs):
    return full(shape, 0, ctx, dtype)


def ones(shape, ctx=None, dtype='float32', **kwargs):
    return full(shape, 1, ctx, dtype)


def full(shape, val, ctx=None, dtype='float32', out=None):
    ctx = ctx if ctx is not None else current_context()
    if isinstance(shape, int):
        shape = (shape,)
    data = _build(ctx.jax_device(),
                  lambda: jnp.full(shape, val, dtype=np_dtype(dtype)))
    if out is not None:
        out._set_data(data)
        return out
    return NDArray(data, ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype='float32'):
    ctx = ctx if ctx is not None else current_context()

    def make():
        arr = jnp.arange(start, stop, step, dtype=np_dtype(dtype))
        return jnp.repeat(arr, repeat) if repeat > 1 else arr
    return NDArray(_build(ctx.jax_device(), make), ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return invoke('Concat', list(arrays), {'dim': axis, 'num_args': len(arrays)})


def stack(*arrays, **kwargs):
    axis = kwargs.get('axis', 0)
    arrs = list(arrays[0]) if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)) else list(arrays)
    return invoke('stack', arrs, {'axis': axis, 'num_args': len(arrs)})


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination), tensor._ctx)


def onehot_encode(indices, out):
    depth = out.shape[1]
    res = invoke('one_hot', [indices], {'depth': depth})
    out._set_data(res._data)
    return out


def __getattr__(name):
    """Deep-import compat: the reference defines module-level helpers
    (multiply, maximum, imdecode, ...) in ndarray/ndarray.py itself;
    here they live on the package — forward lookups there."""
    if name.startswith('_'):
        raise AttributeError(name)
    import sys as _s
    pkg = _s.modules[__package__]
    if hasattr(pkg, name):
        return getattr(pkg, name)
    raise AttributeError('module %r has no attribute %r'
                         % (__name__, name))
