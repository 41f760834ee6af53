"""Python side of the C ABI bridge.

Reference: include/mxnet/c_api.h (146 MXNET_DLL entry points over opaque
handles) and src/c_api/c_api.cc / c_api_symbolic.cc / c_api_executor.cc.

Design (TPU-native): the reference's C API fronts a C++ core; here the
core is the JAX/XLA runtime hosted by CPython, so the C ABI
(src/c_api.cc) embeds the interpreter and delegates each entry point to
one helper in this module. Handles crossing the ABI are CPython object
pointers (ref-counted by the C layer); device compute still runs through
XLA, so nothing is lost relative to the reference's dispatch path — the
C frontier is control-plane only, exactly like the reference's (its data
plane is cudnn/mshadow kernels; ours is XLA executables).

Helpers accept/return only simple types (int/float/str/bytes/lists/
tuples and handle objects) so the C marshalling layer stays mechanical.
"""
import pickle

import numpy as np

# Lazy imports: embedding apps call MXPredCreate before anything else and
# must not pay package-import cost twice.
from . import ndarray as _nd_mod
from .ndarray import NDArray
from .ndarray.ndarray import invoke as _nd_invoke, waitall as _nd_waitall
from .ndarray import utils as _nd_utils
from .context import Context
from .ops import registry as _op_reg
from .symbol import Symbol, Variable as _sym_var
from .symbol.symbol import (_invoke_sym, _parse_attr,
                            load_json as _sym_load_json)
from . import autograd as _autograd
from . import kvstore as _kvstore_mod
from . import random as _random_mod
from . import profiler as _profiler_mod

_DTYPE_TO_CODE = {'float32': 0, 'float64': 1, 'float16': 2, 'uint8': 3,
                  'int32': 4, 'int8': 5, 'int64': 6, 'bfloat16': 7}
_CODE_TO_DTYPE = {v: k for k, v in _DTYPE_TO_CODE.items()}
_DEVTYPE = {1: 'cpu', 2: 'gpu', 3: 'cpu_pinned', 6: 'tpu'}
_DEVTYPE_R = {'cpu': 1, 'gpu': 2, 'cpu_pinned': 3, 'tpu': 6}
_STYPE = {'default': 0, 'row_sparse': 1, 'csr': 2}


def _ctx(dev_type, dev_id):
    name = _DEVTYPE.get(int(dev_type), 'cpu')
    if name == 'cpu_pinned':
        name = 'cpu'
    return Context(name, int(dev_id))


# ---------------------------------------------------------------- misc --

def random_seed(seed):
    _random_mod.seed(int(seed))
    return 0


def notify_shutdown():
    _nd_waitall()
    return 0


def profiler_set_config(mode, filename):
    _profiler_mod.profiler_set_config(mode=mode, filename=filename)
    return 0


def profiler_set_state(state):
    _profiler_mod.profiler_set_state('run' if int(state) else 'stop')
    return 0


def profiler_dump():
    _profiler_mod.dump_profile()
    return 0


# ------------------------------------------------------------- ndarray --

def nd_create_none():
    return NDArray(np.zeros((), dtype=np.float32))


def nd_create(shape, dev_type, dev_id, delay_alloc, dtype_code):
    dtype = _CODE_TO_DTYPE[int(dtype_code)]
    if dtype == 'bfloat16':
        import jax.numpy as jnp
        import jax
        data = jnp.zeros(tuple(shape), dtype=jnp.bfloat16)
        return NDArray(data, ctx=_ctx(dev_type, dev_id))
    return _nd_mod.zeros(tuple(shape), ctx=_ctx(dev_type, dev_id),
                         dtype=dtype)


def nd_sync_copy_from_bytes(handle, buf, dtype_code):
    """Raw bytes in the array's wire dtype (bf16 = 2 B/elt via ml_dtypes,
    exactly the dtype MXNDArrayGetDType reports)."""
    dtype = _CODE_TO_DTYPE[int(dtype_code)]
    np_dtype = np.dtype(dtype)  # ml_dtypes registers 'bfloat16'
    expect = int(np.prod(handle.shape)) * np_dtype.itemsize
    if len(buf) != expect:
        raise ValueError('SyncCopyFromCPU: got %d bytes, array needs %d'
                         % (len(buf), expect))
    arr = np.frombuffer(buf, dtype=np_dtype).reshape(handle.shape)
    if dtype == 'bfloat16':
        import jax
        handle._set_data(jax.device_put(arr, handle._data.sharding))
        return 0
    handle[:] = arr if handle.ndim else _nd_mod.array(arr.reshape(()))
    return 0


def nd_sync_copy_to_bytes(handle):
    """Raw bytes in the array's own dtype — byte count always equals
    size * itemsize of the dtype MXNDArrayGetDType reports (asnumpy()
    upcasts bf16 for python users, so read the device buffer directly)."""
    return np.ascontiguousarray(np.asarray(handle._data)).tobytes()


def nd_wait_to_read(handle):
    handle.wait_to_read()
    return 0


def nd_wait_all():
    _nd_waitall()
    return 0


def nd_shape(handle):
    return tuple(int(d) for d in handle.shape)


def nd_dtype(handle):
    return _DTYPE_TO_CODE.get(str(handle.dtype), 0)


def nd_stype(handle):
    return _STYPE.get(handle.stype, 0)


def nd_context(handle):
    c = handle.context
    return (_DEVTYPE_R.get(c.device_type, 1), c.device_id)


def nd_slice(handle, begin, end):
    return handle[int(begin):int(end)]


def nd_at(handle, idx):
    return handle[int(idx)]


def nd_reshape(handle, shape):
    return handle.reshape(tuple(shape))


def nd_save(fname, handles, keys):
    if keys:
        _nd_utils.save(fname, dict(zip(keys, handles)))
    else:
        _nd_utils.save(fname, list(handles))
    return 0


def nd_load(fname):
    data = _nd_utils.load(fname)
    if isinstance(data, dict):
        keys = list(data.keys())
        return keys, [data[k] for k in keys]
    return [], list(data)


def nd_save_raw_bytes(handle):
    npy = handle.asnumpy()
    if npy.dtype.name == 'bfloat16':
        npy = npy.astype(np.float32)
    header = pickle.dumps((npy.shape, npy.dtype.str))
    return len(header).to_bytes(8, 'little') + header + npy.tobytes()


def nd_load_from_raw_bytes(buf):
    hlen = int.from_bytes(buf[:8], 'little')
    shape, dtype = pickle.loads(buf[8:8 + hlen])
    npy = np.frombuffer(buf[8 + hlen:], dtype=np.dtype(dtype)).reshape(shape)
    return _nd_mod.array(npy)


# Host mirror buffers for MXNDArrayGetData: NDArray is __slots__'d, so
# pinned numpy views live here, keyed by handle id, until MXNDArrayFree.
_HOST_MIRRORS = {}


def nd_data_ptr(handle):
    npy = handle.asnumpy()
    if npy.dtype.name == 'bfloat16':
        npy = npy.astype(np.float32)
    npy = np.ascontiguousarray(npy)
    _HOST_MIRRORS[id(handle)] = npy
    return npy.ctypes.data


def nd_free(handle):
    _HOST_MIRRORS.pop(id(handle), None)
    return 0


def nd_get_grad(handle):
    return handle.grad


def nd_detach(handle):
    return handle.detach()


# ----------------------------------------------------------- operators --

def list_all_op_names():
    return sorted(_op_reg.list_ops())


def op_info(name):
    op = _op_reg.get(name)
    arg_names = list(op.input_names) + list(op.param_defaults)
    arg_types = (['NDArray-or-Symbol'] * len(op.input_names)
                 + ['string'] * len(op.param_defaults))
    arg_descs = [''] * len(arg_names)
    return (name, op.doc or '', arg_names, arg_types, arg_descs,
            op.key_var_num_args or '', '')


def imperative_invoke(name, inputs, keys, vals, num_out_provided, outputs):
    # C callers send every param as a string; recover typed attrs the same
    # way symbol JSON loading does (tuples, bools, numbers)
    attrs = {k: _parse_attr(v) for k, v in zip(keys, vals)}
    out = None
    if num_out_provided:
        out = outputs if len(outputs) > 1 else outputs[0]
    res = _nd_invoke(name, list(inputs), attrs, out)
    if isinstance(res, (list, tuple)):
        return list(res)
    return [res]


# ------------------------------------------------------------ autograd --

def autograd_set_recording(flag):
    prev = _autograd.is_recording()
    _autograd.set_recording(bool(flag))
    return int(prev)


def autograd_set_training(flag):
    prev = _autograd.is_training()
    _autograd.set_training(bool(flag))
    return int(prev)


def autograd_is_recording():
    return int(_autograd.is_recording())


def autograd_is_training():
    return int(_autograd.is_training())


def autograd_mark_variables(arrays, grad_reqs, grads):
    # OpReqType codes: 0=null, 1=write, 2=inplace, 3=add (ndarray.h)
    req_map = {0: 'null', 1: 'write', 2: 'write', 3: 'add'}
    for arr, req, grad in zip(arrays, grad_reqs, grads):
        req_name = req_map.get(int(req), 'write')
        if grad is not None:
            # bind the caller's buffer: backward rebinds grad._data in
            # place, so the C handle observes the gradients directly
            _autograd.mark_variables([arr], [grad], req_name)
        else:
            arr.attach_grad(grad_req=req_name)
    return 0


def autograd_backward(outputs, head_grads, retain_graph, train_mode):
    _autograd.backward(list(outputs),
                       head_grads=None if not head_grads else list(head_grads),
                       retain_graph=bool(retain_graph),
                       train_mode=bool(train_mode))
    return 0


# ------------------------------------------------------------- symbols --

class _AtomicSymbol:
    """An op + attrs awaiting composition (MXSymbolCreateAtomicSymbol
    result before MXSymbolCompose — reference nnvm Symbol::CreateFunctor)."""

    __slots__ = ('op', 'attrs')

    def __init__(self, op, attrs):
        self.op = op
        self.attrs = attrs


def symbol_create_atomic(op_name, keys, vals):
    if not _op_reg.exists(op_name):
        raise ValueError('unknown operator %s' % op_name)
    return _AtomicSymbol(op_name,
                         {k: _parse_attr(v) for k, v in zip(keys, vals)})


# MXSymbolCompose mutates in place in the reference (nnvm symbols are
# mutable); ours are immutable, so composed results live here, keyed by
# handle id, purged by symbol_free (called from MXSymbolFree).
_COMPOSED = {}


def symbol_compose(handle, name, keys, args):
    """Compose an atomic symbol with its inputs → real Symbol."""
    if isinstance(handle, _AtomicSymbol):
        attrs = dict(handle.attrs)
        if name:
            attrs['name'] = name
        if keys:
            # keyword symbol args map onto the op's declared input names,
            # in declaration order; leftovers are attrs
            op = _op_reg.get(handle.op)
            kw = {k: _as_symbol(a) for k, a in zip(keys, args)}
            inputs = [kw.pop(n) for n in op.input_names if n in kw]
            attrs.update(kw)
            return _invoke_sym(handle.op, inputs, attrs)
        return _invoke_sym(handle.op, [_as_symbol(a) for a in args], attrs)
    sym = _as_symbol(handle)
    if keys:
        return sym(**{k: _as_symbol(a) for k, a in zip(keys, args)})
    return sym(*[_as_symbol(a) for a in args])


def symbol_compose_inplace(handle, name, keys, args):
    _COMPOSED[id(handle)] = symbol_compose(handle, name, keys, args)
    return 0


def symbol_free(handle):
    _COMPOSED.pop(id(handle), None)
    return 0


def _as_symbol(handle):
    composed = _COMPOSED.get(id(handle))
    if composed is not None:
        return composed
    if isinstance(handle, _AtomicSymbol):
        return _invoke_sym(handle.op, [], dict(handle.attrs))
    return handle


def symbol_create_variable(name):
    return _sym_var(name)


def symbol_create_group(handles):
    from .symbol import Group
    return Group([_as_symbol(h) for h in handles])


def symbol_from_json(json_str):
    return _sym_load_json(json_str)


def symbol_from_file(fname):
    from .symbol import load as _sym_load
    return _sym_load(fname)


def symbol_to_json(handle):
    return _as_symbol(handle).tojson()


def symbol_save_file(handle, fname):
    _as_symbol(handle).save(fname)
    return 0


def symbol_copy(handle):
    import copy
    return copy.copy(_as_symbol(handle))


def symbol_print(handle):
    return repr(_as_symbol(handle))


def symbol_get_name(handle):
    name = _as_symbol(handle).name
    return name if name is not None else ''


def symbol_get_attr(handle, key):
    v = _as_symbol(handle).attr(key)
    return v if v is not None else None


def symbol_set_attr(handle, key, value):
    _as_symbol(handle)._set_attr(**{key: value})
    return 0


def symbol_list_attr(handle):
    d = _as_symbol(handle).attr_dict()
    flat = []
    for node_name, attrs in d.items():
        for k, v in attrs.items():
            flat.append('%s$%s' % (node_name, k))
            flat.append(str(v))
    return flat


def symbol_list_arguments(handle):
    return _as_symbol(handle).list_arguments()


def symbol_list_outputs(handle):
    return _as_symbol(handle).list_outputs()


def symbol_list_aux(handle):
    return _as_symbol(handle).list_auxiliary_states()


def symbol_get_internals(handle):
    return _as_symbol(handle).get_internals()


def symbol_get_children(handle):
    return _as_symbol(handle).get_children()


def symbol_get_output(handle, index):
    return _as_symbol(handle)[int(index)]


def symbol_grad(handle, wrt):
    return _as_symbol(handle).gradient(list(wrt))


def _shape_kwargs(keys, arg_ind, arg_data):
    kwargs = {}
    for i, k in enumerate(keys):
        kwargs[k] = tuple(arg_data[arg_ind[i]:arg_ind[i + 1]])
    return kwargs


def symbol_infer_shape(handle, keys, arg_ind, arg_data, partial):
    sym = _as_symbol(handle)
    kwargs = _shape_kwargs(keys, arg_ind, arg_data)
    if partial:
        arg_shapes, out_shapes, aux_shapes = sym.infer_shape_partial(**kwargs)
    else:
        arg_shapes, out_shapes, aux_shapes = sym.infer_shape(**kwargs)
    def pack(shapes):
        return [tuple(int(d) for d in s) if s is not None else ()
                for s in (shapes or [])]
    return pack(arg_shapes), pack(out_shapes), pack(aux_shapes)


def symbol_infer_type(handle, keys, dtype_codes):
    sym = _as_symbol(handle)
    kwargs = {k: _CODE_TO_DTYPE[int(c)] for k, c in zip(keys, dtype_codes)}
    arg_t, out_t, aux_t = sym.infer_type(**kwargs)
    def pack(ts):
        return [_DTYPE_TO_CODE.get(str(np.dtype(t).name) if t is not None
                                   else '', -1) if t is not None else -1
                for t in (ts or [])]
    return pack(arg_t), pack(out_t), pack(aux_t)


# ----------------------------------------------------------- executors --

def executor_bind(sym_handle, dev_type, dev_id, args, arg_grads, grad_reqs,
                  aux_states):
    sym = _as_symbol(sym_handle)
    ctx = _ctx(dev_type, dev_id)
    req_names = {0: 'null', 1: 'write', 3: 'add'}
    arg_names = sym.list_arguments()
    args_map = dict(zip(arg_names, args))
    grads_map = {n: g for n, g in zip(arg_names, arg_grads or [])
                 if g is not None}
    reqs = {n: req_names.get(int(r), 'write')
            for n, r in zip(arg_names, grad_reqs or [])} or 'write'
    aux_map = dict(zip(sym.list_auxiliary_states(), aux_states or []))
    return sym.bind(ctx, args_map, args_grad=grads_map or None,
                    grad_req=reqs, aux_states=aux_map or None)


def executor_forward(handle, is_train):
    handle.forward(is_train=bool(is_train))
    return 0


def executor_backward(handle, out_grads):
    handle.backward(out_grads=list(out_grads) if out_grads else None)
    return 0


def executor_outputs(handle):
    return list(handle.outputs)


def executor_print(handle):
    return repr(handle)


# ------------------------------------------------------------ cachedop --

class _CachedOp:
    """MXCreateCachedOp: a symbol specialized for repeated imperative calls
    (reference src/imperative/cached_op.cc). Here: bind-once + jit reuse
    keyed on input shapes, via Symbol.eval machinery."""

    def __init__(self, sym):
        self.sym = _as_symbol(sym)
        self._cache = {}

    def __call__(self, inputs):
        names = self.sym.list_arguments()
        key = tuple((a.shape, str(a.dtype)) for a in inputs)
        ex = self._cache.get(key)
        if ex is None:
            ctx = inputs[0].context if inputs else Context('cpu', 0)
            ex = self.sym.bind(ctx, dict(zip(names, inputs)),
                               grad_req='null')
            self._cache[key] = ex
        else:
            ex.copy_params_from(dict(zip(names, inputs)),
                                allow_extra_params=True)
        ex.forward(is_train=False)
        return list(ex.outputs)


def cached_op_create(sym_handle):
    return _CachedOp(sym_handle)


def cached_op_invoke(handle, inputs):
    return handle(list(inputs))


# ------------------------------------------------------------- kvstore --

def kv_create(type_name):
    return _kvstore_mod.create(type_name)


def kv_init(handle, keys, values):
    handle.init(list(keys), list(values))
    return 0


def kv_push(handle, keys, values, priority):
    handle.push(list(keys), list(values), priority=int(priority))
    return 0


def kv_pull(handle, keys, outs, priority):
    handle.pull(list(keys), out=list(outs), priority=int(priority))
    return 0


def kv_type(handle):
    return handle.type


def kv_rank(handle):
    return handle.rank


def kv_group_size(handle):
    return handle.num_workers


def kv_barrier(handle):
    if hasattr(handle, '_barrier'):
        handle._barrier()
    return 0


def kv_num_dead_node(handle, node_id):
    if hasattr(handle, 'num_dead_node'):
        return handle.num_dead_node(int(node_id))
    return 0


def kv_run_server(handle):
    """MXKVStoreRunServer — blocks in the server role loop."""
    from . import kvstore_server
    kvstore_server.run_server()
    return 0


def kv_send_command(handle, cmd_id, cmd_body):
    if hasattr(handle, '_send_command_to_servers'):
        handle._send_command_to_servers(int(cmd_id), cmd_body)
    return 0


# ------------------------------------------------------------- dataio --

_ITER_CLASSES = None


def _iter_classes():
    global _ITER_CLASSES
    if _ITER_CLASSES is None:
        from . import io as _io
        _ITER_CLASSES = {
            'MNISTIter': _io.MNISTIter,
            'CSVIter': _io.CSVIter,
            'ImageRecordIter': _io.ImageRecordIter,
            'ImageDetRecordIter': _io.ImageDetRecordIter,
            'LibSVMIter': _io.LibSVMIter,
        }
    return _ITER_CLASSES


def list_data_iters():
    return sorted(_iter_classes().keys())


def data_iter_create(name, keys, vals):
    cls = _iter_classes()[name]
    kwargs = {k: _parse_attr(v) for k, v in zip(keys, vals)}
    return iter(cls(**kwargs))


class _IterState:
    __slots__ = ('it', 'batch')

    def __init__(self, it):
        self.it = it
        self.batch = None


def iter_state_new(it):
    return _IterState(it)


def data_iter_next(handle):
    try:
        handle.batch = next(handle.it)
        return 1
    except StopIteration:
        return 0


def data_iter_before_first(handle):
    handle.it.reset()
    return 0


def data_iter_get_data(handle):
    return handle.batch.data[0]


def data_iter_get_label(handle):
    return handle.batch.label[0]


def data_iter_get_pad(handle):
    return int(handle.batch.pad or 0)


# ------------------------------------------------------------- predict --

class _Predictor:
    """MXPredCreate state (reference src/c_api/c_predict_api.cc:57-177):
    symbol json + param blob → bound inference executor."""

    def __init__(self, symbol_json, param_bytes, dev_type, dev_id,
                 input_keys, input_shapes, output_keys=None):
        import io as _pyio
        sym = _sym_load_json(symbol_json)
        if output_keys:
            outs = sym.list_outputs()
            picked = []
            for k in output_keys:
                name = k if k.endswith('_output') else k + '_output'
                idx = outs.index(name) if name in outs else outs.index(k)
                picked.append(sym[idx])
            from .symbol import Group
            sym = Group(picked) if len(picked) > 1 else picked[0]
        self.sym = sym
        # param blob: NDArray save format (arg:/aux: prefixed dict)
        params = {}
        if param_bytes:
            import tempfile, os
            with tempfile.NamedTemporaryFile(delete=False) as f:
                f.write(param_bytes)
                tmp = f.name
            try:
                loaded = _nd_utils.load(tmp)
            finally:
                os.unlink(tmp)
            for k, v in (loaded.items() if isinstance(loaded, dict) else []):
                params[k.split(':', 1)[-1]] = v
        ctx = _ctx(dev_type, dev_id)
        shapes = dict(zip(input_keys, [tuple(s) for s in input_shapes]))
        arg_shapes, out_shapes, aux_shapes = sym.infer_shape(**shapes)
        # static output shapes: lets MXPredGetOutputShape size buffers
        # without forcing a forward (esp. mid partial_forward pass)
        self._out_shapes = [tuple(int(d) for d in s) for s in out_shapes]
        arg_names = sym.list_arguments()
        aux_names = sym.list_auxiliary_states()
        self.input_keys = list(input_keys)
        args = {}
        for name, shp in zip(arg_names, arg_shapes):
            if name in params:
                args[name] = params[name].as_in_context(ctx)
            else:
                args[name] = _nd_mod.zeros(shp, ctx=ctx)
        aux = {}
        for name, shp in zip(aux_names, aux_shapes or []):
            if name in params:
                aux[name] = params[name].as_in_context(ctx)
            else:
                aux[name] = _nd_mod.zeros(shp, ctx=ctx)
        self.executor = sym.bind(ctx, args, grad_req='null',
                                 aux_states=aux or None)
        self.args = args

    def set_input(self, key, buf, shape):
        arr = np.frombuffer(buf, dtype=np.float32).reshape(shape)
        self.args[key][:] = arr
        return 0

    def forward(self):
        self.executor.forward(is_train=False)
        return 0

    def partial_forward(self, step):
        """MXPredPartialForward (reference include/mxnet/c_predict_api.h:169):
        one operator per call for progress display; returns steps left."""
        return self.executor.partial_forward(False, int(step))

    def get_output_shape(self, index):
        return self._out_shapes[int(index)]

    def get_output(self, index):
        out = self.executor.outputs[int(index)]
        npy = out.asnumpy()
        if npy.dtype != np.float32:
            npy = npy.astype(np.float32)
        return npy.tobytes()


def pred_create(symbol_json, param_bytes, dev_type, dev_id, input_keys,
                input_shapes, output_keys=None):
    return _Predictor(symbol_json, param_bytes, dev_type, dev_id,
                      input_keys, input_shapes, output_keys)


def nd_list_create(buf):
    """MXNDListCreate: load an NDArray-save blob → (keys, arrays)."""
    import tempfile, os
    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(buf)
        tmp = f.name
    try:
        loaded = _nd_utils.load(tmp)
    finally:
        os.unlink(tmp)
    if isinstance(loaded, dict):
        keys = list(loaded.keys())
        return keys, [loaded[k] for k in keys]
    return [''] * len(loaded), list(loaded)


def nd_list_get(keys, arrays, index):
    i = int(index)
    arr = arrays[i]
    npy = arr.asnumpy().astype(np.float32)
    return keys[i], npy.tobytes(), tuple(int(d) for d in npy.shape)


# ---------------------------------------------------------------------------
# Round-3 additions: the 38 remaining reference entry points
# (reference include/mxnet/c_api.h; closes the C ABI to 146/146).
# ---------------------------------------------------------------------------

def _ctypes():
    import ctypes
    return ctypes


def _handle_ptr(obj):
    """The PyObject* of obj as an integer — what the C caller sees as an
    NDArrayHandle. The caller of the C callback must keep obj alive for
    the duration of the call (we do, via locals)."""
    return id(obj)


# -- imperative/cachedop Ex variants (storage types out) --

def nd_stype_code(arr):
    from .ndarray import sparse as _sp
    if isinstance(arr, _sp.RowSparseNDArray):
        return 1
    if isinstance(arr, _sp.CSRNDArray):
        return 2
    return 0


def imperative_invoke_ex(name, inputs, keys, vals, num_out_provided, outputs):
    outs = imperative_invoke(name, inputs, keys, vals, num_out_provided,
                             outputs)
    return outs, [nd_stype_code(o) for o in outs]


def cached_op_invoke_ex(handle, inputs):
    outs = handle(list(inputs))
    return outs, [nd_stype_code(o) for o in outs]


# -- sparse creation + accessors --

def nd_create_sparse(storage_type, shape, dev_type, dev_id, dtype_code,
                     aux_types, aux_shapes):
    from .ndarray import sparse as _sp
    from .ndarray import zeros as _zeros
    ctx = _ctx(dev_type, dev_id)
    dtype = _CODE_TO_DTYPE.get(int(dtype_code), 'float32')
    shape = tuple(int(d) for d in shape)
    if int(storage_type) == 1:      # row_sparse: aux [indices]
        nrows = int(aux_shapes[0][0]) if aux_shapes and aux_shapes[0] else 0
        return _sp.RowSparseNDArray(
            _zeros((nrows,) + shape[1:], dtype=dtype),
            _zeros((nrows,), dtype='int64'), shape, ctx=ctx)
    if int(storage_type) == 2:      # csr: aux [indptr, indices]
        nnz = int(aux_shapes[1][0]) if len(aux_shapes) > 1 and aux_shapes[1] else 0
        return _sp.CSRNDArray(
            _zeros((nnz,), dtype=dtype),
            _zeros((shape[0] + 1,), dtype='int64'),
            _zeros((nnz,), dtype='int64'), shape, ctx=ctx)
    return _zeros(shape, dtype=dtype, ctx=ctx)


def _aux_arrays(arr):
    from .ndarray import sparse as _sp
    if isinstance(arr, _sp.RowSparseNDArray):
        return [arr.indices]
    if isinstance(arr, _sp.CSRNDArray):
        return [arr.indptr, arr.indices]
    raise TypeError('dense NDArray has no aux arrays')


def nd_aux_type(handle, i):
    aux = _aux_arrays(handle)[int(i)]
    return _DTYPE_TO_CODE.get(str(aux.dtype), 6)


def nd_get_aux(handle, i):
    return _aux_arrays(handle)[int(i)]


def nd_get_data(handle):
    return handle.data


def nd_grad_state(handle):
    return 1 if getattr(handle, '_fresh_grad', False) else 0


def nd_set_grad_state(handle, state):
    handle._fresh_grad = bool(state)
    return 0


def nd_sync_copy_from_ndarray(dst, src, i):
    from .ndarray import sparse as _sp
    if int(i) >= 0:
        src = _aux_arrays(src)[int(i)]
    elif isinstance(src, _sp.BaseSparseNDArray):
        src = src.data
    dst[:] = src.astype(dst.dtype) if str(src.dtype) != str(dst.dtype) else src
    return 0


# -- autograd extras --

def autograd_get_symbol(handle):
    """Export the recorded imperative history of `handle` as a Symbol
    (reference MXAutogradGetSymbol / nnvm graph behind the tape).
    Leaves and unrecorded inputs become Variables."""
    from .symbol import Variable
    node = handle._node
    if node is None:
        name = 'var0'
        return Variable(name)
    memo = {}
    counter = [0]

    def build(entry):
        src, idx = entry
        if src is None or not hasattr(src, 'op_info') or \
                getattr(src, 'op_info', None) is None:
            key = id(src) if src is not None else ('anon', counter[0])
            if key not in memo:
                memo[key] = Variable('var%d' % counter[0])
                counter[0] += 1
            return memo[key]
        if id(src) in memo:
            sym = memo[id(src)]
        else:
            op_name, attrs = src.op_info
            parents = [build(p) for p in src.parents[:src.n_grad_inputs]]
            attrs = {k: v for k, v in attrs.items()
                     if not k.startswith('__')}
            sym = _invoke_sym(op_name, parents, attrs)
            memo[id(src)] = sym
        if src.n_outputs > 1:
            return sym[idx]
        return sym
    return build((node, handle._out_idx))


class _CCustomFunction:
    """MXCustomFunctionRecord: a python-side Function whose backward calls
    the C callback list (kCustomFunctionBackward)."""

    def __init__(self, callbacks_ptr, n_in, n_out):
        ct = _ctypes()
        self._cb = callbacks_ptr      # (fnptr_int, ctx_int) list
        self.n_in, self.n_out = int(n_in), int(n_out)
        fnptr, ctx = callbacks_ptr[0]
        proto = ct.CFUNCTYPE(ct.c_int, ct.c_int, ct.c_int,
                             ct.POINTER(ct.c_void_p), ct.POINTER(ct.c_int),
                             ct.c_int, ct.c_void_p)
        self._bwd = proto(fnptr) if fnptr else None
        self._bwd_ctx = ctx

    def backward_arrays(self, ograds):
        """Run the C backward: ograds (NDArrays) -> igrads (NDArrays)."""
        ct = _ctypes()
        from .ndarray import zeros as _zeros
        igrads = [_zeros(s) for s in self._igrad_shapes]
        all_arrays = list(ograds) + igrads
        n = len(all_arrays)
        ptrs = (ct.c_void_p * n)(*[_handle_ptr(a) for a in all_arrays])
        reqs = (ct.c_int * len(igrads))(*([1] * len(igrads)))
        rc = self._bwd(len(ograds), len(igrads), ptrs, reqs, 1,
                       ct.c_void_p(self._bwd_ctx))
        if rc == 0:
            raise RuntimeError('CustomFunction backward callback failed')
        return igrads


def custom_function_record(inputs, outputs, callbacks):
    """Attach a C-callback backward to the tape edge inputs->outputs."""
    from . import autograd as _ag
    import jax.numpy as jnp
    fn = _CCustomFunction(callbacks, len(inputs), len(outputs))
    fn._igrad_shapes = [tuple(a.shape) for a in inputs]

    def vjp_fn(cotangents):
        if not isinstance(cotangents, (tuple, list)):
            cotangents = (cotangents,)
        ograds = [NDArray(jnp.asarray(g)) for g in cotangents]
        igrads = fn.backward_arrays(ograds)
        return tuple(g._data for g in igrads)

    parents = []
    for a in inputs:
        if a._node is not None:
            parents.append((a._node, a._out_idx))
        elif a._leaf is not None:
            parents.append((a._leaf, 0))
        else:
            parents.append((None, 0))
    node = _ag.record_op(vjp_fn, parents, len(outputs), len(inputs),
                         op_info=('_CustomFunction', {}))
    node.head_ids = [(tuple(o.shape), o.dtype) for o in outputs]
    for i, o in enumerate(outputs):
        o._node = node
        o._out_idx = i
    return 0


# -- legacy NDArray-function registry (MXFunc*) --

class _LegacyFunction:
    __slots__ = ('name', 'op')

    def __init__(self, name):
        self.name = name
        self.op = _op_reg.get(name)


_FUNC_CACHE = {}


def list_functions():
    return [get_function(n) for n in _op_reg.list_ops()]


def get_function(name):
    f = _FUNC_CACHE.get(name)
    if f is None:
        f = _FUNC_CACHE[name] = _LegacyFunction(name)
    return f


def func_describe(fun):
    n_in = 0 if fun.op.variadic else len(fun.op.input_names)
    n_out = fun.op.num_outputs if isinstance(fun.op.num_outputs, int) else 1
    return n_in, 0, n_out, 0


def func_get_info(fun):
    op = fun.op
    args = list(op.param_defaults)
    return (fun.name, op.doc or '', args, ['string'] * len(args),
            [''] * len(args), 'NDArray')


def func_invoke(fun, use_vars, scalars, mutate_vars, keys, vals):
    attrs = {k: _parse_attr(v) for k, v in zip(keys, vals)}
    outs = mutate_vars if mutate_vars else None
    out = outs if outs and len(outs) > 1 else (outs[0] if outs else None)
    res = _nd_invoke(fun.name, list(use_vars), attrs, out)
    return 0


# -- kvstore Ex / row_sparse / updater --

def kv_init_ex(handle, keys, values):
    handle.init(list(keys), list(values))
    return 0


def kv_push_ex(handle, keys, values, priority):
    handle.push(list(keys), list(values), priority=int(priority))
    return 0


def kv_pull_ex(handle, keys, outs, priority):
    handle.pull(list(keys), out=list(outs), priority=int(priority))
    return 0


def kv_pull_row_sparse(handle, keys, outs, row_ids, priority):
    handle.row_sparse_pull(list(keys), out=list(outs),
                           priority=int(priority), row_ids=list(row_ids))
    return 0


def kv_set_barrier_before_exit(handle, flag):
    if hasattr(handle, 'set_barrier_before_exit'):
        handle.set_barrier_before_exit(bool(flag))
    return 0


def kv_set_updater(handle, fnptr, str_fnptr, ctx_ptr):
    """MXKVStoreSetUpdater(Ex): wrap the C function pointer in a python
    updater. NDArray handles passed to C are live PyObject pointers kept
    alive for the call duration."""
    ct = _ctypes()
    int_proto = ct.CFUNCTYPE(None, ct.c_int, ct.c_void_p, ct.c_void_p,
                             ct.c_void_p)
    str_proto = ct.CFUNCTYPE(None, ct.c_char_p, ct.c_void_p, ct.c_void_p,
                             ct.c_void_p)
    c_int_fn = int_proto(fnptr) if fnptr else None
    c_str_fn = str_proto(str_fnptr) if str_fnptr else None

    def updater(key, recv, local):
        if isinstance(key, str) and not key.isdigit():
            if c_str_fn is None:
                raise RuntimeError(
                    'string key %r needs MXKVStoreSetUpdaterEx with a '
                    'str_updater (reference kvstore.cc semantics)' % key)
            c_str_fn(key.encode(), _handle_ptr(recv), _handle_ptr(local),
                     ct.c_void_p(ctx_ptr))
        elif c_int_fn is not None:
            c_int_fn(int(key), _handle_ptr(recv), _handle_ptr(local),
                     ct.c_void_p(ctx_ptr))
        elif c_str_fn is not None:
            c_str_fn(str(key).encode(), _handle_ptr(recv),
                     _handle_ptr(local), ct.c_void_p(ctx_ptr))
    handle.set_updater(updater)
    return 0


def init_ps_env(keys, vals):
    import os as _os
    for k, v in zip(keys, vals):
        _os.environ[str(k)] = str(v)
    return 0


# -- executor extras --

def executor_backward_ex(handle, out_grads, is_train):
    handle.backward(out_grads=list(out_grads) if out_grads else None)
    return 0


def executor_bind_x(sym_handle, dev_type, dev_id, map_keys, map_dev_types,
                    map_dev_ids, args, arg_grads, grad_reqs, aux_states):
    sym = _as_symbol(sym_handle)
    ctx = _ctx(dev_type, dev_id)
    g2c = {k: _ctx(t, i) for k, t, i in
           zip(map_keys, map_dev_types, map_dev_ids)}
    req_names = {0: 'null', 1: 'write', 3: 'add'}
    arg_names = sym.list_arguments()
    args_map = dict(zip(arg_names, args))
    grads_map = {n: g for n, g in zip(arg_names, arg_grads or [])
                 if g is not None}
    reqs = {n: req_names.get(int(r), 'write')
            for n, r in zip(arg_names, grad_reqs or [])} or 'write'
    aux_map = dict(zip(sym.list_auxiliary_states(), aux_states or []))
    from .executor import Executor
    return Executor(sym, ctx, args_map, args_grad=grads_map or None,
                    grad_req=reqs, aux_states=aux_map or None,
                    group2ctx=g2c or None)


def executor_simple_bind(sym_handle, dev_type, dev_id, g2c_keys,
                         g2c_dev_types, g2c_dev_ids, grad_req_names,
                         grad_req_types, shape_names, shapes, dtype_names,
                         dtypes, stype_names, stypes,
                         shared_buffer_names, shared_buffer_arrays):
    """MXExecutorSimpleBind: allocate arg/grad/aux arrays from hints.
    Returns (executor, arg_names, in_args, arg_grads(list w/ None),
    aux_names, aux_states, updated_buffer_names, updated_buffer_arrays)."""
    sym = _as_symbol(sym_handle)
    ctx = _ctx(dev_type, dev_id)
    kwargs = {}
    for n, s in zip(shape_names, shapes):
        kwargs[n] = tuple(int(d) for d in s)
    grad_req = 'write'
    named = [(n, t) for n, t in zip(grad_req_names, grad_req_types) if n]
    if named:
        grad_req = dict(named)
    elif grad_req_types:
        grad_req = grad_req_types[0]
    type_dict = {n: _CODE_TO_DTYPE.get(int(t), 'float32')
                 for n, t in zip(dtype_names, dtypes)} or None
    g2c = {k: _ctx(t, i) for k, t, i in
           zip(g2c_keys, g2c_dev_types, g2c_dev_ids)}
    shared = dict(zip(shared_buffer_names or [],
                      shared_buffer_arrays or []))
    ex = sym.simple_bind(ctx, grad_req=grad_req, type_dict=type_dict,
                         group2ctx=g2c or None, **kwargs)
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    in_args = [ex.arg_dict[n] for n in arg_names]
    arg_grads = [ex.grad_dict.get(n) for n in arg_names]
    aux_states = [ex.aux_dict[n] for n in aux_names]
    # updated shared buffer: existing entries plus this bind's args
    # (memory identity is an XLA concern here; values are what matter)
    for n in arg_names:
        shared.setdefault(n, ex.arg_dict[n])
    upd_names = list(shared.keys())
    upd_arrays = [shared[n] for n in upd_names]
    return (ex, arg_names, in_args, arg_grads, aux_names, aux_states,
            upd_names, upd_arrays)


def executor_set_monitor_callback(handle, fnptr, ctx_ptr):
    ct = _ctypes()
    proto = ct.CFUNCTYPE(None, ct.c_char_p, ct.c_void_p, ct.c_void_p)
    c_fn = proto(fnptr)

    def monitor(name, arr):
        c_fn(str(name).encode(), _handle_ptr(arr), ct.c_void_p(ctx_ptr))
    handle.set_monitor_callback(monitor)
    return 0


# -- data iter index --

def data_iter_get_index(handle):
    batch = handle.batch
    idx = getattr(batch, 'index', None)
    if idx is None:
        n = int(batch.data[0].shape[0]) if batch.data else 0
        idx = np.arange(n, dtype=np.uint64)
    return np.asarray(idx, dtype=np.uint64).tobytes()


# -- custom op registration from C (MXCustomOpRegister) --

_C_CUSTOM_CREATORS = {}


def custom_op_register(op_type, creator_ptr):
    """Register a C CustomOpPropCreator under op_type. A python
    CustomOpProp proxy calls the C callback list for list_arguments/
    list_outputs/infer_shape/create_operator (+forward/backward),
    mirroring the reference's CustomOpProp-over-MXCallbackList protocol
    (src/operator/custom/custom.cc)."""
    ct = _ctypes()
    from . import operator as _op_mod

    creator_proto = ct.CFUNCTYPE(
        ct.c_int, ct.c_char_p, ct.c_int, ct.POINTER(ct.c_char_p),
        ct.POINTER(ct.c_char_p), ct.c_void_p)
    creator = creator_proto(creator_ptr)
    _C_CUSTOM_CREATORS[op_type] = creator

    class _CallbackList(ct.Structure):
        _fields_ = [('num_callbacks', ct.c_int),
                    ('callbacks', ct.POINTER(ct.CFUNCTYPE(ct.c_int))),
                    ('contexts', ct.POINTER(ct.c_void_p))]

    list_proto = ct.CFUNCTYPE(ct.c_int, ct.POINTER(ct.POINTER(ct.c_char_p)),
                              ct.c_void_p)
    shape_proto = ct.CFUNCTYPE(ct.c_int, ct.c_int, ct.POINTER(ct.c_int),
                               ct.POINTER(ct.POINTER(ct.c_uint)), ct.c_void_p)
    create_proto = ct.CFUNCTYPE(ct.c_int, ct.c_char_p, ct.c_int,
                                ct.POINTER(ct.POINTER(ct.c_uint)),
                                ct.POINTER(ct.c_int), ct.POINTER(ct.c_int),
                                ct.c_void_p, ct.c_void_p)
    fb_proto = ct.CFUNCTYPE(ct.c_int, ct.c_int, ct.POINTER(ct.c_void_p),
                            ct.POINTER(ct.c_int), ct.POINTER(ct.c_int),
                            ct.c_int, ct.c_void_p)

    def _read_strlist(fn_addr, context):
        fn = list_proto(fn_addr)
        arr = ct.POINTER(ct.c_char_p)()
        if not fn(ct.byref(arr), context):
            raise RuntimeError('%s: C list callback failed' % op_type)
        out, i = [], 0
        while arr[i]:
            out.append(arr[i].decode())
            i += 1
        return out

    class CProp(_op_mod.CustomOpProp):
        def __init__(self, **kwargs):
            super().__init__(need_top_grad=True)
            keys = [k.encode() for k in kwargs]
            vals = [str(v).encode() for v in kwargs.values()]
            karr = (ct.c_char_p * len(keys))(*keys)
            varr = (ct.c_char_p * len(vals))(*vals)
            cblist = _CallbackList()
            if not creator(op_type.encode(), len(keys), karr, varr,
                           ct.cast(ct.byref(cblist), ct.c_void_p)):
                raise RuntimeError('CustomOpPropCreator for %r failed'
                                   % op_type)
            # order: CustomOpPropCallbacks enum (c_api.h:137-146)
            self._cbs = [(ct.cast(cblist.callbacks[i], ct.c_void_p).value,
                          cblist.contexts[i])
                         for i in range(cblist.num_callbacks)]

        def _cb(self, idx):
            fnptr, context = self._cbs[idx]
            return fnptr, context

        def list_arguments(self):
            fnptr, context = self._cb(1)
            return _read_strlist(fnptr, context)

        def list_outputs(self):
            fnptr, context = self._cb(2)
            return _read_strlist(fnptr, context)

        def list_auxiliary_states(self):
            if len(self._cbs) > 3 and self._cbs[3][0]:
                return _read_strlist(*self._cb(3))
            return []

        def infer_shape(self, in_shape):
            fnptr, context = self._cb(4)
            fn = shape_proto(fnptr)
            n_in = len(self.list_arguments())
            n_out = len(self.list_outputs())
            n_aux = len(self.list_auxiliary_states())
            # total includes aux states (reference custom.cc:109)
            n = n_in + n_out + n_aux
            # protocol: in entries filled by caller, callback fills rest
            ndims = (ct.c_int * n)()
            shape_ptrs = (ct.POINTER(ct.c_uint) * n)()
            keep = []
            for i, s in enumerate(in_shape):
                ndims[i] = len(s)
                buf = (ct.c_uint * max(1, len(s)))(*[int(d) for d in s])
                keep.append(buf)
                shape_ptrs[i] = ct.cast(buf, ct.POINTER(ct.c_uint))
            if not fn(n, ndims, shape_ptrs, context):
                raise RuntimeError('%s: infer_shape callback failed'
                                   % op_type)
            shapes = []
            for i in range(n):
                shapes.append(tuple(int(shape_ptrs[i][j])
                                    for j in range(ndims[i])))
            return (shapes[:n_in], shapes[n_in:n_in + n_out],
                    shapes[n_in + n_out:])

        def create_operator(self, ctx_str, in_shapes, in_dtypes):
            fnptr, context = self._cb(6)
            fn = create_proto(fnptr)
            n = len(in_shapes)
            ndims = (ct.c_int * n)(*[len(s) for s in in_shapes])
            keep = []
            shape_ptrs = (ct.POINTER(ct.c_uint) * n)()
            for i, s in enumerate(in_shapes):
                buf = (ct.c_uint * max(1, len(s)))(*[int(d) for d in s])
                keep.append(buf)
                shape_ptrs[i] = ct.cast(buf, ct.POINTER(ct.c_uint))
            dts = (ct.c_int * n)(*[_DTYPE_TO_CODE.get(str(t), 0)
                                   for t in in_dtypes])
            op_cblist = _CallbackList()
            if not fn(b'cpu', n, shape_ptrs, ndims, dts,
                      ct.cast(ct.byref(op_cblist), ct.c_void_p), context):
                raise RuntimeError('%s: create_operator callback failed'
                                   % op_type)
            op_cbs = [(ct.cast(op_cblist.callbacks[i], ct.c_void_p).value,
                       op_cblist.contexts[i])
                      for i in range(op_cblist.num_callbacks)]
            prop = self

            class COp(_op_mod.CustomOp):
                def _run_fb(self, idx, arrays_tagged, is_train):
                    fnptr2, context2 = op_cbs[idx]
                    fn2 = fb_proto(fnptr2)
                    n2 = len(arrays_tagged)
                    ptrs = (ct.c_void_p * n2)(
                        *[_handle_ptr(a) for a, _ in arrays_tagged])
                    tags = (ct.c_int * n2)(*[t for _, t in arrays_tagged])
                    reqs = (ct.c_int * n2)(*([1] * n2))
                    if not fn2(n2, ptrs, tags, reqs, int(is_train),
                               context2):
                        raise RuntimeError('%s: forward/backward callback '
                                           'failed' % op_type)

                def forward(self, is_train, req, in_data, out_data, aux):
                    tagged = [(a, 0) for a in in_data] + \
                             [(a, 1) for a in out_data] + \
                             [(a, 4) for a in aux]
                    self._run_fb(1, tagged, is_train)

                def backward(self, req, out_grad, in_data, out_data,
                             in_grad, aux):
                    tagged = [(a, 3) for a in out_grad] + \
                             [(a, 0) for a in in_data] + \
                             [(a, 1) for a in out_data] + \
                             [(a, 2) for a in in_grad] + \
                             [(a, 4) for a in aux]
                    self._run_fb(2, tagged, is_train=True)
            return COp()

    CProp.__name__ = 'CProp_%s' % op_type
    _op_mod.register(op_type)(CProp)
    return 0


# -- rtc --

def rtc_create(name, input_names, output_names, inputs, outputs, kernel):
    from . import rtc as _rtc
    ins = list(zip(input_names, inputs))
    outs = list(zip(output_names, outputs))
    return _rtc.Rtc(name, ins, outs, kernel)


def rtc_push(handle, inputs, outputs):
    handle.push(list(inputs), list(outputs))
    return 0


# -- symbol shallow attrs --

def symbol_list_attr_shallow(handle):
    sym = _as_symbol(handle)
    flat = []
    for node, _idx in sym._outputs:
        for k, v in node.attr_dict.items():
            flat.append(str(k))
            flat.append(str(v))
    return flat
