"""DataParallelExecutorGroup — the data-parallel heart of Module.

Reference: python/mxnet/module/executor_group.py:99 (decide_slices:233 splits
the batch over contexts by workload, _bind_ith_exec:584 per-device
simple_bind with shared memory pool, forward/backward fan-out,
_merge_multi_context:75).

TPU note: when the context list is homogeneous (the common data-parallel
case) Module uses :class:`SPMDExecutorGroup` instead — ONE GSPMD
computation over a jax Mesh of the devices, with the gradient all-reduce
compiled into the step (the reference's KVStore push becomes a psum by
construction). This class keeps the reference's explicit per-context
semantics for heterogeneous/unequal-workload setups and as the fallback.
"""
import logging
import os

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import telemetry as _tele
from ..io import DataDesc
from ..executor import Executor

__all__ = ['DataParallelExecutorGroup', 'SPMDExecutorGroup']


def _load_general(data, targets, major_axis):
    """Load a list of batch arrays into per-device slices (reference :33).

    The device slice runs along each entry's BATCH axis (major_axis,
    from the DataDesc layout) — slicing axis 0 unconditionally
    truncated time-major 'TN' batches along TIME whenever T exceeded
    the batch size (and silently no-op'd when T <= batch, python
    slicing being clamped)."""
    for d_src, d_targets, axis in zip(data, targets, major_axis):
        if isinstance(d_targets, nd.NDArray):
            d_src.copyto(d_targets)
            continue
        src_np = d_src.asnumpy()
        for slice_idx, d_dst in d_targets:
            if axis >= 0:
                idx = [slice(None)] * src_np.ndim
                idx[axis] = slice(slice_idx.start, slice_idx.stop)
                part = src_np[tuple(idx)]
            else:
                part = src_np
            if tuple(part.shape) != tuple(d_dst.shape):
                raise ValueError(
                    'batch slice has shape %s but the bound buffer is %s '
                    '(batch axis %d)' % (part.shape, tuple(d_dst.shape),
                                         axis))
            d_dst._data = nd.array(part, ctx=d_dst.context)._data


def _merge_multi_context(outputs, major_axis):
    """Concat per-device outputs along the batch axis (reference :75)."""
    rets = []
    for tensors, axis in zip(outputs, major_axis):
        if axis >= 0 and len(tensors) > 1:
            rets.append(nd.concatenate(tensors, axis=axis))
        else:
            rets.append(tensors[0])
    return rets


def _output_layouts(symbol):
    """Per-output batch axis from each output's ``__layout__`` attr (the
    reference derives merge/slice/shape axes the same way), so a
    time-major ('TN') output reports/merges on its real batch axis
    instead of assuming axis 0. -1 means no batch axis."""
    return [DataDesc.get_batch_axis(symbol[name].attr('__layout__'))
            for name in symbol.list_outputs()]


def _check_label_args(label_shapes, arg_dict, symbol):
    """A label name that isn't an argument of the bound symbol can only
    come from a provide_label/label_names mismatch that the bind-time
    name check already warned about (reference base_module.py:56 warns
    for labels instead of raising) — fail like the reference's
    simple_bind/infer_shape does at the same point, with the argument
    list instead of a bare KeyError."""
    for d in label_shapes:
        name = d.name if isinstance(d, DataDesc) else d[0]
        if name not in arg_dict:
            raise ValueError(
                "label '%s' is not an argument of the symbol (arguments:"
                ' %s) — pass matching label_names to Module or rename '
                'the iterator label' % (name, symbol.list_arguments()))


def _update_metric(eval_metric, symbol, label_descs, labels, outputs):
    """``eval_metric.update_dict`` with the graph's own names (reference
    executor_group.py:update_metric), so that a metric that names the
    output and label it reads (``output_names``, ``label_names``) gets
    those and no others; one that names none gets them all, in order."""
    names = [d if isinstance(d, str) else
             d.name if isinstance(d, DataDesc) else d[0]
             for d in (label_descs or [])]
    if len(names) != len(labels):       # labels the bind never named
        return eval_metric.update(labels, outputs)
    eval_metric.update_dict(dict(zip(names, labels)),
                            dict(zip(symbol.list_outputs(), outputs)))


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req='write', state_names=None):
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload or [1] * len(contexts)
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        self.logger = logger

        if grad_req != 'null' and for_training:
            self.grad_req = {}
            for k in self.arg_names:
                if k in self.param_names:
                    self.grad_req[k] = 'null' if k in self.fixed_param_names \
                        else grad_req
                elif k in [d.name if isinstance(d, DataDesc) else d[0]
                           for d in data_shapes]:
                    self.grad_req[k] = grad_req if inputs_need_grad else 'null'
                else:
                    self.grad_req[k] = 'null'
        else:
            self.grad_req = {k: 'null' for k in self.arg_names}

        self.execs = []
        self.slices = None
        self.data_shapes = None
        self.label_shapes = None
        self.data_layouts = None
        self.label_layouts = None
        self.output_layouts = _output_layouts(symbol)
        self.batch_size = None

        self.bind_exec(data_shapes, label_shapes, shared_group)

    def decide_slices(self, data_shapes):
        """Reference :233 — split batch_size over contexts by workload."""
        assert len(data_shapes) > 0
        major_axis = [DataDesc.get_batch_axis(getattr(d, 'layout', 'NCHW'))
                      for d in data_shapes]
        if len(self.contexts) > 1 and any(a > 0 for a in major_axis):
            # output merge / head-grad slicing honor per-output layout
            # axes, but INPUT loading across unequal per-device chunks
            # with a non-leading batch axis is untested territory —
            # fail loudly rather than risk interleaving time across
            # devices. The SPMD group (homogeneous contexts, even batch)
            # handles non-zero batch axes.
            raise NotImplementedError(
                'multi-device per-context execution with a non-leading '
                'batch axis (layouts %s) is not supported; use equal '
                'workloads so the SPMD group handles it, or batch-major '
                'layouts' % [getattr(d, 'layout', 'NCHW')
                             for d in data_shapes])
        for (name, shape), axis in zip(
                [(d.name, d.shape) if isinstance(d, DataDesc) else d
                 for d in data_shapes], major_axis):
            if axis == -1:
                continue
            batch_size = shape[axis]
            if self.batch_size is not None:
                assert batch_size == self.batch_size, \
                    ('all data must have the same batch size: batch_size = %d,'
                     ' but %s has shape %s') % (self.batch_size, name, shape)
            else:
                self.batch_size = batch_size
                total = sum(self.workload[:len(self.contexts)])
                chunks = [self.batch_size * w // total for w in
                          self.workload[:len(self.contexts)]]
                rem = self.batch_size - sum(chunks)
                for i in range(rem):
                    chunks[i] += 1
                starts = np.cumsum([0] + chunks)
                self.slices = [slice(starts[i], starts[i + 1])
                               for i in range(len(self.contexts))]
        return major_axis

    def _sliced_shape(self, shapes, i, major_axis):
        sliced = []
        for (name, shape), axis in zip(
                [(d.name, d.shape) if isinstance(d, DataDesc) else d
                 for d in shapes], major_axis):
            shape = list(shape)
            if axis >= 0:
                shape[axis] = self.slices[i].stop - self.slices[i].start
            sliced.append(DataDesc(name, tuple(shape)))
        return sliced

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        self.data_layouts = self.decide_slices(data_shapes)
        if label_shapes is not None and len(label_shapes) > 0:
            self.label_layouts = self.decide_slices(label_shapes)
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        self.execs = []
        for i in range(len(self.contexts)):
            self.execs.append(self._bind_ith_exec(i, data_shapes, label_shapes,
                                                  shared_group))

        self.data_arrays = [[(self.slices[i], e.arg_dict[name])
                             for i, e in enumerate(self.execs)]
                            for name, _ in [(d.name, d.shape) if isinstance(d, DataDesc)
                                            else d for d in data_shapes]]
        if label_shapes is not None and len(label_shapes) > 0:
            _check_label_args(label_shapes, self.execs[0].arg_dict,
                              self.symbol)
            self.label_arrays = [[(self.slices[i], e.arg_dict[name])
                                  for i, e in enumerate(self.execs)]
                                 for name, _ in [(d.name, d.shape) if isinstance(d, DataDesc)
                                                 else d for d in label_shapes]]
        else:
            self.label_arrays = None

        self.param_arrays = [[e.arg_dict[name] for e in self.execs]
                             for name in self.param_names]
        if self.for_training:
            self.grad_arrays = [[e.grad_dict.get(name) for e in self.execs]
                                for name in self.param_names]
        else:
            self.grad_arrays = [[None] * len(self.execs)
                                for _ in self.param_names]
        data_names = [d.name if isinstance(d, DataDesc) else d[0]
                      for d in data_shapes]
        if self.inputs_need_grad:
            self.input_grad_arrays = [[e.grad_dict[name] for e in self.execs]
                                      for name in data_names]
        self.aux_arrays = [[e.aux_dict[name] for e in self.execs]
                           for name in self.aux_names]

    def _bind_ith_exec(self, i, data_shapes, label_shapes, shared_group):
        """Reference :584 — per-device simple_bind."""
        shapes = self._sliced_shape(data_shapes, i, self.data_layouts)
        if label_shapes is not None and len(label_shapes) > 0:
            shapes = shapes + self._sliced_shape(label_shapes, i,
                                                 self.label_layouts)
        input_shapes = {d.name: d.shape for d in shapes}
        return self.symbol.simple_bind(self.contexts[i],
                                       grad_req=self.grad_req,
                                       **input_shapes)

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and label_shapes == self.label_shapes:
            return
        self.batch_size = None
        self.bind_exec(data_shapes, label_shapes, reshape=True)

    def set_params(self, arg_params, aux_params, allow_extra=False):
        for e in self.execs:
            e.copy_params_from(arg_params, aux_params,
                               allow_extra_params=allow_extra)

    def get_params(self, arg_params, aux_params):
        """Reference :420 — weights averaged... actually copied from dev 0."""
        for name, block in zip(self.param_names, self.param_arrays):
            arg_params[name]._data = block[0]._data
        for name, block in zip(self.aux_names, self.aux_arrays):
            aux_params[name]._data = block[0]._data

    def forward(self, data_batch, is_train=None):
        with _tele.span('exec_group.forward', 'executor'):
            _load_general(data_batch.data, self.data_arrays,
                          self.data_layouts)
            if is_train is None:
                is_train = self.for_training
            if self.label_arrays is not None and data_batch.label:
                _load_general(data_batch.label, self.label_arrays,
                              self.label_layouts)
            for e in self.execs:
                e.forward(is_train=is_train)

    def get_output_shapes(self):
        outputs = self.execs[0].outputs
        shapes = [out.shape for out in outputs]
        concat_shapes = []
        for key, the_shape, axis in zip(self.symbol.list_outputs(), shapes,
                                        self.output_layouts):
            the_shape = list(the_shape)
            if axis >= 0:
                the_shape[axis] = self.batch_size
            concat_shapes.append((key, tuple(the_shape)))
        return concat_shapes

    def get_outputs(self, merge_multi_context=True):
        outputs = [[exec_.outputs[i] for exec_ in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            outputs = _merge_multi_context(outputs, self.output_layouts)
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        if merge_multi_context:
            return _merge_multi_context(self.input_grad_arrays,
                                        self.data_layouts)
        return self.input_grad_arrays

    def backward(self, out_grads=None):
        assert self.for_training, 're-bind with for_training=True to run backward'
        with _tele.span('exec_group.backward', 'executor'):
            self._backward_impl(out_grads)

    def _backward_impl(self, out_grads):
        for i, exec_ in enumerate(self.execs):
            out_grads_slice = None
            if out_grads is not None:
                out_grads_slice = []
                for grad, axis in zip(out_grads, self.output_layouts):
                    if axis >= 0:
                        # slice the head gradient along the OUTPUT's
                        # batch axis (a 'TNC' output's is 1, not 0)
                        idx = [slice(None)] * len(grad.shape)
                        idx[axis] = self.slices[i]
                        og = nd.array(grad.asnumpy()[tuple(idx)],
                                      ctx=self.contexts[i])
                    else:
                        og = grad.as_in_context(self.contexts[i]) \
                            if grad.context != self.contexts[i] else grad
                    out_grads_slice.append(og)
            exec_.backward(out_grads=out_grads_slice)

    def update_metric(self, eval_metric, labels):
        axes = self.label_layouts if self.label_layouts is not None \
            else [0] * len(labels)
        for texec, islice in zip(self.execs, self.slices):
            labels_slice = []
            for label, axis in zip(labels, axes):
                # slice along the label's BATCH axis (TN layouts carry
                # the batch on axis 1, reference executor_group.py:549)
                if axis < 0 or \
                        islice.stop - islice.start == label.shape[axis]:
                    labels_slice.append(label)
                else:
                    idx = [slice(None)] * len(label.shape)
                    idx[axis] = islice
                    labels_slice.append(
                        nd.array(label.asnumpy()[tuple(idx)]))
            _update_metric(eval_metric, self.symbol, self.label_shapes,
                           labels_slice, texec.outputs)

    def install_monitor(self, mon):
        for e in self.execs:
            mon.install(e)


class SPMDExecutorGroup:
    """GSPMD form of DataParallelExecutorGroup: one executor, one mesh.

    The reference's per-batch step is slice → per-device executors →
    KVStore reduce → update → broadcast (§3.3). Here the full-batch
    symbol is bound ONCE and its fused fwd+bwd jit runs over a 1-d
    ``dp`` Mesh of the bound contexts: data/label arrays carry a
    batch-sharded NamedSharding, parameters a replicated one, and XLA's
    partitioner inserts the gradient all-reduce exactly where the
    reference pushed to the KVStore — compiled into the step and
    overlapped with backprop. Gradients surface already merged, so
    Module's update (or kvstore push/pull) runs the optimizer once per
    parameter instead of once per device.

    Exposes the DataParallelExecutorGroup surface Module relies on, with
    single-entry per-device lists (there is one logical executor).
    """

    @staticmethod
    def window_sharding(mesh, ndim):
        """NamedSharding for a (W, batch, ...) window stack fed to a
        compiled multi-step window (the fused fit/eval loops): dp
        shards the BATCH axis (axis 1 of the stack), the window axis
        stays unsharded so lax.scan peels whole dp-sharded batches."""
        return NamedSharding(mesh, P(*((None, 'dp') + (None,) * (ndim - 2))))

    @staticmethod
    def replicate_sharding(mesh):
        """Fully-replicated NamedSharding on ``mesh``. The fused window
        pins its tiny whole-mesh operands (the scan's s32 step-index
        vector, the per-step lr/wd rows) with it: left unannotated,
        GSPMD's partitioner re-derives their placement per use and
        emits '[spmd] Involuntary full rematerialization' stderr
        warnings for each one (the PR 9 known residue) — an explicit
        replicated constraint makes the derivation trivial and the
        warnings disappear."""
        return NamedSharding(mesh, P())

    @staticmethod
    def update_sharding(mesh):
        """NamedSharding for an update-phase leaf (the ZeRO layout of
        arXiv:2004.13336): optimizer-state tensors flattened to 1-D and
        padded to a multiple of dp (parallel/sharding.zero_flatten) are
        row-sharded over the dp axis, so each device owns — and
        updates — exactly 1/dp of every leaf. The companion of
        :meth:`window_sharding` for the fused window's carried state."""
        return NamedSharding(mesh, P('dp'))

    @staticmethod
    def eligible(contexts, workload, batch_size, symbol):
        from ..config import flags as _flags
        _flags.reload('MXTPU_NO_SPMD_MODULE')  # tests toggle it per-case
        if _flags.get('MXTPU_NO_SPMD_MODULE'):
            return False
        if len(contexts) < 2:
            return False
        if len({c.device_type for c in contexts}) != 1:
            return False
        if workload and len(set(workload[:len(contexts)])) != 1:
            return False  # unequal workloads need explicit slices
        if batch_size % len(contexts):
            return False  # NamedSharding needs an even batch split
        # a context that names no device raises here (context.py's rule)
        return len({c.jax_device() for c in contexts}) == len(contexts)

    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req='write', state_names=None):
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.symbol = symbol
        self.contexts = contexts
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.logger = logger
        self.output_layouts = _output_layouts(symbol)

        self.mesh = Mesh(np.array([c.jax_device() for c in contexts]),
                         ('dp',))
        self._replicate = NamedSharding(self.mesh, P())

        self._data_names = [d.name if isinstance(d, DataDesc) else d[0]
                            for d in data_shapes]
        self._label_names = [] if not label_shapes else \
            [d.name if isinstance(d, DataDesc) else d[0] for d in label_shapes]
        if label_shapes:
            _check_label_args(label_shapes,
                              dict.fromkeys(symbol.list_arguments()), symbol)
        # dp shards each input along ITS batch axis (a 'TN' layout puts
        # the batch on axis 1; sharding axis 0 would split time)
        self._batch_axes = {
            (d.name if isinstance(d, DataDesc) else d[0]):
            DataDesc.get_batch_axis(getattr(d, 'layout', 'NCHW'))
            for d in list(data_shapes) + list(label_shapes or [])}

        if grad_req != 'null' and for_training:
            self.grad_req = {}
            for k in self.arg_names:
                if k in self.param_names:
                    self.grad_req[k] = 'null' if k in self.fixed_param_names \
                        else grad_req
                elif k in self._data_names:
                    self.grad_req[k] = grad_req if inputs_need_grad else 'null'
                else:
                    self.grad_req[k] = 'null'
        else:
            self.grad_req = {k: 'null' for k in self.arg_names}

        self.bind_exec(data_shapes, label_shapes, shared_group)

    # -- binding ---------------------------------------------------------
    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        shapes = {(d.name if isinstance(d, DataDesc) else d[0]):
                  (d.shape if isinstance(d, DataDesc) else d[1])
                  for d in data_shapes}
        if label_shapes:
            shapes.update({(d.name if isinstance(d, DataDesc) else d[0]):
                           (d.shape if isinstance(d, DataDesc) else d[1])
                           for d in label_shapes})
        first = data_shapes[0]
        first_axis = max(self._batch_axes.get(
            first.name if isinstance(first, DataDesc) else first[0], 0), 0)
        self.batch_size = (first.shape if isinstance(first, DataDesc)
                           else first[1])[first_axis]
        exec_ = self.symbol.simple_bind(self.contexts[0],
                                        grad_req=self.grad_req, **shapes)
        self.execs = [exec_]
        self.slices = [slice(0, self.batch_size)]
        self.param_arrays = [[exec_.arg_dict[n]] for n in self.param_names]
        self.grad_arrays = [[exec_.grad_dict.get(n)] for n in
                            self.param_names] if self.for_training else \
            [[None] for _ in self.param_names]
        if self.inputs_need_grad:
            self.input_grad_arrays = [[exec_.grad_dict[n]]
                                      for n in self._data_names]
        self.aux_arrays = [[exec_.aux_dict[n]] for n in self.aux_names]

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and \
                label_shapes == self.label_shapes:
            return
        self.bind_exec(data_shapes, label_shapes, reshape=True)

    # -- params ----------------------------------------------------------
    def set_params(self, arg_params, aux_params, allow_extra=False):
        self.execs[0].copy_params_from(arg_params, aux_params,
                                       allow_extra_params=allow_extra)
        self._place_replicated()

    def get_params(self, arg_params, aux_params):
        for name, block in zip(self.param_names, self.param_arrays):
            arg_params[name]._data = block[0]._data
        for name, block in zip(self.aux_names, self.aux_arrays):
            aux_params[name]._data = block[0]._data

    def _place_replicated(self):
        """Pin every non-data array to the replicated mesh sharding so
        GSPMD sees params/aux as broadcast and grads come out psum'd."""
        e = self.execs[0]
        skip = set(self._data_names) | set(self._label_names)
        for name, arr in e.arg_dict.items():
            if name not in skip:
                arr._data = jax.device_put(arr._data, self._replicate)
        for arr in e.aux_dict.values():
            arr._data = jax.device_put(arr._data, self._replicate)

    def _shard_for(self, name, ndim):
        axis = self._batch_axes.get(name, 0)
        if axis < 0 or axis >= ndim:
            return self._replicate
        spec = [None] * ndim
        spec[axis] = 'dp'
        return NamedSharding(self.mesh, P(*spec))

    # -- step ------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        with _tele.span('exec_group.forward', 'executor'):
            e = self.execs[0]
            if is_train is None:
                is_train = self.for_training
            for name, src in zip(self._data_names, data_batch.data):
                e.arg_dict[name]._data = jax.device_put(
                    src._data, self._shard_for(name, src._data.ndim))
            if self._label_names and data_batch.label:
                for name, src in zip(self._label_names, data_batch.label):
                    e.arg_dict[name]._data = jax.device_put(
                        src._data, self._shard_for(name, src._data.ndim))
            self._place_replicated()
            e.forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.for_training, \
            're-bind with for_training=True to run backward'
        with _tele.span('exec_group.backward', 'executor'):
            self.execs[0].backward(out_grads=out_grads)

    # -- results ---------------------------------------------------------
    def get_output_shapes(self):
        return [(key, out.shape) for key, out in
                zip(self.symbol.list_outputs(), self.execs[0].outputs)]

    def get_outputs(self, merge_multi_context=True):
        outs = self.execs[0].outputs
        return list(outs) if merge_multi_context else [[o] for o in outs]

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [g[0] for g in self.input_grad_arrays]
        return grads if merge_multi_context else self.input_grad_arrays

    def update_metric(self, eval_metric, labels):
        _update_metric(eval_metric, self.symbol, self._label_names, labels,
                       self.execs[0].outputs)

    def install_monitor(self, mon):
        for e in self.execs:
            mon.install(e)
