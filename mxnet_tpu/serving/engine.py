"""Serving engine: checkpoint -> pre-compiled bucketed forward programs.

A production endpoint cannot pay a 20-40s XLA compile mid-request, and
it cannot compile one program per observed batch size either — request
sizes are arbitrary. The standard resolution (and this engine's core)
is a BUCKET LADDER: forward programs are compiled once per power-of-two
batch size up to ``MXTPU_SERVE_MAX_BATCH``, every request pads up to
the smallest covering bucket, and pad rows are stripped from the
outputs before they leave the engine. After :meth:`ServingEngine.warmup`
the steady state performs zero compiles — each program registers
through ``telemetry/programs.register``, so the existing
``xla.compiles`` counter is the proof (asserted in
tests/unittest/test_serving.py), and the persistent compile cache makes even
the warmup itself warm across restarts.

The forward program is the read-only single-step twin of
``module/fused_eval.py``'s window body: the bound executor's
``_run_eager`` traced over (params, aux, data, key) with
``is_train=False``, exactly the math ``Module.predict`` runs — a
full-bucket request answers bit-identically to ``Module.predict`` at
the same batch size. Pad rows never influence real rows (the graph is
per-example at inference: BatchNorm uses moving stats), and they are
sliced off on axis 0 exactly where the reference predict slices pad.
"""
import logging
import threading

import numpy as np

import jax

from .. import random as _random
from .. import telemetry as _tele

__all__ = ['ServingEngine', 'bucket_ladder']


def bucket_ladder(max_batch):
    """Powers of two up to ``max_batch`` (inclusive when it is one,
    appended when it is not), ascending — the warm shapes the engine
    compiles and the batcher coalesces toward."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError('max_batch must be >= 1, got %d' % max_batch)
    ladder = []
    b = 1
    while b <= max_batch:
        ladder.append(b)
        b *= 2
    if ladder[-1] != max_batch:
        ladder.append(max_batch)
    return ladder


def _serve_max_batch():
    from ..config import flags
    flags.reload('MXTPU_SERVE_MAX_BATCH')
    return flags.get('MXTPU_SERVE_MAX_BATCH')


class _SingleExecutorEngine:
    """Shared plumbing of the serving engines (:class:`ServingEngine`
    and step_cache's :class:`~.step_cache.DecodeEngine`): module
    eligibility validation, the per-bucket program cache, the cached
    param/aux snapshot (mesh-replicated on SPMD), and host->device
    placement. The eligibility set mirrors fused-eval's, but serving
    RAISES instead of falling back — an engine that silently
    recompiled per shape would violate the latency contract it exists
    for."""

    _default_name = 'model'

    def __init__(self, module, logger=logging, name=None):
        from ..module.module import Module
        from ..module.executor_group import SPMDExecutorGroup
        cls = type(self).__name__
        if type(module) is not Module:
            raise ValueError('%s needs a plain Module, got %s'
                             % (cls, type(module).__name__))
        assert module.binded and module.params_initialized, \
            'bind the module (for_training=False) and load params first'
        eg = module._exec_group
        execs = getattr(eg, 'execs', ())
        if len(execs) != 1:
            raise ValueError('%s needs a single-executor module (one '
                             'context, or an SPMD group)' % cls)
        e = execs[0]
        if e._use_staged() or e._monitor is not None:
            raise ValueError('%s cannot serve a staged/monitored module'
                             % cls)
        self.module = module
        self._exec = e
        self._run = e._run_eager
        self._arg_names = list(e._prog.arg_names)
        self._aux_names = list(e._prog.aux_names)
        self._mesh = eg.mesh if isinstance(eg, SPMDExecutorGroup) else None
        self._descs = {d.name: d for d in module.data_shapes}
        from ..telemetry.programs import scope_name
        self.name = name or scope_name(
            getattr(module._symbol, 'name', None) or self._default_name)
        self._programs = {}        # bucket -> (program, fixed_names)
        self._snap = None          # cached (fixed, aux) param snapshot
        self._snap_lock = threading.Lock()
        self.logger = logger

    def _program(self, bucket):
        entry = self._programs.get(bucket)
        if entry is None:
            with _tele.span('serve.build', 'serve'):
                entry = self._build_program(bucket)
            self._programs[bucket] = entry
        return entry

    def _snapshot(self, fixed_names):
        """Param/aux arrays in program order, cached — serving params
        are immutable between :meth:`refresh_params` calls, so the
        snapshot (and any SPMD re-placement) is paid once, not per
        request."""
        with self._snap_lock:
            if self._snap is None:
                e = self._exec
                fixed = tuple(e.arg_dict[n]._data for n in fixed_names)
                aux = tuple(e.aux_dict[n]._data for n in self._aux_names)
                if self._mesh is not None:
                    from ..module.window_pipeline import place_replicated
                    fixed, aux = place_replicated(self._mesh, fixed, aux)
                self._snap = (fixed, aux)
            return self._snap

    def refresh_params(self):
        """Drop the cached param snapshot (after set_params / a hot
        reload); the next dispatch re-reads the executor's arrays.
        Programs stay warm — the signature (shape/dtype/sharding) is
        unchanged, so no recompile happens."""
        with self._snap_lock:
            self._snap = None

    def _place(self, stack):
        if self._mesh is None:
            return jax.device_put(stack, self._exec._ctx.jax_device())
        # replicated on the mesh: buckets smaller than dp need not
        # divide, and the per-example forward is correct either way
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(stack, NamedSharding(self._mesh, P()))

    def _desc_dtype(self, n):
        return getattr(self._descs[n], 'dtype', None) or np.float32


class ServingEngine(_SingleExecutorEngine):
    """Bucketed, pre-compilable inference over one bound Module.

    The module must be plain (single executor, not staged, no monitor)
    and bound ``for_training=False`` at the largest bucket's batch
    size with parameters loaded.
    """

    def __init__(self, module, max_batch=None, logger=logging, name=None):
        super().__init__(module, logger=logger, name=name)
        self._data_names = list(module._data_names)
        self.max_batch = int(max_batch) if max_batch else _serve_max_batch()
        self.buckets = bucket_ladder(self.max_batch)
        self.output_names = list(module._output_names)
        self.warmed = False

    # -- checkpoint -> engine ----------------------------------------------
    @classmethod
    def from_checkpoint(cls, prefix, epoch, data_shapes, context,
                        max_batch=None, logger=logging, **module_kwargs):
        """``Module.load`` + inference bind + engine in one step.

        ``data_shapes``: [(name, per_example_shape)] WITHOUT the batch
        dimension — the engine owns batching. ``context`` is the device
        (or device list) to serve from and has no default: a checkpoint
        trained on the chip is not quietly served from the host. Label variables a
        training graph carries (e.g. ``softmax_label``) are bound as
        plain zero arrays, exactly like a predict-bound module
        (``label_names=[]``); the ``is_train=False`` forward never
        reads them."""
        from ..module.module import Module
        data_shapes = [(n, tuple(s)) for n, s in data_shapes]
        max_b = int(max_batch) if max_batch else _serve_max_batch()
        mod = Module.load(prefix, epoch,
                          data_names=[n for n, _ in data_shapes],
                          label_names=[], context=context,
                          logger=logger, **module_kwargs)
        mod.bind(data_shapes=[(n, (max_b,) + s) for n, s in data_shapes],
                 for_training=False)
        return cls(mod, max_batch=max_b, logger=logger)

    # -- programs ----------------------------------------------------------
    def _build_program(self, bucket):
        run = self._run
        arg_pos = {n: i for i, n in enumerate(self._arg_names)}
        data_names = self._data_names
        io_pos = set(arg_pos[n] for n in data_names)
        fixed_names = [n for i, n in enumerate(self._arg_names)
                       if i not in io_pos]

        def fwd(fixed, aux, datas, key):
            full = [None] * len(arg_pos)
            for n, v in zip(fixed_names, fixed):
                full[arg_pos[n]] = v
            for n, v in zip(data_names, datas):
                full[arg_pos[n]] = v
            outs, _ = run(tuple(full), aux, key, False)
            return outs

        from ..module.window_pipeline import registered_jit
        prog = registered_jit('serve.predict[%s][b%d]' % (self.name, bucket),
                              fwd)
        return prog, fixed_names

    def bucket_for(self, rows):
        """Smallest warm bucket covering ``rows`` (chunk first when
        rows exceed the largest bucket)."""
        for b in self.buckets:
            if b >= rows:
                return b
        raise ValueError('rows=%d exceeds the largest bucket %d — '
                         'chunk via dispatch_rows()' % (rows,
                                                        self.buckets[-1]))

    # -- dispatch ----------------------------------------------------------
    def _check_and_cast(self, arrays):
        if not isinstance(arrays, (list, tuple)):
            arrays = [arrays]
        if len(arrays) != len(self._data_names):
            raise ValueError('expected %d input arrays (%s), got %d'
                             % (len(self._data_names),
                                ', '.join(self._data_names), len(arrays)))
        out = []
        for n, a in zip(self._data_names, arrays):
            desc = self._descs[n]
            a = np.asarray(a, dtype=self._desc_dtype(n))
            want = tuple(desc.shape[1:])
            if tuple(a.shape[1:]) != want:
                raise ValueError('input %r: per-example shape %s does not '
                                 'match the bound %s'
                                 % (n, tuple(a.shape[1:]), want))
            out.append(a)
        rows = out[0].shape[0]
        if rows == 0:
            raise ValueError('empty request (0 rows)')
        if any(a.shape[0] != rows for a in out):
            raise ValueError('input arrays disagree on the row count')
        return out, rows

    def _dispatch_chunk(self, arrays, rows, timings=None):
        import time as _time
        bucket = self.bucket_for(rows)
        prog, fixed_names = self._program(bucket)
        fixed, aux = self._snapshot(fixed_names)
        t0 = _time.perf_counter()
        padded = []
        for a in arrays:
            if rows < bucket:
                a = np.concatenate(
                    [a, np.zeros((bucket - rows,) + a.shape[1:], a.dtype)])
            # device_put takes the host array directly — one transfer,
            # not a default-device stage + re-place
            padded.append(self._place(a))
        t1 = _time.perf_counter()
        _tele.histogram('serve.pad').observe((t1 - t0) * 1e3)
        with _tele.span('serve.dispatch', 'serve'):
            pieces = prog(fixed, aux, tuple(padded), _random.next_key())
        if timings is not None:
            timings['pad_ms'] = timings.get('pad_ms', 0.0) \
                + (t1 - t0) * 1e3
            timings['dispatch_ms'] = timings.get('dispatch_ms', 0.0) \
                + (_time.perf_counter() - t1) * 1e3
        return pieces, rows, bucket

    def dispatch_rows(self, arrays, timings=None):
        """Asynchronously dispatch ``arrays`` (row counts beyond the
        largest bucket are chunked across several device calls).
        Returns a list of (device_outputs, rows, bucket) chunks —
        device compute proceeds while the caller does host work; hand
        the chunks to :meth:`fetch_chunks` for the one blocking
        device->host fetch. ``timings`` (a dict, optional) accumulates
        the host-measured ``pad_ms`` / ``dispatch_ms`` for the caller's
        request-trace breakdown."""
        arrays, rows = self._check_and_cast(arrays)
        chunks = []
        off = 0
        while off < rows:
            take = min(rows - off, self.buckets[-1])
            chunks.append(self._dispatch_chunk(
                [a[off:off + take] for a in arrays], take,
                timings=timings))
            off += take
        return chunks

    def fetch_chunks(self, chunks, timings=None):
        """Fetch + pad-strip the chunks of one :meth:`dispatch_rows`
        call back into host arrays: one np list per output, rows in
        request order, pad rows sliced off axis 0 exactly where
        ``Module.predict`` slices the iterator pad. ``timings``
        accumulates the blocking ``fetch_ms``."""
        import time as _time
        per_out = None
        t0 = _time.perf_counter()
        with _tele.span('serve.fetch', 'serve'):
            for pieces, rows, _bucket in chunks:
                host = [np.asarray(o)[:rows] for o in pieces]
                if per_out is None:
                    per_out = [[h] for h in host]
                else:
                    for acc, h in zip(per_out, host):
                        acc.append(h)
        if timings is not None:
            timings['fetch_ms'] = timings.get('fetch_ms', 0.0) \
                + (_time.perf_counter() - t0) * 1e3
        return [np.concatenate(parts) if len(parts) > 1 else parts[0]
                for parts in per_out]

    def infer(self, arrays):
        """Synchronous predict: pad-to-bucket, dispatch, strip. Returns
        the list of output arrays (len == number of graph outputs),
        each with exactly the request's row count."""
        return self.fetch_chunks(self.dispatch_rows(arrays))

    # -- warmup ------------------------------------------------------------
    def warmup(self, buckets=None):
        """Compile (or load from the persistent compile cache) every bucket's
        program and run each once, so the serving steady state performs
        zero compiles — the `xla.compiles` counter is flat afterwards.
        Returns the number of programs warmed."""
        warmed = 0
        for b in (buckets or self.buckets):
            zeros = []
            for n in self._data_names:
                desc = self._descs[n]
                zeros.append(np.zeros((b,) + tuple(desc.shape[1:]),
                                      dtype=self._desc_dtype(n)))
            chunk = self._dispatch_chunk(zeros, b)
            self.fetch_chunks([chunk])     # block: the compile is done
            warmed += 1
        self.warmed = True
        _tele.gauge('serve.buckets_warm').set(warmed)
        self.logger.info('serving engine %s: %d bucket programs warm '
                         '(ladder %s)', self.name, warmed, self.buckets)
        return warmed

    def describe(self):
        """The /models payload for this engine."""
        return {
            'name': self.name,
            'buckets': list(self.buckets),
            'max_batch': self.max_batch,
            'inputs': [{'name': n,
                        'shape': list(self._descs[n].shape[1:]),
                        'dtype': str(np.dtype(self._desc_dtype(n)))}
                       for n in self._data_names],
            'outputs': list(self.output_names),
            'warmed': bool(self.warmed),
        }
