"""Fused inference/evaluation fast path for score / predict / iter_predict.

Reference base_module.py:204 (score) and :292 (predict) run one
synchronous forward + one device->host copy per batch. On a TPU each
dispatch and each fetch is a host round trip to the device, which
caps eval throughput exactly the way the per-batch train loop capped
fit (module/fused_fit.py) — the dispatch-bound pattern whole-program
compilation kills (TVM arXiv:1802.04799, Julia->TPU arXiv:1810.09868:
hand XLA a large region once, not a kernel per batch). This module
compiles a WINDOW of W forward steps into ONE XLA computation via
lax.scan — the read-only twin of FusedFitLoop — behind the unchanged
score/predict/iter_predict APIs:

- score: Accuracy / TopKAccuracy / CrossEntropy (and composites of
  them) are accumulated from in-graph sufficient statistics packed
  into one vector per step — a single host fetch per window. ANY other
  metric takes stacked-output mode: the window ships the per-step
  outputs (still one fetch per window) and eval_metric.update runs per
  batch on the host exactly as the reference loop would. Metric values
  and batch_end_callback cadence match the reference loop (callbacks
  fire in a burst after each window — the one observable difference);
- predict / iter_predict: the window returns the stacked per-step
  outputs; ONE host fetch per window replaces a per-batch ``.copy()``
  + device->host round-trip, then pad rows are trimmed per batch on
  the host exactly where the reference slices them (axis 0,
  ``out[:shape[0]-pad]``) before merging;
- batches are snapshotted at draw time and stacked/uploaded through
  the shared :class:`~.window_pipeline.WindowPipeline` — window k+1's
  stack + host->device transfer run on a side thread while window k
  computes on device (MXTPU_FUSED_EVAL_PREFETCH=0 restores the serial
  order);
- tail batches (< window, or a ``num_batch`` remainder) run the
  reference per-batch path on batches rebuilt from the draw-time
  snapshots, so buffer-reusing iterators stay correct;
- forward-only means nothing is written back: parameters and aux
  (BatchNorm moving stats) are read-only, matching the reference's
  ``is_train=False`` forward.

Eligibility (build() returns None -> the reference per-batch loop runs,
mirroring FusedFitLoop.build_cached's silent fallback): plain Module,
one executor (single context or SPMD group), non-staged graph, no
monitor, inferable shapes; stacked-output modes additionally cap the
window's output footprint. Toggles: MXTPU_FUSED_EVAL=0 disables;
MXTPU_EVAL_STEPS_PER_CALL sets W (default 32 on TPU, 4 elsewhere).
"""
import logging

import numpy as np

import jax
import jax.numpy as jnp

from .. import random as _random
from .. import telemetry as _tele
from ..ndarray.ndarray import from_jax
from .window_pipeline import (WindowPipeline, health_sentinel, host_wrap,
                              module_platform, plan_metric, registered_jit,
                              window_bisect, window_size)

__all__ = ['FusedEvalLoop']

# stacked-output modes ship W batches of outputs per fetch; bound the
# device-side footprint the same way the fit loop's host-metric mode does
_OUT_STACK_CAP = 256 * 1024 * 1024


def _eval_window(module):
    return window_size(module, 'MXTPU_EVAL_STEPS_PER_CALL')


class FusedEvalLoop:
    """One compiled W-step forward window driving score/predict."""

    def __init__(self, module, children, stat_fns, window, kind='eval'):
        self.module = module
        self.children = children   # leaf metrics fed by in-graph stats
        self.stat_fns = stat_fns   # None => stacked-output mode
        self.window = window
        self._programs = {}
        e = module._exec_group.execs[0]
        self._exec = e
        self._run = e._run_eager
        from ..telemetry.programs import scope_name
        # score and predict build separate loop instances (separate
        # cache slots) compiling different programs — give each its own
        # registrar row so neither masks the other's cost/memory record
        self._prog_name = 'fused_eval.%s[%s]' % (kind, scope_name(
            getattr(module._symbol, 'name', None) or 'graph'))
        self._arg_names = list(e._prog.arg_names)
        self._aux_names = list(e._prog.aux_names)
        from .executor_group import SPMDExecutorGroup
        self._mesh = module._exec_group.mesh \
            if isinstance(module._exec_group, SPMDExecutorGroup) else None
        self._pipe = WindowPipeline(window,
                                    device_fn=lambda: e._ctx.jax_device(),
                                    mesh=self._mesh,
                                    span_prefix='fused_eval')
        # training-health sentinels (per-output finite flags only — a
        # forward window has no grads/updates); None = window traced
        # byte-identical to the plain form
        self._health_fn = health_sentinel()

    # -- reuse across score()/predict() calls ------------------------------
    def _rebind_metric(self, eval_metric):
        from .window_pipeline import rebind_children
        self.children = rebind_children(eval_metric, self.children)

    @classmethod
    def build_cached(cls, module, eval_metric, logger=logging):
        """build(), but reuse the previous call's loop — with its
        compiled window programs — when everything the traced window
        depends on is unchanged: same bound executor, window size, and
        (for score) an equal-config metric. ``eval_metric=None`` is the
        predict/iter_predict form. Score and predict loops cache in
        separate slots, so a score-between-epochs driver that also
        predicts never thrashes either program set."""
        from ..config import flags
        flags.reload('MXTPU_FUSED_EVAL')
        if not flags.get('MXTPU_FUSED_EVAL'):
            module.__dict__.pop('_fused_eval_cache', None)
            return None
        kind = 'score' if eval_metric is not None else 'predict'
        eg = getattr(module, '_exec_group', None)
        execs = getattr(eg, 'execs', None) or []
        sig = None
        if len(execs) == 1 and execs[0]._monitor is None \
                and not execs[0]._use_staged():
            # a monitor installed (or staging forced) between calls
            # must invalidate reuse the same way build() rejects it
            if eval_metric is None:
                msig = '<predict>'
            else:
                from .fused_fit import FusedFitLoop
                msig = FusedFitLoop._metric_sig(eval_metric)
            if msig is not None:
                # the health sentinels are traced INTO the window
                # program — flipping MXTPU_HEALTH between calls must
                # rebuild the loop
                from ..telemetry import health as _health
                sig = (id(execs[0]), _eval_window(module), msig,
                       bool(_health.enabled()))
        cache = module.__dict__.get('_fused_eval_cache')
        if sig is None:
            # unsignable (monitor/staged/multi-exec, or a metric whose
            # get_config raises): an uncached loop would re-trace and
            # re-compile the window EVERY score() call — strictly worse
            # than the per-batch loop it was built to beat. Fall back.
            if cache is not None:
                cache.pop(kind, None)
            return None
        cached = cache.get(kind) if cache is not None else None
        if cached is not None and cached[0] == sig:
            loop = cached[1]
            if eval_metric is not None:
                loop._rebind_metric(eval_metric)
            return loop
        loop = cls.build(module, eval_metric, logger=logger)
        if loop is not None:
            module.__dict__.setdefault('_fused_eval_cache', {})[kind] = \
                (sig, loop)
        elif cache is not None:
            cache.pop(kind, None)
        return loop

    # -- eligibility -------------------------------------------------------
    @staticmethod
    def build(module, eval_metric, logger=logging):
        from ..config import flags
        flags.reload('MXTPU_FUSED_EVAL')
        if not flags.get('MXTPU_FUSED_EVAL'):
            return None
        from .module import Module
        if type(module) is not Module:
            return None
        eg = module._exec_group
        if len(getattr(eg, 'execs', ())) != 1:
            return None
        e = eg.execs[0]
        if e._use_staged() or e._monitor is not None:
            return None
        shapes = {d.name: d.shape for d in
                  list(module.data_shapes) + list(module.label_shapes or [])}
        try:
            _, out_shapes, _ = module._symbol.infer_shape(**shapes)
        except Exception:  # noqa: BLE001 — undecidable shapes: fall back
            return None
        if out_shapes is None:
            return None
        window = _eval_window(module)
        children, fns = None, None
        if eval_metric is not None:
            # plan_metric also enforces the stat fns' output/label
            # geometry; other geometries use stacked-output mode, whose
            # host-side eval_metric.update is reference-exact
            plan = plan_metric(eval_metric, out_shapes,
                               module._label_names,
                               module._symbol.list_outputs())
            if plan is not None:
                children, fns = plan
        if fns is None:
            # stacked-output mode (predict, and score with an unplanned
            # metric): W stacked fp32 outputs must stay under the
            # device-memory cap
            est = 4 * window * sum(
                int(np.prod(s)) for s in out_shapes if s)
            if est > _OUT_STACK_CAP:
                return None
        loop = FusedEvalLoop(module, children, fns, window,
                             kind='score' if eval_metric is not None
                             else 'predict')
        logger.info('fused eval fast path active: %d steps/device-call%s',
                    window,
                    '' if fns is not None else ' (stacked-output mode)')
        return loop

    # -- program -----------------------------------------------------------
    def _program(self, snaps):
        """Compiled window for the drawn batches' shapes. One program
        per (shapes, labels-present) signature; everything else —
        params, aux, RNG key — enters traced."""
        has_labels = len(snaps[0][1]) > 0
        shapes_key = tuple((tuple(a.shape), str(a.dtype))
                           for a in snaps[0][0] + snaps[0][1])
        key = (has_labels, shapes_key)
        entry = self._programs.get(key)
        if entry is None:
            with _tele.span('fused_eval.build', 'fused_eval'):
                entry = self._build_program(has_labels)
            self._programs[key] = entry
            # same-key rebuilds only happen when the program dict was
            # torn down; the storm detector keys on the SHAPES
            _tele.xla.note_retrace(('fused_eval.window', shapes_key))
        return entry

    def _build_program(self, has_labels):
        run = self._run
        arg_pos = {n: i for i, n in enumerate(self._arg_names)}
        data_names = list(self.module._data_names)
        label_names = list(self.module._label_names) if has_labels else []
        # a label that is an argument of the bound graph is fed into it
        # (a predict-bound module may carry label args as plain zeros —
        # the reference forward loads labels only when both sides have
        # them); labels the graph does not consume still reach the
        # metric stat fns through the scan xs
        fed_pairs = [(li, arg_pos[n]) for li, n in enumerate(label_names)
                     if n in arg_pos]
        io_pos = set(arg_pos[n] for n in data_names) | \
            set(ai for _, ai in fed_pairs)
        fixed_names = [n for i, n in enumerate(self._arg_names)
                       if i not in io_pos]
        stat_fns = self.stat_fns
        health_fn = self._health_fn
        W = self.window

        def window_fn(fixed, aux, data_stack, label_stack, key):
            def body(carry, xs):
                step_i, datas, labels = xs
                k = jax.random.fold_in(key, step_i)
                full = [None] * len(arg_pos)
                for n, v in zip(fixed_names, fixed):
                    full[arg_pos[n]] = v
                for n, v in zip(data_names, datas):
                    full[arg_pos[n]] = v
                for li, ai in fed_pairs:
                    full[ai] = labels[li]
                outs, _ = run(tuple(full), aux, k, False)
                if stat_fns is not None:
                    # all metric stats packed into ONE vector per step
                    # so the host needs a single fetch per window
                    ys = jnp.stack([v for fn in stat_fns
                                    for v in fn(outs, labels)])
                else:
                    # stacked-output mode: scan stacks the per-step
                    # outputs into (W, ...) per output
                    ys = outs
                if health_fn is not None:
                    # per-step finite flags ride the scan ys — home in
                    # the window's existing single fetch
                    ys = (ys, health_fn(outs))
                return carry, ys

            # XLA:CPU parallelizes poorly inside while-loop bodies: the
            # rolled scan ran a ResNet-50 window ~as slow as (112px,
            # f32) or slower than (224px, bf16) per-batch forwards,
            # while the fully unrolled window is ~2.3x FASTER than
            # per-batch — XLA fuses/parallelizes across steps. TPU
            # keeps the rolled form: at W=32 unrolling multiplies
            # compile time for no dispatch win.
            unroll = W if module_platform(self.module) != 'tpu' else 1
            _, ys = jax.lax.scan(
                body, 0, (jnp.arange(W), data_stack, label_stack),
                unroll=unroll)
            return ys

        # no donation: eval mutates nothing — params/aux stay live for
        # the next window and for the module's own per-batch paths.
        # registered_jit routes the compile through the telemetry
        # program registrar (cost/memory analysis per program)
        return registered_jit(self._prog_name, window_fn), fixed_names

    def _snapshot(self, fixed_names):
        """Current parameter/aux arrays in program order, mesh-
        replicated on an SPMD group (window_pipeline.place_replicated,
        shared with the fit loop)."""
        from .window_pipeline import place_replicated
        e = self._exec
        fixed = tuple(e.arg_dict[n]._data for n in fixed_names)
        aux = tuple(e.aux_dict[n]._data for n in self._aux_names)
        if self._mesh is not None:
            fixed, aux = place_replicated(self._mesh, fixed, aux)
        return fixed, aux

    def _pool(self):
        from ..config import flags
        return self._pipe.pool() \
            if flags.get('MXTPU_FUSED_EVAL_PREFETCH') else None

    def _rebuild_batch(self, snap):
        """Reference-path DataBatch from a draw-time snapshot (the
        iterator's own batch buffers may have been overwritten by
        later draws)."""
        from ..io import DataBatch
        ds, ls, pad, idx = snap
        ctx = self._exec._ctx
        return DataBatch(data=[from_jax(d, ctx) for d in ds],
                         label=[from_jax(l, ctx) for l in ls],
                         pad=pad, index=idx)

    # -- the shared window drive -------------------------------------------
    def _drive(self, eval_data, num_batch, snap_labels=False):
        """Drive the pipelined window loop once for score AND predict:
        yields ('window', pieces, win_snaps, labels_snap, win) per
        resolved window (win: the number its spans carry) and ('tail',
        rebuilt_batch, snap, None, None) per remaining batch. Window
        results surface ONE WINDOW LATE by design — the
        consumer's host fetch at the yield point overlaps the next
        window's device compute and side-thread upload; values and
        per-batch cadence are unchanged."""
        it = iter(eval_data)
        pipe = self._pipe
        pool = self._pool()
        drawn = 0
        pending = None

        def collect():
            nonlocal drawn
            lim = None if num_batch is None else num_batch - drawn
            batches, snaps, win = pipe.collect(it, limit=lim)
            drawn += len(batches)
            return batches, snaps, win

        batches, snaps, win = collect()
        fut = pipe.start_put(snaps, pool, win) \
            if len(batches) == self.window else None
        try:
            while len(batches) == self.window:
                window_fn, fixed_names = self._program(snaps)
                labels_snap = None
                if snap_labels:
                    # stacked-output score: keep per-batch label
                    # wrappers from the draw-time snapshots for the
                    # deferred eval_metric.update
                    labels_snap = [[from_jax(l, self._exec._ctx)
                                    for l in ls] for _, ls, _, _ in snaps]
                fixed, aux = self._snapshot(fixed_names)
                with _tele.span('fused_eval.put', 'fused_eval', win=win):
                    data_stack, label_stack = fut()
                with _tele.span('fused_eval.dispatch', 'fused_eval',
                                win=win):
                    pieces = window_fn(fixed, aux, data_stack, label_stack,
                                       _random.next_key())
                _tele.counter('fused_eval.windows').inc()
                _tele.counter('eval.batches').inc(self.window)
                # hang-watchdog progress mark: eval windows count too,
                # or a long between-epoch score() would false-trip it
                _tele.watchdog.note_progress('fused_eval.window')
                # dispatch is async: draw the NEXT window (its stack +
                # transfer start on the side thread), then hand the
                # PREVIOUS window to the consumer while this one
                # computes
                dispatched = (pieces, snaps, labels_snap, win)
                batches, snaps, win = collect()
                fut = pipe.start_put(snaps, pool, win) \
                    if len(batches) == self.window else None
                if pending is not None:
                    yield ('window',) + pending
                pending = dispatched
        except Exception as e:
            # RESOURCE_EXHAUSTED in the upload/dispatch drive: dump the
            # per-program memory breakdown (no-op otherwise)
            _tele.programs.maybe_oom_report(e)
            raise
        finally:
            # drain an in-flight prefetch before the cache teardown (or
            # an exception/close unwind) can race the side thread
            if pool is not None:
                WindowPipeline.drain(fut)
            pipe.drop_cache()
        if pending is not None:
            yield ('window',) + pending
        for snap in snaps:
            # tail (< window, or a num_batch remainder): reference
            # per-batch path on snapshot-rebuilt batches
            yield ('tail', self._rebuild_batch(snap), snap, None, None)

    def _note_window_health(self, hrows, win_snaps, nbatch):
        """Check a fetched (W, k) sentinel matrix (no-op when the
        sentinels are off): exact-step attribution + the staged-path
        bisect on the offending batch's snapshot, is_train=False."""
        if hrows is None:
            return
        _tele.health.note_window(
            hrows, source='fused_eval',
            nbatch_base=nbatch, has_grads=False,
            bisect=window_bisect(self._exec,
                                 list(self.module._data_names),
                                 list(self.module._label_names),
                                 win_snaps, False))

    # -- score -------------------------------------------------------------
    def run_score(self, eval_data, eval_metric, num_batch,
                  batch_end_callback, epoch):
        """Windowed score pass; returns the number of batches consumed
        (the reference's actual_num_batch)."""
        from ..model import BatchEndParam
        from .base_module import _as_list

        m = self.module
        _tele.gauge('fused_eval.steps_per_call').set(self.window)
        host_nd = host_wrap(self._exec._ctx)
        nbatch = 0

        def fire_callback(nbatch):
            if batch_end_callback is not None:
                p = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                  eval_metric=eval_metric, locals=locals())
                for cb in _as_list(batch_end_callback):
                    cb(p)

        for kind, a, b, labels_w, win in self._drive(
                eval_data, num_batch, snap_labels=self.stat_fns is None):
            if kind == 'tail':
                sb = a
                with _tele.span('eval.dispatch', 'eval'):
                    m.forward(sb, is_train=False)
                with _tele.span('eval.metric', 'eval'):
                    m.update_metric(eval_metric, sb.label)
                _tele.counter('eval.batches').inc()
                fire_callback(nbatch)
                nbatch += 1
                continue
            # one host fetch for the window's results, then exact
            # per-batch metric application + callbacks (the fit loop's
            # deferred-apply shape)
            pieces = a
            hmat = None
            if self._health_fn is not None:
                pieces, hrows = pieces
            with _tele.span('fused_eval.fetch', 'fused_eval', win=win):
                if self.stat_fns is not None:
                    host = np.asarray(pieces)      # (W, 2 * n_metrics)
                    steps = host.shape[0]
                else:
                    outs_host = [np.asarray(o) for o in pieces]  # (W, ...)
                    steps = outs_host[0].shape[0]
                if self._health_fn is not None:
                    hmat = np.asarray(hrows)
            self._note_window_health(hmat, b, nbatch)
            for i in range(steps):
                if self.stat_fns is not None:
                    for j, child in enumerate(self.children):
                        child.sum_metric += float(host[i, 2 * j])
                        child.num_inst += int(host[i, 2 * j + 1])
                else:
                    preds = [host_nd(o[i]) for o in outs_host]
                    eval_metric.update(labels_w[i], preds)
                fire_callback(nbatch)
                nbatch += 1
        return nbatch

    # -- predict / iter_predict --------------------------------------------
    def iter_windows(self, eval_data, num_batch):
        """Windowed generator behind predict/iter_predict: yields
        (outputs, nbatch, batch) per BATCH — the iter_predict contract —
        but fetches one stacked window at a time. Windowed outputs are
        HOST-resident NDArrays (carrying the host cpu context — that IS
        the fast path: one fetch per window instead of a per-batch
        device round-trip), already trimmed of pad rows exactly where
        the reference slices them (axis 0). Use as_in_context to move
        one back to the accelerator for further device math."""
        from ..context import cpu as _cpu

        m = self.module
        _tele.gauge('fused_eval.steps_per_call').set(self.window)
        host_nd = host_wrap(_cpu())
        nbatch = 0
        for kind, a, b, _, win in self._drive(eval_data, num_batch):
            if kind == 'tail':
                sb = a
                with _tele.span('eval.dispatch', 'eval'):
                    m.forward(sb, is_train=False)
                pad = sb.pad or 0
                with _tele.span('eval.fetch', 'eval'):
                    # host-resident like the windowed outputs, so a
                    # predict merge never concatenates across devices
                    outputs = [host_nd(out[0:out.shape[0] - pad].asnumpy())
                               for out in m.get_outputs()]
                _tele.counter('eval.batches').inc()
                yield outputs, nbatch, sb
                nbatch += 1
                continue
            pieces, win_snaps = a, b
            hmat = None
            if self._health_fn is not None:
                pieces, hrows = pieces
            # one host fetch for the window's stacked outputs, then
            # per-batch pad trim + wrap
            with _tele.span('fused_eval.fetch', 'fused_eval', win=win):
                outs_host = [np.asarray(o) for o in pieces]   # (W, ...)
                if self._health_fn is not None:
                    hmat = np.asarray(hrows)
            self._note_window_health(hmat, win_snaps, nbatch)
            for i, snap in enumerate(win_snaps):
                pad = snap[2] or 0
                outputs = [host_nd(o[i][0:o[i].shape[0] - pad])
                           for o in outs_host]
                yield outputs, nbatch, self._rebuild_batch(snap)
                nbatch += 1
