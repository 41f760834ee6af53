"""Fused multi-step fast path for Module.fit.

Reference: python/mxnet/module/base_module.py:376 runs one
forward_backward + update + update_metric per batch. On a TPU each of
those is a separate host dispatch (and the metric a device->host fetch),
so the host bounds throughput regardless of chip speed (how much on the
attached chip: not measured). This module compiles a WINDOW of W
training steps into ONE
XLA computation via lax.scan — the standard in-graph-train-loop TPU
pattern — behind the unchanged Module.fit API:

- numerics are identical to the per-batch path: the same _GraphProgram
  runner, the same jax.vjp with all-ones head gradients, and the same
  registered fused update ops with the same attrs. Every optimizer
  whose update() is a single registered op is supported — SGD/ccSGD
  (incl. fp16 master weights), NAG, Adam, RMSProp (both forms), Ftrl —
  via a per-optimizer plan that mirrors its op choice, static attrs,
  state<->op-input order, and host-side lr transform (Adam's bias
  correction);
- metrics: Accuracy / TopKAccuracy / CrossEntropy (and composites of
  them) are computed from in-graph sufficient statistics — per-step
  sums packed into one vector, fetched once per window. ANY other
  metric takes the host-fallback mode: the window returns the stacked
  per-step outputs (one fetch per window) and eval_metric.update runs
  per batch on the host exactly as the reference loop would. Either
  way metric values and batch_end_callback cadence match the
  reference loop exactly (callbacks fire in a burst after each window
  — the one observable difference);
- the learning rate enters the compiled program as a traced (W, n)
  array sampled per batch on the host (no recompile when a scheduler
  moves it), so scheduler boundaries are EXACT even mid-window, and
  Adam's per-update-count bias correction is exact. Bookkeeping
  (num_update) advances per-batch as in the reference;
- grad_req='add' carries the gradient accumulators through the scan
  and writes them back, matching the reference loop's accumulate-
  without-clear semantics.

Eligibility (build() returns None → fit falls back to the reference
loop): plain Module, one executor (single context or SPMD group),
non-staged graph, grad_req 'write'/'add', an optimizer with a plan
(above; multi-precision only for SGD), and a single-process kvstore
(None/'local'/'device' — dist kvstores need per-batch push/pull).

Toggles: MXTPU_FUSED_FIT=0 disables; MXTPU_FIT_STEPS_PER_CALL sets W
(default 32 on TPU, 4 elsewhere).
"""
import logging
import time

import numpy as np

import jax
import jax.numpy as jnp

from .. import faults as _faults
from .. import metric as metric_mod
from .. import optimizer as opt_mod
from .. import profiler as _profiler
from .. import telemetry as _tele
from ..optimizer import _as_clip
from ..executor import mirror_wrap
from ..kvstore import _updater_key
from ..ndarray.ndarray import from_jax
from ..ops import registry as _reg
from .window_pipeline import (WindowPipeline, delta_sentinel,
                              dynamics_sentinel, health_sentinel, host_wrap,
                              hyper_sentinel, moe_sentinel, note_delta_window,
                              note_hyper_window, note_moe_window,
                              registered_jit, window_bisect, window_size)
from .window_pipeline import plan_metric_or_reason as _metric_plan

__all__ = ['FusedFitLoop']


def _window_size(module):
    return window_size(module, 'MXTPU_FIT_STEPS_PER_CALL')


def _shard_update_enabled():
    from ..config import flags
    flags.reload('MXTPU_SHARDED_UPDATE')
    return flags.get('MXTPU_SHARDED_UPDATE')


def _shard_update_requested():
    """True only when MXTPU_SHARDED_UPDATE is EXPLICITLY set truthy in
    the environment. The flag defaults on, so the flag-honesty warning
    below must not fire on every unconfigured single-device run — only
    when someone asked for the sharded update and is not getting it."""
    import os
    return os.environ.get('MXTPU_SHARDED_UPDATE') is not None \
        and _shard_update_enabled()


_replicated_warned = set()


def note_replicated_update(reason, site='fused_fit'):
    """Flag-honesty warning, once per (site, reason) per process:
    MXTPU_SHARDED_UPDATE was explicitly requested but the update about
    to run is REPLICATED — full optimizer state on every device. The
    sharded path engages only on the SPMD fused-fit window with dp > 1
    and the module not opted out (docs/env_vars.md)."""
    key = (site, reason)
    if key in _replicated_warned:
        return
    _replicated_warned.add(key)
    logging.warning(
        'MXTPU_SHARDED_UPDATE is set but the %s update runs REPLICATED '
        '(%s): every device materializes the full optimizer state. The '
        'sharded update (arXiv:2004.13336) engages only inside the SPMD '
        'fused-fit window with dp > 1 — see MXTPU_SHARDED_UPDATE in '
        'docs/env_vars.md', site, reason)


_compress_off_warned = set()


def _warn_compress_off(reason):
    """Flag-honesty warning, once per reason per process:
    MXTPU_GRAD_COMPRESS was set but the gradients about to move are
    UNCOMPRESSED. Quantization rides the ZeRO sharded-update path
    (the flat, dp-sharded leaf is the block layout) — see
    MXTPU_GRAD_COMPRESS in docs/env_vars.md."""
    if reason in _compress_off_warned:
        return
    _compress_off_warned.add(reason)
    logging.warning(
        'MXTPU_GRAD_COMPRESS is set but gradients run UNCOMPRESSED: '
        '%s — see MXTPU_GRAD_COMPRESS in docs/env_vars.md', reason)


def flush_sharded_states(module):
    """Materialize any optimizer-state leaves the module's cached fused
    loop holds in the ZeRO update-phase layout (flat, padded,
    dp-sharded) back to their canonical shapes. Safe no-op when there
    is no cached loop or the sharded update never engaged — callers
    (save/load_optimizer_states, checkpoint restore, the tail path)
    need the canonical layout without caring how training ran."""
    cached = module.__dict__.get('_fused_fit_cache')
    if cached is not None:
        cached[1].flush_zero_states()


def zero_shape_probe(module):
    """``probe(state_wrapper) -> canonical shape | None`` for the
    module's cached fused loop, or None when no loop holds ZeRO-layout
    state. module/checkpointing.py calls the probe on every state
    wrapper it walks: a non-None answer means the wrapper's array is
    currently in the update-phase form (flat, padded, dp-sharded) and
    the checkpoint must record the canonical shape next to it so a
    restore — possibly onto a different dp — can reshape it back."""
    cached = module.__dict__.get('_fused_fit_cache')
    if cached is None:
        return None
    loop = cached[1]
    if loop._zero is None:
        return None
    # snapshot the wrapper->shape map NOW, from the live wrappers the
    # caller is about to walk (id() keys are only valid against these
    # exact objects — see zero_wrapper_shapes)
    shapes = loop.zero_wrapper_shapes()
    if not shapes:
        return None

    def probe(wrapper):
        return shapes.get(id(wrapper))
    # the canonical NamedSharding of the layout: jit outputs carry an
    # equivalent GSPMDSharding that orbax cannot serialize (it warns
    # per leaf per save) — the checkpoint walk relabels onto this
    probe.row = loop._zero['row']
    return probe


def _compress_flag():
    from ..config import flags
    flags.reload('MXTPU_GRAD_COMPRESS')
    return flags.get('MXTPU_GRAD_COMPRESS')


def _compress_block():
    from ..config import flags
    flags.reload('MXTPU_GRAD_COMPRESS_BLOCK')
    return int(flags.get('MXTPU_GRAD_COMPRESS_BLOCK'))


def _mirror_flag():
    from ..config import flags
    flags.reload('MXTPU_BACKWARD_DO_MIRROR')
    return flags.get('MXTPU_BACKWARD_DO_MIRROR')


def _donate_flag():
    from ..config import flags
    flags.reload('MXTPU_FUSED_DONATE')
    return flags.get('MXTPU_FUSED_DONATE')


def _remat_policy():
    from ..config import flags
    flags.reload('MXTPU_REMAT_POLICY')
    return flags.get('MXTPU_REMAT_POLICY')


def _bn_onepass_flag():
    from ..ops.nn import _bn_onepass
    return bool(_bn_onepass())


def _remat_wrap(f):
    """Per-step remat for the window body: MXTPU_REMAT_POLICY
    (none/dots/full) is the roofline block's memory-bound lever,
    scoped to the fused window; empty defers to the process-wide
    MXTPU_BACKWARD_DO_MIRROR via executor.mirror_wrap exactly as
    before (so existing mirror configurations lower unchanged)."""
    policy = _remat_policy()
    if policy == '':
        return mirror_wrap(f)
    if policy == 'none':
        return f
    if policy == 'dots':
        return jax.checkpoint(
            f,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(f)


def _install_donate_filter():
    """The window deliberately donates its input/label stacks for their
    LIFETIME (freed at last in-program use, so window k's and k+1's
    stacks are never both live under the prefetch pipeline) even though
    no output aliases them — jax warns 'Some donated buffers were not
    usable' for exactly that shape of donation, once per compile.
    Filter that one message; every other donation diagnostic stays.
    Installed at every donated window BUILD (not once per process):
    test harnesses save/restore the warnings filter list around each
    case, and a once-guard would leave later builds unfiltered. The
    presence check keeps a long-lived process that rebuilds windows
    many times from growing warnings.filters unboundedly."""
    import warnings
    msg = 'Some donated buffers were not usable'
    for f in warnings.filters:
        if f[0] == 'ignore' and getattr(f[1], 'pattern', None) == msg:
            return
    warnings.filterwarnings('ignore', message=msg)


def _is_half(dt):
    return str(dt) in ('float16', 'bfloat16')


def updater_obj(module):
    """The updater that holds this module's optimizer state (the
    kvstore's when update_on_kvstore, the module's local one
    otherwise)."""
    return module._kvstore._updater if module._update_on_kvstore \
        else module._updater


def updater_keys(module, grad_names):
    """The key each param updates under, matching the unfused path:
    update_on_kvstore pushes by NAME (kvstore._updater keys); the
    local updater uses integer position (model._update_params)."""
    if module._update_on_kvstore:
        return {n: _updater_key(n) for n in grad_names}
    pnames = module._exec_group.param_names
    return {n: pnames.index(n) for n in grad_names}


def _walk_state_wrappers(st):
    """The NDArray state wrappers inside one optimizer-state entry, in
    the same traversal order module/checkpointing._walk_opt uses."""
    if st is None:
        return []
    if isinstance(st, tuple):
        out = []
        for s in st:
            out.extend(_walk_state_wrappers(s))
        return out
    return [st]


def ensure_opt_states(module, grad_names, upd_keys, arg_dict):
    """Pre-create optimizer states through the optimizer's own
    create_state path (the lazy per-batch loop only builds them at the
    first update) so every caller — the fused window, checkpointing,
    save/load_optimizer_states — sees the same structure. Returns the
    updater."""
    upd = updater_obj(module)
    for n in grad_names:
        key = upd_keys[n]
        if key not in upd.states:
            upd.states[key] = \
                module._optimizer.create_state_multi_precision(
                    key, arg_dict[n])
            upd.states_synced[key] = True
    return upd


# ---------------------------------------------------------------------------
# optimizer plans: one registered fused update op per optimizer
# ---------------------------------------------------------------------------

class _OptPlan:
    """Expresses one optimizer's update() as its registered fused op
    inside the scan body, mirroring the NDArray path exactly: op
    choice, static attrs, host-side lr transform (e.g. Adam's bias
    correction), and the state<->op-input-order mapping. All fused
    update ops return (new_weight, *new_states) with states in input
    order, so application in the scan body is generic."""

    supports_mp = False

    def __init__(self, opt):
        self.opt = opt

    _clip = staticmethod(_as_clip)   # None → -1.0 sentinel, shared
    # with the imperative updaters so the convention lives in one place

    def lr_wd(self, index):
        """(lr, wd) the updater would use for the CURRENT update count
        of `index` (call right after _update_count, like update())."""
        return self.opt._get_lr(index), self.opt._get_wd(index)

    def state_arrays(self, st):
        """Optimizer state -> jax arrays in the op's input order."""
        if st is None:
            return []
        if isinstance(st, tuple):
            return [s._data for s in st]
        return [st._data]

    def writeback_state(self, st, arrays):
        if st is None:
            return
        if isinstance(st, tuple):
            for s, a in zip(st, arrays):
                s._data = a
        else:
            st._data = arrays[0]


class _SGDPlan(_OptPlan):
    supports_mp = True

    def mode(self, weight_dtype):
        """Mirrors SGD.update_multi_precision's op choice."""
        mp = self.opt.multi_precision and _is_half(weight_dtype)
        mom = self.opt.momentum != 0.0
        return ('mp_' if mp else '') + ('sgd_mom_update' if mom
                                        else 'sgd_update')

    def static_attrs(self):
        o = self.opt
        return {'momentum': o.momentum, 'rescale_grad': o.rescale_grad,
                'clip_gradient': self._clip(o.clip_gradient)}

    def state_arrays(self, st):
        if isinstance(st, tuple):           # multi-precision (w32, mom)
            w32, mom = st
            if mom is None:
                return [w32._data]          # mp_sgd_update(..., weight32)
            return [mom._data, w32._data]   # mp_sgd_mom_update(.., mom, w32)
        return [st._data] if st is not None else []

    def writeback_state(self, st, arrays):
        if isinstance(st, tuple):
            w32, mom = st
            if mom is None:
                w32._data = arrays[0]
            else:
                mom._data = arrays[0]
                w32._data = arrays[1]
        elif st is not None:
            st._data = arrays[0]


class _NAGPlan(_SGDPlan):
    supports_mp = False

    def mode(self, weight_dtype):
        return ('nag_mom_update' if self.opt.momentum != 0.0
                else 'sgd_update')


class _AdamPlan(_OptPlan):
    def mode(self, weight_dtype):
        return 'adam_update'

    def static_attrs(self):
        o = self.opt
        return {'beta1': o.beta1, 'beta2': o.beta2, 'epsilon': o.epsilon,
                'rescale_grad': o.rescale_grad,
                'clip_gradient': self._clip(o.clip_gradient)}

    def lr_wd(self, index):
        """Adam.update's per-update-count bias correction, folded into
        the per-batch lr row on the host."""
        import math
        o = self.opt
        lr, wd = o._get_lr(index), o._get_wd(index)
        t = o._index_update_count[index]
        lr *= math.sqrt(1. - o.beta2 ** t) / (1. - o.beta1 ** t)
        return lr, wd


class _RMSPropPlan(_OptPlan):
    def mode(self, weight_dtype):
        return ('rmspropalex_update' if self.opt.centered
                else 'rmsprop_update')

    def static_attrs(self):
        o = self.opt
        attrs = {'gamma1': o.gamma1, 'epsilon': o.epsilon,
                 'rescale_grad': o.rescale_grad,
                 'clip_gradient': self._clip(o.clip_gradient),
                 'clip_weights': self._clip(o.clip_weights)}
        if o.centered:
            attrs['gamma2'] = o.gamma2
        return attrs


class _FtrlPlan(_OptPlan):
    def mode(self, weight_dtype):
        return 'ftrl_update'

    def static_attrs(self):
        o = self.opt
        return {'lamda1': o.lamda1, 'beta': o.beta,
                'rescale_grad': o.rescale_grad,
                'clip_gradient': self._clip(o.clip_gradient)}


def _opt_plan(opt):
    """Plan for this optimizer type, or None (→ reference loop).
    Exact-type dispatch: a user subclass with an overridden update()
    must not silently take the base class's fused form."""
    table = {opt_mod.SGD: _SGDPlan, opt_mod.ccSGD: _SGDPlan,
             opt_mod.NAG: _NAGPlan, opt_mod.Adam: _AdamPlan,
             opt_mod.RMSProp: _RMSPropPlan, opt_mod.Ftrl: _FtrlPlan}
    cls = table.get(type(opt))
    return cls(opt) if cls is not None else None


# metric plans (in-graph sufficient statistics) live in
# window_pipeline.plan_metric — shared with the fused eval loop.

_SAID = set()


def _say_once(logger, msg):
    """A warning a process gives once: why a fit left the fused window."""
    if msg not in _SAID:
        _SAID.add(msg)
        logger.warning(msg)


class FusedFitLoop:
    """One compiled W-step train window driving Module's state."""

    def __init__(self, module, children, stat_fns, window, oplan):
        self.module = module
        self.children = children
        self.stat_fns = stat_fns
        self.window = window
        self._programs = {}
        import weakref
        self._defer_fns = weakref.WeakKeyDictionary()

        e = module._exec_group.execs[0]
        self._exec = e
        self._run = e._run_eager
        # program-registrar name for this module's compiled windows
        from ..telemetry.programs import scope_name
        self._prog_name = 'fused_fit.window[%s]' % scope_name(
            getattr(module._symbol, 'name', None) or 'graph')
        self._arg_names = list(e._prog.arg_names)
        self._aux_names = list(e._prog.aux_names)
        self._grad_names = list(e._grad_names)
        io_names = set(module._data_names) | set(module._label_names)
        self._carry_names = [n for n in self._arg_names if n not in io_names]
        self._carry_pos = {n: i for i, n in enumerate(self._carry_names)}
        self._optimizer = module._optimizer
        self._plan = oplan  # the instance build() validated eligibility on
        self._accum = (module._grad_req == 'add')
        # SPMD group: every carried array must live replicated on the
        # mesh and batch stacks sharded over dp, or jit rejects the
        # mixed-device argument set
        from .executor_group import SPMDExecutorGroup
        self._mesh = module._exec_group.mesh \
            if isinstance(module._exec_group, SPMDExecutorGroup) else None
        # the shared draw/stack/upload machinery (module/window_pipeline)
        self._pipe = WindowPipeline(window,
                                    device_fn=lambda: e._ctx.jax_device(),
                                    mesh=self._mesh,
                                    span_prefix='fused_fit',
                                    donate=bool(_donate_flag()))
        # training-health sentinels: captured at loop build (build_cached
        # keys reuse on the flag) — None keeps the traced window
        # byte-identical to the plain form
        self._health_fn = health_sentinel()
        # per-layer training dynamics (telemetry/dynamics): same
        # contract — captured at build, traced into the window, rides
        # the existing single fetch; None = byte-identical program
        self._dyn_fn = dynamics_sentinel()
        # what the ops that keep statistics in an auxiliary state did, step
        # by step: routed expert layers, the residual streams' mixing
        # matrices, the linear-attention layers' recurrent states (same
        # contract: none without telemetry or without such a node)
        self._aux_stats = [(fn, note) for fn, note in (
            (sentinel(module._symbol, self._aux_names), note)
            for sentinel, note in ((moe_sentinel, note_moe_window),
                                   (hyper_sentinel, note_hyper_window),
                                   (delta_sentinel, note_delta_window)))
            if fn is not None]
        self._out_names = list(module._symbol.list_outputs())
        self._last_lr = None   # last sampled lr (run-ledger scalars)
        self._upd_keys = updater_keys(module, self._grad_names)
        self._ensure_states()
        # ZeRO-style sharded weight update (arXiv:2004.13336): on an
        # SPMD group with dp > 1, optimizer state lives in the
        # update-phase form — every leaf flat, zero-padded to a
        # multiple of dp, row-sharded over the dp axis — persistently
        # across windows (donated in place through the scan carry), so
        # per-device optimizer/master-param memory drops by ~dp x.
        # Inside the window body: reduce-scatter(grads) -> shard-local
        # update -> all-gather(params). self._zero is None on the
        # documented fallback (flag off, dp == 1, no mesh, or the
        # module opted out via `module.sharded_update = False`) — the
        # replicated update then lowers byte-identically to the
        # pre-sharding program.
        self._zero = None
        self._update_gauged = False
        dp = int(self._mesh.shape['dp']) if self._mesh is not None else 1
        if _shard_update_enabled() and getattr(module, 'sharded_update',
                                               True) and dp > 1:
            from .executor_group import SPMDExecutorGroup
            self._zero = {'dp': dp,
                          'row': SPMDExecutorGroup.update_sharding(
                              self._mesh)}
            # canonical (pre-flatten) shape/dtype per state leaf, in
            # state_arrays (op-input) order — the snapshot/flush paths
            # and the per-device-bytes gauge key on it
            self._zero_shapes = {
                n: [(tuple(a.shape), a.dtype)
                    for a in self._state_arrays(n)]
                for n in self._grad_names}
            # ...and in raw-tuple WALK order (differs from the op-input
            # order for multi-precision plans): the checkpoint walk
            # traverses the raw state tuples and maps canonical shapes
            # per wrapper (zero_wrapper_shapes) — keyed name+position
            # so it survives wrapper replacement (set_states /
            # load_optimizer_states)
            upd = self._updater_obj()
            self._zero_walk_shapes = {
                n: [tuple(w._data.shape) for w in _walk_state_wrappers(
                    upd.states[self._upd_keys[n]])]
                for n in self._grad_names}
        elif _shard_update_requested():
            note_replicated_update(
                'module opted out (sharded_update=False)'
                if self._mesh is not None and dp > 1
                else 'no SPMD mesh / dp axis is 1')
        # Quantized gradient collectives (MXTPU_GRAD_COMPRESS): the
        # error-feedback residuals live here between windows — one flat
        # leaf per grad in the ZeRO update-phase layout, donated
        # through the scan carry like opt-state leaves. Loop-local on
        # purpose: a restart resets the residual to zero, which costs
        # one step of quantization error and nothing else, so the
        # checkpoint format is untouched.
        self._resid = None
        self._resid_meta = None
        # per-run flip bookkeeping: last window's resolved mode + wall
        # ms, and whether the one-shot 'compression' record fired
        self._cstate = {'mode': None, 'ms': None, 'emitted': False,
                        'windows': 0}
        if _compress_flag() != 'off' and self._zero is None:
            _warn_compress_off(
                'no ZeRO sharded update engaged (the flat dp-sharded '
                'leaf form is the quantization block layout)')

    # -- reuse across fit() calls ------------------------------------------
    @staticmethod
    def _metric_sig(eval_metric):
        """Value signature of the metric configuration (class + every
        distinguishing kwarg: axis/top_k/eps/... all flow through
        EvalMetric._kwargs into get_config). None = unsignable, never
        reuse."""
        if isinstance(eval_metric, metric_mod.CompositeEvalMetric):
            leaves = list(eval_metric.metrics)
        else:
            leaves = [eval_metric]
        try:
            return repr([sorted(m.get_config().items(), key=str)
                         for m in leaves])
        except Exception:  # noqa: BLE001 — custom metric w/o get_config
            return None

    def _rebind_metric(self, eval_metric):
        from .window_pipeline import rebind_children
        self.children = rebind_children(eval_metric, self.children)

    @classmethod
    def build_cached(cls, module, eval_metric, logger=logging):
        """build(), but reuse the previous fit() call's loop — with its
        compiled window programs — when everything the traced window
        depends on is unchanged: same bound executor, same optimizer
        instance, grad_req, kvstore mode, window size, remat/sharding
        flags, and an equal-config metric.

        An epoch-at-a-time driver (fit(begin_epoch=e, num_epoch=e+1)
        in a loop — the resume / eval-between-epochs pattern) otherwise
        pays a full retrace + XLA recompile of the window EVERY call:
        tens of seconds of compile against seconds of compute per
        64-batch ImageNet epoch."""
        from ..config import flags
        flags.reload('MXTPU_FUSED_FIT')
        if not flags.get('MXTPU_FUSED_FIT'):
            # a discarded loop may hold ZeRO-layout optimizer state —
            # materialize it before the reference loop reads it
            flush_sharded_states(module)
            module.__dict__.pop('_fused_fit_cache', None)
            return None
        eg = getattr(module, '_exec_group', None)
        execs = getattr(eg, 'execs', None) or []
        sig = None
        if len(execs) == 1 and execs[0]._monitor is None \
                and not execs[0]._use_staged():
            # a monitor installed (or staging forced) between fit()
            # calls must invalidate reuse the same way build() rejects
            # it — the per-batch reference loop is the one that honors
            # monitor callbacks
            msig = cls._metric_sig(eval_metric)
            if msig is not None:
                sig = (id(execs[0]), id(module._optimizer),
                       module._grad_req,
                       bool(module._update_on_kvstore),
                       getattr(module._kvstore, 'type', None),
                       _window_size(module), bool(_shard_update_enabled()),
                       bool(getattr(module, 'sharded_update', True)),
                       # the compression FLAG + block (not the auto-
                       # resolved mode: an auto flip mid-run is handled
                       # by the per-window program key, not a rebuild)
                       str(_compress_flag()), _compress_block(),
                       str(_mirror_flag()), str(_remat_policy()),
                       bool(_donate_flag()),
                       # BatchNorm's stats form is traced INTO the
                       # window — flipping MXTPU_BN_ONEPASS between
                       # fit() calls must rebuild the loop (a cached
                       # program would silently keep the old math)
                       _bn_onepass_flag(), msig,
                       # the health sentinels are traced INTO the window
                       # program — flipping MXTPU_HEALTH between fit()
                       # calls must rebuild the loop
                       bool(_tele.health.enabled()),
                       # ...and so is the per-layer dynamics matrix
                       bool(_tele.dynamics.enabled()),
                       # ...and the expert layers' statistics
                       bool(_tele.enabled()))
        cached = module.__dict__.get('_fused_fit_cache')
        if cached is not None and sig is not None and cached[0] == sig:
            loop = cached[1]
            loop._rebind_metric(eval_metric)
            return loop
        loop = cls.build(module, eval_metric, logger=logger)
        if loop is None:
            # falling back to the reference per-batch loop: it updates
            # against the canonical state layout
            flush_sharded_states(module)
        if loop is not None and sig is not None:
            module.__dict__['_fused_fit_cache'] = (sig, loop)
        else:
            module.__dict__.pop('_fused_fit_cache', None)
        return loop

    # -- eligibility -------------------------------------------------------
    @staticmethod
    def build(module, eval_metric, logger=logging):
        from ..config import flags
        flags.reload('MXTPU_FUSED_FIT')
        if not flags.get('MXTPU_FUSED_FIT'):
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
        if module._grad_req not in ('write', 'add') \
                or module.inputs_need_grad:
            return None
        opt = module._optimizer
        oplan = _opt_plan(opt)
        if oplan is None:
            return None
        if not oplan.supports_mp and opt.multi_precision and any(
                _is_half(e.arg_dict[n]._data.dtype) for n in e._grad_names):
            return None  # mp master-weight form only planned for SGD
        kv = module._kvstore
        if kv is not None and kv.type not in ('local', 'device'):
            return None
        shapes = {d.name: d.shape for d in
                  list(module.data_shapes) + list(module.label_shapes or [])}
        try:
            _, out_shapes, _ = module._symbol.infer_shape(**shapes)
        except Exception:  # noqa: BLE001 — undecidable shapes: fall back
            return None
        if out_shapes is None:
            return None
        window = _window_size(module)
        # the plan also enforces the stat fns' output/label geometry;
        # other geometries use the host-fallback mode below
        plan, why = _metric_plan(eval_metric, out_shapes,
                                 module._label_names,
                                 module._symbol.list_outputs())
        if plan is not None:
            children, fns = plan
        else:
            # host-fallback metric mode: the window ships the stacked
            # per-step outputs (one fetch per window) and the metric's
            # own update() runs per batch on the host. Bounded: W
            # stacked fp32 outputs must stay under a device-memory cap.
            est = 4 * window * sum(
                int(np.prod(s)) for s in out_shapes if s)
            if est > 256 * 1024 * 1024:
                _say_once(logger, 'fused fit window not taken for %s: the '
                          'metric has no in-graph plan (%s) and the '
                          'host-metric mode would stack %.2f GB of outputs '
                          'a window (cap 0.27): one step a dispatch'
                          % (getattr(module._symbol, 'name', None)
                             or 'the graph', why, est / 1e9))
                return None
            children, fns = None, None
        # a previously-cached loop (about to be replaced) may hold the
        # optimizer state in the ZeRO layout: the new loop must read
        # CANONICAL shapes at construction
        flush_sharded_states(module)
        loop = FusedFitLoop(module, children, fns, window, oplan)
        logger.info('fused fit fast path active: %d steps/device-call%s',
                    loop.window,
                    '' if fns is not None else ' (host-metric mode)')
        return loop

    # -- optimizer state ---------------------------------------------------
    def _updater_obj(self):
        return updater_obj(self.module)

    def _ensure_states(self):
        ensure_opt_states(self.module, self._grad_names, self._upd_keys,
                          self._exec.arg_dict)

    def _state_arrays(self, n):
        st = self._updater_obj().states[self._upd_keys[n]]
        return self._plan.state_arrays(st)

    def _writeback_state(self, n, arrays):
        st = self._updater_obj().states[self._upd_keys[n]]
        self._plan.writeback_state(st, arrays)

    # -- program -----------------------------------------------------------
    def _static_attrs(self):
        """Optimizer-wide attrs that never change across windows (lr/wd
        are dynamic: they enter the compiled program as traced arrays
        so a per-update lr scheduler never forces a recompile)."""
        return self._plan.static_attrs()

    def _sample_window_lr(self):
        """Advance the optimizer's update bookkeeping batch-by-batch
        (exactly as the reference loop's per-batch update() calls
        would) and return (W, n_params) lr/wd arrays holding the value
        the updater would use for EACH batch of the window — scheduler
        boundaries and per-update-count transforms (Adam) are exact
        even mid-window."""
        o = self._optimizer
        n = len(self._grad_names)
        lr = np.empty((self.window, n), np.float32)
        wd = np.empty((self.window, n), np.float32)
        for w in range(self.window):
            for j, name in enumerate(self._grad_names):
                idx = self._upd_keys[name]
                o._update_count(idx)
                lr[w, j], wd[w, j] = self._plan.lr_wd(idx)
        if n:
            self._last_lr = float(lr[-1, 0])
        return lr, wd

    def _mode(self, n):
        """Update-op choice per param, delegated to the optimizer plan."""
        return self._plan.mode(self._exec.arg_dict[n]._data.dtype)

    def _cmode(self):
        """Resolved gradient-compression mode for the NEXT window:
        'off'/'int8'/'bf16'. 'auto' resolves against the cluster
        verdict state (parallel/compression.py), so a sync round that
        classifies the run communication_bound flips this mid-run —
        the mode is part of the per-window program key, so the flip
        rebuilds the window program at the next dispatch. Pinned to
        'off' (warn-once) when the ZeRO update path is not engaged:
        the flat dp-sharded leaf IS the quantization block layout."""
        from ..parallel import compression
        mode = compression.resolved_mode()
        if mode != 'off' and self._zero is None:
            _warn_compress_off(
                'no ZeRO sharded update engaged (the flat dp-sharded '
                'leaf form is the quantization block layout)')
            return 'off'
        return mode

    def _build_program(self, static_attrs, shapes_key, cmode=None):
        run = self._run
        arg_pos = {n: i for i, n in enumerate(self._arg_names)}
        data_names = list(self.module._data_names)
        label_names = list(self.module._label_names)
        carry_names = self._carry_names
        grad_names = self._grad_names
        grad_carry_idx = [self._carry_pos[n] for n in grad_names]
        modes = {n: self._mode(n) for n in grad_names}
        ops = {mode: _reg.get(mode) for mode in set(modes.values())}
        stat_fns = self.stat_fns
        health_fn = self._health_fn
        dyn_fn = self._dyn_fn
        aux_fns = [fn for fn, _ in self._aux_stats]
        accum = self._accum
        W = self.window
        mesh = self._mesh
        defer_fn = self._defer_fn   # traced INTO the program (or None)
        donate = _donate_flag()
        rep_pin = None
        if mesh is not None:
            # tiny whole-mesh operands (the s32 step-index vector, the
            # per-step lr/wd rows) get an explicit replicated pin: left
            # unannotated, GSPMD re-derives their placement per use and
            # prints an '[spmd] Involuntary full rematerialization'
            # stderr warning for each (the PR 9 known residue)
            from .executor_group import SPMDExecutorGroup
            rep_pin = SPMDExecutorGroup.replicate_sharding(mesh)
        shard_update = self._zero is not None
        cmode = self._cmode() if cmode is None else cmode
        compress = shard_update and cmode != 'off'
        if compress:
            # error-feedback quantization of the update-form gradient
            # (parallel/compression.py): the numerics of the EQuARX
            # recipe, applied inside the jitted window; the residual
            # rides the scan carry next to the opt-state leaves
            from ..parallel import compression as _compr
            cblock = _compress_block()
        if shard_update:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..parallel.sharding import zero_flatten, zero_unflatten
            dp = self._zero['dp']
            row = self._zero['row']
            rep = NamedSharding(mesh, P())

            def to_update_form(t):
                """Weight/grad -> the update-phase form: flat, zero-
                padded to a multiple of dp, row-sharded (every leaf
                divides, whatever its shape — the per-leaf padding of
                arXiv:2004.13336). Constraining the GRADIENT here turns
                its all-reduce into a reduce-scatter: each replica
                receives — and updates — only its 1/dp slice."""
                return jax.lax.with_sharding_constraint(
                    zero_flatten(t, dp), row)

            def from_update_form(t, shape):
                """Fresh weight -> canonical shape, replicated: the
                all-gather that hands the next forward a whole param."""
                return jax.lax.with_sharding_constraint(
                    zero_unflatten(t, shape), rep)

            def pin_state(t):
                # optimizer states arrive AND leave in the update-phase
                # form: pinning both body entry and exit keeps the scan
                # carry's sharding in equilibrium (no per-iteration
                # reshard) and the jit outputs dp-sharded — the ZeRO
                # layout the loop holds between windows
                return jax.lax.with_sharding_constraint(t, row)

        def make_body(key):
            def body(carry, xs):
                if compress:
                    params, states, aux, gaccs, resids = carry
                    new_resids = list(resids)
                else:
                    params, states, aux, gaccs = carry
                step_i, datas, labels, lr_row, wd_row = xs
                k = jax.random.fold_in(key, step_i)
                if defer_fn is not None:
                    # deferred device-augment: raw uint8 batch -> the
                    # graph's float input, inside THIS program (zero
                    # per-batch dispatches; iterator's eager mode runs
                    # the identical math per batch)
                    ka = jax.random.fold_in(k, 0x41554721)
                    datas = (defer_fn(datas[0], ka),) + tuple(datas[1:])

                def f(wrt):
                    full = [None] * len(arg_pos)
                    for n, v in zip(carry_names, params):
                        full[arg_pos[n]] = v
                    for n, v in zip(data_names, datas):
                        full[arg_pos[n]] = v
                    for n, v in zip(label_names, labels):
                        full[arg_pos[n]] = v
                    for n, v in zip(grad_names, wrt):
                        full[arg_pos[n]] = v
                    return run(tuple(full), aux, k, True)

                wrt = tuple(params[i] for i in grad_carry_idx)
                (outs, new_aux), vjp = jax.vjp(_remat_wrap(f), wrt)
                heads = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
                zero_aux = tuple(jnp.zeros_like(a) for a in new_aux)
                (grads,) = vjp((heads, zero_aux))
                if accum:
                    # grad_req='add': the reference loop accumulates
                    # into grad buffers and never clears them
                    grads = tuple(ga + g for ga, g in zip(gaccs, grads))
                    gaccs = grads

                new_params = list(params)
                new_states = list(states)

                def update_leaf(j, n):
                    ci = grad_carry_idx[j]
                    attrs = dict(static_attrs)
                    attrs['lr'] = lr_row[j]   # traced: scheduler-safe
                    attrs['wd'] = wd_row[j]
                    w, g = params[ci], grads[j]
                    st = states[j]
                    if shard_update:
                        w_shape = w.shape
                        w, g = to_update_form(w), to_update_form(g)
                        st = tuple(pin_state(s) for s in st)
                    if compress:
                        # quantize -> dequantize the reduced gradient
                        # with error feedback: the dropped precision of
                        # this step re-enters at the next via the
                        # carried residual (convergence gated by the
                        # chaos-lane run_compare e2e, never assumed)
                        g, nr = _compr.ef_roundtrip(g, resids[j], cmode,
                                                    cblock)
                        g = pin_state(g)
                        new_resids[j] = pin_state(nr)
                    # every fused update op returns (w, *states) with
                    # states in input order — application is generic
                    res = ops[modes[n]].fn(attrs, w, g, *st)
                    if not isinstance(res, tuple):
                        res = (res,)
                    # the traced lr/wd scalars are strong f32 where the
                    # imperative path feeds weak python floats: without
                    # this cast a bf16 weight/state promotes to f32 in
                    # the update and the scan carry rejects the dtype
                    # drift (found by the bf16 BN parity tests)
                    ins = (w,) + tuple(st)
                    res = tuple(r.astype(i.dtype)
                                if r.dtype != i.dtype else r
                                for r, i in zip(res, ins))
                    if shard_update:
                        # only the WEIGHT re-gathers (the next forward
                        # needs it whole); optimizer states stay flat +
                        # dp-sharded through the scan carry and out of
                        # the program — the ZeRO layout
                        res = (from_update_form(res[0], w_shape),) + \
                            tuple(pin_state(s) for s in res[1:])
                    new_params[ci] = res[0]
                    if len(res) > 1:
                        new_states[j] = tuple(res[1:])

                # trace-time names for what no symbol node covers, so
                # that the compiled program's scope map (telemetry/
                # programs.py: WINDOW_PARTS) can tell a capture's device
                # time apart: 'update', 'metric', 'sentinel', and
                # 'window' around the scan for whatever is left
                with jax.named_scope('update'):
                    for j, n in enumerate(grad_names):
                        update_leaf(j, n)
                if stat_fns is not None:
                    # all metric stats packed into ONE vector per step
                    # so the host needs a single fetch per window (each
                    # fetch is a host round trip to the device)
                    with jax.named_scope('metric'):
                        ys = jnp.stack([v for fn in stat_fns
                                        for v in fn(outs, labels)])
                else:
                    # host-fallback metric: ship the raw outputs; scan
                    # stacks them into (W, ...) per output
                    ys = outs
                extras = []
                with jax.named_scope('sentinel'):
                    if health_fn is not None:
                        # per-step sentinel vector rides the scan ys — the
                        # (W, k) stack comes home in the window's existing
                        # fetch, so a mid-window NaN keeps its step index
                        extras.append(health_fn(
                            outs, grads=grads,
                            params=tuple(params[i] for i in grad_carry_idx),
                            new_params=tuple(new_params[i]
                                             for i in grad_carry_idx)))
                    if dyn_fn is not None:
                        # per-layer dynamics vector rides the same ys — the
                        # (W, 3n+outs) matrix ships in the SAME single
                        # fetch (no added syncs; counter-asserted in tests)
                        extras.append(dyn_fn(
                            outs, grads=grads,
                            params=tuple(params[i] for i in grad_carry_idx),
                            new_params=tuple(new_params[i]
                                             for i in grad_carry_idx)))
                    extras.extend(fn(new_aux) for fn in aux_fns)
                if extras:
                    ys = (ys, *extras)
                if compress:
                    return (tuple(new_params), tuple(new_states),
                            new_aux, gaccs, tuple(new_resids)), ys
                return (tuple(new_params), tuple(new_states), new_aux,
                        gaccs), ys
            return body

        def make_xs(lr_arr, wd_arr):
            step_idx = jnp.arange(W)
            lr_xs = jnp.asarray(lr_arr)
            wd_xs = jnp.asarray(wd_arr)
            if rep_pin is not None:
                step_idx = jax.lax.with_sharding_constraint(step_idx,
                                                            rep_pin)
                lr_xs = jax.lax.with_sharding_constraint(lr_xs, rep_pin)
                wd_xs = jax.lax.with_sharding_constraint(wd_xs, rep_pin)
            return step_idx, lr_xs, wd_xs

        if compress:
            # the residual tuple is an extra carry member right after
            # gaccs — donated like the other carry leaves, returned in
            # the ZeRO layout for the loop to hold between windows
            def window_fn(params, states, aux, gaccs, resids, data_stack,
                          label_stack, key, lr_arr, wd_arr):
                with jax.named_scope('window'):
                    step_idx, lr_xs, wd_xs = make_xs(lr_arr, wd_arr)
                    (p, s, a, g, r), ys = jax.lax.scan(
                        make_body(key),
                        (params, states, aux, gaccs, resids),
                        (step_idx, data_stack, label_stack, lr_xs, wd_xs))
                return p, s, a, g, r, ys
        else:
            def window_fn(params, states, aux, gaccs, data_stack,
                          label_stack, key, lr_arr, wd_arr):
                with jax.named_scope('window'):
                    step_idx, lr_xs, wd_xs = make_xs(lr_arr, wd_arr)
                    (p, s, a, g), ys = jax.lax.scan(
                        make_body(key), (params, states, aux, gaccs),
                        (step_idx, data_stack, label_stack, lr_xs, wd_xs))
                return p, s, a, g, ys

        # the train-step program of the fused path: its XLA cost
        # analysis (scan body counted once = per-step FLOPs) is the
        # xla.step_flops gauge, through the registrar. Donation
        # (MXTPU_FUSED_DONATE): the param/state/aux/gacc carry aliases
        # in place onto the matching outputs, and the input/label
        # stacks are donated for their lifetime — the runtime frees
        # them at their last in-program use, so the prefetched next
        # window's stacks never coexist with this window's. =0 builds
        # the undonated reference program (bit-exact numerics, parity-
        # tested) for A/B evidence.
        if donate:
            _install_donate_filter()
        if compress:
            donate_idx = (0, 1, 2, 3, 4, 5, 6) if donate else ()
        else:
            donate_idx = (0, 1, 2, 3, 4, 5) if donate else ()
        return registered_jit(
            self._prog_name, window_fn, step_flops=True,
            donate_argnums=donate_idx)

    # -- ZeRO state layout -------------------------------------------------
    def zero_wrapper_shapes(self):
        """{id(state wrapper): canonical shape} for the leaves CURRENTLY
        in the update-phase form, built FRESH from the live updater
        walk on every call: wrapper objects can be replaced under the
        loop (set_states / load_optimizer_states) and CPython recycles
        id() values, so this map must never be cached across calls —
        the checkpoint walk builds it immediately before traversing
        the very same wrappers."""
        if self._zero is None:
            return {}
        from .window_pipeline import is_update_sharded
        row = self._zero['row']
        out = {}
        upd = self._updater_obj()
        for n in self._grad_names:
            ws = _walk_state_wrappers(upd.states[self._upd_keys[n]])
            for w, shape in zip(ws, self._zero_walk_shapes[n]):
                if is_update_sharded(getattr(w, '_data', None), row):
                    out[id(w)] = shape
        return out

    def flush_zero_states(self):
        """Materialize every state leaf held in the ZeRO update-phase
        form back to its canonical shape, replicated on the mesh.
        Runs before anything OUTSIDE the compiled window consumes the
        states — the per-batch tail path, save/load_optimizer_states,
        a checkpoint restore. The next window re-shards lazily
        (place_update_sharded passes converted leaves through), so the
        cost is one gather per excursion, not per window."""
        if self._zero is None:
            return
        from .window_pipeline import is_update_sharded
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.sharding import zero_unflatten
        row = self._zero['row']
        rep = NamedSharding(self._mesh, P())
        for n in self._grad_names:
            arrays = self._state_arrays(n)
            out, changed = [], False
            for a, (shape, _d) in zip(arrays, self._zero_shapes[n]):
                if is_update_sharded(a, row):
                    a = jax.device_put(zero_unflatten(a, shape), rep)
                    changed = True
                out.append(a)
            if changed:
                self._writeback_state(n, out)
        # the gauges must flip AS A PAIR: a flush back to the
        # replicated layout also restores the replicated footprint
        # (a 'replicated' bit next to the 1/dp byte count would be a
        # self-contradictory record)
        _tele.gauge('update.sharded').set(0)
        _tele.gauge('update.opt_state_bytes_per_device').set(int(sum(
            int(np.prod(shape)) * np.dtype(dt).itemsize
            for n in self._grad_names
            for shape, dt in self._zero_shapes[n])))

    def _prepare_tail(self):
        """Restore the per-batch update invariant before tail batches
        run the imperative path: the kvstore machinery keeps its
        update-side arrays (store weights, updater states) on the
        CONTEXT device — its reduce lands merged grads there — while
        the window writeback leaves everything mesh-placed. Only the
        SPMD path needs this; everywhere else the context device IS the
        placement. The next epoch's first window re-shards lazily."""
        if self._mesh is None:
            return
        self.flush_zero_states()
        m = self.module
        if not m._update_on_kvstore:
            # local-updater tail: weights/grads/states all live mesh-
            # replicated (arg_dict pinned at forward, grads from the
            # SPMD backward, states from the window writeback or the
            # flush above) — already co-located
            return
        dev = self._exec._ctx.jax_device()
        upd = self._updater_obj()
        for n in self._grad_names:
            store = m._kvstore._store.get(n)
            if store is not None:
                store._data = jax.device_put(store._data, dev)
            for w in _walk_state_wrappers(upd.states[self._upd_keys[n]]):
                w._data = jax.device_put(w._data, dev)

    def _note_update_gauges(self):
        """Publish the per-device optimizer-state footprint: with the
        sharded update on, the ZeRO layout's exact ceil(n/dp)/device
        bytes; otherwise the full replicated bytes — so a sharded-vs-
        replicated A/B reads the win off one gauge. Published at every
        snapshot (pure shape arithmetic, no device access) so the pair
        of gauges tracks every layout transition — a tail flush zeroes
        them and the next window's re-shard must flip them back."""
        if self._zero is not None:
            from ..parallel.sharding import zero_sharded_bytes
            total = sum(zero_sharded_bytes(shape, dt, self._zero['dp'])
                        for n in self._grad_names
                        for shape, dt in self._zero_shapes[n])
            _tele.gauge('update.sharded').set(1)
            _tele.gauge('update.dp').set(self._zero['dp'])
        elif self._update_gauged:
            return   # replicated layout never transitions
        else:
            total = sum(int(a.nbytes) for n in self._grad_names
                        for a in self._state_arrays(n))
            _tele.gauge('update.sharded').set(0)
        self._update_gauged = True
        _tele.gauge('update.opt_state_bytes_per_device').set(int(total))

    # -- quantized gradient collectives ------------------------------------
    def _resid_specs(self):
        """(name, padded flat length, dtype) per grad leaf in the ZeRO
        update-phase layout — the residual shapes AND the wire-byte
        model's element counts."""
        if self._resid_meta is None:
            from ..parallel.sharding import zero_pad_len
            dp = self._zero['dp']
            meta = []
            for n in self._grad_names:
                a = self._exec.arg_dict[n]._data
                size = int(np.prod(a.shape)) if a.shape else 1
                meta.append((n, zero_pad_len(size, dp), np.dtype(a.dtype)))
            self._resid_meta = meta
        return self._resid_meta

    def _ensure_resids(self):
        """Error-feedback residuals in grad_names order: zeros on first
        use (or after a shape change), row-sharded like the opt-state
        leaves, then carried window to window via the donated call."""
        if self._resid is None:
            self._resid = {}
        row = self._zero['row']
        out = []
        for n, L, dt in self._resid_specs():
            r = self._resid.get(n)
            if r is None or r.shape != (L,):
                r = jax.device_put(np.zeros((L,), dt), row)
            self._resid[n] = r
            out.append(r)
        return tuple(out)

    def _publish_comm_gauges(self, cmode):
        """comm.* gauges for the window just dispatched. The byte count
        is the wire MODEL (comm.bytes_src='modeled'): in global-view
        SPMD the partitioner moves the reduced gradient itself, so the
        gauge is arithmetic over the leaf layout, not a socket counter
        — the kvstore_dist path publishes the measured twin."""
        if not _tele.enabled():
            return
        from ..parallel import compression
        block = _compress_block()
        total = unc = 0
        for _n, L, dt in self._resid_specs():
            total += compression.wire_bytes(L, cmode, block, dt.itemsize)
            unc += compression.wire_bytes(L, 'off', block, dt.itemsize)
        _tele.gauge('comm.bytes_on_wire_per_step').set(int(total))
        _tele.gauge('comm.compression_ratio').set(
            round(unc / max(total, 1), 3))
        _tele.gauge('comm.mode').set(cmode)
        _tele.gauge('comm.bytes_src').set('modeled')

    def _note_compress_window(self, cmode, win_ms):
        """Per-window compression bookkeeping: publish the comm gauges
        and, on the first completed window after a mode flip (the auto
        trigger engaging mid-run), emit the one-shot 'compression'
        JSONL record carrying the before/after per-step wall delta."""
        st = self._cstate
        st['windows'] += 1
        self._publish_comm_gauges(cmode)
        prev, last_ms = st['mode'], st['ms']
        W = self.window
        if (prev is not None and cmode != prev and not st['emitted']
                and st.get('flip') is None and last_ms is not None):
            # the first window in the new mode pays the program
            # rebuild + compile — hold the record until the next
            # (steady-state) window so the after-side is honest
            st['flip'] = {'prev': prev, 'to': cmode,
                          'before_ms': last_ms}
        elif (st.get('flip') is not None and not st['emitted']
                and cmode == st['flip']['to']):
            from ..parallel import compression
            before = st['flip']['before_ms']
            compression.emit_record(
                event='mode_flip', mode=cmode,
                prev_mode=st['flip']['prev'],
                auto=compression.auto_engaged(),
                step=int(st['windows'] * W),
                before_step_ms=round(before / W, 3),
                after_step_ms=round(win_ms / W, 3),
                delta_step_ms=round((win_ms - before) / W, 3))
            st['emitted'] = True
        st['mode'], st['ms'] = cmode, win_ms

    # -- per-epoch drive ---------------------------------------------------
    def _snapshot(self):
        e = self._exec
        params = tuple(e.arg_dict[n]._data for n in self._carry_names)
        states = tuple(tuple(self._state_arrays(n))
                       for n in self._grad_names)
        aux = tuple(e.aux_dict[n]._data for n in self._aux_names)
        gaccs = tuple(e.grad_dict[n]._data for n in self._grad_names) \
            if self._accum else ()
        if self._mesh is not None:
            from .window_pipeline import place_replicated
            if self._zero is not None:
                # optimizer state enters (and stays) in the ZeRO
                # update-phase form; already-converted leaves pass
                # through untouched, so this is free in steady state
                from .window_pipeline import place_update_sharded
                flat = place_update_sharded(self._mesh, [
                    (a, shape)
                    for n, st in zip(self._grad_names, states)
                    for a, (shape, _d) in zip(st, self._zero_shapes[n])])
                regrouped, i = [], 0
                for n in self._grad_names:
                    k = len(self._zero_shapes[n])
                    regrouped.append(tuple(flat[i:i + k]))
                    i += k
                states = tuple(regrouped)
                params, aux, gaccs = place_replicated(
                    self._mesh, params, aux, gaccs)
            else:
                params, states, aux, gaccs = place_replicated(
                    self._mesh, params, states, aux, gaccs)
        self._note_update_gauges()
        return params, states, aux, gaccs

    def _writeback(self, params, states, aux, gaccs):
        e = self._exec
        m = self.module
        for n, v in zip(self._carry_names, params):
            e.arg_dict[n]._data = v
        for n, st in zip(self._grad_names, states):
            self._writeback_state(n, list(st))
            if m._update_on_kvstore:
                # keep the kvstore's canonical copy in sync (pull reads it)
                store = m._kvstore._store.get(n)
                if store is not None:
                    store._data = e.arg_dict[n]._data
        for n, v in zip(self._aux_names, aux):
            e.aux_dict[n]._data = v
        if self._accum:
            for n, v in zip(self._grad_names, gaccs):
                e.grad_dict[n]._data = v
        m._params_dirty = True

    def run_epoch(self, train_data, eval_metric, epoch,
                  batch_end_callback, monitor=None, ckpt=None):
        """Run one epoch; returns the number of batches consumed.
        Tail batches (< window) run through the reference per-batch
        path — state is written back after every window, so the two
        paths interleave safely. ``ckpt`` is fit's TrainCheckpointer
        (module/checkpointing.py), fed once per dispatched window."""
        from ..model import BatchEndParam
        from .base_module import _as_list

        _tele.gauge('fused_fit.steps_per_call').set(self.window)
        # cpu-backed NDArray wrapper for already-host data, so the
        # metric's .asnumpy() calls cost no device round-trip
        host_nd = host_wrap(self._exec._ctx)

        # which metric children carry a per-batch loss: the in-graph
        # CrossEntropy sufficient statistics feed the health plane's
        # rolling loss-spike detector AND the run ledger's per-step
        # loss scalar for free (note_loss no-ops while health is off)
        ce_idx = [j for j, c in enumerate(self.children or ())
                  if type(c) is metric_mod.CrossEntropy] \
            if self.stat_fns is not None and (
                self._health_fn is not None or _tele.ledger.enabled()) \
            else []
        # rows that carried loss under a label-ignoring metric: its count
        labelled_idx = [j for j, c in enumerate(self.children or ())
                        if type(c) is metric_mod.Perplexity
                        and c.ignore_label is not None] \
            if self.stat_fns is not None and _tele.enabled() else []

        # wall stamp of the previous apply_stats fetch: the ledger's
        # per-step timestamps amortize over the inter-window wall so
        # W steps processed in one burst don't bunch at one instant
        # (which would inflate steps_per_sec and zero run_compare's
        # step_time deltas)
        _stats_t = [None]

        def apply_stats(pending, nbatch):
            """One host fetch for the window's results, then exact
            per-batch metric application + callbacks. ``pending`` is
            the dispatched window as the loop kept it: (pieces, labels,
            snapshots or None, win). Stats mode feeds
            the packed sufficient-statistic sums into the metric
            children; host-metric mode replays eval_metric.update with
            each step's outputs against the window's own labels
            (snapshotted at collection time — see below), the way the
            reference loop's update_metric would."""
            pieces, labels_w, win_snaps, win = pending
            hrows = drows = None
            aux_rows = []
            if self._health_fn is not None or self._dyn_fn is not None \
                    or self._aux_stats:
                parts = list(pieces)
                pieces = parts.pop(0)
                if self._health_fn is not None:
                    hrows = parts.pop(0)
                if self._dyn_fn is not None:
                    drows = parts.pop(0)
                aux_rows = [parts.pop(0) for _ in self._aux_stats]
            with _tele.span('fused_fit.fetch', 'fused_fit', win=win):
                # the window's one device->host fetch (everything
                # after is host math) —
                # the (W, k) sentinel AND dynamics matrices ride the
                # same fetch
                if self.stat_fns is not None:
                    host = np.asarray(pieces)      # (W, 2 * n_metrics)
                    steps = host.shape[0]
                else:
                    outs_host = [np.asarray(o) for o in pieces]  # (W, ...)
                    steps = outs_host[0].shape[0]
                if hrows is not None:
                    hmat = np.asarray(hrows)
                if drows is not None:
                    dmat = np.asarray(drows)
                aux_mats = [np.asarray(r) for r in aux_rows]
            for (_, note), mat in zip(self._aux_stats, aux_mats):
                note(mat, win=win)
            for j in labelled_idx:
                _tele.counter('fit.labelled_rows').inc(
                    int(host[:, 2 * j + 1].sum()))
            if hrows is not None:
                # mid-window NaN -> exact step attribution + (first
                # incident) staged-path first-bad-layer bisect on the
                # offending batch's draw-time snapshot. raise action
                # surfaces here, before the metric sees garbage.
                _tele.health.note_window(
                    hmat, source='fused_fit', nbatch_base=nbatch,
                    bisect=window_bisect(
                        self._exec, list(self.module._data_names),
                        list(self.module._label_names), win_snaps, True,
                        defer_fn=self._defer_eager)
                    if win_snaps is not None else None)
            if drows is not None:
                # per-layer dynamics: each row keeps its exact step,
                # feeds the per-layer spike detectors and raises a
                # named-layer incident on a non-finite statistic
                _tele.dynamics.note_window(
                    dmat, self._grad_names, self._out_names,
                    nbatch_base=nbatch)
            ledger_on = _tele.ledger.enabled()
            if ledger_on:
                t_apply = time.time()
                t_prev = _stats_t[0]
                _stats_t[0] = t_apply
            for i in range(steps):
                loss_i = None
                if self.stat_fns is not None:
                    for j, child in enumerate(self.children):
                        child.sum_metric += float(host[i, 2 * j])
                        child.num_inst += int(host[i, 2 * j + 1])
                    for j in ce_idx:
                        loss_i = host[i, 2 * j] / max(host[i, 2 * j + 1],
                                                      1.0)
                        _tele.health.note_loss(loss_i)
                else:
                    preds = [host_nd(o[i]) for o in outs_host]
                    eval_metric.update(labels_w[i], preds)
                if ledger_on:
                    # run-ledger scalars (decimated inside): the step's
                    # in-graph CE loss when the stats plan computes one,
                    # the running metric otherwise. Steps spread evenly
                    # across the inter-window wall; the first window has
                    # no baseline so its due steps bunch at ITS fetch
                    # stamp — the same timeline later windows
                    # interpolate on (emission-time clocks would land
                    # PAST the next window's anchor and break
                    # monotonicity)
                    _tele.ledger.note_train_step(
                        loss=loss_i, lr=self._last_lr,
                        metric=None if loss_i is not None
                        else eval_metric,
                        t=t_apply if t_prev is None else
                        t_prev + (t_apply - t_prev) * (i + 1) / steps)
                if batch_end_callback is not None:
                    p = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                      eval_metric=eval_metric,
                                      locals=locals())
                    for cb in _as_list(batch_end_callback):
                        cb(p)
                nbatch += 1
            return nbatch

        from ..io import DataBatch as _DataBatch
        # deferred device-augment: when the iterator supports it, draw
        # RAW uint8 batches and trace the augmentation inside the
        # window program — an eager aug dispatch per batch would put
        # the host back on every step's critical path
        defer_switch = getattr(train_data, 'defer_device_aug', None)
        self._defer_fn = None
        self._defer_eager = None
        self._defer_sig = False
        if callable(defer_switch) and defer_switch(True):
            # one pure fn per ITERATOR object (WeakKey: dies with it) —
            # an unsigned iterator would otherwise key a fresh program
            # every epoch through the identity fallback below
            try:
                self._defer_fn = self._defer_fns[train_data]
            except KeyError:
                self._defer_fn = train_data.device_aug_pure()
                self._defer_fns[train_data] = self._defer_fn
            # tail batches (< window) materialize per batch: ONE
            # compiled call each, not the pure fn's ~10 eager ops
            self._defer_eager = jax.jit(self._defer_fn)
            # the aug MATH is baked into the compiled window, so the
            # program key must carry its configuration — a second
            # iterator with equal batch shapes but different
            # mean/std/scale/rand flags must NOT reuse this program.
            # Unsigned fallback keys by the LIVE function object (held
            # by the key itself), never by a recyclable id()
            sig_fn = getattr(train_data, 'device_aug_signature', None)
            self._defer_sig = sig_fn() if callable(sig_fn) \
                else ('defer-unsigned', self._defer_fn)
        else:
            defer_switch = None
        try:
            return self._run_epoch_inner(
                train_data, eval_metric, epoch, batch_end_callback,
                _DataBatch, apply_stats, host_nd, ckpt)
        except Exception as e:
            # RESOURCE_EXHAUSTED anywhere in the window drive (upload,
            # dispatch, stats fetch): dump the per-program memory
            # breakdown before the crash surfaces (no-op otherwise)
            _tele.programs.maybe_oom_report(e)
            raise
        finally:
            if defer_switch is not None:
                defer_switch(False)
                self._defer_fn = None
                self._defer_eager = None
            # the loop now outlives fit() (build_cached): drop the last
            # window's device stack + its strong host refs — the
            # identity cache only ever hits while an epoch is running
            self._pipe.drop_cache()

    def _run_epoch_inner(self, train_data, eval_metric, epoch,
                         batch_end_callback, _DataBatch, apply_stats,
                         host_nd, ckpt=None):
        from ..model import BatchEndParam
        from .base_module import _as_list
        from .. import random as _random
        m = self.module
        # a resumed epoch's first fused batch IS batch r_step of the
        # epoch: counting from the checkpointer's base keeps callback/
        # incident batch indices true (and the failure bound correct)
        nbatch = ckpt.epoch_nbatch_base if ckpt is not None else 0
        pending = None
        it = iter(train_data)
        from ..config import flags as _flags
        _clk = time.perf_counter
        pipe = self._pipe
        pool = pipe.pool() \
            if _flags.get('MXTPU_FUSED_FIT_PREFETCH') else None

        faults_on = _faults.enabled()

        def collect():
            # draw-time snapshotting lives in the shared pipeline:
            # iterators may legally reuse their DataBatch/NDArray
            # buffers for the next batch; the draw-time jax-array
            # references stay valid while the window is collected and
            # the apply is deferred. `win` numbers the window for its
            # spans, from this draw to the fetch one window later.
            batches, snaps, win = pipe.collect(it)
            if faults_on:
                # nan-grad draw seam: training batches counted in step
                # order, the armed one poisoned before stack/upload
                snaps = [_faults.maybe_poison_snap(s) for s in snaps]
            return batches, snaps, win

        def start_put(win_snaps, win):
            # with the prefetch pool, window k+1's stack + put run on
            # the side thread while window k computes on device and
            # k-1's stats fetch waits
            return pipe.start_put(win_snaps, pool, win)

        health_on = self._health_fn is not None
        cluster_on = _tele.cluster.enabled()
        mem_on = _tele.memory.enabled()
        tl_on = _tele.timeline.enabled()
        _t_win = _clk()   # wall clock per dispatched window (health)
        batches, snaps, win = collect()
        if not batches:
            if ckpt is not None and ckpt.allow_empty_epoch(epoch):
                # checkpoint-resume landed exactly on this epoch's
                # boundary: the skip consumed every batch — the epoch
                # is already trained
                return 0
            # exhausted before the FIRST batch: the reference loop's
            # unguarded first next() (base_module.py:482) raises here —
            # fail just as loudly instead of silently training a
            # zero-batch epoch (callers must reset() an iterator that a
            # score()/predict pass drained)
            raise StopIteration(
                'training iterator is exhausted at epoch start — '
                'reset() it (a score()/predict pass leaves the '
                'iterator drained, matching the reference fit loop)')
        fut = start_put(snaps, win) \
            if len(batches) == self.window else None
        try:
            while len(batches) == self.window:
                # one program per (static attrs, shapes); lr/wd enter
                # as traced arrays sampled at each window start, so an
                # lr scheduler never forces a recompile
                static_attrs = self._static_attrs()
                attrs_key = tuple(sorted(static_attrs.items()))
                shapes_key = tuple((tuple(d.shape), str(d.dtype))
                                   for d in snaps[0][0])
                # resolved compression mode is part of the program key:
                # an auto flip (cluster verdict) lands here as a new
                # key and rebuilds the window at this dispatch edge
                cmode = self._cmode()
                prog_key = (attrs_key, shapes_key, self._defer_sig,
                            cmode)
                if prog_key not in self._programs:
                    with _tele.span('fused_fit.build', 'fused_fit'):
                        self._programs[prog_key] = self._build_program(
                            static_attrs, shapes_key, cmode)
                    # same-key rebuilds only happen when the program dict
                    # was torn down; the storm detector keys on the
                    # SHAPES — a shape/attr leaking into attrs_key shows
                    # up as many builds of one shapes_key
                    _tele.xla.note_retrace(('fused_fit.window', shapes_key))
                window_fn = self._programs[prog_key]

                # host-metric mode: keep per-batch label wrappers from
                # the draw-time snapshots for the deferred
                # eval_metric.update. Stats mode needs nothing from the
                # host batches.
                labels_snap = None
                if self.stat_fns is None:
                    labels_snap = [[from_jax(l, self._exec._ctx)
                                    for l in ls] for _, ls, _, _ in snaps]
                if faults_on:
                    # dispatch-exception seam: fire before the window
                    # containing the armed step is dispatched
                    _faults.maybe_raise('dispatch', upcoming=self.window)
                params, states, aux, gaccs = self._snapshot()
                # the optimizer's host tail — W x n_params update-count
                # walks + lr/wd sampling, plus the snapshot above —
                # runs BEFORE the put wait, so it hides under window
                # k+1's side-thread transfer instead of serializing
                # after it (the update/upload overlap: the side
                # thread's .stack/.upload spans against this .put wait
                # of the same win are the evidence)
                lr_arr, wd_arr = self._sample_window_lr()
                with _tele.span('fused_fit.put', 'fused_fit', win=win):
                    data_stack, label_stack = fut()
                with _tele.span('fused_fit.dispatch', 'fused_fit',
                                win=win):
                    self._base_key = _random.next_key()
                    if cmode != 'off':
                        resids = self._ensure_resids()
                        (params, states, aux, gaccs, resids,
                         pieces) = window_fn(
                            params, states, aux, gaccs, resids,
                            data_stack, label_stack,
                            self._base_key, lr_arr, wd_arr)
                        self._resid = dict(zip(self._grad_names, resids))
                    else:
                        params, states, aux, gaccs, pieces = window_fn(
                            params, states, aux, gaccs, data_stack,
                            label_stack, self._base_key, lr_arr, wd_arr)
                    self._writeback(params, states, aux, gaccs)
                _tele.counter('fit.steps').inc(self.window)
                _tele.counter('fused_fit.windows').inc()
                # hang-watchdog progress mark: one whole window
                # dispatched (the dispatch is async, but an enqueued
                # window IS host-side progress; a wedged device shows
                # up at the next put/fetch, which then stops marking)
                _tele.watchdog.note_progress('fused_fit.window')
                if cluster_on:
                    # a whole window of steps advanced in one dispatch;
                    # the sync (if due) piggybacks on the window edge
                    _tele.cluster.note_step(self.window)
                # MXTPU_XPROF step window (quantized to whole windows)
                _profiler.note_step(self.window)
                # dispatch is async: while this window computes, draw
                # the NEXT window (its stack + transfer start on the
                # side thread) and fetch the PREVIOUS window's stats —
                # both the transfer and the fetch RTT disappear behind
                # device time (callbacks run one window late; values
                # and cadence are unchanged)
                dispatched = (pieces, labels_snap,
                              snaps if health_on else None, win)
                batches, snaps, win = collect()
                fut = start_put(snaps, win) \
                    if len(batches) == self.window else None
                if pending is not None:
                    nbatch = apply_stats(pending, nbatch)
                pending = dispatched
                # one wall observation per window (window-edge to
                # window-edge): in steady state the loop is device-
                # bound, so wall / W IS the per-step time — health's
                # step-time stream and the compression flip record's
                # before/after delta both read it
                _now = _clk()
                _win_wall = _now - _t_win
                _t_win = _now
                if health_on:
                    _tele.health.note_step_time(_win_wall,
                                                steps=self.window)
                if self._zero is not None:
                    self._note_compress_window(cmode, _win_wall * 1e3)
                if ckpt is not None:
                    lag = self.window
                    if pending is not None and ckpt.save_due(self.window):
                        # a save will initiate for THIS window: flush
                        # the pipelined stats/health rows first so the
                        # capture's eval-metric state covers every step
                        # the checkpoint claims (and a NaN in this
                        # window raises BEFORE a poisoned capture)
                        nbatch = apply_stats(pending, nbatch)
                        pending = None
                        lag = 0   # health checked through this window
                    # otherwise the health plane has only processed the
                    # PREVIOUS window's rows (the fetch is pipelined one
                    # window late): certification trails by lag=W
                    ckpt.note_steps(self.window, lag=lag)
                if faults_on:
                    _faults.note_steps(self.window)
                if mem_on:
                    # live-bytes timeline (MXTPU_MEMORY): a host-side
                    # allocator query at the scalars cadence, no
                    # device sync
                    _tele.memory.note_step(self.window)
                if tl_on:
                    # pod step timeline (MXTPU_TIMELINE): a whole
                    # window of steps for the phase ledger's per-step
                    # normalization — one clock read
                    _tele.timeline.note_step(self.window)
        finally:
            # drain an in-flight prefetch before run_epoch's cache
            # teardown (or an exception unwind) can race the side thread
            if pool is not None:
                WindowPipeline.drain(fut)
        if pending is not None:
            nbatch = apply_stats(pending, nbatch)
        if snaps:
            # tail batches run the imperative per-batch update: ZeRO
            # leaves materialize to canonical shapes and the kvstore-
            # side arrays return to the context device (the per-batch
            # machinery's placement invariant)
            self._prepare_tail()
        for ds, ls, pad, idx in snaps:
            # tail (< window): reference per-batch path, on a rebuilt
            # batch (the original's buffers may have been overwritten
            # by later draws — pad/index come from the draw-time
            # snapshot for the same reason). Deferred uint8 batches are
            # materialized eagerly here — one aug dispatch per tail
            # batch, exactly the eager mode's cost
            if self._defer_eager is not None:
                ds = (self._defer_eager(ds[0], _random.next_key()),
                      ) + tuple(ds[1:])
            sb = _DataBatch(
                data=[from_jax(d, self._exec._ctx) for d in ds],
                label=[from_jax(l, self._exec._ctx) for l in ls],
                pad=pad, index=idx)
            if health_on or self._dyn_fn is not None:
                # the tail runs the executor path: incidents (health
                # AND dynamics) carry the real batch index through the
                # note_batch context
                _tele.health.note_batch(nbatch)
            m.forward_backward(sb)
            m.update()
            _tele.counter('fit.steps').inc()
            _tele.watchdog.note_progress('fit.step')
            if cluster_on:
                _tele.cluster.note_step()
            if faults_on:
                _faults.note_steps(1)
            if tl_on:
                _tele.timeline.note_step(1)
            _profiler.note_step()
            m.update_metric(eval_metric, sb.label)
            _tele.ledger.note_train_step(lr=self._last_lr,
                                         metric=eval_metric)
            if ckpt is not None:
                # after update_metric, so a save initiated on a tail
                # step captures the metric including this batch; the
                # sentinel check already ran inside backward (lag=0)
                ckpt.note_steps(1)
            if batch_end_callback is not None:
                p = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                  eval_metric=eval_metric,
                                  locals=locals())
                for cb in _as_list(batch_end_callback):
                    cb(p)
            nbatch += 1
        return nbatch
