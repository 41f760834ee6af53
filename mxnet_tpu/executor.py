"""Executor — a bound, XLA-compiled symbol graph.

Reference: include/mxnet/executor.h:52-153 + src/executor/graph_executor.cc.
The reference's Init pipeline (InitFullGraph → gradient pass → AssignContext
→ PlanMemory → AttachOpExecs → InitCachedOps → bulk segments,
graph_executor.cc:917-1336) collapses here into tracing ONE pure function
over the argument arrays and letting jax.jit/XLA do gradient (via vjp),
scheduling, fusion, and memory planning (SURVEY.md §3.2 "TPU mapping").

Two execution modes:
- compiled (default): forward and forward+backward are each one jitted XLA
  computation. When is_train=True the forward is LAZY — Module's
  forward→backward sequence runs a single fused fwd+bwd computation.
- staged: used when group2ctx (manual model parallelism) or a monitor
  callback is active — per-node eager interpretation with device_put at
  ctx_group boundaries (reference AssignContext + _CrossDeviceCopy,
  graph_executor.cc:309-423) and per-op observability (ExecuteMonCallback,
  graph_executor.cc:1398).
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp

from .base import MXNetError, np_dtype
from .context import Context
from . import faults as _faults
from . import random as _random
from .ndarray.ndarray import NDArray, zeros as nd_zeros, from_jax
from .ops import registry as _reg

__all__ = ['Executor', 'simple_bind']


def _note_kept(kept):
    """What the mirrored stages of the training program just traced keep
    for its backward pass (0 where it was traced without one)."""
    from . import telemetry as _tele
    _tele.gauge('executor.mirror_kept').set(len(kept))
    _tele.gauge('executor.mirror_kept_bytes').set(
        sum(x.size * x.dtype.itemsize for x in kept))


def mirror_wrap(f):
    """Gradient-memory tradeoff ≙ XLA rematerialization.

    Reference: MXNET_BACKWARD_DO_MIRROR (graph_executor.cc:273-287) marks
    cheap forward nodes for recompute in backward. Here the same knob is a
    jax.checkpoint policy applied to the whole traced forward:
      MXTPU_BACKWARD_DO_MIRROR=1     recompute all but what an op named as
                                     dear (``ops.registry.mirrored``, as
                                     a symbol's mirrored stages)
      MXTPU_BACKWARD_DO_MIRROR=dots  keep matmul results, recompute the rest
                                     (closest to the reference's heuristic
                                     of mirroring everything but convolution
                                     and dot outputs)
    The legacy MXNET_ spelling is honored too. Loss and gradients are
    bit-identical either way — only the memory/time tradeoff changes.
    """
    from .config import flags as _flags
    _flags.reload('MXTPU_BACKWARD_DO_MIRROR')  # tests toggle it per-case
    val = _flags.get('MXTPU_BACKWARD_DO_MIRROR')
    if val in ('', '0', 'false', 'False'):
        return f
    if val == 'dots':
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(f, policy=policy)

    def g(*args):
        kept = []
        out = _reg.mirrored(f, kept)(*args)
        _note_kept(kept)
        return out

    return g


def _align_head(g, sharding):
    """Move a head-gradient (cotangent) onto the primal's sharding if it
    arrived committed elsewhere — SequentialModule hands gradients
    across module device groups; the reference engine does this copy
    implicitly via cross-context dependency edges."""
    if getattr(g, 'sharding', None) == sharding:
        return g
    return jax.device_put(g, sharding)


def _entry_key(node, idx):
    return (id(node), idx)


class _GraphProgram:
    """Compiled form of a symbol: canonical input orders + a pure runner."""

    def __init__(self, symbol):
        self.symbol = symbol
        self.topo = symbol._topo()
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.outputs = list(symbol._outputs)
        self._aux_set = set(self.aux_names)
        # host ops (image codecs, legacy callback bridges) cannot be
        # traced; their presence forces the staged per-op path
        self.has_host_ops = any(not n.is_variable() and n.opdef().host
                                for n in self.topo)
        self.op_nodes = [n for n in self.topo if not n.is_variable()]
        self.topo_index = {n: i for i, n in enumerate(self.topo)}
        # per-node jax.named_scope names: device traces, HLO dumps and
        # profiler output attribute ops to the SYMBOL's layer names
        # instead of anonymous fusion.123 clusters
        from .telemetry.programs import note_nodes, scope_name
        self.scope_names = [scope_name(n.name) for n in self.topo]
        # the scope map of a compiled program (telemetry/programs.py)
        # tells a node's segment by this table, and reads its op there
        note_nodes({s: n.op for s, n in zip(self.scope_names, self.topo)
                    if not n.is_variable()})

    def make_runner(self):
        """Build run(arg_arrays, aux_arrays, key, is_train) ->
        (outputs, new_aux). Pure; jit-compiled by the executor.

        Nodes that carry the reference's mirroring attribute
        (``mx.AttrScope(__force_mirroring__=<stage>)``, graph_executor.cc's
        per-node mirror mark) are recomputed in the backward pass instead
        of stored: consecutive marked op nodes with the same value form one
        stage, run as ``ops.registry.mirrored`` when training. It keeps
        what it reads, what leaves it, and the values an op inside it named
        as dear to recompute (``ops.registry.dear``): what an attention
        kernel's backward pass reads (its output and log-sum-exp, query,
        key and value), the output of a ``FullyConnected`` that contracts,
        an expert layer's routing and plan, a gated MLP's two hidden
        products. Everything else it computes again: norms, expanding
        projections. The gauges ``executor.mirror_kept`` and ``_bytes`` say
        how many arrays the ops of the traced program named so, each once,
        and their size (one that no backward rule reads, a key's projection
        before its rotary turn, is counted and not held). A builder gives
        each block of a deep network its own value."""
        topo = self.topo
        arg_index = {n: i for i, n in enumerate(self.arg_names)}
        aux_index = {n: i for i, n in enumerate(self.aux_names)}
        outputs = self.outputs
        scope_names = self.scope_names

        def exec_node(env, ni, key, is_train, new_aux):
            node = topo[ni]
            op = node.opdef()
            _reg.record(op)
            attrs = dict(node.attrs)
            if op.train_aware:
                attrs['__is_train__'] = is_train
            ins = [env[_entry_key(p, i)] for p, i in node.inputs]
            if op.needs_rng:
                ins.append(jax.random.fold_in(key, ni))
            # named_scope threads the symbol's layer name into the
            # HLO metadata of everything this node lowers to —
            # trace-time only, zero cost in the compiled program
            with jax.named_scope(scope_names[ni]):
                if op.host:
                    # pure_callback bridge: host python at execution
                    # time, traceable (and differentiable via legacy
                    # backward)
                    outs = _reg.host_bridge(op, attrs)(*ins)
                else:
                    outs = op.fn(attrs, *ins)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            for i, o in enumerate(outs):
                env[_entry_key(node, i)] = o
            # collect aux updates (BatchNorm moving stats)
            for in_idx, out_idx in op.mutated(node.attrs).items():
                if in_idx < len(node.inputs):
                    src, _ = node.inputs[in_idx]
                    if src.is_variable() and src.name in aux_index:
                        new_aux[aux_index[src.name]] = outs[out_idx]

        plan = self._mirror_plan()

        def run(arg_arrays, aux_arrays, key, is_train):
            env = {}
            new_aux = dict()
            kept = []
            for node in topo:
                if node.is_variable():
                    if node.name in aux_index:
                        env[_entry_key(node, 0)] = aux_arrays[aux_index[node.name]]
                    else:
                        env[_entry_key(node, 0)] = arg_arrays[arg_index[node.name]]
            for nis, reads, leaves in plan:
                if reads is None or not is_train:
                    for ni in nis:
                        exec_node(env, ni, key, is_train, new_aux)
                    continue

                def stage(vals, key, nis=nis, reads=reads, leaves=leaves):
                    local, aux_up = dict(zip(reads, vals)), {}
                    for ni in nis:
                        exec_node(local, ni, key, is_train, aux_up)
                    return [local[k] for k in leaves], aux_up

                outs, aux_up = _reg.mirrored(stage, kept)(
                    [env[k] for k in reads], key)
                env.update(zip(leaves, outs))
                new_aux.update(aux_up)
            if is_train:
                _note_kept(kept)
            out_arrays = tuple(env[_entry_key(n, i)] for n, i in outputs)
            aux_out = tuple(new_aux.get(i, aux_arrays[i])
                            for i in range(len(self.aux_names)))
            return out_arrays, aux_out

        return run

    def _mirror_plan(self):
        """[(op node indices, reads, leaves)] in execution order: a node of
        its own (reads None), or a mirrored stage with the entries it
        reads from outside and the entries that leave it (read by a later
        node, or outputs of the graph)."""
        topo = self.topo

        def stage_of(node):
            tag = str(node.attr_dict.get(
                '__force_mirroring__',
                node.attr_dict.get('force_mirroring', '')))
            return '' if tag in ('', '0', 'False', 'false') else tag

        runs = []       # [tag, [ni, ...]]
        for ni, node in enumerate(topo):
            if node.is_variable():
                continue
            tag = stage_of(node)
            if tag and runs and runs[-1][0] == tag:
                runs[-1][1].append(ni)
            else:
                runs.append([tag, [ni]])
        graph_outs = {_entry_key(n, i) for n, i in self.outputs}
        plan = []
        for tag, nis in runs:
            if not tag:
                plan.append((nis, None, None))
                continue
            inside = set(nis)
            made = {id(topo[ni]) for ni in nis}
            reads, seen = [], set()
            for ni in nis:
                for p, i in topo[ni].inputs:
                    k = _entry_key(p, i)
                    if id(p) not in made and k not in seen:
                        seen.add(k)
                        reads.append(k)
            leaves, seen = [], set()
            for nj, node in enumerate(topo):
                if node.is_variable() or nj in inside:
                    continue
                for p, i in node.inputs:
                    k = _entry_key(p, i)
                    if id(p) in made and k not in seen:
                        seen.add(k)
                        leaves.append(k)
            for k in graph_outs:
                if k[0] in made and k not in seen:
                    seen.add(k)
                    leaves.append(k)
            plan.append((nis, reads, leaves))
        return plan


class Executor:
    """Reference executor.py:45 wrapper + graph_executor.cc in one."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req='write',
                 aux_states=None, group2ctx=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._prog = _GraphProgram(symbol)
        self._group2ctx = group2ctx
        self._monitor = None
        self._monitor_all = False

        self.arg_arrays = self._canon_args(args, self._prog.arg_names, 'args')
        self.aux_arrays = self._canon_args(aux_states or [],
                                           self._prog.aux_names, 'aux_states')
        self.arg_dict = dict(zip(self._prog.arg_names, self.arg_arrays))
        self.aux_dict = dict(zip(self._prog.aux_names, self.aux_arrays))

        # grad bookkeeping
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._prog.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._prog.arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, 'null')
                              for n in self._prog.arg_names}
        if args_grad is None:
            self.grad_arrays = [None] * len(self.arg_arrays)
        else:
            self.grad_arrays = self._canon_args(args_grad, self._prog.arg_names,
                                                'args_grad', allow_missing=True)
        self.grad_dict = {n: g for n, g in zip(self._prog.arg_names,
                                               self.grad_arrays)}
        self._grad_names = [n for n in self._prog.arg_names
                            if self._grad_req.get(n, 'null') != 'null'
                            and self.grad_dict.get(n) is not None]

        run = self._prog.make_runner()
        self._fwd = jax.jit(functools.partial(run), static_argnums=(3,))
        grad_idx = tuple(self._prog.arg_names.index(n) for n in self._grad_names)

        # training-health sentinels (telemetry/health): with
        # MXTPU_HEALTH=1 (and telemetry on) the fused fwd+bwd program
        # ALSO returns one packed stats vector — grad/param norms,
        # update ratio, per-output finite flags — computed on device
        # inside the same compiled step. Off: the trace is byte-
        # identical to the plain form (asserted by test_health.py).
        from .telemetry import health as _health
        from .telemetry import dynamics as _dynamics
        self._health_on = _health.enabled() and bool(self._grad_names)
        health_on = self._health_on
        # per-layer training dynamics (telemetry/dynamics): with
        # MXTPU_DYNAMICS=1 the fused fwd+bwd ALSO returns the packed
        # per-layer stats vector — it rides the same per-batch host
        # sync the health sentinel already pays. Off: byte-identical
        # trace (asserted by test_dynamics.py).
        self._dyn_on = _dynamics.enabled() and bool(self._grad_names)
        dyn_on = self._dyn_on
        self._out_names = list(symbol.list_outputs())

        def fwd_bwd(arg_arrays, aux_arrays, key, head_grads):
            def f(wrt):
                full = list(arg_arrays)
                for i, gi in enumerate(grad_idx):
                    full[gi] = wrt[i]
                outs, new_aux = run(tuple(full), aux_arrays, key, True)
                return outs, new_aux

            wrt = tuple(arg_arrays[gi] for gi in grad_idx)
            (outs, new_aux), vjp = jax.vjp(mirror_wrap(f), wrt)
            zero_aux = tuple(jnp.zeros_like(a) for a in new_aux)
            (grads,) = vjp((head_grads, zero_aux))
            rets = (outs, new_aux, grads)
            if health_on:
                rets += (_health.step_stats(outs, grads=grads,
                                            params=wrt),)
            if dyn_on:
                rets += (_dynamics.step_stats(outs, grads=grads,
                                              params=wrt),)
            return rets

        self._fwd_bwd = jax.jit(fwd_bwd)
        self._run_eager = run

        self.outputs_cached = None
        self._pending = None  # (arg jax arrays, aux jax arrays, key) for lazy train fwd
        self._partial = None  # partial_forward stepping state

        from . import telemetry as _tele
        if _tele.enabled():
            # cost attribution: route both compiles through the program
            # registrar — an explicit lower().compile() whose executable
            # yields XLA's cost/memory analysis (program.* gauges, the
            # per-program summary table). fwd_bwd is THE train step of
            # the per-batch loop: its FLOPs are the xla.step_flops gauge.
            gname = _tele.programs.scope_name(
                getattr(symbol, 'name', None) or 'graph')
            self._fwd = _tele.programs.register(
                'executor.fwd[%s]' % gname, self._fwd, static_argnums=(3,))
            self._fwd_bwd = _tele.programs.register(
                'executor.fwd_bwd[%s]' % gname, self._fwd_bwd,
                step_flops=True)
            # retrace-storm detector: binding the same graph signature
            # repeatedly (rebind-per-batch, reshape loops) recompiles
            # the same XLA program each time
            _tele.xla.note_retrace(
                ('executor', tuple(self._prog.arg_names),
                 tuple(symbol.list_outputs()),
                 tuple((tuple(a.shape), str(a._data.dtype))
                       for a in self.arg_arrays)))

    def _canon_args(self, args, names, what, allow_missing=False):
        if isinstance(args, dict):
            out = []
            for n in names:
                if n in args:
                    out.append(args[n])
                elif allow_missing:
                    out.append(None)
                else:
                    raise MXNetError('missing %s: %s' % (what, n))
            return out
        args = list(args)
        if len(args) != len(names):
            raise MXNetError('length of %s (%d) != expected (%d: %s)'
                             % (what, len(args), len(names), names))
        return args

    # -- forward ----------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Reference executor.py:89 / GraphExecutor::Forward."""
        from . import telemetry as _tele
        with _tele.span('executor.forward', 'executor'):
            try:
                return self._forward_impl(is_train, **kwargs)
            except Exception as e:
                # RESOURCE_EXHAUSTED: dump the per-program memory
                # breakdown before the crash surfaces (no-op otherwise)
                _tele.programs.maybe_oom_report(e)
                raise

    def _forward_impl(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                # onto the device this argument was bound on, not the
                # process's default one
                bound = self.arg_dict[k]
                bound._data = jax.device_put(
                    v._data if isinstance(v, NDArray) else np.asarray(v),
                    bound._data.sharding)
        self._partial = None  # a full forward invalidates any stepping pass
        if self._use_staged():
            return self._forward_staged(is_train)

        arg_data = tuple(a._data for a in self.arg_arrays)
        aux_data = tuple(a._data for a in self.aux_arrays)
        key = _random.next_key()
        if is_train and self._grad_names:
            # defer: backward will run the fused fwd+bwd computation
            self._pending = (arg_data, aux_data, key)
            self.outputs_cached = None
            return self._lazy_outputs()
        self._pending = None
        outs, new_aux = self._fwd(arg_data, aux_data, key, bool(is_train))
        if is_train:
            self._write_aux(new_aux)
        self.outputs_cached = [from_jax(o, self._ctx) for o in outs]
        return self.outputs_cached

    def partial_forward(self, is_train, step):
        """Interactive stepping forward: execute exactly one operator
        node per call (the GraphExecutor::PartialForward role; the
        stepping loop contract is documented at reference
        include/mxnet/c_predict_api.h:160-169 — call from step=0,
        increment until the return value hits 0).

        Unlike :meth:`forward`, which dispatches one fused XLA program,
        each step here eagerly dispatches a single operator so callers
        can report progress on slow models; intermediate buffers
        persist in an env dict between calls.  Variable values are
        snapshotted into the env when a pass starts — inputs written
        mid-pass take effect on the next pass (restart at step 0), not
        on the remaining steps of the current one.  Restarting at step
        0 (or jumping to an arbitrary step) rebuilds the env and
        replays up to that node.  An abandoned pass keeps its env (and
        the device buffers it holds) until the next full forward,
        param copy, or restart releases it.  Returns the number of
        steps left.
        """
        prog = self._prog
        n_steps = len(prog.op_nodes)
        step = int(step)
        if n_steps == 0:
            # variable-only graph: outputs are just current variables
            env = self._snapshot_env()
            self.outputs_cached = [from_jax(env[_entry_key(n, i)], self._ctx)
                                   for n, i in prog.outputs]
            return 0
        if step < 0 or step >= n_steps:
            return 0
        st = self._partial
        if st is None or st['next'] != step:
            st = self._partial = {'env': self._snapshot_env(), 'next': 0,
                                  'key': _random.next_key(), 'new_aux': {}}
            lo = 0
        else:
            lo = step
        for k in range(lo, step + 1):
            node = prog.op_nodes[k]
            # deterministic per-node stream: fold the stepping pass's
            # base key by topo position, like the jitted runner does
            rng_key = functools.partial(jax.random.fold_in, st['key'],
                                        prog.topo_index[node])
            self._exec_node(node, st['env'], is_train, rng_key,
                            new_aux=st['new_aux'])
        st['next'] = step + 1
        left = n_steps - step - 1
        if left == 0:
            self._pending = None
            self.outputs_cached = [from_jax(st['env'][_entry_key(n, i)],
                                            self._ctx)
                                   for n, i in prog.outputs]
            if is_train:
                for name, v in st['new_aux'].items():
                    self.aux_dict[name]._data = v
            self._partial = None
        return left

    def _lazy_outputs(self):
        self._out_handles = [from_jax(None, self._ctx)
                             for _ in self._prog.outputs]
        self._materialized = False
        return _LazyOutputs(self)

    def _materialize(self):
        if self._pending is None:
            return
        arg_data, aux_data, key = self._pending
        outs, new_aux = self._fwd(arg_data, aux_data, key, True)
        self._write_aux(new_aux)
        for h, o in zip(self._out_handles, outs):
            h._data = o
        self.outputs_cached = self._out_handles
        self._pending = None

    def _write_aux(self, new_aux):
        for a, v in zip(self.aux_arrays, new_aux):
            a._data = v

    @property
    def outputs(self):
        if self._pending is not None:
            self._materialize()
        if self.outputs_cached is None:
            self.forward(False)
        return self.outputs_cached

    # -- backward ---------------------------------------------------------
    def backward(self, out_grads=None, is_train=True):
        """Reference GraphExecutor::Backward (graph_executor.cc:93)."""
        from . import telemetry as _tele
        with _tele.span('executor.backward', 'executor'):
            try:
                return self._backward_impl(out_grads, is_train)
            except Exception as e:
                _tele.programs.maybe_oom_report(e)
                raise

    def _backward_impl(self, out_grads=None, is_train=True):
        if self._use_staged():
            return self._backward_staged(out_grads)
        if self._pending is not None:
            arg_data, aux_data, key = self._pending
        else:
            arg_data = tuple(a._data for a in self.arg_arrays)
            aux_data = tuple(a._data for a in self.aux_arrays)
            key = _random.next_key()
        heads = self._head_grads(out_grads, arg_data, aux_data)
        if _faults.enabled():
            # dispatch-exception seam: the per-batch loop's fused
            # fwd+bwd is about to train one step
            _faults.maybe_raise('executor')
        hv = dv = None
        rets = list(self._fwd_bwd(arg_data, aux_data, key, heads))
        outs, new_aux, grads = rets[0], rets[1], rets[2]
        extra = rets[3:]
        if self._health_on:
            hv = extra.pop(0)
        if self._dyn_on:
            dv = extra.pop(0)
        self._write_aux(new_aux)
        if self._pending is not None:
            for h, o in zip(self._out_handles, outs):
                h._data = o
            self.outputs_cached = self._out_handles
            self._pending = None
        else:
            self.outputs_cached = [from_jax(o, self._ctx) for o in outs]
        self._assign_grads(grads)
        if hv is not None:
            # the sentinel check fetches the small stats vector — the
            # per-batch loop's one added sync (it already synchronizes
            # per batch for its metric). On a non-finite flag the
            # offending batch is STILL loaded in arg_dict, so the
            # first-bad-layer bisect replays it directly.
            from .telemetry import health as _health
            _health.note_step(hv, source='executor',
                              bisect=self.first_nonfinite_node)
        if dv is not None:
            # per-layer dynamics row: rides the same per-batch sync
            from .telemetry import dynamics as _dynamics
            _dynamics.note_step(dv, self._grad_names, self._out_names)

    def _head_grads(self, out_grads, arg_data, aux_data):
        if out_grads is None:
            return tuple(jnp.ones(s, d)
                         for s, d in self._out_shapes(arg_data, aux_data))
        if isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        heads = tuple(g._data if isinstance(g, NDArray) else jnp.asarray(g)
                      for g in out_grads)
        # head grads handed over from ANOTHER module's executor live on
        # that module's devices (SequentialModule chains modules across
        # device groups); pull them onto this computation's output
        # sharding — the reference's engine does this copy implicitly
        # via cross-context dependency edges. Target shardings: the
        # materialized outputs when available (callers that build head
        # grads have read get_outputs()); else, for a single-device
        # computation, the args' device.
        outs = self.outputs_cached
        if outs and len(outs) == len(heads):
            return tuple(_align_head(g, o._data.sharding)
                         for g, o in zip(heads, outs))
        arg_shardings = {a.sharding for a in arg_data
                         if hasattr(a, 'sharding')}
        if len(arg_shardings) == 1:
            (sh,) = arg_shardings
            if len(sh.device_set) == 1:
                heads = tuple(_align_head(g, sh) for g in heads)
        return heads

    def _out_shapes(self, arg_data, aux_data):
        key = tuple((a.shape, str(a.dtype)) for a in arg_data)
        cached = getattr(self, '_out_shapes_memo', None)
        if cached is not None and cached[0] == key:
            return cached[1]
        outs = jax.eval_shape(lambda a, x: self._run_eager(a, x, jnp.zeros((2,), jnp.uint32), True)[0],
                              arg_data, aux_data)
        res = [(o.shape, o.dtype) for o in outs]
        self._out_shapes_memo = (key, res)
        return res

    def _assign_grads(self, grads):
        for name, g in zip(self._grad_names, grads):
            dst = self.grad_dict[name]
            req = self._grad_req[name]
            if req == 'add':
                dst._data = dst._data + g.astype(dst._data.dtype)
            else:
                dst._data = g.astype(dst._data.dtype)

    # -- staged (group2ctx / monitor) mode --------------------------------
    def _use_staged(self):
        return (self._group2ctx is not None or self._monitor is not None
                or self._prog.has_host_ops)

    def _node_device(self, node):
        if self._group2ctx:
            grp = node.attr_dict.get('ctx_group')
            if grp and grp in self._group2ctx:
                return self._group2ctx[grp].jax_device()
        return self._ctx.jax_device()

    def _env_put_variable(self, node, env):
        """Load a variable node's current value into an eager env."""
        src = (self.aux_dict[node.name] if node.name in self.aux_dict
               else self.arg_dict[node.name])
        env[_entry_key(node, 0)] = jax.device_put(src._data,
                                                  self._node_device(node))

    def _snapshot_env(self):
        """Fresh eager env with all variable values snapshotted."""
        env = {}
        for node in self._prog.topo:
            if node.is_variable():
                self._env_put_variable(node, env)
        return env

    def _exec_node(self, node, env, is_train, rng_key, new_aux=None):
        """Eagerly execute one non-variable node into ``env``.

        Shared per-node dispatch for the staged forward and the
        partial_forward stepping path: group2ctx device placement,
        host-op direct call, monitor callbacks, and mutate_inputs aux
        collection (into ``new_aux`` keyed by aux name, if given) all
        live here so the two eager paths cannot drift.
        """
        dev = self._node_device(node)
        op = node.opdef()
        _reg.record(op)
        attrs = dict(node.attrs)
        if op.train_aware:
            attrs['__is_train__'] = bool(is_train)
        ins = [jax.device_put(env[_entry_key(p, i)], dev)
               for p, i in node.inputs]
        if op.needs_rng:
            ins.append(rng_key())
        # same layer-name attribution as the jitted runner: profiler
        # spans and any per-op jit cache entries carry the node name
        with jax.named_scope(self._prog.scope_names[
                self._prog.topo_index[node]]):
            outs = op.fn(attrs, *ins)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        for i, o in enumerate(outs):
            env[_entry_key(node, i)] = o
        if self._monitor is not None:
            # reference entry naming: <node>_output / <node>_output<i>
            # (what Monitor patterns like '.*output.*' match against)
            nvis = op.n_visible_outputs(node.attrs)
            for i in range(nvis):
                self._monitor('%s_output' % node.name if nvis == 1 else
                              '%s_output%d' % (node.name, i),
                              from_jax(outs[i], self._ctx))
        if new_aux is not None:
            for in_idx, out_idx in op.mutated(node.attrs).items():
                if in_idx < len(node.inputs):
                    src, _ = node.inputs[in_idx]
                    if src.is_variable() and src.name in self.aux_dict:
                        new_aux[src.name] = outs[out_idx]
        return outs

    def _forward_staged(self, is_train):
        env = {}
        prog = self._prog
        new_aux = {} if is_train else None
        for node in prog.topo:
            if node.is_variable():
                self._env_put_variable(node, env)
            else:
                self._exec_node(node, env, is_train, _random.next_key,
                                new_aux=new_aux)
        if new_aux:
            for name, v in new_aux.items():
                self.aux_dict[name]._data = v
        self.outputs_cached = [from_jax(env[_entry_key(n, i)], self._ctx)
                               for n, i in prog.outputs]
        self._staged_env_inputs = None
        return self.outputs_cached

    def _backward_staged(self, out_grads):
        # eager vjp over the pure runner (device movement handled by jax)
        arg_data = tuple(a._data for a in self.arg_arrays)
        aux_data = tuple(a._data for a in self.aux_arrays)
        key = _random.next_key()
        grad_idx = tuple(self._prog.arg_names.index(n) for n in self._grad_names)

        def f(wrt):
            full = list(arg_data)
            for i, gi in enumerate(grad_idx):
                full[gi] = wrt[i]
            outs, _ = self._run_eager(tuple(full), aux_data, key, True)
            return outs

        wrt = tuple(arg_data[gi] for gi in grad_idx)
        outs, vjp = jax.vjp(f, wrt)
        if out_grads is None:
            heads = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            heads = tuple(g._data if isinstance(g, NDArray) else jnp.asarray(g)
                          for g in out_grads)
            if len(heads) != len(outs):
                raise ValueError(
                    'backward got %d head gradients for %d outputs'
                    % (len(heads), len(outs)))
            # cross-device handoff (see _head_grads): cotangents must
            # live where the primals do
            heads = tuple(_align_head(g, o.sharding)
                          for g, o in zip(heads, outs))
        (grads,) = vjp(heads)
        self.outputs_cached = [from_jax(o, self._ctx) for o in outs]
        self._assign_grads(grads)

    def first_nonfinite_node(self, overrides=None, is_train=True):
        """First-bad-layer bisect (telemetry/health): replay the graph
        through the staged per-node path and return the first symbol
        whose VALUE is non-finite, as ``(name, output_index)`` — or
        None when everything is finite. Variables are checked too, so a
        poisoned weight (or a NaN input batch) is named directly rather
        than through the first op that touches it.

        ``overrides`` maps variable names to jax arrays replacing the
        executor's current values (the fused window loops pass the
        offending batch's draw-time snapshot). Parameters are whatever
        the executor holds NOW — for a window incident that is the
        post-window state, which a mid-window NaN has usually poisoned;
        the poisoned weight then IS the attribution. Once-per-incident
        cost: one eager dispatch + host check per node."""
        from .telemetry.health import has_nonfinite
        prog = self._prog
        env = {}
        key = _random.next_key()
        mon, self._monitor = self._monitor, None   # no monitor callbacks
        try:                                       # during the replay
            for node in prog.topo:
                if node.is_variable():
                    if overrides and node.name in overrides:
                        env[_entry_key(node, 0)] = jax.device_put(
                            overrides[node.name], self._node_device(node))
                    else:
                        self._env_put_variable(node, env)
                    vals = (env[_entry_key(node, 0)],)
                else:
                    rng_key = functools.partial(jax.random.fold_in, key,
                                                prog.topo_index[node])
                    vals = self._exec_node(node, env, is_train, rng_key)
                for i, v in enumerate(vals):
                    if has_nonfinite(np.asarray(v)):
                        return node.name, i
        finally:
            self._monitor = mon
        return None

    # -- misc API ---------------------------------------------------------
    def set_monitor_callback(self, callback, monitor_all=False):
        """Reference executor.h:148 SetMonitorCallback; forces staged mode."""
        self._monitor = callback
        self._monitor_all = monitor_all

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        self._partial = None  # param writes invalidate a stepping pass
        dev = self._ctx.jax_device()
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                dst = self.arg_dict[name]
                dst._data = jax.device_put(
                    arr._data.astype(dst._data.dtype), dev)
            elif not allow_extra_params:
                raise ValueError('Found name "%s" that is not in the arguments' % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    dst = self.aux_dict[name]
                    dst._data = jax.device_put(
                        arr._data.astype(dst._data.dtype), dev)
                elif not allow_extra_params:
                    raise ValueError('Found name "%s" that is not in the auxiliary states' % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes; XLA recompiles (cached per shape)."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for name, sh in zip(self._prog.arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if tuple(cur.shape) == tuple(sh):
                new_args[name] = cur
            else:
                new_args[name] = nd_zeros(sh, ctx=self._ctx,
                                          dtype=str(cur._data.dtype))
        new_aux = {}
        for name, sh in zip(self._prog.aux_names, aux_shapes):
            cur = self.aux_dict[name]
            new_aux[name] = cur if tuple(cur.shape) == tuple(sh) else \
                nd_zeros(sh, ctx=self._ctx, dtype=str(cur._data.dtype))
        grads = None
        if any(g is not None for g in self.grad_arrays):
            grads = {n: nd_zeros(new_args[n].shape, ctx=self._ctx,
                                 dtype=str(new_args[n]._data.dtype))
                     for n in self._grad_names}
        return Executor(self._symbol, self._ctx, new_args, grads,
                        self._grad_req, new_aux, group2ctx=self._group2ctx)

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))


class _LazyOutputs(list):
    """List proxy that materializes the deferred training forward on access."""

    def __init__(self, executor):
        super().__init__(executor._out_handles)
        self._exec = executor

    def __getitem__(self, i):
        self._exec._materialize()
        return super().__getitem__(i)

    def __iter__(self):
        self._exec._materialize()
        return super().__iter__()


def simple_bind(symbol, ctx, grad_req='write', type_dict=None, group2ctx=None,
                shared_exec=None, **kwargs):
    """Reference symbol.py:1250 Symbol.simple_bind: infer shapes, allocate."""
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
    if arg_shapes is None:
        raise MXNetError('cannot infer shapes')
    type_dict = type_dict or {}
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    # dtypes via type inference (reference simple_bind runs InferType):
    # a Cast to bf16 makes downstream parameters bf16 automatically
    arg_types, _, aux_types = symbol.infer_type(**type_dict)
    args = {}
    for name, sh, it in zip(arg_names, arg_shapes, arg_types):
        # keep the dtype OBJECT: str() of the bf16 scalar class is not a
        # parseable dtype name (np_dtype is idempotent)
        args[name] = nd_zeros(sh, ctx=ctx,
                              dtype=np_dtype(type_dict.get(name, it)))
    aux = {}
    for name, sh, it in zip(aux_names, aux_shapes, aux_types):
        aux[name] = nd_zeros(sh, ctx=ctx, dtype=np_dtype(it))
    grads = None
    req_of = (lambda n: grad_req) if isinstance(grad_req, str) else \
        (lambda n: grad_req[arg_names.index(n)] if isinstance(grad_req, (list, tuple))
         else grad_req.get(n, 'null'))
    if grad_req != 'null':
        grads = {}
        for name, sh in zip(arg_names, arg_shapes):
            if req_of(name) != 'null':
                grads[name] = nd_zeros(sh, ctx=ctx,
                                       dtype=str(args[name]._data.dtype))
    return Executor(symbol, ctx, args, grads, grad_req, aux,
                    group2ctx=group2ctx)
