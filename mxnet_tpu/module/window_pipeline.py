"""Shared window machinery for the fused fit/eval fast paths.

module/fused_fit.py compiles W training steps into one XLA call;
module/fused_eval.py does the same for the read-only half of the API
(score / predict / iter_predict). Both loops need identical host-side
input machinery, extracted here so it is ONE subsystem with two
consumers instead of two private copies:

- draw-time batch snapshotting (:meth:`WindowPipeline.collect`):
  iterators may legally reuse their DataBatch/NDArray buffers for the
  next batch — the reference per-batch loop consumes each batch before
  drawing the next. jax arrays are immutable, so references captured
  as each batch is drawn stay valid while a whole window is in flight,
  along with the batch's draw-time ``pad``/``index``;
- window stacking (:meth:`WindowPipeline.device_batches`): W batches
  become (W, ...) device arrays with ONE host->device transfer per
  input. Host-resident parts stack on the host first so the whole
  window crosses in a single ``device_put`` (not W per-batch
  transfers, each a host dispatch of its own); on an SPMD
  mesh the stacks land dp-sharded over the batch axis
  (:meth:`executor_group.SPMDExecutorGroup.window_sharding`). The host
  stack lives in a window-sized buffer of
  :func:`~mxnet_tpu.ndarray.ndarray._host_buffer`, each batch written
  into its slot: memory that an earlier window's stack was written to
  wherever one of its size is idle, because the first touch of new
  pages, not the copy, is what a 2.5 GB stack costs. A stack's memory
  is idle, and may be written again, when nothing refers to it any
  more: this pipeline's identity cache, a transfer still reading it, a
  cpu-backed array that adopted it. That is decided by reference, not
  by counting windows: one buffer serves every window where the
  transfer has ended by the next stack, two rotate where something
  holds the stack a window longer; they outlive an epoch. An identity
  cache short-circuits synthetic/benchmark iterators that yield the
  same arrays every batch;
- a one-thread upload pool (:meth:`WindowPipeline.start_put`): window
  k+1's stack + transfer run on a side thread while window k computes
  on device — the stack's copies and the transfer both release the
  GIL, so the overlap is real even on a one-core host;
- the in-graph metric plans (:func:`plan_metric`): sufficient
  statistics for Accuracy / TopKAccuracy / CrossEntropy (and
  composites of them) that both loops compile into their scan bodies,
  packed so the host needs a single fetch per window.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp

from .. import metric as metric_mod
from .. import telemetry as _tele
from ..ndarray.ndarray import _POOLED_FROM, _host_buffer, from_jax

__all__ = ['WindowPipeline', 'window_size', 'module_platform', 'plan_metric',
           'plan_metric_or_reason', 'host_wrap',
           'registered_jit', 'health_sentinel', 'dynamics_sentinel',
           'window_bisect']


def module_platform(module):
    """Platform of the devices the module was bound to ('tpu', 'cpu')."""
    return module._context[0].jax_device().platform


def window_size(module, flag='MXTPU_FIT_STEPS_PER_CALL'):
    """Window size W from the given env flag; 0 = auto: 32 for a module
    bound to TPU devices (one dispatch and one fetch per 32 steps), 4 for
    one bound to the CPU (enough to exercise the windowed path in tests)."""
    from ..config import flags
    flags.reload(flag)
    n = flags.get(flag)
    if n > 0:
        return n
    return 32 if module_platform(module) == 'tpu' else 4


def registered_jit(name, fn, step_flops=False, **jit_kwargs):
    """``jax.jit`` + telemetry program registration in one step — the
    compile-site idiom both fused loops use. With telemetry on, the
    returned callable compiles via an explicit ``lower().compile()``
    and the executable's XLA cost/memory analysis lands in the
    per-program table (telemetry.programs); ``step_flops=True`` marks
    the program whose FLOPs define a training step (the
    ``xla.step_flops`` gauge). With telemetry off this is exactly
    ``jax.jit(fn)``."""
    return _tele.programs.register(name, jax.jit(fn, **jit_kwargs),
                                   step_flops=step_flops)


def health_sentinel():
    """The in-graph training-health stats fn for a compiled window body
    (telemetry/health: grad/param norms, update ratio, per-output
    finite flags packed into one f32 vector per step, stacked by the
    scan so a mid-window NaN carries its exact step index through the
    window's single host fetch) — or None while the sentinels are off,
    leaving the traced window byte-identical to today's program."""
    from ..telemetry import health as _health
    return _health.step_stats if _health.enabled() else None


def dynamics_sentinel():
    """The in-graph per-layer dynamics stats fn for a compiled window
    body (telemetry/dynamics: per-layer grad/param norms + update
    ratios and per-output activation zero-fractions packed into one
    f32 vector per step, stacked by the scan so the (W, k) matrix
    rides the window's single host fetch) — or None while
    MXTPU_DYNAMICS is off, leaving the traced window byte-identical
    to today's program."""
    from ..telemetry import dynamics as _dynamics
    return _dynamics.step_stats if _dynamics.enabled() else None


def _aux_sentinel(symbol, aux_names, stat_names):
    """``fn(new_aux) -> (nodes, k)``: this step's rows of the auxiliary
    states that ``stat_names(symbol)`` names, stacked; None while telemetry
    is off or the graph has no such node."""
    if not _tele.enabled():
        return None
    idx = [aux_names.index(n) for n in stat_names(symbol) if n in aux_names]
    if not idx:
        return None
    return lambda new_aux: jnp.stack(
        [new_aux[i].astype(jnp.float32) for i in idx])


def moe_sentinel(symbol, aux_names):
    """For a compiled window body whose graph holds routed expert layers:
    ``fn(new_aux) -> (layers, len(MOE_STATS))``, this step's statistics as
    each ``MoE`` node left them in its auxiliary state, stacked by the scan
    so that the (W, layers, k) block rides the window's single host fetch;
    :func:`note_moe_window` turns it into the ``moe.*`` counters. None
    while telemetry is off or the graph has no such layer, leaving the
    traced window byte-identical to the plain form."""
    from ..ops.transformer import moe_stat_names
    return _aux_sentinel(symbol, aux_names, moe_stat_names)


def note_moe_window(rows, win=None):
    """The host side of :func:`moe_sentinel`: `rows` (W, layers, k) as
    fetched. Counters ``moe.pairs`` (token-expert pairs computed by the
    experts held here), ``moe.tokens`` (tokens routed, per layer) and
    ``moe.dropped``; ``moe.passes`` (passes the expert layers made over
    their sorted buffers) and ``moe.layer_steps`` (layers times steps), so
    that their ratio is the mean and 1.0 says that one short pass always
    held the rows present; gauge ``moe.load_max_over_mean`` (the fullest
    held expert's rows over the mean, worst layer and step of the window);
    and one ``moe.window`` event with each step's pairs per layer."""
    from ..ops.transformer import MOE_STATS
    col = {n: rows[..., i] for i, n in enumerate(MOE_STATS)}
    _tele.counter('moe.pairs').inc(int(col['pairs'].sum()))
    _tele.counter('moe.tokens').inc(int(col['tokens'].sum()))
    _tele.counter('moe.dropped').inc(int(col['dropped'].sum()))
    _tele.counter('moe.passes').inc(int(col['passes'].sum()))
    _tele.counter('moe.layer_steps').inc(col['passes'].size)
    _tele.gauge('moe.load_max_over_mean').set(
        float(col['load_max_over_mean'].max()))
    _tele.event('moe.window', win=win,
                pairs=col['pairs'].astype(int).tolist(),
                dropped=int(col['dropped'].sum()))


def hyper_sentinel(symbol, aux_names):
    """:func:`moe_sentinel` for the ``HyperPre`` nodes of the graph:
    ``fn(new_aux) -> (nodes, len(HYPER_STATS))``, each step's statistics as
    the nodes left them in their auxiliary states; None while telemetry is
    off or the graph has no such node."""
    from ..ops.transformer import hyper_stat_names
    return _aux_sentinel(symbol, aux_names, hyper_stat_names)


def note_hyper_window(rows, win=None):
    """The host side of :func:`hyper_sentinel`: `rows` (W, nodes, k) as
    fetched. Gauge ``hyper.res_dev_max``: the largest distance of a mixing
    matrix's row and column sums from 1, over the window's steps, nodes
    and tokens (0 is doubly stochastic)."""
    from ..ops.transformer import HYPER_STATS
    _tele.gauge('hyper.res_dev_max').set(
        float(rows[..., HYPER_STATS.index('res_dev_max')].max()))


def delta_sentinel(symbol, aux_names):
    """:func:`moe_sentinel` for the ``GatedDeltaRule`` nodes of the graph:
    ``fn(new_aux) -> (nodes, len(DELTA_STATS))``."""
    from ..ops.transformer import delta_stat_names
    return _aux_sentinel(symbol, aux_names, delta_stat_names)


def note_delta_window(rows, win=None):
    """The host side of :func:`delta_sentinel`: `rows` (W, nodes, k) as
    fetched. Counter ``delta_rule.rows`` (rows handed to the nodes, summed
    over nodes and steps); gauge ``delta_rule.state_abs_max``: the largest magnitude
    of a recurrent state after a step's last row, over the window's steps,
    nodes and heads."""
    from ..ops.transformer import DELTA_STATS
    col = {n: rows[..., i] for i, n in enumerate(DELTA_STATS)}
    _tele.counter('delta_rule.rows').inc(int(col['rows'].sum()))
    _tele.gauge('delta_rule.state_abs_max').set(
        float(col['state_abs_max'].max()))


def window_bisect(executor, data_names, label_names, snaps, is_train,
                  defer_fn=None):
    """First-bad-layer driver for a fused-window incident: returns
    ``bisect(i)`` replaying window step ``i``'s draw-time snapshot
    through the staged per-node executor path
    (:meth:`~mxnet_tpu.executor.Executor.first_nonfinite_node`).
    ``defer_fn`` materializes a deferred uint8 batch (fused fit's
    device-augment mode) so the replay sees the graph's real input."""
    def bisect(i):
        ds, ls, _, _ = snaps[i]
        if defer_fn is not None:
            from .. import random as _random
            ds = (defer_fn(ds[0], _random.next_key()),) + tuple(ds[1:])
        overrides = dict(zip(data_names, ds))
        overrides.update(zip(label_names, ls))
        return executor.first_nonfinite_node(overrides, is_train=is_train)
    return bisect


def host_device():
    """The host (cpu-backend) jax device, or None when unavailable."""
    try:
        return jax.local_devices(backend='cpu')[0]
    except RuntimeError:
        return None


def host_wrap(ctx):
    """Returns ``host_nd(a)``: a cpu-backed NDArray wrapper for
    already-host data, so downstream ``.asnumpy()`` calls (metric math,
    user code) cost no device round-trip."""
    dev = host_device()

    def host_nd(a):
        arr = jax.device_put(np.asarray(a), dev) if dev is not None \
            else jnp.asarray(a)
        return from_jax(arr, ctx)

    return host_nd


# ---------------------------------------------------------------------------
# metric plans: in-graph sufficient statistics + host-side apply
# ---------------------------------------------------------------------------

def _plan_one(m):
    """(stats_fn(outs, labels) -> (sum, count)) for one metric, or None
    if unsupported. Statistics mirror metric.py's numpy math — in
    particular every reference metric RAVELS the label, so an (N, 1)
    column label (CSVIter and friends) compares elementwise against the
    (N,) argmax instead of broadcasting into an (N, N) matrix."""
    if type(m) is metric_mod.Accuracy:
        if getattr(m, 'axis', 1) != 1:
            return None     # stats below assume 2-D preds, class axis 1
        def stats(outs, labels):
            pred = outs[0]
            lab = labels[0].reshape(-1).astype(jnp.int32)
            hit = jnp.argmax(pred, axis=-1).astype(jnp.int32) == lab
            return jnp.sum(hit).astype(jnp.float32), \
                jnp.float32(hit.size)
        return stats
    if type(m) is metric_mod.TopKAccuracy:
        k = m.top_k

        def stats(outs, labels, k=k):
            pred = outs[0]
            lab = labels[0].reshape(-1).astype(jnp.int32)
            # reference TopKAccuracy clamps: top_k = min(classes, k)
            # (lax.top_k would raise past the minor dim, where the
            # per-batch loop computes a valid result)
            _, idx = jax.lax.top_k(pred, min(k, pred.shape[-1]))
            hit = jnp.any(idx.astype(jnp.int32) == lab[:, None], axis=-1)
            return jnp.sum(hit).astype(jnp.float32), \
                jnp.float32(hit.size)
        return stats
    if type(m) is metric_mod.CrossEntropy:
        eps = getattr(m, 'eps', 1e-12)

        def stats(outs, labels, eps=eps):
            pred = outs[0]
            lab = labels[0].reshape(-1).astype(jnp.int32)
            p = jnp.take_along_axis(pred, lab[:, None], axis=-1)[:, 0]
            return jnp.sum(-jnp.log(p + eps)).astype(jnp.float32), \
                jnp.float32(lab.size)
        return stats
    if type(m) is metric_mod.Perplexity and m.axis in (-1, 1):
        ignore = m.ignore_label

        def stats(outs, labels, ignore=ignore):
            pred = outs[0]
            lab = labels[0].reshape(-1).astype(jnp.int32)
            # an ignored label (-1) is no class: it reads class 0 and
            # counts for nothing, as metric.Perplexity has it
            kept = jnp.ones_like(lab, bool) if ignore is None \
                else lab != ignore
            p = jnp.take_along_axis(
                pred, jnp.where(kept, lab, 0)[:, None], axis=-1)[:, 0]
            p = jnp.where(kept, p, 1.0)
            return jnp.sum(-jnp.log(jnp.maximum(1e-10, p))) \
                .astype(jnp.float32), jnp.sum(kept).astype(jnp.float32)
        return stats
    return None


def _reads(m, output_names, label_names):
    """(output index, label index) that leaf metric `m` reads, or a string
    saying why the plan cannot tell: a metric names what it reads through
    ``output_names`` / ``label_names`` (one of each), and may leave out a
    name only where the graph has just one to choose from."""
    at = []
    for kind, named, have in (('output', m.output_names, output_names),
                              ('label', m.label_names, label_names)):
        if named is None:
            if len(have) != 1:
                return ('metric %r names no %s and the graph has %d (%s)'
                        % (m.name, kind, len(have), ', '.join(have)))
            at.append(0)
        elif len(named) != 1 or named[0] not in have:
            return ('metric %r reads %s %s; the graph has %s'
                    % (m.name, kind, list(named), ', '.join(have)))
        else:
            at.append(list(have).index(named[0]))
    return tuple(at)


def plan_metric_or_reason(eval_metric, out_shapes=None, label_names=None,
                          output_names=None):
    """((children, [stats_fn]), None), or (None, why no plan was made).

    children are the leaf EvalMetric objects to update. When
    ``out_shapes``/``label_names`` are given, also enforces the geometry
    every stat fn assumes, so the fit and eval loops cannot drift on the
    eligibility condition: every output a metric reads is 2-D (batch,
    classes) with classes >= 2 (reference Accuracy SKIPS the argmax on a
    width-1 class dim and compares raw values). A graph with ONE output
    and one label is planned as ever (its stat fns read ``outs[0]``,
    ``labels[0]``). With several (``output_names``: the graph's), every
    leaf metric has to name the one output and the one label it reads
    (``EvalMetric(output_names=[...], label_names=[...])``); its stat fn
    is handed just those, and an output no metric names is read by
    nothing, so the compiled window neither stacks nor fetches it."""
    if isinstance(eval_metric, metric_mod.CompositeEvalMetric):
        children = list(eval_metric.metrics)
    else:
        children = [eval_metric]
    several = out_shapes is not None and (
        len(out_shapes) != 1
        or (label_names is not None and len(label_names) != 1))
    if several and (output_names is None or label_names is None
                    or len(output_names) != len(out_shapes)):
        return None, ('%d outputs and %s labels, unnamed'
                      % (len(out_shapes), 'no' if label_names is None
                         else len(label_names)))
    fns = []
    for m in children:
        fn = _plan_one(m)
        if fn is None:
            return None, ('metric %r (%s) has no in-graph statistics'
                          % (m.name, type(m).__name__))
        oi = 0
        if several:
            at = _reads(m, list(output_names), list(label_names))
            if isinstance(at, str):
                return None, at
            oi, li = at
            fn = functools.partial(_pick, fn, oi, li)
        if out_shapes is not None and (
                len(out_shapes[oi]) != 2 or out_shapes[oi][1] < 2):
            return None, ('output %d of shape %s is not (batch, classes '
                          '>= 2)' % (oi, tuple(out_shapes[oi])))
        fns.append(fn)
    return (children, fns), None


def _pick(fn, oi, li, outs, labels):
    return fn((outs[oi],), (labels[li],))


def plan_metric(eval_metric, out_shapes=None, label_names=None,
                output_names=None):
    """:func:`plan_metric_or_reason`'s plan alone: (children, [stats_fn])
    or None."""
    return plan_metric_or_reason(eval_metric, out_shapes, label_names,
                                 output_names)[0]


def place_replicated(mesh, *trees):
    """device_put every array in the given pytrees onto the mesh's
    fully-replicated sharding (no-op for arrays already there): on an
    SPMD group every array a compiled window closes over must live
    replicated on the mesh, or jit rejects the mixed-device argument
    set. Returns the trees in call order."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    put = lambda a: a if getattr(a, 'sharding', None) == rep \
        else jax.device_put(a, rep)  # noqa: E731
    return tuple(jax.tree_util.tree_map(put, t) for t in trees)


def is_update_sharded(a, row):
    """Whether ``a`` is already in the ZeRO update-phase form for row
    sharding ``row`` (1-D and equivalently sharded) — jit outputs may
    come back under an equivalent-but-distinct sharding object, so
    plain equality is not enough."""
    if getattr(a, 'ndim', 0) != 1:
        return False
    sh = getattr(a, 'sharding', None)
    if sh is None:
        return False
    if sh == row:
        return True
    try:
        return sh.is_equivalent_to(row, 1)
    except Exception:  # noqa: BLE001 — sharding impl without the probe
        return False


def place_update_sharded(mesh, arrays_with_shapes):
    """Place optimizer-state leaves in the ZeRO update-phase layout
    (arXiv:2004.13336): each ``(array, canonical_shape)`` pair comes
    back as a 1-D leaf zero-padded to a multiple of dp and row-sharded
    over the mesh's dp axis (executor_group.SPMDExecutorGroup.
    update_sharding) — 1/dp of every leaf per device. Arrays already in
    that form pass through untouched, so the per-window snapshot is a
    no-op in steady state and the conversion runs only on entry to the
    fused path (first window, after a restore, after a flush)."""
    import jax
    from .executor_group import SPMDExecutorGroup
    from ..parallel.sharding import zero_flatten, zero_pad_len
    row = SPMDExecutorGroup.update_sharding(mesh)
    dp = int(mesh.shape['dp'])
    out = []
    for a, shape in arrays_with_shapes:
        padded = zero_pad_len(int(np.prod(shape)) if shape else 1, dp)
        if is_update_sharded(a, row) and int(a.shape[0]) == padded:
            out.append(a)
            continue
        out.append(jax.device_put(zero_flatten(a, dp), row))
    return out


def rebind_children(eval_metric, current_children):
    """Point a cached loop's stat writeback at the CURRENT call's
    metric objects (each call may construct fresh instances from the
    same config — exactly what the loops' reuse signatures guarantee,
    so the stat fns, which capture only config values like top_k/eps,
    stay valid). Returns the new children list (or the old one for a
    loop without in-graph stats)."""
    if isinstance(eval_metric, metric_mod.CompositeEvalMetric):
        return list(eval_metric.metrics)
    if current_children is not None:
        return [eval_metric]
    return current_children


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

class WindowPipeline:
    """Draw/stack/upload machinery for one compiled-window loop.

    ``device_fn`` resolves the target jax device lazily (the bound
    executor's context); ``mesh`` switches placement to dp-sharded
    window stacks. ``span_prefix`` names the telemetry spans
    ('fused_fit' / 'fused_eval'): ``.draw`` around a window's draws with
    one ``.next`` per batch inside it, ``.stack`` around the host-side
    stacking (``reused``: how many of its ``bytes`` went into memory
    written before) and ``.upload`` around the ``device_put`` calls, the
    last two on the thread that does the work (the side thread with the
    pool). Each carries ``win``, the window's sequence number since this
    object was built, which the owning loop hands on to its own
    ``.put`` / ``.dispatch`` / ``.fetch`` spans. Counters
    ``<prefix>.stacks_reused`` / ``.stacks_new``: the windows whose large
    host stacks all went into memory written before, and those of which
    one at least went into new memory (a window with no host stack of a
    pooled size counts in neither). The owning loop object lives across
    fit()/score() calls, so the upload pool it carries does too.
    """

    def __init__(self, window, device_fn, mesh=None, span_prefix='window',
                 donate=False):
        self.window = window
        self.mesh = mesh
        self._device_fn = device_fn
        self._span = span_prefix
        self._span_draw = span_prefix + '.draw'
        self._span_next = span_prefix + '.next'
        self._span_stack = span_prefix + '.stack'
        self._span_upload = span_prefix + '.upload'
        self._windows_drawn = 0
        self._dev_cache_key = None
        self._dev_cache = None
        self._pool_obj = None
        # donate=True: the consuming program DONATES the window stacks
        # to XLA, so a device stack handed out once is dead — the
        # identity cache then holds the HOST-side stacked arrays (the
        # np.stack memcpy is still saved) and the device transfer runs
        # fresh per window. The owning loop sets it to match its
        # program's donate_argnums (fused_fit honors MXTPU_FUSED_DONATE;
        # fused_eval never donates its read-only stacks).
        self.donate = donate

    # -- draw --------------------------------------------------------------
    def collect(self, it, limit=None):
        """Draw up to ``window`` batches (further bounded by ``limit``,
        the eval loops' num_batch remainder), snapshotting each batch's
        underlying jax arrays, pad, and index AT DRAW TIME. Returns
        (batches, snaps, win) with snaps a list of (data_arrays,
        label_arrays, pad, index) tuples and win the window's sequence
        number, the ``win`` attribute of its spans."""
        n = self.window if limit is None else min(self.window, limit)
        batches, snaps = [], []
        win = self._windows_drawn
        self._windows_drawn += 1
        with _tele.span(self._span_draw, self._span, win=win):
            while len(batches) < n:
                try:
                    # the iterator's own cost per batch, apart from the
                    # snapshotting below
                    with _tele.span(self._span_next, self._span, win=win):
                        b = next(it)
                except StopIteration:
                    break
                batches.append(b)
                snaps.append((tuple(a._data for a in b.data),
                              tuple(l._data for l in (b.label or ())),
                              getattr(b, 'pad', None),
                              getattr(b, 'index', None)))
        return batches, snaps, win

    # -- stack + upload ----------------------------------------------------
    def device_batches(self, snaps, win=None):
        """Stack W draw-time snapshots into device (W, ...) arrays.
        Identity-cached: synthetic/benchmark iterators yield the same
        arrays every batch, so the transfer happens once. The cache key
        holds STRONG references to the source arrays — identity is
        compared against live objects, so a freed array's id can never
        produce a false hit.

        With ``donate`` set the device stacks are consumed by the
        dispatch, so the cache stores the HOST-side stacks (or, for
        device-resident sources, the unstacked parts) instead and
        re-runs the device transfer per window (the prefetch pool hides
        it behind window k's compute) — returning a cached device array
        would hand the program an already-deleted donated buffer.

        A host stack is written, batch by batch, into a buffer of
        :func:`_host_buffer`'s: an idle one of its size where there is
        one, else new memory. It becomes idle again by itself when the
        last reference to it goes: the cache's (when the next window
        misses, or at :meth:`drop_cache`), the transfer's, a cpu-backed
        array's whose memory it is. Nothing here counts windows to
        decide that. What is too large to be kept idle (`_idle_limit`)
        is new memory every time, as ``np.stack``'s result was.

        The ``.stack`` and ``.upload`` spans open here, on the thread
        that runs this (the side thread under :meth:`start_put`'s
        pool): ``.stack`` not on a cache hit, ``.upload`` wherever
        ``device_put`` is called. ``.upload`` ends when the calls
        return and adds no synchronisation: a backend that copies on
        behind the call (the TPU's does) ends the transfer later, by
        the time the window's program starts on the device."""
        arrays = [a for ds, ls, _, _ in snaps for a in ds + ls]
        nbytes = sum(a.nbytes for a in arrays)

        def upload(data_e, label_e):
            with _tele.span(self._span_upload, self._span, win=win,
                            bytes=nbytes):
                return (tuple(self._realize(e) for e in data_e),
                        tuple(self._realize(e) for e in label_e))

        if self._dev_cache_key is not None and \
                len(arrays) == len(self._dev_cache_key) and \
                all(a is c for a, c in zip(arrays, self._dev_cache_key)):
            if not self.donate:
                return self._dev_cache
            return upload(*self._dev_cache)
        # a miss: what the cache holds can never hit again, so it lets go
        # before this window's stacks take their memory and not after, and
        # the stack of the window before is idle as soon as its transfer
        # has ended (one buffer then serves every window)
        self.drop_cache()
        key = arrays

        def _on_host(a):
            if isinstance(a, np.ndarray):
                return True
            try:
                return all(d.platform == 'cpu' for d in a.devices())
            except Exception:  # noqa: BLE001 — tracer/abstract array
                return False

        # host-resident parts (defer-mode uint8 batches and their
        # labels) stack on the host so the whole window crosses to the
        # device in _realize()'s ONE device_put, not W per-batch
        # transfers. Device-resident parts stay unstacked in the cache
        # entry (the stacked device buffer is donate-consumed, but the
        # sources remain valid to restack from). A host stack's memory
        # is taken here and nothing of it touched, so that the span can
        # say how much of it has been written before.
        n_data = len(snaps[0][0])
        columns = [[ds[i] for ds, _, _, _ in snaps] for i in range(n_data)] \
            + [[ls[i] for _, ls, _, _ in snaps]
               for i in range(len(snaps[0][1]))]
        entries, reuse = [], []     # reuse: of each stack of a pooled size
        for parts in columns:
            if not all(_on_host(p) for p in parts):
                entries.append(('dev', tuple(parts)))
                continue
            out, reused = _host_buffer(
                (len(parts),) + tuple(parts[0].shape),
                np.result_type(*(p.dtype for p in parts)))
            entries.append(('host', out))
            if out.nbytes >= _POOLED_FROM:
                reuse.append(out.nbytes if reused else 0)
        with _tele.span(self._span_stack, self._span, win=win,
                        bytes=nbytes, reused=sum(reuse)):
            for (kind, out), parts in zip(entries, columns):
                if kind == 'host':
                    # each batch into its slot of the window's buffer
                    np.stack([np.asarray(p) for p in parts], out=out)
        if reuse:
            _tele.counter(self._span + ('.stacks_reused' if all(reuse)
                                        else '.stacks_new')).inc()
        data_e, label_e = entries[:n_data], entries[n_data:]
        data_stack, label_stack = upload(data_e, label_e)
        self._dev_cache_key = key
        self._dev_cache = (data_e, label_e) if self.donate \
            else (data_stack, label_stack)
        return data_stack, label_stack

    def _realize(self, entry):
        """One cache entry -> a fresh placed device stack."""
        kind, v = entry
        stack = v if kind == 'host' \
            else jnp.stack([jnp.asarray(p) for p in v])
        return self._shard(stack)

    def _shard(self, stack):
        if self.mesh is None:
            # source arrays may be committed to the host device
            # (cpu_pinned iterators); the window runs where the
            # executor's params live
            return jax.device_put(stack, self._device_fn())
        from .executor_group import SPMDExecutorGroup
        return jax.device_put(
            stack, SPMDExecutorGroup.window_sharding(self.mesh,
                                                     stack.ndim))

    def pool(self):
        """One-thread executor for the pipelined window upload. A
        single worker keeps transfers ordered; the owning loop (cached
        on the module across calls) keeps it for its lifetime."""
        if self._pool_obj is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool_obj = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix='mxtpu-window-put')
        return self._pool_obj

    def start_put(self, snaps, pool, win=None):
        """Begin the window's host-stack + device transfer; returns a
        no-arg resolver. With a pool, the stack + put for window k+1
        run on the side thread while window k computes on device, the
        previous window's stats fetch waits, and the optimizer's
        host-side window bookkeeping runs — the update/upload overlap.
        How much of it hid is in the spans: the side thread's
        ``.stack`` and ``.upload`` against the loop's ``.put`` wait of
        the same ``win``. The resolver refers to nothing but the
        result, so a window's device stack is freed with the last
        reference to it."""
        if pool is None:
            res = self.device_batches(snaps, win)
            return lambda: res
        return pool.submit(self.device_batches, snaps, win).result

    @staticmethod
    def drain(fut):
        """Resolve an in-flight prefetch before teardown (or an
        exception unwind) can race the side thread."""
        if fut is not None:
            try:
                fut()
            except Exception:  # noqa: BLE001 — primary error wins
                pass

    def drop_cache(self):
        """Release the last window's device stack + its strong host
        refs — the identity cache only ever hits while an epoch/pass
        is running."""
        self._dev_cache_key = None
        self._dev_cache = None
