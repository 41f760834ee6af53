"""Runtime telemetry: spans, counters, gauges — the observability layer.

The engine/executor/kvstore stack only earns "as fast as the hardware
allows" if we can see where time goes. This package is the process-wide
instrumentation the hot paths report through:

- a metrics registry (:mod:`.registry`): counters, gauges, histograms
  with recent-window p50/p95;
- a low-overhead span tracer (:func:`span`): times a host-side region
  into a histogram and a JSONL record (name, path, start, duration,
  thread, attributes), opens a ``jax.profiler.TraceAnnotation`` so that
  during any ``jax.profiler`` capture the span is an event of the
  capture's ``/host:CPU`` plane, on the device events' clock and on the
  line of its thread — and, whenever the chrome-trace profiler is
  running, writes into the same trace file ``profiler.py`` writes, so
  telemetry spans and engine op spans land on one timeline;
- XLA gauges (:mod:`.xla`): compile count/seconds via jax.monitoring,
  retrace-storm detection, live/peak device bytes, per-chip peaks;
- per-program cost attribution (:mod:`.programs`): every compile site
  routes through a registrar that captures XLA's cost/memory analysis
  per compiled program (``program.*`` gauges, a per-program summary
  table, the automatic ``xla.step_flops`` feed, and an
  on-RESOURCE_EXHAUSTED memory-breakdown report);
- training-health sentinels (:mod:`.health`, MXTPU_HEALTH=1): in-graph
  NaN/Inf detection with exact-step attribution through the fused
  windows, a first-bad-layer bisect, rolling-baseline anomaly detectors
  over step time / loss / grad-norm, an input-bound classifier, and a
  "Run health" block in the end-of-run summary (``health`` /
  ``anomaly`` JSONL records, ``MXTPU_HEALTH_ACTION={warn,record,raise}``);
- exporters (:mod:`.export`): an append-only JSONL log (host-stamped,
  size-capped via ``MXTPU_TELEMETRY_MAX_MB``) plus an end-of-run
  human-readable summary table (``tools/telemetry_report.py`` renders
  one or many per-host logs offline);
- the live plane (:mod:`.serve`, ``MXTPU_TELEMETRY_PORT``): a
  background HTTP endpoint exposing ``/metrics`` (Prometheus text),
  ``/healthz`` (200/503 from the health incident state) and
  ``/summary`` (snapshot JSON) — ``tools/telemetry_watch.py`` renders
  it as a refreshing dashboard;
- cluster aggregation (:mod:`.cluster`, ``MXTPU_TELEMETRY_SYNC_EVERY``):
  every N steps one small off-graph allgather carries each host's key
  gauges; process 0 publishes ``cluster.*`` per-host gauges, the
  step-time spread, the slowest-host id and a straggler classification
  (input-bound vs compute-bound). With ``MXTPU_ELASTIC_INPUT`` every
  host additionally derives the same shard-shift decision from the
  same gathered round and re-balances input shards away from an
  input-bound host at the next epoch boundary;
- request-level tracing (:mod:`.trace`): one trace id per serving
  request (minted, or client-supplied via ``X-Request-Id`` /
  ``traceparent``), a queue/coalesce/pad/dispatch/fetch/split stage
  breakdown per request as a ``trace`` JSONL record (N coalesced
  requests share ONE dispatch span id), exemplar trace ids on the
  ``serve.request_latency`` /metrics summary, and the request's spans
  merged into the chrome-trace timeline when the profiler runs;
- the SLO plane (:mod:`.slo`, ``MXTPU_SLO_LATENCY_MS`` /
  ``MXTPU_SLO_ERROR_PCT``): rolling error-budget burn rate over the
  serving request stream, ``slo.*`` gauges on ``/metrics``, and an
  ``slo_degraded`` /healthz state (distinct from hung/non-finite) on
  sustained burn, clearing on recovery;
- the incident flight recorder (:mod:`.flight`,
  ``MXTPU_FLIGHT_RECORDER``, default on with telemetry): a bounded
  in-memory ring of the most recent records, dumped to
  ``flight-<reason>.jsonl`` by every incident path — watchdog stall,
  non-finite incident, OOM report, SLO burn, supervised restart —
  so a postmortem has the seconds BEFORE the incident
  (``tools/trace_report.py`` renders a dump);
- per-layer training dynamics (:mod:`.dynamics`, ``MXTPU_DYNAMICS``):
  the in-graph sentinel extended from one global vector to a
  per-parameter matrix — per-layer grad-norm, param-norm, update
  ratio ``||dw||/||w||`` and activation zero-fractions on named
  outputs — computed inside the compiled fused window / executor
  programs and shipped home in the window's existing single fetch;
  per-layer spike detectors raise NAMED anomalies, non-finite layer
  statistics raise named-layer ``dynamics`` incidents, and
  ``dynamics.<layer>.*`` gauges publish at the decimated
  ``MXTPU_SCALARS_EVERY`` cadence;
- the run ledger (:mod:`.ledger`, ``MXTPU_SCALARS_EVERY``): a
  ``manifest`` JSONL record (resolved flags, jax version, device kind,
  mesh, git sha) plus a bounded per-step ``scalars`` timeseries (loss,
  lr, throughput, grad stats, eval metrics), mirrored as native
  TensorBoard event files through a dependency-free TFRecord/Event
  writer when ``MXTPU_TFEVENTS_DIR`` is set —
  ``tools/run_compare.py`` diffs two runs' ledgers and exits 1 on a
  regression past tolerance;
- the hang watchdog (:mod:`.watchdog`, ``MXTPU_WATCHDOG_SECS``):
  a daemon-thread progress monitor fed by the hot loops' dispatch /
  sync / kvstore / checkpoint sites; a stall dumps all-thread stacks
  as a ``hang`` JSONL incident, flips ``/healthz`` to a 503 ``hung``
  digest, and (``MXTPU_WATCHDOG_ACTION=abort``) exits with the
  distinct code 85 so the supervisor relaunches from last-good.

Everything is OFF by default. ``MXTPU_TELEMETRY=1`` turns it on;
``MXTPU_TELEMETRY_PATH`` points the JSONL log (default
``telemetry.jsonl``). While off, every entry point degrades to a
shared no-op object — zero I/O, no registry writes, one cached-bool
check per call site (asserted by tests/unittest/test_telemetry.py).

Instrumented sites (the names to grep for in the log):
``fit.bind`` / ``fit.init_params`` / ``fit.init_optimizer`` (set-up, at
the head of ``Module.fit``), ``fit.batch`` / ``fit.dispatch`` /
``fit.metric`` / ``fit.callback`` (reference per-batch loop),
``fused_fit.draw|next|stack|upload|put|dispatch|fetch`` each with the
attribute ``win`` (the window's sequence number: the spans of one
window share it; ``.next`` is one ``next(iterator)`` inside ``.draw``;
``.stack`` and ``.upload``, with ``bytes``, run on the side thread
``mxtpu-window-put`` when the prefetch pool is on; ``.stack`` also says
how many of them were ``reused``, written into host memory written
before) + ``fused_fit.build`` + counters ``fused_fit.windows``,
``fused_fit.stacks_reused`` and ``fused_fit.stacks_new`` (windows whose
large host stacks all reused memory, or not) + gauge
``fused_fit.steps_per_call`` (compiled window loop), ``eval.dispatch|metric|fetch`` + counter
``eval.batches`` + gauge ``eval_samples_per_sec`` (per-batch
score/predict loops), ``fused_eval.draw|next|stack|upload|put|dispatch|
fetch|build`` (the same set, from the shared window pipeline) + counter
``fused_eval.windows`` + gauge ``fused_eval.steps_per_call`` (compiled
eval window loop), ``executor.forward|backward`` + gauges
``executor.mirror_kept`` / ``executor.mirror_kept_bytes`` (set while a
training program is traced: what its mirrored stages keep for the backward
pass by the five rules of ``ops/registry.py``'s ``dear``, and their
bytes), ``exec_group.forward|backward``, ``module.update``, histogram
``io.prefetch_wait`` + counter ``io.batches``, ``kvstore.push|pull``
spans + ``kvstore.push_bytes`` / ``kvstore.pull_bytes`` counters,
gauge ``speedometer.samples_per_sec``, the ``xla.*`` compile/memory
metrics, and — with the persistent compile cache on — ``xla.cache_hits`` /
``xla.cache_saved_secs`` for compiles served from the persistent
cache. The serving plane (mxnet_tpu/serving) reports through the same
registry: ``serve.request_latency`` histogram + ``serve.requests`` /
``serve.errors`` / ``serve.dispatches`` counters, queue/batch/pad
gauges, and ``serve.decode_steps`` for the autoregressive step cache
(docs/serving.md).
"""
import atexit
import logging
import os
import threading
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .registry import (Registry, NULL_COUNTER, NULL_GAUGE, NULL_HISTOGRAM)
from . import export as _export
from . import xla  # noqa: F401  (public submodule: telemetry.xla.*)
from . import programs  # noqa: F401  (public submodule: telemetry.programs.*)
from . import health  # noqa: F401  (public submodule: telemetry.health.*)
from . import cluster  # noqa: F401  (public submodule: telemetry.cluster.*)
from . import serve  # noqa: F401  (public submodule: telemetry.serve.*)
from . import roofline  # noqa: F401  (public submodule: telemetry.roofline.*)
from . import watchdog  # noqa: F401  (public submodule: telemetry.watchdog.*)
from . import trace  # noqa: F401  (public submodule: telemetry.trace.*)
from . import slo  # noqa: F401  (public submodule: telemetry.slo.*)
from . import flight  # noqa: F401  (public submodule: telemetry.flight.*)
from . import dynamics  # noqa: F401  (public submodule: telemetry.dynamics.*)
from . import ledger  # noqa: F401  (public submodule: telemetry.ledger.*)
from . import goodput  # noqa: F401  (public submodule: telemetry.goodput.*)
from . import memory  # noqa: F401  (public submodule: telemetry.memory.*)
from . import timeline  # noqa: F401  (public submodule: telemetry.timeline.*)

__all__ = ['enabled', 'counter', 'gauge', 'histogram', 'span', 'event',
           'snapshot', 'summary', 'write_summary', 'shutdown', 'xla',
           'programs', 'health', 'cluster', 'serve', 'roofline',
           'watchdog', 'trace', 'slo', 'flight', 'dynamics', 'ledger',
           'goodput', 'memory', 'timeline', 'get_registry']


class _State:
    __slots__ = ('decided', 'active', 'registry', 'sink', 't_start',
                 'retraces', 'lock', 'summary_written')

    def __init__(self):
        self.decided = False
        self.active = False
        self.registry = Registry()
        self.sink = None
        self.t_start = None
        self.retraces = {}
        self.lock = threading.Lock()
        self.summary_written = False


_state = _State()
_decide_lock = threading.Lock()
_atexit_registered = False


def _decide():
    global _atexit_registered
    with _decide_lock:
        if _state.decided:
            return _state.active
        from ..config import flags
        try:
            on = bool(flags.get('MXTPU_TELEMETRY'))
        except Exception:  # noqa: BLE001 — stripped builds without the flag
            on = False
        _state.active = on
        _state.decided = True
        if on:
            _state.t_start = time.time()
            from ..config import flags as _flags
            try:
                path = _flags.get('MXTPU_TELEMETRY_PATH')
            except Exception:  # noqa: BLE001
                path = ''
            path = os.path.expanduser(path or 'telemetry.jsonl')
            try:
                _flags.reload('MXTPU_TELEMETRY_MAX_MB')
                max_mb = float(_flags.get('MXTPU_TELEMETRY_MAX_MB'))
            except Exception:  # noqa: BLE001
                max_mb = 0.0
            try:
                _state.sink = _export.JsonlSink(
                    path,
                    max_bytes=int(max_mb * 2**20) if max_mb else None)
                # every record carries this process's host index so
                # multi-host logs merge on it (telemetry/cluster.py)
                _state.sink.host = cluster.host_index()
                _state.sink.emit({'type': 'start', 'pid': os.getpid(),
                                  'path': path})
            except OSError as e:
                logging.warning('telemetry: cannot open %s (%s) — metrics '
                                'stay in-process, no JSONL log', path, e)
                _state.sink = None
            xla.install()
            # live endpoint (telemetry/serve.py): only with
            # MXTPU_TELEMETRY_PORT set — port unset = no thread/socket
            serve.maybe_start()
            if not _atexit_registered:
                _atexit_registered = True
                atexit.register(shutdown)
    return _state.active


def enabled():
    """Whether telemetry is on (decided once from MXTPU_TELEMETRY; the
    first True decision opens the sink and installs the XLA listener).
    Hot call sites rely on this being one attribute check after the
    first call."""
    if _state.decided:
        return _state.active
    return _decide()


def get_registry():
    return _state.registry


def counter(name):
    """Live counter when enabled, shared no-op otherwise."""
    return _state.registry.counter(name) if enabled() else NULL_COUNTER


def gauge(name):
    return _state.registry.gauge(name) if enabled() else NULL_GAUGE


def histogram(name):
    return _state.registry.histogram(name) if enabled() else NULL_HISTOGRAM


# -- span tracer -------------------------------------------------------------

_TLS = threading.local()


def _stack():
    st = getattr(_TLS, 'stack', None)
    if st is None:
        st = _TLS.stack = []
    return st


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """Times a host region: histogram (ms) + JSONL record, an event of
    the ``/host:CPU`` plane of any ``jax.profiler`` capture that is
    running (a ``TraceAnnotation``: on the device's clock, on the line
    of the thread the span ran on, the attributes among its statistics;
    a no-op of the profiler's own outside a capture), and a chrome-trace
    event whenever profiler.py's tracer is running. Nesting is tracked
    per-thread; the JSONL record carries the full path
    ('fit.batch/fit.dispatch') so traces reconstruct the tree, the
    thread's name (``tid``) and the attributes. A span on a side thread
    says what caused it through an attribute (the window pipeline's
    ``win``), not through ``path``."""

    __slots__ = ('name', 'cat', 'attrs', 't0', 'path', '_ann')

    def __init__(self, name, category, attrs):
        self.name = name
        self.cat = category
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.path = (stack[-1].path + '/' + self.name) if stack else self.name
        stack.append(self)
        self._ann = _TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        t1 = time.time()
        self._ann.__exit__(*a)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:           # unwound out of order (exception)
            stack.remove(self)
        dur_ms = (t1 - self.t0) * 1e3
        st = _state
        if st.active:
            st.registry.histogram(self.name).observe(dur_ms)
            # step-phase ledger (MXTPU_TIMELINE): leaf phase spans
            # bucket into per-phase accumulators — one cached bool off
            if timeline.enabled():
                timeline.note_span(self.name, dur_ms)
            if st.sink is not None:
                rec = {'type': 'span', 'name': self.name,
                       'path': self.path, 't': self.t0,
                       'dur_ms': round(dur_ms, 4),
                       'tid': threading.current_thread().name}
                for k, v in self.attrs.items():
                    rec.setdefault(k, v)
                st.sink.emit(rec)
        from .. import profiler as _profiler
        if _profiler.is_running():
            _profiler.record_event(self.name, int(self.t0 * 1e6),
                                   int(t1 * 1e6), self.cat)


def span(name, category='telemetry', **attrs):
    """Context manager timing a host-side region.

    Enabled telemetry: records a histogram observation (ms) under
    ``name``, appends a JSONL span record (with the thread's name and
    ``attrs``) and, during a ``jax.profiler`` capture, is an event of
    the capture's ``/host:CPU`` plane beside the device's operations.
    Running profiler.py tracer: emits a chrome-trace event into its
    timeline (this works even with telemetry off, replacing
    profiler.maybe_span at call sites). Neither: returns the shared
    no-op."""
    if enabled():
        return _Span(name, category, attrs)
    from .. import profiler as _profiler
    if _profiler.is_running():
        return _Span(name, category, attrs)   # exit skips st
    return _NULL_SPAN


def current_span_path():
    """Dotted path of the innermost open span on this thread (tests)."""
    stack = getattr(_TLS, 'stack', None)
    return stack[-1].path if stack else None


def event(name, **fields):
    """Append an ad-hoc JSONL record (type='event')."""
    if enabled() and _state.sink is not None:
        rec = {'type': 'event', 'name': name}
        rec.update(fields)
        _state.sink.emit(rec)


# -- summary / shutdown ------------------------------------------------------

def snapshot():
    return _state.registry.snapshot()


def summary():
    """The human-readable end-of-run table, as a string. Renders the
    same Run health block write_summary() does — including the
    input-bound share — but read-only: no gauges are written, no
    classifier record is emitted."""
    elapsed = (time.time() - _state.t_start) if _state.t_start else None
    return _export.summary_table(_state.registry.snapshot(), elapsed,
                                 programs=programs.snapshot_programs()
                                 or None,
                                 health=health.snapshot_health(
                                     input_bound=health.input_bound_pct()),
                                 cluster=cluster.snapshot_cluster(),
                                 roofline=roofline.snapshot_roofline(),
                                 ledger=ledger.snapshot_ledger(),
                                 goodput=goodput.current(),
                                 memory=memory.snapshot_memory(),
                                 timeline=timeline.snapshot_timeline())


def write_summary(log=True):
    """Sample the XLA gauges one last time, append the JSONL summary
    record, and (by default) log the table. Returns the table string,
    or None when telemetry is off."""
    if not enabled():
        return None
    xla.sample_memory()
    # run-health roll-up: publishes the derived fit.input_bound_pct
    # gauge and (with MXTPU_HEALTH=1) returns the "Run health" block's
    # input + the summary record's 'health' key
    hsnap = health.summarize()
    # roofline attribution (MXTPU_ROOFLINE): publishes roofline.*
    # gauges + the roofline JSONL record; must run before the snapshot
    # below so the gauges land in the summary record too
    rsnap = roofline.summarize()
    # memory attribution + forecast (MXTPU_MEMORY): publishes mem.*
    # gauges + the full memory JSONL record, same contract as roofline
    msnap = memory.summarize()
    csnap = cluster.snapshot_cluster()
    lsnap = ledger.snapshot_ledger()
    elapsed = time.time() - _state.t_start
    # wall-clock attribution: publishes goodput.* gauges + the goodput
    # JSONL record; after roofline (the comm bucket reads its published
    # provenance-labeled share) and before the snapshot below so the
    # gauges land in the summary record too
    gsnap = goodput.summarize(elapsed)
    # pod step timeline (MXTPU_TIMELINE): the last sync round's
    # critical-path attribution, or a local one on a run that never
    # synced — publishes timeline.* gauges + the timeline JSONL record
    # before the snapshot below so the gauges land in the summary too
    tsnap = timeline.summarize()
    snap = _state.registry.snapshot()
    progs = programs.snapshot_programs()
    if _state.sink is not None:
        rec = {'type': 'summary', 'elapsed_s': round(elapsed, 3),
               'snapshot': snap}
        if progs:
            rec['programs'] = progs
        if hsnap:
            rec['health'] = hsnap
        if csnap:
            rec['cluster'] = csnap
        if rsnap:
            rec['roofline'] = rsnap
        if lsnap:
            rec['ledger'] = lsnap
        if gsnap:
            rec['goodput'] = gsnap
        if msnap:
            rec['memory'] = msnap
        if tsnap:
            rec['timeline'] = tsnap
        _state.sink.emit(rec)
        _state.sink.flush()
    table = _export.summary_table(snap, elapsed, programs=progs or None,
                                  health=hsnap, cluster=csnap,
                                  roofline=rsnap, ledger=lsnap,
                                  goodput=gsnap, memory=msnap,
                                  timeline=tsnap)
    if log:
        logging.info('%s', table)
    _state.summary_written = True
    return table


def shutdown():
    """atexit hook: final summary + sink close. Idempotent — and when
    the program already called write_summary() itself, that record IS
    the end-of-run summary: no duplicate is appended here."""
    st = _state
    if not st.active:
        return
    if not st.summary_written:
        try:
            write_summary()
        except Exception:  # noqa: BLE001 — an atexit hook must not raise
            pass
    if st.sink is not None:
        try:
            st.sink.close()
        except Exception:  # noqa: BLE001
            pass
        st.sink = None
    serve.stop()
    st.active = False


def _reset_for_tests():
    """Close the current epoch of telemetry state and re-read the flags
    on next use (tests toggle MXTPU_TELEMETRY via monkeypatch +
    config.flags.reload)."""
    global _state
    if _state.sink is not None:
        try:
            _state.sink.close()
        except Exception:  # noqa: BLE001
            pass
    serve.stop()
    _state = _State()
    programs._reset_for_tests()
    health._reset_for_tests()
    cluster._reset_for_tests()
    roofline._reset_for_tests()
    watchdog._reset_for_tests()
    slo._reset_for_tests()
    flight._reset_for_tests()
    dynamics._reset_for_tests()
    ledger._reset_for_tests()
    goodput._reset_for_tests()
    memory._reset_for_tests()
    timeline._reset_for_tests()
    try:
        from ..parallel import compression
        compression._reset_for_tests()
    except Exception:  # noqa: BLE001 — parallel may not be importable
        pass
