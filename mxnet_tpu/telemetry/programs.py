"""Per-program cost attribution: the compiled-program registrar.

Everything XLA runs for this framework is built at a handful of compile
sites (the executor's fwd / fwd+bwd programs, the fused fit/eval window
programs, the serving bucket ladder). Spans time those dispatches but
the programs themselves would stay anonymous blobs and memory gauges
whole-device totals. This module makes every compiled program
self-describing, following the compiler-stack practice of making
per-program cost a first-class primitive (TVM, arXiv:1802.04799; the
compiled-program boundary as the natural instrumentation unit,
Julia->TPU arXiv:1810.09868):

- :func:`analyze_compiled` — pure: XLA's own ``cost_analysis()`` /
  ``memory_analysis()`` of a compiled executable as a plain dict
  (FLOPs, bytes accessed, temp/argument/output/generated-code bytes).
  Works with telemetry off;
- :func:`note_program` — publish one program's analysis: ``program.*``
  gauges in the registry, a ``program`` JSONL record, a row in the
  end-of-run per-program summary table, and (for programs marked as
  the train step) :func:`telemetry.xla.note_step_flops`, the
  ``xla.step_flops`` gauge;
- :func:`register` — the compile-site interceptor. Wraps a
  ``jax.jit``-ed callable so its lazy compile becomes an explicit
  ``lower().compile()`` whose executable this module can analyze; the
  wrapper then dispatches through the AOT executable (ONE compile
  total, same numerics). With telemetry off it returns the jitted
  callable unchanged — the zero-overhead no-op contract;
- :func:`maybe_oom_report` — on a ``RESOURCE_EXHAUSTED`` error, dump
  the per-program memory breakdown alongside ``memory_stats()`` so an
  OOM stops being a one-line crash: the report says which programs
  were resident and what XLA planned to allocate for each.
"""
import logging
import threading
import time

__all__ = ['analyze_compiled', 'note_program', 'register',
           'snapshot_programs', 'maybe_oom_report']

_lock = threading.Lock()
_programs = {}          # name -> record dict (see note_program)
_step_flops_seen = {}   # name -> max flops across its recompiles
_oom_reported = False

_ANALYSIS_FIELDS = ('flops', 'bytes_accessed', 'temp_bytes',
                    'argument_bytes', 'output_bytes',
                    'generated_code_bytes', 'alias_bytes', 'live_bytes')


def _state():
    from . import enabled
    enabled()   # decide from the flag if nothing else has yet
    from . import _state as st
    return st


def _empty_analysis():
    return {'flops': 0.0, 'bytes_accessed': 0.0, 'temp_bytes': 0,
            'argument_bytes': 0, 'output_bytes': 0,
            'generated_code_bytes': 0, 'alias_bytes': 0, 'live_bytes': 0}


def analyze_compiled(compiled):
    """XLA's own cost + memory analysis of a compiled executable, as a
    plain dict (zeros where a backend doesn't report). Pure — no
    registry writes, no I/O — so callers that need the numbers with
    telemetry off can use it directly."""
    rec = _empty_analysis()
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec['flops'] = float(cost.get('flops', 0.0) or 0.0)
        rec['bytes_accessed'] = float(cost.get('bytes accessed', 0.0) or 0.0)
    except Exception as e:  # noqa: BLE001 — observability must not kill
        logging.debug('telemetry: cost_analysis unavailable: %s', e)
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, (list, tuple)):
            ma = ma[0]
        for field, attr in (('temp_bytes', 'temp_size_in_bytes'),
                            ('argument_bytes', 'argument_size_in_bytes'),
                            ('output_bytes', 'output_size_in_bytes'),
                            ('generated_code_bytes',
                             'generated_code_size_in_bytes'),
                            ('alias_bytes', 'alias_size_in_bytes')):
            rec[field] = int(getattr(ma, attr, 0) or 0)
        # steady-state footprint of one dispatch: args + temps + outputs
        # minus the donated-input bytes the outputs alias in place. The
        # donation ledger: aliasing a carry moves its output bytes into
        # alias_bytes, so live_bytes is what a window actually makes
        # XLA hold beyond the buffers the caller already owns.
        rec['live_bytes'] = max(0, rec['argument_bytes']
                                + rec['temp_bytes'] + rec['output_bytes']
                                - rec['alias_bytes'])
    except Exception as e:  # noqa: BLE001
        logging.debug('telemetry: memory_analysis unavailable: %s', e)
    return rec


def note_program(name, compiled=None, analysis=None, step_flops=False,
                 compile_s=None):
    """Record one compiled program under ``name``. Returns the analysis
    dict (computed from ``compiled`` when not given) whether or not
    telemetry is on; publication — ``program.*`` gauges, the JSONL
    ``program`` record, the summary-table row, the automatic
    :func:`~.xla.note_step_flops` feed for ``step_flops=True``
    programs — only happens while telemetry is active."""
    if analysis is None:
        analysis = analyze_compiled(compiled) if compiled is not None \
            else _empty_analysis()
    st = _state()
    if not st.active:
        return analysis
    if compiled is not None:
        # roofline attribution (MXTPU_ROOFLINE): parse the program's
        # HLO into per-layer costs while the executable is in hand —
        # one cached-bool check when the flag is off
        from . import roofline
        if roofline.enabled():
            roofline.note_compiled(name, compiled, analysis=analysis,
                                   step_flops=step_flops)
        # memory attribution (MXTPU_MEMORY): same contract — parse the
        # HLO into per-layer buffer bytes while the executable is in
        # hand, one cached-bool check when the flag is off
        from . import memory
        if memory.enabled():
            memory.note_compiled(name, compiled, analysis=analysis)
    with _lock:
        rec = _programs.get(name)
        if rec is None:
            rec = _programs[name] = {'name': name, 'compiles': 0,
                                     'dispatches': 0}
            rec.update(_empty_analysis())
        for f in _ANALYSIS_FIELDS:
            # a name can cover several compiled variants (shape
            # variants, train/eval forms): keep the LARGEST value per
            # field — the conservative bound the OOM report and MFU
            # want, instead of whichever variant compiled last.
            # .get(): hand-crafted analysis dicts (tests, older
            # callers) may predate the alias/live fields
            rec[f] = max(rec[f], analysis.get(f, 0))
        merged = {f: rec[f] for f in _ANALYSIS_FIELDS}
        rec['compiles'] += 1
    reg = st.registry
    reg.counter('program.compiles').inc()
    # gauges mirror the MERGED record so the two views never disagree
    reg.gauge('program.%s.flops' % name).set(merged['flops'])
    reg.gauge('program.%s.bytes_accessed' % name).set(
        merged['bytes_accessed'])
    reg.gauge('program.%s.temp_bytes' % name).set(merged['temp_bytes'])
    reg.gauge('program.%s.alias_bytes' % name).set(merged['alias_bytes'])
    reg.gauge('program.%s.live_bytes' % name).set(merged['live_bytes'])
    if step_flops and analysis['flops']:
        # the train-step program: its FLOPs are xla.step_flops. XLA
        # counts a scan (while-loop) body ONCE regardless of trip
        # count, so a W-step fused window reports per-step FLOPs
        # already — exactly what note_step_flops wants. Feed the MAX
        # across ALL step-marked programs so far: neither a tail-batch
        # shape variant nor the tail's executor.fwd_bwd (compiled after
        # the fused window, without the update math) may shrink the
        # per-step FLOPs the run reports.
        with _lock:
            _step_flops_seen[name] = max(_step_flops_seen.get(name, 0.0),
                                         analysis['flops'])
            fed = max(_step_flops_seen.values())
        from . import xla
        xla.note_step_flops(fed)
    if st.sink is not None:
        out = {'type': 'program', 'name': name}
        out.update({f: analysis.get(f, 0) for f in _ANALYSIS_FIELDS})
        if compile_s is not None:
            out['compile_s'] = round(float(compile_s), 3)
        st.sink.emit(out)
    return analysis


def note_dispatch(name):
    """Count one dispatch of a registered program (wrapper-internal)."""
    with _lock:
        rec = _programs.get(name)
        if rec is not None:
            rec['dispatches'] += 1


def snapshot_programs():
    """Point-in-time {name: record} copy — the summary table's input."""
    with _lock:
        return {n: dict(r) for n, r in _programs.items()}


# -- the compile-site interceptor -------------------------------------------

class _RegisteredProgram:
    """AOT wrapper around a jitted callable: the first call per
    argument signature runs ``lower().compile()`` explicitly (one
    compile total — the lazy path would have compiled here anyway),
    hands the executable to :func:`note_program`, then dispatches
    through it. What the ahead-of-time path itself cannot take (the
    TypeError/ValueError of an argument layout, an unhashable leaf)
    falls back to the wrapped lazy jit for that signature —
    attribution is best-effort, execution is not. An error of the
    backend (a compile or a run out of memory) is raised as it is,
    once: the lazy jit would only repeat it."""

    __slots__ = ('name', 'jitted', 'static_argnums', 'step_flops',
                 '_compiled')

    def __init__(self, name, jitted, static_argnums, step_flops):
        self.name = name
        self.jitted = jitted
        self.static_argnums = tuple(static_argnums)
        self.step_flops = step_flops
        self._compiled = {}

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)

    def _signature(self, args):
        import jax
        sig = []
        for i, arg in enumerate(args):
            flat, treedef = jax.tree_util.tree_flatten(arg)
            static = i in self.static_argnums
            leaves = []
            for leaf in flat:
                if hasattr(leaf, 'shape') and hasattr(leaf, 'dtype'):
                    leaves.append((tuple(leaf.shape), str(leaf.dtype),
                                   getattr(leaf, 'sharding', None)))
                elif static:
                    # static args select programs by VALUE, exactly as
                    # the jax.jit declaration does
                    leaves.append(('static', leaf))
                else:
                    # a traced python scalar: jit specializes on its
                    # TYPE (weak dtype), never its value — keying by
                    # value would compile per distinct value where the
                    # lazy jit compiles once
                    leaves.append(('scalar', type(leaf)))
            sig.append((treedef, tuple(leaves)))
        return tuple(sig)

    def _compile(self, args, key):
        t0 = time.time()
        try:
            compiled = self.jitted.lower(*args).compile()
        except (TypeError, ValueError) as e:
            # what the ahead-of-time path itself cannot take (an
            # argument layout): the lazy jit handles it. An error of the
            # backend (RESOURCE_EXHAUSTED among them) is the program's
            # own and reaches the caller as it is, after this one
            # attempt: compiling the same program again through the
            # lazy jit would only fail a second time.
            logging.debug('telemetry: AOT compile of %s failed (%s); '
                          'using lazy jit for this signature',
                          self.name, e)
            self._compiled[key] = False
            return False
        note_program(self.name, compiled=compiled,
                     step_flops=self.step_flops,
                     compile_s=time.time() - t0)
        self._compiled[key] = compiled
        return compiled

    def __call__(self, *args):
        try:
            key = self._signature(args)
            entry = self._compiled.get(key)
        except Exception:  # noqa: BLE001 — unhashable leaf etc.
            return self.jitted(*args)
        if entry is None:
            entry = self._compile(args, key)
        if entry is False:
            return self.jitted(*args)
        if self.static_argnums:
            dyn = [a for i, a in enumerate(args)
                   if i not in self.static_argnums]
        else:
            dyn = args
        try:
            out = entry(*dyn)
        except (TypeError, ValueError) as e:
            # an argument layout/device surprise the signature key
            # missed: the lazy jit handles it (argument checks raise
            # before any buffer is donated, so args are still alive).
            # Runtime errors (a genuine OOM mid-execution) re-raise —
            # retrying after donation would only mask the real failure.
            logging.debug('telemetry: AOT dispatch of %s failed (%s); '
                          'retrying via lazy jit', self.name, e)
            return self.jitted(*args)
        note_dispatch(self.name)
        return out


def register(name, jitted, static_argnums=(), step_flops=False):
    """Intercept a compile site. With telemetry on, returns a wrapper
    that compiles via ``lower().compile()``, analyzes the executable
    (:func:`note_program`), and dispatches through it; with telemetry
    off, returns ``jitted`` unchanged (zero overhead — the hot path
    sees the very same object it constructed).

    ``static_argnums`` must mirror the ``jax.jit`` declaration (AOT
    executables take only the dynamic arguments). ``step_flops=True``
    marks the program whose FLOPs define a training step — it feeds
    the ``xla.step_flops`` gauge."""
    from . import enabled
    if not enabled():
        return jitted
    return _RegisteredProgram(name, jitted, static_argnums, step_flops)


def scope_name(name):
    """Sanitize a symbol/layer name for ``jax.named_scope`` / HLO
    metadata (scopes join with '/', so strip everything exotic)."""
    import re
    return re.sub(r'[^A-Za-z0-9_.\-]', '_', str(name)) or '_'


# -- OOM diagnostics ---------------------------------------------------------

def _looks_like_oom(msg):
    low = msg.lower()
    return 'resource_exhausted' in low or 'resource exhausted' in low


def maybe_oom_report(exc):
    """If ``exc`` is an XLA RESOURCE_EXHAUSTED error (and telemetry is
    on), log the per-program memory breakdown next to the device's
    ``memory_stats()`` and append an ``oom`` JSONL record — once per
    process, so a crash-loop cannot spam the log. Returns True when a
    report was (or already had been) written for an OOM error."""
    st = _state()
    if not st.active:
        return False
    msg = str(exc)
    if not _looks_like_oom(msg):
        return False
    global _oom_reported
    with _lock:
        if _oom_reported:
            return True
        _oom_reported = True
        progs = {n: dict(r) for n, r in _programs.items()}
    from . import xla
    stats = xla.sample_memory()
    lines = ['device OOM (RESOURCE_EXHAUSTED) — per-program memory '
             'breakdown (XLA memory_analysis, bytes XLA planned to '
             'allocate per program):']
    for name in sorted(progs):
        r = progs[name]
        lines.append(
            '  %-44s temp=%8.1f MiB  args=%8.1f MiB  out=%8.1f MiB  '
            'dispatches=%d' % (name, r['temp_bytes'] / 2**20,
                               r['argument_bytes'] / 2**20,
                               r['output_bytes'] / 2**20,
                               r['dispatches']))
    if not progs:
        lines.append('  (no programs registered — the failing compile '
                     'itself may have exhausted memory)')
    if stats:
        keep = ('bytes_in_use', 'peak_bytes_in_use', 'bytes_limit',
                'largest_free_block_bytes')
        lines.append('  device memory_stats: %s' %
                     ', '.join('%s=%s' % (k, stats[k])
                               for k in keep if k in stats))
    else:
        lines.append('  device memory_stats() unavailable on this backend')
    logging.error('%s', '\n'.join(lines))
    if st.sink is not None:
        clean_stats = {k: v for k, v in (stats or {}).items()
                       if isinstance(v, (int, float, str, bool))}
        rec = {'type': 'oom', 'error': msg[:500],
               'programs': progs, 'memory_stats': clean_stats}
        # cross-link what the MXTPU_MEMORY forecaster last said before
        # the allocator died — the post-mortem's "was this predicted?"
        try:
            from . import memory
            fc = memory.last_forecast()
            if fc:
                rec['last_forecast'] = {k: v for k, v in fc.items()
                                        if k != 'type'}
        except Exception:  # noqa: BLE001 — forensics must not add a crash
            pass
        st.sink.emit(rec)
        st.sink.flush()
    # flight recorder: what the process was doing in the records
    # before the allocation failed
    try:
        from . import flight
        flight.dump('oom')
    except Exception:  # noqa: BLE001 — forensics must not add a crash
        pass
    return True


def _reset_for_tests():
    global _oom_reported
    with _lock:
        _programs.clear()
        _step_flops_seen.clear()
        _oom_reported = False
