"""HBM attribution & forecast: per-layer peak memory, OOM prediction.

The registrar (:mod:`.programs`) knows each compiled program's total
argument/temp/output/alias bytes and the XLA gauges know the device's
live/peak byte counters — but neither says *which layer owns the peak*,
and the first warning of an out-of-memory run is RESOURCE_EXHAUSTED
itself. This module is the memory twin of :mod:`.roofline`: attribute
the peak to named layers, watch the live-bytes timeline, and alarm
BEFORE the allocator dies.

Data flow, all host-side (the compiled programs are untouched — the
lowered HLO is byte-identical with the flag on or off):

1. **per-layer peak attribution** — when a compile site registers a
   program, :func:`note_compiled` parses its HLO text with the same
   machinery the roofline uses (instruction shapes give buffer bytes,
   ``metadata={op_name="..."}`` carries the ``jax.named_scope`` layer
   names). ENTRY parameters are the argument buffers, the ENTRY ROOT
   is the output, everything else that materializes is temp; the three
   parsed buckets are calibrated against ``compiled.memory_analysis()``
   so the per-layer split always sums to what XLA reports for the
   whole program, and the donated ``alias_bytes`` are shared out in
   proportion to each layer's argument bytes. Programs merge
   largest-variant-per-name — the registrar's own rule.
2. **live-bytes timeline** — the step loops feed :func:`note_step`
   (one cached-bool check while off); at the MXTPU_SCALARS_EVERY
   cadence one host-side ``memory_stats()`` allocator query (no device
   sync) lands a ``(step, bytes_in_use, bytes_limit)`` sample in a
   bounded ring, publishes the ``mem.*`` gauges and a ``memory`` JSONL
   record, and feeds the ``mem_growth`` spike detector (the
   :mod:`.health` registry) so a leak — a serving session ring that
   never evicts, host-side accumulation across windows — raises a
   NAMED anomaly.
3. **forecast** — a least-squares slope over the ring turns headroom
   into ``mem.steps_to_oom``; a forecast at or below
   MXTPU_MEMORY_OOM_STEPS flips /healthz to ``mem_pressure`` and dumps
   the flight recorder (flight-mem-pressure.jsonl) while the process
   can still write — the seconds before the OOM, on disk before the
   allocator dies. The OOM report cross-links the last forecast.

Surfacing: a "Memory" block in the end-of-run summary table, ``memory``
JSONL records, ``mem.*`` gauges on /metrics and /summary, a headroom
slot in the cluster sync vector (process 0 names the most
memory-pressured host), a memory line in tools/telemetry_watch.py and
``tools/memory_report.py`` offline (byte-identical block + a what-if
sizing table).

Gating: ``MXTPU_MEMORY=1`` *and* ``MXTPU_TELEMETRY=1``. Off = the
zero-overhead no-op contract of the rest of the plane: no HLO text is
ever rendered or parsed, no ring is filled, no records are written,
one cached-bool check at the registrar hook and the step loops.
"""
import collections
import logging
import threading

__all__ = ['enabled', 'note_compiled', 'note_hlo', 'hlo_layer_buffers',
           'note_step', 'record_sample', 'analyze', 'summarize',
           'republish', 'snapshot_memory', 'local_headroom',
           'pressure_info', 'last_forecast', 'TOP_N', 'RING_CAP']

TOP_N = 8        # layer rows rendered in the summary block
RING_CAP = 256   # live-bytes samples retained (bounded by construction)

_lock = threading.Lock()
_decided = None
_programs = {}       # name -> parsed per-layer buffer store (see note_hlo)
_last = None         # last published analysis dict (snapshot_memory)
_ring = collections.deque(maxlen=RING_CAP)  # (step, bytes_in_use, limit)
_steps = 0           # cumulative trained steps fed through note_step
_next_sample = 0     # next _steps value that takes a ring sample
_pressure = None     # active mem_pressure digest (healthz), or None
_last_forecast = None  # last emitted memory record (OOM cross-link)
_flight_dumped = False
_cadence_cached = None
_threshold_cached = None


def _tele():
    from . import enabled as tele_enabled
    tele_enabled()
    from . import _state as st
    return st


def enabled():
    """MXTPU_MEMORY=1 and telemetry on (decided once; off = one
    cached-bool check at the registrar hook and the step loops)."""
    global _decided
    if _decided is None:
        from . import enabled as tele_enabled
        on = tele_enabled()
        if on:
            from ..config import flags
            try:
                on = bool(flags.get('MXTPU_MEMORY'))
            except Exception:  # noqa: BLE001 — stripped builds
                on = False
        _decided = on
    return _decided


def _cadence():
    global _cadence_cached
    if _cadence_cached is None:
        from ..config import flags
        try:
            n = int(flags.get('MXTPU_SCALARS_EVERY'))
        except Exception:  # noqa: BLE001 — stripped builds
            n = 25
        _cadence_cached = n if n > 0 else 25
    return _cadence_cached


def _oom_threshold():
    global _threshold_cached
    if _threshold_cached is None:
        from ..config import flags
        try:
            _threshold_cached = int(flags.get('MXTPU_MEMORY_OOM_STEPS'))
        except Exception:  # noqa: BLE001 — stripped builds
            _threshold_cached = 200
    return _threshold_cached


# ---------------------------------------------------------------------------
# HLO text -> per-layer buffer-byte parse
# ---------------------------------------------------------------------------

# ops whose output is a view/bookkeeping handle, not a fresh buffer —
# counting their shapes would double every real allocation. Derived
# from the roofline's free set, minus `parameter` (ENTRY parameters ARE
# the argument buffers here) and `custom-call` (its result
# materializes), plus `iota` (negligible, usually folded)
def _no_buffer_ops():
    from . import roofline
    return (roofline._FREE_OPS | frozenset(('iota',))) \
        - frozenset(('parameter', 'custom-call'))


def hlo_layer_buffers(hlo_text):
    """Parse an HLO module's text into the per-layer buffer store::

        {'layers':     {layer: {'args': b, 'temp': b, 'out': b}},
         'args_total': ENTRY-parameter bytes,
         'temp_total': materialized intermediate bytes,
         'out_total':  ENTRY-ROOT bytes}

    Best-effort by construction: unparsed lines contribute nothing,
    buffers without a named scope pool under ``_unattributed``, and the
    three buckets are later CALIBRATED against memory_analysis() so
    parse inflation (a while carry counted at both the instruction and
    its body) cannot move the totals — only the relative shares."""
    from . import roofline as _r
    from .programs import _layer_from_op_name
    no_buffer = _no_buffer_ops()
    layers = {}
    args_total = temp_total = out_total = 0.0
    in_entry = False
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith('ENTRY'):
            in_entry = True
            continue
        if s == '}':
            in_entry = False
            continue
        m = _r._INSTR_RE.match(line)
        if not m:
            continue
        _name, out_sig, opcode = m.groups()
        out_bytes = 0
        for dt, dims in _r._SHAPE_RE.findall(out_sig):
            b, _n = _r._shape_bytes(dt, dims)
            out_bytes += b
        # the ENTRY ROOT is usually a free op (a tuple of loss + grads +
        # carried state) but its shape IS the program's output
        # allocation — never skip it
        is_root = in_entry and s.startswith('ROOT')
        if out_bytes <= 0 or (opcode in no_buffer and not is_root):
            continue
        mo = _r._OP_NAME_RE.search(line)
        layer = (_layer_from_op_name(mo.group(1)) if mo else None) \
            or '_unattributed'
        rec = layers.setdefault(layer, {'args': 0.0, 'temp': 0.0,
                                        'out': 0.0})
        if opcode == 'parameter':
            if in_entry:
                rec['args'] += out_bytes
                args_total += out_bytes
        elif is_root:
            rec['out'] += out_bytes
            out_total += out_bytes
        else:
            rec['temp'] += out_bytes
            temp_total += out_bytes
    layers = {k: v for k, v in layers.items()
              if v['args'] or v['temp'] or v['out']}
    return {'layers': layers, 'args_total': args_total,
            'temp_total': temp_total, 'out_total': out_total}


# ---------------------------------------------------------------------------
# registrar hook (telemetry.programs.note_program calls this)
# ---------------------------------------------------------------------------

def note_hlo(name, hlo_text, analysis=None):
    """Ingest one program's HLO text (tests feed synthetic modules
    here; live compiles arrive via :func:`note_compiled`). ``analysis``
    is the registrar's memory_analysis dict — its ``argument_bytes`` /
    ``temp_bytes`` / ``output_bytes`` / ``alias_bytes`` calibrate the
    parsed per-layer split."""
    if not enabled():
        return
    buf = hlo_layer_buffers(hlo_text)
    buf['analysis'] = dict(analysis or {})
    buf['name'] = name
    buf['parsed_total'] = (buf['args_total'] + buf['temp_total']
                          + buf['out_total'])
    rank = float(buf['analysis'].get('live_bytes') or 0.0) \
        or buf['parsed_total']
    buf['rank'] = rank
    with _lock:
        prev = _programs.get(name)
        if prev is not None and prev['rank'] > rank:
            # keep the largest variant per name — the registrar's own
            # merge rule (a tail-batch recompile must not shrink the
            # peak the run is judged by)
            return
        _programs[name] = buf


def note_compiled(name, compiled, analysis=None):
    """The live hook: render ``compiled.as_text()`` and ingest it.
    Never raises — attribution is best-effort, execution is not."""
    if not enabled():
        return
    try:
        if analysis is None:
            from . import programs as _p
            analysis = _p.analyze_compiled(compiled)
        note_hlo(name, compiled.as_text(), analysis=analysis)
    except Exception as e:  # noqa: BLE001 — observability must not kill
        logging.debug('memory: HLO ingest of %s failed: %s', name, e)


def _pick_program():
    """The program whose peak the plane attributes: the one with the
    largest steady-state footprint (analysis live_bytes, else the
    parsed total)."""
    with _lock:
        progs = list(_programs.values())
    if not progs:
        return None
    return max(progs, key=lambda p: p['rank'])


def _calibrated_layers(prog):
    """Per-layer rows with each parsed bucket rescaled so the bucket
    sums equal XLA's own memory_analysis totals (when present). The
    donated alias bytes are shared in proportion to argument bytes —
    donation aliases inputs onto outputs, so the layers holding the
    arguments hold the refund."""
    ana = prog['analysis']
    targets = {'args': float(ana.get('argument_bytes') or 0.0),
               'temp': float(ana.get('temp_bytes') or 0.0),
               'out': float(ana.get('output_bytes') or 0.0)}
    parsed = {'args': prog['args_total'], 'temp': prog['temp_total'],
              'out': prog['out_total']}
    layers = {k: dict(v) for k, v in prog['layers'].items()}
    for k in targets:
        if targets[k] > 0 and parsed[k] <= 0:
            # the bucket never parsed (a ROOT/shape format the parser
            # doesn't know) — land the whole target unattributed so the
            # bucket sums still match XLA's totals
            u = layers.setdefault('_unattributed',
                                  {'args': 0.0, 'temp': 0.0, 'out': 0.0})
            u[k] += targets[k]
            parsed[k] = targets[k]
    scale = {k: (targets[k] / parsed[k]
                 if parsed[k] > 0 and targets[k] > 0 else 1.0)
             for k in targets}
    alias_total = float(ana.get('alias_bytes') or 0.0)
    args_cal = sum(v['args'] for v in layers.values()) \
        * scale['args']
    rows = []
    for layer, v in layers.items():
        args = v['args'] * scale['args']
        temp = v['temp'] * scale['temp']
        out = v['out'] * scale['out']
        alias = args / args_cal * alias_total if args_cal > 0 else 0.0
        rows.append({'layer': layer, 'args': int(round(args)),
                     'temp': int(round(temp)), 'out': int(round(out)),
                     'alias': int(round(alias)),
                     'total': int(round(args + temp + out))})
    rows.sort(key=lambda r: -r['total'])
    return rows


# ---------------------------------------------------------------------------
# live-bytes timeline + forecaster
# ---------------------------------------------------------------------------

def _fit_slope(ring):
    """Least-squares bytes-per-step over the ring (None below 4
    samples or with no step spread)."""
    if len(ring) < 4:
        return None
    n = float(len(ring))
    mx = sum(r[0] for r in ring) / n
    my = sum(r[1] for r in ring) / n
    sxx = sum((r[0] - mx) ** 2 for r in ring)
    if sxx <= 0:
        return None
    sxy = sum((r[0] - mx) * (r[1] - my) for r in ring)
    return sxy / sxx


def _note_growth(bytes_in_use):
    """Feed the mem_growth spike detector (the health registry's
    rolling-median/MAD family): a constant baseline never alarms, a
    leak's climb past k robust deviations raises the NAMED anomaly.
    Only upward excursions publish — a freed buffer is not a leak."""
    from . import health
    try:
        a = health.detector('mem_growth').observe(bytes_in_use / 2.0**20)
        if a is not None and a['value'] > a['baseline']:
            health.publish_anomaly(a)
    except Exception as e:  # noqa: BLE001 — observability must not kill
        logging.debug('memory: growth detector failed: %s', e)


def note_step(n=1):
    """Step-loop hook (fused window tail feeds W, the per-batch loop
    feeds 1). One cached-bool check while off; at the scalars cadence
    one host-side ``memory_stats()`` allocator query (no device sync)
    lands a ring sample. Backends without memory statistics (CPU) warn
    once through the xla plane and sample nothing."""
    if not enabled():
        return
    global _steps, _next_sample
    with _lock:
        _steps += n
        if _steps < _next_sample:
            return
        _next_sample = _steps + _cadence()
        step = _steps
    from . import xla
    stats = xla.sample_memory()
    if not stats:
        return
    live = stats.get('bytes_in_use')
    if live is None:
        return
    record_sample(step, live, stats.get('bytes_limit'))


def record_sample(step, bytes_in_use, bytes_limit=None):
    """Land one live-bytes sample: ring, ``mem.*`` gauges, the
    ``memory`` JSONL record, the growth detector, and the steps-to-OOM
    forecast verdict. Tests feed synthetic ramps here; live training
    arrives via :func:`note_step`. Returns the record dict."""
    if not enabled():
        return None
    global _pressure, _last_forecast, _flight_dumped
    bytes_in_use = float(bytes_in_use)
    limit = float(bytes_limit or 0.0)
    with _lock:
        _ring.append((int(step), bytes_in_use, limit))
        ring = list(_ring)
    st = _tele()
    reg = st.registry
    reg.gauge('mem.bytes_in_use').set(int(bytes_in_use))
    headroom = None
    if limit > 0:
        reg.gauge('mem.bytes_limit').set(int(limit))
        headroom = 100.0 * (limit - bytes_in_use) / limit
        reg.gauge('mem.headroom_pct').set(round(headroom, 2))
    slope = _fit_slope(ring)
    steps_to_oom = None
    if slope is not None:
        reg.gauge('mem.slope_bytes_per_step').set(round(slope, 1))
        if slope > 0 and limit > 0:
            steps_to_oom = max(0, int((limit - bytes_in_use) / slope))
            reg.gauge('mem.steps_to_oom').set(steps_to_oom)
    _note_growth(bytes_in_use)
    tripped = (steps_to_oom is not None
               and steps_to_oom <= _oom_threshold())
    reg.gauge('mem.pressure').set(1 if tripped else 0)
    rec = {'type': 'memory', 'step': int(step),
           'bytes_in_use': int(bytes_in_use)}
    if limit > 0:
        rec['bytes_limit'] = int(limit)
        rec['headroom_pct'] = round(headroom, 2)
    if slope is not None:
        rec['slope_bytes_per_step'] = round(slope, 1)
    if steps_to_oom is not None:
        rec['steps_to_oom'] = steps_to_oom
    if tripped:
        rec['pressure'] = True
    with _lock:
        _last_forecast = dict(rec)
        _pressure = ({'step': int(step), 'steps_to_oom': steps_to_oom,
                      'headroom_pct': (round(headroom, 2)
                                       if headroom is not None else None)}
                     if tripped else None)
    if st.sink is not None:
        st.sink.emit(rec)
    if tripped and not _flight_dumped:
        # dump while the process can still write — the whole point of
        # forecasting is beating RESOURCE_EXHAUSTED to the disk
        _flight_dumped = True
        logging.warning(
            'memory: forecast predicts OOM in ~%d steps (headroom '
            '%.1f%%, +%.0f bytes/step) — dumping flight recorder',
            steps_to_oom, headroom if headroom is not None else -1.0,
            slope or 0.0)
        from . import flight
        try:
            flight.dump('mem-pressure', {'forecast': dict(rec)})
        except Exception as e:  # noqa: BLE001
            logging.debug('memory: flight dump failed: %s', e)
    return rec


# ---------------------------------------------------------------------------
# analysis + publication
# ---------------------------------------------------------------------------

def analyze():
    """The full memory picture as one dict (None while off or before
    anything is ingested): the attributed step program's per-layer
    rows + bucket totals, every program's peak bytes, and the timeline
    /forecast state. Pure — no gauges, no records."""
    if not enabled():
        return None
    prog = _pick_program()
    with _lock:
        ring = list(_ring)
        peaks = {n: int(p['rank']) for n, p in _programs.items()}
        pressure = dict(_pressure) if _pressure else None
    if prog is None and not ring:
        return None
    d = {}
    if prog is not None:
        ana = prog['analysis']
        d['program'] = prog['name']
        for src, dst in (('argument_bytes', 'args_bytes'),
                         ('temp_bytes', 'temp_bytes'),
                         ('output_bytes', 'output_bytes'),
                         ('alias_bytes', 'alias_bytes'),
                         ('live_bytes', 'live_bytes')):
            v = ana.get(src)
            if v is not None:
                d[dst] = int(v)
        rows = _calibrated_layers(prog)
        d['layers'] = rows
        if rows:
            d['worst_layer'] = rows[0]['layer']
            d['worst_layer_bytes'] = rows[0]['total']
    if peaks:
        d['peaks'] = peaks
    if ring:
        step, bytes_in_use, limit = ring[-1]
        d['step'] = int(step)
        d['bytes_in_use'] = int(bytes_in_use)
        d['samples'] = len(ring)
        if limit > 0:
            d['bytes_limit'] = int(limit)
            d['headroom_pct'] = round(
                100.0 * (limit - bytes_in_use) / limit, 2)
        slope = _fit_slope(ring)
        if slope is not None:
            d['slope_bytes_per_step'] = round(slope, 1)
            if slope > 0 and limit > 0:
                d['steps_to_oom'] = max(
                    0, int((limit - bytes_in_use) / slope))
    d['pressure'] = bool(pressure)
    return d


def _publish_gauges(d, reg):
    """One analysis dict -> the mem.* gauge family (shared by
    :func:`summarize` and the cluster-cadence :func:`republish`)."""
    if d.get('worst_layer') is not None:
        reg.gauge('mem.worst_layer').set(d['worst_layer'])
        reg.gauge('mem.worst_layer_bytes').set(d['worst_layer_bytes'])
    if d.get('live_bytes') is not None:
        reg.gauge('mem.program_live_bytes').set(d['live_bytes'])
    if d.get('headroom_pct') is not None:
        reg.gauge('mem.headroom_pct').set(d['headroom_pct'])
    if d.get('steps_to_oom') is not None:
        reg.gauge('mem.steps_to_oom').set(d['steps_to_oom'])


def summarize():
    """Run :func:`analyze`, publish the ``mem.*`` gauges + the full
    ``memory`` JSONL record, and return the analysis dict (None when
    off/empty). Called from telemetry.write_summary."""
    global _last
    d = analyze()
    if d is None:
        return None
    st = _tele()
    _publish_gauges(d, st.registry)
    if st.sink is not None:
        rec = {'type': 'memory'}
        rec.update(d)
        st.sink.emit(rec)
    with _lock:
        _last = d
    return d


def republish():
    """Cluster-sync-cadence hook (telemetry/cluster.py): refresh the
    ``mem.*`` gauges from a read-only analysis so a mid-run /metrics
    scrape sees live memory state. No JSONL record — a sync round must
    stay cheap. Returns the analysis dict or None."""
    global _last
    if not enabled():
        return None
    d = analyze()
    if d is None:
        return None
    _publish_gauges(d, _tele().registry)
    with _lock:
        _last = d
    return d


def snapshot_memory():
    """The last published analysis dict (the /summary payload's and
    read-only summary()'s input), or None."""
    with _lock:
        return _last


def local_headroom():
    """This host's latest headroom %, NaN while off or before any
    sample carries a byte limit — the cluster sync vector's
    NaN-padding contract (old senders simply ship shorter rows)."""
    if not enabled():
        return float('nan')
    with _lock:
        if not _ring:
            return float('nan')
        _s, b, limit = _ring[-1]
    if limit <= 0:
        return float('nan')
    return 100.0 * (limit - b) / limit


def pressure_info():
    """The active mem_pressure digest for /healthz (step,
    steps_to_oom, headroom_pct), or None while the forecast is clear —
    pressure is recoverable: a sample whose forecast rises back above
    the threshold clears it."""
    if not enabled():
        return None
    with _lock:
        return dict(_pressure) if _pressure else None


def last_forecast():
    """The most recent ``memory`` sample record (the OOM report's
    cross-link: what the forecaster last said before the allocator
    died), or None."""
    if not enabled():
        return None
    with _lock:
        return dict(_last_forecast) if _last_forecast else None


def _reset_for_tests():
    global _decided, _last, _steps, _next_sample, _pressure, \
        _last_forecast, _flight_dumped, _cadence_cached, _threshold_cached
    with _lock:
        _programs.clear()
        _ring.clear()
        _last = None
        _pressure = None
        _last_forecast = None
    _decided = None
    _steps = 0
    _next_sample = 0
    _flight_dumped = False
    _cadence_cached = None
    _threshold_cached = None
