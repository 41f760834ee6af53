"""Roofline attribution: per-layer achieved-vs-peak diagnosis.

The registrar (:mod:`.programs`) knows each compiled program's total
FLOPs and bytes; which layer they belong to it does not say. This module
splits them the way cost-model-driven compiler stacks do (TVM,
arXiv:1802.04799): place every layer on the device's roofline (Williams
et al., the operational-intensity model) and classify what bounds it.
Every time in it is modeled. Measured device time by symbol node is the
benchmark's to read, from a capture's events through the compiled
program's scope map (:func:`.programs.scope_map`).

Data flow, all host-side (the compiled programs are untouched — the
lowered HLO is byte-identical with the flag on or off):

1. **per-layer costs** — when a compile site registers a program,
   :func:`note_compiled` parses its HLO text. Every instruction carries
   ``metadata={op_name="..."}`` with the ``jax.named_scope`` layer name
   PR 3 planted (executor nodes, fused-window bodies); shapes give
   bytes, and dot/convolution contraction dims give FLOPs. The parsed
   totals are calibrated against XLA's own ``cost_analysis()`` /
   ``memory_analysis()`` numbers so the per-layer split always sums to
   what XLA reports for the whole program.
2. **modeled timings** — the measured step time is *distributed*
   across layers in proportion to each layer's roofline-minimum time
   (``source: modeled``).
3. **classification** — per layer: achieved FLOP/s, achieved bytes/s,
   arithmetic intensity, and the placement against the peak table
   (:func:`.xla.device_peaks`): the roofline-minimum time is
   ``max(flops/peak_flops, bytes/peak_hbm)``; a layer whose FLOPs term
   dominates is **compute-bound**, one whose bytes term dominates is
   **memory-bound**, and one carrying no cost at all is
   **overhead-bound**.
4. **communication accounting** — all-reduce / all-gather /
   collective-permute / reduce-scatter / all-to-all instructions are
   summed separately: bytes on the wire per step, the collective time
   modeled at the HBM ceiling and its share of the step — the
   per-collective numbers the cluster straggler classifier's
   ``communication_bound`` verdict is grounded in.

Surfacing: a ranked bottleneck block in the end-of-run summary table
("layer, class, achieved/peak %, est. headroom ms"), a ``roofline``
JSONL record carrying the full analysis, ``roofline.*`` gauges on
/metrics and /summary, and ``tools/roofline_report.py`` offline
(byte-identical block).

Gating: ``MXTPU_ROOFLINE=1`` *and* ``MXTPU_TELEMETRY=1``. Off = the
zero-overhead no-op contract of the rest of the plane: no HLO text is
ever rendered or parsed, no registry writes, one cached-bool check at
the registrar hook.
"""
import logging
import re
import threading

from .programs import _layer_from_op_name

__all__ = ['enabled', 'note_compiled', 'note_hlo', 'hlo_layer_costs',
           'analyze', 'summarize', 'republish',
           'snapshot_roofline', 'comm_share',
           'comm_pct_of_step', 'suggest_action',
           'RECLAIM_ACTIONS', 'TOP_N',
           'CLASS_COMPUTE', 'CLASS_MEMORY',
           'CLASS_OVERHEAD']

TOP_N = 8                  # bottleneck rows rendered in the summary block
CLASS_COMPUTE = 'compute-bound'
CLASS_MEMORY = 'memory-bound'
CLASS_OVERHEAD = 'overhead-bound'
CLASS_UNKNOWN = 'unknown'  # no peak table entry for this device

# class -> the concrete lever to pull (kept next to the classifier so
# the two never drift):
# which knob in THIS codebase reclaims a layer of that class
RECLAIM_ACTIONS = {
    CLASS_MEMORY: 'cut HBM traffic: MXTPU_BN_ONEPASS=1 one-pass stats, '
                  'full window donation (MXTPU_FUSED_DONATE=1), '
                  'layout work',
    CLASS_COMPUTE: 'remove work: MXTPU_REMAT_POLICY=none keeps forward '
                   'residuals (no backward recompute); shrink the math',
    CLASS_OVERHEAD: 'fuse/batch: raise MXTPU_FIT_STEPS_PER_CALL, keep '
                    'the upload overlapped (MXTPU_FUSED_FIT_PREFETCH=1); '
                    'MXTPU_REMAT_POLICY=dots/full if temp-bound',
}


def suggest_action(cls):
    """The lever string for a bottleneck class ('' for unknown): what
    the class->action table above says to pull, machine-readable
    so the worst layer's record/gauge names its remedy directly."""
    return RECLAIM_ACTIONS.get(cls, '')

# HLO opcode prefixes that move bytes between chips instead of running
# math — the communication-accounting family ('-start' variants match
# by prefix; '-done' halves are skipped so nothing counts twice)
COMM_OPS = ('all-reduce', 'all-gather', 'collective-permute',
            'reduce-scatter', 'all-to-all', 'collective-broadcast')

_lock = threading.Lock()
_decided = None
_programs = {}   # name -> parsed per-layer cost store (see _ingest)
_last = None     # last published analysis dict (snapshot_roofline)
_explicit_step_ms = None   # measured per-step ms a caller fed summarize()


def _tele():
    from . import enabled as tele_enabled
    tele_enabled()
    from . import _state as st
    return st


def enabled():
    """MXTPU_ROOFLINE=1 and telemetry on (decided once; off = one
    cached-bool check at the registrar hook)."""
    global _decided
    if _decided is None:
        from . import enabled as tele_enabled
        on = tele_enabled()
        if on:
            from ..config import flags
            try:
                on = bool(flags.get('MXTPU_ROOFLINE'))
            except Exception:  # noqa: BLE001 — stripped builds
                on = False
        _decided = on
    return _decided


# ---------------------------------------------------------------------------
# HLO text -> per-layer cost parse
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    'pred': 1, 's2': 1, 'u2': 1, 's4': 1, 'u4': 1, 's8': 1, 'u8': 1,
    'f8e5m2': 1, 'f8e4m3': 1, 'f8e4m3fn': 1, 'f8e4m3b11fnuz': 1,
    'f8e5m2fnuz': 1, 'f8e4m3fnuz': 1,
    's16': 2, 'u16': 2, 'f16': 2, 'bf16': 2,
    's32': 4, 'u32': 4, 'f32': 4,
    's64': 8, 'u64': 8, 'f64': 8, 'c64': 8, 'c128': 16,
}

_INSTR_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)\(')
_SHAPE_RE = re.compile(r'\b([a-z]+[0-9]+[a-z0-9]*|pred)\[([0-9,]*)\]')
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CONTRACT_RE = re.compile(r'lhs_contracting_dims=\{([0-9,]*)\}')
_DIM_LABELS_RE = re.compile(r'dim_labels=([\w?]+)_([\w?]+)->([\w?]+)')

# opcodes that are pure data movement / bookkeeping: no FLOPs, and no
# bytes either (a reshape/bitcast costs nothing at run time; counting
# its shapes would double every real operand)
_FREE_OPS = frozenset((
    'parameter', 'constant', 'tuple', 'get-tuple-element', 'bitcast',
    'reshape', 'transpose', 'broadcast', 'iota', 'copy', 'copy-start',
    'copy-done', 'after-all', 'partition-id', 'replica-id', 'domain',
    'opt-barrier', 'custom-call', 'rng-get-and-update-state',
    'get-dimension-size',
))

# wrapper instructions whose cost lives in a separately-parsed called
# computation: contribute nothing here (their bodies' instructions are
# parsed on their own lines)
_CALL_OPS = frozenset(('fusion', 'while', 'call', 'conditional',
                       'async-start', 'async-done'))


def _shape_bytes(dtype, dims):
    n = 1
    for d in dims.split(','):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4), n


def _instr_flops(opcode, line, out_elems, operands):
    """Estimated FLOPs for one instruction. Exact-ish for the terms
    that matter (dot: 2*out*K from the contracting dims; convolution:
    2*out*kernel/out_features from dim_labels); one-flop-per-output for
    the elementwise/reduce rest; zero for data movement."""
    if opcode == 'dot':
        k = 1
        m = _CONTRACT_RE.search(line)
        if m and operands:
            lhs_dims = operands[0][1]
            for idx in m.group(1).split(','):
                if idx and int(idx) < len(lhs_dims):
                    k *= lhs_dims[int(idx)]
        return 2.0 * out_elems * k
    if opcode == 'convolution':
        if len(operands) >= 2:
            kern = operands[1][1]
            kern_elems = 1
            for d in kern:
                kern_elems *= d
            out_feat = 1
            m = _DIM_LABELS_RE.search(line)
            if m:
                o_idx = m.group(2).find('o')
                if 0 <= o_idx < len(kern):
                    out_feat = kern[o_idx]
            elif kern:
                out_feat = kern[0]
            return 2.0 * out_elems * kern_elems / max(1, out_feat)
        return 0.0
    if opcode in ('reduce', 'reduce-window'):
        # one op per INPUT element, not per output
        if operands:
            n = 1
            for d in operands[0][1]:
                n *= d
            return float(n)
        return float(out_elems)
    if opcode in _FREE_OPS:
        return 0.0
    return float(out_elems)


def hlo_layer_costs(hlo_text):
    """Parse an HLO module's text into the per-layer cost store::

        {'layers':      {layer: {'flops': f, 'bytes': b}},
         'comm_bytes':  total bytes written by collectives (per step),
         'comm_ops':    {opcode: bytes},
         'flops_total': parsed-FLOPs sum, 'bytes_total': parsed-bytes sum}

    Best-effort by construction: unparsed lines cost nothing, ops
    without a named scope pool under ``_unattributed``. A scan/while
    body is parsed once — the same per-step convention XLA's own
    cost_analysis uses."""
    layers = {}
    comm_ops = {}
    comm_bytes = 0.0
    flops_total = bytes_total = 0.0
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        _name, out_sig, opcode = m.groups()
        out_bytes = out_elems = 0
        for dt, dims in _SHAPE_RE.findall(out_sig):
            b, n = _shape_bytes(dt, dims)
            out_bytes += b
            out_elems += n
        rest = line[m.end():]
        # operand shapes live between the opcode '(' and the attrs; the
        # attr tail (window/dim_labels/metadata) carries no shapes, so
        # scanning the rest of the line is safe
        operands = []
        for dt, dims in _SHAPE_RE.findall(rest):
            b, _n = _shape_bytes(dt, dims)
            dims_t = tuple(int(d) for d in dims.split(',') if d)
            operands.append((b, dims_t))
        is_comm = any(opcode.startswith(c) for c in COMM_OPS)
        if is_comm:
            if not opcode.endswith('-done'):
                comm_bytes += out_bytes
                comm_ops[opcode] = comm_ops.get(opcode, 0.0) + out_bytes
            continue
        if opcode in _FREE_OPS:
            continue
        if opcode in _CALL_OPS:
            continue
        mo = _OP_NAME_RE.search(line)
        layer = (_layer_from_op_name(mo.group(1)) if mo else None) \
            or '_unattributed'
        flops = _instr_flops(opcode, line, out_elems, operands)
        nbytes = float(out_bytes + sum(b for b, _d in operands))
        rec = layers.setdefault(layer, {'flops': 0.0, 'bytes': 0.0})
        rec['flops'] += flops
        rec['bytes'] += nbytes
        flops_total += flops
        bytes_total += nbytes
    return {'layers': layers, 'comm_bytes': comm_bytes,
            'comm_ops': comm_ops, 'flops_total': flops_total,
            'bytes_total': bytes_total}


# ---------------------------------------------------------------------------
# registrar hook (telemetry.programs.note_program calls this)
# ---------------------------------------------------------------------------

def note_hlo(name, hlo_text, analysis=None, step_flops=False):
    """Ingest one program's HLO text (tests feed synthetic modules
    here; live compiles arrive via :func:`note_compiled`). ``analysis``
    is the registrar's cost/memory dict — its ``flops`` /
    ``bytes_accessed`` calibrate the parsed per-layer split."""
    if not enabled():
        return
    costs = hlo_layer_costs(hlo_text)
    costs['analysis'] = dict(analysis or {})
    costs['step'] = bool(step_flops)
    costs['name'] = name
    with _lock:
        prev = _programs.get(name)
        if prev is not None and \
                prev['flops_total'] > costs['flops_total']:
            # keep the largest variant per name — the registrar's own
            # merge rule (a tail-batch recompile must not shrink the
            # roofline the run is judged by)
            return
        _programs[name] = costs


def note_compiled(name, compiled, analysis=None, step_flops=False):
    """The live hook: render ``compiled.as_text()`` and ingest it.
    Never raises — attribution is best-effort, execution is not."""
    if not enabled():
        return
    try:
        note_hlo(name, compiled.as_text(), analysis=analysis,
                 step_flops=step_flops)
    except Exception as e:  # noqa: BLE001 — observability must not kill
        logging.debug('roofline: HLO ingest of %s failed: %s', name, e)


def _pick_step_program():
    """The program the roofline diagnoses: the step-marked one with the
    most FLOPs (the registrar's MFU-feed rule), else the largest
    program seen at all."""
    with _lock:
        progs = list(_programs.values())
    if not progs:
        return None
    step = [p for p in progs if p['step']]
    pool = step or progs
    return max(pool, key=lambda p: (p.get('analysis', {}).get('flops')
                                    or p['flops_total']))


# ---------------------------------------------------------------------------
# classification + communication accounting
# ---------------------------------------------------------------------------

def _registry_step_ms(reg):
    """Best per-step milliseconds from the registry (the modeled path's
    denominator): fused window dispatch p50 / W, else the per-batch
    dispatch p50."""
    h = reg.get('fused_fit.dispatch')
    if h is not None and h.count:
        p50 = h.percentile(50)
        w = reg.get('fused_fit.steps_per_call')
        if p50 and w is not None and w.value:
            return float(p50) / float(w.value)
    h = reg.get('fit.dispatch')
    if h is not None and h.count:
        p50 = h.percentile(50)
        if p50:
            return float(p50)
    return None


def _classify(flops, nbytes, time_ms, peaks):
    """(class, roof_ms, roof_pct) for one layer against the peaks."""
    if peaks['flops'] <= 0 or peaks['hbm_bytes_s'] <= 0:
        return CLASS_UNKNOWN, None, None
    if flops <= 0 and nbytes <= 0:
        return CLASS_OVERHEAD, 0.0, 0.0
    ft = flops / peaks['flops']
    bt = nbytes / peaks['hbm_bytes_s']
    roof_ms = max(ft, bt) * 1e3
    cls = CLASS_COMPUTE if ft >= bt else CLASS_MEMORY
    roof_pct = None
    if time_ms and time_ms > 0:
        roof_pct = min(100.0, 100.0 * roof_ms / time_ms)
    return cls, roof_ms, roof_pct


def analyze(step_time_ms=None, device=None, warn_unknown=True):
    """Compute the roofline analysis dict (no publication — see
    :func:`summarize`). Returns None when roofline is off or no
    program has been ingested.

    ``step_time_ms`` overrides the registry-derived per-step time.
    ``warn_unknown=False`` makes the call truly read-only (the
    unknown-device peak lookup neither warns nor writes the
    ``roofline.peaks_unknown`` gauge — the scrape path). Every time in
    it is modeled: device time by symbol node is read from a capture
    through the compiled program's scope map (:func:`.programs.scope_map`,
    ``benchmark/reduce/scopes.py``), not here."""
    if not enabled():
        return None
    prog = _pick_step_program()
    if prog is None:
        return None
    from . import xla
    peaks = xla.device_peaks(device, warn=warn_unknown)

    analysis = prog.get('analysis') or {}
    # calibrate the parsed split against XLA's own whole-program totals
    # so per-layer numbers sum to what cost_analysis reported
    scale_f = scale_b = 1.0
    if analysis.get('flops') and prog['flops_total'] > 0:
        scale_f = float(analysis['flops']) / prog['flops_total']
    if analysis.get('bytes_accessed') and prog['bytes_total'] > 0:
        scale_b = float(analysis['bytes_accessed']) / prog['bytes_total']

    reg = _tele().registry
    if step_time_ms is None:
        step_time_ms = _registry_step_ms(reg)

    rows = []
    roof_total_ms = 0.0
    layer_items = sorted(prog['layers'].items())
    for layer, c in layer_items:
        flops = c['flops'] * scale_f
        nbytes = c['bytes'] * scale_b
        if peaks['flops'] > 0 and peaks['hbm_bytes_s'] > 0:
            roof_total_ms += max(flops / peaks['flops'],
                                 nbytes / peaks['hbm_bytes_s']) * 1e3
        rows.append([layer, flops, nbytes])

    # distribute the measured step time across layers in proportion
    # to each one's roofline-minimum time (perfect execution would
    # land exactly there); with no step time either, assume the
    # roofline itself
    layer_ms = {}
    for layer, flops, nbytes in rows:
        if peaks['flops'] > 0 and peaks['hbm_bytes_s'] > 0:
            roof = max(flops / peaks['flops'],
                       nbytes / peaks['hbm_bytes_s']) * 1e3
        else:
            roof = 0.0
        if step_time_ms and roof_total_ms > 0:
            layer_ms[layer] = step_time_ms * roof / roof_total_ms
        else:
            layer_ms[layer] = roof

    out_rows = []
    for layer, flops, nbytes in rows:
        t_ms = layer_ms.get(layer, 0.0)
        cls, roof_ms, roof_pct = _classify(flops, nbytes, t_ms, peaks)
        row = {'layer': layer, 'class': cls,
               'flops': round(flops, 1), 'bytes': round(nbytes, 1),
               'time_ms': round(t_ms, 4),
               'ai': round(flops / nbytes, 3) if nbytes > 0 else None,
               'achieved_flops_s': round(flops / (t_ms / 1e3), 1)
               if t_ms > 0 else None,
               'achieved_bytes_s': round(nbytes / (t_ms / 1e3), 1)
               if t_ms > 0 else None,
               'roof_pct': round(roof_pct, 1)
               if roof_pct is not None else None,
               'headroom_ms': round(max(0.0, t_ms - roof_ms), 4)
               if roof_ms is not None else None}
        out_rows.append(row)
    out_rows.sort(key=lambda r: (-(r['headroom_ms'] or 0.0),
                                 -r['time_ms'], r['layer']))

    # communication accounting: bytes are per step by the scan-body
    # convention; the time is modeled at the HBM ceiling — a deliberate
    # lower bound, labeled as such
    comm_bytes = prog['comm_bytes']
    comm = None
    if comm_bytes > 0:
        comm_ms = (comm_bytes / peaks['hbm_bytes_s'] * 1e3) \
            if peaks['hbm_bytes_s'] > 0 else None
        comm = {'bytes': round(comm_bytes, 1),
                'time_ms': round(comm_ms, 4)
                if comm_ms is not None else None,
                'overlap_pct': None,
                'pct_of_step': round(100.0 * comm_ms / step_time_ms, 1)
                if comm_ms and step_time_ms else None,
                'ops': {k: round(v, 1)
                        for k, v in sorted(prog['comm_ops'].items())},
                'source': 'modeled'}

    return {
        'program': prog['name'],
        'source': 'modeled',
        'device': peaks['kind'],
        'peaks': peaks['source'],
        'peak_tflops': round(peaks['flops'] / 1e12, 3)
        if peaks['flops'] else None,
        'peak_hbm_gbs': round(peaks['hbm_bytes_s'] / 1e9, 3)
        if peaks['hbm_bytes_s'] else None,
        'step_time_ms': round(step_time_ms, 4)
        if step_time_ms is not None else None,
        'layers': out_rows,
        'worst_action': suggest_action(out_rows[0]['class'])
        if out_rows else None,
        'comm': comm,
    }


def comm_share():
    """``(pct, source)`` — the collective share of the step (%) with
    its provenance attached: ``'modeled'``, the HBM-ceiling lower
    bound (nothing here measures it), or ``(None, None)`` when there is
    nothing to report.
    The provenance travels with the number everywhere it is consumed
    (cluster records, /metrics, the goodput comm bucket) so a model is
    never laundered into a measurement. Uses the last published
    analysis when one carries comm numbers; otherwise a live sync round
    computes the MODELED share directly from the program's collective
    bytes and the HBM ceiling — the same arithmetic as analyze()'s
    modeled comm path, without rebuilding the per-layer analysis every
    sync round (the common no-collective program exits on the bytes
    check)."""
    with _lock:
        last = _last
    if last is not None and last.get('comm'):
        comm = last['comm']
        return (comm.get('pct_of_step'),
                comm.get('source') or last.get('source') or 'modeled')
    if not enabled():
        return None, None
    prog = _pick_step_program()
    if prog is None or prog['comm_bytes'] <= 0:
        return None, None
    from . import xla
    peaks = xla.device_peaks()
    if peaks['hbm_bytes_s'] <= 0:
        return None, None
    step_ms = _registry_step_ms(_tele().registry)
    if not step_ms:
        return None, None
    comm_ms = prog['comm_bytes'] / peaks['hbm_bytes_s'] * 1e3
    return round(100.0 * comm_ms / step_ms, 1), 'modeled'


def comm_pct_of_step():
    """The collective share of the step (%), or None — the provenance-
    free convenience over :func:`comm_share` (callers feeding records
    or /metrics should use comm_share and carry the source along)."""
    return comm_share()[0]


def summarize(step_time_ms=None):
    """Run :func:`analyze`, publish ``roofline.*`` gauges + the
    ``roofline`` JSONL record, and return the analysis dict (None when
    off/empty). Called from telemetry.write_summary.

    A measured ``step_time_ms`` (a caller that timed its own loop) is
    remembered: a later summarize() with none — the atexit
    write_summary — reuses it instead of falling back to the
    registry-derived time, so the run's roofline records never
    disagree about the step-time denominator."""
    global _last, _explicit_step_ms
    if step_time_ms is not None:
        _explicit_step_ms = step_time_ms
    elif _explicit_step_ms is not None:
        step_time_ms = _explicit_step_ms
    d = analyze(step_time_ms=step_time_ms)
    if d is None:
        return None
    st = _tele()
    _publish_gauges(d, st.registry)
    if st.sink is not None:
        rec = {'type': 'roofline'}
        rec.update(d)
        st.sink.emit(rec)
    with _lock:
        _last = d
    return d


def _publish_gauges(d, reg):
    """One analysis dict -> the roofline.* gauge family (shared by
    :func:`summarize` and the cluster-cadence :func:`republish`)."""
    reg.gauge('roofline.layers').set(len(d['layers']))
    if d['layers']:
        worst = d['layers'][0]
        reg.gauge('roofline.worst_layer').set(worst['layer'])
        reg.gauge('roofline.worst_class').set(worst['class'])
        # unconditionally, so an 'unknown'-class round ('' action)
        # never leaves a previous round's lever string stale next to
        # the updated worst_layer/worst_class pair
        reg.gauge('roofline.worst_action').set(
            d.get('worst_action') or '')
        if worst['roof_pct'] is not None:
            reg.gauge('roofline.worst_roof_pct').set(worst['roof_pct'])
        if worst['headroom_ms'] is not None:
            reg.gauge('roofline.worst_headroom_ms').set(
                worst['headroom_ms'])
    comm = d.get('comm')
    if comm:
        reg.gauge('roofline.comm_bytes').set(comm['bytes'])
        if comm['time_ms'] is not None:
            reg.gauge('roofline.comm_time_ms').set(comm['time_ms'])
        if comm['overlap_pct'] is not None:
            reg.gauge('roofline.comm_overlap_pct').set(
                comm['overlap_pct'])
        if comm['pct_of_step'] is not None:
            reg.gauge('roofline.comm_pct_of_step').set(
                comm['pct_of_step'])


def republish():
    """Cluster-sync-cadence hook (telemetry/cluster.py): refresh the
    ``roofline.*`` gauges from a read-only MODELED analysis so a
    mid-run ``/metrics`` scrape sees live roofline state, not just the
    values frozen at the last summarize()/write_summary(). No JSONL
    record is emitted and no profiler capture is loaded from disk — a
    sync round must stay cheap. Returns the analysis dict, or None
    while the flag is off / nothing is ingested yet."""
    global _last
    if not enabled():
        return None
    d = analyze(step_time_ms=_explicit_step_ms, warn_unknown=False)
    if d is None:
        return None
    _publish_gauges(d, _tele().registry)
    with _lock:
        _last = d
    return d


def snapshot_roofline():
    """The last published analysis dict (the /summary payload's and
    read-only summary()'s input), or None."""
    with _lock:
        return _last


def _reset_for_tests():
    global _decided, _last, _explicit_step_ms
    with _lock:
        _programs.clear()
        _last = None
    _decided = None
    _explicit_step_ms = None
    from . import xla
    xla._reset_peaks_warned_for_tests()
