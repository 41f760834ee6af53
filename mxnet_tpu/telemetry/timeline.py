"""Pod-level step timeline: clock alignment + critical-path attribution.

The cluster plane (telemetry/cluster.py) can already NAME the slowest
host of a gang; this module answers the next question — which PHASE on
that host gates the pod, and by how much. Three pieces:

- **clock alignment**: every sync-round allgather already acts as a
  barrier, so the instant it returns is (approximately) the same true
  time on every host. Each host samples ``(wall, monotonic)`` at that
  instant (:func:`note_sync_exit`) and contributes the pair in the
  NEXT round's sync vector — zero new collectives, the vector just
  grows (NaN-padded for senders predating the slots). Process 0 turns
  each round's wall samples into per-host offsets against the fleet
  median, keeps a bounded ring per host, and publishes the ring median
  as ``cluster.h<i>.clock_offset_ms`` — NTP-style, drift-tolerant, and
  robust to one noisy barrier exit. A wall clock that STEPS (ntpdate)
  betrays itself against the monotonic companion and its ring is
  discarded rather than averaged across the step.
- **step-phase ledger**: the hot loops already emit spans for every
  host-side phase (draw, put, dispatch, fetch, checkpoint, kvstore
  push/pull); :func:`note_span` buckets their durations per phase
  (:data:`PHASE_SPANS`), and each sync round ships this host's
  per-step phase milliseconds over the round window in the same grown
  sync vector. ``tools/trace_merge.py`` stitches the per-host span
  records / chrome traces into ONE offset-corrected Perfetto trace
  with ``pid=host``.
- **critical-path attribution**: per sync round, process 0 decomposes
  the gang step into compute / collective-wait / io / host-side per
  host (:func:`decompose`), reads the skew (fastest-host idle at the
  allreduce = slowest minus fastest step time) and names the gating
  host AND phase — the phase on the slowest host with the largest
  excess over the fleet's best (``timeline.critical_host``,
  ``timeline.critical_phase``, ``timeline.skew_ms`` gauges, a "step
  timeline" summary block, ``timeline`` JSONL records). That sharpens
  "host 3 is slow" into "host 3's input draw adds 4.1 ms of skew per
  step".

Gating: ``MXTPU_TIMELINE=1`` *and* ``MXTPU_TELEMETRY=1``. Off = true
no-op: one cached-bool check per entry point, no registry writes, no
I/O, and the lowered programs are byte-identical (everything here is
host-side arithmetic over already-collected numbers — asserted by
tests/unittest/test_timeline.py like every prior plane).
"""
import collections
import math
import threading
import time

import numpy as np

__all__ = ['PHASES', 'PHASE_SPANS', 'SLOTS', 'CLOCK_RING', 'enabled',
           'note_span', 'note_step', 'note_sync_exit', 'local_slots',
           'estimate_offsets', 'decompose', 'attribute', 'publish_round',
           'summarize', 'snapshot_timeline']

# the ledger's phases, in sync-vector slot order (SLOTS[2 + k] carries
# PHASES[k]); 'collective' and 'compute' are DERIVED per round from the
# step time + the roofline comm share, never shipped
PHASES = ('draw', 'put', 'dispatch', 'fetch', 'checkpoint', 'kvstore')

# this plane's appended cluster.SYNC_KEYS slots, in order: the clock
# pair sampled at the PREVIOUS round's allgather exit, then each
# phase's per-step milliseconds over the round window. All NaN while
# MXTPU_TIMELINE is off (the append-only/NaN-pad vector rule holds)
SLOTS = ('clock_wall_s', 'clock_mono_s', 'tl_draw_ms', 'tl_put_ms',
         'tl_dispatch_ms', 'tl_fetch_ms', 'tl_ckpt_ms', 'tl_kv_ms')

# LEAF span -> phase. Only leaves (goodput.py's double-count rule):
# parents like fit.batch never feed, or a phase would count twice.
PHASE_SPANS = {
    'fit.draw': 'draw', 'fused_fit.draw': 'draw',
    'fused_fit.put': 'put',
    'fit.dispatch': 'dispatch', 'fused_fit.dispatch': 'dispatch',
    'fused_fit.fetch': 'fetch', 'fit.metric': 'fetch',
    'ckpt.save': 'checkpoint', 'ckpt.capture': 'checkpoint',
    'kvstore.push': 'kvstore', 'kvstore.pull': 'kvstore',
}

CLOCK_RING = 16        # per-host offset samples backing the median
# wall minus monotonic advancing differently by more than this between
# two rounds = the wall clock STEPPED (ntpdate, not drift): the host's
# ring history predates a different clock and is discarded
_WALL_STEP_MS = 250.0
# the sync vector travels as float32 (cluster._allgather), whose
# resolution at epoch magnitude (~1.7e9 s) is ~2 MINUTES — raw
# time.time() would swallow any skew. Both clock samples therefore
# ship modulo this window: float32 below 64 resolves ~8 µs, and the
# offset math is circular (true inter-host skews beyond ±32 s alias,
# far past anything clock sync leaves standing)
CLOCK_MOD = 64.0


def _wrap(d):
    """Centre a CLOCK_MOD-circular difference into [-32 s, +32 s)."""
    return float(d - CLOCK_MOD * np.floor(d / CLOCK_MOD + 0.5))


class _TState:
    __slots__ = ('decided', 'active', 'lock', 'steps', 'wall_ms',
                 'last_t', 't_start', 'phase_ms', 'round_base',
                 'round_steps', 'pend_wall', 'pend_mono', 'offset_rings',
                 'last_pair', 'last')

    def __init__(self):
        self.decided = False
        self.active = False
        self.lock = threading.Lock()
        # local step/wall bookkeeping (every host)
        self.steps = 0
        self.wall_ms = 0.0          # wall between note_step calls
        self.last_t = None
        self.t_start = None
        self.phase_ms = {p: 0.0 for p in PHASES}   # cumulative, run-long
        self.round_base = dict(self.phase_ms)      # snapshot at last round
        self.round_steps = 0
        # the clock pair sampled at the last sync-round barrier exit,
        # shipped in the NEXT round's vector (NaN before the first)
        self.pend_wall = float('nan')
        self.pend_mono = float('nan')
        # process-0 aggregation state
        self.offset_rings = {}      # host -> deque of per-round offsets
        self.last_pair = {}         # host -> (wall, mono) of prior round
        self.last = None            # last attribution dict


_state = _TState()
_decide_lock = threading.Lock()


def _tele():
    """The telemetry package state (deciding it from the flag first)."""
    from . import enabled as _tele_enabled, _state as st
    _tele_enabled()
    return st


def _decide():
    with _decide_lock:
        if _state.decided:
            return _state.active
        on = False
        if _tele().active:
            from ..config import flags
            try:
                flags.reload('MXTPU_TIMELINE')
                on = bool(flags.get('MXTPU_TIMELINE'))
            except Exception:  # noqa: BLE001 — stripped builds w/o the flag
                on = False
        _state.active = on
        _state.decided = True
    return _state.active


def enabled():
    """Whether the timeline plane is on: MXTPU_TIMELINE=1 *and*
    MXTPU_TELEMETRY=1, decided once. One attribute check after the
    first call — the span tap's and the fit loops' gate."""
    if _state.decided:
        return _state.active
    return _decide()


# ---------------------------------------------------------------------------
# local ledger (every host)
# ---------------------------------------------------------------------------

def note_span(name, dur_ms):
    """Span tap (telemetry._Span.__exit__, already inside the
    telemetry-active branch): bucket a finished leaf span's duration
    into its phase. Non-phase spans cost one dict miss."""
    if not enabled():
        return
    p = PHASE_SPANS.get(name)
    if p is None:
        return
    st = _state
    with st.lock:
        st.phase_ms[p] += dur_ms


def note_step(steps=1):
    """Hot-path hook (both fit loops, same seam as memory.note_step):
    count trained steps and the wall between calls, so the phase
    ledger can normalize to per-step milliseconds."""
    if not enabled():
        return
    now = time.time()
    st = _state
    with st.lock:
        if st.t_start is None:
            st.t_start = now
        if st.last_t is not None and steps > 0:
            st.wall_ms += (now - st.last_t) * 1e3
        st.last_t = now
        st.steps += steps
        st.round_steps += steps


def note_sync_exit():
    """Called on EVERY host the instant the sync-round allgather
    returns (cluster.sync_now): the barrier exit is the shared-time
    reference. The pair ships in the NEXT round's vector. An armed
    ``clock-skew`` fault (faults.py) shifts the wall sample here —
    injected drift the estimator must then name."""
    if not enabled():
        return
    from .. import faults
    wall = time.time() + faults.clock_skew_ms() / 1e3
    mono = time.monotonic()
    st = _state
    with st.lock:
        st.pend_wall = wall
        st.pend_mono = mono


def local_slots():
    """This host's contribution to the sync vector (SLOTS order): the
    pending clock pair + per-step phase ms over the round window.
    All-NaN while off — the vector's shape never depends on the flag."""
    if not enabled():
        return [float('nan')] * len(SLOTS)
    st = _state
    with st.lock:
        wall, mono = st.pend_wall, st.pend_mono
        steps = st.round_steps
        deltas = [st.phase_ms[p] - st.round_base[p] for p in PHASES]
        st.round_base = dict(st.phase_ms)
        st.round_steps = 0
    # modulo the float32-safe window (see CLOCK_MOD); NaN stays NaN
    out = [wall % CLOCK_MOD, mono % CLOCK_MOD]
    out.extend((d / steps) if steps > 0 else float('nan') for d in deltas)
    return out


# ---------------------------------------------------------------------------
# offset estimation (pure math + the process-0 rings)
# ---------------------------------------------------------------------------

def estimate_offsets(walls):
    """One round's wall samples -> per-row offset_ms against the fleet
    median (NaN rows — senders without a sample yet — stay NaN). The
    samples arrive modulo CLOCK_MOD, so the math is circular: deltas
    against the first finite sample, centred into ±CLOCK_MOD/2, then
    re-based on their median — identical to a plain median for
    non-wrapping inputs. Pure; the unit the drift tests pin."""
    walls = np.asarray(walls, np.float64)
    valid = np.isfinite(walls)
    if not valid.any():
        return [float('nan')] * len(walls)
    anchor = float(walls[valid][0])
    d = np.array([_wrap(w - anchor) for w in walls])
    ref = float(np.median(d[valid]))
    return [float((x - ref) * 1e3) if ok else float('nan')
            for x, ok in zip(d, valid)]


def _note_round_clocks(walls, monos, host_ids):
    """Fold one round's gathered clock samples into the per-host
    offset rings; returns {host: ring-median offset_ms}. A wall that
    stepped against its monotonic companion resets that host's ring."""
    st = _state
    offs = estimate_offsets(walls)
    out = {}
    with st.lock:
        for i, hid in enumerate(host_ids):
            w = float(walls[i])
            m = float(monos[i]) if i < len(monos) else float('nan')
            if not math.isfinite(w):
                continue
            prev = st.last_pair.get(hid)
            if prev is not None and math.isfinite(m) \
                    and math.isfinite(prev[1]) \
                    and abs(_wrap((w - prev[0]) - (m - prev[1]))) * 1e3 \
                    > _WALL_STEP_MS:
                st.offset_rings.pop(hid, None)
            st.last_pair[hid] = (w, m)
            if math.isfinite(offs[i]):
                ring = st.offset_rings.get(hid)
                if ring is None:
                    ring = st.offset_rings[hid] = collections.deque(
                        maxlen=CLOCK_RING)
                ring.append(offs[i])
        for hid in sorted(st.offset_rings):
            ring = st.offset_rings[hid]
            if ring:
                out[hid] = float(np.median(list(ring)))
    return out


# ---------------------------------------------------------------------------
# critical-path attribution (pure, shared with the offline CLIs)
# ---------------------------------------------------------------------------

def _finite(v):
    try:
        return v is not None and math.isfinite(float(v))
    except (TypeError, ValueError):
        return False


def decompose(step_ms, phases, comm_pct=None):
    """One host's per-step decomposition (ms): collective-wait from the
    roofline's comm share, io = draw + put, host-side = fetch +
    checkpoint + kvstore, compute = the clamped remainder. Pure."""
    step = float(step_ms) if _finite(step_ms) else None
    def f(name):
        v = phases.get(name)
        return float(v) if _finite(v) else 0.0
    coll = step * float(comm_pct) / 100.0 \
        if step is not None and _finite(comm_pct) else 0.0
    io = f('draw') + f('put')
    host = f('fetch') + f('checkpoint') + f('kvstore')
    compute = max(0.0, step - coll - io - host) if step is not None else 0.0
    return {'compute_ms': compute, 'collective_ms': coll, 'io_ms': io,
            'host_ms': host}


def attribute(mat, host_ids=None, step=None, offsets=None):
    """Critical-path attribution for one gathered sync matrix: the
    per-host gang-step decomposition, the skew (fastest-host idle at
    the allreduce), and the gating host AND phase — the phase on the
    slowest host with the largest excess over the fleet's best host
    (a single-host round falls back to the largest share). Pure math
    over the matrix — shared by the live publish path, the offline
    CLIs and the unit tests."""
    from . import cluster as _cluster
    mat = np.asarray(mat, np.float64)
    if mat.ndim == 1:
        mat = mat[None, :]
    n = mat.shape[0]
    if host_ids is None:
        host_ids = _cluster._host_ids(mat)
    keys = _cluster.SYNC_KEYS

    def col(name):
        j = keys.index(name)
        return [float(mat[i, j]) if j < mat.shape[1] else float('nan')
                for i in range(n)]

    times = col('step_time_ms')
    comms = col('comm_pct')
    phase_cols = {p: col(SLOTS[2 + k]) for k, p in enumerate(PHASES)}
    decomps = []
    per_host = []
    for i in range(n):
        phases = {p: phase_cols[p][i] for p in PHASES}
        d = decompose(times[i], phases,
                      comms[i] if _finite(comms[i]) else None)
        decomps.append(d)
        row = {'host': host_ids[i],
               'step_time_ms': round(times[i], 3) if _finite(times[i])
               else None}
        row.update({k: round(v, 3) for k, v in d.items()})
        row['phases'] = {p: round(phases[p], 3) if _finite(phases[p])
                         else None for p in PHASES}
        if offsets and host_ids[i] in offsets:
            row['clock_offset_ms'] = round(offsets[host_ids[i]], 3)
        per_host.append(row)
    out = {'hosts': n, 'per_host': per_host}
    if step is not None:
        out['step'] = int(step)
    valid = [i for i in range(n) if _finite(times[i])]
    if not valid:
        return out
    crit = max(valid, key=lambda i: times[i])
    tmax, tmin = times[crit], min(times[i] for i in valid)
    out['gang_step_ms'] = round(tmax, 3)
    out['skew_ms'] = round(tmax - tmin, 3) if len(valid) > 1 else 0.0
    out['critical_host'] = host_ids[crit]
    # candidates: every measured ledger phase plus the derived compute/
    # collective splits. Multi-host: a candidate's score is the slowest
    # host's EXCESS over the fleet's best host — how much skew that
    # phase adds per step. Single host: the raw share (largest wins).
    cand = {}
    series = {p: phase_cols[p] for p in PHASES}
    series['compute'] = [d['compute_ms'] for d in decomps]
    series['collective'] = [d['collective_ms'] for d in decomps]
    for name, vals in series.items():
        v = vals[crit]
        if not _finite(v):
            continue
        if len(valid) > 1:
            others = [vals[i] for i in valid if _finite(vals[i])]
            if not others:
                continue
            cand[name] = float(v) - min(float(o) for o in others)
        else:
            cand[name] = float(v)
    if cand:
        phase = max(sorted(cand), key=lambda k: cand[k])
        out['critical_phase'] = phase
        out['phase_excess_ms'] = round(max(0.0, cand[phase]), 3)
    return out


# ---------------------------------------------------------------------------
# publication (process 0, once per sync round) + summary
# ---------------------------------------------------------------------------

def publish_round(mat, host_ids, steps):
    """Process 0, per sync round (cluster._publish): fold the round's
    clock samples into the offset rings, attribute the gang step, and
    publish the gauges + the ``timeline`` JSONL record. Returns the
    attribution dict, or None while off."""
    if not enabled():
        return None
    from . import cluster as _cluster
    mat = np.asarray(mat, np.float64)
    if mat.ndim == 1:
        mat = mat[None, :]
    keys = _cluster.SYNC_KEYS
    n = mat.shape[0]

    def col(name):
        j = keys.index(name)
        return [float(mat[i, j]) if j < mat.shape[1] else float('nan')
                for i in range(n)]

    offsets = _note_round_clocks(col('clock_wall_s'), col('clock_mono_s'),
                                 host_ids)
    out = attribute(mat, host_ids, step=steps, offsets=offsets)
    _publish_snapshot(out, offsets)
    return out


def _publish_snapshot(out, offsets=None):
    """Gauges + JSONL record + the stored snapshot for one attribution
    dict (the sync-round path and the end-of-run fallback share it)."""
    st = _tele()
    reg = st.registry
    for hid, off in sorted((offsets or {}).items()):
        reg.gauge('cluster.h%d.clock_offset_ms' % hid).set(round(off, 3))
    if out.get('gang_step_ms') is not None:
        reg.gauge('timeline.gang_step_ms').set(out['gang_step_ms'])
    if out.get('skew_ms') is not None:
        reg.gauge('timeline.skew_ms').set(out['skew_ms'])
    if out.get('critical_host') is not None:
        reg.gauge('timeline.critical_host').set(out['critical_host'])
    if out.get('critical_phase') is not None:
        reg.gauge('timeline.critical_phase').set(out['critical_phase'])
    with _state.lock:
        _state.last = out
    if st.sink is not None:
        rec = {'type': 'timeline'}
        rec.update(out)
        st.sink.emit(rec)


def _local_attribution():
    """A single-host attribution from this host's own ledger (no sync
    round ever published): per-step wall from the note_step stream,
    phases from the span tap, comm share from the roofline. None
    before any counted step."""
    st = _state
    with st.lock:
        steps = st.steps
        wall_ms = st.wall_ms
        phases = {p: st.phase_ms[p] for p in PHASES}
    if steps <= 0:
        return None
    from . import cluster as _cluster, roofline
    keys = _cluster.SYNC_KEYS
    row = [float('nan')] * len(keys)
    # the first note_step opens the wall window, so wall_ms spans
    # steps-1 intervals in the per-batch loop; the fused loop notes
    # whole windows, where steps per interval is exact — use the
    # honest denominator and accept the per-batch off-by-one
    if wall_ms > 0:
        step_ms = wall_ms / steps
    else:
        # a run short enough to fit in ONE window never opened a wall
        # interval — fall back to the span histograms, with the same
        # per-step normalization the offline per-host table uses
        snap = _tele().registry.snapshot()
        hists, gauges = snap['histograms'], snap['gauges']
        h = hists.get('fit.batch')
        w = gauges.get('fused_fit.steps_per_call')
        if h and h.get('count') and h.get('p50') is not None:
            step_ms = float(h['p50'])
        elif w:
            h = hists.get('fused_fit.dispatch')
            step_ms = float(h['p50']) / float(w) \
                if h and h.get('count') and h.get('p50') is not None \
                else float('nan')
        else:
            step_ms = float('nan')
    row[keys.index('step_time_ms')] = step_ms
    comm, _src = roofline.comm_share()
    if comm is not None:
        row[keys.index('comm_pct')] = float(comm)
    row[keys.index('proc_index')] = float(_cluster.host_index())
    for k, p in enumerate(PHASES):
        row[keys.index(SLOTS[2 + k])] = phases[p] / steps
    return attribute([row], step=steps)


def summarize():
    """End-of-run roll-up (telemetry.write_summary): the last published
    sync-round attribution, or — on a run that never synced — a
    single-host attribution from the local ledger, published the same
    way. Returns the summary record's 'timeline' dict, or None."""
    if not enabled():
        return None
    with _state.lock:
        last = dict(_state.last) if _state.last else None
    if last is not None:
        return last
    out = _local_attribution()
    if out is None:
        return None
    _publish_snapshot(out)
    return out


def snapshot_timeline():
    """The last attribution (sync round or end-of-run local), or None
    — the /summary key and the summary table's block input."""
    with _state.lock:
        return dict(_state.last) if _state.last else None


def _reset_for_tests():
    global _state
    _state = _TState()
