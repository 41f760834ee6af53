"""XLA-side telemetry: compile events, device memory, retraces, peaks.

Compile observability comes from jax.monitoring: XLA emits
``/jax/core/compile/backend_compile_duration`` once per backend
compile, which feeds the ``xla.compiles`` counter, the accumulated
``xla.compile_secs``, and a per-compile JSONL record. With the
persistent compilation cache on (config.enable_compile_cache), the cache's
``cache_hits`` / ``compile_time_saved_sec`` events feed
``xla.cache_hits`` and ``xla.cache_saved_secs`` — how many compiles a
warm start was served from disk, and the seconds it refunded. The
listeners are registered once per process and are no-ops while
telemetry is off, so they can stay installed across test resets.

Retrace detection is framework-side: the sites that BUILD compiled
programs (Executor construction, the fused-fit window builder) call
:func:`note_retrace` with a value key identifying the graph; the same
key arriving more than ``MXTPU_TELEMETRY_RETRACE_WARN`` times is the
classic retrace storm (a shape/attr leaking into the program key every
batch) and logs one loud
warning plus a ``retrace_storm`` JSONL record.

Memory gauges read ``device.memory_stats()`` (live/peak bytes on TPU;
None on CPU — sampled best-effort, with ONE process-wide warning the
first time no device reports stats so empty gauges are explained). The
program registrar (:mod:`.programs`) feeds :func:`note_step_flops` from
whichever train-step program the fit loop compiles: XLA's count of the
compiled step's FLOPs (recomputation included), published as the
``xla.step_flops`` gauge. :func:`device_peaks` holds the per-chip
ceilings the roofline plane divides by.
"""
import logging
import threading
import time

__all__ = ['install', 'note_retrace', 'note_step_flops', 'sample_memory',
           'device_peak_flops', 'device_peaks']

_COMPILE_EVENT_SUFFIX = 'backend_compile_duration'
# persistent-compilation-cache events: a hit
# means a compile request was served from disk instead of XLA
_CACHE_HIT_EVENT = '/jax/compilation_cache/cache_hits'
_CACHE_SAVED_SUFFIX = 'compile_time_saved_sec'

# Per-chip hardware ceilings, by device_kind substring (order matters:
# 'v5p' must match before 'v5'). Columns: peak dense bf16 FLOP/s and
# peak HBM bytes/s — the two roofline denominators (telemetry/roofline
# classifies each layer by which ceiling bounds it).
_PEAK_TABLE = [
    ('v6', 918e12, 1640e9), ('v5p', 459e12, 2765e9), ('v5', 197e12, 819e9),
    ('v4', 275e12, 1228e9), ('v3', 123e12, 900e9), ('v2', 45e12, 700e9),
]
# A host CPU has no entry and gets none: a roofline share or an MFU
# against a guessed peak would be a CPU number under a device metric's
# name. MXTPU_PEAK_TFLOPS / MXTPU_PEAK_HBM_GBS name one explicitly.

_installed = False
_install_lock = threading.Lock()


def _state():
    from . import enabled
    enabled()   # decide from the flag if nothing else has yet
    from . import _state as st
    return st


def install():
    """Register the jax.monitoring compile listener (once per process)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        try:
            import jax.monitoring as _mon
            _mon.register_event_duration_secs_listener(_on_duration)
            _mon.register_event_listener(_on_event)
            _installed = True
        except Exception as e:  # noqa: BLE001 — observability must not kill
            logging.debug('telemetry: jax.monitoring unavailable: %s', e)


def _on_duration(event, duration, **kwargs):
    st = _state()
    if not st.active:
        return
    if event.endswith(_COMPILE_EVENT_SUFFIX):
        st.registry.counter('xla.compiles').inc()
        st.registry.counter('xla.compile_secs').inc(float(duration))
        if st.sink is not None:
            st.sink.emit({'type': 'compile', 't': time.time(),
                          'dur_s': round(float(duration), 4)})
    elif event.endswith(_CACHE_SAVED_SUFFIX):
        # compile seconds the persistent cache refunded this process
        st.registry.counter('xla.cache_saved_secs').inc(float(duration))


def _on_event(event, **kwargs):
    st = _state()
    if not st.active:
        return
    if event == _CACHE_HIT_EVENT:
        st.registry.counter('xla.cache_hits').inc()
        if st.sink is not None:
            st.sink.emit({'type': 'cache_hit', 't': time.time()})


def _retrace_threshold():
    from ..config import flags
    try:
        return flags.get('MXTPU_TELEMETRY_RETRACE_WARN')
    except Exception:  # noqa: BLE001 — undeclared in stripped builds
        return 5


def note_retrace(key):
    """A compiled program for graph ``key`` was (re)built. The first
    build is free; every further build of the SAME key counts as a
    retrace, and crossing the warn threshold logs the storm once."""
    st = _state()
    if not st.active:
        return
    with st.lock:
        n = st.retraces[key] = st.retraces.get(key, 0) + 1
    if n > 1:
        st.registry.counter('xla.retraces').inc()
    thresh = _retrace_threshold()
    if n == thresh + 1:
        logging.warning(
            'telemetry: retrace storm — the same graph was compiled %d '
            'times (key=%s). A shape/dtype/attr is leaking into the '
            'program cache key every batch; throughput is bounded by '
            'compile time until it stops.', n, _short(key))
        if st.sink is not None:
            st.sink.emit({'type': 'retrace_storm', 'key': _short(key),
                          'count': n})


def _short(key, limit=200):
    s = str(key)
    return s if len(s) <= limit else s[:limit] + '...'


def note_step_flops(flops):
    """Record the compiled training step's FLOPs (the
    ``xla.step_flops`` gauge). Fed automatically by telemetry.programs
    when a step-marked program (executor fwd+bwd, fused fit window)
    compiles."""
    st = _state()
    if st.active and flops:
        st.registry.gauge('xla.step_flops').set(float(flops))


_memory_stats_warned = False


def _warn_memory_unavailable(reason):
    """Once per process at WARNING (debug thereafter): a user on an
    unsupported backend must learn WHY the memory gauges stay empty —
    a forever-debug message buries the explanation."""
    global _memory_stats_warned
    if _memory_stats_warned:
        logging.debug('telemetry: memory_stats still unavailable: %s',
                      reason)
        return
    _memory_stats_warned = True
    logging.warning(
        'telemetry: device memory_stats() unavailable (%s) — the '
        'xla.bytes_in_use / xla.peak_bytes_in_use gauges and the OOM '
        'device totals stay empty on this backend', reason)


def sample_memory(device=None):
    """Update live/peak device-byte gauges from ``memory_stats()``.
    Best-effort: CPU backends return None and are skipped (warned once
    per process so empty gauges are explained)."""
    st = _state()
    if not st.active:
        return None
    try:
        if device is None:
            import jax
            devices = jax.local_devices()
        else:
            devices = [device]
        for d in devices:
            stats = d.memory_stats()
            if not stats:
                continue
            live = stats.get('bytes_in_use')
            peak = stats.get('peak_bytes_in_use')
            if live is not None:
                st.registry.gauge('xla.bytes_in_use').set(int(live))
            if peak is not None:
                st.registry.gauge('xla.peak_bytes_in_use').set(int(peak))
            return stats
        _warn_memory_unavailable(
            'no local device reports memory statistics — platform %r'
            % (getattr(devices[0], 'platform', '?') if devices else '?'))
    except Exception as e:  # noqa: BLE001 — observability must not kill
        _warn_memory_unavailable(e)
    return None


_peaks_unknown_warned = False


def _peak_overrides():
    """(flops, hbm_bytes_s) from MXTPU_PEAK_TFLOPS / MXTPU_PEAK_HBM_GBS
    (human units: TFLOP/s, GB/s); 0.0 = no override."""
    from ..config import flags
    try:
        f = float(flags.get('MXTPU_PEAK_TFLOPS')) * 1e12
        b = float(flags.get('MXTPU_PEAK_HBM_GBS')) * 1e9
        return f, b
    except Exception:  # noqa: BLE001 — undeclared in stripped builds
        return 0.0, 0.0


def _warn_peaks_unknown(kind):
    """An unknown device kind must not SILENTLY lose MFU and the
    roofline: warn once per process and publish roofline.peaks_unknown
    so the gap is visible in /metrics and the summary."""
    global _peaks_unknown_warned
    st = _state()
    if st.active:
        st.registry.gauge('roofline.peaks_unknown').set(1)
    if _peaks_unknown_warned:
        logging.debug('telemetry: no peak table entry for device kind %r',
                      kind)
        return
    _peaks_unknown_warned = True
    logging.warning(
        'telemetry: device kind %r has no peak table entry — the MFU '
        'estimate and the roofline achieved-vs-peak placement are '
        'skipped for this run (roofline.peaks_unknown=1). Set '
        'MXTPU_PEAK_TFLOPS / MXTPU_PEAK_HBM_GBS to this chip\'s peak '
        'dense bf16 TFLOP/s and HBM GB/s to restore them.', kind)


def device_peaks(device=None, warn=True):
    """The roofline denominators for ``device`` (default: devices()[0])
    as a dict: ``flops`` (peak dense bf16 FLOP/s), ``hbm_bytes_s``
    (peak HBM bytes/s), ``kind``, and per-component
    ``flops_source``/``hbm_source`` — 'table' (a known chip),
    'override' (MXTPU_PEAK_TFLOPS/MXTPU_PEAK_HBM_GBS), 'none' (a host
    CPU: zero, and no share of a peak is computed), or 'unknown' (no
    entry: zero, warned once, ``roofline.peaks_unknown`` published; for
    a device whose platform is 'tpu' that is an MXNetError). ``source`` is the combined
    label ('a+b' when the components disagree). ``warn=False``
    suppresses the unknown-kind warn + gauge write — the read-only
    scrape path's contract (a /summary request must not write the
    registry)."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        kind = (getattr(device, 'device_kind', '') or '').lower()
    except Exception:  # noqa: BLE001
        kind = ''
    flops = hbm = 0.0
    flops_src = hbm_src = 'unknown'
    for sub, f, b in _PEAK_TABLE:
        if sub in kind:
            flops, hbm = f, b
            flops_src = hbm_src = 'table'
            break
    on_cpu = getattr(device, 'platform', '') == 'cpu'
    if flops_src == 'unknown' and on_cpu:
        flops_src = hbm_src = 'none'       # by design, not a lookup miss
    # Overrides replace only the component they set — a lone
    # MXTPU_PEAK_HBM_GBS must not promote a nominal/unknown FLOP/s
    # value to trusted-for-MFU status (device_peak_flops keys on the
    # FLOP/s component's source alone).
    ov_f, ov_b = _peak_overrides()
    if ov_f:
        flops, flops_src = ov_f, 'override'
    if ov_b:
        hbm, hbm_src = ov_b, 'override'
    if 'unknown' in (flops_src, hbm_src):
        if getattr(device, 'platform', '') == 'tpu':
            # the chip path: a device that is not in the table is an
            # error, not a default
            from ..base import MXNetError
            raise MXNetError(
                'TPU device kind %r has no entry in telemetry.xla.'
                '_PEAK_TABLE; add its published peaks there (or set '
                'MXTPU_PEAK_TFLOPS and MXTPU_PEAK_HBM_GBS)' % kind)
        if warn:
            _warn_peaks_unknown(kind)
    source = (flops_src if flops_src == hbm_src
              else flops_src + '+' + hbm_src)
    return {'flops': flops, 'hbm_bytes_s': hbm, 'kind': kind,
            'source': source, 'flops_source': flops_src,
            'hbm_source': hbm_src}


def device_peak_flops(device=None):
    """(peak_bf16_flops, device_kind) for the MFU denominator. A host
    CPU reports 0.0 (no MFU there); unknown kinds also report 0.0, after
    the warn-once + ``roofline.peaks_unknown`` publication."""
    p = device_peaks(device)
    if p['flops_source'] in ('table', 'override'):
        return p['flops'], p['kind']
    return 0.0, p['kind']


def _reset_peaks_warned_for_tests():
    global _peaks_unknown_warned
    _peaks_unknown_warned = False
