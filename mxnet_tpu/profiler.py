"""Profiler — Chrome-trace / TensorBoard profiling control.

Reference: python/mxnet/profiler.py (108 LoC: profiler_set_config/
set_state/dump_profile) over src/engine/profiler.{h,cc} which emitted
Chrome trace-event JSON.

TPU-native: delegates to the JAX/XLA profiler (jax.profiler), which captures
device traces viewable in TensorBoard/Perfetto — same role, richer data.
A lightweight host-side op-timeline (chrome trace JSON) is kept for parity
with the reference's output format.
"""
import atexit
import json
import os
import threading
import time

import jax

__all__ = ['profiler_set_config', 'profiler_set_state', 'dump_profile',
           'Profiler', 'note_step']

_state = {'mode': 'symbolic', 'filename': 'profile.json', 'running': False,
          'events': [], 'jax_dir': None, 'ran': False, 'dumped': False}
_written = set()   # profile paths THIS process wrote (merge on re-dump)
_lock = threading.Lock()


def _xla_trace_allowed():
    """Whether to attach jax.profiler alongside the host-span trace:
    yes, on the chip as on the CPU, unless MXTPU_PROFILER_XLA_TRACE=0."""
    from .config import flags
    return flags.get('MXTPU_PROFILER_XLA_TRACE')


def _atexit_dump():
    """Reference initialize.cc:57-67 — the profile is written at process
    exit even when the script never calls dump_profile (the example
    scripts rely on this). Events recorded AFTER a mid-run user dump are
    flushed too: dump_profile merges into a file this process already
    wrote, so a periodic-dump pattern loses nothing and an
    already-complete dump is simply rewritten unchanged."""
    if _state['running']:
        try:
            # jax.profiler.stop_trace can raise during interpreter
            # shutdown; an atexit hook must not turn a successful run
            # into a nonzero exit
            profiler_set_state('stop')
        except Exception:
            pass
    if _state['ran'] and (_state['events'] or not _state['dumped']):
        try:
            dump_profile()
        except Exception:
            pass


# -- MXTPU_XPROF: step-windowed jax.profiler capture -------------------------
#
# MXTPU_XPROF=start:stop arms a one-shot device-trace capture over a
# window of TRAINING STEPS: the trace starts once `start` steps have
# completed and stops once `stop` have, landing a TensorBoard/Perfetto
# trace in MXTPU_XPROF_DIR without bracketing code by hand — steady-state
# windows (past warmup/compile) are exactly what a perf investigation
# wants. The fit loops report progress via note_step(); the fused paths
# advance a whole window at a time, so boundaries quantize to window
# multiples there. MXTPU_PROFILER_XLA_TRACE=0 turns the capture off along
# with the chrome-trace profiler's device trace (_xla_trace_allowed).

_xprof = 'unset'   # 'unset' -> parsed lazily on first note_step; None = off


def _xprof_parse():
    from .config import flags
    try:
        raw = flags.get('MXTPU_XPROF')
    except Exception:  # noqa: BLE001 — undeclared in stripped builds
        raw = ''
    if not raw:
        return None
    try:
        a, b = raw.split(':', 1)
        start, stop = int(a), int(b)
        if start < 0 or stop <= start:
            raise ValueError
    except ValueError:
        import logging
        logging.warning("MXTPU_XPROF=%r ignored — expected 'start:stop' "
                        'with stop > start >= 0', raw)
        return None
    try:
        trace_dir = flags.get('MXTPU_XPROF_DIR')
    except Exception:  # noqa: BLE001
        trace_dir = ''
    return {'start': start, 'stop': stop,
            'dir': os.path.expanduser(trace_dir or 'xprof_trace'),
            'steps': 0, 'on': False}


def _xprof_atexit():
    """Never leave a device trace running past interpreter teardown."""
    w = _xprof
    if isinstance(w, dict) and w['on']:
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            pass
        w['on'] = False


def note_step(n=1):
    """Advance the training-step count for the MXTPU_XPROF capture
    window (the fit loops call this; n = steps completed by the call).
    Free when the flag is unset: one global load + None check."""
    global _xprof
    w = _xprof
    if w is None:
        return
    if w == 'unset':
        w = _xprof = _xprof_parse()
        if w is None:
            return
    w['steps'] += n
    was_on = w['on']
    if not w['on'] and w['steps'] >= w['start']:
        import logging
        if not _xla_trace_allowed():
            logging.warning(
                'MXTPU_XPROF: device trace suppressed on this backend '
                '(MXTPU_PROFILER_XLA_TRACE guard) — no capture')
            _xprof = None
            return
        try:
            jax.profiler.start_trace(w['dir'])
            w['on'] = True
            atexit.register(_xprof_atexit)
            logging.info('MXTPU_XPROF: device trace started at step %d '
                         '-> %s', w['steps'], w['dir'])
        except Exception as e:  # noqa: BLE001 — a capture failure must
            logging.warning('MXTPU_XPROF: start_trace failed: %s', e)
            _xprof = None       # not kill training
            return
    # stop only on a call AFTER the one that started the trace: when a
    # fused window jumps past both boundaries at once, the capture
    # still spans one full window instead of closing empty
    if was_on and w['steps'] >= w['stop']:
        import logging
        try:
            jax.profiler.stop_trace()
            logging.info('MXTPU_XPROF: device trace stopped at step %d '
                         '(window %d:%d) — open %s in TensorBoard/'
                         'Perfetto', w['steps'], w['start'], w['stop'],
                         w['dir'])
        except Exception as e:  # noqa: BLE001
            logging.warning('MXTPU_XPROF: stop_trace failed: %s', e)
        w['on'] = False
        _xprof = None           # one-shot: further steps cost one check


def _xprof_reset_for_tests():
    global _xprof
    if isinstance(_xprof, dict) and _xprof['on']:
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            pass
    _xprof = 'unset'


def profiler_set_config(mode='symbolic', filename='profile.json'):
    """Reference profiler.py:25. mode: 'symbolic' or 'all'."""
    _state['mode'] = mode
    _state['filename'] = filename


def profiler_set_state(state='stop'):
    """Reference profiler.py:42. state: 'run' or 'stop'."""
    from . import _native
    lib = _native.get_lib()
    if lib is not None:  # native engine-op spans (src/profiler.cc)
        lib.MXTProfilerSetState(1 if state == 'run' else 0)
    with _lock:
        if state == 'run' and not _state['running']:
            _state['running'] = True
            if not _state['ran']:
                _state['ran'] = True
                atexit.register(_atexit_dump)
            _state['dumped'] = False
            _state['events'] = []
            _state['start'] = time.time()
            _state['jax_dir'] = None
            if _xla_trace_allowed():
                jax_dir = os.path.splitext(_state['filename'])[0] + '_xla'
                try:
                    jax.profiler.start_trace(jax_dir)
                    _state['jax_dir'] = jax_dir
                except Exception:
                    _state['jax_dir'] = None
        elif state == 'stop' and _state['running']:
            _state['running'] = False
            if _state['jax_dir']:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass


def record_event(name, start_us, end_us, category='operator'):
    """Host-side event hook (engine profiler OprExecStat analog).
    Thread-safe: prefetch iterators invoke ops off the main thread."""
    if _state['running']:
        ev = {'name': name, 'cat': category, 'ph': 'X',
              'ts': start_us, 'dur': end_us - start_us,
              'pid': os.getpid(), 'tid': threading.get_ident()}
        with _lock:
            _state['events'].append(ev)


def is_running():
    """Fast gate for callers that would otherwise pay timing overhead."""
    return _state['running']


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        pass


_NULL_SPAN = _NullSpan()


def maybe_span(name, category='operator'):
    """span(...) when profiling is on, a shared no-op otherwise — the
    one-liner gate for hot call sites (eager invoke, executor fwd/bwd)."""
    return span(name, category) if _state['running'] else _NULL_SPAN


class span:
    """Time a host-side region into the trace (executor fwd/bwd, eager
    invokes). Events are dispatch-side spans — inside a fused XLA step
    the per-op schedule belongs to the XLA trace, not this one."""

    __slots__ = ('name', 'cat', 't0')

    def __init__(self, name, category='operator'):
        self.name = name
        self.cat = category

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        if _state['running']:
            t1 = time.time()
            record_event(self.name, int(self.t0 * 1e6), int(t1 * 1e6),
                         self.cat)


def dump_profile():
    """Reference profiler.py:57 — writes Chrome trace-event JSON (python
    events merged with the native engine's op spans)."""
    # drain python events (the native dump below also drains its buffer,
    # so repeated dumps are symmetric: each event appears exactly once)
    with _lock:
        events = list(_state['events'])
        _state['events'] = []
    from . import _native
    lib = _native.get_lib()
    if lib is not None:
        import tempfile
        with tempfile.NamedTemporaryFile('r', suffix='.json',
                                         delete=False) as tmp:
            path = tmp.name
        try:
            if lib.MXTProfilerDump(path.encode()) == 0:
                with open(path) as f:
                    events.extend(json.load(f).get('traceEvents', []))
        finally:
            os.unlink(path)
    path = _state['filename']
    if path in _written and os.path.exists(path):
        # repeated dumps in one process accumulate (each drain appears
        # exactly once): merge with what this process wrote before
        try:
            with open(path) as f:
                events = json.load(f).get('traceEvents', []) + events
        except Exception:
            pass
    with open(path, 'w') as f:
        json.dump({'traceEvents': events, 'displayTimeUnit': 'ms'}, f)
    _written.add(path)
    _state['dumped'] = True


class Profiler:
    """Context manager convenience (TPU-native extension)."""

    def __init__(self, mode='all', filename='profile.json'):
        profiler_set_config(mode, filename)

    def __enter__(self):
        profiler_set_state('run')
        return self

    def __exit__(self, *args):
        profiler_set_state('stop')
        dump_profile()
