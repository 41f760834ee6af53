"""Deterministic fault injection for the resilience test matrix.

``MXTPU_FAULT_INJECT=<kind>:<step>[:<arg>]`` arms ONE fault that fires
at a deterministic training step, so every recovery path in the
resilient training stack (module/checkpointing.py restore-from-last-
good, module/resilient_fit.py restart loop, tools/train_supervisor.py)
is exercised by real failures instead of mocks. Kinds:

- ``nan-grad:<k>``       — poison the k-th drawn training batch with a
  NaN (host-side, before upload), so step k computes non-finite
  gradients: the health sentinels detect it at the exact step, the
  bisect names the input, and MXTPU_HEALTH_ACTION=raise turns it into
  the TrainingHealthError the restart driver recovers from. Fires once.
- ``checkpoint-corrupt:<k>`` — scribble over the data files of the
  first checkpoint saved at step >= k AFTER it commits, so a later
  restore of that step fails and the restore path must fall back to an
  older checkpoint. Fires once.
- ``dispatch-exception:<k>[:<seam>]`` — raise :class:`FaultInjected`
  from a dispatch seam (the fused-fit window dispatch, the executor's
  fused fwd+bwd, or the kvstore push) when the training-step counter
  reaches k. ``seam`` restricts which seam fires ('dispatch',
  'executor', 'kvstore'; default: whichever reaches the step first).
  Fires once.
- ``slow-host:<k>[:<ms>]`` — sleep ``ms`` (default 50) per training
  step from step k on, persistently: this host becomes the straggler
  the cluster telemetry names. Never disarms.
- ``mem-hog:<k>[:<mb>]`` — allocate and retain ``mb`` MiB (default 8)
  of device memory per training step from step k on, persistently:
  deterministic host-side allocation growth (a leak's shape) at the
  same step-counter seam slow-host uses. The MXTPU_MEMORY forecaster
  is what should notice — steps-to-OOM shrinking, /healthz flipping to
  ``mem_pressure``, the flight recorder dumped — before the allocator
  dies. Never disarms; the compiled programs are untouched.
- ``clock-skew:<k>[:<ms>]`` — shift this host's wall clock BY MS as
  the timeline plane samples it (telemetry/timeline.py's
  ``note_sync_exit``), from step k on, persistently: injected clock
  drift with zero effect on the training math, the schedule or the
  real clocks. The MXTPU_TIMELINE offset estimator is what should
  notice — ``cluster.h<i>.clock_offset_ms`` naming this host's offset
  while the merged Perfetto trace stays aligned. Never disarms.
- ``hang:<k>[:<secs>]`` — wedge the first dispatch seam that reaches
  step k by sleeping ``secs`` (default 3600) in place: the shape of a
  collective waiting on a dead peer or a dispatch that never
  returns. The hang watchdog (telemetry/watchdog.py) is what should
  notice; with MXTPU_WATCHDOG_ACTION=abort the process dies with the
  distinct exit code and the supervisor relaunches. Fires once.
- ``host-loss:<k>`` — ``os._exit`` (exit code 113) from the first
  dispatch seam that reaches step k: the process vanishes mid-window
  with no unwind, no atexit, no final checkpoint — exactly what losing
  a host looks like to the supervisor. Fires once (per process; a
  relaunch re-arms unless the driver disarms the env).

Gang scoping: ``MXTPU_FAULT_HOST=<i>`` restricts an armed fault to ONE
host of a multi-process job (matched against this process's
``MXTPU_HOST_ID``). The launcher env rides into every worker of a gang,
so without the guard a ``host-loss:<k>`` would kill EVERY worker at
step k — the chaos tests need to lose exactly one. Unset (default) =
the fault arms wherever the env reaches.

Off (the default, flag empty) every seam is one cached-bool check —
the same zero-overhead contract the telemetry stack keeps. Nothing
here is ever traced into a compiled program: injection happens at
host-side seams (batch draw, dispatch call, checkpoint commit), so the
lowered XLA programs are byte-identical with the harness armed or not.
"""
import logging
import os
import threading
import time

import numpy as np

__all__ = ['FaultInjected', 'HOST_LOSS_EXIT_CODE', 'enabled', 'spec',
           'note_steps', 'clock_skew_ms', 'maybe_poison_snap',
           'maybe_poison_batch', 'maybe_raise',
           'maybe_corrupt_checkpoint']

KINDS = ('nan-grad', 'checkpoint-corrupt', 'dispatch-exception',
         'slow-host', 'hang', 'host-loss',
         'mem-hog', 'clock-skew')

_SLOW_DEFAULT_MS = 50.0
_HOG_DEFAULT_MB = 8.0
_SKEW_DEFAULT_MS = 100.0
_hog = []   # mem-hog's retained device allocations (the leak itself)
_HANG_DEFAULT_SECS = 3600.0
HOST_LOSS_EXIT_CODE = 113   # distinct from the watchdog's 85


class FaultInjected(RuntimeError):
    """Raised by an armed ``dispatch-exception`` fault; carries the
    seam and step for the restart driver's restart record."""

    def __init__(self, message, seam=None, step=None):
        super().__init__(message)
        self.seam = seam
        self.step = step


class _FState:
    __slots__ = ('decided', 'active', 'kind', 'step', 'arg', 'drawn',
                 'steps', 'fired', 'lock')

    def __init__(self):
        self.decided = False
        self.active = False
        self.kind = None
        self.step = 0
        self.arg = None
        self.drawn = 0      # training batches drawn so far (draw order
        self.steps = 0      # == step order in every fit loop)
        self.fired = False
        self.lock = threading.Lock()


_state = _FState()
_decide_lock = threading.Lock()


def _parse(raw):
    """'<kind>:<step>[:<arg>]' -> (kind, step, arg) or None."""
    parts = raw.split(':')
    if len(parts) < 2 or parts[0] not in KINDS:
        raise ValueError(
            'MXTPU_FAULT_INJECT=%r: expected <kind>:<step>[:<arg>] with '
            'kind one of %s' % (raw, list(KINDS)))
    return parts[0], int(parts[1]), (parts[2] if len(parts) > 2 else None)


def _host_guard():
    """(fault_host, my_host): the MXTPU_FAULT_HOST restriction and this
    process's MXTPU_HOST_ID rank. fault_host None = unrestricted."""
    try:
        from .config import flags
        flags.reload('MXTPU_FAULT_HOST')
        flags.reload('MXTPU_HOST_ID')
        fault_host = flags.get('MXTPU_FAULT_HOST')
        my_host = flags.get('MXTPU_HOST_ID')
    except Exception:  # noqa: BLE001 — stripped builds without the flags
        try:
            fault_host = int(os.environ.get('MXTPU_FAULT_HOST', '-1'))
            my_host = int(os.environ.get('MXTPU_HOST_ID', '0'))
        except ValueError:
            return None, 0
    return (None if fault_host is None or fault_host < 0 else
            int(fault_host)), int(my_host)


def _decide():
    with _decide_lock:
        if _state.decided:
            return _state.active
        raw = ''
        try:
            from .config import flags
            flags.reload('MXTPU_FAULT_INJECT')
            raw = flags.get('MXTPU_FAULT_INJECT') or ''
        except Exception:  # noqa: BLE001 — stripped builds without the flag
            raw = os.environ.get('MXTPU_FAULT_INJECT', '')
        raw = raw.strip()
        if raw:
            try:
                kind, step, arg = _parse(raw)
                fault_host, my_host = _host_guard()
                if fault_host is not None and fault_host != my_host:
                    # another gang member's fault: the launcher env
                    # reaches every worker, but only host <fault_host>
                    # arms — this process runs clean (and says so once,
                    # or a one-worker kill would look like magic)
                    logging.info(
                        'fault injection: %s armed for host %d only — '
                        'this is host %d, fault inert', kind, fault_host,
                        my_host)
                else:
                    _state.kind, _state.step, _state.arg = kind, step, arg
                    _state.active = True
                    logging.warning(
                        'fault injection armed: %s at step %d%s%s',
                        kind, step, ' (%s)' % arg if arg else '',
                        ' [host %d]' % my_host
                        if fault_host is not None else '')
            except ValueError as e:
                logging.warning('%s — fault injection disabled', e)
        _state.decided = True
    return _state.active


def enabled():
    """Whether a fault is armed (decided once from MXTPU_FAULT_INJECT).
    One attribute check after the first call — the seams' gate."""
    if _state.decided:
        return _state.active
    return _decide()


def spec():
    """(kind, step, arg) of the armed fault, or None."""
    if not enabled():
        return None
    return _state.kind, _state.step, _state.arg


def note_steps(n=1):
    """Advance the trained-step counter (fed by the fit loops at the
    same sites that count fit.steps). An armed ``slow-host`` fault
    sleeps here once the counter passes its step; an armed ``mem-hog``
    allocates-and-retains here — both persist, never disarm."""
    if not enabled():
        return
    with _state.lock:
        _state.steps += n
        slow = (_state.kind == 'slow-host' and _state.steps > _state.step)
        hog = (_state.kind == 'mem-hog' and _state.steps > _state.step)
    if slow:
        try:
            ms = float(_state.arg) if _state.arg else _SLOW_DEFAULT_MS
        except ValueError:
            ms = _SLOW_DEFAULT_MS
        time.sleep(n * ms / 1e3)
    if hog:
        try:
            mb = float(_state.arg) if _state.arg else _HOG_DEFAULT_MB
        except ValueError:
            mb = _HOG_DEFAULT_MB
        try:
            import jax.numpy as jnp
            # n steps' worth of leak, committed to the device so the
            # allocator's bytes_in_use actually climbs (block_until_
            # ready: a never-dispatched lazy array leaks nothing)
            arr = jnp.zeros((max(1, int(n * mb * 2**20 / 4)),),
                            jnp.float32)
            _hog.append(arr.block_until_ready())
        except Exception as e:  # noqa: BLE001 — a chaos harness must
            logging.warning(                   # not crash the run itself
                'fault injection: mem-hog allocation failed: %s', e)


def clock_skew_ms():
    """The wall-clock shift (ms) an armed ``clock-skew`` fault applies
    to this host's timeline clock samples — 0.0 unarmed / before the
    armed step. ``>=`` so ``clock-skew:0`` skews from the very first
    sync round (the trained-step counter may still be 0 then); like
    slow-host/mem-hog it persists and never disarms."""
    if not enabled():
        return 0.0
    with _state.lock:
        hit = (_state.kind == 'clock-skew' and _state.steps >= _state.step)
        arg = _state.arg
    if not hit:
        return 0.0
    try:
        return float(arg) if arg else _SKEW_DEFAULT_MS
    except ValueError:
        return _SKEW_DEFAULT_MS


def _poison(arr):
    """One NaN planted at the origin of a float array (jax or numpy);
    non-float arrays come back unchanged."""
    import jax.numpy as jnp
    idx = tuple(0 for _ in arr.shape)
    if isinstance(arr, np.ndarray):
        if arr.dtype.kind != 'f':
            return arr, False
        out = arr.copy()
        out[idx] = np.nan
        return out, True
    if jnp.issubdtype(arr.dtype, jnp.floating):
        return arr.at[idx].set(jnp.nan), True
    return arr, False


def _poison_arrays(datas, labels):
    """Poison the first float array among datas then labels (defer-mode
    uint8 batches fall through to the label). Returns (datas, labels,
    poisoned_any)."""
    datas = list(datas)
    for i, a in enumerate(datas):
        out, ok = _poison(a)
        if ok:
            datas[i] = out
            return tuple(datas), tuple(labels), True
    labels = list(labels)
    for i, a in enumerate(labels):
        out, ok = _poison(a)
        if ok:
            labels[i] = out
            return tuple(datas), tuple(labels), True
    return tuple(datas), tuple(labels), False


def _armed_draw():
    """True when THIS draw is the poisoned one (advances the counter)."""
    with _state.lock:
        hit = (_state.kind == 'nan-grad' and not _state.fired
               and _state.drawn == _state.step)
        _state.drawn += 1
        if hit:
            _state.fired = True
    return hit


def _note_poison(hit):
    if hit:
        logging.warning('fault injection: nan-grad fired on batch %d',
                        _state.step)
    else:
        # the armed draw is consumed either way (firing at a LATER step
        # than requested would be worse) — but dropping the fault
        # silently would make a hung chaos test undebuggable
        logging.warning(
            'fault injection: nan-grad armed for batch %d but the batch '
            'holds no float array (defer-mode uint8 data, int labels?) '
            '— fault NOT injected', _state.step)


def maybe_poison_snap(snap):
    """Fused-loop draw seam: one (data_arrays, label_arrays, pad, index)
    draw-time snapshot in, possibly NaN-poisoned out. Counts every
    drawn training batch so the armed step is a global batch index."""
    if not _armed_draw():
        return snap
    ds, ls, pad, idx = snap
    ds, ls, hit = _poison_arrays(ds, ls)
    _note_poison(hit)
    return ds, ls, pad, idx


def maybe_poison_batch(batch):
    """Per-batch-loop draw seam: poison a DataBatch's NDArrays in place
    (same counter as :func:`maybe_poison_snap`)."""
    if not _armed_draw():
        return batch
    ds = tuple(a._data for a in batch.data)
    ls = tuple(a._data for a in (batch.label or ()))
    ds, ls, hit = _poison_arrays(ds, ls)
    if hit:
        for a, v in zip(batch.data, ds):
            a._data = v
        for a, v in zip(batch.label or (), ls):
            a._data = v
    _note_poison(hit)
    return batch


def maybe_raise(seam, upcoming=1):
    """Dispatch seam: fire an armed ``dispatch-exception`` (raise
    :class:`FaultInjected`), ``hang`` (sleep in place — the wedged-
    collective shape the watchdog must catch) or ``host-loss``
    (``os._exit``, no unwind) fault when its step falls inside the
    ``upcoming`` steps this dispatch is about to advance (the fused
    window passes its window size). For ``dispatch-exception``,
    ``arg`` (when set) restricts the firing seam."""
    if not enabled():
        return
    with _state.lock:
        kind = _state.kind
        if (kind not in ('dispatch-exception', 'hang', 'host-loss')
                or _state.fired
                or _state.steps + upcoming <= _state.step):
            return
        if kind == 'dispatch-exception' and _state.arg \
                and _state.arg != seam:
            return
        _state.fired = True
        step = _state.step
        arg = _state.arg
    if kind == 'hang':
        try:
            secs = float(arg) if arg else _HANG_DEFAULT_SECS
        except ValueError:
            secs = _HANG_DEFAULT_SECS
        logging.warning('fault injection: hang fired at the %s seam '
                        '(step %d) — sleeping %.1fs', seam, step, secs)
        time.sleep(secs)
        return
    if kind == 'host-loss':
        logging.warning('fault injection: host-loss fired at the %s seam '
                        '(step %d) — os._exit(%d)', seam, step,
                        HOST_LOSS_EXIT_CODE)
        os._exit(HOST_LOSS_EXIT_CODE)
    raise FaultInjected(
        'injected dispatch failure at the %s seam (step %d)'
        % (seam, step), seam=seam, step=step)


def maybe_corrupt_checkpoint(directory, step):
    """Checkpoint seam (called after a save commits): truncate the
    committed step's data files so a later restore of it fails. Fires
    on the first save at step >= the armed step."""
    if not enabled():
        return False
    with _state.lock:
        hit = (_state.kind == 'checkpoint-corrupt' and not _state.fired
               and int(step) >= _state.step)
        if hit:
            _state.fired = True
    if not hit:
        return False
    n = 0
    for root, _, files in os.walk(os.path.join(str(directory), str(step))):
        for name in files:
            try:
                with open(os.path.join(root, name), 'r+b') as f:
                    f.truncate(2)
                n += 1
            except OSError:
                pass
    logging.warning('fault injection: checkpoint-corrupt fired — '
                    'truncated %d file(s) of step %s in %s',
                    n, step, directory)
    return True


def _reset_for_tests():
    global _state
    _state = _FState()
    _hog.clear()
