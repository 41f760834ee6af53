"""Supervised training: fit() under a restart-from-last-good loop.

``resilient_fit(module, train_data, ...)`` runs :meth:`BaseModule.fit`
and, when the run dies of something survivable — a
:class:`~mxnet_tpu.telemetry.health.TrainingHealthError` raised by the
in-graph sentinels (MXTPU_HEALTH_ACTION=raise), an injected or real
dispatch failure, a backend/runtime error — it certifies the
checkpointer's pending saves against the failure diagnostic, applies
escalating backoff, and re-enters fit(), which restores from the
last-good checkpoint and resumes mid-epoch (module/checkpointing.py:
parameters, optimizer state, RNG streams and the data-iterator cursor
all come back, so a clean replay reaches the same final state an
uninterrupted run would). Every restart is recorded as a ``restart``
JSONL record and counted under ``health.restarts``.

Budget: ``MXTPU_RESTART_MAX`` attempts with
``MXTPU_RESTART_BACKOFF * 2^(k-1)`` seconds between them (capped at
60s); a failure past the budget — or one that is not retryable
(assertion errors, keyboard interrupt, shape/user errors) — re-raises
unchanged.

For whole-process supervision (host loss, wedged backends that take
the process down) see ``tools/train_supervisor.py``, which wraps any
training command in the same restart-and-resume loop from the outside.
"""
import logging
import time

from .. import telemetry as _tele
from ..faults import FaultInjected
from ..telemetry.health import TrainingHealthError

__all__ = ['resilient_fit', 'is_retryable']

_BACKOFF_CAP_S = 60.0

# error families worth a restore-and-retry: health incidents, injected
# faults, runtime/backend failures (XlaRuntimeError subclasses
# RuntimeError), lost connections (the coordination service, a
# remote filesystem). User/shape
# errors (ValueError/TypeError/AssertionError) re-raise immediately.
_RETRYABLE = (TrainingHealthError, FaultInjected, RuntimeError,
              ConnectionError, TimeoutError, OSError)
_FATAL = (KeyboardInterrupt, SystemExit, MemoryError)


def is_retryable(exc):
    if isinstance(exc, _FATAL):
        return False
    return isinstance(exc, _RETRYABLE)


def _budget():
    from ..config import flags
    flags.reload('MXTPU_RESTART_MAX')
    flags.reload('MXTPU_RESTART_BACKOFF')
    return flags.get('MXTPU_RESTART_MAX'), flags.get('MXTPU_RESTART_BACKOFF')


def resilient_fit(module, train_data, restart_max=None,
                  restart_backoff=None, logger=logging, **fit_kwargs):
    """Run ``module.fit(train_data, **fit_kwargs)`` under supervision.

    Returns the number of restarts it took (0 = clean first run).
    Checkpoint cadence/restore come from the MXTPU_CKPT_* flags — with
    them unset this still retries, but every retry starts from epoch 0
    (nothing to restore), which is only sane for transient backend
    errors."""
    max_restarts, backoff = _budget()
    if restart_max is not None:
        max_restarts = int(restart_max)
    if restart_backoff is not None:
        backoff = float(restart_backoff)
    attempts = 0
    while True:
        try:
            module.fit(train_data, **fit_kwargs)
            return attempts
        except Exception as e:  # noqa: BLE001 — filtered right below
            if not is_retryable(e) or attempts >= max_restarts:
                raise
            attempts += 1
            diag = dict(getattr(e, 'diagnostic', None) or {})
            ckpt = module.__dict__.get('_mxtpu_ckpt')
            restore_from = None
            if ckpt is not None:
                # drain the async writer and certify pending saves
                # against the incident before the next attempt reads
                # the last-good pointer
                try:
                    ckpt.handle_failure(diag)
                except Exception:  # noqa: BLE001 — never mask the retry
                    pass
                restore_from = ckpt.last_good
                # restart rework: every step between the restore point
                # and where the crashed attempt had reached will be
                # re-trained — badput the goodput ledger must attribute
                reached = int(getattr(ckpt, 'global_step', 0) or 0)
                _tele.goodput.note_rework(
                    reached - int(restore_from or 0))
            _tele.health.note_restart(
                attempt=attempts, reason=type(e).__name__,
                message=str(e)[:200], restore_step=restore_from,
                diagnostic=diag or None)
            delay = min(_BACKOFF_CAP_S, backoff * (2.0 ** (attempts - 1)))
            logger.warning(
                'resilient_fit: attempt %d/%d failed (%s: %s) — '
                'restoring from %s and retrying in %.1fs',
                attempts, max_restarts, type(e).__name__,
                str(e)[:200],
                'step %s' % restore_from if restore_from is not None
                else 'scratch (no certified checkpoint)', delay)
            if delay:
                time.sleep(delay)
            # the crashed attempt leaves the iterator mid-epoch; the
            # next fit() must draw epoch data from the top so the
            # skip-to-step lands on the right batches
            try:
                train_data.reset()
            except Exception:  # noqa: BLE001
                pass
