"""Multi-host SPMD initialization — XLA collectives over ICI/DCN.

Reference analog: the ps-lite bootstrap (`ps::Postoffice` role/rank
wiring, kvstore.h:257-301) that connects MXNet workers across machines.
The TPU-native transport is NOT a parameter server: every host joins
one jax.distributed job, `jax.devices()` becomes the GLOBAL device
list, and a `Mesh` laid out over it makes pjit/shard_map insert DCN/ICI
collectives automatically (psum replaces push/pull — SURVEY §5.8).

The dist kvstore tier (kvstore_dist.py) remains for reference-API
compatibility; this module is the idiomatic path for new code:

    mx.parallel.init_multihost()              # env-driven, launcher-set
    mesh = mx.parallel.global_mesh({'dp': -1})
    ... pjit/shard_map over mesh ...

`tools/launch.py` exports MXTPU_COORDINATOR / MXTPU_NUM_HOSTS /
MXTPU_HOST_ID for its workers, so the same launcher drives both the PS
tier and this one.
"""
import logging
import time

import numpy as np

__all__ = ['init_multihost', 'global_mesh', 'process_index',
           'process_count', 'local_devices', 'is_multihost',
           'mesh_descriptor', 'is_primary', 'barrier', 'agree_min',
           'agree_any']

_initialized = False
_INIT_ATTEMPTS = 3


def _enable_cpu_collectives():
    """REAL multi-process jobs on the CPU backend need a cross-process
    collectives implementation: without one, the very first jitted
    collective dies with "Multiprocess computations aren't implemented
    on the CPU backend" — which is why every multi-host behavior was
    only ever simulated single-process before the gang tier. Gloo ships
    in jaxlib; selecting it must happen before the CPU client
    initializes (jax.distributed.initialize guarantees we are early
    enough). Non-CPU platforms ignore the setting."""
    import jax
    try:
        current = jax.config.values.get('jax_cpu_collectives_implementation')
    except AttributeError:      # much older jax: nothing to select
        return
    if current in (None, 'none'):
        try:
            jax.config.update('jax_cpu_collectives_implementation', 'gloo')
        except Exception as e:  # noqa: BLE001 — jaxlib without gloo
            logging.warning(
                'multihost: cannot select the gloo CPU collectives '
                'implementation (%s) — CPU multi-process collectives '
                'will fail', e)


def init_multihost(coordinator_address=None, num_processes=None,
                   process_id=None):
    """Join (or create) a jax.distributed job.

    Arguments default from the launcher env protocol:
    ``MXTPU_COORDINATOR`` (host:port), ``MXTPU_NUM_HOSTS``,
    ``MXTPU_HOST_ID``. With one process (or no env), this is a no-op —
    single-host programs need no coordinator. Safe to call twice.

    Transient join failures retry with backoff: a relaunched gang can
    race a dying predecessor for the coordinator port, and workers can
    reach the coordinator before it listens. ``MXTPU_COORD_TIMEOUT``
    bounds each attempt (0 = jax's default, 5 minutes) so a gang
    relaunch against a never-arriving coordinator fails fast enough
    for the supervisor to tear it down and try a fresh port. (One
    failure mode is not recoverable in-process: on jax 0.4.x a
    coordinator whose port is already bound dies in grpc before Python
    can catch anything — tools/gang_supervisor.py treats that unclean
    exit like any other and relaunches the gang on a fresh port.)
    """
    global _initialized
    if _initialized:
        return False
    from ..config import flags
    flags.reload('MXTPU_COORDINATOR')
    flags.reload('MXTPU_NUM_HOSTS')
    flags.reload('MXTPU_HOST_ID')
    flags.reload('MXTPU_COORD_TIMEOUT')
    coordinator_address = coordinator_address or \
        flags.get('MXTPU_COORDINATOR')
    num_processes = num_processes if num_processes is not None else \
        flags.get('MXTPU_NUM_HOSTS')
    process_id = process_id if process_id is not None else \
        flags.get('MXTPU_HOST_ID')
    if num_processes <= 1 or not coordinator_address:
        return False
    import jax
    _enable_cpu_collectives()
    kwargs = {}
    timeout = flags.get('MXTPU_COORD_TIMEOUT')
    if timeout and timeout > 0:
        # jax takes whole seconds; a sub-second operator value must
        # round UP to 1, not truncate to an immediate 0s timeout
        kwargs['initialization_timeout'] = max(1, int(round(timeout)))
    for attempt in range(_INIT_ATTEMPTS):
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id, **kwargs)
            break
        except Exception as e:  # noqa: BLE001 — connect timeout / bind race
            if attempt + 1 >= _INIT_ATTEMPTS:
                raise
            logging.warning(
                'multihost: jax.distributed join attempt %d/%d failed '
                '(%s) — retrying', attempt + 1, _INIT_ATTEMPTS, e)
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 — nothing to tear down
                pass
            time.sleep(0.5 * 2 ** attempt)
    _initialized = True
    # telemetry plane: from here jax.process_index() is authoritative —
    # pin the host stamp (JSONL records, /metrics labels) and announce
    # the job size so cluster aggregation can name every host
    try:
        from .. import telemetry as _tele
        if _tele.enabled():
            _tele.cluster.set_host(jax.process_index())
            _tele.gauge('cluster.process_count').set(int(num_processes))
            _tele.event('multihost.init', host=int(jax.process_index()),
                        num_hosts=int(num_processes),
                        coordinator=coordinator_address)
    except Exception:  # noqa: BLE001 — observability must not block init
        pass
    return True


def process_index():
    import jax
    return jax.process_index()


def process_count():
    import jax
    return jax.process_count()


def local_devices():
    import jax
    return jax.local_devices()


def is_multihost():
    import jax
    return jax.process_count() > 1


def mesh_descriptor():
    """The live process/device set as a plain JSON-able dict —
    recorded into every checkpoint's meta sidecar
    (module/checkpointing.py) so a restore can tell "same mesh, plain
    resume" from "smaller/larger mesh, reshard-on-restore" and remap
    the io shard cursor accordingly. Requires the backend to be up
    (checkpointing only runs after bind, so it always is)."""
    import jax
    return {'devices': int(jax.device_count()),
            'local_devices': int(jax.local_device_count()),
            'processes': int(jax.process_count()),
            'process_index': int(jax.process_index())}


# ---------------------------------------------------------------------------
# cross-host agreement over the jax.distributed coordination service
# ---------------------------------------------------------------------------
#
# The gang checkpoint tier (module/checkpointing.py) must make a few
# small decisions that every host of a job answers IDENTICALLY — "is
# any host's async writer still busy?", "what is the newest step every
# host has committed and health-cleared?" — or the per-host answers
# diverge and an orbax collective save wedges / a relaunched gang
# restores divergent steps. These ride the coordination service's KV
# store + named barrier (NOT device collectives): they are safe from
# any thread, independent of the XLA collective schedule, and every
# wait is bounded — a gang mid-death times out and returns None
# instead of wedging the anti-hang machinery itself.

_AGREE_TIMEOUT_S = 60.0


def _client():
    """The jax.distributed coordination-service client, or None when no
    multi-process job is up (single-process: every agreement is local)."""
    import jax
    if not jax.distributed.is_initialized():
        return None
    # jax 0.9.0 has no public accessor for the coordination client; the
    # experimental multihost_utils module carries the handle it uses
    # itself. (Its own barriers are device collectives with no timeout,
    # which is exactly what these agreements must not be.)
    from jax.experimental import multihost_utils
    return multihost_utils.distributed.global_state.client


def is_primary():
    """Whether this process writes shared-FS artifacts the whole job
    reads (the last_good pointer): process 0, or any single process."""
    if _client() is None:
        return True
    import jax
    return jax.process_index() == 0


def barrier(name, timeout_s=_AGREE_TIMEOUT_S):
    """Named barrier across every process of the job. True once all
    arrived; False on timeout/error (callers keep their safe behavior —
    never advance shared state on False). No-op True single-process."""
    c = _client()
    if c is None:
        return True
    try:
        c.wait_at_barrier('mxtpu_' + str(name), int(timeout_s * 1000))
        return True
    except Exception as e:  # noqa: BLE001 — peer died / timed out
        logging.warning('multihost: barrier %r failed (%s)', name, e)
        return False


def _exchange(name, value, timeout_s):
    """All-hosts value exchange through the coordination KV store:
    every process contributes ``value`` under a ``name``d round, waits
    for the rest, and reads everyone's. Returns the list of int values
    (all processes see the same list) or None on timeout/error.
    ``name`` must be unique per call (callers thread a round counter
    through) — coordination barriers are one-shot."""
    c = _client()
    if c is None:
        return [int(value)]
    import jax
    n = jax.process_count()
    prefix = 'mxtpu_agree/%s/' % name
    try:
        c.key_value_set(prefix + str(jax.process_index()), str(int(value)))
    except Exception as e:  # noqa: BLE001
        logging.warning('multihost: agreement %r failed to publish (%s)',
                        name, e)
        return None
    if not barrier(str(name) + '/gather', timeout_s):
        return None
    # the read phase retries once: it is the one step whose failure is
    # ASYMMETRIC (this host returns None while peers that read fine
    # proceed on the gathered values). The window cannot be closed
    # entirely — two-phase-commit impossibility — only shrunk; callers
    # therefore treat None as the conservative vote (skip the save,
    # freeze the pointer), and the per-step round naming self-heals at
    # the next lockstep point
    items = None
    for attempt in range(2):
        try:
            items = c.key_value_dir_get(prefix)
            break
        except Exception as e:  # noqa: BLE001
            if attempt:
                logging.warning(
                    'multihost: agreement %r failed to read (%s)',
                    name, e)
                return None
            time.sleep(0.2)
    if len(items) != n:
        logging.warning('multihost: agreement %r saw %d/%d contributions',
                        name, len(items), n)
        return None
    vals = []
    try:
        for _key, raw in items:
            vals.append(int(raw))
    except (TypeError, ValueError) as e:
        logging.warning('multihost: agreement %r garbled (%s)', name, e)
        return None
    # second barrier before cleanup: a host still inside dir_get must
    # not race the delete
    if barrier(str(name) + '/done', timeout_s) and jax.process_index() == 0:
        try:
            c.key_value_delete(prefix)
        except Exception:  # noqa: BLE001 — stale keys are harmless
            pass
    return vals


def agree_min(name, value, timeout_s=_AGREE_TIMEOUT_S):
    """The minimum of every host's ``value`` — the cross-host-agreed
    checkpoint step: a step is safe to restore only once EVERY host has
    committed and cleared it. None on timeout/error (no agreement)."""
    vals = _exchange(name, value, timeout_s)
    return min(vals) if vals else None


def agree_any(name, flag, timeout_s=_AGREE_TIMEOUT_S):
    """Whether ``flag`` is true on ANY host — the global busy-writer
    skip: an orbax save is a collective, so either every host of the
    gang initiates it or none does. None on timeout/error."""
    vals = _exchange(name, 1 if flag else 0, timeout_s)
    return any(vals) if vals is not None else None


def global_mesh(axes):
    """Build a Mesh over the GLOBAL device list.

    ``axes``: ordered dict/list of (name, size); one size may be -1
    (inferred). Axis order should put the fastest-varying (ICI-local)
    axis last so DCN only carries the leading axes — the
    how-to-scale-your-model layout rule.
    """
    import jax
    from jax.sharding import Mesh

    if isinstance(axes, dict):
        items = list(axes.items())
    else:
        items = list(axes)
    names = [k for k, _ in items]
    sizes = [v for _, v in items]
    devs = jax.devices()
    n = len(devs)
    if sizes.count(-1) > 1:
        raise ValueError('at most one axis size may be -1')
    known = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if -1 in sizes:
        if n % known:
            raise ValueError('device count %d not divisible by %d'
                             % (n, known))
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError('mesh %r does not cover %d global devices'
                         % (dict(zip(names, sizes)), n))
    return Mesh(np.array(devs).reshape(sizes), tuple(names))
