"""Device context — TPU-native analog of MXNet's Context.

Reference: python/mxnet/context.py (Context, mx.cpu()/mx.gpu(), current_context)
and include/mxnet/base.h (Context struct, dev_type/dev_id).

Design: a Context names a JAX device, and it names exactly the device that
was asked for or raises :class:`MXNetError`. It never wraps an index around and
never gives another platform's device in place of the one named:

- ``cpu(i)`` is the i-th host CPU device (with
  ``--xla_force_host_platform_device_count=N`` this gives the multi-device-
  without-a-cluster testing story the reference got from ``mx.cpu(1..n)``,
  tests/python/unittest/test_multi_device_exec.py).
- ``tpu(i)`` is the i-th TPU chip. ``gpu(i)`` is accepted for API
  compatibility with reference scripts and names the same chip.
- THE CPU MESH (the one exception, and an explicit one): when the caller has
  pinned JAX's platform list to exactly ``cpu`` (``JAX_PLATFORMS=cpu`` or
  ``jax.config.update('jax_platforms', 'cpu')``, as tests/conftest.py does),
  ``tpu(i)``/``gpu(i)`` name the i-th virtual CPU device, so that code written
  for chips can be rehearsed without one. A chip that is merely missing is
  not that mode: without the pin, ``tpu(0)`` on a host with no chip raises.
"""
import threading

import jax

from .base import MXNetError

__all__ = ['Context', 'cpu', 'gpu', 'tpu', 'cpu_pinned', 'current_context', 'num_gpus', 'num_tpus']

_thread_local = threading.local()


class Context:
    """Execution device. Immutable, hashable, usable as a `with` scope."""

    devtype2str = {1: 'cpu', 2: 'gpu', 3: 'cpu_pinned', 4: 'tpu'}
    devstr2type = {'cpu': 1, 'gpu': 2, 'cpu_pinned': 3, 'tpu': 4}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if isinstance(device_type, str):
                device_type = self.devstr2type[device_type]
            self.device_typeid = device_type
            self.device_id = device_id
        self._jax_device = None

    def __getstate__(self):
        # the cached jax Device is process-local and unpicklable
        return {'device_typeid': self.device_typeid,
                'device_id': self.device_id}

    def __setstate__(self, state):
        self.device_typeid = state['device_typeid']
        self.device_id = state['device_id']
        self._jax_device = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __repr__(self):
        return '%s(%d)' % (self.device_type, self.device_id)

    def __enter__(self):
        if not hasattr(_thread_local, 'stack'):
            _thread_local.stack = []
        _thread_local.stack.append(self)
        return self

    def __exit__(self, exc_type, exc_value, traceback):
        _thread_local.stack.pop()

    # -- JAX mapping ------------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device (cached)."""
        if self._jax_device is None:
            self._jax_device = _resolve_device(self.device_type, self.device_id)
        return self._jax_device

    def empty_cache(self):
        """MXNet API compat (GPU mem pool flush). No-op: XLA owns HBM."""


def _platform_devices(platform):
    try:
        return jax.devices(platform)
    except RuntimeError:
        return []


def cpu_mesh_mode():
    """True when the caller pinned JAX's platform list to exactly ``cpu``:
    the explicit rehearsal mode in which accelerator contexts name virtual
    CPU devices (see the module docstring; this is the one place that
    decides it)."""
    return (jax.config.jax_platforms or '').strip().lower() == 'cpu'


def _resolve_device(device_type, device_id):
    if device_type in ('cpu', 'cpu_pinned') or cpu_mesh_mode():
        platform = 'cpu'
    else:
        platform = 'tpu'
    devs = _platform_devices(platform)
    if not devs:
        raise MXNetError(
            '%s(%d): no %s device is visible to JAX (platforms: %r)'
            % (device_type, device_id, platform,
               jax.config.jax_platforms or 'default'))
    if not 0 <= device_id < len(devs):
        raise MXNetError(
            '%s(%d): only %d %s device(s) here'
            % (device_type, device_id, len(devs), platform))
    return devs[device_id]


def cpu(device_id=0):
    return Context('cpu', device_id)


def cpu_pinned(device_id=0):
    return Context('cpu_pinned', device_id)


def gpu(device_id=0):
    """Compatibility alias for :func:`tpu` (reference scripts say gpu)."""
    return Context('gpu', device_id)


def tpu(device_id=0):
    return Context('tpu', device_id)


def num_gpus():
    return num_tpus()


def num_tpus():
    return len(_platform_devices('tpu'))


def current_context():
    if getattr(_thread_local, 'stack', None):
        return _thread_local.stack[-1]
    return Context('cpu', 0)
