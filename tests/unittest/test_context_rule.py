"""The device rule of context.py: a context names exactly the device that
was asked for, or raises; and where the persistent compile cache goes
(config.enable_compile_cache)."""
import os
import pickle

import numpy as np
import pytest

import jax
import mxnet_tpu as mx
from mxnet_tpu import config, context
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_cpu_mesh_mode_names_virtual_cpu_devices():
    """tests/conftest.py pins the platform list to cpu: the one explicit
    mode in which accelerator contexts name virtual CPU devices."""
    assert context.cpu_mesh_mode()
    cpus = jax.devices('cpu')
    assert mx.tpu(3).jax_device() is cpus[3]
    assert mx.gpu(1).jax_device() is cpus[1]
    assert mx.cpu(7).jax_device() is cpus[7]
    assert mx.context.num_tpus() == 0 and mx.context.num_gpus() == 0


@pytest.mark.parametrize('value', ['numpy', 'scalar', 'ndarray_elsewhere'])
def test_whole_array_assignment_stays_on_the_arrays_device(value):
    """`arr[:] = v`, the reference idiom for filling a bound argument:
    the array stays on the device its context names. jnp puts an
    uncommitted operand on the process's default device (cpu(0) here,
    the chip beside an mx.cpu() array on the machine that has one)."""
    import numpy as np
    dev = jax.devices('cpu')[3]
    a = mx.nd.zeros((2, 2), ctx=mx.cpu(3))
    a[:] = {'numpy': np.ones((2, 2)), 'scalar': 1.0,
            'ndarray_elsewhere': mx.nd.ones((2, 2), ctx=mx.cpu(5))}[value]
    assert a._data.devices() == {dev} and a.context == mx.cpu(3)
    assert a.asnumpy().sum() == 4


def _assigned(key, value):
    def build(ctx):
        a = mx.nd.zeros((3, 2), ctx=ctx)
        a[key] = value
        return a
    return build


_TWO_BY_THREE = np.arange(6.).reshape(2, 3)
# constructor -> (build(ctx), the values it must hold, their dtype)
_CONSTRUCTORS = {
    'array_numpy': (lambda c: mx.nd.array(_TWO_BY_THREE, ctx=c),
                    _TWO_BY_THREE, 'float32'),
    'array_numpy_view': (lambda c: mx.nd.array(_TWO_BY_THREE.T[1:], ctx=c),
                         _TWO_BY_THREE.T[1:], 'float32'),
    'array_int64': (lambda c: mx.nd.array(np.arange(3), ctx=c),
                    [0, 1, 2], 'int32'),
    'array_uint8': (lambda c: mx.nd.array(np.arange(3, dtype='uint8'), ctx=c),
                    [0, 1, 2], 'uint8'),
    'array_as_bfloat16': (
        lambda c: mx.nd.array(np.ones(3), ctx=c, dtype='bfloat16'),
        [1, 1, 1], 'bfloat16'),
    'array_list': (lambda c: mx.nd.array([[1, 2], [3, 4]], ctx=c),
                   [[1, 2], [3, 4]], 'float32'),
    'array_scalar': (lambda c: mx.nd.array(3, ctx=c), 3, 'float32'),
    'array_empty': (lambda c: mx.nd.array(np.zeros((0, 4)), ctx=c),
                    np.zeros((0, 4)), 'float32'),
    'array_ndarray_elsewhere': (
        lambda c: mx.nd.array(mx.nd.ones((2, 2), ctx=mx.cpu(5)), ctx=c),
        np.ones((2, 2)), 'float32'),
    'zeros': (lambda c: mx.nd.zeros((2, 3), ctx=c), np.zeros((2, 3)),
              'float32'),
    'ones_int32': (lambda c: mx.nd.ones(3, ctx=c, dtype='int32'), [1, 1, 1],
                   'int32'),
    'full': (lambda c: mx.nd.full((2,), 2.5, ctx=c), [2.5, 2.5], 'float32'),
    'full_out': (lambda c: mx.nd.full((2,), 2.5, ctx=c,
                                      out=mx.nd.zeros((2,), ctx=c)),
                 [2.5, 2.5], 'float32'),
    'arange_repeat': (lambda c: mx.nd.arange(0, 3, repeat=2, ctx=c),
                      [0, 0, 1, 1, 2, 2], 'float32'),
    'empty': (lambda c: mx.nd.empty((2, 2), ctx=c), None, 'float32'),
    'assign_all_numpy': (_assigned(slice(None), np.ones((3, 2))),
                         np.ones((3, 2)), 'float32'),
    'assign_all_numpy_broadcast': (_assigned(slice(None), np.array([1, 2])),
                                   [[1, 2]] * 3, 'float32'),
    'assign_all_scalar': (_assigned(slice(None), 2), np.full((3, 2), 2),
                          'float32'),
    'assign_part_numpy': (_assigned(slice(1, None), np.ones((2, 2))),
                          [[0, 0], [1, 1], [1, 1]], 'float32'),
    'assign_part_scalar': (_assigned(1, 2.0), [[0, 0], [2, 2], [0, 0]],
                           'float32'),
    'pickle': (lambda c: pickle.loads(pickle.dumps(
        mx.nd.array(_TWO_BY_THREE, ctx=c))), _TWO_BY_THREE, 'float32'),
    'pickle_bfloat16': (lambda c: pickle.loads(pickle.dumps(
        mx.nd.array([1, 2], ctx=c, dtype='bfloat16'))), [1, 2], 'bfloat16'),
    'row_sparse_to_dense': (
        lambda c: mx.nd.sparse.row_sparse_array(
            (np.ones((1, 2)), [1]), shape=(3, 2), ctx=c).tostype('default'),
        [[0, 0], [1, 1], [0, 0]], 'float32'),
    'csr_to_dense': (
        lambda c: mx.nd.sparse.csr_matrix(
            ([5.], [1], [0, 0, 1]), shape=(2, 2), ctx=c).tostype('default'),
        [[0, 0], [0, 5]], 'float32'),
}


@pytest.mark.parametrize('ctx', [mx.cpu(3), mx.tpu(2)], ids=str)
@pytest.mark.parametrize('constructor', sorted(_CONSTRUCTORS))
def test_building_an_ndarray_touches_only_its_contexts_device(
        constructor, ctx):
    """jnp alone puts what it makes on the process's default device
    (cpu(0) here; the chip on the machine that has one, whatever the
    context says). With transfers between devices forbidden, anything
    built there and moved afterwards raises."""
    build, values, dtype = _CONSTRUCTORS[constructor]
    with jax.transfer_guard_device_to_device('disallow_explicit'):
        a = build(ctx)
    assert a._data.devices() == {ctx.jax_device()} and a._data.committed
    assert a.context == ctx and a.dtype == np.dtype(dtype)
    if values is not None:
        np.testing.assert_array_equal(a.asnumpy(), np.asarray(values))


@pytest.mark.parametrize('source', ['aligned_64KiB', 'unaligned_64MiB'])
def test_array_copies_its_source_at_the_call(source):
    """The reference's _sync_copyfrom: writing to the source afterwards
    does not change the NDArray. The cpu backend alone does not give
    that: it keeps a 64-byte-aligned source as the array's own memory,
    and copies any other behind the call."""
    n = {'aligned_64KiB': 1 << 14, 'unaligned_64MiB': 1 << 24}[source]
    pool = np.zeros(n + 32, np.float32)
    start = -pool.ctypes.data % 64 // 4
    if source.startswith('unaligned'):
        start += 1
    view = pool[start:start + n]
    for fill in (1, 2, 3):
        view[...] = fill
        a = mx.nd.array(view)
        view[...] = 0
        assert (a.asnumpy() == fill).all()


def test_memory_of_a_dead_host_array_is_written_again(monkeypatch):
    """A large host array's memory costs more to touch for the first time
    than to fill: what a dead NDArray held is kept, up to a limit, for the
    next array of its size, and never while anything still reads it."""
    from mxnet_tpu.ndarray import ndarray as nda

    def settle():       # jax lets go of host memory at its next call
        jax.block_until_ready(jax.numpy.zeros(()) + 1)

    src = np.arange((1 << 19) + 3, dtype=np.float32)    # 2 MiB and a bit

    def idle():         # buffers of this test's size alone
        return len(nda._idle_buffers.get(src.nbytes + 64, []))

    monkeypatch.setattr(nda, '_idle_buffers', {})
    a = mx.nd.array(src)
    held = np.asarray(a._data)          # a reader of a's memory
    where = a._data.unsafe_buffer_pointer()
    del a
    settle()
    b = mx.nd.array(src + 1)
    assert idle() == 0 and b._data.unsafe_buffer_pointer() != where
    np.testing.assert_array_equal(held, src)
    del held
    settle()
    assert idle() == 1
    c = mx.nd.array(src + 2, ctx=mx.cpu(3))
    assert idle() == 0 and c._data.unsafe_buffer_pointer() == where
    np.testing.assert_array_equal(c.asnumpy(), src + 2)
    np.testing.assert_array_equal(b.asnumpy(), src + 1)
    # a small array's memory is not kept; at the limit the buffer idle
    # longest goes and the others stay, and what is larger than the limit
    # is never kept and costs the idle ones nothing
    small = mx.nd.array(src[:1000])
    del small, c
    settle()
    assert idle() == 1
    monkeypatch.setattr(nda, '_idle_limit', lambda idle: 5 << 20)
    e, f = mx.nd.array(src + 3), mx.nd.array(src + 4)
    assert idle() == 0
    del b
    settle()
    oldest = nda._idle_buffers[src.nbytes + 64][0][1]
    del e
    settle()
    assert idle() == 2
    del f
    settle()
    kept = [raw for _, raw in nda._idle_buffers[src.nbytes + 64]]
    assert len(kept) == 2 and not any(raw is oldest for raw in kept)
    monkeypatch.setattr(nda, '_idle_limit', lambda idle: 1 << 20)
    d = mx.nd.array(src)
    assert idle() == 1
    del d
    settle()
    assert idle() == 1


def test_the_idle_limit_follows_what_the_machine_has_left():
    """Half of what the machine could hand out if the idle buffers held
    nothing: what they hold is part of that, so holding more does not by
    itself shrink the limit, and it never passes the installed memory."""
    import os
    from mxnet_tpu.ndarray import ndarray as nda
    installed = os.sysconf('SC_PHYS_PAGES') * os.sysconf('SC_PAGE_SIZE')
    empty = nda._idle_limit(0)
    assert 0 < empty <= installed // 2
    # two readings a moment apart, on a machine that other tests share
    assert abs(nda._idle_limit(1 << 30) - (1 << 29) - empty) < installed // 20


def test_recycled_memory_is_never_shared_by_live_arrays():
    """An unpickled array's dtype is an instance of its own, for which
    jax reads the source through a view it makes itself: memory is idle
    when no view of it is left, not when the array it was handed out as
    is gone."""
    from mxnet_tpu.ndarray import ndarray as nda
    made = [mx.nd.array(np.full((1 << 19) + 5, i, np.float32), ctx=mx.cpu(3))
            for i in range(4)]
    for rounds in range(2):     # the second with the first's memory idle
        back = pickle.loads(pickle.dumps(made))
        assert [float(a.asnumpy()[-1]) for a in back] == [0, 1, 2, 3]
        assert len({a._data.unsafe_buffer_pointer() for a in back}) == 4
        del back
        jax.block_until_ready(jax.numpy.zeros(()) + 1)
    assert len(nda._idle_buffers[made[0]._data.nbytes + 64]) >= 4


def test_threads_never_get_memory_that_is_in_use():
    """The idle buffers are shared by every thread and guarded by no lock
    (a finalizer may run inside any allocation): more threads than cores
    make, check and drop arrays of one size, and none ever reads another
    thread's values."""
    import sys
    import threading
    size, wrong = (1 << 18) + 7, []

    def work(me):
        held = []
        for i in range(12):
            value = me * 100 + i
            held.append((value, mx.nd.array(np.full(size, value, np.float32))))
            if len(held) > 2:
                held.pop(0)
            for want, a in held:
                got = a.asnumpy()
                if got[0] != want or got[-1] != want:
                    wrong.append((me, i, want, float(got[0])))

    threads = [threading.Thread(target=work, args=(t,)) for t in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong, wrong[:3]


def test_forward_kwargs_land_on_the_bound_device():
    import numpy as np
    dev = jax.devices('cpu')[3]
    x = mx.sym.Variable('x')
    ex = (x * 2).simple_bind(mx.cpu(3), x=(2, 2))
    for v in (np.ones((2, 2), np.float32), mx.nd.ones((2, 2), ctx=mx.cpu(5))):
        out = ex.forward(x=v)[0]
        assert ex.arg_dict['x']._data.devices() == {dev}
        assert out._data.devices() == {dev} and out.asnumpy().sum() == 8


@pytest.mark.parametrize('ctor', [mx.tpu, mx.gpu, mx.cpu, mx.cpu_pinned])
@pytest.mark.parametrize('dev_id', [9, 8, -1])
def test_device_that_is_not_there_raises(ctor, dev_id):
    """Eight devices here: index 8, 9 or -1 is an error, never wrapped."""
    with pytest.raises(MXNetError, match=r'only 8 cpu device'):
        ctor(dev_id).jax_device()


def test_missing_chip_without_the_pin_raises(monkeypatch):
    """A chip that is merely missing is not the CPU-mesh mode: without the
    pin tpu(0)/gpu(0) raise, and cpu(0) is still the host CPU."""
    monkeypatch.setattr(context, 'cpu_mesh_mode', lambda: False)
    for ctor in (mx.tpu, mx.gpu):
        with pytest.raises(MXNetError, match='no tpu device is visible'):
            ctor(0).jax_device()
    assert mx.cpu(0).jax_device() is jax.devices('cpu')[0]


def test_module_over_a_missing_device_raises_at_bind():
    """A context list naming a device that is not there raises; it does
    not quietly lose the SPMD group."""
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=4),
        name='softmax')
    mod = mx.mod.Module(net, context=[mx.tpu(0), mx.tpu(9)])
    with pytest.raises(MXNetError, match=r'tpu\(9\)'):
        mod.bind(data_shapes=[('data', (8, 6))],
                 label_shapes=[('softmax_label', (8,))])


def test_serving_engine_has_no_default_device():
    from mxnet_tpu.serving import ServingEngine
    with pytest.raises(TypeError, match='context'):
        ServingEngine.from_checkpoint('nowhere', 1, [('data', (4,))])


@pytest.fixture
def cache_config():
    """Put jax's compile-cache configuration back as it was."""
    names = ('jax_compilation_cache_dir',
             'jax_persistent_cache_min_compile_time_secs',
             'jax_persistent_cache_min_entry_size_bytes')
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_obeys_jax_compilation_cache_dir(
        monkeypatch, tmp_path, cache_config):
    """The variable names the directory and no other is set in code."""
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    for chip_possible in (False, True):
        monkeypatch.setattr(context, 'cpu_mesh_mode',
                            lambda v=chip_possible: not v)
        assert config.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    # the pinned CPU mesh: off
    assert config.enable_compile_cache() is None
    # a process that may use the chip: on, at the one fixed place
    monkeypatch.setattr(context, 'cpu_mesh_mode', lambda: False)
    path = config.enable_compile_cache()
    assert path == config.COMPILE_CACHE_DIR \
        == os.path.join(REPO, '.jax_compile_cache')
    assert jax.config.jax_compilation_cache_dir == path
    assert config.enable_compile_cache() == path      # and it stays there
    with open(os.path.join(REPO, '.gitignore')) as f:
        assert '.jax_compile_cache/' in f.read().split()


def test_compile_cache_goes_off_with_a_warning_where_it_cannot_be_made(
        monkeypatch, cache_config, tmp_path, caplog):
    """A package installed outside a writable checkout: the default
    directory cannot be made, so the cache stays off and says why."""
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    monkeypatch.setattr(context, 'cpu_mesh_mode', lambda: False)
    blocker = tmp_path / 'a_file'
    blocker.write_text('')
    monkeypatch.setattr(config, 'COMPILE_CACHE_DIR',
                        str(blocker / '.jax_compile_cache'))
    before = jax.config.jax_compilation_cache_dir
    assert config.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert 'JAX_COMPILATION_CACHE_DIR' in caplog.text
