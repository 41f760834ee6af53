"""The device rule of context.py: a context names exactly the device that
was asked for, or raises; and where the persistent compile cache goes
(config.enable_compile_cache)."""
import os

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
