"""mxnet_tpu — a TPU-native deep-learning framework with MXNet's capabilities.

From-scratch rebuild of Apache MXNet 0.11.1's API surface and semantics
(reference at /root/reference) on a JAX/XLA/Pallas execution model: eager
NDArray ops dispatch through cached jit closures, Symbol.bind compiles whole
graphs into single XLA computations, KVStore lowers to mesh collectives.
See SURVEY.md for the layer map this follows.
"""
from .libinfo import __version__  # noqa: F401  (single version source)

from . import base
from . import libinfo
from . import log
from . import name
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus
from . import ndarray
from . import ndarray as nd
from . import random
from .random import seed  # noqa: F401
from . import autograd
from . import engine
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import attribute
from .attribute import AttrScope
from . import executor
from . import initializer
from . import initializer as init  # mx.init.Xavier() etc. (reference alias)
from . import optimizer
from . import optimizer as opt
from . import lr_scheduler
from . import metric
from . import callback
from . import io
from . import image
from . import recordio
from . import kvstore
from . import kvstore as kv
from . import model
from .model import save_checkpoint, load_checkpoint
from . import module
from . import module as mod
from . import rnn
from . import gluon
from . import monitor
from . import monitor as mon  # reference __init__.py:62 alias
from .monitor import Monitor
from . import profiler
from . import visualization
from . import visualization as viz
from . import test_utils
from . import registry
from .executor_manager import DataParallelExecutorManager  # noqa: F401
from . import operator
from .operator import CustomOp, CustomOpProp
from . import rtc
from . import contrib
from . import plugin
from . import parallel
from . import telemetry

# Decide telemetry at import so the jax.monitoring compile listener is
# installed before the process's FIRST compile (a fit run must log its
# warmup compiles too). With MXTPU_TELEMETRY unset this is one cached
# flag read and nothing else.
telemetry.enabled()

# Persistent XLA compilation cache: placed at import, before the first
# compile (config.enable_compile_cache decides where; no device is touched).
from .config import enable_compile_cache as _enable_compile_cache
_enable_compile_cache()
del _enable_compile_cache

# Server/scheduler processes block in their role loop here and exit with the
# job (reference python/mxnet/kvstore_server.py:75).
from .kvstore_server import init_server_module_if_needed as _init_kv_server
_init_kv_server()
del _init_kv_server
