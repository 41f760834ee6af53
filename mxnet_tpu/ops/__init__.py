"""Operator library — importing this package registers all ops.

Reference analog: the static-initializer op registrations across
src/operator/*.cc collected by the NNVM registry at library load.
"""
from . import registry
from . import elemwise            # noqa: F401
from . import reduce_ops          # noqa: F401
from . import shape_ops           # noqa: F401
from . import nn                  # noqa: F401
from . import linalg_sort         # noqa: F401
from . import random_ops          # noqa: F401
from . import optimizer_ops       # noqa: F401
from . import rnn_ops             # noqa: F401
from . import contrib_ops         # noqa: F401
from . import sparse_ops          # noqa: F401
from . import transformer         # noqa: F401
from . import legacy_ops          # noqa: F401  (alias/legacy names last)

from .registry import register, get, list_ops, exists
from . import pallas_kernels      # noqa: F401  (TPU kernels for hot ops)
from .pallas_kernels import (flash_attention, fused_rmsnorm,  # noqa: F401
                             fused_layernorm, softmax_xent)
