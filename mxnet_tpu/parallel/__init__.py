"""parallel — SPMD distribution over TPU device meshes.

This package is the TPU-native replacement for the reference's entire
distribution story (SURVEY.md §2.3, §5.8):

- ``src/kvstore/comm.h`` device-tier reduce/broadcast  → XLA collectives
  over the ICI mesh (:mod:`collectives`).
- ``DataParallelExecutorGroup`` (module/executor_group.py:99) batch
  slicing  → one pjit'd step with the batch sharded on the ``dp`` mesh
  axis (:mod:`data_parallel`).
- ``AttrScope(ctx_group)`` manual model parallelism  → sharding
  annotations (:mod:`sharding`) and a real micro-batch pipeline schedule
  (:mod:`pipeline`) — new capability, absent in the reference.
- Long sequences: the reference buckets (BucketingModule); here sequence/
  context parallelism via ring attention over ``ppermute``
  (:mod:`ring_attention`) — new capability.
"""
from jax import shard_map  # noqa: F401  (re-exported: mx.parallel.shard_map)
from .mesh import DeviceMesh, make_mesh, local_mesh
from .collectives import (allreduce, allgather, reduce_scatter, ring_permute,
                          alltoall, axis_index, axis_size, pbroadcast)
from .sharding import (ShardingPlan, data_parallel_plan, constrain,
                       shard_params, replicate_params)
from .data_parallel import make_train_step, ShardedTrainer
from . import checkpoint  # noqa: F401  (sharded SPMD checkpointing)
from .ring_attention import (ring_attention, blockwise_attention,
                             ulysses_attention, striped_attention,
                             stripe_layout, unstripe_layout,
                             make_ring_attention,
                             attention_reference)
from .pipeline import PipelineStage, pipeline_apply, stack_stage_params
from .multihost import (init_multihost, global_mesh, process_index,
                        process_count, is_multihost)
from .five_d import (TransformerConfig, full_mesh, make_5d_train_step,
                     make_loss_fn as make_5d_loss_fn)
from . import compression  # noqa: F401  (quantized gradient collectives)
from .compression import compressed_psum

__all__ = [
    'DeviceMesh', 'make_mesh', 'local_mesh',
    'allreduce', 'allgather', 'reduce_scatter', 'ring_permute', 'alltoall',
    'axis_index', 'axis_size', 'pbroadcast',
    'ShardingPlan', 'data_parallel_plan', 'constrain', 'shard_params',
    'replicate_params',
    'make_train_step', 'ShardedTrainer',
    'checkpoint',
    'ring_attention', 'blockwise_attention', 'ulysses_attention',
    'striped_attention', 'stripe_layout', 'unstripe_layout',
    'make_ring_attention', 'attention_reference',
    'PipelineStage', 'pipeline_apply', 'stack_stage_params',
    'TransformerConfig', 'full_mesh', 'make_5d_train_step',
    'make_5d_loss_fn',
    'init_multihost', 'global_mesh', 'process_index', 'process_count',
    'is_multihost',
    'compression', 'compressed_psum',
]
