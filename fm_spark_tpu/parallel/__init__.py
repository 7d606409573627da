"""Distributed execution: device meshes, sharding strategies, psum steps.

This package is the TPU-native replacement for the reference's entire
distributed runtime (SURVEY.md §1 L1, §5 "Distributed communication
backend"): Spark's driver-mediated per-iteration ``treeAggregate`` reduce
and ``TorrentBroadcast`` weight redistribution become ``jax.lax.psum`` over
a device mesh inside one compiled step — collectives ride ICI within a
slice (DCN across slices), parameters stay resident on device, and the
broadcast disappears entirely.

Two strategies (SURVEY.md §2 parallelism table):

- ``dp`` — data parallel, the reference's one true strategy: batch sharded
  over the ``data`` axis, model replicated, gradients psum'd (the
  ``treeAggregate`` equivalent). Works for every model family.
- ``row`` — feature/row-sharded embeddings over the ``feat`` axis composed
  with data parallelism over ``data`` (the scale-out path for 10M-feature
  tables, BASELINE.json:9): each shard computes masked partial sums
  (linear, s_f) for its rows, one psum over ``feat`` reconstructs the exact
  forward, and backward touches only shard-local rows.
"""

from fm_spark_tpu.parallel.mesh import make_mesh  # noqa: F401
from fm_spark_tpu.parallel.step import (  # noqa: F401
    param_specs,
    shard_params,
    shard_batch,
    lower_parallel_train_step,
    make_parallel_train_step,
    make_parallel_eval_step,
    precompile_parallel_train_step,
)
from fm_spark_tpu.parallel.field_step import (  # noqa: F401
    FieldBatchFeed,
    field_batch_specs,
    field_param_specs,
    make_field_deepfm_sharded_step,
    make_field_ffm_sharded_body,
    make_field_ffm_sharded_eval_step,
    make_field_ffm_sharded_step,
    lower_field_sharded_step,
    make_field_mesh,
    make_field_sharded_sgd_body,
    precompile_field_sharded_step,
    make_field_deepfm_sharded_eval_step,
    make_field_sharded_eval_step,
    make_field_sharded_multistep,
    make_field_deepfm_sharded_multistep,
    make_field_sharded_sgd_step,
    evaluate_field_sharded,
    pad_field_batch,
    shard_field_batch,
    shard_field_batch_stacked,
    shard_field_batch_stacked_local,
    stacked_field_batch_specs,
    shard_field_batch_local,
    place_compact_aux,
    shard_compact_aux,
    shard_field_deepfm_params,
    shard_field_params,
    stack_compact_aux,
    stack_field_deepfm_params,
    stack_field_params,
    unstack_field_deepfm_params,
    unstack_field_params,
)
