"""fm_spark_tpu — a TPU-native factorization-machine training framework.

A ground-up JAX/XLA rebuild of the capabilities of ``Rainbowboys/fm_spark``
(a Scala/Spark FM trainer in the spark-libFM lineage; see SURVEY.md). Instead
of the reference's driver-loop minibatch SGD with per-iteration
``treeAggregate``/broadcast round-trips, everything here is one jit-compiled
on-device training step:

- the order-2 interaction term and its latent-factor gradient live in
  :mod:`fm_spark_tpu.ops.fm` over gathered embedding rows (a dense
  ``(k x nnz)`` contraction XLA tiles onto the MXU);
- model families (FM, FFM, DeepFM) are frozen specs + pure init/scores/
  predict functions in :mod:`fm_spark_tpu.models`.

Data parallelism (`psum` as the ``treeAggregate`` equivalent), row-sharded
feature tables, the trainer, orbax checkpointing, and streaming metrics are
built on top of these kernels in the sibling subpackages.
"""

__version__ = "0.1.0"

from fm_spark_tpu import ops, models  # noqa: F401
