"""Shared model-spec scaffolding and the task-switch prediction link.

The reference's ``FMModel.predict`` applies a task switch: classification →
sigmoid (threshold left to the caller), regression → clip predictions to the
[min, max] seen at training time (SURVEY.md §2 row 4, §3.2). That switch
lives here, shared by all model families.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Static model hyperparameters, hashable for use as a jit static arg.

    Mirrors the reference's ``train()`` parameterization (SURVEY.md §1 L5):
    ``dim=(k0, k1, k2)`` → (use_bias, use_linear, rank); ``initStd`` →
    ``init_std``; task switch; regression min/max clip.
    """

    num_features: int
    rank: int
    task: str = "classification"          # 'classification' | 'regression'
    loss: str | None = None       # 'logistic'|'squared'|'hinge'; None ⇒ by task
    use_bias: bool = True                 # dim k0
    use_linear: bool = True               # dim k1
    init_std: float = 0.01
    min_target: float = -math.inf        # regression clip, learned from data
    max_target: float = math.inf
    param_dtype: str = "float32"          # storage dtype for the big tables
    compute_dtype: str = "float32"        # accumulation dtype

    # Field-partitioned subclasses override to True: their tables take
    # FIELD-LOCAL ids in [0, bucket) and data layers must convert
    # per-field-offset global ids first (cli._field_local).
    field_local_ids = False

    # The parameter keys whose per-field leaves a spec reads by row
    # through models/rows.gather, and a scorer may therefore hold packed
    # (models/rows.hold). The flat specs read theirs through ops/fm: none.
    row_tables = ()

    def __post_init__(self):
        if self.task not in ("classification", "regression"):
            raise ValueError(f"unknown task {self.task!r}")
        # The reference's task switch ties the loss to the task; keep that
        # as the default and fail at construction, not first training step.
        if self.loss is None:
            object.__setattr__(
                self,
                "loss",
                "logistic" if self.task == "classification" else "squared",
            )
        from fm_spark_tpu.ops import losses

        losses.loss_fn(self.loss)
        if self.task == "regression" and self.loss in ("logistic", "hinge"):
            raise ValueError(
                f"{self.loss} loss expects {{0,1}} labels; use "
                "loss='squared' (or leave loss unset) for task='regression'"
            )

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)


def predict_from_scores(spec: ModelSpec, scores: jax.Array) -> jax.Array:
    """Raw scores → predictions per the reference's task switch."""
    if spec.task == "classification":
        return jax.nn.sigmoid(scores)
    lo = spec.min_target if spec.min_target > -math.inf else None
    hi = spec.max_target if spec.max_target < math.inf else None
    if lo is None and hi is None:
        return scores
    return jnp.clip(scores, lo, hi)


def init_linear_terms(rng: jax.Array, spec: ModelSpec) -> dict:
    """Bias + linear weights, zero-initialized like the reference (w=0, w0=0)."""
    del rng
    return {
        "w0": jnp.zeros((), dtype=jnp.float32),
        "w": jnp.zeros((spec.num_features,), dtype=spec.pdtype),
    }
