"""The benchmark run configs (BASELINE.json:7-11) as dataclasses.

The reference has no config framework — hyperparameters are ``train()``
arguments and cluster settings live in SparkConf (SURVEY.md §5 "Config /
flag system"). The rebuild keeps that spirit: one frozen dataclass per
benchmark config, a flat registry, and ``dataclasses.replace``-style CLI
overrides (:mod:`fm_spark_tpu.cli`). No config-library dependency.

Registry names map to the BASELINE table (SURVEY.md §6):

- ``movielens_fm_r8``   — config 1: FM rank-8, MovieLens-100K, logistic
  loss; the CPU-quality anchor.
- ``criteo_kaggle_fm_r32`` — config 2: FM rank-32, Criteo-Kaggle 45M,
  ~1M hashed features, data-parallel psum.
- ``criteo1tb_fm_r64``  — config 3: FM rank-64, Criteo-1TB, ~10M hashed
  features, field-partitioned tables (the bench.py headline layout) with
  the row-sharded strategy as the scale-out path.
- ``avazu_ffm_r16``     — config 4: FFM rank-16, Avazu CTR.
- ``avazu_ffm_r16_adagrad`` — config 4 under its paper's update rule:
  per-coordinate AdaGrad on every table.
- ``criteo1tb_deepfm``  — config 5 (stretch): DeepFM, FM + 3-layer MLP.
"""

from __future__ import annotations

import dataclasses

from fm_spark_tpu import models
from fm_spark_tpu.train import TrainConfig

_TRAIN_FIELDS = {f.name for f in dataclasses.fields(TrainConfig)}


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """One benchmark run: model family + shapes + data + training recipe."""

    name: str
    description: str
    model: str                      # 'fm' | 'field_fm' | 'ffm' | 'deepfm'
    dataset: str                    # 'movielens' | 'criteo' | 'avazu' | 'synthetic'
    rank: int
    num_fields: int                 # fixed nnz slot count
    bucket: int = 0                 # per-field hash buckets; 0 ⇒ dense ids,
                                    # num_features supplied by the data
    strategy: str = "single"        # 'single' | 'dp' | 'row' | 'field_sparse'
    task: str = "classification"
    loss: str | None = None
    param_dtype: str = "float32"
    # Forward/backward buffer dtype for the [B, w] passes (storage stays
    # param_dtype); the bench-measured +6% lever, quality pinned by
    # bench_quality.py's bf16_compact_cdbf16 variant.
    compute_dtype: str = "float32"
    mlp_dims: tuple = (400, 400, 400)
    # Training recipe (TrainConfig subset).
    num_steps: int = 1000
    batch_size: int = 8192
    learning_rate: float = 0.1
    lr_schedule: str = "inv_sqrt"
    optimizer: str = "sgd"
    adagrad_init_accumulator: float = 0.0   # TrainConfig's, same name
    reg_bias: float = 0.0
    reg_linear: float = 0.0
    reg_factors: float = 1e-6
    seed: int = 0
    # Sparse-row write strategy for the fused FieldFM steps (ops/scatter.py);
    # picked up by train_config() via _TRAIN_FIELDS, so the CLI
    # --sparse-update override reaches the fused step. dedup_sr is the
    # bf16-storage quality fix promoted in PERF.md.
    sparse_update: str = "scatter_add"
    # Route fused-step row gather/update through the Pallas pipelined-DMA
    # kernels (ops/pallas_fm.py) instead of XLA gather/scatter; reaches
    # the step via train_config() like sparse_update.
    use_pallas: bool = False

    @property
    def field_local_ids(self) -> bool:
        """True for field-partitioned models whose per-field tables take
        FIELD-LOCAL ids in [0, bucket) — the single source of truth for
        every CLI id-conversion gate (a missed conversion means XLA
        silently clamps out-of-range ids into the table edge)."""
        return self.model in ("field_fm", "field_ffm", "field_deepfm")

    @property
    def num_features(self) -> int:
        if self.bucket <= 0:
            raise ValueError(
                f"config {self.name!r} takes num_features from the data; "
                "pass it to spec(num_features=...)"
            )
        return self.num_fields * self.bucket

    def spec(self, num_features: int | None = None) -> models.ModelSpec:
        """Build the model spec; ``num_features`` overrides the hashed size
        (required for dense-id datasets like MovieLens)."""
        n = num_features if num_features is not None else self.num_features
        common = dict(
            num_features=n, rank=self.rank, task=self.task, loss=self.loss,
            init_std=0.01, param_dtype=self.param_dtype,
            compute_dtype=self.compute_dtype,
        )
        if self.model == "fm":
            return models.FMSpec(**common)
        if self.model == "field_fm":
            if num_features is not None and num_features != self.num_features:
                raise ValueError("field_fm shapes are fixed by num_fields*bucket")
            return models.FieldFMSpec(
                **common, num_fields=self.num_fields, bucket=self.bucket
            )
        if self.model == "field_ffm":
            if num_features is not None and num_features != self.num_features:
                raise ValueError("field_ffm shapes are fixed by num_fields*bucket")
            return models.FieldFFMSpec(
                **common, num_fields=self.num_fields, bucket=self.bucket
            )
        if self.model == "ffm":
            return models.FFMSpec(**common, num_fields=self.num_fields)
        if self.model == "deepfm":
            return models.DeepFMSpec(
                **common, num_fields=self.num_fields, mlp_dims=self.mlp_dims
            )
        if self.model == "field_deepfm":
            if num_features is not None and num_features != self.num_features:
                raise ValueError(
                    "field_deepfm shapes are fixed by num_fields*bucket"
                )
            return models.FieldDeepFMSpec(
                **common, num_fields=self.num_fields, bucket=self.bucket,
                mlp_dims=self.mlp_dims,
            )
        raise ValueError(f"unknown model family {self.model!r}")

    def train_config(self, **overrides) -> TrainConfig:
        base = {k: getattr(self, k) for k in _TRAIN_FIELDS if hasattr(self, k)}
        base.update({k: v for k, v in overrides.items() if v is not None})
        return TrainConfig(**base)


CONFIGS = {
    c.name: c
    for c in [
        RunConfig(
            name="movielens_fm_r8",
            description="Config 1 (BASELINE.json:7): FM rank-8, MovieLens-100K,"
            " logistic loss; quality anchor vs the Spark local[*] CPU baseline.",
            model="fm", dataset="movielens", rank=8, num_fields=2,
            strategy="single", num_steps=2000, batch_size=4096,
            learning_rate=0.05, reg_factors=1e-4, reg_linear=1e-5,
        ),
        RunConfig(
            name="criteo_kaggle_fm_r32",
            description="Config 2 (BASELINE.json:8): FM rank-32, Criteo-Kaggle"
            " 45M, 39×32768 ≈ 1.28M per-field hashed features, data-parallel"
            " psum over the mesh.",
            model="fm", dataset="criteo", rank=32, num_fields=39,
            bucket=1 << 15, strategy="dp", num_steps=100_000,
            batch_size=16384, learning_rate=0.05, lr_schedule="constant",
        ),
        RunConfig(
            name="criteo1tb_fm_r64",
            description="Config 3 (BASELINE.json:9): FM rank-64, Criteo-1TB,"
            " 39×262144 ≈ 10.2M hashed features; field-partitioned tables"
            " (bench.py headline) via the fused sparse-SGD step. Multi-chip"
            " scale-out IS this strategy: fields shard over the mesh"
            " automatically, and --row-shards adds bucket row-sharding"
            " (2-D feat×row mesh). The generic 'row' strategy materializes"
            " dense gradients (optax path) — correctness fallback, not the"
            " at-scale path. Measured at these defaults by the benchmark's"
            " cells fm_r64.train (one chip) and fm_r64.train_4chip"
            " (PERF.md). Weak scaling: size with --batch-per-chip 131072.",
            model="field_fm", dataset="criteo", rank=64, num_fields=39,
            bucket=1 << 18, strategy="field_sparse", num_steps=1_000_000,
            batch_size=1 << 17, learning_rate=0.05, lr_schedule="constant",
        ),
        RunConfig(
            name="avazu_ffm_r16",
            description="Config 4 (BASELINE.json:10): FFM rank-16, Avazu CTR,"
            " 23 fields (avazu.py), per-field hashed; field-partitioned"
            " packed tables + fused sparse-SGD fast path. Measured at these"
            " defaults (bucket raised to 2^17) by the benchmark's cell"
            " ffm_r16.train (PERF.md).",
            model="field_ffm", dataset="avazu", rank=16, num_fields=23,
            bucket=1 << 14, strategy="field_sparse", num_steps=100_000,
            batch_size=8192, learning_rate=0.05, lr_schedule="constant",
        ),
        RunConfig(
            name="avazu_ffm_r16_adagrad",
            description="Config 4 as its paper trains it (Juan et al.,"
            " RecSys 2016, Algorithm 1; libffm): avazu_ffm_r16's model"
            " under per-coordinate AdaGrad on every table, eta 0.2,"
            " lambda 2e-5, duplicates of a minibatch coalesced, G0 = 1"
            " written against batch-mean gradients (1/8192^2). One chip:"
            " the fused FieldFFM AdaGrad body, float32 accumulator tables"
            " held beside the parameter tables. Measured at these defaults"
            " (bucket raised to 2^17) by the benchmark's cell"
            " ffm_r16_adagrad.train (PERF.md).",
            model="field_ffm", dataset="avazu", rank=16, num_fields=23,
            bucket=1 << 14, strategy="field_sparse", num_steps=100_000,
            batch_size=8192, learning_rate=0.2, lr_schedule="constant",
            optimizer="adagrad", adagrad_init_accumulator=2.0 ** -26,
            reg_factors=2e-5,
        ),
        RunConfig(
            name="criteo1tb_deepfm",
            description="Config 5, stretch (BASELINE.json:11): DeepFM — FM"
            " rank-16 + 3-layer 400-wide MLP on Criteo shapes, on the CTR"
            " fast path: field-partitioned embedding with fused sparse"
            " scatter updates; dense Adam covers only the MLP + bias"
            " (no table-sized gradients or moment state). The head's"
            " products run at the declared compute_dtype (float32:"
            " precision HIGHEST on the TPU). Measured at these defaults"
            " by the benchmark's cell deepfm_r16.train (PERF.md).",
            model="field_deepfm", dataset="criteo", rank=16, num_fields=39,
            bucket=1 << 18, strategy="field_sparse", num_steps=1_000_000,
            batch_size=16384, learning_rate=1e-3, lr_schedule="constant",
            optimizer="adam",
        ),
    ]
}


def get_config(name: str, **overrides) -> RunConfig:
    """Look up a registered config, optionally overriding fields."""
    if name not in CONFIGS:
        raise KeyError(
            f"unknown config {name!r}; available: {sorted(CONFIGS)}"
        )
    cfg = CONFIGS[name]
    overrides = {k: v for k, v in overrides.items() if v is not None}
    return dataclasses.replace(cfg, **overrides) if overrides else cfg
