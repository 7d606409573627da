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
- ``criteo1tb_dlrm_mlperf`` — DLRM (Naumov et al., arXiv:1906.00091) at
  the MLPerf Training recommendation benchmark's sizes on Criteo-1TB: 13
  dense columns through a bottom MLP, 26 tables of 128-wide rows, the
  pairwise dot interaction, a top MLP; plain SGD on everything.
- ``criteo1tb_dcnv2_multihot`` — DCN-v2 (Wang et al., arXiv:2008.13535,
  sections 3.2 and 3.4) at the sizes of the MLPerf Training
  recommendation benchmark since v3.0 on the multi-hot Criteo-1TB
  (mlcommons/training, recommendation_v2/torchrec_dlrm): 214 ids an
  example sum-pooled into 26 rows of 128, a three-layer low-rank cross
  network, AdaGrad on every parameter.
- ``criteo_xdeepfm_cin200`` — xDeepFM (Lian et al., KDD 2018,
  arXiv:1803.05170, eq. 6-9) at its paper's Criteo settings: 39 fields
  of 10-wide embeddings, a Compressed Interaction Network of three layers
  of 200 feature maps beside a 400-400 DNN and the linear term; Adam on
  the dense leaves, SGD on the rows.
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
    model: str                      # 'fm' | 'field_fm' | 'ffm' | 'deepfm' |
                                    # 'field_ffm' | 'field_deepfm' | 'field_dlrm'
                                    # | 'field_dcn' | 'field_xdeepfm'
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
    # 'field_dlrm' alone: the leading batch slots that carry a real value
    # and own no table, and the bottom stack's widths after them
    # (``mlp_dims`` is its top stack).
    dense_fields: int = 0
    bottom_mlp_dims: tuple = ()
    # 'field_dcn' alone: ids a categorical column (a bag, sum-pooled;
    # ``num_fields`` is then ``dense_fields + sum(hots)`` batch slots over
    # ``dense_fields + len(hots)`` columns), and its cross network.
    hots: tuple = ()
    cross_layers: int = 0
    cross_rank: int = 0
    # 'field_xdeepfm' alone: the feature maps of each CIN layer
    # (``mlp_dims`` is its DNN's hidden widths).
    cin_layers: tuple = ()
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
        return self.model in ("field_fm", "field_ffm", "field_deepfm",
                              "field_dlrm", "field_dcn", "field_xdeepfm")

    @property
    def num_features(self) -> int:
        if self.bucket <= 0:
            raise ValueError(
                f"config {self.name!r} takes num_features from the data; "
                "pass it to spec(num_features=...)"
            )
        # One bucket range a column: a slot, or with bags all of a
        # column's slots.
        columns = (self.dense_fields + len(self.hots) if self.hots
                   else self.num_fields)
        return columns * self.bucket

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
        if self.model == "field_xdeepfm":
            if num_features is not None and num_features != self.num_features:
                raise ValueError(
                    "field_xdeepfm shapes are fixed by num_fields*bucket"
                )
            return models.FieldXDeepFMSpec(
                **common, num_fields=self.num_fields, bucket=self.bucket,
                mlp_dims=self.mlp_dims, cin_layers=self.cin_layers,
            )
        if self.model == "field_dlrm":
            if num_features is not None and num_features != self.num_features:
                raise ValueError(
                    "field_dlrm shapes are fixed by num_fields*bucket"
                )
            common.pop("init_std")      # the source's own initial values
            return models.FieldDLRMSpec(
                **common, num_fields=self.num_fields, bucket=self.bucket,
                dense_fields=self.dense_fields,
                bottom_mlp_dims=self.bottom_mlp_dims,
                mlp_dims=self.mlp_dims,
            )
        if self.model == "field_dcn":
            if num_features is not None and num_features != self.num_features:
                raise ValueError(
                    "field_dcn shapes are fixed by its columns and bucket"
                )
            common.pop("init_std")      # the source's own initial values
            return models.FieldDCNSpec(
                **common, num_fields=self.num_fields, bucket=self.bucket,
                dense_fields=self.dense_fields, hots=self.hots,
                bottom_mlp_dims=self.bottom_mlp_dims,
                cross_layers=self.cross_layers, cross_rank=self.cross_rank,
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
        RunConfig(
            name="criteo1tb_dlrm_mlperf",
            description="DLRM (Naumov et al., arXiv:1906.00091, section 2)"
            " at the sizes of the MLPerf Training recommendation benchmark"
            " on Criteo-1TB (facebookresearch/dlrm): 13 dense columns"
            " (log1p of the integer columns, as values) through a"
            " 13-512-256-128 bottom stack, 26 categorical columns of"
            " 128-wide rows, the pairwise dot interaction without its"
            " diagonal (351 pairs), a 479-1024-1024-512-256-1 top stack,"
            " binary cross-entropy, plain SGD on every parameter, batch"
            " 55,296. Each column hashed into 2^19 equal buckets (the"
            " source keeps per-column vocabularies capped at 40M rows);"
            " a constant rate where the source warms up and decays. One"
            " chip: the fused FieldDLRM body. Measured at these defaults"
            " by the benchmark's cell dlrm_e128.train (PERF.md).",
            model="field_dlrm", dataset="criteo", rank=128, num_fields=39,
            dense_fields=13, bottom_mlp_dims=(512, 256, 128),
            mlp_dims=(1024, 1024, 512, 256),
            bucket=1 << 19, strategy="field_sparse", num_steps=1_000_000,
            batch_size=55296, learning_rate=1.0, lr_schedule="constant",
            optimizer="sgd", reg_factors=0.0,
        ),
        RunConfig(
            name="criteo1tb_dcnv2_multihot",
            description="DCN-v2 (Wang et al., arXiv:2008.13535, the cross"
            " layer of section 3.2 in the low-rank form of section 3.4) at"
            " the sizes of the MLPerf Training recommendation benchmark"
            " since v3.0, 'DLRM-DCNv2' on the multi-hot Criteo-1TB"
            " (mlcommons/training, recommendation_v2/torchrec_dlrm): 13"
            " dense columns through a 13-512-256-128 bottom stack, 26"
            " categorical columns each a BAG of ids (multi_hot_sizes, 214"
            " ids an example) sum-pooled into one 128-wide row, the 3,456"
            " concatenation through 3 cross layers of rank 512, a"
            " 3456-1024-1024-512-256-1 top stack, binary cross-entropy,"
            " AdaGrad on every parameter (0.004, epsilon 1e-8, G0 = 0,"
            " duplicates of a batch coalesced), batch 65,536. Each column"
            " hashed into 2^18 equal buckets (the source keeps per-column"
            " vocabularies capped at 40M rows). One chip: the fused"
            " FieldDCN AdaGrad body. Measured at these defaults by the"
            " benchmark's cell dcnv2_mh.train (PERF.md).",
            model="field_dcn", dataset="criteo", rank=128, num_fields=227,
            dense_fields=13,
            hots=(3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1,
                  12, 100, 27, 10, 3, 1, 1),
            bottom_mlp_dims=(512, 256, 128), cross_layers=3, cross_rank=512,
            mlp_dims=(1024, 1024, 512, 256),
            bucket=1 << 18, strategy="field_sparse", num_steps=1_000_000,
            batch_size=65536, learning_rate=0.004, lr_schedule="constant",
            optimizer="adagrad", adagrad_init_accumulator=0.0,
            reg_factors=0.0,
        ),
        RunConfig(
            name="criteo_xdeepfm_cin200",
            description="xDeepFM (Lian et al., KDD 2018, arXiv:1803.05170:"
            " the CIN layer of eq. 6, sum pooling of eq. 7, the output of"
            " eq. 9) at its paper's Criteo settings (section 4.1.3): 39"
            " fields (13 numeric discretized, 26 categorical; one active"
            " feature each) of 10-wide embeddings, a Compressed Interaction"
            " Network of three layers of 200 feature maps (identity"
            " activation, every layer pooled to the output) beside a"
            " 390-400-400 ReLU DNN and the linear term, log loss, L2 1e-4,"
            " batch 4,096. Each field hashed into 2^18 buckets (the paper"
            " keeps Criteo's own vocabulary); Adam at 1e-3 on the dense"
            " leaves and plain SGD at 1e-3 on the rows (the paper: Adam on"
            " everything). One chip: the fused DeepFM body with the CIN as"
            " its head. Measured at these defaults by the benchmark's cell"
            " xdeepfm_cin200.train (PERF.md).",
            model="field_xdeepfm", dataset="criteo", rank=10, num_fields=39,
            bucket=1 << 18, cin_layers=(200, 200, 200), mlp_dims=(400, 400),
            strategy="field_sparse", num_steps=1_000_000, batch_size=4096,
            learning_rate=1e-3, lr_schedule="constant", optimizer="adam",
            reg_factors=1e-4,
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
