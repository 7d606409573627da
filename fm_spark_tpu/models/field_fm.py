"""Field-partitioned FM: the CTR-scale TPU layout of the FM table.

Measured on TPU v5e (see bench.py): XLA gathers/scatters into one
monolithic ``[10M, k]`` table are per-index latency-bound (~50ms per 5M
gathered rows) and scatter falls off a cliff beyond ~512k rows (~1s/step).
Splitting the table into one sub-table per Criteo-style field — each below
the fast-path thresholds — makes the same math ~7× faster: the model IS the
reference's FM (BASELINE.json:5), only the parameter layout is TPU-native.

Encoding: ids are FIELD-LOCAL, shape ``[B, F]`` with ``ids[:, f] ∈
[0, bucket_f)``; the hashed feature space is the disjoint union of the
per-field buckets (exactly how Criteo/Avazu hashing is done per field —
SURVEY.md §2 row 7). Equivalence with the flat ``FMSpec`` under the offset
embedding ``global_id = Σ_{g<f} bucket_g + local_id`` is property-tested.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from fm_spark_tpu.models import base, rows as rows_lib
from fm_spark_tpu.ops import fm as fm_ops


@dataclasses.dataclass(frozen=True)
class FieldFMSpec(base.ModelSpec):
    """FM with one sub-table per field.

    ``num_fields`` fields, each with ``bucket`` hashed rows (uniform for
    now); ``num_features`` is derived as ``num_fields * bucket``.
    """

    num_fields: int = 0
    bucket: int = 0
    # Store the linear weight as column `rank` of each factor table so the
    # forward/backward does ONE gather/scatter per field instead of two —
    # the per-index op cost dominates on TPU (see module docstring), so
    # halving index ops is ~2× on the hot path.
    fused_linear: bool = True

    # Tables take FIELD-LOCAL ids in [0, bucket) — data layers must
    # convert per-field-offset globals (cli._field_local; the CLI gates
    # key on this flag).
    field_local_ids = True

    def __post_init__(self):
        super().__post_init__()
        if self.num_fields <= 0 or self.bucket <= 0:
            raise ValueError("FieldFMSpec requires num_fields > 0 and bucket > 0")
        if self.num_features != self.num_fields * self.bucket:
            raise ValueError(
                f"num_features ({self.num_features}) must equal "
                f"num_fields*bucket ({self.num_fields * self.bucket})"
            )

    @property
    def table_width(self) -> int:
        return self.rank + 1 if self.fused_linear else self.rank

    @property
    def row_tables(self) -> tuple[str, ...]:
        """The parameter keys whose per-field leaves are read by row
        through :func:`rows.gather` (a scorer may hold those packed)."""
        return ("vw",) if self.fused_linear else ("v", "w")

    def init(self, rng: jax.Array) -> dict:
        keys = jax.random.split(rng, self.num_fields)
        factors = [
            (jax.random.normal(keys[f], (self.bucket, self.rank), jnp.float32)
             * self.init_std).astype(self.pdtype)
            for f in range(self.num_fields)
        ]
        if self.fused_linear:
            # Column `rank` is the linear weight w, zero-initialized like
            # the reference.
            vw = [
                jnp.concatenate(
                    [v, jnp.zeros((self.bucket, 1), self.pdtype)], axis=1
                )
                for v in factors
            ]
            return {"w0": jnp.zeros((), jnp.float32), "vw": vw}
        return {
            "w0": jnp.zeros((), jnp.float32),
            "w": [jnp.zeros((self.bucket,), self.pdtype)
                  for _ in range(self.num_fields)],
            "v": factors,
        }

    def gather_rows(self, params: dict, ids: jax.Array):
        """One gather per field → list of F ``[B, width]`` rows (compute dtype)."""
        cd = self.cdtype
        tables = params["vw"] if self.fused_linear else params["v"]
        return [rows_lib.gather(tables[f], ids[:, f]).astype(cd)
                for f in range(self.num_fields)]

    def scores(self, params: dict, ids: jax.Array, vals: jax.Array) -> jax.Array:
        if ids.shape[1] != self.num_fields:
            raise ValueError(
                f"batch has {ids.shape[1]} slots, spec has {self.num_fields} fields"
            )
        cd = self.cdtype
        vals_c = vals.astype(cd)
        rows = self.gather_rows(params, ids)
        k = self.rank
        xvs = [r[:, :k] * vals_c[:, f : f + 1] for f, r in enumerate(rows)]
        xv = jnp.stack(xvs, axis=1)                       # [B, F, k]
        score = fm_ops.fm_interaction_from_xv(xv)
        if self.use_linear:
            if self.fused_linear:
                lin = sum(
                    r[:, k] * vals_c[:, f] for f, r in enumerate(rows)
                )
            else:
                lin = sum(
                    rows_lib.gather(params["w"][f], ids[:, f]).astype(cd)
                    * vals_c[:, f]
                    for f in range(self.num_fields)
                )
            score = score + lin
        if self.use_bias:
            score = score + params["w0"].astype(cd)
        return score

    def predict(self, params: dict, ids: jax.Array, vals: jax.Array) -> jax.Array:
        return base.predict_from_scores(self, self.scores(params, ids, vals))

    # -- layout conversion (testing / interop with the flat FMSpec) --------

    def flat_spec(self):
        from fm_spark_tpu.models.fm import FMSpec

        kwargs = dataclasses.asdict(self)
        kwargs.pop("num_fields")
        kwargs.pop("bucket")
        kwargs.pop("fused_linear")
        return FMSpec(**kwargs)

    def to_flat_params(self, params: dict) -> dict:
        """Concatenate per-field tables into the flat [N, k] layout."""
        if self.fused_linear:
            k = self.rank
            vw = params["vw"]
            return {
                "w0": params["w0"],
                "w": jnp.concatenate([t[:, k] for t in vw]),
                "v": jnp.concatenate([t[:, :k] for t in vw], axis=0),
            }
        return {
            "w0": params["w0"],
            "w": jnp.concatenate(params["w"]),
            "v": jnp.concatenate(params["v"], axis=0),
        }

    def to_global_ids(self, ids) -> jax.Array:
        """Field-local ids → flat global ids (offset embedding)."""
        offs = jnp.arange(self.num_fields, dtype=jnp.int32) * self.bucket
        return ids + offs[None, :]
