"""Field-partitioned FFM: the CTR-scale TPU layout of the FFM table.

Same motivation as :mod:`fm_spark_tpu.models.field_fm` (measured XLA
gather/scatter cliffs on monolithic tables — PERF.md), applied to the
field-aware model (reference config 4, BASELINE.json:10): instead of one
``[n, F, k]`` tensor, each field owns a ``[bucket, F·k (+1)]`` table whose
row packs the feature's F per-target-field factor vectors (and, fused in
the last column, its linear weight) — so the hot path stays ONE gather and
ONE scatter per field per step, identical in index-op count to FieldFM,
with F·k-wide rows (row width is nearly free once the index is paid,
PERF.md fact 2).

Encoding matches FieldFM: field-local ids ``[B, F]`` with the fixed
slot==field CTR layout (one active feature per field). Equivalence with
the flat :class:`FFMSpec` under the offset embedding is property-tested.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from fm_spark_tpu.models import base, rows as rows_lib


@dataclasses.dataclass(frozen=True)
class FieldFFMSpec(base.ModelSpec):
    """FFM with one packed sub-table per field.

    ``num_fields`` fields with ``bucket`` hashed rows each;
    ``num_features = num_fields * bucket``. Row layout of table f:
    columns ``[j*k : (j+1)*k]`` hold the factor vector used when the
    feature interacts with field ``j``; column ``F*k`` is the linear
    weight (``fused_linear``).
    """

    num_fields: int = 0
    bucket: int = 0
    fused_linear: bool = True

    # Tables take FIELD-LOCAL ids (see FieldFMSpec).
    field_local_ids = True
    row_tables = ("vw",)

    def __post_init__(self):
        super().__post_init__()
        if self.num_fields <= 0 or self.bucket <= 0:
            raise ValueError("FieldFFMSpec requires num_fields > 0 and bucket > 0")
        if self.num_features != self.num_fields * self.bucket:
            raise ValueError(
                f"num_features ({self.num_features}) must equal "
                f"num_fields*bucket ({self.num_fields * self.bucket})"
            )
        if not self.fused_linear:
            raise ValueError("FieldFFMSpec ships the fused layout only")

    @property
    def table_width(self) -> int:
        return self.num_fields * self.rank + 1

    def init(self, rng: jax.Array) -> dict:
        """One table at a time, each waited for (as ``rows.hold`` forms
        them): buffers are allocated as work is queued, ahead of the
        device, so an init left to run ahead holds a few tables' worth
        of transients and where the tables come to lie on the chip
        follows how far ahead the host happened to get: a different
        layout every run, and at this cell's sizes (23 x 192 MiB) some
        of them scatter and gather 1-3% slower for the whole run
        (PERF.md §6, PR 33). Waited for, the layout is one and the
        transient one table's."""
        f, k = self.num_fields, self.rank
        keys = jax.random.split(rng, f)
        tables = []
        for i in range(f):
            v = (
                jax.random.normal(keys[i], (self.bucket, f * k), jnp.float32)
                * self.init_std
            ).astype(self.pdtype)
            table = jnp.concatenate(
                [v, jnp.zeros((self.bucket, 1), self.pdtype)], axis=1
            )
            del v
            tables.append(jax.block_until_ready(table))
        return {"w0": jnp.zeros((), jnp.float32), "vw": tables}

    def gather_rows(self, params: dict, ids: jax.Array):
        """One gather per field → list of F ``[B, F·k+1]`` rows."""
        cd = self.cdtype
        return [
            rows_lib.gather(params["vw"][f], ids[:, f]).astype(cd)
            for f in range(self.num_fields)
        ]

    def _sel(self, rows, vals_c):
        """``sel[b, i, j, :] = v[id_i, field j] * x_i`` — the [B,F,F,k]
        interaction tensor (x folded in), shared by scores and the fused
        step's backward."""
        f, k = self.num_fields, self.rank
        factors = jnp.stack(
            [r[:, : f * k].reshape(-1, f, k) for r in rows], axis=1
        )  # [B, i(owner), j(target), k]
        return factors * vals_c[:, :, None, None]

    def scores(self, params: dict, ids: jax.Array, vals: jax.Array) -> jax.Array:
        if ids.shape[1] != self.num_fields:
            raise ValueError(
                f"batch has {ids.shape[1]} slots, spec has {self.num_fields} fields"
            )
        cd = self.cdtype
        f, k = self.num_fields, self.rank
        vals_c = vals.astype(cd)
        rows = self.gather_rows(params, ids)
        sel = self._sel(rows, vals_c)
        a = jnp.sum(sel * jnp.swapaxes(sel, 1, 2), axis=-1)  # [B, F, F]
        diag = jnp.trace(a, axis1=1, axis2=2)
        score = 0.5 * (jnp.sum(a, axis=(1, 2)) - diag)
        if self.use_linear:
            score = score + sum(
                r[:, f * k] * vals_c[:, i] for i, r in enumerate(rows)
            )
        if self.use_bias:
            score = score + params["w0"].astype(cd)
        return score

    def predict(self, params: dict, ids: jax.Array, vals: jax.Array) -> jax.Array:
        return base.predict_from_scores(self, self.scores(params, ids, vals))

    # -- layout conversion (testing / interop with the flat FFMSpec) -------

    def flat_spec(self):
        from fm_spark_tpu.models.ffm import FFMSpec

        kwargs = dataclasses.asdict(self)
        kwargs.pop("bucket")
        kwargs.pop("fused_linear")
        return FFMSpec(**kwargs)

    def to_flat_params(self, params: dict) -> dict:
        f, k = self.num_fields, self.rank
        return {
            "w0": params["w0"],
            "w": jnp.concatenate([t[:, f * k] for t in params["vw"]]),
            "v": jnp.concatenate(
                [t[:, : f * k].reshape(-1, f, k) for t in params["vw"]],
                axis=0,
            ),
        }

    def to_global_ids(self, ids) -> jax.Array:
        offs = jnp.arange(self.num_fields, dtype=jnp.int32) * self.bucket
        return ids + offs[None, :]
