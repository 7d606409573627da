"""Field-partitioned DeepFM: the CTR-scale TPU layout of DeepFM.

Same architecture as :class:`~fm_spark_tpu.models.deepfm.DeepFMSpec`
(Guo et al., IJCAI 2017 — FM and deep head SHARE the embedding; score =
y_fm + y_deep; reference stretch config, BASELINE.json:11), but the
shared embedding uses the measured CTR layout of
:class:`~fm_spark_tpu.models.field_fm.FieldFMSpec`: one sub-table per
field, linear weight fused into column ``rank``, field-local ids. That
makes the embedding side eligible for the fused sparse-SGD scatter
update (sparse.py) — the flat ``DeepFMSpec`` + dense optax path
materializes a dense [10M, k] gradient AND two Adam moment tables per
step, which is the measured ~94k samples/sec/chip slow path (PERF.md).

The training split (sparse.make_field_deepfm_sparse_step): embedding
tables update via analytic sparse scatter-SGD (lazy L2), while the MLP
+ bias — the only dense, non-embedding parameters — update with the
configured optax optimizer (Adam for config 5).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from fm_spark_tpu.models import base, rows as rows_lib
from fm_spark_tpu.models.field_fm import FieldFMSpec


@dataclasses.dataclass(frozen=True)
class FieldDeepFMSpec(base.ModelSpec):
    """DeepFM over field-partitioned embedding tables.

    ``num_fields`` fields × ``bucket`` hashed rows each; the MLP input is
    ``num_fields * rank`` (concatenated value-scaled rows). The linear
    weight is fused into column ``rank`` of each table (one gather per
    field serves the FM term, the linear term, AND the deep head).
    """

    num_fields: int = 0
    bucket: int = 0
    mlp_dims: tuple = (400, 400, 400)

    def __post_init__(self):
        super().__post_init__()
        if self.num_fields <= 0 or self.bucket <= 0:
            raise ValueError(
                "FieldDeepFMSpec requires num_fields > 0 and bucket > 0"
            )
        if self.num_features != self.num_fields * self.bucket:
            raise ValueError(
                f"num_features ({self.num_features}) must equal "
                f"num_fields*bucket ({self.num_fields * self.bucket})"
            )

    # Table layout identical to FieldFMSpec(fused_linear=True); tables
    # take FIELD-LOCAL ids (see FieldFMSpec).
    fused_linear = True
    field_local_ids = True
    row_tables = ("vw",)
    # The parameters that are not tables: what the loop's optax state
    # covers (cli._fit_field_sparse).
    dense_keys = ("w0", "mlp")
    # The FM second-order term beside the head (the fused body's analytic
    # share of the rows' gradient); a head that replaces it says False.
    fm_interaction = True

    @property
    def table_width(self) -> int:
        return self.rank + 1

    def init(self, rng: jax.Array) -> dict:
        k_emb, k_mlp = jax.random.split(rng)
        field_spec = self._field_fm_spec()
        params = field_spec.init(k_emb)
        dims = (self.num_fields * self.rank, *self.mlp_dims, 1)
        keys = jax.random.split(k_mlp, len(dims) - 1)
        layers = []
        for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
            scale = jnp.sqrt(2.0 / d_in)  # He init for the relu stack
            layers.append({
                "kernel": jax.random.normal(keys[i], (d_in, d_out),
                                            jnp.float32) * scale,
                "bias": jnp.zeros((d_out,), jnp.float32),
            })
        params["mlp"] = layers
        return params

    def _field_fm_spec(self) -> FieldFMSpec:
        return FieldFMSpec(
            num_features=self.num_features, rank=self.rank,
            num_fields=self.num_fields, bucket=self.bucket,
            task=self.task, loss=self.loss, use_bias=self.use_bias,
            use_linear=self.use_linear, init_std=self.init_std,
            param_dtype=self.param_dtype,
            min_target=self.min_target, max_target=self.max_target,
        )

    def gather_rows(self, params: dict, ids: jax.Array):
        """One gather per field → list of F ``[B, rank+1]`` rows."""
        cd = self.cdtype
        return [rows_lib.gather(params["vw"][f], ids[:, f]).astype(cd)
                for f in range(self.num_fields)]

    def deep_scores(self, mlp, h: jax.Array) -> jax.Array:
        """The MLP head over ``h = concat(xv) [B, F*rank]`` → ``[B]``.

        The one precision rule of the head (forward, the ``jax.vjp``
        pullback, eval, predict and the sharded step all come through
        here): its products run at the precision ``compute_dtype``
        declares. float32 asks for float32 products
        (``precision=HIGHEST``; on the TPU the default for float32
        operands is one bfloat16 pass); bfloat16 compute keeps the MXU's
        single pass.
        """
        cd = self.cdtype
        precision = (jax.lax.Precision.HIGHEST if cd == jnp.float32
                     else None)
        n_hidden = len(self.mlp_dims)
        for li, layer in enumerate(mlp):
            h = jnp.dot(h, layer["kernel"].astype(cd),
                        precision=precision) + layer["bias"].astype(cd)
            if li < n_hidden:
                h = jax.nn.relu(h)
        return h[:, 0]

    def head_scores(self, dense: dict, h: jax.Array) -> jax.Array:
        """The head over ``h = concat(xv) [B, F*rank]`` → ``[B]``;
        ``dense`` holds the ``dense_keys`` (the bias is not the head's).
        The fused body's forward and its ``jax.vjp`` come through here."""
        return self.deep_scores(dense["mlp"], h)

    def scores(self, params: dict, ids: jax.Array, vals: jax.Array) -> jax.Array:
        if ids.shape[1] != self.num_fields:
            raise ValueError(
                f"batch has {ids.shape[1]} slots, spec has "
                f"{self.num_fields} fields"
            )
        cd = self.cdtype
        vals_c = vals.astype(cd)
        rows = self.gather_rows(params, ids)
        k = self.rank
        xvs = [r[:, :k] * vals_c[:, f : f + 1] for f, r in enumerate(rows)]
        score = 0.0
        if self.fm_interaction:
            s = sum(xvs)
            sum_sq = sum(jnp.sum(x * x, axis=1) for x in xvs)
            score = 0.5 * (jnp.sum(s * s, axis=1) - sum_sq)
        if self.use_linear:
            score = score + sum(
                r[:, k] * vals_c[:, f] for f, r in enumerate(rows)
            )
        if self.use_bias:
            score = score + params["w0"].astype(cd)
        h = jnp.concatenate(xvs, axis=1)                  # [B, F*k]
        return score + self.head_scores(
            {key: params[key] for key in self.dense_keys}, h)

    def predict(self, params: dict, ids: jax.Array, vals: jax.Array) -> jax.Array:
        return base.predict_from_scores(self, self.scores(params, ids, vals))
