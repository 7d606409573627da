"""Field-partitioned xDeepFM: a Compressed Interaction Network beside a
ReLU stack and the linear term, over DeepFM's tables.

Lian et al., "xDeepFM: Combining Explicit and Implicit Feature
Interactions for Recommender Systems", KDD 2018 (arXiv:1803.05170). For
one example with field-local ids ``c_1..c_m`` and values ``x_1..x_m``,
``E_f`` field f's table (``rank`` factor columns, then the linear weight
in column ``rank``, DeepFM's layout):

1. ``e_f = x_f * E_f[c_f][:rank]``; ``X^0 = [e_1; ...; e_m]``, ``[m, D]``
   with ``D = rank``;
2. the CIN (eq. 6), for k = 1..K, ``H_0 = m``, no bias, identity
   activation: ``X^k[h] = sum_{i, j} W^k[h, i, j] (X^{k-1}[i] * X^0[j])``,
   ``*`` elementwise over the ``D`` columns;
3. sum pooling (eq. 7): ``p^k[h] = sum_d X^k[h, d]``; every layer's
   ``p^k`` reaches the output, ``p+ = [p^1; ...; p^K]``;
4. the DNN: ``a_0 = concat_f e_f``, ``a_l = relu(a_{l-1} K_l + b_l)``;
5. the logit (eq. 9): ``w0 + sum_f x_f E_f[c_f][rank] + w_dnn . a_L +
   w_cin . p+``.

The CIN is laid out for the MXU with the pair (d, example) on the lanes:
``X^k`` is ``[H_k, D * B]``, lane ``d * B + b``; layer k's Hadamard
products are one ``[m * H_{k-1}, D * B]`` block, row ``j * H_{k-1} + i``
(``cin/outer``), and the compression one product of ``W^k``, read as
``[H_k, m * H_{k-1}]``, with it (``cin/compress``). Every lane is
dense, so the forward hands each block to its product as it was built
(where ``H_{k-1}`` is a multiple of 8); the kernels are stored ``[H_k,
H_{k-1}, m]`` all the same. The pooling, a sum over a leading axis, and
the output weight are ``cin/pool``. The last layer's maps feed no next
layer and are only pooled, and the compression is linear, so that layer
pools first: ``p^K = W^K . sum_d (X^0[j, d] * X^{K-1}[i, d])``, a
``[m * H_{K-1}, B]`` block, a D-th of the others', and a D-th of their
products, forward and back.

The training split is DeepFM's (``sparse.make_field_deepfm_sparse_body``
takes the spec's head): the rows by the sparse SGD write, ``{w0, cin,
mlp}`` by Adam; the step reports its CIN's pooled maps, summed over the
batch, on every log line (``cin_pooled``). The spec subclasses :class:`FieldDeepFMSpec` for its
tables, its gather and its scoring path; ``fm_interaction`` is False, so
no FM term is computed anywhere, and it has no mesh step
(``cli._FIELD_CAPS``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec
from fm_spark_tpu.models.stacks import ReluStacks


def glorot_uniform(key, shape, fan_in: int, fan_out: int) -> jax.Array:
    """``U(-sqrt(6 / (fan_in + fan_out)), +...)`` of ``shape``, float32."""
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


@dataclasses.dataclass(frozen=True)
class FieldXDeepFMSpec(ReluStacks, FieldDeepFMSpec):
    """xDeepFM over field-partitioned tables: ``cin_layers`` feature maps
    a CIN layer, ``mlp_dims`` the DNN's hidden widths."""

    mlp_dims: tuple = (400, 400)
    cin_layers: tuple = (200, 200, 200)

    dense_keys = ("w0", "cin", "mlp")
    fm_interaction = False

    def __post_init__(self):
        super().__post_init__()
        if not self.cin_layers or min(self.cin_layers) < 1:
            raise ValueError(
                f"FieldXDeepFMSpec has at least one CIN layer of >= 1 "
                f"feature maps (got {self.cin_layers})")
        if not self.mlp_dims:
            raise ValueError("FieldXDeepFMSpec has at least one DNN layer")

    @property
    def cin_dims(self) -> tuple:
        """``(H_0, H_1, ..., H_K)``, ``H_0 = m``."""
        return (self.num_fields, *self.cin_layers)

    @property
    def dnn_dims(self) -> tuple:
        return (self.num_fields * self.rank, *self.mlp_dims)

    def cin_outer_elems_per_step(self, batch: int) -> int:
        """Hadamard products a step's forward forms (gauge
        ``train/cin_outer_elems_per_step``): ``B D sum_k H_{k-1} m``. The
        last layer's are summed over the ``D`` columns as they are formed,
        and never held as a block."""
        m, dims = self.num_fields, self.cin_dims
        return batch * self.rank * sum(h * m for h in dims[:-1])

    def cin_product_flops_per_step(self, batch: int) -> int:
        """Operations of the CIN's matrix products one training step runs
        (gauge ``train/cin_product_flops_per_step``): as
        :meth:`mxu_flops_per_step` counts them, but the last layer, which
        pools first, meets ``B`` columns where the others meet ``D B``:
        ``6 B (D sum_{k<K} H_{k-1} m H_k + H_{K-1} m H_K)``."""
        m, dims = self.num_fields, self.cin_dims
        per_column = [a * m * b for a, b in zip(dims[:-1], dims[1:])]
        return 6 * batch * (self.rank * sum(per_column[:-1])
                            + per_column[-1])

    def mxu_flops_per_step(self, batch: int) -> int:
        """Operations of one training step's matrix products over
        ``batch`` examples as the model states them (gauge
        ``train/mxu_flops_per_step``, which the benchmark holds to its own
        count): per kernel element a multiply-add forward, one for the
        input's gradient and one for the kernel's, 2 operations each. A
        CIN kernel element meets every one of the ``D`` columns, eq. 6 as
        written, although the last layer runs a D-th of that
        (:meth:`cin_product_flops_per_step`); the DNN's input and the
        CIN's are the rows, so every product has its input gradient. The
        Hadamard products and the pooling are elementwise."""
        m, dims = self.num_fields, self.cin_dims
        cin = self.rank * sum(a * m * b for a, b in zip(dims[:-1], dims[1:]))
        dnn = sum(a * b for a, b in zip(self.dnn_dims[:-1],
                                        self.dnn_dims[1:]))
        outputs = self.mlp_dims[-1] + sum(self.cin_layers)
        return 6 * batch * (cin + dnn + outputs)

    def init(self, rng: jax.Array) -> dict:
        """DeepFM's tables from the same key (``N(0, init_std)`` factors,
        zero linear weights), the DNN He-normal with zero biases, the CIN
        kernels and both output vectors Glorot-uniform."""
        k_emb, k_head = jax.random.split(rng)
        params = self._field_fm_spec().init(k_emb)
        k_mlp, k_dnn_out, k_cin, k_cin_out = jax.random.split(k_head, 4)
        dims = self.dnn_dims
        layers = []
        for key, d_in, d_out in zip(jax.random.split(k_mlp, len(dims) - 1),
                                    dims[:-1], dims[1:]):
            layers.append({
                "kernel": jax.random.normal(key, (d_in, d_out), jnp.float32)
                * jnp.sqrt(2.0 / d_in),
                "bias": jnp.zeros((d_out,), jnp.float32),
            })
        last = dims[-1]
        params["mlp"] = {"layers": layers,
                         "out": glorot_uniform(k_dnn_out, (last,), last, 1)}
        m, cin = self.num_fields, self.cin_dims
        kernels = [glorot_uniform(key, (h, h_prev, m), h_prev * m, h)
                   for key, h_prev, h in zip(
                       jax.random.split(k_cin, len(cin) - 1),
                       cin[:-1], cin[1:])]
        pooled = sum(self.cin_layers)
        params["cin"] = {"layers": kernels,
                         "out": glorot_uniform(k_cin_out, (pooled,), pooled,
                                               1)}
        return params

    def cin(self, kernels, x0: jax.Array) -> jax.Array:
        """Points 2 and 3: ``x0`` is ``X^0`` as ``[B * D, m]`` (row ``b *
        D + d`` holds column d of example b's field embeddings) → ``p+``
        ``[B, sum_k H_k]``."""
        cd = self.cdtype
        rows, m = x0.shape
        d, batch = self.rank, rows // self.rank
        with jax.named_scope("cin/outer"):
            # [m, D * B], lane d * B + b: d-major, so pooling is a sum
            # over a leading axis.
            x0t = x0.reshape(batch, d, m).transpose(2, 1, 0).reshape(
                m, d * batch)
        x, pooled = x0t, []
        for k, w in enumerate(kernels, start=1):
            h, h_prev, _ = w.shape
            # The last layer's maps are only pooled: pooling before the
            # (linear) compression leaves B columns where there were D * B.
            last = k == len(kernels)
            with jax.named_scope("cin/outer"):
                # Row j * H_{k-1} + i holds X^0[j] * X^{k-1}[i] (the
                # last layer's summed over the D columns): a free
                # merge of leading axes wherever H_{k-1} is a multiple
                # of 8. A broadcast multiply, not an einsum, which would
                # lower to one more dot_general.
                if last:
                    z = (x0t.reshape(m, 1, d, batch)
                         * x.reshape(1, h_prev, d, batch)).sum(axis=2)
                else:
                    z = x0t[:, None, :] * x[None, :, :]
                z = z.reshape(m * h_prev, -1)
            with jax.named_scope("cin/compress"):
                x = jax.lax.dot_general(
                    jnp.swapaxes(w, 1, 2).reshape(h, m * h_prev).astype(cd),
                    z, (((1,), (0,)), ((), ())), precision=self._precision)
            with jax.named_scope("cin/pool"):
                pooled.append(x if last
                              else x.reshape(h, d, batch).sum(axis=1))
        with jax.named_scope("cin/pool"):
            return jnp.concatenate(pooled, axis=0).T

    def cin_input(self, h: jax.Array) -> jax.Array:
        """``X^0`` as :meth:`cin` takes it, ``[B * D, m]``, from ``h =
        concat_f e_f`` ``[B, m * D]``."""
        batch, m, d = h.shape[0], self.num_fields, self.rank
        return jnp.swapaxes(h.reshape(batch, m, d), 1, 2).reshape(batch * d, m)

    def head_scores(self, dense: dict, h: jax.Array) -> jax.Array:
        """Points 2 to 5 but the bias and the linear term: ``h = concat_f
        e_f`` ``[B, m * D]`` → ``w_dnn . a_L + w_cin . p+``, ``[B]``. Eval,
        predict and the scorer come through here, the fused body through
        :meth:`head_scores_and_stats`."""
        return self.head_scores_and_stats(dense, h)[0]

    def head_scores_and_stats(self, dense: dict, h: jax.Array):
        """:meth:`head_scores` and what a training step reports of its CIN
        (the fused body's forward and ``jax.vjp`` take this):
        ``{"cin_pooled": the batch's sum of each pooled map, [sum_k H_k]}``,
        summed from the ``p+`` the score takes."""
        cd = self.cdtype
        p = self.cin(dense["cin"]["layers"], self.cin_input(h))
        with jax.named_scope("cin/pool"):
            cin = jnp.dot(p, dense["cin"]["out"].astype(cd),
                          precision=self._precision)
            stats = {"cin_pooled": jnp.sum(p.astype(jnp.float32), axis=0)}
        mlp = dense["mlp"]
        a = self._stack(mlp["layers"], h, relu_last=True)
        return jnp.dot(a, mlp["out"].astype(cd),
                       precision=self._precision) + cin, stats
