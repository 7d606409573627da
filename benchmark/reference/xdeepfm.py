"""xDeepFM (Lian, Zhou, Zhang, Chen, Xie, Sun, KDD 2018, arXiv:1803.05170)
at its paper's Criteo settings, and the step the program descends, written
out.

For one example with field-local ids ``c_1..c_m`` and values ``x_1..x_m``,
``rows[f]`` is ``[B, rank + 1]``: the embedding of example b's feature in
field f, then its linear weight.

1. embeddings: ``e_f = x_f * E_f[c_f][:rank]``; ``X^0`` is ``[m, D]``,
   ``D = rank``;
2. the CIN, eq. 6, for k = 1..K with ``H_0 = m``, no bias and the identity
   as activation, written as the equation reads: the outer product ``Z^k
   [H_{k-1}, m, D]`` of every feature map of the layer before with every
   field embedding, elementwise over the ``D`` columns, then its
   contraction with ``W^k [H_k, H_{k-1}, m]`` over both of its indices;
3. sum pooling, eq. 7: ``p^k[h] = sum_d X^k[h, d]``; every layer reaches
   the output, ``p+ = [p^1; ...; p^K]``;
4. the DNN: ``a_0 = concat_f e_f``, ``a_l = relu(a_{l-1} K_l + b_l)``;
5. the logit, eq. 9: ``s = w0 + sum_f x_f E_f[c_f][rank] + w_dnn . a_L +
   w_cin . p+``;
6. the loss: the batch mean of ``softplus(s) - y s``, with L2
   ``reg_factors`` per OCCURRENCE of a row on its embedding columns
   (``reg_linear`` on its linear weight) and ``reg_factors * p`` added to
   the gradient of every kernel, bias and output vector of the CIN and the
   DNN (``reg_bias * w0`` to the bias's);
7. the update: Adam (b1 0.9, b2 0.999, eps 1e-8, bias-corrected) on
   ``{w0, cin, mlp}``, plain SGD at the same rate on the batch's unique
   rows, a row met n times taking the sum of its n gradients.

Departures from the paper (the configuration's file lists them under
``assumed``): hashed buckets; two optimizers where the paper runs Adam on
everything; L2 as above where eq. 11 puts ``lambda ||Theta||`` on the
objective; no dropout; the initial values (the paper states none): rows
``N(0, init_std)`` with a zero linear weight, DNN kernels He-normal with
zero biases, CIN kernels and both output vectors Glorot-uniform.

Everything is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` and nothing is imported from the
program. The loss and its gradients are computed in BLOCKS of examples
(``Z^2`` of 4,096 examples is 1.28 GB) and the blocks' gradient sums
added. Initial values mirror the program's key splits
(``FieldXDeepFMSpec.init``: the seed's key splits into an embedding key,
DeepFM's, and a head key, itself split four ways: the DNN's layers, its
output vector, the CIN's layers, its output vector): the reference has to
start where ``cli train --seed`` starts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepfm import ADAM_B1, ADAM_B2, ADAM_EPS, init_rows

__all__ = ["cin_pooled", "init_dense", "init_rows", "row_width", "scores",
           "scores_and_pooled", "train"]


def row_width(fields: int, rank: int) -> int:
    return rank + 1


def cin_dims(fields: int, cin_layers) -> tuple:
    """``(H_0, ..., H_K)``, ``H_0`` the number of fields."""
    return (fields, *cin_layers)


def dnn_dims(fields: int, rank: int, mlp_dims) -> tuple:
    return (fields * rank, *mlp_dims)


def _rounded(x, precision: str):
    """An operand as one pass of the MXU sees it at ``precision``."""
    if precision == "float32":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def cin_pooled(kernels, x0, precision: str = "float32"):
    """Points 2 and 3: ``kernels`` the ``W^k [H_k, H_{k-1}, m]``, ``x0``
    ``X^0`` ``[B, m, D]`` → ``p+`` ``[B, sum_k H_k]``."""
    x, pooled = x0, []
    for w in kernels:
        z = x[:, :, None, :] * x0[:, None, :, :]                # [B, H, m, D]
        x = jnp.einsum("hij,bijd->bhd", _rounded(w, precision),
                       _rounded(z, precision))                  # [B, H_k, D]
        pooled.append(x.sum(axis=2))
    return jnp.concatenate(pooled, axis=1)


def scores(rows, dense, vals, rank: int, precision: str = "float32"):
    """Points 1 to 5. ``rows``: F arrays ``[B, rank + 1]``; ``dense``:
    ``{"w0", "cin": {"layers": [W^k], "out"}, "mlp": {"layers":
    [{"kernel", "bias"}], "out"}}``; ``vals``: ``[B, F]``. ``precision``
    "float32" is the reference proper; "bfloat16" rounds the operands of
    every product as one bfloat16 pass does (the nearest precision
    below)."""
    return scores_and_pooled(rows, dense, vals, rank, precision)[0]


def scores_and_pooled(rows, dense, vals, rank: int,
                      precision: str = "float32"):
    """:func:`scores` and the ``p+`` ``[B, sum_k H_k]`` they take."""
    def mm(subscripts, a, b):
        return jnp.einsum(subscripts, _rounded(a, precision),
                          _rounded(b, precision))

    e = [r[:, :rank] * vals[:, f:f + 1] for f, r in enumerate(rows)]
    linear = sum(r[:, rank] * vals[:, f] for f, r in enumerate(rows))
    pooled = cin_pooled(dense["cin"]["layers"], jnp.stack(e, axis=1),
                        precision)
    cin = mm("bp,p->b", pooled, dense["cin"]["out"])
    a = jnp.concatenate(e, axis=1)                              # [B, m D]
    for layer in dense["mlp"]["layers"]:
        a = jnp.maximum(mm("bi,io->bo", a, layer["kernel"]) + layer["bias"],
                        0.0)
    dnn = mm("bi,i->b", a, dense["mlp"]["out"])
    return dense["w0"] + linear + dnn + cin, pooled


# ------------------------------------------------------- initial values


def _glorot(key, shape, fan_in: int, fan_out: int):
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def init_dense(seed: int, fields: int, rank: int, cin_layers,
               mlp_dims) -> dict:
    """``{"w0", "cin", "mlp"}`` as the program draws them
    (``init_rows`` is DeepFM's: the same tables from the same key)."""
    k_head = jax.random.split(jax.random.key(seed))[1]
    k_mlp, k_dnn_out, k_cin, k_cin_out = jax.random.split(k_head, 4)
    dims = dnn_dims(fields, rank, mlp_dims)
    layers = [{"kernel": jax.random.normal(key, (d_in, d_out), jnp.float32)
               * jnp.sqrt(2.0 / d_in),
               "bias": jnp.zeros((d_out,), jnp.float32)}
              for key, d_in, d_out in zip(
                  jax.random.split(k_mlp, len(dims) - 1), dims[:-1],
                  dims[1:])]
    cin = cin_dims(fields, cin_layers)
    kernels = [_glorot(key, (h, h_prev, fields), h_prev * fields, h)
               for key, h_prev, h in zip(
                   jax.random.split(k_cin, len(cin) - 1), cin[:-1], cin[1:])]
    pooled = sum(cin_layers)
    return {"w0": jnp.zeros((), jnp.float32),
            "cin": {"layers": kernels,
                    "out": _glorot(k_cin_out, (pooled,), pooled, 1)},
            "mlp": {"layers": layers,
                    "out": _glorot(k_dnn_out, (dims[-1],), dims[-1], 1)}}


# -------------------------------------------------------------- the step


def train(rows0, dense0, inv, vals, labels, *, rank: int, steps: int,
          learning_rate: float, reg_factors: float, reg_linear: float,
          reg_bias: float, block: int, keep_after: tuple = (),
          precision: str = "float32") -> dict:
    """``steps`` full-batch steps from ``rows0`` / ``dense0`` and zero
    moments: ``rows[f, u]`` is row ``uniq[f, u]`` of field f's table,
    ``inv[b, f]`` says which ``u`` example b uses; ``block`` examples at a
    time (it divides the batch). The loss a step reports is the mean
    logistic loss before its update, ``pooled`` the batch's sum of each
    map of the ``p+`` that loss took. Returns NumPy::

        {"losses": [steps], "pooled": [steps, sum_k H_k], "rows": [F, U,
         w], "dense": {"w0", "cin", "mlp"}, "m", "v": Adam's moments of
         the dense leaves, "after": {n: the same, after n steps}}
    """
    batch, fields = inv.shape
    if batch % block:
        raise ValueError(f"blocks of {block} do not divide a batch of {batch}")
    tree = jax.tree_util.tree_map

    def objective(rows_u, dense, inv, vals, labels):
        rows = [rows_u[f][inv[:, f]] for f in range(fields)]
        s, pooled = scores_and_pooled(rows, dense, vals, rank, precision)
        per = jnp.sum(jnp.logaddexp(0.0, s) - labels * s)
        reg = sum(0.5 * reg_factors * jnp.sum(r[:, :rank] ** 2)
                  + 0.5 * reg_linear * jnp.sum(r[:, rank:] ** 2)
                  for r in rows)
        return per / batch + reg, (per, jnp.sum(pooled, axis=0))

    # The batch is an argument, not a constant of the program: the
    # compiled step is the same for every seed, and the compile cache's.
    grad = jax.jit(jax.value_and_grad(objective, argnums=(0, 1),
                                      has_aux=True))

    @jax.jit
    def update(rows_u, dense, m, v, t, g_rows, g):
        g = {"w0": g["w0"] + reg_bias * dense["w0"],
             **{key: tree(lambda gg, p: gg + reg_factors * p, g[key],
                          dense[key]) for key in ("cin", "mlp")}}
        m = tree(lambda a, gg: ADAM_B1 * a + (1 - ADAM_B1) * gg, m, g)
        v = tree(lambda a, gg: ADAM_B2 * a + (1 - ADAM_B2) * gg * gg, v, g)
        dense = tree(
            lambda p, a, b: p - learning_rate * (a / (1 - ADAM_B1 ** t))
            / (jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS),
            dense, m, v)
        return rows_u - learning_rate * g_rows, dense, m, v

    def host(rows_u, dense, m, v):
        return {"rows": np.asarray(rows_u), "dense": tree(np.asarray, dense),
                "m": tree(np.asarray, m), "v": tree(np.asarray, v)}

    rows_u, dense = jnp.asarray(rows0), tree(jnp.asarray, dense0)
    m, v = tree(jnp.zeros_like, dense), tree(jnp.zeros_like, dense)
    blocks = [tuple(jnp.asarray(a[lo:lo + block]) for a in (inv, vals, labels))
              for lo in range(0, batch, block)]
    losses, pooled, after = [], [], {}
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            total, summed, g_rows, g = 0.0, 0.0, None, None
            for data in blocks:
                (_, (part, p)), (gr, gd) = grad(rows_u, dense, *data)
                total += float(part)
                summed = summed + np.asarray(p, np.float64)
                g_rows = gr if g_rows is None else g_rows + gr
                g = gd if g is None else tree(jnp.add, g, gd)
            losses.append(total / batch)
            pooled.append(summed)
            rows_u, dense, m, v = update(rows_u, dense, m, v,
                                         jnp.float32(i + 1), g_rows, g)
            if i + 1 in keep_after:
                after[i + 1] = host(rows_u, dense, m, v)
    return {"losses": np.asarray(losses), "pooled": np.stack(pooled),
            **host(rows_u, dense, m, v), "after": after}
