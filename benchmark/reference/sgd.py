"""Plain minibatch SGD on the logistic loss, by automatic differentiation
of a model's ``scores`` — the training reference.

The program's fused steps hand-write the backward pass, dedup or scatter
the row updates and (on a mesh) split the batch's fields over chips; this
differentiates the written-out score and applies ``p -= lr * dL/dp``.
Only the rows a batch touches change, so the state is the touched rows:
``rows[f, u]`` is row ``uniq[f, u]`` of field f's table, and ``inv[b, f]``
says which ``u`` example b uses.

The objective one step descends (what the program's update implements,
``fm_spark_tpu/sparse.py``): the mean logistic loss, plus for every
OCCURRENCE of a row in the batch ``reg_factors / 2 * |factors|^2 +
reg_linear / 2 * linear^2``, plus ``reg_bias / 2 * w0^2`` once. The loss
a step reports is the mean logistic loss before its update.

Initial rows mirror the program's ``spec.init`` (per-field keys split
from ``jax.random.key(seed)``, ``normal * init_std`` factors, zero
linear weights, zero bias): the reference has to start where ``cli
train --seed`` starts, and the program hands out no parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def touched(ids: np.ndarray):
    """``ids`` ``[B, F]`` field-local -> ``(uniq [F, U], counts [F, U],
    inv [B, F], n_uniq [F])``, ``U`` the largest per-field count rounded
    up to a multiple of 1024; padding repeats row 0 with count 0."""
    per = [np.unique(ids[:, f], return_inverse=True, return_counts=True)
           for f in range(ids.shape[1])]
    n_uniq = np.asarray([len(u) for u, _, _ in per])
    width = int(-(-n_uniq.max() // 1024) * 1024)
    uniq = np.zeros((len(per), width), np.int32)
    counts = np.zeros((len(per), width), np.int64)
    for f, (u, _, c) in enumerate(per):
        uniq[f, :len(u)] = u
        counts[f, :len(u)] = c
    inv = np.stack([i.reshape(-1) for _, i, _ in per], axis=1).astype(np.int32)
    return uniq, counts, inv, n_uniq


def init_rows(seed: int, uniq: np.ndarray, bucket: int, factor_cols: int,
              init_std: float) -> jax.Array:
    """``[F, U, factor_cols + 1]`` float32: the program's initial rows
    ``uniq`` of each field's table."""
    @jax.jit
    def field(key, rows):
        table = jax.random.normal(key, (bucket, factor_cols),
                                  jnp.float32) * init_std
        picked = table[rows]
        return jnp.concatenate(
            [picked, jnp.zeros((picked.shape[0], 1), jnp.float32)], axis=1)

    keys = jax.random.split(jax.random.key(seed), uniq.shape[0])
    return jnp.stack([field(keys[f], jnp.asarray(uniq[f]))
                      for f in range(uniq.shape[0])])


def train(scores, rank: int, factor_cols: int, rows0, inv, vals, labels, *,
          steps: int, learning_rate: float, lr_schedule: str,
          reg_factors: float, reg_linear: float, reg_bias: float,
          chunk: int):
    """``steps`` full-batch SGD steps from ``rows0`` / zero bias.
    Returns ``(losses [steps], rows [F, U, w], w0)`` as NumPy."""
    batch = inv.shape[0]
    fields = inv.shape[1]
    if batch % chunk:
        raise ValueError(f"chunk {chunk} does not divide the batch {batch}")

    def objective(rows_u, w0, inv_c, vals_c, labels_c):
        rows = [rows_u[f][inv_c[:, f]] for f in range(fields)]
        s = scores(rows, w0, vals_c, rank)
        per = jnp.logaddexp(0.0, s) - labels_c * s
        reg = sum(0.5 * reg_factors * jnp.sum(r[:, :factor_cols] ** 2)
                  + 0.5 * reg_linear * jnp.sum(r[:, factor_cols:] ** 2)
                  for r in rows)
        return jnp.sum(per) / batch + reg, jnp.sum(per)

    grad = jax.jit(jax.value_and_grad(objective, argnums=(0, 1),
                                      has_aux=True))

    @jax.jit
    def apply(rows_u, w0, g_rows, g_w0, lr):
        return (rows_u - lr * g_rows,
                w0 - lr * (g_w0 + reg_bias * w0))

    if lr_schedule == "constant":
        lr_at = lambda i: learning_rate                     # noqa: E731
    elif lr_schedule == "inv_sqrt":
        lr_at = lambda i: learning_rate / np.sqrt(i + 1.0)  # noqa: E731
    else:
        raise ValueError(f"the reference knows no lr_schedule {lr_schedule!r}")

    chunks = [(jnp.asarray(inv[lo:lo + chunk]), jnp.asarray(vals[lo:lo + chunk]),
               jnp.asarray(labels[lo:lo + chunk]))
              for lo in range(0, batch, chunk)]
    rows_u, w0 = jnp.asarray(rows0), jnp.zeros((), jnp.float32)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            g_rows, g_w0, loss_sum = None, None, 0.0
            for c in chunks:
                (_, part), (gr, gw) = grad(rows_u, w0, *c)
                g_rows = gr if g_rows is None else g_rows + gr
                g_w0 = gw if g_w0 is None else g_w0 + gw
                loss_sum = loss_sum + part
            losses.append(float(loss_sum) / batch)
            rows_u, w0 = apply(rows_u, w0, g_rows, g_w0,
                               jnp.float32(lr_at(i)))
    return np.asarray(losses), np.asarray(rows_u), float(w0)
