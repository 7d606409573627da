"""Per-coordinate AdaGrad on the rows a minibatch touches, by automatic
differentiation of a model's ``scores`` — the training reference for
``avazu_ffm_r16_adagrad``: FFM trained the way its paper trains it (Juan,
Zhuang, Chin, Lin, Field-aware Factorization Machines for CTR Prediction,
RecSys 2016, section 3.1 and Algorithm 1; libffm after it).

The forward is ``reference/ffm.py``'s ``scores`` (eq. 4), unchanged. The
state is the touched rows, as in ``reference/sgd.py`` (``rows[f, u]`` is
row ``uniq[f, u]`` of field f's table, ``inv[b, f]`` says which ``u``
example b uses), an accumulator ``G`` of the same shape, and the bias.
One step over a batch of B examples:

- the objective is ``reference/sgd.py``'s, the program's: the MEAN
  logistic loss, plus ``reg_factors / 2 * |factors|^2 + reg_linear / 2 *
  linear^2`` for every OCCURRENCE of a row in the batch;
- ``g_bar[f, u]`` is its gradient with respect to row u of field f: by
  construction the SUM over the row's occurrences (autodiff through
  ``rows_u[f][inv[:, f]]`` coalesces them), the L2 term inside it, as
  the paper's ``g = lambda * w + kappa * ...`` has it;
- ``G <- G + g_bar^2``, then ``row <- row - eta * g_bar / (sqrt(G) +
  1e-8)`` with the UPDATED ``G``. A coordinate whose ``g_bar`` is
  exactly 0 keeps its bits, row and accumulator;
- ``w0 <- w0 - eta * (dL/dw0 + reg_bias * w0)``: the bias keeps plain
  SGD (one scalar needs no per-coordinate rate);
- the loss a step reports is the mean logistic loss before its update.

Departures from Algorithm 1, each also in the configuration's
``assumed``:

- minibatches with each unique row's gradient coalesced, where libffm
  steps example by example (what TensorFlow's and PyTorch's sparse
  AdaGrad do with duplicate indices);
- ``G`` starts at ``init_accumulator`` = 1/B^2 (2^-26 at B = 8,192), the
  paper's ``G0 = 1`` written against a mean: Algorithm 1 accumulates
  per-example gradients of the un-averaged loss, this objective's row
  gradients are B times smaller, and ``eta * g_sum / sqrt(1 + sum
  g_sum^2) = eta * g_bar / sqrt(1/B^2 + sum g_bar^2)`` with ``g_sum = B *
  g_bar``. The ``1e-8`` floor stands outside the root;
- ``lambda`` multiplies every occurrence of a row against a batch-MEAN
  loss, as the program's objective has it: in the paper's per-example
  terms that is ``lambda * B``;
- rank 16 for the paper's 4; hashed buckets; a linear term and a bias,
  which the paper's FFM lacks; ``N(0, init_std)`` factors where libffm
  draws uniformly from ``[0, 1/sqrt(k)]``; no instance-wise
  normalisation.

Initial rows are ``reference/sgd.py``'s mirror of the program's
``spec.init``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.sgd import init_rows, touched  # noqa: F401

EPS = 1e-8      # the floor beside sqrt(G), outside the root


def train(scores, rank: int, factor_cols: int, rows0, inv, vals, labels, *,
          steps: int, learning_rate: float, reg_factors: float,
          reg_linear: float, reg_bias: float, init_accumulator: float,
          keep_after: tuple = (), compute_dtype: str = "float32") -> dict:
    """``steps`` full-batch steps from ``rows0`` / zero bias / ``G =
    init_accumulator`` everywhere. Returns NumPy::

        {"losses": [steps], "rows": [F, U, w], "slots": [F, U, w],
         "w0": float, "after": {n: {"rows", "slots", "w0"} ...}}

    ``after[n]`` is the state as it stood after n steps (the check reads
    the program early as well as late). ``compute_dtype`` is "float32"
    for the reference proper; a caller that asks what the nearest
    precision below would read passes "bfloat16": the rows and values
    rounded to it before the forward, as the program's ``compute_dtype``
    would (state, accumulators and the rule stay float32)."""
    batch, fields = inv.shape
    cd = jnp.dtype(compute_dtype)
    data = tuple(map(jnp.asarray, (inv, vals, labels)))

    def objective(rows_u, w0, inv, vals, labels):
        rows = [rows_u[f][inv[:, f]].astype(cd) for f in range(fields)]
        s = scores(rows, w0.astype(cd), vals.astype(cd),
                   rank).astype(jnp.float32)
        per = jnp.logaddexp(0.0, s) - labels * s
        reg = sum(0.5 * reg_factors
                  * jnp.sum(r[:, :factor_cols].astype(jnp.float32) ** 2)
                  + 0.5 * reg_linear
                  * jnp.sum(r[:, factor_cols:].astype(jnp.float32) ** 2)
                  for r in rows)
        loss = jnp.sum(per) / batch
        return loss + reg, loss

    # The batch is an argument, not a constant of the program: the
    # compiled step is the same for every seed, and the compile cache's.
    @jax.jit
    def step(rows_u, acc, w0, *data):
        (_, loss), (g_rows, g_w0) = jax.value_and_grad(
            objective, argnums=(0, 1), has_aux=True)(rows_u, w0, *data)
        acc = acc + g_rows * g_rows
        rows_u = rows_u - learning_rate * g_rows / (jnp.sqrt(acc) + EPS)
        w0 = w0 - learning_rate * (g_w0 + reg_bias * w0)
        return rows_u, acc, w0, loss

    def host(rows_u, acc, w0):
        return {"rows": np.asarray(rows_u), "slots": np.asarray(acc),
                "w0": float(w0)}

    rows_u = jnp.asarray(rows0, jnp.float32)
    acc = jnp.full(rows_u.shape, init_accumulator, jnp.float32)
    w0 = jnp.zeros((), jnp.float32)
    losses, after = [], {}
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            rows_u, acc, w0, loss = step(rows_u, acc, w0, *data)
            losses.append(float(loss))
            if i + 1 in keep_after:
                after[i + 1] = host(rows_u, acc, w0)
    return {"losses": np.asarray(losses), **host(rows_u, acc, w0),
            "after": after}
