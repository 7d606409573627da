"""DeepFM (Guo, Tang, Ye, Li, He, IJCAI 2017, eq. 1-4) and the step the
program descends, written out: ``y = y_FM + y_DNN`` over ONE shared
embedding.

One active feature per field f with value ``x_f``; ``rows[f]`` is ``[B,
rank + 1]``: the factor vector ``v_f`` of example b's feature in field f,
then its linear weight ``w_f``.

- ``y_FM = w0 + sum_f w_f x_f + sum_{f<g} <v_f, v_g> x_f x_g`` (eq. 2),
  pair by pair as ``reference/fm.py`` writes it (the program goes through
  the O(k n) identity);
- ``a_0 = concat_f(x_f v_f)`` (eq. 3's embedding layer, ``fields * rank``
  wide), ``a_{l+1} = relu(a_l W_l + b_l)`` for the hidden layers,
  ``y_DNN = a_H W_H + b_H`` (eq. 4 without its sigmoid: eq. 1 takes it
  once, over the sum);
- the loss is the mean logistic loss of ``y_FM + y_DNN``.

Departures from the paper (the configuration's file lists them under
``assumed``), each because it is what the program implements:

- rank 16 where the paper's embeddings are 10 wide, 39 x 2^18 hashed
  buckets where the paper keeps Criteo's own vocabulary, three hidden
  layers of 400 (the paper's Criteo network, its "constant" shape);
- no dropout (the paper trains with 0.5; the program has none);
- TWO optimizers in one step: the tables by plain SGD at the
  configuration's rate, with L2 ``reg_factors`` (and ``reg_linear``)
  counted once per OCCURRENCE of a row in the batch; ``{w0, W_l, b_l}`` by
  Adam (b1 0.9, b2 0.999, eps 1e-8, bias-corrected, at the same rate),
  written out in :func:`train`, with ``reg_factors * p`` added to the
  gradient of every kernel AND bias and ``reg_bias * w0`` to the bias's
  (the paper trains everything with Adam and no L2);
- He-normal kernels, zero biases, ``N(0, init_std)`` factors, zero linear
  weights and bias (the paper does not state its initialisation).

Everything here is ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (:func:`train` sets it; on a
TPU a float32 product is otherwise one bfloat16 pass), and nothing is
imported from the program. Initial values mirror the program's key
splits (``FieldDeepFMSpec.init``: the seed's key splits into an embedding
key, itself split per field, and a head key, itself split per layer): the
reference has to start where ``cli train --seed`` starts, and the program
hands out no initial parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def row_width(fields: int, rank: int) -> int:
    return rank + 1


def factor_columns(fields: int, rank: int) -> int:
    """Leading columns of a row that are factors (the rest is linear)."""
    return rank


def head_dims(fields: int, rank: int, mlp_dims) -> tuple:
    """Layer widths of the dense head, input to output."""
    return (fields * rank, *mlp_dims, 1)


def fm_scores(rows, w0, vals, rank: int):
    """Eq. 2. ``rows``: F arrays ``[B, rank + 1]``; ``vals``: ``[B, F]``."""
    xv = jnp.stack([r[:, :rank] * vals[:, f:f + 1]
                    for f, r in enumerate(rows)], axis=1)        # [B, F, k]
    pair = jnp.einsum("bik,bjk->bij", xv, xv,
                      precision=jax.lax.Precision.HIGHEST)
    upper = jnp.triu(jnp.ones(pair.shape[1:], pair.dtype), k=1)
    linear = sum(r[:, rank] * vals[:, f] for f, r in enumerate(rows))
    return w0 + linear + jnp.sum(pair * upper, axis=(1, 2))


def deep_scores(head, rows, vals, rank: int):
    """Eq. 3-4 over the SAME factor vectors. ``head``: a list of
    ``{"kernel": [d_in, d_out], "bias": [d_out]}``, the last 1 wide. Its
    products take the precision of the enclosing
    ``jax.default_matmul_precision``."""
    a = jnp.concatenate([r[:, :rank] * vals[:, f:f + 1]
                         for f, r in enumerate(rows)], axis=1)   # [B, F k]
    for layer in head[:-1]:
        a = jnp.maximum(a @ layer["kernel"] + layer["bias"], 0.0)
    return (a @ head[-1]["kernel"] + head[-1]["bias"])[:, 0]


def scores(rows, w0, head, vals, rank: int):
    """Eq. 1 before its sigmoid."""
    return fm_scores(rows, w0, vals, rank) + deep_scores(head, rows, vals,
                                                         rank)


# ------------------------------------------------------- initial values


def _keys(seed: int):
    """``(embedding key, head key)``, as ``FieldDeepFMSpec.init`` splits."""
    k_emb, k_head = jax.random.split(jax.random.key(seed))
    return k_emb, k_head


def init_rows(seed: int, uniq: np.ndarray, bucket: int, rank: int,
              init_std: float) -> jax.Array:
    """``[F, U, rank + 1]`` float32: the program's initial rows ``uniq``
    of each field's table."""
    @jax.jit
    def field(key, rows):
        table = jax.random.normal(key, (bucket, rank), jnp.float32) * init_std
        picked = table[rows]
        return jnp.concatenate(
            [picked, jnp.zeros((picked.shape[0], 1), jnp.float32)], axis=1)

    keys = jax.random.split(_keys(seed)[0], uniq.shape[0])
    return jnp.stack([field(keys[f], jnp.asarray(uniq[f]))
                      for f in range(uniq.shape[0])])


def init_head(seed: int, dims) -> list:
    """He-normal kernels and zero biases for ``dims`` (:func:`head_dims`)."""
    keys = jax.random.split(_keys(seed)[1], len(dims) - 1)
    return [{"kernel": jax.random.normal(keys[i], (d_in, d_out), jnp.float32)
             * jnp.sqrt(2.0 / d_in),
             "bias": jnp.zeros((d_out,), jnp.float32)}
            for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:]))]


# -------------------------------------------------------------- the step


def train(rows0, head0, inv, vals, labels, *, rank: int, steps: int,
          learning_rate: float, reg_factors: float, reg_linear: float,
          reg_bias: float, keep_after: tuple = (),
          matmul_precision: str = "highest") -> dict:
    """``steps`` full-batch steps from ``rows0`` / ``head0`` / zero bias:
    SGD on the touched rows (``rows[f, u]`` is row ``uniq[f, u]`` of field
    f's table, ``inv[b, f]`` says which ``u`` example b uses), Adam on
    ``{w0, head}``. The loss a step reports is the mean logistic loss
    before its update. Returns NumPy::

        {"losses": [steps], "rows": [F, U, w], "w0": float, "head": [...],
         "after": {n: {"rows", "w0", "head"} for n in keep_after}}

    ``after[n]`` is the state as it stood after n steps (the comparison
    reads it early as well as late, ``drivers/train_deep.py``).
    ``matmul_precision`` is "highest" for the reference proper; a caller
    that asks what a lower precision would read passes "default" (one
    bfloat16 pass on a TPU)."""
    batch, fields = inv.shape
    data = tuple(map(jnp.asarray, (inv, vals, labels)))
    tree = jax.tree_util.tree_map

    def objective(rows_u, dense, inv, vals, labels):
        rows = [rows_u[f][inv[:, f]] for f in range(fields)]
        s = scores(rows, dense["w0"], dense["head"], vals, rank)
        per = jnp.logaddexp(0.0, s) - labels * s
        reg = sum(0.5 * reg_factors * jnp.sum(r[:, :rank] ** 2)
                  + 0.5 * reg_linear * jnp.sum(r[:, rank:] ** 2)
                  for r in rows)
        loss = jnp.sum(per) / batch
        return loss + reg, loss

    # The batch is an argument, not a constant of the program: the
    # compiled step is the same for every seed, and the compile cache's.
    @jax.jit
    def step(rows_u, dense, m, v, t, *data):
        (_, loss), (g_rows, g) = jax.value_and_grad(
            objective, argnums=(0, 1), has_aux=True)(rows_u, dense, *data)
        g = {"w0": g["w0"] + reg_bias * dense["w0"],
             "head": tree(lambda gg, p: gg + reg_factors * p, g["head"],
                          dense["head"])}
        m = tree(lambda a, gg: ADAM_B1 * a + (1 - ADAM_B1) * gg, m, g)
        v = tree(lambda a, gg: ADAM_B2 * a + (1 - ADAM_B2) * gg * gg, v, g)
        dense = tree(
            lambda p, a, b: p - learning_rate * (a / (1 - ADAM_B1 ** t))
            / (jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS),
            dense, m, v)
        return rows_u - learning_rate * g_rows, dense, m, v, loss

    def host(rows_u, dense):
        return {"rows": np.asarray(rows_u), "w0": float(dense["w0"]),
                "head": tree(np.asarray, dense["head"])}

    rows_u = jnp.asarray(rows0)
    dense = {"w0": jnp.zeros((), jnp.float32),
             "head": tree(jnp.asarray, head0)}
    m, v = tree(jnp.zeros_like, dense), tree(jnp.zeros_like, dense)
    losses, after = [], {}
    with jax.default_matmul_precision(matmul_precision):
        for i in range(steps):
            rows_u, dense, m, v, loss = step(rows_u, dense, m, v,
                                             jnp.float32(i + 1), *data)
            losses.append(float(loss))
            if i + 1 in keep_after:
                after[i + 1] = host(rows_u, dense)
    return {"losses": np.asarray(losses), **host(rows_u, dense),
            "after": after}
