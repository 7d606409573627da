"""Field-aware factorization machine (Juan et al., RecSys 2016, eq. 4):
``y = w0 + sum_i w_i x_i + sum_{i<j} <v_{i,f_j}, v_{j,f_i}> x_i x_j``.

One active feature per field, so ``rows[f]`` is ``[B, F * rank + 1]``:
for example b's feature in field f, the factor vector it uses against
each field j in columns ``[j * rank, (j + 1) * rank)``, then its linear
weight.
"""

from __future__ import annotations

import jax.numpy as jnp


def row_width(fields: int, rank: int) -> int:
    return fields * rank + 1


def factor_columns(fields: int, rank: int) -> int:
    return fields * rank


def scores(rows, w0, vals, rank: int):
    """``rows``: F arrays ``[B, F * rank + 1]``; ``vals``: ``[B, F]``."""
    fields = len(rows)
    # v[b, i, j, :] = x_i * (factor of field i's feature against field j)
    v = jnp.stack([r[:, :fields * rank].reshape(-1, fields, rank)
                   * vals[:, f, None, None] for f, r in enumerate(rows)],
                  axis=1)
    pair = jnp.sum(v * jnp.swapaxes(v, 1, 2), axis=-1)           # [B, F, F]
    upper = jnp.triu(jnp.ones(pair.shape[1:], pair.dtype), k=1)
    linear = sum(r[:, fields * rank] * vals[:, f]
                 for f, r in enumerate(rows))
    return w0 + linear + jnp.sum(pair * upper, axis=(1, 2))
