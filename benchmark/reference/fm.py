"""Factorization machine (Rendle, ICDM 2010, eq. 1), written out pair by
pair: ``y = w0 + sum_i w_i x_i + sum_{i<j} <v_i, v_j> x_i x_j``.

One active feature per field, so ``rows[f]`` is ``[B, rank + 1]``: the
factor vector of example b's feature in field f, then its linear weight.
The program computes the same score through the O(k n) identity; this
does not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def row_width(fields: int, rank: int) -> int:
    return rank + 1


def factor_columns(fields: int, rank: int) -> int:
    """Leading columns of a row that are factors (the rest is linear)."""
    return rank


def scores(rows, w0, vals, rank: int):
    """``rows``: F arrays ``[B, rank + 1]``; ``vals``: ``[B, F]``."""
    xv = jnp.stack([r[:, :rank] * vals[:, f:f + 1]
                    for f, r in enumerate(rows)], axis=1)        # [B, F, k]
    pair = jnp.einsum("bik,bjk->bij", xv, xv,
                      precision=jax.lax.Precision.HIGHEST)
    upper = jnp.triu(jnp.ones(pair.shape[1:], pair.dtype), k=1)
    linear = sum(r[:, rank] * vals[:, f] for f, r in enumerate(rows))
    return w0 + linear + jnp.sum(pair * upper, axis=(1, 2))
