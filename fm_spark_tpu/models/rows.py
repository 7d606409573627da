"""How the field models read rows of a per-field table, and the packed
form a scorer holds a narrow table in.

The TPU lays a tall narrow ``f32[rows, w]`` out dimension-0-minor unless
``w`` is a whole number of 128-lane tiles, and XLA's gather reads rows:
a program handed such a table first copies all of it (PERF.md §5). The
training loop holds its tables lane-padded (``sparse.pad_field_tables``);
a scorer, which holds nothing but tables, cannot pay 128 lanes for 65
columns. :class:`PackedTable` is the form that costs no extra bytes: the
leading ``p`` columns (``p`` the largest power of two <= ``w``) held
``128 / p`` rows to a 128-lane line, the one column left over on its
own. Both parts are row-major by default, so nobody states a layout.

The specs read every table through :func:`gather`, which tells the two
apart by the leaf's TYPE while tracing: a plain array takes
``table[ids]``, the text a step lowered with plain tables has always had.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fm_spark_tpu.ops.vmem import LANES


def packed_columns(width: int) -> int:
    """``p``: the columns of a ``width``-wide row that go into lines (the
    largest power of two <= ``width``), or 0 where packing does not
    apply: ``p`` would fill a line or more, or leave more than one
    column over."""
    p = 1 << (int(width).bit_length() - 1)
    return p if p < LANES and width - p <= 1 else 0


@jax.tree_util.register_pytree_node_class
class PackedTable:
    """A ``[rows, width]`` row table as ``lines [rows * p / 128, 128]``
    (row ``i`` is line ``i // g``, lanes ``(i % g) * p`` onward, ``g =
    128 / p``) and ``rest [rows]``, column ``p`` (None where ``width ==
    p``). A pytree node: the arrays are its leaves, ``width`` is static."""

    def __init__(self, lines, rest, width: int):
        self.lines, self.rest, self.width = lines, rest, int(width)

    def tree_flatten(self):
        return (self.lines, self.rest), self.width

    @classmethod
    def tree_unflatten(cls, width, children):
        return cls(*children, width)

    @classmethod
    def pack(cls, table) -> "PackedTable":
        rows, width = table.shape
        p = packed_columns(width)
        if not p or rows * p % LANES:
            raise ValueError(f"a [{rows}, {width}] table does not pack")
        return cls(table[:, :p].reshape(rows * p // LANES, LANES),
                   table[:, p] if width > p else None, width)

    @property
    def shape(self) -> tuple[int, int]:
        """The table's own ``(rows, width)``."""
        p = packed_columns(self.width)
        return self.lines.shape[0] * (LANES // p), self.width

    def unpack(self):
        """The ``[rows, width]`` table."""
        lead = self.lines.reshape(self.shape[0], -1)
        if self.rest is None:
            return lead
        return jnp.concatenate([lead, self.rest[:, None]], axis=1)

    def gather(self, ids):
        """Rows ``ids`` as ``[B, width]``: the values ``table[ids]``
        gives, ids past the table's edge clamped to it as there."""
        rows, width = self.shape
        p = packed_columns(width)
        g = LANES // p
        ids = jnp.clip(ids, 0, rows - 1)
        line = self.lines[ids // g]                     # [B, 128]
        part = (ids % g)[:, None]
        lead = line[:, :p]
        for j in range(1, g):
            lead = jnp.where(part == j, line[:, j * p:(j + 1) * p], lead)
        if self.rest is None:
            return lead
        return jnp.concatenate([lead, self.rest[ids][:, None]], axis=1)


def gather(table, ids):
    """``table[ids]`` for an array (a ``[rows, w]`` table or a ``[rows]``
    vector), the same rows of a :class:`PackedTable`. The choice is a
    Python ``isinstance`` made while tracing; no op of either path is in
    the other's program."""
    if isinstance(table, PackedTable):
        return _gather_packed(table, ids)
    return table[ids]


# A model reads F tables of one shape: under an inner jit the packed
# read is traced once a program and called F times (XLA inlines it).
# Written out per field it doubled warm-up's tracing (PERF.md §6, PR 30).
_gather_packed = jax.jit(PackedTable.gather)
