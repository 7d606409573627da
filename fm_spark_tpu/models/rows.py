"""The form a per-field table is held in on the device: who asks, the
rule, the walk there, the way back, and how the models read rows of it.

The TPU lays a tall narrow ``f32[rows, w]`` out dimension-0-minor unless
``w`` is a whole number of 128-lane tiles, and XLA's gather and scatter
read rows: a program handed such a table copies all of it first (a
training step transposed every table in and out, 12% of config 3's step
and 50% of avazu's; a predict program spent 99% of a dispatch on it:
PERF.md §5). Stating a row-major layout on the jit removes the copies,
but an executable READ BACK from the persistent compile cache returns
its results in the default layout again (jax 0.9.0 / libtpu 0.0.34;
PERF.md §6), and every entry point runs with that cache on. So a holder
keeps such a table in a SHAPE whose DEFAULT layout is row-major, and
nobody states a layout. Three holders, one canonical tree (what
``spec.init`` gives, checkpoints hold and the models score):

- the one-chip training loop (``cli._place_field_state``,
  ``sparse.lower_field_sparse_step``) WRITES its tables and holds them
  ``padded``: zero columns up to whole lanes, byte for byte what a
  row-major ``[rows, w]`` occupies. Its bodies cut gathered rows to the
  model's ``w`` (``sparse._rows_for``) and every write pads its rows
  back with zeros (``ops/scatter._to_table_width``), so the arithmetic
  between runs at the model's width and the padding stays zero;
- the scorer (``serve.PredictEngine``) only reads, holds nothing but
  tables and cannot pay 128 lanes for 65 columns: ``padded`` where that
  costs at most 1/8 more bytes (FFM's 369 -> 384), else ``packed``
  (:class:`PackedTable`: 65 -> two rows a 128-lane line, 17 -> eight;
  fewer bytes than the canonical table, whose 65 columns sit in 72
  sublanes). It has no scatter, so a holder that writes never takes it;
- the field-sharded mesh (``parallel/field_step.py``) holds a third
  form, all of a chip's fields stacked ``[F_local, bucket, w]``, which
  this module does not own yet: it still enters and leaves its step
  bucket-minor (PERF.md §5; ROADMAP Speed 6).

Where the device's default is row-major already (every table on the
CPU; a width of whole lanes; a ``[rows]`` vector) a table is held
``as_is``; so is a width that neither pads nor packs: slower, never
wrong. :func:`held_form` is the rule, :func:`hold` the walk,
:func:`canonical` the way back.

The specs read every table through :func:`gather`, which tells a packed
table from an array by the leaf's TYPE while tracing: a plain array
takes ``table[ids]``, the text a step lowered with plain tables has
always had.
"""

from __future__ import annotations

import functools

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


# --------------------------------------------------------------------------
# Which form, the walk there, the way back.
# --------------------------------------------------------------------------

def _pad_lanes(table):
    return jnp.pad(table, ((0, 0), (0, -table.shape[1] % LANES)))


_FORMERS = {"packed": jax.jit(PackedTable.pack), "padded": _pad_lanes}
FORMS = (*_FORMERS, "as_is")


@functools.lru_cache(maxsize=None)
def default_is_row_major(shape, dtype, device) -> bool:
    """Does ``device`` lay a ``dtype[shape]`` array out row-major when
    nobody says how? Asked of the compiler (a program that only makes
    such an array), so it holds for a described device too."""
    made = jax.jit(
        lambda: jnp.zeros(shape, dtype),
        out_shardings=jax.sharding.SingleDeviceSharding(device),
    ).lower().compile()
    layout = made.output_formats.layout
    return layout is None or (
        tuple(layout.major_to_minor) == tuple(range(len(shape))))


def held_form(shape, dtype, device, writes: bool) -> str:
    """Which of :data:`FORMS` a ``dtype[shape]`` table takes on
    ``device``. ``writes``: the holder scatters into the table (the
    training loop) and takes ``padded`` wherever the default is not
    row-major; a holder that only reads (the scorer) takes the first of
    ``padded`` (if at most 1/8 more bytes) and ``packed`` whose default
    layout there is row-major, else ``as_is``."""
    def row_major(shape):
        return default_is_row_major(tuple(shape), jnp.dtype(dtype), device)

    if len(shape) != 2 or shape[1] % LANES == 0 or row_major(shape):
        return "as_is"
    if writes:
        return "padded"
    rows, width = shape
    lanes = width + -width % LANES
    if (lanes - width) * 8 <= width and row_major((rows, lanes)):
        return "padded"
    p = packed_columns(width)
    if p and rows * p % LANES == 0 and row_major((rows * p // LANES, LANES)):
        return "packed"
    return "as_is"


def _device_of(leaf):
    """Where a leaf is or is described to be; the default device for a
    NumPy array or a bare shape."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        return next(iter(sharding.device_set))
    return jax.config.jax_default_device or jax.local_devices()[0]


def hold(params, keys, *, writes: bool, consume: bool = False):
    """``(held, shapes, report)``: the canonical tree ``params`` on the
    device with every leaf under the parameter keys ``keys`` in its
    :func:`held_form`, the canonical tree's shapes
    (``jax.ShapeDtypeStruct``, what a checkpoint of this model restores
    into and :func:`canonical` cuts back to) and what was done
    (``tables_packed``, ``tables_padded``, ``tables_as_is``,
    ``resident_table_bytes``: the held tree's bytes, which are its
    tables and the little else a model has).

    ``keys`` are the caller's to say: the scorer's are
    ``spec.row_tables`` (what the spec reads through :func:`gather`),
    the training loop's ``("vw",)`` (the tables whose rows its fused
    bodies cut on read and pad on write). One table at a time, each that
    changes form waited for (buffers are allocated as work is queued,
    ahead of the device: first for whatever makes the table, then for
    its held one), so that the transient on the device is one table and
    not all of them. ``consume``: each such table is deleted as soon as
    its held one is there, so no second copy of the tables stands beside
    the first; otherwise the caller's arrays stay alive and unchanged.
    Shapes (``jax.ShapeDtypeStruct``) go through as arrays do and come
    back as shapes: the tree a program is compiled against for a device
    that is described and not attached."""
    keys = set(keys)
    report = dict.fromkeys((f"tables_{form}" for form in FORMS), 0)
    report["resident_table_bytes"] = 0
    shapes = []

    def place(path, leaf):
        described = isinstance(leaf, jax.ShapeDtypeStruct)
        form = "as_is"
        if getattr(path[0], "key", None) in keys:
            form = held_form(leaf.shape, leaf.dtype, _device_of(leaf), writes)
            report[f"tables_{form}"] += 1
        if form == "as_is":
            out = leaf if described else jax.device_put(leaf)
        elif described:
            out = jax.tree.map(
                lambda part: jax.ShapeDtypeStruct(
                    part.shape, part.dtype, sharding=leaf.sharding),
                jax.eval_shape(_FORMERS[form], leaf))
        else:
            jax.block_until_ready(leaf)
            out = jax.block_until_ready(_FORMERS[form](leaf))
            if consume and isinstance(leaf, jax.Array):
                leaf.delete()
        parts = jax.tree.leaves(out)
        report["resident_table_bytes"] += sum(
            part.size * part.dtype.itemsize for part in parts)
        shapes.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                           sharding=parts[0].sharding))
        return out

    held = jax.tree_util.tree_map_with_path(place, params)
    return held, jax.tree.unflatten(jax.tree.structure(params), shapes), report


def canonical(held, shapes, release: bool = False):
    """The canonical tree of ``held`` (``shapes``: :func:`hold`'s), or
    of what a step made of it: a packed table unpacked, a padded one cut
    to the width it came with, every other leaf as it is. ``release``:
    the caller is done with ``held``, and each table that changes form
    goes as soon as its canonical one is on the device (never two
    generations of the tables)."""
    def back(shape, leaf):
        if isinstance(leaf, PackedTable):
            table = leaf.unpack()
        elif leaf.shape != shape.shape:
            table = leaf[:, :shape.shape[1]]
        else:
            return leaf
        if release:
            jax.block_until_ready(table)
            for part in jax.tree.leaves(leaf):
                part.delete()
        return table

    return jax.tree.map(back, shapes, held)
