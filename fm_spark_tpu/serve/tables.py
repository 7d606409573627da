"""The form a scorer holds a generation's tables in.

A predict program handed a table the device does not lay out row-major
copies the whole table before it gathers a few hundred rows of it: 99%
of a dispatch of config 3 on the v5e (PERF.md §5). :func:`install` puts
a canonical parameter tree (what ``spec.init`` gives and checkpoints
hold) on the device in a form whose DEFAULT layout is row-major, chosen
per table from its shape, its dtype and the device's own answer
(``sparse._default_is_row_major``, asked of the compiler):

- row-major already (every table on the CPU; a width of whole lanes; a
  ``[rows]`` vector): held as it is;
- whole lanes cost at most 1/8 more bytes (FFM's 369 -> 384): padded
  with zero columns, a plain array the models read as they read any;
- else packed, ``128 / p`` rows to a line and the left-over column on
  its own (:class:`~fm_spark_tpu.models.rows.PackedTable`): 65 -> two
  rows a line, 17 -> eight. Fewer bytes on the chip than the canonical
  table, whose 65 columns sit in 72 sublanes.

A width that neither pads nor packs keeps today's placement: slower,
never wrong. :func:`unpack` is the way back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fm_spark_tpu import sparse
from fm_spark_tpu.models.rows import LANES, PackedTable, packed_columns

__all__ = ["FORMS", "install", "serving_form", "unpack"]

def _pad_lanes(table):
    return jnp.pad(table, ((0, 0), (0, -table.shape[1] % LANES)))


_FORMERS = {"packed": jax.jit(PackedTable.pack), "padded": jax.jit(_pad_lanes),
            "as_is": jax.device_put}
FORMS = tuple(_FORMERS)


def serving_form(shape, dtype, device) -> str:
    """Which of :data:`FORMS` a ``dtype[shape]`` table takes on
    ``device``: the first whose default layout there is row-major and
    whose bytes the rule allows."""
    def row_major(shape):
        return sparse._default_is_row_major(tuple(shape), jnp.dtype(dtype),
                                            device)

    if len(shape) != 2 or row_major(shape):
        return "as_is"
    rows, width = shape
    lanes = width + -width % LANES
    if (lanes - width) * 8 <= width and row_major((rows, lanes)):
        return "padded"
    p = packed_columns(width)
    if p and rows * p % LANES == 0 and row_major((rows * p // LANES, LANES)):
        return "packed"
    return "as_is"


def install(spec, params):
    """``(served, shapes, held)``: ``params`` on the device in serving
    form, the canonical tree's shapes (``jax.ShapeDtypeStruct``, what a
    checkpoint of this model restores into), and what was installed
    (``tables_packed``, ``tables_padded``, ``tables_as_is``,
    ``resident_table_bytes``: the served tree's bytes, which are its
    tables and the little else a model has).

    Only the leaves under ``spec.row_tables`` change form: the ones the
    spec reads through ``rows.gather``. ``params`` is NOT consumed: the
    caller's arrays stay alive and unchanged. One table at a time, each
    that changes form waited for, so that the transient on the device is
    one table and not all of them. Shapes with a sharding
    (``jax.ShapeDtypeStruct``) go through as arrays do and come back as
    shapes: the tree a program is compiled against for a device that is
    described and not attached."""
    keys = set(spec.row_tables)
    held = dict.fromkeys((f"tables_{form}" for form in FORMS), 0)
    held["resident_table_bytes"] = 0
    shapes = []

    def place(path, leaf):
        described = isinstance(leaf, jax.ShapeDtypeStruct)
        form = "as_is"
        if getattr(path[0], "key", None) in keys:
            on = (next(iter(leaf.sharding.device_set))
                  if described or isinstance(leaf, jax.Array)
                  else jax.config.jax_default_device or jax.local_devices()[0])
            form = serving_form(leaf.shape, leaf.dtype, on)
            held[f"tables_{form}"] += 1
        if described:
            out = jax.tree.map(
                lambda part: jax.ShapeDtypeStruct(
                    part.shape, part.dtype, sharding=leaf.sharding),
                leaf if form == "as_is"
                else jax.eval_shape(_FORMERS[form], leaf))
        else:
            out = _FORMERS[form](leaf)
            if form != "as_is":
                jax.block_until_ready(out)
        parts = jax.tree.leaves(out)
        held["resident_table_bytes"] += sum(
            part.size * part.dtype.itemsize for part in parts)
        shapes.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                           sharding=parts[0].sharding))
        return out

    served = jax.tree_util.tree_map_with_path(place, params)
    return served, jax.tree.unflatten(jax.tree.structure(params), shapes), held


def unpack(served, shapes):
    """The canonical tree of ``served`` (``shapes``: :func:`install`'s):
    a packed table unpacked, a padded one cut to its width."""
    def back(shape, leaf):
        if isinstance(leaf, PackedTable):
            return leaf.unpack()
        return leaf[:, :shape.shape[1]] if leaf.shape != shape.shape else leaf

    return jax.tree.map(back, shapes, served)
