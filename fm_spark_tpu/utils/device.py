"""The device a number came from, as JAX reports it.

Every entry point that prints a rate prints this first, so that a
figure is never read without the platform it was taken on.
"""

from __future__ import annotations


def describe() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend
    (initialises it)."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory() -> list[dict]:
    """Per-device ``bytes_in_use`` / ``peak_bytes_in_use``; ``None``
    where the backend keeps no memory statistics (cpu)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def placement(params) -> dict:
    """Where a parameter tree lives: the platforms of its devices, the
    parameter bytes each addressable device holds, and — for the field
    families' ``vw`` tables, a per-field list on one chip and one
    stacked ``[F_pad, bucket, w]`` array on a mesh — how many field
    slots each device holds, the distinct on-device layouts of those
    tables (``major_to_minor``; ``[0, 1]`` is row-major, what the
    one-chip loop holds: models/rows.py) and the bytes they
    occupy on their devices, lane padding included."""
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    nbytes: dict[int, int] = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            nbytes[shard.device.id] = (
                nbytes.get(shard.device.id, 0) + shard.data.nbytes)
    fields: dict[int, int] = {}
    layouts: set[tuple[int, ...]] = set()
    table_bytes = 0
    vw = params.get("vw") if isinstance(params, dict) else None
    for table in jax.tree_util.tree_leaves(vw):
        layout = table.format.layout
        if layout is not None:
            layouts.add(tuple(layout.major_to_minor))
        for shard in table.addressable_shards:
            held = 1 if table.ndim == 2 else shard.data.shape[0]
            fields[shard.device.id] = fields.get(shard.device.id, 0) + held
            table_bytes += shard.data.on_device_size_in_bytes()
    return {
        "platforms": sorted({d.platform for leaf in leaves
                             for d in leaf.devices()}),
        "param_bytes_per_device": {str(k): v
                                   for k, v in sorted(nbytes.items())},
        "fields_per_device": {str(k): v
                              for k, v in sorted(fields.items())},
        "table_layouts": sorted(map(list, layouts)),
        "table_device_bytes": table_bytes,
        "memory": memory(),
    }
