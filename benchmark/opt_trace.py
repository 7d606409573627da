"""The table update's device time out of a profiler trace: the self time
of the events the program ran under its ``opt/coalesce``, ``opt/gather``,
``opt/rule`` and ``opt/write`` named scopes (``fm_spark_tpu/sparse.py``,
the FieldFFM AdaGrad body).

``trace_reduce.reduce`` ranks op families and knows no scope; an event's
scope is the ``tf_op`` stat of its metadata, which ``deep_trace
.op_scopes`` reads from the file's own bytes (that module says why and
how). Unlike the dense head's reader this one never tells events by
shape: the update's gathers, scatters and elementwise passes have the
shapes of the forward's and the backward's, so where a trace states no
``opt/*`` scope (a program without them, a profiler that stops writing
``tf_op``) there is nothing to read and :func:`update_seconds` says so
(None), and the metric is absent from the run's line.

Time is SELF time on the device's own clock (``trace_reduce
.self_times``), mean over the chips that ran anything of the update.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce as tr
from benchmark.deep_trace import op_scopes

PARTS = ("coalesce", "gather", "rule", "write")
_SCOPE = re.compile("opt/(" + "|".join(PARTS) + ")")
OTHER = "other"


def part_of(tf_op: str | None) -> str | None:
    """Which of :data:`PARTS` an ``op_name`` lies in (the first such
    scope in it, the outermost)."""
    m = _SCOPE.search(tf_op or "")
    return m.group(1) if m else None


def update_seconds(xplane: str) -> dict | None:
    """``{"seconds", "parts", "events", "chips"}`` of one ``.xplane.pb``:
    the update's self seconds over the whole profiled span, mean over the
    chips that ran anything of it, split by :data:`PARTS`. None where no
    device plane states an ``opt/*`` scope."""
    from jax.profiler import ProfileData

    scopes = op_scopes(xplane)
    chips = []
    for plane in ProfileData.from_file(xplane).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if tr.OPS_LINE not in lines:
            continue
        stated = {name: part for name, op in scopes.get(plane.name,
                                                         {}).items()
                  if (part := part_of(op))}
        if not stated:
            continue
        start, end, names = tr._events(lines[tr.OPS_LINE])
        labels = [stated.get(n, OTHER) for n in names]
        took = tr.self_times(start, end, labels)
        took.pop(OTHER, None)
        if took:
            chips.append((took, sum(label != OTHER for label in labels)))
    if not chips:
        return None
    n = len(chips)
    parts = {p: float(sum(c[0].get(p, 0.0) for c in chips)) * 1e-9 / n
             for p in PARTS if any(p in c[0] for c in chips)}
    return {"seconds": sum(parts.values()), "parts": parts,
            "events": sum(c[1] for c in chips) // n, "chips": n}
