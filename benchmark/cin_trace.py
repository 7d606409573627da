"""The xDeepFM step's CIN out of a profiler trace: the device self time of
the events the program ran under its ``cin/outer`` (the Hadamard
products), ``cin/compress`` (their contraction with the kernels) and
``cin/pool`` (sum pooling and the output weight) named scopes
(``fm_spark_tpu/models/field_xdeepfm.py``), forward and backward apart.

An event's scope is the ``tf_op`` stat of its metadata, which
``deep_trace.op_scopes`` reads from the file's own bytes (that module says
why and how). The forward's ops are ``jit(_step)/jvp(deep/forward)/
cin/compress/dot_general``, the pullback's ``jit(_step)/deep/backward/
transpose(jvp(deep/forward))/cin/compress/dot_general``: the PART is the
cin scope an op name holds, its direction backward where ``deep/backward``
stands before it. Like ``dcn_trace.py`` this reader never tells events by
shape: where a trace states none of the scopes (a program without them, a
profiler that stops writing ``tf_op``) there is nothing to read and
:func:`cin_seconds` says so (None): the metrics are absent from the run's
line.

Time is SELF time on the device's own clock (``trace_reduce.self_times``),
mean over the chips that ran anything of it.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce as tr
from benchmark.deep_trace import op_scopes

PARTS = ("outer", "compress", "pool")
_SCOPE = re.compile("cin/(" + "|".join(PARTS) + ")")
BACKWARD = "deep/backward"
OTHER = "other"


def part_of(tf_op: str | None) -> str | None:
    """``"compress.forward"``, ``"outer.backward"``, ..., or None for an
    op name outside the three scopes."""
    m = _SCOPE.search(tf_op or "")
    if not m:
        return None
    before = tf_op[:m.start()]
    return f"{m.group(1)}.{'backward' if BACKWARD in before else 'forward'}"


def cin_seconds(xplane: str) -> dict | None:
    """``{"seconds", "parts", "events", "chips"}`` of one ``.xplane.pb``:
    the CIN's self seconds over the whole profiled span, mean over the
    chips that ran anything of it, split by :func:`part_of`. None where no
    device plane states one of the scopes."""
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
             for p in sorted({p for c in chips for p in c[0]})}
    return {"seconds": sum(parts.values()), "parts": parts,
            "events": sum(c[1] for c in chips) // n, "chips": n}
