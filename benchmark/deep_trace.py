"""The dense head's device time out of a profiler trace: the self time of
the events the program ran under its ``deep/forward``, ``deep/backward``
and ``deep/adam`` named scopes.

``trace_reduce.reduce`` cannot give this: it ranks op FAMILIES (a name's
stem and its result's shape), keeps ten, and the harness deletes the
trace when it has them. The DeepFM driver therefore calls
:func:`head_seconds` itself, after the window and before it returns.

Which events are the head's is what the trace itself states, where it
does. An event on the ``XLA Ops`` line is named by its instruction's
text, which carries no scope; the scope (``jit(step)/jvp(deep/forward)/
dot_general``: the instruction's ``op_name``) is the ``tf_op`` stat of
the event's METADATA, which ``jax.profiler.ProfileData`` does not hand
out (an event's ``stats`` are its own: offsets and durations). So the
metadata is read from the file's own bytes by :func:`op_scopes`, forty
lines of protobuf wire format (``XSpace.planes[].event_metadata[]
.stats[]``; field numbers from tsl's ``xplane.proto``) that touch nothing
but the two maps they need. Where a trace states no scope for any event
(a program without the scopes, a profiler that stops writing ``tf_op``),
the head's events are told by their result shapes, derived from the
configuration (:func:`head_shapes`), and the result says so
(``"selected_by": "shape"``).

Time is SELF time on the device's own clock (``trace_reduce.self_times``,
so a ``while`` or a ``call`` around the head counts nothing twice), mean
over the chips that ran anything.
"""

from __future__ import annotations

import re

from benchmark import trace_reduce as tr

PARTS = ("forward", "backward", "adam")
_SCOPE = re.compile("deep/(" + "|".join(PARTS) + ")")
OTHER = "other"


# ------------------------------------------------- the file's own bytes


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _map_value(entry):
    """The value message of one ``map<int64, Message>`` entry."""
    for number, value in _fields(entry):
        if number == 2:
            return value
    return memoryview(b"")


def op_scopes(xplane_path: str) -> dict[str, dict[str, str]]:
    """``{plane name: {event name: tf_op}}`` for every event metadata
    that has a ``tf_op`` stat (the instruction's ``op_name``: its scopes
    and its primitive)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for number, plane in _fields(space):
        if number != 1:                               # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:                           # XPlane.name
                name = bytes(value).decode("utf-8", "replace")
            elif number == 4:                         # .event_metadata
                events.append(_map_value(value))
            elif number == 5:                         # .stat_metadata
                ident, text = 0, ""
                for k, v in _fields(_map_value(value)):
                    if k == 1:
                        ident = v
                    elif k == 2:
                        text = bytes(v).decode("utf-8", "replace")
                stat_names[ident] = text
        if not tr.DEVICE_PLANE.match(name):
            continue
        scopes: dict[str, str] = {}
        for meta in events:
            event_name, tf_op = "", None
            for number, value in _fields(meta):
                if number == 2:                       # XEventMetadata.name
                    event_name = bytes(value).decode("utf-8", "replace")
                elif number == 5:                     # .stats
                    which, text = None, None
                    for k, v in _fields(value):
                        if k == 1:                    # XStat.metadata_id
                            which = stat_names.get(v)
                        elif k == 5:                  # .str_value
                            text = bytes(v).decode("utf-8", "replace")
                        elif k == 7:                  # .ref_value
                            text = stat_names.get(v)
                    if which == "tf_op" and text:
                        tf_op = text
            if tf_op is not None:
                scopes[event_name] = tf_op
        out[name] = scopes
    return out


# --------------------------------------------------------- the reduction


def head_shapes(batch: int, dims) -> list[str]:
    """Result shapes only the head makes, as an instruction's text spells
    them: activations and their gradients ``[B, d]``, kernels and their
    gradients and moments ``[d_in, d_out]``."""
    shapes = {f"f32[{batch},{d}]" for d in dims[:-1]}
    shapes |= {f"f32[{a},{b}]" for a, b in zip(dims[:-1], dims[1:])}
    return sorted(shapes)


def part_of(tf_op: str | None) -> str | None:
    """Which of :data:`PARTS` an ``op_name`` lies in. The pullback's ops
    are ``deep/backward/transpose(jvp(deep/forward))/...``: the outermost
    scope, the first, says what ran."""
    m = _SCOPE.search(tf_op or "")
    return m.group(1) if m else None


def _result_type(name: str) -> str:
    m = tr._INSTRUCTION.match(tr._LAYOUT.sub("", name))
    return m["type"] if m else ""


def head_seconds(xplane: str, shapes: list[str]) -> dict | None:
    """``{"seconds", "parts", "selected_by", "events", "chips"}`` of one
    ``.xplane.pb``: the head's self seconds over the whole profiled span,
    mean over the chips that ran anything; ``parts`` splits them by
    :data:`PARTS` where scopes told the events (``"selected_by":
    "scope"``) and is ``{"head": seconds}`` where shapes did. None where
    no device plane holds an op of the head."""
    from jax.profiler import ProfileData

    scopes = op_scopes(xplane)
    by_shape = re.compile("|".join(re.escape(s) for s in shapes))
    chips = []
    for plane in ProfileData.from_file(xplane).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if tr.OPS_LINE not in lines:
            continue
        start, end, names = tr._events(lines[tr.OPS_LINE])
        stated = {name: part for name, op in scopes.get(plane.name,
                                                         {}).items()
                  if (part := part_of(op))}
        if stated:
            how = "scope"
            labels = [stated.get(n, OTHER) for n in names]
        else:
            how = "shape"
            labels = ["head" if by_shape.search(_result_type(n)) else OTHER
                      for n in names]
        took = tr.self_times(start, end, labels)
        took.pop(OTHER, None)
        if took:
            chips.append((how, took,
                          sum(label != OTHER for label in labels)))
    if not chips:
        return None
    n = len(chips)
    parts = {p: float(sum(c[1].get(p, 0.0) for c in chips)) * 1e-9 / n
             for p in sorted({p for c in chips for p in c[1]})}
    return {"seconds": sum(parts.values()), "parts": parts,
            "selected_by": chips[0][0],
            "events": sum(c[2] for c in chips) // n, "chips": n}
