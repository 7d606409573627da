"""From a profiler trace (``*.xplane.pb``) to device busy time, idle gaps,
per-op time and collective time.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the
trace has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops``
holds one event per executed HLO instruction, named by the instruction's
whole text (events nest: a ``while`` contains its body), and whose line
``XLA Modules`` holds one event per executed program
(``jit_step(<fingerprint>)``). Everything here works on those two lines
(``Async XLA Ops`` holds the DMAs between a ``-start`` and its ``-done``,
which overlap the ops and are no work of the core's):

- busy time is the UNION of the op intervals (nesting and overlap count
  once), idle share is ``1 - busy / window`` with the window given by
  the caller (the host-clock length of the traced span);
- an op's time is its SELF time (its duration minus the events nested
  in it), so a parent never counts its children twice; ops are ranked
  as families, the instruction's name without its ``.<n>`` suffix plus
  the shape of its result (``fusion f32[262144,65]``), so the 39
  per-field scatters of one step rank as one entry and the shape says
  which tensor it made;
- collective time is the union of the intervals of collective ops, told
  by the instruction's OPCODE (``all-reduce``): its name is whatever the
  program called it (a ``lax.psum`` is ``%psum.3``);
- an idle gap is the space between two merged busy intervals, labelled
  by the programs around it: ``in <module>`` when one program spans it
  (the device waited between two ops of one program) and
  ``<module> -> <module>`` when the host had not yet launched the next.
  The program carries no host spans yet, and the trace's own host events
  sit on another clock (on the v5e the device's events lead the calls
  that launched them by about 1.2 ms), so that is all a gap can say.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|collective-broadcast)")
_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE_ARGS = re.compile(r"\(.*$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTRUCTION = re.compile(
    r"^%?(?P<name>[^\s=]+) = (?P<type>\([^()]*\)|\S+) (?P<opcode>[\w\-]+)\(")
TOP = 10
LABEL_CHARS = 64


def find_xplane(trace_dir: str) -> str | None:
    """The one ``.xplane.pb`` a ``start_trace(trace_dir)`` session wrote."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _stem(name: str) -> str:
    """``%psum.12`` -> ``psum``."""
    name = name.lstrip("%")
    return _SUFFIX.sub("", name) or name


@functools.lru_cache(maxsize=4096)     # a trace repeats a few hundred texts
def parse_op(text: str) -> tuple[str, str]:
    """``(family, opcode)`` of an op event. ``%psum.3 = f32[8,64]{1,0:T(8,128)}
    all-reduce(...), channel_id=2`` -> ``("psum f32[8,64]", "all-reduce")``:
    the family an op ranks under is its name's stem and its result's
    shape. A bare name (no instruction text) is both."""
    m = _INSTRUCTION.match(_LAYOUT.sub("", text))
    if not m:
        stem = _stem(text.split(" ", 1)[0])
        return stem, stem
    return f"{_stem(m['name'])} {m['type']}"[:LABEL_CHARS], m["opcode"]


def module_name(name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return _MODULE_ARGS.sub("", name).strip() or name


def _events(line):
    """``(start_ns, end_ns, names)`` of a line, sorted by start and,
    among equal starts, the longer (outer) event first."""
    rows = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]
    rows.sort(key=lambda r: (r[0], -r[1]))
    if not rows:
        return np.zeros(0), np.zeros(0), []
    start, end, names = zip(*rows)
    return np.asarray(start, float), np.asarray(end, float), list(names)


def merge(start, end):
    """Union of intervals: ``(starts, ends)`` of the merged, disjoint
    intervals, in order. Input sorted by start."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    reach = np.maximum.accumulate(end)
    # A new merged interval opens wherever an event starts after
    # everything before it has ended.
    opens = np.concatenate([[True], start[1:] > reach[:-1]])
    idx = np.flatnonzero(opens)
    closes = np.concatenate([reach[idx[1:] - 1], reach[-1:]])
    return start[idx], closes


def self_times(start, end, names) -> dict[str, float]:
    """Self nanoseconds per op family (duration minus nested events)."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [end, family, self_ns]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + max(item[2], 0.0)

    for s, e, name in zip(start, end, names):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, parse_op(name)[0], e - s])
    while stack:
        close(stack.pop())
    return out


def _label_gaps(gap_start, gap_end, mod_start, mod_end, mod_names):
    """One label per gap, from the programs around it (module docstring)."""
    labels = []
    for g0, g1 in zip(gap_start, gap_end):
        if len(mod_start) == 0:
            labels.append("unattributed")
            continue
        # The last program that started at or before the gap.
        i = int(np.searchsorted(mod_start, g0, side="right")) - 1
        before = module_name(mod_names[i]) if i >= 0 else "trace start"
        if i >= 0 and mod_end[i] >= g1:
            labels.append(f"in {before}")
            continue
        # A program's event opens a few ns before its first op, so the
        # one that ends the gap is simply the next to start.
        after = (module_name(mod_names[i + 1]) if i + 1 < len(mod_names)
                 else "trace end")
        labels.append(f"{before} -> {after}")
    return labels


def reduce_plane(plane) -> dict | None:
    """One chip's plane -> busy / collective seconds, self seconds per
    op family and labelled idle gaps; None if it has no op line."""
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return None
    start, end, names = _events(lines[OPS_LINE])
    if len(start) == 0:
        return None
    busy_start, busy_end = merge(start, end)
    coll = np.asarray([bool(COLLECTIVE.match(parse_op(n)[1]))
                       for n in names])
    c_start, c_end = merge(start[coll], end[coll])
    gap_start, gap_end = busy_end[:-1], busy_start[1:]
    if MODULES_LINE in lines:
        m_start, m_end, m_names = _events(lines[MODULES_LINE])
    else:
        m_start, m_end, m_names = np.zeros(0), np.zeros(0), []
    gaps: dict[str, float] = {}
    for label, g0, g1 in zip(
            _label_gaps(gap_start, gap_end, m_start, m_end, m_names),
            gap_start, gap_end):
        gaps[label] = gaps.get(label, 0.0) + (g1 - g0) * 1e-9
    return {
        "busy_s": float(np.sum(busy_end - busy_start)) * 1e-9,
        "span_s": float(busy_end[-1] - busy_start[0]) * 1e-9,
        "collective_s": float(np.sum(c_end - c_start)) * 1e-9,
        "ops": {k: v * 1e-9 for k, v in
                self_times(start, end, names).items()},
        "gaps": gaps,
        "events": len(names),
        "modules": sorted({module_name(n) for n in m_names}),
    }


def _top(totals: dict[str, float]) -> list[list]:
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[k, float(v)] for k, v in ranked]


def reduce(xplane_path: str) -> dict | None:
    """The whole trace -> means over the chips that ran anything::

        {"chips": n, "busy_s", "collective_s", "span_s",
         "device_ops": [[family, seconds] x <=10],
         "idle_gaps":  [[label, seconds] x <=10], "per_chip": [...]}

    None when no chip's plane holds an op (a CPU trace, or a window in
    which nothing ran on the device)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    chips = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        got = reduce_plane(plane)
        if got is not None:
            chips.append((int(m.group(1)), got))
    if not chips:
        return None
    chips.sort(key=lambda c: c[0])
    n = len(chips)
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for _, c in chips:
        for k, v in c["ops"].items():
            ops[k] = ops.get(k, 0.0) + v / n
        for k, v in c["gaps"].items():
            gaps[k] = gaps.get(k, 0.0) + v / n
    mean = lambda key: sum(c[key] for _, c in chips) / n   # noqa: E731
    return {
        "chips": n,
        "busy_s": mean("busy_s"),
        "collective_s": mean("collective_s"),
        "span_s": mean("span_s"),
        "device_ops": _top(ops),
        "idle_gaps": _top(gaps),
        "per_chip": [{"chip": i, "busy_s": c["busy_s"],
                      "collective_s": c["collective_s"],
                      "events": c["events"], "modules": c["modules"]}
                     for i, c in chips],
    }
