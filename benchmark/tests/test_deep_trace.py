"""The head's device time out of a recorded trace: by the scopes the
trace states, and by result shapes where it states none."""

import os

import pytest

from benchmark import deep_trace as dt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEEP = os.path.join(DATA, "small_deep.xplane.pb")
PLAIN = os.path.join(DATA, "small_1chip.xplane.pb")


def test_part_of_reads_the_outermost_scope():
    assert dt.part_of("jit(_step)/jvp(deep/forward)/dot_general:") == "forward"
    assert dt.part_of("jit(_step)/deep/backward/transpose(jvp(deep/forward))"
                      "/dot_general:") == "backward"
    assert dt.part_of("jit(_step)/deep/adam/sub:") == "adam"
    assert dt.part_of("jit(_step)/scatter-add:") is None
    assert dt.part_of(None) is None


def test_head_shapes_of_config_5():
    assert dt.head_shapes(16384, (624, 400, 400, 400, 1)) == [
        "f32[16384,400]", "f32[16384,624]", "f32[400,1]", "f32[400,400]",
        "f32[624,400]"]


def test_the_files_own_bytes_give_each_events_op_name():
    """``ProfileData`` hands out no event metadata; the wire-format
    reader does (``small_1chip.xplane.pb``, record_xplane.py: the
    scatter-add in the while, the gather of the plain program)."""
    scopes = dt.op_scopes(PLAIN)
    assert list(scopes) == ["/device:TPU:0"]
    by_stem = {name.split(" = ")[0]: op
               for name, op in scopes["/device:TPU:0"].items()}
    assert by_stem["%fusion.18"] == (
        "jit(looped)/while/body/closed_call/scatter-add:")
    assert by_stem["%fusion"] == "jit(plain)/gather:"
    assert by_stem["%multiply_reduce_fusion"] == "jit(plain)/reduce_sum:"
    # Copies and the while itself state no op name.
    assert not any(s.startswith(("%copy", "%while")) for s in by_stem)


def test_recorded_deep_step_by_scope():
    """``small_deep.xplane.pb`` (record_deep_xplane.py on a v5e): three
    steps of the program's fused DeepFM step at 5 fields, rank 4, a
    16-16-16 head, batch 128. The numbers are what the recording
    printed; 14 events a step lie in the head's scopes."""
    got = dt.head_seconds(DEEP, dt.head_shapes(128, (20, 16, 16, 16, 1)))
    assert got["selected_by"] == "scope" and got["chips"] == 1
    assert got["events"] == 42
    assert got["parts"] == pytest.approx(
        {"forward": 1.797e-06, "backward": 5.511e-06, "adam": 6.67e-07},
        rel=1e-3)
    assert got["seconds"] == pytest.approx(7.975e-06, rel=1e-3)
    # The gathers and scatters beside the head are nobody's.
    stated = dt.op_scopes(DEEP)["/device:TPU:0"]
    assert any("scatter-add" in op for op in stated.values())
    assert not any(dt.part_of(op) for op in stated.values()
                   if "scatter-add" in op or "/gather" in op)


def test_without_scopes_shapes_tell_the_events():
    # The plain recording states no head scope: the scatter-adds into the
    # [4096, 128] table stand in for a head told by its result shape
    # (3 rounds x 3 scatters + the copies of that shape: 30 events).
    got = dt.head_seconds(PLAIN, ["f32[4096,128]"])
    assert got["selected_by"] == "shape" and got["events"] == 30
    assert list(got["parts"]) == ["head"]
    assert got["seconds"] == pytest.approx(1.93263e-04, rel=1e-3)
    assert dt.head_seconds(PLAIN, ["f32[7,7]"]) is None
