"""The update's device time out of a recorded trace, by the scopes the
trace states; a trace that states none gives nothing, not a guess."""

import os

import pytest

from benchmark import deep_trace
from benchmark import opt_trace as ot

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OPT = os.path.join(DATA, "small_opt.xplane.pb")
PLAIN = os.path.join(DATA, "small_1chip.xplane.pb")
DEEP = os.path.join(DATA, "small_deep.xplane.pb")


def test_part_of_reads_the_outermost_scope():
    assert ot.part_of("jit(step)/opt/coalesce/jit(argsort)/sort:") == (
        "coalesce")
    assert ot.part_of("jit(step)/while/body/opt/gather/gather:") == "gather"
    assert ot.part_of("jit(step)/while/body/opt/rule/mul:") == "rule"
    assert ot.part_of("jit(step)/while/body/opt/write/scatter:") == "write"
    assert ot.part_of("jit(step)/gather:") is None
    assert ot.part_of("jit(_step)/deep/adam/sub:") is None
    assert ot.part_of(None) is None


def test_recorded_adagrad_step_by_scope():
    """``small_opt.xplane.pb`` (record_opt_xplane.py on a v5e): three
    steps of the program's fused FieldFFM AdaGrad step at 5 fields, rank
    4, 64 buckets, batch 128. The numbers are what the recording
    printed; 100 events a step lie in the update's scopes."""
    got = ot.update_seconds(OPT)
    assert got["chips"] == 1 and got["events"] == 300
    assert list(got["parts"]) == list(ot.PARTS)
    assert got["parts"] == pytest.approx(
        {"coalesce": 1.02805e-04, "gather": 5.252e-06, "rule": 2.36e-07,
         "write": 2.5306e-05}, rel=1e-3)
    assert got["seconds"] == pytest.approx(1.33599e-04, rel=1e-3)
    # The forward's gathers beside the update are nobody's, and the
    # update's own lie inside the chunk loop's body.
    stated = deep_trace.op_scopes(OPT)["/device:TPU:0"]
    assert "jit(step)/gather:" in stated.values()
    assert "jit(step)/while/body/opt/gather/gather:" in stated.values()
    assert not any(ot.part_of(op) for op in stated.values()
                   if op.startswith("jit(step)/gather"))


@pytest.mark.parametrize("xplane", [PLAIN, DEEP])
def test_a_trace_without_the_scopes_gives_nothing(xplane):
    # Scatters and gathers of the same shapes, no opt/* scope: the
    # reader does not tell the update by shape.
    assert ot.update_seconds(xplane) is None
