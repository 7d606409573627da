"""The reduction from a trace to busy time, idle gaps, op time and
collective time."""

import os

import numpy as np
import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_counts_overlap_and_nesting_once():
    start = np.asarray([0.0, 1.0, 2.0, 10.0, 12.0, 30.0])
    end = np.asarray([5.0, 3.0, 7.0, 11.0, 20.0, 31.0])
    s, e = tr.merge(start, end)
    assert s.tolist() == [0.0, 10.0, 12.0, 30.0]
    assert e.tolist() == [7.0, 11.0, 20.0, 31.0]
    assert tr.merge(np.zeros(0), np.zeros(0))[0].size == 0


def test_self_time_takes_children_out_of_the_parent():
    # while.1 [0, 100) holds fusion.3 [10, 40) and all-reduce.2 [50, 90),
    # which itself holds copy.7 [60, 70); gather.5 [100, 130) stands alone.
    start = np.asarray([0.0, 10.0, 50.0, 60.0, 100.0])
    end = np.asarray([100.0, 40.0, 90.0, 70.0, 130.0])
    names = ["while.1", "%fusion.3", "all-reduce.2", "copy.7",
             "%gather.5.1 = f32[8,4]{1,0:T(8,128)} gather(f32[64,4]{1,0} %t)"]
    assert tr.self_times(start, end, names) == {
        "while": 30.0, "fusion": 30.0, "all-reduce": 30.0, "copy": 10.0,
        "gather f32[8,4]": 30.0}


def test_gap_labels_say_which_programs_stand_around():
    mod_start = np.asarray([0.0, 100.0, 300.0])
    mod_end = np.asarray([80.0, 250.0, 400.0])
    mods = ["jit_step(123)", "jit_step(123)", "jit_convert_element_type(9)"]
    labels = tr._label_gaps(np.asarray([20.0, 85.0, 260.0]),
                            np.asarray([30.0, 100.0, 300.0]),
                            mod_start, mod_end, mods)
    assert labels == ["in jit_step", "jit_step -> jit_step",
                      "jit_step -> jit_convert_element_type"]
    assert tr._label_gaps(np.asarray([1.0]), np.asarray([2.0]),
                          np.zeros(0), np.zeros(0), []) == ["unattributed"]


def test_names():
    # As the v5e's trace spells them: the instruction's whole text.
    fusion = ("%fusion.18 = f32[4096,128]{1,0:T(8,128)S(1)} fusion(f32[4096,128]"
              "{1,0:T(8,128)S(1)} %get-tuple-element.37, s32[2048]{0:T(1024)S(1)} "
              "%bitcast.7), kind=kCustom, calls=%fused_computation.2.clone.clone")
    loop = ("%while = (s32[]{:T(128)}, f32[4096,128]{1,0:T(8,128)S(1)}) "
            "while((s32[]{:T(128)}, f32[4096,128]{1,0:T(8,128)S(1)}) %tuple.5), "
            "condition=%cond, body=%body")
    psum = ("%psum.3 = f32[524288,64]{1,0:T(8,128)} all-reduce(f32[524288,64]{1,0:"
            "T(8,128)} %add.7), channel_id=2, replica_groups={{0,1,2,3}}, "
            "to_apply=%region_0.1")
    assert tr.parse_op(fusion) == ("fusion f32[4096,128]", "fusion")
    assert tr.parse_op(loop) == ("while (s32[], f32[4096,128])", "while")
    # The name is the program's (lax.psum), the opcode says what it is.
    assert tr.parse_op(psum) == ("psum f32[524288,64]", "all-reduce")
    assert tr.parse_op("%all-to-all.12") == ("all-to-all", "all-to-all")
    assert tr.COLLECTIVE.match("all-reduce-start")
    assert tr.COLLECTIVE.match("collective-permute-done")
    assert not tr.COLLECTIVE.match("fusion")
    assert tr.module_name("jit_step(7816234)") == "jit_step"


def _plane(path, name="/device:TPU:0"):
    from jax.profiler import ProfileData

    return {p.name: p for p in ProfileData.from_file(path).planes}[name]


def test_recorded_one_chip_trace():
    """``data/small_1chip.xplane.pb`` (record_xplane.py on a v5e): three
    rounds of ``jit_looped`` (a while of three scatter-adds into a
    [4096, 128] table, 61.7 us) and ``jit_plain`` (a gather and a reduce,
    7.4 us), the host sleeping 2 ms between them. The numbers below were
    read off the events by hand."""
    path = os.path.join(HERE, "data", "small_1chip.xplane.pb")
    got = tr.reduce(path)
    assert got["chips"] == 1 and got["collective_s"] == 0.0
    # Busy: the union, by a sweep over the sorted end points.
    ops = {line.name: line for line in _plane(path).lines}["XLA Ops"]
    points = sorted([(e.start_ns, 1) for e in ops.events]
                    + [(e.start_ns + e.duration_ns, -1) for e in ops.events])
    busy = depth = 0
    for (t, step), (t_next, _) in zip(points, points[1:]):
        depth += step
        busy += (t_next - t) if depth > 0 else 0
    assert got["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert got["busy_s"] == pytest.approx(207.1e-6, rel=1e-3)
    assert got["span_s"] == pytest.approx(12.815e-3, rel=1e-3)
    # Ranking: nine scatter-add fusions of 18.36 us lead; the while that
    # holds them keeps only its own 170 ns.
    ops = dict(got["device_ops"])
    assert got["device_ops"][0][0] == "fusion f32[4096,128]"
    assert ops["fusion f32[4096,128]"] == pytest.approx(9 * 18.36e-6, rel=1e-3)
    assert ops["while (s32[], f32[4096,128], s32[2048], s32[], f32[])"] < 1e-6
    assert sum(ops.values()) == pytest.approx(got["busy_s"], rel=1e-3)
    # Idle: three sleeps before jit_plain, two returns to jit_looped.
    gaps = dict(got["idle_gaps"])
    assert gaps["jit_looped -> jit_plain"] == pytest.approx(11.08e-3, rel=1e-3)
    assert gaps["jit_plain -> jit_looped"] == pytest.approx(1.526e-3, rel=1e-3)
    assert gaps["in jit_looped"] < 1e-6
    idle = 1.0 - got["busy_s"] / got["span_s"]
    assert idle == pytest.approx(0.98384, abs=1e-4)


def test_recorded_four_chip_trace():
    """``data/small_4chip.xplane.pb``: chip 0 runs the one-chip programs
    too; every chip runs ``jit_across`` three times — an all-to-all
    (9.5-9.9 us), a reduce and a psum (3.2-3.4 us). Chip 1's events, by
    hand: all-to-all 9,522 + 9,941 + 9,586 ns, all-reduce 3,398 + 3,186 +
    3,347 ns, fusions 493 + 493 + 495 ns."""
    got = tr.reduce(os.path.join(HERE, "data", "small_4chip.xplane.pb"))
    assert got["chips"] == 4
    one = got["per_chip"][1]
    assert one["chip"] == 1 and one["modules"] == ["jit_across"]
    assert one["collective_s"] == pytest.approx(38_980e-9, rel=1e-9)
    assert one["busy_s"] == pytest.approx(40_461e-9, rel=1e-9)
    # Means over the chips, as the metrics take them.
    assert got["busy_s"] == pytest.approx(
        sum(c["busy_s"] for c in got["per_chip"]) / 4, rel=1e-12)
    assert got["collective_s"] == pytest.approx(39.98e-6, rel=1e-3)
    ops = dict(got["device_ops"])
    # Named as the program named them, found by their opcode.
    assert ops["all_to_all f32[4,64,512]"] == pytest.approx(29.0e-6, rel=5e-3)
    assert "psum_invariant f32[]" in ops
    assert "jit_across -> jit_across" in dict(got["idle_gaps"])


def test_a_trace_without_device_ops_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    assert tr.reduce(tr.find_xplane(str(tmp_path))) is None
