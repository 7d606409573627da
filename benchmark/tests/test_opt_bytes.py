"""The update's byte floor: each unique row and its accumulator row, read
once and written once."""

import pytest

from benchmark import opt_bytes
from benchmark.layer_metrics import opt_update_hbm_roofline, opt_update_ms


def test_the_issues_count():
    # 13,150 unique rows of 369 float32 columns with float32 slots:
    # 4 x U x 369 x 4 B = 77.6 MB, 0.095 ms at 819 GB/s.
    moved = opt_bytes.update_bytes(unique_rows=13150, row_width=369,
                                   param_bytes=4)
    assert moved == 4 * 13150 * 369 * 4 == 77_637_600
    assert opt_bytes.least_update_seconds(
        unique_rows=13150, row_width=369, param_bytes=4,
        hbm_bytes_per_s=819e9) == pytest.approx(9.48e-5, rel=1e-3)


def test_slots_stay_float32_under_bfloat16_parameters():
    assert opt_bytes.update_bytes(
        unique_rows=10, row_width=5, param_bytes=2) == 10 * 5 * 2 * (2 + 4)
    assert opt_bytes.DTYPE_BYTES == {"float32": 4, "bfloat16": 2}


class Run:
    def __init__(self, log, traced, peak={"hbm_bytes_per_s": 819e9}):
        self.log, self.traced, self.peak = log, traced, peak


def test_the_readers_divide_by_the_steps_and_the_peak():
    run = Run({"opt_update": {"seconds": 0.75}, "opt_update_bytes": 77.6e6},
              {"seconds": 4.0, "steps": 150})
    assert opt_update_ms.read(run) == pytest.approx(5.0)
    # 77.6 MB at 819 GB/s is 0.0947 ms of a 5 ms update: 1.9%.
    assert opt_update_hbm_roofline.read(run) == pytest.approx(1.895, rel=1e-3)


@pytest.mark.parametrize("log,traced", [
    ({}, {"seconds": 4.0, "steps": 150}),                 # no scope stated
    ({"opt_update": {"seconds": 0.75}}, None),            # untraced
    ({"opt_update": {"seconds": 0.75}}, {"seconds": 4.0, "steps": 0}),
])
def test_nothing_to_read_is_none_not_a_guess(log, traced):
    run = Run(log, traced)
    assert opt_update_ms.read(run) is None
    assert opt_update_hbm_roofline.read(run) is None


def test_no_counter_no_roofline():
    run = Run({"opt_update": {"seconds": 0.75}}, {"seconds": 4.0, "steps": 150})
    assert opt_update_ms.read(run) == pytest.approx(5.0)
    assert opt_update_hbm_roofline.read(run) is None
