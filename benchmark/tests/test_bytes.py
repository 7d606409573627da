"""bytes.py against counts worked by hand for both configurations."""

from benchmark import bytes as step_bytes


def test_fm_r64_step_bytes():
    # 131,072 examples x 39 fields = 5,111,808 lanes; rows of 65 float32.
    got = step_bytes.train_step_bytes(batch=131072, fields=39, row_width=65,
                                      param_bytes=4)
    assert got == {
        "gather_rows_read": 1_329_070_080,      # 5,111,808 * 65 * 4
        "update_rows_read": 1_329_070_080,
        "update_rows_written": 1_329_070_080,
        "ids": 20_447_232,                      # 5,111,808 * 4
        "vals_labels_weights": 21_495_808,      # + 2 * 131,072 * 4
    }
    assert sum(got.values()) == 4_029_153_280
    # 4.029 GB at 819 GB/s is 4.92 ms; four chips share a 4x batch.
    one = step_bytes.least_step_seconds(
        batch=131072, fields=39, row_width=65, param_bytes=4, chips=1,
        hbm_bytes_per_s=819e9)
    four = step_bytes.least_step_seconds(
        batch=524288, fields=39, row_width=65, param_bytes=4, chips=4,
        hbm_bytes_per_s=819e9)
    assert abs(one - 4.91960e-3) < 1e-7 and abs(one - four) < 1e-12


def test_ffm_r16_step_bytes():
    # 8,192 x 23 = 188,416 lanes; rows of 23 * 16 + 1 = 369 float32.
    got = step_bytes.train_step_bytes(batch=8192, fields=23, row_width=369,
                                      param_bytes=4)
    assert got["gather_rows_read"] == 278_102_016   # 188,416 * 369 * 4
    assert got["ids"] == 753_664
    assert got["vals_labels_weights"] == 753_664 + 65_536
    assert sum(got.values()) == 3 * 278_102_016 + 753_664 + 819_200
