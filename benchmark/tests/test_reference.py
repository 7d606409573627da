"""The plain references against sums written out in float64 NumPy, and
the benchmark's copy of the traffic arithmetic against the program's."""

import itertools

import numpy as np
import pytest

from benchmark import synthetic
from benchmark.reference import ffm, fm, sgd


def test_fm_scores_pair_by_pair():
    rng = np.random.default_rng(0)
    b, f, k = 5, 4, 3
    rows = [rng.normal(size=(b, k + 1)).astype(np.float32) for _ in range(f)]
    vals = rng.uniform(0.5, 1.5, (b, f)).astype(np.float32)
    want = np.full(b, 0.25)
    for e in range(b):
        for i in range(f):
            want[e] += rows[i][e, k] * vals[e, i]
        for i, j in itertools.combinations(range(f), 2):
            want[e] += (np.dot(rows[i][e, :k].astype(np.float64),
                               rows[j][e, :k]) * vals[e, i] * vals[e, j])
    got = np.asarray(fm.scores(rows, np.float32(0.25), vals, k))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)


def test_ffm_scores_pair_by_pair():
    rng = np.random.default_rng(1)
    b, f, k = 4, 3, 2
    rows = [rng.normal(size=(b, f * k + 1)).astype(np.float32)
            for _ in range(f)]
    vals = rng.uniform(0.5, 1.5, (b, f)).astype(np.float32)
    want = np.full(b, -0.5)
    for e in range(b):
        for i in range(f):
            want[e] += rows[i][e, f * k] * vals[e, i]
        for i, j in itertools.combinations(range(f), 2):
            v_ij = rows[i][e, j * k:(j + 1) * k].astype(np.float64)
            v_ji = rows[j][e, i * k:(i + 1) * k]
            want[e] += np.dot(v_ij, v_ji) * vals[e, i] * vals[e, j]
    got = np.asarray(ffm.scores(rows, np.float32(-0.5), vals, k))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)


def test_sgd_step_is_the_gradient_of_the_written_objective():
    """One step on two fields by hand: row 0 of field 0 occurs twice, so
    its regulariser counts twice."""
    k = 1
    rows0 = np.asarray([[[0.5, 0.0], [0.2, 0.0]],
                        [[-0.3, 0.0], [0.1, 0.0]]], np.float32)   # [F, U, w]
    inv = np.asarray([[0, 0], [0, 1], [1, 1]], np.int32)
    vals = np.ones((3, 2), np.float32)
    labels = np.asarray([1.0, 0.0, 1.0], np.float32)
    lr, reg = 0.5, 0.1
    losses, rows, w0 = sgd.train(
        fm.scores, k, k, rows0, inv, vals, labels, steps=1,
        learning_rate=lr, lr_schedule="constant", reg_factors=reg,
        reg_linear=0.0, reg_bias=0.0, chunk=3)
    v = rows0[..., 0].astype(np.float64)
    a, c = v[0][inv[:, 0]], v[1][inv[:, 1]]
    s = a * c
    ds = (1 / (1 + np.exp(-s)) - labels) / 3
    assert losses[0] == pytest.approx(
        np.mean(np.log1p(np.exp(s)) - labels * s), rel=1e-6)
    g0 = np.zeros(2)
    g1 = np.zeros(2)
    for e in range(3):
        g0[inv[e, 0]] += ds[e] * c[e] + reg * a[e]
        g1[inv[e, 1]] += ds[e] * a[e] + reg * c[e]
    np.testing.assert_allclose(rows[0, :, 0], v[0] - lr * g0, rtol=1e-5)
    np.testing.assert_allclose(rows[1, :, 0], v[1] - lr * g1, rtol=1e-5)
    np.testing.assert_allclose(rows[:, :, 1],
                               -lr * np.asarray([[ds[0] + ds[1], ds[2]],
                                                 [ds[0], ds[1] + ds[2]]]),
                               rtol=1e-5)
    assert w0 == pytest.approx(-lr * ds.sum(), rel=1e-5)


def test_touched_pads_with_uncounted_rows():
    ids = np.asarray([[3, 1], [3, 2], [0, 1]], np.int32)
    uniq, counts, inv, n_uniq = sgd.touched(ids)
    assert uniq.shape == counts.shape == (2, 1024)
    assert n_uniq.tolist() == [2, 2]
    assert uniq[0, :2].tolist() == [0, 3] and counts[0, :2].tolist() == [1, 2]
    assert counts[:, 2:].sum() == 0
    assert np.array_equal(uniq[np.arange(2)[None, :], inv], ids)


def test_traffic_copy_draws_what_the_program_draws():
    from fm_spark_tpu import data as data_lib

    for seed in (0, 5):
        args = (512, 39 * 64, 39)
        assert synthetic.checksum(*synthetic.synthetic_ctr(*args, seed=seed)) \
            == synthetic.checksum(*data_lib.synthetic_ctr(*args, seed=seed))
    ids, _, _ = synthetic.synthetic_ctr(64, 23 * 16, 23, seed=1)
    local = synthetic.field_local(ids, 16)
    assert local.min() >= 0 and local.max() < 16
