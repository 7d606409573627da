"""The schedule is a fixed amount of work, replayed bit for bit by a seed."""

import threading

import numpy as np

from benchmark import loadgen, synthetic

SIZES = {"median": 32, "sigma": 1.0, "min": 1, "max": 512}


def schedule(seed, stream=2, schedule_seed=22):
    return loadgen.make_schedule(requests_per_s=500, seconds=4.0, seed=seed,
                                 schedule_seed=schedule_seed, stream=stream,
                                 rows_per_request=SIZES, pool_rows=4096)


def test_same_seed_replays_identical_requests():
    a, b = schedule(7), schedule(7)
    assert synthetic.checksum(a.due, a.rows, a.offset) == synthetic.checksum(
        b.due, b.rows, b.offset)
    pa = synthetic.zipf_pool(4096, 39, 1 << 18, 7)
    pb = synthetic.zipf_pool(4096, 39, 1 << 18, 7)
    assert synthetic.checksum(*pa) == synthetic.checksum(*pb)


def test_seeds_meet_the_same_trace_with_other_rows():
    a, b = schedule(7), schedule(8)
    assert len(a) == len(b) == 2000
    # One trace of instants and sizes for every run ...
    assert np.array_equal(a.due, b.due) and np.array_equal(a.rows, b.rows)
    # ... the seed picks the rows (and the pool they are cut from).
    assert not np.array_equal(a.offset, b.offset)
    assert synthetic.checksum(*synthetic.zipf_pool(4096, 39, 64, 7)) \
        != synthetic.checksum(*synthetic.zipf_pool(4096, 39, 64, 8))
    # Another trace is the same work in another order.
    c = schedule(7, schedule_seed=23)
    assert c.total_rows == a.total_rows and sorted(c.rows) == sorted(a.rows)
    assert not np.array_equal(c.rows, a.rows)
    assert not np.array_equal(c.due, a.due)
    assert np.all(np.diff(a.due) >= 0) and a.due[-1] < 4.0
    assert a.rows.min() >= 1 and a.rows.max() <= 512
    assert np.median(a.rows) == 32
    assert (a.offset + a.rows).max() <= 4096
    # The warm-up's draw is apart from the window's.
    assert not np.array_equal(schedule(7, stream=1).due, a.due)


class _Future:
    def __init__(self, value):
        self._value = value

    def result(self, timeout=None):
        if isinstance(self._value, Exception):
            raise self._value
        return self._value


def test_play_answers_every_request_once_and_counts_failures():
    sched = loadgen.make_schedule(requests_per_s=400, seconds=0.5, seed=3,
                                  schedule_seed=22, stream=2,
                                  rows_per_request=SIZES, pool_rows=4096)
    ids, vals = synthetic.zipf_pool(4096, 5, 64, 3)
    seen = []
    lock = threading.Lock()

    def submit(i, v):
        with lock:
            seen.append(len(i))
            n = len(seen)
        return _Future(TimeoutError("late") if n == 10 else i[:, 0].copy())

    played = loadgen.play(submit, ids, vals, sched, grace_seconds=1.0)
    assert seen == sched.rows.tolist()
    assert [e[0] for e in played.errors] == [9]
    for k, answer in enumerate(played.answers):
        if k == 9:
            assert answer is None and np.isnan(played.done[k])
        else:
            lo = sched.offset[k]
            assert np.array_equal(answer, ids[lo:lo + sched.rows[k], 0])
    sent_late = played.sent - (played.t0 + sched.due)
    assert np.all(sent_late >= 0)           # never before it is due
