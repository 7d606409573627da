"""Batch pipeline: determinism, resume, epoch coverage, padding."""

import threading

import numpy as np
import pytest

from fm_spark_tpu.data import Batches, iterate_once, synthetic_ctr, train_test_split


def _data(n=103, nnz=4, f=40):
    return synthetic_ctr(n, f, nnz, seed=3)


def test_epoch_covers_every_example_once():
    ids, vals, labels = _data()
    b = Batches(ids, vals, labels, batch_size=20, seed=1)
    seen = []
    # 103 examples → 6 batches (last padded with 17 zero-weight slots).
    for _ in range(6):
        bi, bv, bl, bw = b.next_batch()
        order = np.flatnonzero(bw > 0)
        seen.extend(bi[order][:, 0].tolist())
    assert len(seen) == 103
    assert b.epoch == 1 and b.index == 0


def test_determinism_and_resume():
    ids, vals, labels = _data()
    b1 = Batches(ids, vals, labels, batch_size=16, seed=7)
    for _ in range(3):
        b1.next_batch()
    state = b1.state()
    want = [b1.next_batch() for _ in range(4)]
    b2 = Batches(ids, vals, labels, batch_size=16, seed=7)
    b2.restore(state)
    got = [b2.next_batch() for _ in range(4)]
    for (a_ids, a_vals, a_l, a_w), (c_ids, c_vals, c_l, c_w) in zip(want, got):
        np.testing.assert_array_equal(a_ids, c_ids)
        np.testing.assert_array_equal(a_l, c_l)
        np.testing.assert_array_equal(a_w, c_w)


def test_restore_wrong_seed_raises():
    ids, vals, labels = _data()
    b = Batches(ids, vals, labels, batch_size=16, seed=1)
    import pytest

    with pytest.raises(ValueError):
        b.restore({"epoch": 0, "index": 0, "seed": 2})


def test_epochs_reshuffle():
    ids, vals, labels = _data(n=64)
    b = Batches(ids, vals, labels, batch_size=64, seed=0)
    e0 = b.next_batch()[0].copy()
    e1 = b.next_batch()[0].copy()
    assert not np.array_equal(e0, e1)
    assert set(map(tuple, e0)) == set(map(tuple, e1))  # same examples


def test_iterate_once_padding():
    ids, vals, labels = _data(n=50)
    batches = list(iterate_once(ids, vals, labels, 16))
    assert len(batches) == 4
    assert all(b[0].shape[0] == 16 for b in batches)
    total = sum(int(b[3].sum()) for b in batches)
    assert total == 50


def test_train_test_split_disjoint_and_total():
    ids, vals, labels = _data(n=100)
    (tr_i, _, tr_l), (te_i, _, te_l) = train_test_split(ids, vals, labels, 0.25, seed=0)
    assert tr_i.shape[0] == 75 and te_i.shape[0] == 25
    assert tr_l.shape[0] + te_l.shape[0] == 100


def test_batches_rejects_impossible_config():
    import pytest
    ids, vals, labels = _data(n=10)
    with pytest.raises(ValueError, match="exceeds dataset"):
        Batches(ids, vals, labels, batch_size=64, drop_remainder=True)
    with pytest.raises(ValueError, match="empty"):
        Batches(ids[:0], vals[:0], labels[:0], batch_size=4)


# ------------------------------------------------------------- Prefetcher


def test_prefetcher_same_stream_and_state_resume():
    from fm_spark_tpu.data import Prefetcher

    ids, vals, labels = _data(n=200)
    ref = Batches(ids, vals, labels, batch_size=32, seed=7)
    src = Batches(ids, vals, labels, batch_size=32, seed=7)
    with Prefetcher(src, depth=3) as pf:
        states = []
        for _ in range(9):
            a = ref.next_batch()
            b = pf.next_batch()
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, np.asarray(y))
            states.append(pf.state())
        # Resume from the state after batch 5: restore a FRESH source
        # first, then wrap — the stream must continue at batch 6.
        resumed = Batches(ids, vals, labels, batch_size=32, seed=7)
        resumed.restore(states[4])
    with Prefetcher(resumed, depth=3) as pf2:
        ref2 = Batches(ids, vals, labels, batch_size=32, seed=7)
        ref2.restore(states[4])
        for _ in range(5):
            a = ref2.next_batch()
            b = pf2.next_batch()
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, np.asarray(y))


def test_prefetcher_propagates_producer_error():
    import pytest

    from fm_spark_tpu.data import Prefetcher

    class Boom:
        def __init__(self):
            self.n = 0

        def next_batch(self):
            self.n += 1
            if self.n > 2:
                raise RuntimeError("producer crashed")
            return (np.zeros(3),)

        def state(self):
            return {"n": self.n}

    with Prefetcher(Boom(), depth=1) as pf:
        pf.next_batch()
        pf.next_batch()
        with pytest.raises(RuntimeError, match="producer crashed"):
            pf.next_batch()


def test_prefetcher_finite_source_stop_iteration():
    import pytest

    from fm_spark_tpu.data import Prefetcher

    class Finite:
        def __init__(self):
            self.n = 0

        def next_batch(self):
            if self.n >= 3:
                raise StopIteration
            self.n += 1
            return (np.full(2, self.n),)

    with Prefetcher(Finite(), depth=2) as pf:
        got = [int(pf.next_batch()[0][0]) for _ in range(3)]
        assert got == [1, 2, 3]
        with pytest.raises(StopIteration):
            pf.next_batch()
        # Exhausted iterators must KEEP raising (not deadlock on the
        # empty queue of a dead producer).
        with pytest.raises(StopIteration):
            pf.next_batch()


def test_prefetcher_close_unblocks_producer():
    from fm_spark_tpu.data import Prefetcher

    ids, vals, labels = _data(n=200)
    src = Batches(ids, vals, labels, batch_size=16, seed=0)
    pf = Prefetcher(src, depth=1)  # tiny queue → producer blocks on put
    pf.next_batch()
    pf.close()  # must not hang
    assert not pf._thread.is_alive()
    # Use-after-close errors instead of deadlocking on the dead queue.
    import pytest

    with pytest.raises(RuntimeError, match="closed"):
        pf.next_batch()


def test_prefetcher_close_is_idempotent():
    import pytest

    from fm_spark_tpu.data import Prefetcher

    ids, vals, labels = _data(n=100)
    pf = Prefetcher(Batches(ids, vals, labels, batch_size=16, seed=0),
                    depth=1)
    pf.next_batch()
    pf.close()
    pf.close()  # second close: no hang, no error, thread stays down
    assert not pf._thread.is_alive()
    with pytest.raises(RuntimeError, match="closed"):
        pf.next_batch()


def test_prefetcher_producer_error_keeps_reraising_without_blocking():
    """A producer crash must re-raise on EVERY subsequent next_batch()
    — the terminal sentinel is enqueued exactly once, so a second call
    that blocked on the dead queue would hang the training loop."""
    import pytest

    from fm_spark_tpu.data import Prefetcher

    class Boom:
        def __init__(self):
            self.n = 0

        def next_batch(self):
            self.n += 1
            if self.n > 1:
                raise RuntimeError("producer crashed")
            return (np.zeros(3),)

        def state(self):
            return {"n": self.n}

    with Prefetcher(Boom(), depth=1) as pf:
        pf.next_batch()
        for _ in range(3):
            with pytest.raises(RuntimeError, match="producer crashed"):
                pf.next_batch()


def test_prefetcher_restore_after_start_raises_documented_error():
    import pytest

    from fm_spark_tpu.data import Prefetcher

    ids, vals, labels = _data(n=64)
    src = Batches(ids, vals, labels, batch_size=16, seed=3)
    with Prefetcher(src, depth=2) as pf:
        with pytest.raises(RuntimeError, match="BEFORE constructing"):
            pf.restore({"epoch": 0, "index": 0, "seed": 3})


# ------------------------------------------- the feed places its batches
#
# wrap_prefetch(batches, depth, place=...): what the loop takes is on the
# devices already. The reference is the parent's way, written out: the
# loader's ``a[sel]`` gather, pad_field_batch, and shard_field_batch as it
# was (``jnp.asarray`` of the whole batch, then a re-sharding device_put).

FIELDS = 39
_LOCK = threading.Lock()


def _feed_workers():
    return [t for t in threading.enumerate()
            if t.name.startswith("fm-spark-feed")]


def _parent_next_batch(b):
    """Batches.next_batch as it read before next_rows / take: fancy
    indexing on the one thread (the loop version kept as the reference)."""
    n, size = b.num_examples, b.batch_size
    perm = b._epoch_perm()
    start, end = b.index, b.index + size
    if end <= n:
        sel, weights = perm[start:end], np.ones((size,), np.float32)
        b.index = end
    elif b.drop_remainder or start >= n:
        b.epoch, b.index, b._perm = b.epoch + 1, 0, None
        return _parent_next_batch(b)
    else:
        sel = perm[start:n]
        pad = size - sel.shape[0]
        weights = np.concatenate([np.ones(sel.shape[0], np.float32),
                                  np.zeros(pad, np.float32)])
        sel = np.concatenate([sel, np.zeros(pad, np.int64)])
        b.epoch, b.index, b._perm = b.epoch + 1, 0, None
    return b.ids[sel], b.vals[sel], b.labels[sel], weights


def _parent_shard_field_batch(batch, mesh):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from fm_spark_tpu.parallel import field_batch_specs

    return tuple(jax.device_put(jnp.asarray(x), NamedSharding(mesh, s))
                 for x, s in zip(batch, field_batch_specs(mesh)))


@pytest.mark.parametrize("drop_remainder", [False, True])
def test_next_rows_and_take_make_the_parents_batches(drop_remainder):
    ids, vals, labels = _data(n=70, nnz=FIELDS, f=FIELDS * 64)
    ref = Batches(ids, vals, labels, 16, seed=5,
                  drop_remainder=drop_remainder)
    b = Batches(ids, vals, labels, 16, seed=5, drop_remainder=drop_remainder)
    for _ in range(10):
        for want, got in zip(_parent_next_batch(ref), b.next_batch()):
            assert want.dtype == got.dtype
            np.testing.assert_array_equal(want, got)
        assert b.state() == ref.state()
    sel = np.array([3, 0, 3])
    wide_ids, wide_vals, _ = b.take(sel, width=40)
    assert wide_ids.shape == wide_vals.shape == (3, 40)
    np.testing.assert_array_equal(wide_ids[:, :FIELDS], ids[sel])
    assert not wide_ids[:, FIELDS:].any() and not wide_vals[:, FIELDS:].any()


@pytest.mark.parametrize("by_rows", [True, False],
                         ids=["by_rows", "host_batch"])
@pytest.mark.parametrize("depth", [2, 0], ids=["prefetched", "inline"])
@pytest.mark.parametrize("n_feat,n_row", [(4, 1), (2, 2)],
                         ids=["feat4", "feat2xrow2"])
def test_placed_feed_is_the_parents_stream_bit_for_bit(n_feat, n_row, depth,
                                                       by_rows):
    """Ten batches across two epoch boundaries (70 rows in batches of 16:
    the fifth of an epoch is a padded tail), from a four-shard place:
    shard by shard and as global arrays what the parent's loop placed, on
    the mesh's own NamedSharding with every shard on a device of its own,
    and ``state()`` after each consumed batch the parent's cursor."""
    import jax
    from jax.sharding import NamedSharding

    from fm_spark_tpu import obs
    from fm_spark_tpu.data import wrap_prefetch
    from fm_spark_tpu.parallel import (
        FieldBatchFeed, field_batch_specs, make_field_mesh, pad_field_batch,
    )

    mesh = make_field_mesh(n_feat * n_row, n_row=n_row)
    ids, vals, labels = _data(n=70, nnz=FIELDS, f=FIELDS * 64)
    ref = Batches(ids, vals, labels, 16, seed=11)
    feed = FieldBatchFeed(mesh, FIELDS)
    place = feed if by_rows else (lambda b: feed(b))
    seen = len(obs.intervals())
    source, close = wrap_prefetch(Batches(ids, vals, labels, 16, seed=11),
                                  depth, place=place)
    try:
        for _ in range(10):
            got = source.next_batch()
            want = _parent_shard_field_batch(
                pad_field_batch(_parent_next_batch(ref), FIELDS, n_feat),
                mesh)
            assert source.state() == ref.state()
            for g, w, spec in zip(got, want, field_batch_specs(mesh)):
                assert g.sharding == w.sharding == NamedSharding(mesh, spec)
                assert g.dtype == w.dtype and g.shape == w.shape
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
                assert ({s.device for s in g.addressable_shards}
                        == set(mesh.devices.flat))
                for gs, ws in zip(g.addressable_shards,
                                  w.addressable_shards):
                    assert (gs.device, gs.index) == (ws.device, ws.index)
                    np.testing.assert_array_equal(np.asarray(gs.data),
                                                  np.asarray(ws.data))
        assert ref.epoch == 2
    finally:
        close()
    placed = [r for r in obs.intervals()[seen:] if r.name == "feed/place"]
    assert len(placed) >= 10
    nbytes = 16 * (2 * 40 * 4 + 2 * 4)
    assert {(r.attrs["shards"], r.attrs["bytes"]) for r in placed} == {
        (4, nbytes)}
    if depth:
        made = {r.span_id for r in obs.intervals()[seen:]
                if r.name == "feed/produce"}
        assert all(r.parent_id in made for r in placed)


def test_one_device_place_is_one_shard_on_the_callers_thread():
    """The loop's one-chip ``prep`` as the place: ``shards=1``, no pool,
    the batch a jax array when taken."""
    import jax
    import jax.numpy as jnp

    from fm_spark_tpu import obs
    from fm_spark_tpu.data import wrap_prefetch

    ids, vals, labels = _data(n=64)
    ref = Batches(ids, vals, labels, 16, seed=2)
    host = lambda b: jax.tree_util.tree_map(jnp.asarray, tuple(b))
    seen = len(obs.intervals())
    source, close = wrap_prefetch(Batches(ids, vals, labels, 16, seed=2), 2,
                                  place=host)
    try:
        for _ in range(5):
            got, want = source.next_batch(), ref.next_batch()
            assert all(isinstance(g, jax.Array) for g in got)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), w)
            assert source.state() == ref.state()
    finally:
        close()
    placed = [r for r in obs.intervals()[seen:] if r.name == "feed/place"]
    assert placed and {r.attrs["shards"] for r in placed} == {1}
    assert not _feed_workers()


def test_a_shard_workers_error_reaches_the_consumer_and_close_joins():
    """One of the four gathers fails: the consumer gets that error once
    and on every later call, and ``close()`` leaves no worker behind."""
    from fm_spark_tpu.data import Prefetcher
    from fm_spark_tpu.parallel import FieldBatchFeed, make_field_mesh

    class Flaky(Batches):
        calls = 0

        def take(self, sel, width=None):
            with _LOCK:
                Flaky.calls += 1
                n = Flaky.calls
            if n == 7:              # the third run of the second batch
                raise OSError("a shard's rows could not be read")
            return super().take(sel, width)

    ids, vals, labels = _data(n=200, nnz=FIELDS, f=FIELDS * 64)
    pf = Prefetcher(Flaky(ids, vals, labels, 32, seed=0), depth=1,
                    place=FieldBatchFeed(make_field_mesh(4), FIELDS))
    try:
        assert pf.next_batch()[0].shape == (32, 40)
        assert _feed_workers()
        for _ in range(3):
            with pytest.raises(OSError, match="could not be read"):
                pf.next_batch()
    finally:
        pf.close()
    assert not pf._thread.is_alive()
    assert not _feed_workers()
    pf.close()                      # idempotent with a pool too


# ------------------------------------------------------- BernoulliBatches


def test_bernoulli_batches_reference_sampling_semantics():
    from fm_spark_tpu.data import BernoulliBatches

    ids, vals, labels = _data(n=4000)
    p = 0.25
    b = BernoulliBatches(ids, vals, labels, p, seed=5)
    masks = []
    for _ in range(6):
        bi, bv, bl, w = b.next_batch()
        # Full fixed shape every step; arrays untouched, only the mask
        # varies.
        assert bi.shape == ids.shape and w.shape == (4000,)
        assert set(np.unique(w)) <= {0.0, 1.0}
        masks.append(w)
    # Fresh independent Bernoulli draw each iteration (reference
    # data.sample(false, frac, seed+i)): masks differ, each ~ p·N.
    for i in range(5):
        assert not np.array_equal(masks[i], masks[i + 1])
        assert abs(masks[i].sum() / 4000 - p) < 0.05
    # Deterministic per (seed, step) and exactly resumable.
    b2 = BernoulliBatches(ids, vals, labels, p, seed=5)
    b2.restore({"step": 3, "seed": 5, "fraction": p})
    np.testing.assert_array_equal(b2.next_batch()[3], masks[3])
    # Different seed → different stream.
    b3 = BernoulliBatches(ids, vals, labels, p, seed=6)
    assert not np.array_equal(b3.next_batch()[3], masks[0])


def test_bernoulli_batches_validation():
    import pytest

    from fm_spark_tpu.data import BernoulliBatches

    ids, vals, labels = _data(n=10)
    with pytest.raises(ValueError, match="fraction"):
        BernoulliBatches(ids, vals, labels, 0.0)
    with pytest.raises(ValueError, match="fraction"):
        BernoulliBatches(ids, vals, labels, 1.5)
    b = BernoulliBatches(ids, vals, labels, 0.5, seed=1)
    with pytest.raises(ValueError, match="different seed"):
        b.restore({"step": 0, "seed": 9, "fraction": 0.5})
