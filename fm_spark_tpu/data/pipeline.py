"""In-memory batch pipeline with deterministic, checkpointable iteration.

Replaces the reference's per-iteration ``data.sample(miniBatchFraction)``
over RDD partitions (SURVEY.md §3.1) with epoch-shuffled fixed-size batches:
deterministic from (seed, epoch, step) so a resumed run reproduces the exact
remaining batch sequence (SURVEY.md §5 "deterministic data-pipeline resume").
Large-scale disk-backed loading lives in :mod:`fm_spark_tpu.data.packed`;
this class handles arrays that fit in host RAM.
"""

from __future__ import annotations

import numpy as np

from fm_spark_tpu import obs


def train_test_split(ids, vals, labels, test_fraction=0.2, seed=0):
    """Deterministic shuffled split (the lineage's example-driver idiom)."""
    n = ids.shape[0]
    perm = np.random.default_rng(seed).permutation(n)
    cut = int(n * (1.0 - test_fraction))
    tr, te = perm[:cut], perm[cut:]
    return (ids[tr], vals[tr], labels[tr]), (ids[te], vals[te], labels[te])


def _widened(a, width: int):
    """``a`` with zero columns appended up to ``width``."""
    out = np.zeros((a.shape[0], width), a.dtype)
    out[:, :a.shape[1]] = a
    return out


class Batches:
    """Epoch-shuffling minibatch iterator over fixed-nnz arrays.

    State is ``(epoch, index)``; :meth:`state` / :meth:`restore` give exact
    resume. The final partial batch of an epoch is padded to full size with
    ``weight=0`` examples so jit never sees a new shape.
    """

    def __init__(self, ids, vals, labels, batch_size: int, seed: int = 0,
                 drop_remainder: bool = False):
        self.ids = np.ascontiguousarray(ids)
        self.vals = np.ascontiguousarray(vals)
        self.labels = np.ascontiguousarray(labels)
        self.batch_size = int(batch_size)
        if self.ids.shape[0] == 0:
            raise ValueError("empty dataset")
        if drop_remainder and self.ids.shape[0] < self.batch_size:
            raise ValueError(
                f"batch_size={batch_size} exceeds dataset size "
                f"{self.ids.shape[0]} with drop_remainder=True — no batch "
                "can ever be produced"
            )
        self.seed = int(seed)
        self.drop_remainder = drop_remainder
        self.epoch = 0
        self.index = 0
        self._perm = None

    @property
    def num_examples(self):
        return self.ids.shape[0]

    def _epoch_perm(self):
        if self._perm is None:
            rng = np.random.default_rng((self.seed, self.epoch))
            self._perm = rng.permutation(self.num_examples)
        return self._perm

    def state(self) -> dict:
        return {"epoch": self.epoch, "index": self.index, "seed": self.seed}

    def restore(self, state: dict) -> None:
        if int(state["seed"]) != self.seed:
            raise ValueError("restoring pipeline state with a different seed")
        self.epoch = int(state["epoch"])
        self.index = int(state["index"])
        self._perm = None

    def next_rows(self):
        """``(sel, weights)``: the next batch as row numbers into the
        arrays, with its weights, advancing the cursor. ``next_batch``
        gathers them whole; a feed that places a batch shard by shard
        (:class:`PlacedBatches`) cuts ``sel`` into runs and has each
        gathered by a worker of its own (:meth:`take`)."""
        n, b = self.num_examples, self.batch_size
        perm = self._epoch_perm()
        start = self.index
        end = start + b
        if end <= n:
            sel = perm[start:end]
            weights = np.ones((b,), np.float32)
            self.index = end
        elif self.drop_remainder or start >= n:
            # Roll to the next epoch and take a full batch from it.
            self.epoch += 1
            self.index = 0
            self._perm = None
            return self.next_rows()
        else:
            sel = perm[start:n]
            pad = b - sel.shape[0]
            weights = np.concatenate(
                [np.ones(sel.shape[0], np.float32), np.zeros(pad, np.float32)]
            )
            sel = np.concatenate([sel, np.zeros(pad, np.int64)])
            self.epoch += 1
            self.index = 0
            self._perm = None
        return sel, weights

    def take(self, sel, width: int | None = None):
        """``(ids, vals, labels)`` of the rows ``sel``; ``width`` pads
        ``ids`` and ``vals`` with zero columns up to it (a mesh's padded
        field count). ``np.take`` and not ``a[sel]``: the same rows, a
        third faster, and it lets go of the interpreter lock while it
        copies, so a pool of callers runs side by side (four threads
        indexing ``a[sel]`` take longer than one)."""
        ids = np.take(self.ids, sel, axis=0)
        vals = np.take(self.vals, sel, axis=0)
        if width is not None and width != ids.shape[1]:
            ids, vals = _widened(ids, width), _widened(vals, width)
        return ids, vals, np.take(self.labels, sel, axis=0)

    def next_batch(self):
        """Return ``(ids, vals, labels, weights)``, advancing the cursor."""
        sel, weights = self.next_rows()
        return (*self.take(sel), weights)

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()


class BernoulliBatches:
    """Per-iteration Bernoulli sampling — the reference's exact minibatch
    semantics (``data.sample(withReplacement=false, miniBatchFraction,
    seed+i)`` per SGD iteration, SURVEY.md §3.1), TPU-shaped: every step
    yields the FULL dataset with a fresh Bernoulli(fraction) weight mask,
    so jit sees one fixed shape and the weighted-mean loss averages over
    exactly the sampled examples (MLlib divides by the realized sample
    size; ``wsum`` does the same).

    Deterministic per (seed, step) — resume replays the identical mask
    sequence. Compared to epoch-shuffled fixed-size ``Batches`` (the
    throughput-oriented default), this matches the reference's
    convergence behavior: sample size varies binomially per step and an
    example can repeat in consecutive steps.
    """

    def __init__(self, ids, vals, labels, fraction: float, seed: int = 0):
        if not (0.0 < fraction <= 1.0):
            raise ValueError(f"fraction must be in (0, 1], got {fraction}")
        self.ids = np.ascontiguousarray(ids)
        self.vals = np.ascontiguousarray(vals)
        self.labels = np.ascontiguousarray(labels)
        if self.ids.shape[0] == 0:
            raise ValueError("empty dataset")
        self.fraction = float(fraction)
        self.seed = int(seed)
        self.step = 0

    @property
    def num_examples(self):
        return self.ids.shape[0]

    def state(self) -> dict:
        return {"step": self.step, "seed": self.seed,
                "fraction": self.fraction}

    def restore(self, state: dict) -> None:
        for key, have in [("seed", self.seed), ("fraction", self.fraction)]:
            if key in state and state[key] != have:
                raise ValueError(
                    f"restoring sampler state with a different {key}"
                )
        self.step = int(state["step"])

    def next_batch(self):
        rng = np.random.default_rng((self.seed, 0xB3A2, self.step))
        weights = (
            rng.random(self.num_examples) < self.fraction
        ).astype(np.float32)
        self.step += 1
        return self.ids, self.vals, self.labels, weights

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()


class DedupAuxBatches:
    """Batch-source wrapper that appends host-precomputed dedup aux to
    each 4-tuple batch, yielding ``(ids, vals, labels, weights, aux)``:
    :func:`fm_spark_tpu.ops.scatter.dedup_aux` by default, or the
    COMPACT variant (:func:`...scatter.compact_aux`) when ``cap > 0`` —
    pair with ``TrainConfig.compact_cap`` of the same value (the jitted
    step's aux shapes are static).

    Wrap the source with this BEFORE :class:`Prefetcher` so the sort
    work lands in the producer thread, off the device critical path —
    that placement is the entire point of host-assisted dedup
    (PERF.md round-3 lever).

    ``overflow`` (compact only) picks what happens when a field's
    unique count exceeds ``cap`` mid-run (a DATA property that can drift
    hours into training):

    - ``'error'`` (default) — propagate
      :class:`~fm_spark_tpu.ops.scatter.CompactCapOverflow`; the run
      dies with an actionable message (the round-2 behavior).
    - ``'split'`` — recursively halve the offending batch until every
      field fits, padding each half back to the full batch size with
      INERT lanes (val=0, weight=0, ids copied from the half's first
      row so padding never adds a unique id). Semantics stay exact —
      each half is a correct smaller SGD step — at the cost of extra
      step indices for that batch. While split halves are pending,
      ``state()`` reports the cursor from BEFORE the split batch, so a
      checkpoint-resume replays the WHOLE source batch (already-trained
      halves repeat — no data is ever silently skipped).
    """

    def __init__(self, source, cap: int = 0, overflow: str = "error"):
        from collections import deque

        if overflow not in ("error", "split"):
            raise ValueError(
                f"DedupAuxBatches overflow must be 'error' or 'split', "
                f"got {overflow!r}"
            )
        self._source = source
        self._cap = int(cap)
        self._overflow = overflow
        self._pending = deque()
        self._pre_split_state = None

    def _expand(self, batch, b_full: int):
        """``batch`` holds the REAL rows only (possibly fewer than
        ``b_full`` after splits); padding to the step's static batch
        shape happens at each aux-build attempt, and the recursion
        halves the real rows — strict progress, guaranteed
        termination."""
        from fm_spark_tpu.ops.scatter import (
            CompactCapOverflow,
            compact_aux,
            dedup_aux,
        )

        ids, vals, labels, weights = (np.asarray(a) for a in batch)
        r = ids.shape[0]
        pad = b_full - r
        if pad:
            # Inert padding: repeat the part's first row's ids (no new
            # uniques), zero vals/labels/weights (no forward, loss, or
            # gradient contribution; delta 0 into existing segments).
            ids = np.concatenate(
                [ids, np.broadcast_to(ids[:1], (pad,) + ids.shape[1:])]
            )
            zero = lambda a: np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], a.dtype)]
            )
            vals, labels, weights = zero(vals), zero(labels), zero(weights)
        try:
            aux = (compact_aux(ids, self._cap) if self._cap
                   else dedup_aux(ids))
            return [(ids, vals, labels, weights, aux)]
        except CompactCapOverflow:
            if self._overflow != "split" or r < 2:
                raise
        h = r // 2
        return (
            self._expand(tuple(a[:h] for a in batch), b_full)
            + self._expand(tuple(a[h:r] for a in batch), b_full)
        )

    def next_batch(self):
        if not self._pending:
            pre = (self._source.state() if self._overflow == "split"
                   else None)
            batch = tuple(
                np.asarray(a) for a in self._source.next_batch()
            )
            parts = self._expand(batch, batch[0].shape[0])
            self._pending.extend(parts)
            self._pre_split_state = pre if len(parts) > 1 else None
        out = self._pending.popleft()
        if not self._pending:
            self._pre_split_state = None  # split batch fully consumed
        return out

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()

    def state(self):
        if self._pre_split_state is not None:
            return self._pre_split_state
        return self._source.state()

    def restore(self, state) -> None:
        self._pending.clear()
        self._pre_split_state = None
        self._source.restore(state)

    @property
    def guard(self):
        return getattr(self._source, "guard", None)


class MappedBatches:
    """Batch-source wrapper applying ``fn`` to each yielded batch in the
    PRODUCER thread (wrap before :class:`Prefetcher`). The generic glue
    for per-batch host transforms that belong off the device critical
    path — e.g. the sharded-compact F_pad aux padding (cli) — without
    re-implementing the source protocol per call site."""

    def __init__(self, source, fn):
        self._source = source
        self._fn = fn

    def next_batch(self):
        return self._fn(self._source.next_batch())

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()

    def state(self):
        return self._source.state()

    def restore(self, state) -> None:
        self._source.restore(state)

    @property
    def guard(self):
        return getattr(self._source, "guard", None)


class StackedBatches:
    """Batch-source wrapper that stacks ``n`` consecutive batches on a
    leading axis — the input shape for
    :func:`fm_spark_tpu.sparse.make_field_sparse_multistep` (one device
    dispatch per ``n`` steps). Tree-aware, so it composes with
    :class:`DedupAuxBatches` (the aux tuple's leaves stack too). Wrap
    BEFORE :class:`Prefetcher` so the stacking memcpy runs in the
    producer thread.

    ``state()`` reflects the source cursor AFTER the batches of the last
    stack — resume replays from the next unseen batch. ``total`` bounds
    how many SOURCE batches are ever consumed: the final stack of a
    finite run takes only the remainder from the source and pads with
    inert copies of its last real batch (the consumer's dynamic step
    count never executes them), so the checkpointed cursor stays exact
    — no trained-data gap on resume.
    """

    def __init__(self, source, n: int, total: int | None = None):
        import jax

        if n < 1:
            raise ValueError(f"stack size must be >= 1, got {n}")
        self._source = source
        self._n = n
        self._left = total  # None = unbounded
        self._tree = jax.tree_util

    def next_batch(self):
        import numpy as np

        take = self._n if self._left is None else min(self._n, self._left)
        if take <= 0:
            raise StopIteration
        batches = [tuple(self._source.next_batch()) for _ in range(take)]
        if self._left is not None:
            self._left -= take
        batches += [batches[-1]] * (self._n - take)
        return self._tree.tree_map(
            lambda *xs: np.stack(xs, axis=0), *batches
        )

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()

    def state(self):
        return self._source.state()

    def restore(self, state) -> None:
        self._source.restore(state)

    @property
    def guard(self):
        return getattr(self._source, "guard", None)


class PlacedBatches(MappedBatches):
    """Batch-source wrapper whose batches are ON THE DEVICE(S) when it
    hands them on: placement is the feed's job, not the training loop's,
    and the LAST stage of a producer chain (after :class:`DedupAuxBatches`
    / :class:`MappedBatches` / :class:`StackedBatches`; wrap before or,
    through ``place=``, by :class:`Prefetcher`).

    ``place(batch)`` takes a host batch and returns it placed (the
    loops' ``prep``: ``jnp.asarray`` on one chip, a mesh's sharded
    ``device_put``). A ``place`` that also has ``from_rows(take, sel,
    weights)`` is handed, from a source that has ``next_rows`` /
    ``take`` (:class:`Batches`), the batch as row numbers instead, and
    makes each device's shard itself: gathered by a worker of its own
    and sent straight to its device, no whole host batch in between
    (``parallel.FieldBatchFeed``). Either way the batch is waited for
    (``block_until_ready``) before it is handed on: what the consumer
    takes has arrived.

    One ``feed/place`` interval per batch (inside the producer's
    ``feed/produce``): the call into ``place``, the per-shard gathers
    included where they are its workers', with ``shards`` (addressable
    devices the first array lies on) and ``bytes`` (of all its arrays).
    ``state()`` is the source's: the cursor after the batch last made.
    ``close()`` closes the ``place`` if it has something to close.
    """

    def __init__(self, source, place):
        import jax

        super().__init__(source, place)
        self._jax = jax
        self._by_rows = (hasattr(place, "from_rows")
                         and hasattr(source, "next_rows"))

    def next_batch(self):
        if self._by_rows:
            place = self._fn.from_rows
            args = (self._source.take, *self._source.next_rows())
        else:
            place, args = self._fn, (self._source.next_batch(),)
        with obs.interval("feed/place") as placed:
            batch = self._jax.block_until_ready(place(*args))
            leaves = self._jax.tree_util.tree_leaves(batch)
            placed.set(
                shards=len(leaves[0].sharding.addressable_devices),
                bytes=sum(x.nbytes for x in leaves),
            )
        return batch

    def close(self) -> None:
        close = getattr(self._fn, "close", None)
        if close is not None:
            close()


def _batch_rows(batch) -> int:
    """Examples in a batch tuple (``labels`` is element 2; a stacked
    batch counts every step's), 0 for a shape this cannot read."""
    try:
        return int(np.size(batch[2]))
    except (TypeError, IndexError, KeyError):
        return 0


class Prefetcher:
    """Background-thread batch prefetch with a bounded queue.

    Overlaps host-side batch assembly (memmap reads, row gathers,
    field-local id conversion) and, with ``place``, the host→device
    transfer with device compute — the producer/consumer idiom
    grain/tf.data use, kept dependency-free. Wraps any batch source with
    ``next_batch()`` (Batches, PackedBatches, cli.StreamingBatches).

    Checkpoint semantics: ``state()`` returns the wrapped source's cursor
    as of the LAST CONSUMED batch, not the producer's read-ahead cursor —
    resuming from it replays exactly the batches the training loop never
    saw. (The producer snapshots ``source.state()`` after producing each
    batch and the snapshot travels with the batch through the queue.)

    ``place`` (a callable on a host batch; :class:`PlacedBatches` has
    the contract) puts each batch on its device(s) inside the producer
    thread (``jax.device_put`` is thread-safe), so the consumer takes
    batches that have already arrived and ``feed/produce`` covers making
    AND placing one. The prefetcher owns it from then on: ``close()``
    closes it.
    """

    _STOP = object()

    def __init__(self, source, depth: int = 2, place=None):
        import queue
        import threading

        self._placed = None if place is None else PlacedBatches(source, place)
        self._source = source if place is None else self._placed
        self._has_state = hasattr(source, "state")
        self._last_state = source.state() if self._has_state else None
        self._q = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._terminal = None
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _produce(self):
        try:
            while not self._stop.is_set():
                # Hot intervals (obs.interval, always live): produce is
                # what one batch costs the feed, put_wait the time the
                # producer stood at a full queue — the feed's slack.
                with obs.interval("feed/produce") as made:
                    batch = self._source.next_batch()
                    made.set(rows=_batch_rows(batch))
                state = self._source.state() if self._has_state else None
                with obs.interval("feed/put_wait", rows=made.attrs["rows"]):
                    while not self._stop.is_set():
                        try:
                            self._q.put((batch, state, None), timeout=0.1)
                            break
                        except Exception:  # queue.Full
                            continue
        except StopIteration:
            self._q.put((None, None, StopIteration()))
        except BaseException as e:  # surface producer crashes to consumer
            self._q.put((None, None, e))

    def next_batch(self):
        if self._terminal is not None:
            # The producer enqueued its terminal sentinel exactly once and
            # exited; keep re-raising instead of blocking on a queue that
            # will never be fed again (iterator-protocol contract).
            if isinstance(self._terminal, StopIteration):
                raise StopIteration
            raise self._terminal
        batch, state, err = self._q.get()
        if err is not None:
            self._terminal = err
            if isinstance(err, StopIteration):
                raise StopIteration
            raise err
        self._last_state = state
        return batch

    def __iter__(self):
        return self

    def __next__(self):
        return self.next_batch()

    def state(self) -> dict:
        if not self._has_state:
            raise AttributeError("wrapped source has no state()")
        return self._last_state

    def restore(self, state: dict) -> None:
        raise RuntimeError(
            "restore the wrapped source BEFORE constructing the Prefetcher "
            "(the producer thread starts reading ahead immediately)"
        )

    @property
    def guard(self):
        """The wrapped source's ingest RecordGuard, if any — surfaces
        quarantine counters through the wrapper chain (train.py logs
        them at end of fit)."""
        return getattr(self._source, "guard", None)

    def close(self) -> None:
        self._stop.set()
        # Drain so a blocked producer put() can observe the stop flag.
        try:
            while True:
                self._q.get_nowait()
        except Exception:
            pass
        # A consumer calling next_batch() after (or blocked in get()
        # during) close must get an error, not a permanent hang on a
        # queue no producer will ever feed again.
        if self._terminal is None:
            self._terminal = RuntimeError("Prefetcher is closed")
        try:
            self._q.put_nowait((None, None, self._terminal))
        except Exception:
            pass
        self._thread.join(timeout=5)
        if self._placed is not None:
            self._placed.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def wrap_prefetch(batches, depth: int, place=None):
    """Wrap a batch source with a :class:`Prefetcher`; returns
    ``(source, close)``. No-op (identity source, noop close) when
    ``depth <= 0`` or the source has no ``next_batch`` (plain
    iterables can't be safely read ahead AND checkpointed).

    ``place`` makes the source hand out batches that are on the
    device(s) already (:class:`PlacedBatches`): in the producer thread
    where there is one, in the caller's where there is none — the
    caller takes placed batches either way, and ``close`` closes the
    ``place`` too.

    Call AFTER any checkpoint restore — the producer thread starts
    reading ahead immediately, so a later restore would race it.
    Single definition shared by cli training loops and FMTrainer.fit
    so prefetch lifecycle semantics can never diverge between them.
    """
    if depth > 0 and hasattr(batches, "next_batch"):
        pf = Prefetcher(batches, depth=depth, place=place)
        return pf, pf.close
    if place is None:
        return batches, lambda: None
    placed = PlacedBatches(batches, place)
    return placed, placed.close


def iterate_once(ids, vals, labels, batch_size: int):
    """One ordered, finite pass over the data — for evaluation.

    The final partial batch is zero-padded with ``weight=0`` so jit sees a
    single batch shape.
    """
    n = ids.shape[0]
    for start in range(0, n, batch_size):
        end = min(start + batch_size, n)
        b = end - start
        if b == batch_size:
            yield ids[start:end], vals[start:end], labels[start:end], np.ones(
                (batch_size,), np.float32
            )
        else:
            pad = batch_size - b
            yield (
                np.concatenate([ids[start:end], np.zeros((pad,) + ids.shape[1:], ids.dtype)]),
                np.concatenate([vals[start:end], np.zeros((pad,) + vals.shape[1:], vals.dtype)]),
                np.concatenate([labels[start:end], np.zeros((pad,), labels.dtype)]),
                np.concatenate([np.ones((b,), np.float32), np.zeros((pad,), np.float32)]),
            )
