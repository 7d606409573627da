"""DEVICE-built compact aux (`TrainConfig.compact_device`): the in-step
builder (ops/scatter.device_compact_aux) must reproduce the host builder
bit-for-bit (both sorts are stable), so every downstream compact result
is identical; and it must lift the host aux's structural limits — the
2-D (feat, row) mesh and overflow-without-crash — with the documented
semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import models
from fm_spark_tpu.ops.scatter import compact_aux, device_compact_aux
from fm_spark_tpu.parallel import (
    make_field_mesh,
    make_field_sharded_sgd_step,
    pad_field_batch,
    shard_field_batch,
    shard_field_params,
    stack_field_params,
    unstack_field_params,
)
from fm_spark_tpu.sparse import make_field_sparse_sgd_step
from fm_spark_tpu.train import TrainConfig

F, BUCKET, K, B, CAP = 5, 64, 4, 48, 48


def _batch(rng, b=B, f=F, bucket=BUCKET):
    ids = rng.integers(0, bucket, size=(b, f)).astype(np.int32)
    ids[:, 0] = rng.integers(0, 3, b)          # heavy duplication
    vals = rng.normal(size=(b, f)).astype(np.float32)
    labels = rng.integers(0, 2, b).astype(np.float32)
    weights = np.ones(b, np.float32)
    weights[::7] = 0.0                          # inert rows
    return ids, vals, labels, weights


def _spec(**kw):
    kw.setdefault("param_dtype", "float32")
    return models.FieldFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, **kw
    )


def _base_cfg(**kw):
    base = dict(learning_rate=0.05, optimizer="sgd",
                reg_factors=1e-4, reg_linear=1e-4)
    base.update(kw)
    return TrainConfig(**base)


def test_device_aux_matches_host_aux_bitwise(rng):
    ids = rng.integers(0, 17, size=(40, 3)).astype(np.int32)
    cap = 24
    want = compact_aux(ids, cap)
    names = ("useg", "segstart", "segend", "order", "inv")
    for f in range(3):
        got, nseg = jax.jit(device_compact_aux, static_argnums=1)(
            jnp.asarray(ids[:, f]), cap
        )
        assert int(nseg) == np.unique(ids[:, f]).size
        for g, w, name in zip(got, want, names):
            np.testing.assert_array_equal(
                np.asarray(g), w[f], err_msg=f"field {f} {name}"
            )


def test_device_aux_overflow_counts_and_targets(rng):
    # 30 unique ids, cap 8: segments 8.. (the LARGEST ids) must lose
    # their useg slot; the first 8 stay exact.
    ids = np.arange(30, dtype=np.int32)
    rng.shuffle(ids)
    cap = 8
    (useg, segstart, segend, order, inv), nseg = jax.jit(
        device_compact_aux, static_argnums=1
    )(jnp.asarray(ids), cap)
    assert int(nseg) == 30
    np.testing.assert_array_equal(np.asarray(useg), np.arange(8))
    # inv still maps every lane to its true segment (>= cap for dropped).
    np.testing.assert_array_equal(np.sort(np.asarray(inv)), np.arange(30))


@pytest.mark.parametrize(
    "mode,pdtype", [("dedup", "float32"), ("dedup_sr", "bfloat16")]
)
def test_single_chip_device_matches_host_compact(rng, mode, pdtype):
    ids, vals, labels, weights = _batch(rng)
    spec = _spec(param_dtype=pdtype)
    params = spec.init(jax.random.key(1))
    host_step = make_field_sparse_sgd_step(
        spec, _base_cfg(sparse_update=mode, host_dedup=True,
                        compact_cap=CAP),
    )
    dev_step = make_field_sparse_sgd_step(
        spec, _base_cfg(sparse_update=mode, compact_device=True,
                        compact_cap=CAP),
    )
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids, CAP))
    args = (jnp.int32(3), jnp.asarray(ids), jnp.asarray(vals),
            jnp.asarray(labels), jnp.asarray(weights))
    p_host, l_host = host_step(jax.tree.map(jnp.copy, params), *args, aux)
    p_dev, l_dev = dev_step(params, *args)
    assert float(l_host) == float(l_dev)
    # Same stable sort → same cumsum association → bitwise-equal tables
    # (incl. the SR noise stream, which keys on (step, field) only).
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        ),
        p_host, p_dev,
    )


def test_sharded_1d_device_matches_single_chip(rng):
    ids, vals, labels, weights = _batch(rng, b=64)
    spec = _spec()
    config = _base_cfg(sparse_update="dedup", compact_device=True,
                       compact_cap=CAP)
    canonical = spec.init(jax.random.key(1))
    single = make_field_sparse_sgd_step(spec, config)
    mesh = make_field_mesh(8)
    sharded = make_field_sharded_sgd_step(spec, config, mesh)
    sp = shard_field_params(
        stack_field_params(spec, jax.tree.map(jnp.copy, canonical), 8),
        mesh,
    )
    batch = pad_field_batch((ids, vals, labels, weights), F, 8)
    for i in range(3):
        canonical, l1 = single(
            canonical, jnp.int32(i), jnp.asarray(ids), jnp.asarray(vals),
            jnp.asarray(labels), jnp.asarray(weights),
        )
        sp, l2 = sharded(sp, jnp.int32(i), *shard_field_batch(batch, mesh))
        assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    got = unstack_field_params(spec, jax.device_get(sp))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-6, atol=1e-7,
        ),
        canonical, got,
    )


def test_sharded_2d_device_matches_single_chip(rng):
    ids, vals, labels, weights = _batch(rng, b=64)
    spec = _spec()
    config = _base_cfg(sparse_update="dedup", compact_device=True,
                       compact_cap=CAP)
    canonical = spec.init(jax.random.key(1))
    single = make_field_sparse_sgd_step(spec, config)
    mesh = make_field_mesh(8, n_row=2)     # 4 feat x 2 row
    sharded = make_field_sharded_sgd_step(spec, config, mesh)
    sp = shard_field_params(
        stack_field_params(spec, jax.tree.map(jnp.copy, canonical), 4),
        mesh,
    )
    batch = pad_field_batch((ids, vals, labels, weights), F, 4)
    for i in range(3):
        canonical, l1 = single(
            canonical, jnp.int32(i), jnp.asarray(ids), jnp.asarray(vals),
            jnp.asarray(labels), jnp.asarray(weights),
        )
        sp, l2 = sharded(sp, jnp.int32(i), *shard_field_batch(batch, mesh))
        assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    got = unstack_field_params(spec, jax.device_get(sp))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-5, atol=1e-6,
        ),
        canonical, got,
    )


@pytest.mark.slow
def test_overflow_drop_semantics(rng):
    # One near-unique field overflows cap; policy 'drop' must train
    # through and act exactly as if the overflow ids (the LARGEST ids
    # past the cap-th unique) were absent features (val=0) — provable
    # bitwise with reg=0.
    b, cap = 48, 8
    ids, vals, labels, weights = _batch(rng, b=b)
    ids[:, 2] = rng.permutation(b).astype(np.int32)  # near-unique field
    spec = _spec()
    cfg = dict(learning_rate=0.05, optimizer="sgd", reg_factors=0.0,
               reg_linear=0.0)
    drop_step = make_field_sparse_sgd_step(
        spec, TrainConfig(**cfg, sparse_update="dedup",
                          compact_device=True, compact_cap=cap,
                          compact_overflow="drop"),
    )
    ref_step = make_field_sparse_sgd_step(
        spec, TrainConfig(**cfg, sparse_update="dedup",
                          compact_device=True, compact_cap=b,
                          compact_overflow="error"),
    )
    # Reference batch: overflowing ids' vals zeroed by hand.
    vals_ref = vals.copy()
    for f in range(F):
        uniq = np.unique(ids[:, f])
        if uniq.size > cap:
            vals_ref[np.isin(ids[:, f], uniq[cap:]), f] = 0.0
    params = spec.init(jax.random.key(1))
    p_drop, l_drop = drop_step(
        jax.tree.map(jnp.copy, params), jnp.int32(0), jnp.asarray(ids),
        jnp.asarray(vals), jnp.asarray(labels), jnp.asarray(weights),
    )
    p_ref, l_ref = ref_step(
        params, jnp.int32(0), jnp.asarray(ids), jnp.asarray(vals_ref),
        jnp.asarray(labels), jnp.asarray(weights),
    )
    assert np.isfinite(float(l_drop))
    assert float(l_drop) == float(l_ref)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        p_drop, p_ref,
    )


def test_overflow_error_poisons_loss(rng):
    b, cap = 48, 8
    ids, vals, labels, weights = _batch(rng, b=b)
    ids[:, 2] = rng.permutation(b).astype(np.int32)
    spec = _spec()
    step = make_field_sparse_sgd_step(
        spec, _base_cfg(sparse_update="dedup", compact_device=True,
                        compact_cap=cap),  # compact_overflow defaults to error
    )
    _, loss = step(
        spec.init(jax.random.key(1)), jnp.int32(0), jnp.asarray(ids),
        jnp.asarray(vals), jnp.asarray(labels), jnp.asarray(weights),
    )
    assert np.isneginf(float(loss))


def test_sharded_2d_overflow_sentinel_not_counted(rng):
    # On the 2-D mesh the ownership-mask sentinel segment must NOT count
    # as overflow: a field whose uniques exactly fill cap on one row
    # shard still trains with finite loss under policy 'error'.
    ids, vals, labels, weights = _batch(rng, b=64)
    spec = _spec()
    config = _base_cfg(sparse_update="dedup", compact_device=True,
                       compact_cap=64)
    mesh = make_field_mesh(8, n_row=2)
    sharded = make_field_sharded_sgd_step(spec, config, mesh)
    sp = shard_field_params(
        stack_field_params(spec, spec.init(jax.random.key(1)), 4), mesh
    )
    batch = pad_field_batch((ids, vals, labels, weights), F, 4)
    sp, loss = sharded(sp, jnp.int32(0), *shard_field_batch(batch, mesh))
    assert np.isfinite(float(loss))


def test_config_validation():
    spec = _spec()
    with pytest.raises(ValueError, match="compact_device requires"):
        make_field_sparse_sgd_step(
            spec, _base_cfg(sparse_update="dedup", compact_device=True)
        )
    with pytest.raises(ValueError, match="exclusive"):
        make_field_sparse_sgd_step(
            spec, _base_cfg(sparse_update="dedup", compact_device=True,
                            host_dedup=True, compact_cap=8)
        )
    with pytest.raises(ValueError, match="device-side policy"):
        make_field_sparse_sgd_step(
            spec, _base_cfg(sparse_update="dedup", host_dedup=True,
                            compact_cap=8, compact_overflow="drop")
        )
    with pytest.raises(ValueError, match="host-pipeline policy"):
        make_field_sparse_sgd_step(
            spec, _base_cfg(sparse_update="dedup", compact_device=True,
                            compact_cap=8, compact_overflow="split")
        )
    # A non-default overflow policy without a cap is a silent no-op —
    # rejected (ADVICE r3).
    for policy in ("drop", "split"):
        with pytest.raises(ValueError, match="no.*effect|no effect"):
            make_field_sparse_sgd_step(
                spec, _base_cfg(compact_overflow=policy)
            )
    # The 'error' policy's -inf sentinel requires a provably
    # non-negative loss; an unlisted loss must fail at construction,
    # not silently corrupt the sentinel (ADVICE r4).
    from fm_spark_tpu.sparse import FIELD_FM, refuse_unserved

    with pytest.raises(ValueError, match="non-negative losses"):
        refuse_unserved(
            _base_cfg(sparse_update="dedup_sr", compact_device=True,
                      compact_cap=8, compact_overflow="error"),
            FIELD_FM, "the single-chip FieldFM body",
            "exotic_negative_loss",
        )


@pytest.mark.parametrize("mode", ["dedup", "dedup_sr"])
def test_ffm_device_matches_host_compact(rng, mode):
    """FieldFFM fused step via the shared _rows_for dispatch: device-
    built aux == host-built aux bitwise (stable sorts agree)."""
    from fm_spark_tpu.sparse import make_field_ffm_sparse_sgd_step

    spec = models.FieldFFMSpec(
        num_features=F * BUCKET, rank=3, num_fields=F, bucket=BUCKET,
        init_std=0.1,
    )
    ids_np = rng.integers(0, 8, size=(B, F)).astype(np.int32)
    batch = (jnp.asarray(ids_np),
             jnp.asarray(rng.normal(size=(B, F)).astype(np.float32)),
             jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
             jnp.ones((B,)))
    cfg = dict(learning_rate=0.2, optimizer="sgd", sparse_update=mode)
    params = spec.init(jax.random.key(1))
    params_c = jax.tree.map(jnp.copy, params)
    step_h = make_field_ffm_sparse_sgd_step(
        spec, TrainConfig(host_dedup=True, compact_cap=CAP, **cfg)
    )
    step_d = make_field_ffm_sparse_sgd_step(
        spec, TrainConfig(compact_device=True, compact_cap=CAP, **cfg)
    )
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids_np, CAP))
    for i in range(2):
        params, _ = step_h(params, jnp.int32(i), *batch, aux)
        params_c, _ = step_d(params_c, jnp.int32(i), *batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        ),
        params, params_c,
    )


@pytest.mark.slow
def test_deepfm_device_matches_host_compact(rng):
    """FieldDeepFM hybrid step: device-built aux == host-built aux."""
    from fm_spark_tpu.sparse import make_field_deepfm_sparse_step

    spec = models.FieldDeepFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, mlp_dims=(8, 8),
    )
    ids_np = rng.integers(0, 8, size=(B, F)).astype(np.int32)
    batch = (jnp.asarray(ids_np),
             jnp.asarray(rng.normal(size=(B, F)).astype(np.float32)),
             jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
             jnp.ones((B,)))
    cfg = dict(learning_rate=0.05, optimizer="adam", sparse_update="dedup")
    params = spec.init(jax.random.key(2))
    params_c = jax.tree.map(jnp.copy, params)
    step_h = make_field_deepfm_sparse_step(
        spec, TrainConfig(host_dedup=True, compact_cap=CAP, **cfg)
    )
    step_d = make_field_deepfm_sparse_step(
        spec, TrainConfig(compact_device=True, compact_cap=CAP, **cfg)
    )
    opt_h = step_h.init_opt_state(params)
    opt_d = step_d.init_opt_state(params_c)
    aux = tuple(jnp.asarray(a) for a in compact_aux(ids_np, CAP))
    for i in range(2):
        params, opt_h, _ = step_h(params, opt_h, jnp.int32(i), *batch, aux)
        params_c, opt_d, _ = step_d(params_c, opt_d, jnp.int32(i), *batch)
    # The two programs differ (aux built in-step), so XLA may fuse the
    # dense MLP reductions differently — tight allclose, not bitwise.
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-6, atol=1e-8,
        ),
        params, params_c,
    )


class _ListSource:
    def __init__(self, batches):
        self._batches = list(batches)
        self._i = 0

    def next_batch(self):
        b = self._batches[self._i % len(self._batches)]
        self._i += 1
        return b

    def state(self):
        return {"i": self._i}

    def restore(self, state):
        self._i = int(state["i"])


def test_host_overflow_split_trains_through(rng):
    """VERDICT r2 #4: an adversarial batch (one near-unique field whose
    uniques exceed cap) must TRAIN THROUGH under compact_overflow=
    'split' — halved, inert-padded to the static batch shape, exact
    semantics per half — instead of killing the run."""
    from fm_spark_tpu.data import DedupAuxBatches

    b, cap = 48, 16
    ids, vals, labels, weights = _batch(rng, b=b)
    ids[:, 2] = rng.permutation(b).astype(np.int32)  # 48 uniques > 16
    src = _ListSource([(ids, vals, labels, weights)])
    wrapped = DedupAuxBatches(src, cap=cap, overflow="split")
    spec = _spec()
    step = make_field_sparse_sgd_step(
        spec, _base_cfg(sparse_update="dedup", host_dedup=True,
                        compact_cap=cap, compact_overflow="split"),
    )
    params = spec.init(jax.random.key(1))
    losses = []
    for i in range(4):  # 48/16 → split to quarters: 4 sub-batches queued
        bi = wrapped.next_batch()
        assert bi[0].shape == (b, F)            # static step shape kept
        aux = tuple(jnp.asarray(a) for a in bi[4])
        params, loss = step(
            params, jnp.int32(i), jnp.asarray(bi[0]), jnp.asarray(bi[1]),
            jnp.asarray(bi[2]), jnp.asarray(bi[3]), aux,
        )
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    # All four sub-batches came from the ONE source batch.
    assert src._i == 1
    # Real rows partition the batch: total live weight across the splits
    # equals the original batch's.
    # (weights zeroed every 7th row in _batch)
    # Re-generate the four sub-batches to check the partition property.
    src2 = _ListSource([(ids, vals, labels, weights)])
    w2 = DedupAuxBatches(src2, cap=cap, overflow="split")
    tot = sum(float(w2.next_batch()[3].sum()) for _ in range(4))
    assert tot == float(weights.sum())


def test_host_overflow_error_still_raises(rng):
    from fm_spark_tpu.data import DedupAuxBatches
    from fm_spark_tpu.ops.scatter import CompactCapOverflow

    b, cap = 48, 16
    ids, vals, labels, weights = _batch(rng, b=b)
    ids[:, 2] = rng.permutation(b).astype(np.int32)
    wrapped = DedupAuxBatches(
        _ListSource([(ids, vals, labels, weights)]), cap=cap
    )
    with pytest.raises(CompactCapOverflow):
        wrapped.next_batch()


def test_split_state_replays_whole_batch(rng):
    """A checkpoint cursor taken while split halves are pending must
    point BEFORE the split source batch (resume replays it whole —
    duplicates allowed, silent skips never)."""
    from fm_spark_tpu.data import DedupAuxBatches

    b, cap = 48, 16
    ids, vals, labels, weights = _batch(rng, b=b)
    ids[:, 2] = rng.permutation(b).astype(np.int32)
    src = _ListSource([(ids, vals, labels, weights)])
    wrapped = DedupAuxBatches(src, cap=cap, overflow="split")
    wrapped.next_batch()                    # half 1 of the split
    assert wrapped.state() == {"i": 0}      # pre-split cursor
    for _ in range(3):
        wrapped.next_batch()                # drain remaining halves
    assert wrapped.state() == {"i": 1}      # batch consumed → advanced


def test_multistep_poison_is_sticky(rng):
    """The fori-rolled multistep must not swallow an inner step's −inf
    overflow poison when a later step is clean."""
    from fm_spark_tpu.sparse import make_field_sparse_multistep

    b, cap = 48, 8
    ids, vals, labels, weights = _batch(rng, b=b)
    ids2 = ids.copy()
    ids2[:, 2] = rng.permutation(b).astype(np.int32)  # overflows cap
    spec = _spec()
    cfg = TrainConfig(learning_rate=0.05, optimizer="sgd",
                      sparse_update="dedup", compact_device=True,
                      compact_cap=cap)  # compact_overflow='error'
    mstep = make_field_sparse_multistep(spec, cfg, 2)
    stack = lambda a, b_: jnp.stack([jnp.asarray(a), jnp.asarray(b_)])
    params, loss = mstep(
        spec.init(jax.random.key(1)), jnp.int32(0), jnp.int32(2),
        stack(ids2, ids), stack(vals, vals), stack(labels, labels),
        stack(weights, weights),
    )
    # Step 0 overflowed, step 1 was clean — the poison must survive.
    assert np.isneginf(float(loss))


def test_sharded_builders_validate_unconditionally():
    """compact_device without compact_cap must fail at BUILD time on the
    sharded factories exactly as on the single-chip ones (review r3
    finding: the sharded builders used to validate only when
    compact_cap > 0, silently training the plain path)."""
    from fm_spark_tpu.parallel import (
        make_field_ffm_sharded_body,
        make_field_sharded_sgd_body,
    )

    mesh = make_field_mesh(8)
    cfg = _base_cfg(sparse_update="dedup", compact_device=True)
    with pytest.raises(ValueError, match="compact_device requires"):
        make_field_sharded_sgd_body(_spec(), cfg, mesh)
    ffm_spec = models.FieldFFMSpec(
        num_features=F * BUCKET, rank=3, num_fields=F, bucket=BUCKET,
        init_std=0.1,
    )
    with pytest.raises(ValueError, match="compact_device requires"):
        make_field_ffm_sharded_body(ffm_spec, cfg, mesh)


def test_sharded_deepfm_device_matches_single_chip(rng):
    """Sharded DeepFM with the device-built compact aux must match the
    single-chip device-compact DeepFM step (round-3 capability cell)."""
    from fm_spark_tpu.parallel import (
        make_field_deepfm_sharded_step,
        shard_field_deepfm_params,
        stack_field_deepfm_params,
        unstack_field_deepfm_params,
    )
    from fm_spark_tpu.sparse import make_field_deepfm_sparse_step

    ids, vals, labels, weights = _batch(rng, b=64)
    spec = models.FieldDeepFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, mlp_dims=(8, 8),
    )
    config = _base_cfg(sparse_update="dedup", compact_device=True,
                       compact_cap=CAP, optimizer="adam")
    canonical = spec.init(jax.random.key(2))
    single = make_field_deepfm_sparse_step(spec, config)
    mesh = make_field_mesh(8)
    sharded = make_field_deepfm_sharded_step(spec, config, mesh)
    sp = shard_field_deepfm_params(
        stack_field_deepfm_params(
            spec, jax.tree.map(jnp.copy, canonical), 8
        ),
        mesh,
    )
    opt_s = single.init_opt_state(canonical)
    opt_sh = sharded.init_opt_state(sp)
    batch = pad_field_batch((ids, vals, labels, weights), F, 8)
    for i in range(3):
        canonical, opt_s, l1 = single(
            canonical, opt_s, jnp.int32(i), jnp.asarray(ids),
            jnp.asarray(vals), jnp.asarray(labels), jnp.asarray(weights),
        )
        sp, opt_sh, l2 = sharded(
            sp, opt_sh, jnp.int32(i), *shard_field_batch(batch, mesh)
        )
        assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    got = unstack_field_deepfm_params(spec, jax.device_get(sp))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-5, atol=1e-6,
        ),
        canonical, got,
    )


def test_sharded_deepfm_device_overflow_error(rng):
    """The overflow poison must propagate through the sharded DeepFM
    step's dense-optimizer wrapper too."""
    from fm_spark_tpu.parallel import (
        make_field_deepfm_sharded_step,
        shard_field_deepfm_params,
        stack_field_deepfm_params,
    )

    b, cap = 64, 8
    ids, vals, labels, weights = _batch(rng, b=b)
    ids[:, 2] = rng.permutation(b).astype(np.int32)
    spec = models.FieldDeepFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, mlp_dims=(8, 8),
    )
    config = _base_cfg(sparse_update="dedup", compact_device=True,
                       compact_cap=cap, optimizer="adam")
    mesh = make_field_mesh(8)
    sharded = make_field_deepfm_sharded_step(spec, config, mesh)
    sp = shard_field_deepfm_params(
        stack_field_deepfm_params(spec, spec.init(jax.random.key(2)), 8),
        mesh,
    )
    opt = sharded.init_opt_state(sp)
    batch = pad_field_batch((ids, vals, labels, weights), F, 8)
    sp, opt, loss = sharded(
        sp, opt, jnp.int32(0), *shard_field_batch(batch, mesh)
    )
    assert np.isneginf(float(loss))


@pytest.mark.parametrize("dev_compact", [False, True])
def test_sharded_deepfm_2d_matches_single_chip(rng, dev_compact):
    """DeepFM on the 2-D (feat, row) mesh — shared-forward refactor
    (round 3): ownership-masked gathers + row-psum'd deep-head input
    must match the single-chip step, with and without the device-built
    compact aux."""
    from fm_spark_tpu.parallel import (
        make_field_deepfm_sharded_step,
        shard_field_deepfm_params,
        stack_field_deepfm_params,
        unstack_field_deepfm_params,
    )
    from fm_spark_tpu.sparse import make_field_deepfm_sparse_step

    ids, vals, labels, weights = _batch(rng, b=64)
    spec = models.FieldDeepFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.1, mlp_dims=(8, 8),
    )
    kw = dict(sparse_update="dedup", optimizer="adam")
    if dev_compact:
        kw.update(compact_device=True, compact_cap=CAP)
    config = _base_cfg(**kw)
    canonical = spec.init(jax.random.key(2))
    single = make_field_deepfm_sparse_step(spec, config)
    mesh = make_field_mesh(8, n_row=2)    # 4 feat x 2 row
    sharded = make_field_deepfm_sharded_step(spec, config, mesh)
    sp = shard_field_deepfm_params(
        stack_field_deepfm_params(
            spec, jax.tree.map(jnp.copy, canonical), 4
        ),
        mesh,
    )
    opt_s = single.init_opt_state(canonical)
    opt_sh = sharded.init_opt_state(sp)
    batch = pad_field_batch((ids, vals, labels, weights), F, 4)
    for i in range(3):
        canonical, opt_s, l1 = single(
            canonical, opt_s, jnp.int32(i), jnp.asarray(ids),
            jnp.asarray(vals), jnp.asarray(labels), jnp.asarray(weights),
        )
        sp, opt_sh, l2 = sharded(
            sp, opt_sh, jnp.int32(i), *shard_field_batch(batch, mesh)
        )
        assert float(l1) == pytest.approx(float(l2), rel=2e-5)
    got = unstack_field_deepfm_params(spec, jax.device_get(sp))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-5, atol=2e-6,
        ),
        canonical, got,
    )


def test_overflow_guard_sticky():
    """ADVICE r3 + round-4 review: an overflow at step i followed by
    clean steps must still fail the NEXT boundary check — the guard is
    a running min, not a point read of the latest loss."""
    import jax.numpy as jnp

    from fm_spark_tpu.cli import _make_overflow_guard

    cfg = _base_cfg(sparse_update="dedup", compact_device=True,
                    compact_cap=8)  # compact_overflow defaults to error
    note, check, fetch = _make_overflow_guard(cfg)
    note(jnp.float32(0.69))
    check()  # clean so far
    note(jnp.float32(-jnp.inf))   # the poisoned step
    note(jnp.float32(0.55))       # clean again — must NOT clear it
    with pytest.raises(SystemExit, match="compact_cap overflow"):
        check()
    # fetch_loss shares the sticky detector.
    note2, _, fetch2 = _make_overflow_guard(cfg)
    note2(jnp.float32(-jnp.inf))
    note2(jnp.float32(0.5))
    with pytest.raises(SystemExit, match="compact_cap overflow"):
        fetch2(jnp.float32(0.5))
    # Inactive policy (drop): everything is a no-op / plain float.
    note3, check3, fetch3 = _make_overflow_guard(
        _base_cfg(sparse_update="dedup", compact_device=True,
                  compact_cap=8, compact_overflow="drop"))
    note3(jnp.float32(-jnp.inf))
    check3()
    assert fetch3(jnp.float32(0.5)) == np.float32(0.5)
