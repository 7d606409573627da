"""``criteo_xdeepfm_cin200``: xDeepFM through DeepFM's fused body
(``sparse.make_field_deepfm_sparse_body`` with ``models/field_xdeepfm.py``
as its head) against the benchmark's plain reference
(``benchmark/reference/xdeepfm.py``), at a small size on the CPU: 5
fields, 64 buckets, rank 4, a CIN of 6, 5 and 4 feature maps, a DNN of 8
and 8, batch 32.

- the spec's CIN against eq. 6 and 7 written out pair by pair in float64
  loops (not the same einsum), and the reference's likewise; its
  gradients, kernels and ``X^0``, against the reference's; its last layer,
  which pools before it compresses, against every layer built, compressed
  and pooled in turn, forward and back;
- ``scores`` against the reference's;
- the fused body against the reference at 2 and 8 steps: losses, every
  touched row, every dense leaf and both of Adam's moments;
- ``cli train --config`` through the driver's own comparison
  (``benchmark/drivers/train_xdeep.py`` ``compare`` under the limits of
  ``traffic/train_fed_xdeep.json``: what decides the cell's ``correct``);
  the faults it exists to catch (``benchmark/tests/xdeep_faults.py``) and
  the reference one precision lower each FAIL it;
- the capability row's and the body's refusals;
- ``mxu_flops_per_step`` and the gauges against ``benchmark/cin_flops.py``,
  and ``cin_product_flops_per_step`` against its formula;
- ``cli eval`` / ``cli predict`` and ``PredictEngine`` return
  ``spec.predict``.
"""

import dataclasses
import importlib.util
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cin_flops, cin_trace, harness  # noqa: E402
from benchmark.drivers import train_deep, train_xdeep  # noqa: E402
from benchmark.reference import sgd, xdeepfm  # noqa: E402
from fm_spark_tpu import cli, models, obs, sparse  # noqa: E402
from fm_spark_tpu import configs as configs_lib  # noqa: E402
from fm_spark_tpu import data as data_lib  # noqa: E402
from fm_spark_tpu.models.field_xdeepfm import FieldXDeepFMSpec  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "xdeep_faults", os.path.join(ROOT, "benchmark", "tests",
                                 "xdeep_faults.py"))
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)

F, K, BUCKET, CIN, MLP, BATCH = 5, 4, 64, (6, 5, 4), (8, 8), 32
MIX = harness.load_mix("train_fed_xdeep")
STEPS, EARLY = int(MIX["check_steps"]), int(MIX["early_steps"])
SEED = 11
REGISTERED = configs_lib.CONFIGS["criteo_xdeepfm_cin200"]
TINY = dict(num_fields=F, bucket=BUCKET, rank=K, cin_layers=CIN,
            mlp_dims=MLP, batch_size=BATCH)


def spec_and_config(**overrides):
    cfg = dataclasses.replace(REGISTERED, **TINY)
    return cfg.spec(), cfg.train_config(**overrides)


def one_batch(seed=0):
    """Hot ids take most of the lanes, so every field has duplicates."""
    rng = np.random.default_rng(seed)
    ids = np.where(rng.random((BATCH, F)) < 0.6,
                   rng.integers(0, 4, (BATCH, F)),
                   rng.integers(0, BUCKET, (BATCH, F))).astype(np.int32)
    vals = rng.uniform(0.5, 1.5, (BATCH, F)).astype(np.float32)
    return (ids, vals, rng.integers(0, 2, BATCH).astype(np.float32),
            np.ones((BATCH,), np.float32))


def scaled(params, factor=30.0):
    """``params`` with the rows' factors ``factor`` times larger: at the
    registered ``N(0, 0.01)`` the CIN's deeper layers are too small to read
    in float32 beside the rest."""
    return {**params, "vw": [t.at[:, :K].multiply(factor)
                             for t in params["vw"]]}


# ------------------------------------------------------------ the equations


def cin_by_pairs(kernels, x0):
    """Eq. 6 and 7 pair by pair in float64: ``x0`` ``[m, D]`` of one
    example → ``p+``."""
    x, pooled = x0, []
    for w in kernels:
        h_out, h_prev, m = w.shape
        nxt = np.zeros((h_out, x0.shape[1]))
        for h in range(h_out):
            for i in range(h_prev):
                for j in range(m):
                    nxt[h] += w[h, i, j] * (x[i] * x0[j])
        pooled.append(nxt.sum(axis=1))
        x = nxt
    return np.concatenate(pooled)


def test_the_cin_is_eq_6_and_7_pair_by_pair():
    spec, _ = spec_and_config()
    params = scaled(spec.init(jax.random.key(1)))
    ids, vals, _, _ = one_batch(2)
    kernels = [np.asarray(w, np.float64) for w in params["cin"]["layers"]]
    assert [w.shape for w in kernels] == [(6, 5, 5), (5, 6, 5), (4, 5, 5)]
    rows = [np.asarray(params["vw"][f], np.float64)[ids[:, f]]
            for f in range(F)]
    x0 = np.stack([rows[f][:, :K] * vals[:, f:f + 1] for f in range(F)],
                  axis=1)                                       # [B, m, D]
    want = np.stack([cin_by_pairs(kernels, x0[b]) for b in range(BATCH)])
    h = jnp.asarray(x0.reshape(BATCH, F * K), jnp.float32)
    got = np.asarray(spec.cin(params["cin"]["layers"], spec.cin_input(h)))
    ref = np.asarray(xdeepfm.cin_pooled(
        [jnp.asarray(w, jnp.float32) for w in kernels],
        jnp.asarray(x0, jnp.float32)))
    # float32 against float64 sums of 30 to 150 terms: parts in a million
    # of each layer's largest value.
    for k, (lo, hi) in enumerate(((0, 6), (6, 11), (11, 15))):
        scale = np.abs(want[:, lo:hi]).max()
        assert scale > 0, k
        np.testing.assert_allclose(got[:, lo:hi], want[:, lo:hi], rtol=0,
                                   atol=1e-5 * scale, err_msg=f"p{k + 1}")
        np.testing.assert_allclose(ref[:, lo:hi], want[:, lo:hi], rtol=0,
                                   atol=1e-5 * scale, err_msg=f"ref p{k + 1}")


def test_the_swapped_first_kernel_is_the_same_function():
    """(e) at layer 1 is no fault: ``Z^1 = X^0 (x) X^0`` is symmetric in
    its two indices, so ``W^1[h, j, i]`` gives the same ``X^1`` and the
    same gradient (the same products, added in another order: to a few
    ulps); only a deeper layer can hold the order."""
    spec, _ = spec_and_config()
    params = scaled(spec.init(jax.random.key(4)))
    ids, vals, _, _ = one_batch(5)
    h = jnp.concatenate([params["vw"][f][ids[:, f], :K] * vals[:, f:f + 1]
                         for f in range(F)], axis=1)
    x0 = spec.cin_input(h)
    w1 = params["cin"]["layers"][0]

    def first(w):
        return jnp.sum(spec.cin([w], x0) ** 2)

    np.testing.assert_allclose(
        np.asarray(spec.cin([jnp.swapaxes(w1, 1, 2)], x0)),
        np.asarray(spec.cin([w1], x0)), rtol=1e-6, atol=1e-7)
    g, g_swapped = (jax.grad(first)(w) for w in (w1, jnp.swapaxes(w1, 1, 2)))
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(g_swapped, 1, 2)),
                               np.asarray(g), rtol=0,
                               atol=1e-6 * float(jnp.abs(g).max()))
    with faults.kernel_indices_swapped("any"):
        wrong = np.asarray(spec.cin(params["cin"]["layers"], x0))
    right = np.asarray(spec.cin(params["cin"]["layers"], x0))
    np.testing.assert_allclose(wrong[:, :6], right[:, :6], rtol=1e-6,
                               atol=1e-7)
    assert np.abs(wrong[:, 6:] - right[:, 6:]).max() > 0.1 * np.abs(
        right[:, 6:]).max()


def test_the_cins_gradients_are_the_references():
    """``jax.grad`` of ``spec.cin``, which holds its maps ``[H_k, D B]``,
    against the reference's ``cin_pooled`` on ``[B, m, D]``, for the
    kernels and ``X^0`` alike. ``H_0 = m = 5`` is no multiple of 8 and
    the layers' widths differ (6, 4, 3), so a block flattened ``(i, j)``
    where the kernel reads ``(j, i)``, or a layer pooled over the wrong
    axis, cannot pass."""
    cfg = dataclasses.replace(REGISTERED, **{**TINY, "rank": 3,
                                             "cin_layers": (6, 4, 3)})
    spec = cfg.spec()
    m, d = spec.num_fields, spec.rank
    kernels = spec.init(jax.random.key(12))["cin"]["layers"]
    assert [w.shape for w in kernels] == [(6, 5, 5), (4, 6, 5), (3, 4, 5)]
    k_h, k_c = jax.random.split(jax.random.key(13))
    h = jax.random.normal(k_h, (BATCH, m * d), jnp.float32)
    weight = jax.random.normal(k_c, (BATCH, sum(spec.cin_layers)),
                               jnp.float32)

    def program(ks, h):
        return jnp.sum(weight * spec.cin(ks, spec.cin_input(h)))

    def reference(ks, h):
        return jnp.sum(weight * xdeepfm.cin_pooled(
            ks, h.reshape(BATCH, m, d)))

    got = jax.grad(program, argnums=(0, 1))(kernels, h)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(reference, argnums=(0, 1))(kernels, h)
    for what, g, w in zip(("W^1", "W^2", "W^3", "X^0"), (*got[0], got[1]),
                          (*want[0], want[1])):
        assert g.shape == w.shape, what
        scale = float(jnp.abs(w).max())
        assert scale > 0, what
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=1e-5 * scale, err_msg=what)


def cin_layer_by_layer(self, kernels, x0):
    """``FieldXDeepFMSpec.cin`` with every layer, the last too, built as a
    ``[m H_{k-1}, D B]`` Hadamard block, compressed to ``X^k`` ``[H_k, D
    B]`` and then pooled over ``D``: the form the last layer's pooling
    first must equal (``tests/test_table_layout.py`` compiles it too)."""
    rows, m = x0.shape
    d, batch = self.rank, rows // self.rank
    x0t = x0.reshape(batch, d, m).transpose(2, 1, 0).reshape(m, d * batch)
    x, pooled = x0t, []
    for w in kernels:
        h, h_prev, _ = w.shape
        z = (x0t[:, None, :] * x[None, :, :]).reshape(m * h_prev, d * batch)
        x = jax.lax.dot_general(
            jnp.swapaxes(w, 1, 2).reshape(h, m * h_prev), z,
            (((1,), (0,)), ((), ())), precision=self._precision)
        pooled.append(x.reshape(h, d, batch).sum(axis=1))
    return jnp.concatenate(pooled, axis=0).T


# K = 1 (the last layer is the first, X^{K-1} = X^0), 2 and 3 with
# H_{K-1} = 5, 6, 5, no multiple of 8, and one of 16.
@pytest.mark.parametrize("cin_layers", [(6,), (6, 5), (6, 5, 4),
                                        (16, 16, 3)])
def test_the_last_layer_pools_first_and_is_the_same_function(cin_layers):
    """``p^K = W^K . sum_d (X^0 * X^{K-1})``, the sum moved inside the
    linear map: the same ``p+`` and the same gradients, kernels and
    ``X^0``, as the layer built at ``D B`` columns and pooled after, to a
    millionth of each one's largest value; and the gauge of the products
    the step runs is its formula, under the model's count."""
    cfg = dataclasses.replace(REGISTERED, **{**TINY,
                                             "cin_layers": cin_layers})
    spec = cfg.spec()
    m, d, dims = spec.num_fields, spec.rank, spec.cin_dims
    kernels = spec.init(jax.random.key(14))["cin"]["layers"]
    k_h, k_c = jax.random.split(jax.random.key(15))
    h = jax.random.normal(k_h, (BATCH, m * d), jnp.float32)
    weight = jax.random.normal(k_c, (BATCH, sum(cin_layers)), jnp.float32)

    def loss(cin):
        return lambda ks, h: jnp.sum(weight * cin(spec, ks,
                                                  spec.cin_input(h)))

    got_p = spec.cin(kernels, spec.cin_input(h))
    want_p = cin_layer_by_layer(spec, kernels, spec.cin_input(h))
    got = jax.grad(loss(FieldXDeepFMSpec.cin), argnums=(0, 1))(kernels, h)
    want = jax.grad(loss(cin_layer_by_layer), argnums=(0, 1))(kernels, h)
    ends = np.cumsum((0, *cin_layers))
    parts = [(f"p{k + 1}", got_p[:, lo:hi], want_p[:, lo:hi])
             for k, (lo, hi) in enumerate(zip(ends[:-1], ends[1:]))]
    parts += [(f"W^{k + 1}", g, w)
              for k, (g, w) in enumerate(zip(got[0], want[0]))]
    for what, g, w in [*parts, ("X^0", got[1], want[1])]:
        assert g.shape == w.shape, what
        scale = float(jnp.abs(w).max())
        assert scale > 0, what
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=1e-6 * scale, err_msg=what)

    per_column = [a * m * b for a, b in zip(dims[:-1], dims[1:])]
    runs = spec.cin_product_flops_per_step(BATCH)
    assert runs == 6 * BATCH * (d * sum(per_column[:-1]) + per_column[-1])
    assert runs < cin_flops.step_matmul_flops(BATCH, m, d, cin_layers)
    assert runs < spec.mxu_flops_per_step(BATCH)


def test_scores_are_the_references():
    spec, _ = spec_and_config()
    params = scaled(spec.init(jax.random.key(3)))
    params["w0"] = jnp.float32(0.25)
    ids, vals, _, _ = one_batch(6)
    got = np.asarray(spec.scores(params, jnp.asarray(ids), jnp.asarray(vals)))
    rows = [params["vw"][f][ids[:, f]] for f in range(F)]
    dense = {key: params[key] for key in spec.dense_keys}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(xdeepfm.scores(rows, dense, jnp.asarray(vals), K))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(spec.predict(params, jnp.asarray(ids), jnp.asarray(vals))),
        1.0 / (1.0 + np.exp(-want)), rtol=1e-5)
    # Every part reaches the logit: the CIN, the DNN, the linear term.
    for part, change in (
            ("cin", {"cin": {**params["cin"],
                             "out": params["cin"]["out"] * 2}}),
            ("mlp", {"mlp": {**params["mlp"],
                             "out": params["mlp"]["out"] * 2}}),
            ("linear", {"vw": [t.at[:, K].set(0.1) for t in params["vw"]]})):
        moved = np.asarray(spec.scores({**params, **change}, jnp.asarray(ids),
                                       jnp.asarray(vals)))
        assert np.abs(moved - got).max() > 1e-4, part
    with pytest.raises(ValueError, match="slots"):
        spec.scores(params, jnp.asarray(ids[:, 1:]), jnp.asarray(vals[:, 1:]))


def test_registry_shapes_are_the_published_ones():
    spec = REGISTERED.spec()
    assert isinstance(spec, FieldXDeepFMSpec)
    assert (spec.rank, spec.num_fields, spec.bucket) == (10, 39, 1 << 18)
    assert spec.cin_layers == (200, 200, 200) and spec.mlp_dims == (400, 400)
    assert spec.cin_dims == (39, 200, 200, 200)
    assert spec.table_width == 11 and not spec.fm_interaction
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    assert [w.shape for w in shapes["cin"]["layers"]] == [
        (200, 39, 39), (200, 200, 39), (200, 200, 39)]
    assert shapes["cin"]["out"].shape == (600,)
    assert [(l["kernel"].shape, l["bias"].shape)
            for l in shapes["mlp"]["layers"]] == [((390, 400), (400,)),
                                                  ((400, 400), (400,))]
    assert shapes["mlp"]["out"].shape == (400,)
    assert sum(l.size for l in jax.tree.leaves(shapes["cin"])) == 3_424_800
    assert sum(l.size for l in jax.tree.leaves(shapes["mlp"])) == 317_200
    config = REGISTERED.train_config()
    assert (config.optimizer, config.learning_rate, config.reg_factors,
            config.lr_schedule, REGISTERED.batch_size) == (
                "adam", 1e-3, 1e-4, "constant", 4096)
    batch = 4096
    cin = cin_flops.step_matmul_flops(batch, 39, 10, spec.cin_layers)
    assert cin == 6 * batch * 10 * 3_424_200 == 841_531_392_000
    assert spec.mxu_flops_per_step(batch) == cin + 6 * batch * 317_000
    assert spec.cin_outer_elems_per_step(batch) == cin_flops.outer_elems(
        batch, 39, 10, spec.cin_layers) == 701_276_160
    # The last layer pools first: its 200 x 39 x 200 kernel meets B
    # columns, not D B.
    assert spec.cin_product_flops_per_step(batch) == (
        6 * batch * (10 * (39 * 39 * 200 + 200 * 39 * 200)
                     + 200 * 39 * 200)) == 496_484_352_000


def test_init_is_mirrored_by_the_reference():
    spec, _ = spec_and_config()
    params = spec.init(jax.random.key(7))
    uniq = np.tile(np.arange(BUCKET, dtype=np.int32), (F, 1))
    rows = xdeepfm.init_rows(7, uniq, BUCKET, K, 0.01)
    for f in range(F):
        # DeepFM's mirror scales the draw inside a jit, the program
        # eagerly: the same numbers to an ulp.
        np.testing.assert_allclose(np.asarray(rows[f]),
                                   np.asarray(params["vw"][f]), rtol=2e-7,
                                   atol=0)
    mirror = xdeepfm.init_dense(7, F, K, CIN, MLP)
    for key in spec.dense_keys:
        for a, b in zip(jax.tree.leaves(mirror[key]),
                        jax.tree.leaves(params[key])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The tables are DeepFM's, from the same key.
    deep = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_deepfm"], num_fields=F, bucket=BUCKET,
        rank=K).spec().init(jax.random.key(7))
    for f in range(F):
        np.testing.assert_array_equal(np.asarray(deep["vw"][f]),
                                      np.asarray(params["vw"][f]))


# --------------------------------------------- the fused body, directly


def run_body(steps, seed=0, **overrides):
    spec, config = spec_and_config(**overrides)
    body, init_opt = sparse.make_field_deepfm_sparse_body(spec, config)
    step = jax.jit(body)
    params0 = scaled(spec.init(jax.random.key(3)))
    params, opt = params0, init_opt(params0)
    batch = tuple(map(jnp.asarray, one_batch(seed)))
    losses, pooled = [], []
    for i in range(steps):
        params, opt, loss, stats = step(params, opt, jnp.int32(i), *batch)
        losses.append(float(loss))
        pooled.append(np.asarray(stats["cin_pooled"]))
    return params, opt, losses, params0, pooled


@pytest.mark.parametrize("steps", [EARLY, STEPS])
def test_fused_steps_match_the_reference_in_every_leaf_and_moment(steps):
    """Two horizons, from rows 30 times the registered scale so that every
    CIN layer carries gradient. After two steps program and reference are
    two float32 evaluations of the same arithmetic: every element within
    1e-4 of its leaf's largest move (Adam's ``g / sqrt(v)`` carries a
    gradient's last bits into the move). After eight steps on one batch
    the trajectory has amplified that: 1e-2."""
    params, opt, losses, params0, pooled = run_body(steps)
    ids, vals, labels, _ = one_batch(0)
    uniq, counts, inv, _ = sgd.touched(ids)
    rows0 = np.stack([np.asarray(params0["vw"][f])[uniq[f]]
                      for f in range(F)])
    dense0 = {key: params0[key] for key in ("w0", "cin", "mlp")}
    want = xdeepfm.train(rows0, dense0, inv, vals, labels, rank=K,
                         steps=STEPS, learning_rate=REGISTERED.learning_rate,
                         reg_factors=REGISTERED.reg_factors, reg_linear=0.0,
                         reg_bias=0.0, block=BATCH // 2,
                         keep_after=(EARLY,))
    ref = want if steps == STEPS else {**want["after"][steps],
                                       "losses": want["losses"][:steps]}
    rtol = 1e-4 if steps == EARLY else 1e-2
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-5)
    # What the step reports of its CIN, each map summed over the batch, is
    # the reference's at every step (sums of 32 examples' maps, float32).
    np.testing.assert_allclose(np.stack(pooled), want["pooled"][:steps],
                               rtol=rtol, atol=1e-6 * np.abs(
                                   want["pooled"]).max())

    def close(got, ref_leaf, start, what):
        # Plus two ulps of the leaf's largest value: a row moves by parts
        # in a thousand of itself a step, near its own rounding.
        move = np.abs(ref_leaf - start).max()
        assert move > 0, what
        ulp = np.spacing(np.float32(np.abs(ref_leaf).max()))
        np.testing.assert_allclose(got, ref_leaf, rtol=0,
                                   atol=rtol * move + 2 * ulp, err_msg=what)

    live = counts > 0
    for f in range(F):
        table, start = (np.asarray(t[f]) for t in (params["vw"],
                                                   params0["vw"]))
        touched = uniq[f][live[f]]
        close(table[touched], ref["rows"][f][live[f]], start[touched],
              f"rows {f}")
        rest = np.setdiff1d(np.arange(BUCKET), touched)
        np.testing.assert_array_equal(table[rest], start[rest])
    leaves = jax.tree_util.tree_leaves_with_path
    got = dict(leaves({key: params[key] for key in ("w0", "cin", "mlp")}))
    start = dict(leaves(dense0))
    for path, leaf in leaves(ref["dense"]):
        close(np.asarray(got[path]), leaf, np.asarray(start[path]),
              jax.tree_util.keystr(path))
    # Adam's moments: the first of the optax state's two trees.
    adam = opt[0]
    for moment, mine in (("m", adam.mu), ("v", adam.nu)):
        for path, leaf in leaves(ref[moment]):
            mine_leaf = np.asarray(dict(leaves(mine))[path])
            np.testing.assert_allclose(
                mine_leaf, leaf, rtol=0, atol=10 * rtol * np.abs(leaf).max()
                + 1e-30, err_msg=f"{moment} {jax.tree_util.keystr(path)}")
    assert int(adam.count) == steps


def test_the_body_adds_no_fm_term():
    """Without ``fm_interaction`` a row's gradient is the head's pullback
    alone: with the head's output vectors zero and no L2, the factors do
    not move (the FM term would move them)."""
    spec, config = spec_and_config(reg_factors=0.0)
    body, init_opt = sparse.make_field_deepfm_sparse_body(spec, config)
    params0 = spec.init(jax.random.key(9))
    params0["cin"] = {**params0["cin"],
                      "out": jnp.zeros_like(params0["cin"]["out"])}
    params0["mlp"] = {**params0["mlp"],
                      "out": jnp.zeros_like(params0["mlp"]["out"])}
    batch = tuple(map(jnp.asarray, one_batch(1)))
    params, *_ = jax.jit(body)(params0, init_opt(params0), jnp.int32(0),
                               *batch)
    for f in range(F):
        np.testing.assert_array_equal(np.asarray(params["vw"][f][:, :K]),
                                      np.asarray(params0["vw"][f][:, :K]))
        # The linear weight is the logit's, and moves.
        assert np.abs(np.asarray(params["vw"][f][:, K])).max() > 0


# --------------------------------------------------- through cli train


@pytest.fixture
def tiny(monkeypatch):
    """The registry's entry at the small size, as a cell's context."""
    small = dataclasses.replace(REGISTERED, name="xdeepfm_tiny", **TINY)
    monkeypatch.setitem(configs_lib.CONFIGS, small.name, small)
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    written = harness.load_cell("xdeepfm_cin200.train").config
    config = {**written, "registry": small.name,
              "model": {**written["model"], "rank": K, "num_fields": F,
                        "bucket": BUCKET},
              "head": {**written["head"], "cin_layers": list(CIN),
                       "mlp_dims": list(MLP)},
              "training": {**written["training"], "batch_per_chip": BATCH}}
    cell = harness.Cell(name="tiny", chips=1, config=config,
                        mix={**MIX, "check_chunk": BATCH // 2},
                        end_to_end=[], per_layer=[])
    ctx = harness.Context(cell=cell, seed=SEED, seconds=0.0,
                          t_start=time.perf_counter(), trace_dir=None)
    return ctx, small


def through_cli(ctx, cfg, precision="float32"):
    uniq, counts, inv, vals, labels = train_deep.one_batch(ctx, 1)
    runs = train_xdeep.two_runs(ctx, cfg, 1, uniq)
    got = train_xdeep.program_cin(cfg, runs[1], inv, vals)
    want, start = train_xdeep.reference_run(ctx, uniq, inv, vals, labels,
                                            precision=precision)
    forward = train_xdeep.cin_errors(
        got, train_xdeep.reference_cin(runs[1], inv, vals, K, BATCH // 2,
                                       precision=precision), CIN)
    return train_xdeep.compare(
        *runs, want, start, counts, forward, steps=STEPS, early_steps=EARLY,
        learning_rate=REGISTERED.learning_rate, tol=ctx.cell.mix,
        cin_layers=CIN)


def test_cli_train_matches_the_reference_from_its_own_init(tiny):
    ctx, cfg = tiny
    held, memory = train_xdeep.hold_program(ctx)
    assert held.name == cfg.name and memory["argument"] > 0
    t = time.perf_counter()
    verdict = through_cli(ctx, cfg)
    assert verdict["ok"], verdict
    # Well inside the limits, not just under them; the loss, a float32
    # mean near 0.69, to a few of its ulps (8.6e-8 of it each).
    early = verdict["early"]
    assert early["loss_rel_err"] < 4 * 2.0 ** -24 / 0.69
    assert all(b["mean_rates"] < 0.1 * MIX["head_mean_rates"]
               for b in early["dense"].values())
    assert max(verdict["forward"]["cin_rel_err"].values()) < (
        0.1 * MIX["cin_rtol"])
    assert max(verdict["step"]["cin_rel_err"].values()) < (
        0.1 * MIX["cin_pooled_rtol"])
    # The loop's hot intervals on this path, one train/step a step.
    records = [iv for iv in obs.intervals() if iv.t0 >= t]
    steps = [iv.attrs["step"] for iv in records if iv.name == "train/step"]
    assert steps == list(range(STEPS)) + list(range(EARLY))
    # What the loop said of itself.
    assert obs.gauge("train/mxu_flops_per_step").value == (
        cin_flops.step_matmul_flops(BATCH, F, K, CIN)
        + 6 * BATCH * (F * K * 8 + 8 * 8 + 8 + sum(CIN)))
    assert obs.gauge("train/cin_outer_elems_per_step").value == (
        cin_flops.outer_elems(BATCH, F, K, CIN))
    assert obs.gauge("train/cin_product_flops_per_step").value == (
        6 * BATCH * (K * (F * F * 6 + 6 * F * 5) + 5 * F * 4))


def test_the_lowered_step_states_its_scopes_and_its_precision():
    spec, config = spec_and_config()
    text = sparse.lower_field_sparse_step(spec, config, BATCH).as_text(
        debug_info=True)
    for scope in ("cin/outer", "cin/compress", "cin/pool", "deep/forward",
                  "deep/backward", "deep/adam"):
        assert scope in text, scope
    stated = re.findall(r"precision = \[(\w+), (\w+)\]", text)
    assert len(stated) == train_xdeep.products(
        {"cin_layers": CIN, "mlp_dims": MLP}) == 21
    assert set(stated) == {("HIGHEST", "HIGHEST")}
    # The trace reader's parts, from the op names the step carries.
    names = set(re.findall(r'"(jit\(_step\)/[^"]*cin/[^"]*)"', text))
    parts = {cin_trace.part_of(n) for n in names}
    assert parts == {f"{p}.{d}" for p in cin_trace.PARTS
                     for d in ("forward", "backward")}
    assert cin_trace.part_of("jit(_step)/jvp(deep/forward)/dot_general") is None


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_the_check_catches(tiny, fault):
    ctx, cfg = tiny
    with faults.FAULTS[fault](cfg.name):
        verdict = through_cli(ctx, configs_lib.CONFIGS[cfg.name])
    assert not verdict["ok"], (fault, verdict)


def test_a_cin_the_step_alone_computes_is_read_from_the_step(tiny):
    """A CIN that only the step's head computes wrongly (a later change to
    the step, a fused kernel say) leaves ``spec.cin`` on its own, the
    second witness, as it was: what the step reports decides it."""
    ctx, cfg = tiny
    with faults.step_cin_one_bf16_pass(cfg.name):
        verdict = through_cli(ctx, configs_lib.CONFIGS[cfg.name])
    assert verdict["forward"]["ok"] and not verdict["step"]["ok"], verdict
    assert not verdict["ok"]


def test_a_step_that_reports_no_cin_fails_the_check(tiny, monkeypatch):
    ctx, cfg = tiny
    real = FieldXDeepFMSpec.head_scores_and_stats

    def silent(self, dense, h):
        return real(self, dense, h)[0], {}

    monkeypatch.setattr(FieldXDeepFMSpec, "head_scores_and_stats", silent)
    verdict = through_cli(ctx, cfg)
    assert verdict["step"] == {"cin_rel_err": None, "ok": False}
    assert not verdict["ok"]


def test_the_reference_one_precision_lower_fails(tiny):
    ctx, cfg = tiny
    verdict = through_cli(ctx, cfg, precision="bfloat16")
    assert not verdict["ok"] and not verdict["forward"]["ok"]


def test_a_program_that_is_not_the_files_is_refused_before_any_work(
        tiny, monkeypatch):
    ctx, cfg = tiny
    with monkeypatch.context() as m:
        m.setattr(FieldXDeepFMSpec, "_precision", None)
        with pytest.raises(SystemExit, match="precision HIGHEST"):
            train_xdeep.hold_program(ctx)
    for change, message in ((dict(cin_layers=(6, 5)), "cin_layers"),
                            (dict(mlp_dims=(8,)), "mlp_dims")):
        with monkeypatch.context() as m:
            m.setitem(configs_lib.CONFIGS, cfg.name,
                      dataclasses.replace(cfg, **change))
            with pytest.raises(SystemExit, match=message):
                train_xdeep.hold_program(ctx)
    with monkeypatch.context() as m:
        m.delitem(configs_lib.CONFIGS, cfg.name)
        with pytest.raises(SystemExit, match="no such configuration"):
            train_xdeep.hold_program(ctx)


def test_the_limits_and_the_configuration_are_the_files():
    assert MIX["driver"] == "train_xdeep" and MIX["like"] == "train_fed"
    assert 0 < MIX["cin_rtol"] < 1e-3 and 0 < MIX["cin_pooled_rtol"] < 1e-3
    assert MIX["loss_rtol"] < MIX["loss_rtol_late"] <= 1e-4
    assert EARLY == 2 < STEPS
    assert MIX["head_mean_rates"] < MIX["head_mean_rates_late"]
    assert MIX["rows_rtol"] < MIX["rows_rtol_late"]
    cell = harness.load_cell("xdeepfm_cin200.train")
    assert cell.chips == 1 and cell.mix["rows"] == 128 * 4096
    written = cell.config
    assert written["reduced"] == [] and written["reference"] == "xdeepfm"
    assert written["model"] == {"rank": 10, "num_fields": 39,
                                "bucket": 1 << 18, "param_dtype": "float32",
                                "compute_dtype": "float32"}
    assert written["head"]["cin_layers"] == [200, 200, 200]
    assert written["head"]["mlp_dims"] == [400, 400]
    assert written["training"] == {
        **written["training"], "optimizer": "adam", "learning_rate": 0.001,
        "reg_factors": 1e-4, "batch_per_chip": 4096}
    for key in ("published", "assumed", "deployment", "rehearsal"):
        assert written[key], key
    assert {m["name"] for m in cell.per_layer} >= {"cin_ms",
                                                   "cin_mxu_roofline"}


# -------------------------------------------- eval, predict, the scorer


def test_cli_eval_predict_and_the_engine_return_spec_predict(tiny, tmp_path,
                                                             capsys):
    _, cfg = tiny
    model_dir = str(tmp_path / "model")
    assert cli.main(["train", "--config", cfg.name, "--synthetic", "256",
                     "--steps", "4", "--log-every", "4", "--obs-dir", "none",
                     "--test-fraction", "0.25", "--model-out",
                     model_dir]) == 0
    out = capsys.readouterr().out
    trained = json.loads([l for l in out.splitlines() if '"eval"' in l][-1])
    assert np.isfinite(trained["eval"]["logloss"])
    spec, params = models.load_model(model_dir)
    assert spec == cfg.spec() and isinstance(spec.cin_layers, tuple)

    from fm_spark_tpu.train import evaluate_params

    ids, vals, labels = data_lib.synthetic_ctr(200, spec.num_features, F,
                                               seed=1)
    ids = ids % BUCKET
    assert cli.main(["eval", "--model", model_dir, "--synthetic", "200",
                     "--batch-size", "64"]) == 0
    said = json.loads(capsys.readouterr().out.splitlines()[-1])
    want = evaluate_params(spec, params, data_lib.iterate_once(
        ids, vals, labels, 64))
    assert said["logloss"] == pytest.approx(want["logloss"], rel=1e-6)
    direct = np.asarray(spec.predict(params, jnp.asarray(ids),
                                     jnp.asarray(vals)))
    assert direct.std() > 0
    pred_file = tmp_path / "preds.txt"
    assert cli.main(["predict", "--model", model_dir, "--synthetic", "200",
                     "--batch-size", "64", "--out", str(pred_file)]) == 0
    np.testing.assert_allclose(np.loadtxt(pred_file), direct, rtol=1e-5)

    from fm_spark_tpu.serve import PredictEngine

    engine = PredictEngine(spec, params, buckets=(16, 64))
    try:
        engine.warmup()
        np.testing.assert_allclose(engine.score(ids[:50], vals[:50]),
                                   direct[:50], rtol=1e-6)
    finally:
        engine.close()


def test_a_chain_follower_restores_the_dense_leaves_adam_state(tiny):
    _, cfg = tiny
    example = cli._serve_opt_example(cfg.spec(), cfg)
    mu = example[0].mu
    assert set(mu) == {"w0", "cin", "mlp"}
    assert mu["cin"]["layers"][1].shape == (5, 6, 5)


# ----------------------------------------------------- what is refused


def argv(cfg, *more, steps=2):
    return ["train", "--config", cfg.name, "--synthetic", str(2 * BATCH),
            "--steps", str(steps), "--obs-dir", "none", "--test-fraction",
            "0", *more]


def test_save_kill_resume_continues_bit_identically(tiny, tmp_path, capsys):
    """The dense leaves' Adam state rides the checkpoint beside the tables:
    a resume continues as if never stopped."""
    _, cfg = tiny

    def losses(steps, *more):
        assert cli.main(argv(cfg, "--log-every", "1", *more,
                             steps=steps)) == 0
        return [json.loads(line)["loss"]
                for line in capsys.readouterr().out.splitlines()
                if line.startswith('{"step"')]

    golden = losses(6)
    chain = ["--checkpoint-dir", str(tmp_path / "chain"),
             "--checkpoint-every", "3"]
    assert losses(3, *chain) + losses(6, *chain) == golden
    assert len(golden) == 6 and len(set(golden)) == 6


def test_the_capability_row_refuses_a_mesh(monkeypatch):
    small = dataclasses.replace(REGISTERED, name="xdeepfm_tiny", **TINY)
    monkeypatch.setitem(configs_lib.CONFIGS, small.name, small)
    assert jax.device_count() == 8
    with pytest.raises(SystemExit,
                       match="FieldXDeepFMSpec has no field-sharded"):
        cli.main(argv(small))
    from fm_spark_tpu.parallel import field_step, make_field_mesh

    with pytest.raises(ValueError, match="no field-sharded step"):
        field_step.lower_field_sharded_step(
            small.spec(), small.train_config(), make_field_mesh(8), BATCH)


@pytest.mark.parametrize("more,message", [
    (["--steps-per-call", "2"], "not supported for FieldXDeepFMSpec"),
    (["--optimizer", "sgd"], "takes optimizer='adam'"),
    (["--optimizer", "adagrad"], "takes optimizer='adam'"),
    (["--embed-tier", "require"], "embed"),
])
def test_the_capability_row_and_the_body_refuse_by_name(tiny, more, message):
    _, cfg = tiny
    with pytest.raises((SystemExit, ValueError), match=message):
        cli.main(argv(cfg, *more))
    row = cli._FIELD_CAPS["FieldXDeepFMSpec"]
    assert row.carries_opt and row.sharded_step is None
    assert row.table_rules == ()
    assert not any(getattr(row, f.name) for f in dataclasses.fields(row)
                   if f.type == "bool" and f.name != "carries_opt")


def test_the_body_refuses_the_fm_terms_lever():
    spec, config = spec_and_config(gfull_fused=True)
    with pytest.raises(ValueError, match="gfull_fused"):
        sparse.make_field_deepfm_sparse_body(spec, config)


def test_the_spec_refuses_an_empty_head():
    base = dict(num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET)
    assert FieldXDeepFMSpec(**base).cin_layers == (200, 200, 200)
    for change in (dict(cin_layers=()), dict(cin_layers=(4, 0)),
                   dict(mlp_dims=()), dict(num_features=F * BUCKET + 1)):
        with pytest.raises(ValueError):
            FieldXDeepFMSpec(**{**base, **change})
