"""Differential tests for the fused Pallas embedding path (ISSUE 8).

Contract under test (ops/pallas_fused.py + the ``fused_embed`` lever in
sparse.py): the fused kernels are the REFERENCE's numerics, not merely
close — fp32 step outputs agree with the XLA path they subsume (the
gfull_fused + segtotal_pallas composition for the FM compact backward;
the sel_blocked body for the FFM kernels) to a few ULP (see
``_assert_ulp`` for why not to the bit), bf16 is tolerance-bounded,
'auto' falls back to XLA with a queryable reason, and 'require' raises
the structured ops.PallasUnavailable everywhere a kernel cannot serve.
Interpret mode on CPU; chip_smoke.py compiles the kernels on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fm_spark_tpu import sparse
from fm_spark_tpu.models.field_ffm import FieldFFMSpec
from fm_spark_tpu.models.field_fm import FieldFMSpec
from fm_spark_tpu.ops import PallasUnavailable, pallas_fused, pallas_segsum
from fm_spark_tpu.ops.scatter import compact_aux
from fm_spark_tpu.train import TrainConfig

B, F, K, BUCKET, CAP = 256, 5, 8, 96, 96


def _fm_spec(**kw):
    kw.setdefault("num_features", F * BUCKET)
    return FieldFMSpec(num_fields=F, bucket=BUCKET, rank=K,
                       fused_linear=True, **kw)


def _batch(seed=1, bucket=BUCKET):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, bucket, (B, F)), jnp.int32)
    vals = jnp.asarray(rng.uniform(0.5, 1.5, (B, F)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, B), jnp.float32)
    weights = jnp.ones((B,), jnp.float32)
    return ids, vals, labels, weights


def _base_cfg(**kw):
    kw.setdefault("sparse_update", "dedup")
    kw.setdefault("host_dedup", True)
    kw.setdefault("compact_cap", CAP)
    return dict(learning_rate=0.05, lr_schedule="constant",
                optimizer="sgd", reg_factors=1e-4, reg_linear=1e-5,
                reg_bias=1e-6, **kw)


def _run(spec, cfg, body_fn, aux, batch, step_idx=3):
    params = spec.init(jax.random.key(0))
    step = body_fn(spec, cfg)
    return step(jax.tree_util.tree_map(jnp.copy, params), step_idx,
                *batch, aux)


def _assert_ulp(got, want, scale=None, max_ulp=8, msg=""):
    """``|got − want|`` in fp32 ULPs, the bar tests/test_gfull.py sets.

    Kernel and reference are the same arithmetic, but jax 0.9.0's
    Pallas interpreter evaluates the kernel body op by op while XLA
    contracts the reference (fma fusion, its own reduction tiling), so
    the two land within a ULP or two of each other, not on the same
    bits (seed failures: max abs diff 1.2e-10 … 1.5e-5, each exactly
    one ULP of the operand it rounds at). ``scale`` is the magnitude
    the ULP is taken at: each element's own by default — with the 1e-9
    absolute floor for near-zero params, where cancellation in the
    update sum turns sub-nano diffs into large ULP counts — and the
    largest summand for a sum, which rounds at THAT magnitude however
    far its terms cancel."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype == np.float32, msg
    d = np.abs(got.astype(np.float64) - want.astype(np.float64))
    if scale is None:
        scale = np.maximum(np.abs(got), np.abs(want))
        d = np.where(d < 1e-9, 0.0, d)
    ulp = d / np.spacing(np.float32(1) * np.asarray(scale, np.float32))
    assert ulp.max() <= max_ulp, f"{msg}: max {ulp.max()} ULP"


def _assert_trees(p1, p2, exact=True, atol=0.0):
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        a, b = np.asarray(a), np.asarray(b)
        if exact == "ulp":
            _assert_ulp(a, b)
        elif exact:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(
                a.astype(np.float64), b.astype(np.float64), atol=atol)


# --------------------------------------------------------------------------
# The fused FM compact backward: bit-exact vs the subsumed composition.
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["dedup", "dedup_sr"])
def test_fm_step_fused_bwd_ulp_exact_fp32(mode):
    spec = _fm_spec()
    batch = _batch()
    aux = jax.device_put(compact_aux(np.asarray(batch[0]), CAP))
    ref = TrainConfig(**_base_cfg(sparse_update=mode), fused_embed="off",
                      gfull_fused=True, segtotal_pallas=True)
    fused = TrainConfig(**_base_cfg(sparse_update=mode),
                        fused_embed="require")
    p1, l1 = _run(spec, ref, sparse.make_field_sparse_sgd_body, aux, batch)
    p2, l2 = _run(spec, fused, sparse.make_field_sparse_sgd_body, aux,
                  batch)
    assert float(l1) == float(l2)
    _assert_trees(p1, p2, exact="ulp")


def test_fm_step_fused_bwd_matches_plain_reference_tolerance():
    # Against the DEFAULT (blocked-prefix, concat-g_full) reference the
    # kernel is reassociation-equal, not bitwise: pin a tight bound.
    spec = _fm_spec()
    batch = _batch(seed=7)
    aux = jax.device_put(compact_aux(np.asarray(batch[0]), CAP))
    ref = TrainConfig(**_base_cfg(), fused_embed="off")
    fused = TrainConfig(**_base_cfg(), fused_embed="require")
    p1, l1 = _run(spec, ref, sparse.make_field_sparse_sgd_body, aux, batch)
    p2, l2 = _run(spec, fused, sparse.make_field_sparse_sgd_body, aux,
                  batch)
    assert abs(float(l1) - float(l2)) < 1e-6
    _assert_trees(p1, p2, exact=False, atol=1e-5)


def test_fm_step_fused_bwd_device_aux_overflow_drop_ulp_exact():
    # compact_device with cap below the unique count: the kernel's
    # trash-row clamp must reproduce the masked-drop overflow semantics
    # exactly (overflow lanes expand to zero rows, updates dropped).
    spec = _fm_spec()
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(0, 2000, (B, F)), jnp.int32)
    batch = (ids, *_batch()[1:])
    kw = dict(host_dedup=False, compact_device=True,
              compact_overflow="drop", sparse_update="dedup_sr")
    spec2 = FieldFMSpec(num_features=F * 2000, num_fields=F, bucket=2000,
                        rank=K, fused_linear=True)
    ref = TrainConfig(**_base_cfg(**kw), fused_embed="off",
                      gfull_fused=True, segtotal_pallas=True)
    fused = TrainConfig(**_base_cfg(**kw), fused_embed="require")
    p1, l1 = _run(spec2, ref, sparse.make_field_sparse_sgd_body, None,
                  batch)
    p2, l2 = _run(spec2, fused, sparse.make_field_sparse_sgd_body, None,
                  batch)
    assert float(l1) == float(l2)
    _assert_trees(p1, p2, exact="ulp")


def test_fm_step_fused_bwd_bf16_tolerance_bounded():
    spec = _fm_spec(param_dtype="bfloat16", compute_dtype="bfloat16")
    batch = _batch(seed=3)
    aux = jax.device_put(compact_aux(np.asarray(batch[0]), CAP))
    ref = TrainConfig(**_base_cfg(sparse_update="dedup_sr"),
                      fused_embed="off", gfull_fused=True,
                      segtotal_pallas=True)
    fused = TrainConfig(**_base_cfg(sparse_update="dedup_sr"),
                        fused_embed="require")
    p1, l1 = _run(spec, ref, sparse.make_field_sparse_sgd_body, aux, batch)
    p2, l2 = _run(spec, fused, sparse.make_field_sparse_sgd_body, aux,
                  batch)
    # bf16 has ~3 decimal digits; one step's updates are O(lr·g) small.
    assert abs(float(l1) - float(l2)) < 1e-3
    _assert_trees(p1, p2, exact=False, atol=1e-2)


def test_fm_bwd_kernel_bit_exact_vs_gfull_plus_segtotal():
    # The kernel alone vs the two-stage reference it fuses, composed
    # exactly as the step composes them (sorted streams in, totals out).
    rng = np.random.default_rng(5)
    b, w, cap = 1024, K + 1, 64
    urows = jnp.asarray(rng.normal(size=(cap, w)), jnp.float32)
    seg = jnp.asarray(np.sort(rng.integers(0, cap, b)), jnp.int32)
    s1 = jnp.asarray(rng.normal(size=(b, w)), jnp.float32)
    ds = jnp.asarray(rng.normal(size=b), jnp.float32)
    x = jnp.asarray(rng.uniform(0.5, 1.5, b), jnp.float32)
    tch = jnp.asarray(rng.integers(0, 2, b), jnp.float32)
    rv = jnp.asarray([1e-4] * K + [1e-5], jnp.float32)
    lr = jnp.float32(0.05)

    got = pallas_fused.fm_bwd_segment_totals(
        urows, s1, ds, x, tch, seg, -lr, rv, k=K, cap=cap,
        interpret=True)

    # Reference: the gfull_fused expression on expanded rows, then the
    # Pallas segment totals (same tile/window math).
    rows = urows[seg]
    colmask = jnp.arange(w) < K
    xv = rows * x[:, None]
    base = ds[:, None] * (s1 - jnp.where(colmask, xv, 0.0))
    g = base * x[:, None] + rv * rows * tch[:, None]
    want = pallas_segsum.segment_totals(
        (-lr * g).astype(jnp.float32), seg, cap, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fm_bwd_kernel_no_reg_matches_reference():
    # rv=None skips the reg term entirely (the reference's conditional
    # add) — a zero rv vector would still change the HLO.
    rng = np.random.default_rng(6)
    b, w, cap = 512, K + 1, 32
    urows = jnp.asarray(rng.normal(size=(cap, w)), jnp.float32)
    seg = jnp.asarray(np.sort(rng.integers(0, cap, b)), jnp.int32)
    s1 = jnp.asarray(rng.normal(size=(b, w)), jnp.float32)
    ds = jnp.asarray(rng.normal(size=b), jnp.float32)
    x = jnp.asarray(rng.uniform(0.5, 1.5, b), jnp.float32)
    got = pallas_fused.fm_bwd_segment_totals(
        urows, s1, ds, x, jnp.ones_like(x), seg, jnp.float32(-0.1),
        None, k=K, cap=cap, interpret=True)
    rows = urows[seg]
    colmask = jnp.arange(w) < K
    g = ds[:, None] * (s1 - jnp.where(colmask, rows * x[:, None], 0.0)
                       ) * x[:, None]
    d = (-0.1 * g).astype(jnp.float32)
    want = pallas_segsum.segment_totals(d, seg, cap, interpret=True)
    # A segment total rounds at its largest summand's magnitude.
    _assert_ulp(got, want, scale=float(jnp.max(jnp.abs(d))))


# --------------------------------------------------------------------------
# The fused gather→interaction forward.
# --------------------------------------------------------------------------


def test_fm_fused_forward_matches_xla_reference():
    rng = np.random.default_rng(8)
    tables = [jnp.asarray(rng.normal(size=(60, K + 1)), jnp.float32)
              for _ in range(F)]
    ids = jnp.asarray(rng.integers(0, 60, (B, F)), jnp.int32)
    vals = jnp.asarray(rng.uniform(0.5, 1.5, (B, F)), jnp.float32)
    scores, acc = pallas_fused.fm_fused_scores(
        tables, ids, vals, w0=jnp.float32(0.3), interpret=True)
    rows = [tables[f][ids[:, f]] for f in range(F)]
    xvs = [r[:, :K] * vals[:, f:f + 1] for f, r in enumerate(rows)]
    s = sum(xvs)
    ssq = sum(jnp.sum(x * x, axis=1) for x in xvs)
    ref = (0.5 * (jnp.sum(s * s, axis=1) - ssq)
           + sum(r[:, K] * vals[:, f] for f, r in enumerate(rows)) + 0.3)
    # The score's largest summand is ½·Σs² (hundreds at unit-scale
    # operands), so that is where its last bit sits.
    _assert_ulp(scores, ref,
                scale=float(jnp.max(0.5 * jnp.sum(s * s, axis=1))))
    # acc carries the forward residuals: cols [:k] = s, col k = linear.
    np.testing.assert_allclose(np.asarray(acc[:, :K]), np.asarray(s),
                               atol=1e-6)


def test_fm_fused_forward_rejects_overwide_on_tpu_contract():
    # Off-TPU the support probe is unrestricted; the width rule is the
    # row-DMA constraint and must stay queryable without raising.
    assert pallas_fused.fm_fwd_supported(1024, 65) is None


# --------------------------------------------------------------------------
# The sel-blocked FFM kernels.
# --------------------------------------------------------------------------


def _ffm_spec(**kw):
    kw.setdefault("num_features", F * BUCKET)
    return FieldFFMSpec(num_fields=F, bucket=BUCKET, rank=6, **kw)


def _ffm_cfg(**kw):
    kw.setdefault("fused_embed", "off")
    return TrainConfig(learning_rate=0.05, lr_schedule="constant",
                       optimizer="sgd", sparse_update="scatter_add",
                       sel_blocked=True, reg_factors=1e-4,
                       reg_linear=1e-5, **kw)


def test_ffm_step_pallas_bit_exact_fp32():
    spec = _ffm_spec()
    batch = _batch(seed=9)
    p1, l1 = _run(spec, _ffm_cfg(),
                  sparse.make_field_ffm_sparse_sgd_body, None, batch)
    p2, l2 = _run(spec, _ffm_cfg(fused_embed="require"),
                  sparse.make_field_ffm_sparse_sgd_body, None, batch)
    assert float(l1) == float(l2)
    _assert_trees(p1, p2, exact=True)


def test_ffm_step_pallas_bf16_compute_tolerance():
    spec = _ffm_spec(compute_dtype="bfloat16")
    batch = _batch(seed=10)
    p1, l1 = _run(spec, _ffm_cfg(),
                  sparse.make_field_ffm_sparse_sgd_body, None, batch)
    p2, l2 = _run(spec, _ffm_cfg(fused_embed="require"),
                  sparse.make_field_ffm_sparse_sgd_body, None, batch)
    assert abs(float(l1) - float(l2)) < 1e-3
    _assert_trees(p1, p2, exact=False, atol=1e-2)


def test_ffm_kernels_match_blocked_loop_directly():
    rng = np.random.default_rng(12)
    b, f, kk = 192, 4, 6
    rstk = jnp.asarray(rng.normal(size=(b, f, f * kk)), jnp.float32)
    vals = jnp.asarray(rng.uniform(0.5, 1.5, (b, f)), jnp.float32)
    ds = jnp.asarray(rng.normal(size=b), jnp.float32)
    acc = pallas_fused.ffm_sel_scores(rstk, vals, interpret=True)
    dvs = pallas_fused.ffm_sel_bwd(rstk, vals, ds, interpret=True)
    Rv = np.asarray(rstk).reshape(b, f, f, kk)
    x = np.asarray(vals)
    want_acc = np.zeros(b, np.float32)
    for i in range(f):
        sel_i = Rv[:, i] * x[:, i, None, None]
        selT_i = Rv[:, :, i, :] * x[:, :, None]
        prod = np.sum(sel_i * selT_i, axis=-1)
        want_acc = want_acc + np.sum(prod, axis=1) - prod[:, i]
        dsel_i = np.asarray(ds)[:, None, None] * selT_i
        dsel_i[:, i, :] = 0
        want_dv = (dsel_i * x[:, i, None, None]).reshape(b, f * kk)
        np.testing.assert_allclose(np.asarray(dvs[:, i, :]), want_dv,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(acc), want_acc, atol=1e-5)


# --------------------------------------------------------------------------
# The lever: plan resolution, auto fallback, require escalation.
# --------------------------------------------------------------------------


def test_plan_resolves_families_and_reasons():
    fm, ffm = _fm_spec(), _ffm_spec()
    base = _base_cfg()
    assert sparse.fused_embed_plan(
        fm, TrainConfig(**base, fused_embed="auto")) == \
        ("fm_compact_bwd", None)
    assert sparse.fused_embed_plan(
        ffm, _ffm_cfg(fused_embed="auto")) == ("ffm_sel", None)
    fam, reason = sparse.fused_embed_plan(
        fm, TrainConfig(**base, fused_embed="off"))
    assert fam is None and "off" in reason
    fam, reason = sparse.fused_embed_plan(
        fm, TrainConfig(**{**base, "compact_cap": 0,
                           "host_dedup": False}, fused_embed="auto"))
    assert fam is None and "compact" in reason
    ffm_cfg = _ffm_cfg(fused_embed="auto")
    import dataclasses

    no_selblk = dataclasses.replace(ffm_cfg, sel_blocked=False)
    fam, reason = sparse.fused_embed_plan(ffm, no_selblk)
    assert fam is None and "sel_blocked" in reason


def test_auto_falls_back_to_xla_bit_identically():
    # 'auto' with no serving family must compile EXACTLY the XLA path.
    spec = _fm_spec()
    batch = _batch(seed=13)
    off = TrainConfig(**_base_cfg(compact_cap=0, host_dedup=False,
                                  sparse_update="scatter_add"),
                      fused_embed="off")
    auto = TrainConfig(**_base_cfg(compact_cap=0, host_dedup=False,
                                   sparse_update="scatter_add"),
                       fused_embed="auto")
    p1, l1 = _run(spec, off, sparse.make_field_sparse_sgd_body, None,
                  batch)
    p2, l2 = _run(spec, auto, sparse.make_field_sparse_sgd_body, None,
                  batch)
    assert float(l1) == float(l2)
    _assert_trees(p1, p2, exact=True)


def test_require_raises_structured_error_when_unserved():
    spec = _fm_spec()
    cfg = TrainConfig(**_base_cfg(compact_cap=0, host_dedup=False,
                                  sparse_update="scatter_add"),
                      fused_embed="require")
    with pytest.raises(PallasUnavailable, match="compact"):
        sparse.make_field_sparse_sgd_body(spec, cfg)


def test_require_rejected_by_non_served_factories():
    from fm_spark_tpu.train import make_train_step

    cfg = TrainConfig(learning_rate=0.05, lr_schedule="constant",
                      optimizer="adam", fused_embed="require")
    spec = _fm_spec()
    with pytest.raises(ValueError, match="fused_embed"):
        make_train_step(spec, cfg)


def test_vmem_budget_is_a_fallback_reason_not_a_crash():
    # A cap far past the residency budget: 'auto' reports the reason,
    # 'require' escalates to the structured error.
    big = _base_cfg(compact_cap=1 << 20)
    spec = FieldFMSpec(num_features=F * (1 << 21), num_fields=F,
                       bucket=1 << 21, rank=K, fused_linear=True)
    fam, reason = sparse.fused_embed_plan(
        spec, TrainConfig(**big, fused_embed="auto"))
    assert fam is None and "VMEM" in reason
    with pytest.raises(PallasUnavailable, match="VMEM"):
        sparse.make_field_sparse_sgd_body(
            spec, TrainConfig(**big, fused_embed="require"))


def test_unknown_fused_embed_value_rejected():
    with pytest.raises(ValueError, match="unknown fused_embed"):
        sparse.fused_embed_plan(
            _fm_spec(), TrainConfig(**_base_cfg(), fused_embed="maybe"))


def test_kernel_errors_are_catchable_as_valueerror():
    # Pre-existing callers pin ValueError; the structured subclass must
    # stay catchable that way (the PallasUnavailable contract).
    assert issubclass(PallasUnavailable, ValueError)


# --------------------------------------------------------------------------
# AOT: the PR-1 lower()/compile() machinery serves the fused families.
# --------------------------------------------------------------------------


def test_aot_lower_compile_fused_fm_step():
    spec = _fm_spec()
    cfg = TrainConfig(**_base_cfg(sparse_update="dedup_sr"),
                      fused_embed="require")
    lowered = sparse.lower_field_sparse_step(spec, cfg, B)
    compiled = lowered.compile()
    assert compiled is not None


def test_aot_lower_compile_fused_ffm_step():
    spec = _ffm_spec()
    lowered = sparse.lower_field_sparse_step(
        spec, _ffm_cfg(fused_embed="require"), B)
    assert lowered.compile() is not None


def test_multistep_roll_carries_fused_step():
    # The fori multistep roll must compose with the fused body (the
    # production loop's dispatch-amortized form).
    spec = _fm_spec()
    cfg = TrainConfig(**_base_cfg(), fused_embed="require")
    ids, vals, labels, weights = _batch(seed=14)
    aux = jax.device_put(compact_aux(np.asarray(ids), CAP))
    n = 2
    stack = lambda a: jnp.stack([a] * n)  # noqa: E731
    mstep = sparse.make_field_sparse_multistep(spec, cfg, n)
    params = spec.init(jax.random.key(0))
    aux_s = jax.tree_util.tree_map(stack, aux)
    p, loss = mstep(params, jnp.int32(0), jnp.int32(n), stack(ids),
                    stack(vals), stack(labels), stack(weights), aux_s)
    assert np.isfinite(float(loss))
