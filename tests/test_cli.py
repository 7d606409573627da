"""configs registry + CLI end-to-end (train → save → eval → predict).

The CLI is the rebuild's example-driver parity surface (SURVEY.md §2
row 8); these tests run it in-process on synthetic data, covering every
registered config's spec construction and the train/eval/predict cycle.
"""

import dataclasses
import json

import numpy as np
import pytest

from fm_spark_tpu import configs as configs_lib
from fm_spark_tpu import cli


def test_registry_has_all_five_baseline_configs():
    names = set(configs_lib.CONFIGS)
    assert names == {
        "movielens_fm_r8",
        "criteo_kaggle_fm_r32",
        "criteo1tb_fm_r64",
        "avazu_ffm_r16",
        "criteo1tb_deepfm",
        # Config 4 under its paper's update rule (PR 34): the same model,
        # per-coordinate AdaGrad on every table.
        "avazu_ffm_r16_adagrad",
        # DLRM at the MLPerf Training recommendation benchmark's sizes
        # (PR 36): dense columns as values, 128-wide rows.
        "criteo1tb_dlrm_mlperf",
        # DCN-v2 over multi-hot columns at MLPerf's sizes since v3.0
        # (PR 40): bags of ids pooled, AdaGrad on every parameter.
        "criteo1tb_dcnv2_multihot",
        # xDeepFM at its paper's Criteo settings: a Compressed
        # Interaction Network as the head of DeepFM's fused body.
        "criteo_xdeepfm_cin200",
    }
    adagrad = configs_lib.CONFIGS["avazu_ffm_r16_adagrad"]
    sgd = configs_lib.CONFIGS["avazu_ffm_r16"]
    assert adagrad.spec() == sgd.spec()
    assert (adagrad.optimizer, adagrad.adagrad_init_accumulator) == (
        "adagrad", 2.0 ** -26)


@pytest.mark.parametrize("name", sorted(configs_lib.CONFIGS))
def test_every_config_builds_a_spec(name):
    cfg = configs_lib.get_config(name)
    spec = cfg.spec(1000 if cfg.bucket <= 0 else None)
    assert spec.rank == cfg.rank
    tc = cfg.train_config(num_steps=3)
    assert tc.num_steps == 3


def test_field_local_id_conversion_covers_every_field_model():
    # Regression (round-2 review): the id-conversion gate must key on the
    # single field_local_ids predicate — a field-partitioned model missed
    # by a hardcoded name tuple trains on silently-clamped ids.
    import argparse

    for model in ("field_fm", "field_ffm", "field_deepfm"):
        cfg = dataclasses.replace(
            configs_lib.CONFIGS["criteo1tb_fm_r64"],
            name=f"t_{model}", model=model, bucket=64, num_fields=5,
            rank=4,
        )
        assert cfg.field_local_ids
        args = argparse.Namespace(synthetic=300, data=None)
        ids, vals, labels, _ = cli.load_dataset(cfg, args)
        assert ids.max() < cfg.bucket, (
            f"{model}: ids not field-local — would clamp into table edge"
        )
        spec = cfg.spec()
        assert getattr(spec, "field_local_ids", False)
    # Non-field models keep global/dense ids.
    assert not configs_lib.CONFIGS["movielens_fm_r8"].field_local_ids
    assert not configs_lib.CONFIGS["criteo_kaggle_fm_r32"].field_local_ids


def test_flagship_config_uses_fused_scale_out_not_dense_row():
    # VERDICT r1 #7: the at-scale CTR path is the fused field-sharded
    # step; the dense-gradient 'row' strategy must not be presented as
    # config 3's scale-out.
    cfg = configs_lib.get_config("criteo1tb_fm_r64")
    assert cfg.strategy == "field_sparse"
    assert "row-shards" in cfg.description or "--row-shards" in cfg.description
    assert "fallback" in cfg.description


def test_get_config_overrides_and_unknown():
    cfg = configs_lib.get_config("movielens_fm_r8", batch_size=64)
    assert cfg.batch_size == 64
    assert configs_lib.get_config("movielens_fm_r8").batch_size != 64 or True
    with pytest.raises(KeyError):
        configs_lib.get_config("nope")


def test_cli_list_configs(capsys):
    assert cli.main(["list-configs"]) == 0
    out = capsys.readouterr().out
    for name in configs_lib.CONFIGS:
        assert name in out


def _train_eval_predict(tmp_path, config_name, capsys, steps="30"):
    model_dir = str(tmp_path / "model")
    rc = cli.main([
        "train", "--config", config_name, "--synthetic", "2000",
        "--steps", steps, "--batch-size", "256", "--model-out", model_dir,
        "--log-every", "10",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    eval_line = [l for l in out.splitlines() if '"eval"' in l][-1]
    metrics = json.loads(eval_line)["eval"]
    assert np.isfinite(metrics["logloss"])

    assert cli.main([
        "eval", "--model", model_dir, "--config", config_name,
        "--synthetic", "500",
    ]) == 0
    m = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert 0.0 <= m["auc"] <= 1.0

    pred_file = tmp_path / "preds.txt"
    assert cli.main([
        "predict", "--model", model_dir, "--config", config_name,
        "--synthetic", "500", "--out", str(pred_file),
    ]) == 0
    preds = np.loadtxt(pred_file)
    assert preds.shape[0] == 500
    assert np.all((preds >= 0) & (preds <= 1))
    return metrics


def test_cli_train_fm_single(tmp_path, capsys):
    _train_eval_predict(tmp_path, "movielens_fm_r8", capsys)


def test_cli_train_field_sparse(tmp_path, capsys):
    # criteo1tb_fm_r64 at full shape is too big for CPU tests; shrink it
    # via a temporary registry entry exercising the same code path.
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_fm_r64"],
        name="criteo_small", bucket=64, num_fields=5,
    )
    configs_lib.CONFIGS["criteo_small"] = small
    try:
        _train_eval_predict(tmp_path, "criteo_small", capsys)
    finally:
        del configs_lib.CONFIGS["criteo_small"]


@pytest.mark.slow
def test_cli_train_field_deepfm(tmp_path, capsys):
    # Config 5's CTR fast path (field-partitioned embedding + dense Adam
    # head), shrunk; exercises the sharded deepfm loop on the fake mesh
    # including model save/eval/predict roundtrip.
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_deepfm"],
        name="deepfm_small", bucket=64, num_fields=5, rank=4,
        mlp_dims=(16, 16, 16),
    )
    configs_lib.CONFIGS["deepfm_small"] = small
    try:
        _train_eval_predict(tmp_path, "deepfm_small", capsys)
    finally:
        del configs_lib.CONFIGS["deepfm_small"]


def test_cli_train_dp(tmp_path, capsys):
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo_kaggle_fm_r32"],
        name="kaggle_small", bucket=64, num_fields=5, rank=4,
    )
    configs_lib.CONFIGS["kaggle_small"] = small
    try:
        rc = cli.main([
            "train", "--config", "kaggle_small", "--synthetic", "2000",
            "--steps", "10", "--batch-size", "256", "--log-every", "5",
        ])
        assert rc == 0
    finally:
        del configs_lib.CONFIGS["kaggle_small"]


def test_cli_train_row_sharded(tmp_path, capsys):
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo_kaggle_fm_r32"],
        name="row_small", bucket=64, num_fields=4, rank=4, strategy="row",
    )
    configs_lib.CONFIGS["row_small"] = small
    try:
        rc = cli.main([
            "train", "--config", "row_small", "--synthetic", "1000",
            "--steps", "8", "--batch-size", "256", "--log-every", "4",
        ])
        assert rc == 0
    finally:
        del configs_lib.CONFIGS["row_small"]


def test_cli_train_ffm_and_deepfm(tmp_path, capsys):
    for base_name, small_kw in [
        ("avazu_ffm_r16", dict(bucket=32, num_fields=4, rank=4)),
        ("criteo1tb_deepfm",
         dict(bucket=32, num_fields=4, rank=4, mlp_dims=(16, 16, 16),
              strategy="single")),
    ]:
        small = dataclasses.replace(
            configs_lib.CONFIGS[base_name], name="tiny", **small_kw
        )
        configs_lib.CONFIGS["tiny"] = small
        try:
            rc = cli.main([
                "train", "--config", "tiny", "--synthetic", "1000",
                "--steps", "10", "--batch-size", "128", "--log-every", "5",
            ])
            assert rc == 0
        finally:
            del configs_lib.CONFIGS["tiny"]


def test_cli_train_movielens_file(tmp_path, capsys):
    # A real ratings file through the movielens loader path.
    rng = np.random.default_rng(0)
    path = tmp_path / "u.data"
    rows = [
        f"{rng.integers(1, 50)}\t{rng.integers(1, 80)}\t"
        f"{rng.integers(1, 6)}\t0"
        for _ in range(1000)
    ]
    path.write_text("\n".join(rows) + "\n")
    model_dir = str(tmp_path / "model")
    rc = cli.main([
        "train", "--config", "movielens_fm_r8", "--data", str(path),
        "--steps", "30", "--batch-size", "128", "--model-out", model_dir,
        "--log-every", "10",
    ])
    assert rc == 0


def test_cli_field_sparse_checkpoint_resume(tmp_path, capsys):
    # Kill-and-resume through the CLI fast path: run 1 stops at 10 steps,
    # run 2 (same flags, more steps) must resume from the checkpoint.
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_fm_r64"],
        name="ck_small", bucket=64, num_fields=5,
    )
    configs_lib.CONFIGS["ck_small"] = small
    ck = str(tmp_path / "ck")
    common = [
        "train", "--config", "ck_small", "--synthetic", "1000",
        "--batch-size", "128", "--log-every", "5",
        "--checkpoint-dir", ck, "--checkpoint-every", "5",
        "--test-fraction", "0",
    ]
    try:
        assert cli.main(common + ["--steps", "10"]) == 0
        capsys.readouterr()
        assert cli.main(common + ["--steps", "14"]) == 0
        out = capsys.readouterr().out
        steps = [json.loads(l)["step"] for l in out.splitlines()
                 if '"step"' in l]
        # Resumed run must start past step 10, not from 1.
        assert min(steps) > 10
    finally:
        del configs_lib.CONFIGS["ck_small"]


def test_libfm_rejects_ffm():
    import jax
    import pytest as _pytest

    from fm_spark_tpu import models as m
    from fm_spark_tpu.models.libfm_io import save_libfm

    spec = m.FFMSpec(num_features=8, rank=2, num_fields=2)
    params = spec.init(jax.random.key(0))
    with _pytest.raises(ValueError, match="plain FM"):
        save_libfm("/tmp/x.libfm", spec, params)


@pytest.mark.slow
def test_compat_positional_train_signatures():
    from fm_spark_tpu.compat import FFMWithSGD, FMWithLBFGS
    from fm_spark_tpu.data import synthetic_ctr

    data = synthetic_ctr(300, 60, 3, seed=0)
    m1 = FMWithLBFGS.train(data, "classification", 5)
    m2 = FFMWithSGD.train(data, "classification", 5, 0.1)
    assert m1.predict(data[0][:4], data[1][:4]).shape == (4,)
    assert m2.predict(data[0][:4], data[1][:4]).shape == (4,)


@pytest.mark.slow
def test_cli_preprocess_and_packed_streaming_train(tmp_path, capsys):
    from fm_spark_tpu.data import criteo

    raw = tmp_path / "day0.tsv"
    criteo.synthesize_tsv(str(raw), 600, seed=0)
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_fm_r64"],
        name="packed_small", bucket=64, num_fields=39,
    )
    configs_lib.CONFIGS["packed_small"] = small
    packed = str(tmp_path / "packed")
    try:
        assert cli.main([
            "preprocess", "--config", "packed_small",
            "--input", str(raw), "--out-dir", packed,
        ]) == 0
        capsys.readouterr()
        model_dir = str(tmp_path / "model")
        assert cli.main([
            "train", "--config", "packed_small", "--data", packed,
            "--steps", "10", "--batch-size", "64", "--log-every", "5",
            "--model-out", model_dir, "--test-fraction", "0.2",
        ]) == 0
        out = capsys.readouterr().out
        assert '"saved"' in out
        # --test-fraction on packed data must produce holdout metrics.
        eval_line = [l for l in out.splitlines() if '"eval"' in l][-1]
        assert np.isfinite(json.loads(eval_line)["eval"]["logloss"])
        # Shapes must match: saved model evals on spec-derived synthetic.
        assert cli.main([
            "eval", "--model", model_dir, "--synthetic", "200",
        ]) == 0
        capsys.readouterr()
        # And on the packed dir itself (streaming finite pass).
        assert cli.main([
            "eval", "--model", model_dir, "--config", "packed_small",
            "--data", packed,
        ]) == 0
        m = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert m["count"] == 600.0
        pred_file = tmp_path / "p.txt"
        assert cli.main([
            "predict", "--model", model_dir, "--config", "packed_small",
            "--data", packed, "--out", str(pred_file),
        ]) == 0
        assert np.loadtxt(pred_file).shape[0] == 600
    finally:
        del configs_lib.CONFIGS["packed_small"]


def test_cli_eval_data_requires_config(tmp_path, capsys):
    model_dir = str(tmp_path / "model")
    assert cli.main([
        "train", "--config", "movielens_fm_r8", "--synthetic", "500",
        "--steps", "5", "--batch-size", "128", "--model-out", model_dir,
        "--test-fraction", "0", "--log-every", "5",
    ]) == 0
    with pytest.raises(SystemExit, match="needs --config"):
        cli.main(["eval", "--model", model_dir, "--data", "/tmp/nope"])


def test_eval_every_field_sparse_strategy(capsys):
    # Periodic eval must work in the non-FMTrainer loops too.
    small = dataclasses.replace(
        configs_lib.CONFIGS["criteo1tb_fm_r64"],
        name="ee_small", bucket=64, num_fields=5,
    )
    configs_lib.CONFIGS["ee_small"] = small
    try:
        rc = cli.main([
            "train", "--config", "ee_small", "--synthetic", "2000",
            "--steps", "24", "--batch-size", "256", "--log-every", "8",
            "--eval-every", "8",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        eval_lines = [l for l in out.splitlines() if "eval_auc" in l]
        assert len(eval_lines) == 3  # steps 8, 16, 24
    finally:
        del configs_lib.CONFIGS["ee_small"]


def test_field_sparse_capability_guards():
    """The _FIELD_CAPS table drives every field_sparse guard: requests a
    family's steps can't serve must hard-fail (never silently fall back)
    — one test per capability column."""
    import pytest

    def run(name, base, extra, small_kw, batch=128):
        small = dataclasses.replace(
            configs_lib.CONFIGS[base], name=name,
            strategy="field_sparse", **small_kw
        )
        configs_lib.CONFIGS[name] = small
        bs = [] if batch is None else ["--batch-size", str(batch)]
        try:
            return cli.main([
                "train", "--config", name, "--synthetic", "512",
                "--steps", "4", *bs, *extra,
            ])
        finally:
            del configs_lib.CONFIGS[name]

    ffm_kw = dict(bucket=32, num_fields=4, rank=4)
    deepfm_kw = dict(bucket=32, num_fields=4, rank=4,
                     mlp_dims=(8, 8))
    # FFM 2-D row sharding is supported since round 4 (sel partials
    # completed by one psum over `row` — field_step._ffm_field_forward).
    assert run("g1", "avazu_ffm_r16", ["--row-shards", "2"], ffm_kw) == 0
    # steps-per-call rolls the SHARDED FM/FFM steps too since round 4
    # (fori inside the shard_map); on the 8-fake-device env this runs
    # the sharded FFM roll end-to-end.
    assert run("g2", "avazu_ffm_r16", ["--steps-per-call", "2"],
               ffm_kw) == 0
    # Sharded DeepFM takes the DEVICE-built compact aux (round 3) but
    # still rejects the host-built one.
    assert run("g3", "criteo1tb_deepfm",
               ["--compact-device", "--compact-cap", "64",
                "--sparse-update", "dedup"], deepfm_kw) == 0
    with pytest.raises(SystemExit, match="not supported"):
        run("g3b", "criteo1tb_deepfm",
            ["--host-dedup", "--compact-cap", "64",
             "--sparse-update", "dedup"], deepfm_kw)
    # Host-built compact aux + --row-shards (2-D) cannot compose.
    fm_kw = dict(bucket=64, num_fields=4, rank=4)
    with pytest.raises(SystemExit, match="compact-device"):
        run("g4", "criteo1tb_fm_r64",
            ["--host-dedup", "--compact-cap", "64", "--sparse-update",
             "dedup", "--row-shards", "2"], fm_kw)
    # Sharded device-compact FFM is SUPPORTED — must run clean.
    assert run("g5", "avazu_ffm_r16",
               ["--compact-device", "--compact-cap", "128",
                "--sparse-update", "dedup"], ffm_kw) == 0
    # DeepFM on the 2-D (feat, row) mesh with the device-built compact
    # aux (round 3) — must run clean, eval included.
    assert run("g6", "criteo1tb_deepfm",
               ["--row-shards", "2", "--compact-device",
                "--compact-cap", "128", "--sparse-update", "dedup",
                "--eval-every", "2", "--test-fraction", "0.2"],
               deepfm_kw) == 0
    # HOST-built compact aux on the sharded (1-D, single-process) FM
    # step — the DedupAuxBatches→stack_compact_aux producer chain the
    # round-4 refactor touched; must run clean end-to-end.
    assert run("g7", "criteo1tb_fm_r64",
               ["--host-dedup", "--compact-cap", "128",
                "--sparse-update", "dedup"], fm_kw) == 0
    # Round-4 levers end-to-end: bf16 wire + score-sharded on the
    # sharded FM step, with weak-scaling batch sizing (global batch =
    # per-chip x 8 fake devices).
    assert run("g8", "criteo1tb_fm_r64",
               ["--collective-dtype", "bfloat16", "--score-sharded",
                "--batch-per-chip", "16"], fm_kw, batch=None) == 0
    with pytest.raises(SystemExit, match="exclusive"):
        run("g9", "criteo1tb_fm_r64",
            ["--batch-per-chip", "16"], fm_kw)
    # Round-5 lever: the example-sharded deep head on the sharded
    # DeepFM step (with bf16 wire) — must run clean end-to-end; FM has
    # no deep head, so the registry guard must hard-fail it.
    assert run("g10", "criteo1tb_deepfm",
               ["--deep-sharded", "--collective-dtype", "bfloat16"],
               deepfm_kw) == 0
    with pytest.raises(SystemExit, match="deep-sharded"):
        run("g11", "criteo1tb_fm_r64", ["--deep-sharded"], fm_kw)
    # Round-5 composed kernels through the CLI registry: --gfull-fused
    # alone and composed with --segtotal-pallas over the device-built
    # compact aux (the measured 1.356M headline combination's scale-out
    # form, PERF.md round-5 table) — must run clean end-to-end.
    assert run("g12", "criteo1tb_fm_r64", ["--gfull-fused"], fm_kw) == 0
    assert run("g13", "criteo1tb_fm_r64",
               ["--gfull-fused", "--segtotal-pallas", "--compact-device",
                "--compact-cap", "128", "--sparse-update", "dedup"],
               fm_kw) == 0


def test_help_renders_for_every_subcommand(capsys):
    # argparse expands help strings with %-formatting at RENDER time, so
    # an unescaped literal % in any flag's help crashes --help for the
    # whole subcommand (round 5: the --gfull-fused lever help's "~+8%"
    # broke `train --help` with "%o format: an integer is required").
    # Render every subcommand's help to pin this class of regression.
    for sub in ("train", "eval", "predict", "preprocess", "list-configs"):
        with pytest.raises(SystemExit) as exc:
            cli.build_parser().parse_args([sub, "--help"])
        assert exc.value.code == 0
        assert capsys.readouterr().out  # non-empty rendered help


def test_distributed_flag_plumbs_initialize(monkeypatch):
    # --distributed must call jax.distributed.initialize BEFORE any
    # backend work: bare flag -> auto-detect (no kwargs); explicit
    # triple -> passed through; partial triple / orphan flags -> hard
    # fail (a partial triple would auto-detect against the wrong
    # cluster). The hook is exercised directly; cmd_train's call
    # ORDERING (init before the first backend touch) is pinned in
    # test_distributed_init_precedes_backend_touch.
    import jax

    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))

    def parse(extra):
        return cli.build_parser().parse_args(
            ["train", "--config", "movielens_fm_r8", "--synthetic", "64"]
            + extra)

    from fm_spark_tpu.cli import _maybe_init_distributed

    _maybe_init_distributed(parse([]))
    assert calls == []  # no flag -> no init

    _maybe_init_distributed(parse(["--distributed"]))
    assert calls == [{}]  # auto-detect form

    calls.clear()
    _maybe_init_distributed(parse(
        ["--distributed", "--coordinator", "127.0.0.1:1234",
         "--num-processes", "2", "--process-id", "1"]))
    assert calls == [{"coordinator_address": "127.0.0.1:1234",
                      "num_processes": 2, "process_id": 1}]

    with pytest.raises(SystemExit):
        _maybe_init_distributed(parse(
            ["--distributed", "--coordinator", "127.0.0.1:1234"]))
    with pytest.raises(SystemExit):
        _maybe_init_distributed(parse(["--num-processes", "2"]))


def test_train_has_no_table_layout_flag(capsys):
    """``--table-layout`` went with ``FieldFMSpec.table_layout`` (PR 31):
    asking for it is a usage error, not a silently ignored option."""
    with pytest.raises(SystemExit) as exc:
        cli.build_parser().parse_args(
            ["train", "--config", "criteo1tb_fm_r64", "--synthetic", "64",
             "--table-layout", "col"])
    assert exc.value.code == 2
    assert "--table-layout" in capsys.readouterr().err


def test_distributed_init_precedes_backend_touch():
    # On a pod slice, jax.distributed.initialize must run before the
    # backend initializes (a single-process backend init first would
    # break multi-host). Pin the cmd_train ordering structurally: the
    # hook call appears before the first backend-touching call.
    import inspect

    src = inspect.getsource(cli.cmd_train)
    hook = src.index("_maybe_init_distributed(args)")
    for touch in ("device_count", "process_count", "jax.devices"):
        if touch in src:
            assert hook < src.index(touch), touch


def test_readme_multihost_exemplar_validates():
    # The README "Multi-host" quick-start command must parse and pass
    # the lever validator — a lever rename or a new validation rule
    # that breaks the documented command should fail here, not in a
    # user's pod job. The command is EXTRACTED from README.md (not
    # hand-copied), so an edit to either side re-validates the pair;
    # only host-environment flags (--data, --checkpoint-dir) are
    # swapped for --synthetic.
    import os
    import re
    import shlex

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "README.md")) as f:
        text = f.read()
    # Continuation lines first ([^\n]*\\\n repeated), then the final
    # line — the naive [^\n]*(?:\\\n...)* form never extends past the
    # first line (the zero-iteration group already succeeds, and greedy
    # quantifiers don't backtrack to lengthen a match).
    cmds = [m.group(0).replace("\\\n", " ") for m in re.finditer(
        r"python -m fm_spark_tpu\.cli train(?:[^\n]*\\\n)*[^\n]*", text)]
    dist = [c for c in cmds if "--distributed" in c]
    assert len(dist) == 1, "expected exactly one --distributed exemplar"
    argv = shlex.split(dist[0])[3:]  # drop 'python -m fm_spark_tpu.cli'
    cleaned, i = [], 0
    while i < len(argv):
        if argv[i] in ("--data", "--checkpoint-dir"):
            i += 2
            continue
        cleaned.append(argv[i])
        i += 1
    args = cli.build_parser().parse_args(cleaned + ["--synthetic", "64"])
    assert args.distributed
    from fm_spark_tpu.cli import _lever_overrides
    from fm_spark_tpu.cli_levers import check_levers_any

    cfg = configs_lib.get_config(args.config)
    tconfig = cfg.train_config(**_lever_overrides(args))
    assert check_levers_any(tconfig) is None
    assert tconfig.compact_device and tconfig.score_sharded
    assert tconfig.collective_dtype == "bfloat16"


def test_cap_advise_bounds_and_format(tmp_path, capsys):
    """cap-advise's recommendation must bound the observed per-field
    unique count with headroom, stay a 512 multiple (segtotal tile),
    and never exceed the batch size."""
    import json as json_lib

    from fm_spark_tpu.cli import build_parser
    from fm_spark_tpu.data import PackedWriter

    rng = np.random.default_rng(0)
    n, f, bucket = 3000, 5, 200
    ids = (rng.integers(0, bucket, size=(n, f))
           + np.arange(f) * bucket).astype(np.int32)
    labels = rng.integers(0, 2, n).astype(np.int8)
    with PackedWriter(str(tmp_path / "pk"), f, store_vals=False) as w:
        w.append(ids, labels)
    args = build_parser().parse_args([
        "cap-advise", "--data", str(tmp_path / "pk"),
        "--batch-size", "256", "--batches", "4",
    ])
    assert args.fn(args) == 0
    out = json_lib.loads(capsys.readouterr().out.strip())
    rec = out["recommended_compact_cap"]
    assert rec % 512 == 0 or rec == 256  # tile-rounded unless batch-capped
    assert rec <= 256
    assert out["max_unique_per_field_overall"] <= 256
    assert len(out["per_field_max"]) == f
    assert max(out["per_field_max"]) == out["max_unique_per_field_overall"]
    if rec % 512:
        # Sub-tile batch: the note must not claim tile rounding.
        assert "NOT tile-aligned" in out["note"]


def test_cap_advise_clamp_note_matches_value(tmp_path, capsys):
    """When the recommendation is clamped to a non-512-multiple batch
    size, the note must stop claiming tile rounding (ADVICE r5) — and
    the clamp itself must stay batch_size, the only value that bounds
    ANY future batch's unique count unconditionally (rounding down to
    the tile could dip under a future batch the scan never saw)."""
    import json as json_lib

    from fm_spark_tpu.cli import build_parser
    from fm_spark_tpu.data import PackedWriter

    rng = np.random.default_rng(1)
    n, f, bucket = 4000, 5, 1000
    # Per-field unique count near 500 at batch 1000 (each residue
    # class has 8 copies in the file): with headroom 0.5 the unclamped
    # recommendation exceeds the batch for any plausible chunk-shuffled
    # coverage (≥ ~342 unique), so the clamp path is deterministic.
    ids = ((np.arange(n)[:, None] % 500)
           + np.arange(f) * bucket).astype(np.int32)
    labels = rng.integers(0, 2, n).astype(np.int8)
    with PackedWriter(str(tmp_path / "pk"), f, store_vals=False) as w:
        w.append(ids, labels)
    args = build_parser().parse_args([
        "cap-advise", "--data", str(tmp_path / "pk"),
        "--batch-size", "1000", "--batches", "3", "--headroom", "0.5",
    ])
    assert args.fn(args) == 0
    out = json_lib.loads(capsys.readouterr().out.strip())
    overall = out["max_unique_per_field_overall"]
    assert 342 <= overall <= 500
    # Clamped to the batch (no batch of 1000 rows can exceed 1000
    # uniques), and the note says so instead of claiming the tile.
    assert out["recommended_compact_cap"] == 1000
    assert "NOT tile-aligned" in out["note"]
    assert "rounded to the segtotal 512 tile" not in out["note"]


def test_row_scale_guard_predicate():
    # ISSUE 2 satellite (VERDICT r5 next-round #8): the ≥1M-feature
    # row-strategy guardrail points the user at the fused path.
    assert cli.check_row_scale("row", 999_999) is None
    assert cli.check_row_scale("field_sparse", 10_000_000) is None
    assert cli.check_row_scale("dp", 10_000_000) is None
    msg = cli.check_row_scale("row", 1_000_000)
    assert msg is not None
    assert "field_sparse" in msg and "--force" in msg


def test_cli_row_at_scale_hard_fails_without_force():
    with pytest.raises(SystemExit, match="field_sparse"):
        cli.main([
            "train", "--config", "criteo1tb_fm_r64", "--strategy", "row",
            "--synthetic", "128", "--steps", "1", "--test-fraction", "0",
        ])


def test_cli_row_at_scale_warns_with_force(monkeypatch, capsys):
    # --force downgrades the guardrail to a stderr warning; the fit
    # itself is stubbed (a 10M-feature dense-row step is exactly what
    # the guard exists to prevent on this box).
    ran = {}
    monkeypatch.setattr(
        cli, "_fit_parallel",
        lambda *a, **k: ran.setdefault("fit", True) and None,
    )
    rc = cli.main([
        "train", "--config", "criteo1tb_fm_r64", "--strategy", "row",
        "--synthetic", "128", "--steps", "1", "--test-fraction", "0",
        "--force",
    ])
    assert rc == 0 and ran["fit"]
    err = capsys.readouterr().err
    assert "warning:" in err and "field_sparse" in err


def test_cli_supervise_requires_single_and_checkpoint_dir():
    with pytest.raises(SystemExit, match="--supervise requires"):
        cli.main([
            "train", "--config", "movielens_fm_r8", "--synthetic", "128",
            "--steps", "1", "--test-fraction", "0", "--supervise",
        ])


def test_cli_supervised_train_recovers_from_device_loss(tmp_path, capsys):
    # End-to-end CLI wiring of the resilience subsystem: a device loss
    # mid-run is recovered via the checkpoint (the continuity assertion
    # itself lives in tests/test_resilience.py) and journaled to
    # <checkpoint-dir>/health.jsonl.
    from fm_spark_tpu.resilience import faults

    faults.activate("train_step@4=device_loss")
    try:
        rc = cli.main([
            "train", "--config", "movielens_fm_r8", "--synthetic", "256",
            "--steps", "6", "--batch-size", "64", "--test-fraction", "0",
            "--checkpoint-dir", str(tmp_path / "ck"),
            "--checkpoint-every", "2", "--supervise", "--prefetch", "0",
        ])
    finally:
        faults.clear()
    assert rc == 0
    from fm_spark_tpu.utils.logging import read_events

    events = [e["event"]
              for e in read_events(str(tmp_path / "ck" / "health.jsonl"))]
    assert "failure" in events and "backoff" in events
    assert "recovered" in events


# ------------------------------------------- streaming text ingest (ISSUE 5)


def _dirty_shards(tmp_path, n_shards=2, rows=60, bad_lines=(6,)):
    from fm_spark_tpu.data import criteo

    paths = []
    for s in range(n_shards):
        p = str(tmp_path / f"s{s}.tsv")
        criteo.synthesize_tsv(p, rows, seed=s)
        paths.append(p)
    with open(paths[-1], "rb") as f:
        lines = f.read().splitlines(keepends=True)
    for ln in bad_lines:
        lines[ln - 1] = b"\x00garbage line\n"
    with open(paths[-1], "wb") as f:
        f.write(b"".join(lines))
    return paths


def test_cli_streaming_text_quarantine_trains_and_dead_letters(tmp_path,
                                                               capsys):
    """--data with a comma-separated shard list streams raw dirty text;
    quarantine policy finishes the run and dead-letters the corrupt
    line with path:lineno."""
    from fm_spark_tpu.utils.logging import read_events

    paths = _dirty_shards(tmp_path)
    qdir = str(tmp_path / "quar")
    rc = cli.main([
        "train", "--config", "criteo_kaggle_fm_r32",
        "--data", ",".join(paths),
        "--steps", "5", "--batch-size", "16", "--test-fraction", "0",
        "--data-policy", "quarantine", "--quarantine-dir", qdir,
        "--log-every", "5",
    ])
    assert rc == 0
    bad = [e for e in read_events(qdir + "/deadletter.jsonl")
           if e["event"] == "bad_record"]
    assert len(bad) == 1
    assert bad[0]["path"] == paths[-1] and bad[0]["lineno"] == 6
    # The run's summary metrics line carries the quarantine accounting.
    out = capsys.readouterr().out
    assert any('"bad_records": 1' in l for l in out.splitlines())


def test_cli_streaming_text_strict_fails_with_path_lineno(tmp_path):
    from fm_spark_tpu.data.stream import BadRecord

    paths = _dirty_shards(tmp_path)
    with pytest.raises(BadRecord, match=r"s1\.tsv:6"):
        cli.main([
            "train", "--config", "criteo_kaggle_fm_r32",
            "--data", ",".join(paths),
            "--steps", "5", "--batch-size", "16", "--test-fraction", "0",
        ])


def test_cli_streaming_text_breaker_aborts_above_max_bad_frac(tmp_path):
    from fm_spark_tpu.data.stream import IngestAborted

    paths = _dirty_shards(tmp_path, bad_lines=tuple(range(5, 35)))
    with pytest.raises(IngestAborted, match="max_bad_frac"):
        cli.main([
            "train", "--config", "criteo_kaggle_fm_r32",
            "--data", ",".join(paths),
            "--steps", "8", "--batch-size", "16", "--test-fraction", "0",
            "--data-policy", "quarantine",
            "--quarantine-dir", str(tmp_path / "quar"),
            "--max-bad-frac", "0.1",
        ])


def test_cli_streaming_native_ingest_quarantines_identically(tmp_path,
                                                             capsys):
    """--native-ingest routes the same shard list through the C++ chunk
    parser: identical quarantine accounting in the summary line, and an
    automatic fallback (with a stderr notice) when the native parser is
    unavailable."""
    from unittest import mock

    from fm_spark_tpu import native
    from fm_spark_tpu.utils.logging import read_events

    if not native.stream_parse_available("criteo"):
        pytest.skip(f"native chunk parser unavailable: "
                    f"{native.build_error()}")
    paths = _dirty_shards(tmp_path)
    qdir = str(tmp_path / "quar")
    argv = [
        "train", "--config", "criteo_kaggle_fm_r32",
        "--data", ",".join(paths),
        "--steps", "5", "--batch-size", "16", "--test-fraction", "0",
        "--data-policy", "quarantine", "--quarantine-dir", qdir,
        "--log-every", "5", "--native-ingest", "--prefetch", "0",
    ]
    assert cli.main(argv) == 0
    bad = [e for e in read_events(qdir + "/deadletter.jsonl")
           if e["event"] == "bad_record"]
    assert len(bad) == 1
    assert bad[0]["path"] == paths[-1] and bad[0]["lineno"] == 6
    out = capsys.readouterr()
    assert any('"bad_records": 1' in l for l in out.out.splitlines())
    assert "fell back" not in out.err
    # .so unavailable: same command falls back to the Python parser and
    # says so, instead of failing.
    with mock.patch.object(native, "stream_parse_available",
                           lambda dataset: False):
        assert cli.main(argv + ["--quarantine-dir",
                                str(tmp_path / "quar2")]) == 0
    assert "fell back" in capsys.readouterr().err


def test_cli_quarantine_defaults_into_obs_run_dir(tmp_path, capsys):
    """ISSUE 7 consolidation: without --quarantine-dir the dead-letter
    journal joins the run's other telemetry under <obs-dir>/<run_id>/,
    and the run id is echoed as the first JSON line."""
    import os

    from fm_spark_tpu.utils.logging import read_events

    paths = _dirty_shards(tmp_path)
    obs_root = tmp_path / "obs"
    assert cli.main([
        "train", "--config", "criteo_kaggle_fm_r32",
        "--data", ",".join(paths), "--steps", "5",
        "--batch-size", "16", "--test-fraction", "0",
        "--data-policy", "quarantine", "--log-every", "5",
        "--obs-dir", str(obs_root),
    ]) == 0
    out = capsys.readouterr().out
    run_line = json.loads(next(
        l for l in out.splitlines() if '"obs_dir"' in l))
    assert run_line["run_id"] in run_line["obs_dir"]
    dead = read_events(os.path.join(run_line["obs_dir"],
                                    "deadletter.jsonl"))
    bad = [e for e in dead if e["event"] == "bad_record"]
    assert len(bad) == 1
    assert bad[0]["path"] == paths[-1] and bad[0]["lineno"] == 6
    # The run's other streams landed beside it, one directory per run.
    names = set(os.listdir(run_line["obs_dir"]))
    assert {"trace.jsonl", "flight.jsonl", "deadletter.jsonl"} <= names


def test_cli_streaming_text_guards(tmp_path):
    paths = _dirty_shards(tmp_path, bad_lines=())
    # quarantine without a dead-letter destination: since ISSUE 7 the
    # journal defaults into the per-run obs dir; with the telemetry
    # plane off there is nowhere to land, so it stays a config error.
    with pytest.raises(SystemExit, match="quarantine-dir"):
        cli.main([
            "train", "--config", "criteo_kaggle_fm_r32",
            "--data", ",".join(paths), "--steps", "2",
            "--batch-size", "16", "--test-fraction", "0",
            "--data-policy", "quarantine", "--obs-dir", "none",
        ])
    # streaming holds out no eval split: an implicit test fraction must
    # hard-fail, never silently train on 100% while reporting nothing.
    with pytest.raises(SystemExit, match="test-fraction"):
        cli.main([
            "train", "--config", "criteo_kaggle_fm_r32",
            "--data", ",".join(paths), "--steps", "2",
            "--batch-size", "16",
        ])
    # a missing shard names itself.
    with pytest.raises(SystemExit, match="missing shard"):
        cli.main([
            "train", "--config", "criteo_kaggle_fm_r32",
            "--data", paths[0] + ",/nonexistent/x.tsv", "--steps", "2",
            "--batch-size", "16", "--test-fraction", "0",
        ])


@pytest.mark.slow
def test_cli_streaming_checkpoint_resume_continues_cursor(tmp_path,
                                                          capsys):
    """The streaming cursor rides the CLI checkpoint path: a second
    invocation with the same --checkpoint-dir resumes and finishes the
    remaining steps instead of replaying from scratch."""
    paths = _dirty_shards(tmp_path, bad_lines=())
    ck = str(tmp_path / "ck")
    common = [
        "train", "--config", "criteo_kaggle_fm_r32",
        "--data", ",".join(paths), "--batch-size", "16",
        "--test-fraction", "0", "--checkpoint-dir", ck,
        "--checkpoint-every", "2", "--log-every", "1", "--prefetch", "0",
    ]
    assert cli.main(common + ["--steps", "4"]) == 0
    first = capsys.readouterr().out
    assert cli.main(common + ["--steps", "8"]) == 0
    second = capsys.readouterr().out
    steps_logged = [json.loads(l)["step"] for l in second.splitlines()
                    if l.startswith('{"step"')]
    # Resumed at 5, not 1 — the cursor (and step count) came back.
    assert min(steps_logged) == 5 and max(steps_logged) == 8
