"""Headline-SHAPE spot check for the bf16-wire lever (VERDICT r4 #5).

The bf16-wire quality rows in QUALITY.md come from a 20k-example planted
task at batch 512-4096 — toy activation shapes. This script runs ONE
field-sharded FM train step at the HEADLINE activation shapes
(B=131072, k=64, 39 fields) on the 8-fake-device CPU mesh, with fp32
wire vs bf16 wire from identical params and batch, and reports the
relative error the wire precision injects into:

  - the step loss,
  - the parameter UPDATE (||p_bf16 − p_fp32|| / ||p_fp32 − p_init||,
    per param group) — the gradient-error norm as it lands in the
    tables, which is what compounds over training.

The bucket is shrunk to 16384 (wire precision touches only the
[B, k]-shaped activation collectives — the psum of (s, sq, lin) — whose
magnitudes depend on B/F/k, not on table height), keeping host memory
sane. Until real multi-chip hardware exists this is the at-scale
evidence next to the toy AUC rows; paste the JSON into QUALITY.md.
"""

import json
import os
import time

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

B, F, K, BUCKET = 131072, 39, 64, 16384


def run_step(wire: str):
    from fm_spark_tpu import models
    from fm_spark_tpu.parallel import (
        make_field_mesh,
        make_field_sharded_sgd_step,
        pad_field_batch,
        shard_field_batch,
        shard_field_params,
        stack_field_params,
    )
    from fm_spark_tpu.train import TrainConfig

    spec = models.FieldFMSpec(
        num_features=F * BUCKET, rank=K, num_fields=F, bucket=BUCKET,
        init_std=0.05,
    )
    config = TrainConfig(learning_rate=0.1, optimizer="sgd",
                         reg_linear=1e-5, reg_factors=1e-5,
                         collective_dtype=wire)
    n = 8
    mesh = make_field_mesh(n)
    step = make_field_sharded_sgd_step(spec, config, mesh)
    stacked = stack_field_params(spec, spec.init(jax.random.key(0)), n)
    init = jax.device_get(stacked)
    params = shard_field_params(stacked, mesh)
    rng = np.random.default_rng(0)
    batch = pad_field_batch(
        (
            rng.integers(0, BUCKET, size=(B, F)).astype(np.int32),
            rng.uniform(0.5, 1.5, size=(B, F)).astype(np.float32),
            rng.integers(0, 2, B).astype(np.float32),
            np.ones((B,), np.float32),
        ),
        F, n,
    )
    t0 = time.perf_counter()
    params, loss = step(params, jnp.int32(0), *shard_field_batch(batch,
                                                                 mesh))
    loss = float(loss)
    out = jax.device_get(params)
    print(f"# {wire}: step ran in {time.perf_counter() - t0:.1f}s "
          f"loss={loss:.6f}", flush=True)
    return init, out, loss


def main():
    init, p32, l32 = run_step("float32")
    _, p16, l16 = run_step("bfloat16")
    report = {
        "shape": {"B": B, "F": F, "k": K, "bucket": BUCKET, "n": 8},
        "loss_fp32": l32,
        "loss_bf16_wire": l16,
        "loss_rel_err": abs(l16 - l32) / max(abs(l32), 1e-12),
    }
    for key in p32:
        upd = np.asarray(p32[key], np.float64) - np.asarray(init[key],
                                                           np.float64)
        diff = np.asarray(p16[key], np.float64) - np.asarray(p32[key],
                                                             np.float64)
        denom = float(np.linalg.norm(upd))
        report[f"update_rel_err_{key}"] = (
            float(np.linalg.norm(diff)) / denom if denom else 0.0
        )
    print(json.dumps(report))


if __name__ == "__main__":
    main()
