#!/usr/bin/env python3
"""How ``benchmark/tests/data/small_opt.xplane.pb`` was recorded (on the
chip):

    python3 benchmark/tests/record_opt_xplane.py chiprun_out/small_opt.xplane.pb

Three steps of the program's own fused FieldFFM AdaGrad step at a tiny
size (5 fields, rank 4, 64 buckets, batch 128), so that the trace holds
what ``benchmark/opt_trace.py`` has to find: events whose metadata
states ``opt/coalesce``, ``opt/gather``, ``opt/rule`` and ``opt/write``
among the forward's gathers and the backward's products, which state
none. Prints every event's name, scope and duration, so that the
recorded file can be read by hand.
"""

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import deep_trace, opt_trace
    from benchmark import trace_reduce as tr
    from fm_spark_tpu import models
    from fm_spark_tpu.sparse import make_field_ffm_adagrad_step
    from fm_spark_tpu.train import TrainConfig

    if jax.devices()[0].platform != "tpu":
        raise SystemExit(f"record_opt_xplane: no TPU "
                         f"({jax.devices()[0].platform})")
    fields, rank, bucket, batch = 5, 4, 64, 128
    spec = models.FieldFFMSpec(
        num_features=fields * bucket, rank=rank, num_fields=fields,
        bucket=bucket)
    step = make_field_ffm_adagrad_step(spec, TrainConfig(
        learning_rate=0.05, lr_schedule="constant", optimizer="adagrad",
        adagrad_init_accumulator=1.0 / batch ** 2, reg_factors=2e-5))
    params = spec.init(jax.random.key(0))
    slots = step.init_opt_state(params)
    rng = np.random.default_rng(0)
    data = (jnp.asarray(rng.integers(0, bucket, (batch, fields)), jnp.int32),
            jnp.ones((batch, fields), jnp.float32),
            jnp.asarray(rng.integers(0, 2, batch), jnp.float32),
            jnp.ones((batch,), jnp.float32))
    for i in range(2):                      # compile outside the trace
        params, slots, loss, _ = step(params, slots, jnp.int32(i), *data)
    jax.block_until_ready(loss)

    trace_dir = tempfile.mkdtemp(prefix="record_opt_xplane_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(3):
        params, slots, loss, _ = step(params, slots, jnp.int32(2 + i), *data)
        jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(found, out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"record_opt_xplane: {out} {os.path.getsize(out)} bytes")

    from jax.profiler import ProfileData

    scopes = deep_trace.op_scopes(out)
    for plane in ProfileData.from_file(out).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for e in list(line.events)[:len(list(line.events)) // 3]:
                print(f"{e.duration_ns:8.0f} ns  "
                      f"{scopes[plane.name].get(e.name, '-'):70s} "
                      f"{e.name[:90]}")
    print(opt_trace.update_seconds(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
