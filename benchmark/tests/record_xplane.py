#!/usr/bin/env python3
"""How ``benchmark/tests/data/*.xplane.pb`` were recorded (on the chip):

    python3 benchmark/tests/record_xplane.py chiprun_out/small_<n>chip.xplane.pb

A few milliseconds of a tiny program with every shape of event the
reduction has to handle: ops nested in a ``while``, gathers and a
scatter-add that rank as families, idle time between two programs and,
on more than one chip, an all-to-all and a psum. Prints what the planes
and lines of the trace are called and which ops it holds, so that the
recorded file can be read by hand.
"""

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"record_xplane: no TPU ({devs[0].platform})")
    n = len(devs)
    table = jnp.arange(4096 * 128, dtype=jnp.float32).reshape(4096, 128)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 4096, 2048))

    @jax.jit
    def looped(t, i):
        def body(_, c):
            rows = c[i]
            return c.at[i].add(rows * 1e-3)
        return jax.lax.fori_loop(0, 3, body, t)

    @jax.jit
    def plain(t, i):
        return jnp.sum(t[i] * 2.0, axis=1)

    mesh = Mesh(np.asarray(devs), ("x",))

    @jax.jit
    def across(x):
        def f(block):
            moved = jax.lax.all_to_all(block, "x", 0, 1, tiled=True)
            return jax.lax.psum(jnp.sum(moved * moved), "x")
        return jax.shard_map(f, mesh=mesh, in_specs=P("x", None),
                         out_specs=P())(x)

    x = jnp.ones((n * 256, n * 128), jnp.float32)
    for _ in range(2):                      # compile outside the trace
        jax.block_until_ready((looped(table, ids), plain(table, ids)))
        if n > 1:
            jax.block_until_ready(across(x))

    trace_dir = tempfile.mkdtemp(prefix="record_xplane_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(3):
        jax.block_until_ready(looped(table, ids))
        time.sleep(0.002)                   # the host dawdles: an idle gap
        jax.block_until_ready(plain(table, ids))
        if n > 1:
            jax.block_until_ready(across(x))
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(found, out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"record_xplane: {out} {os.path.getsize(out)} bytes")

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})
            print(f"  LINE {line.name!r}: {len(events)} events; "
                  f"{names[:12]}")
            if plane.name.startswith("/device:") and events:
                e = events[len(events) // 2]
                print("    e.g.", e.name, e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:12]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
