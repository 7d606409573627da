"""The program's registry, held to a benchmark configuration file."""

from __future__ import annotations

import dataclasses


def registry_config(config: dict):
    """The registry's configuration of that name, held to every size the
    benchmark's file states; only ``bucket`` may differ (PERF.md: raised
    where the registry's tables fall under the driver's memory floor),
    and then the file's value is installed in the registry for this
    process. A rehearsal's tiny sizes are not held against the registry:
    the file as written is (``config["as_written"]``)."""
    from fm_spark_tpu import configs

    cfg = configs.get_config(config["registry"])
    written = config.get("as_written", config)
    want = {**written["model"], **written.get("training", {})}
    want.pop("bucket")
    bucket = config["model"]["bucket"]
    if "batch_per_chip" in want:
        want["batch_size"] = want.pop("batch_per_chip")
    spec = cfg.spec()
    # The registry leaves these two to the spec's defaults.
    held = {k: getattr(cfg, k, None) for k in want}
    held.update(loss=spec.loss, init_std=spec.init_std)
    bad = {k: (held[k], v) for k, v in want.items() if held[k] != v}
    if bad:
        raise SystemExit(
            f"benchmark: registry config {cfg.name!r} differs from "
            f"{config['name']}.json (registry, file): {bad}")
    if cfg.bucket != bucket:
        cfg = dataclasses.replace(cfg, bucket=bucket)
        configs.CONFIGS[cfg.name] = cfg
    return cfg
