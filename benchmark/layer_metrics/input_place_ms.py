"""Host-to-device placement of one batch (ms): median ``train/prep``
(pad, shard, ``jnp.asarray`` / ``device_put``)."""

from benchmark import program_spans


def read(run):
    return program_spans.train_ms(run, "train/prep")
