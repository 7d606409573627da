"""``device_idle_share`` of a scoring cell (%); a metric of its own because
it moves another end-to-end metric than the training cells'."""

from benchmark.layer_metrics import device_idle_share


def read(run):
    return device_idle_share.read(run)
