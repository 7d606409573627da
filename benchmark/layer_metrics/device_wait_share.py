"""Share of the loop's time spent waiting for the device at the loss
fetch (%): sum of ``train/loss_fetch`` over sum of ``train/step``. A loop
that only waits for the chip is a loop the host does not slow: high
where ``device_idle_share`` is low."""

from benchmark import program_spans


def read(run):
    return program_spans.train_share(run, "train/loss_fetch")
