"""What one batch costs the prefetch producer (ms): MEAN ``feed/produce``
(sum / count). An epoch's shuffle falls on its first batch, so the values
alternate, and what bounds the rate is the mean."""

from benchmark import program_spans


def read(run):
    return program_spans.train_ms(run, "feed/produce", mean=True)
