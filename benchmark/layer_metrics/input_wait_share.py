"""Share of the loop's time spent blocked on the prefetch queue (%): sum
of ``train/next_batch`` over sum of ``train/step`` in the window. Step
wait on input; rises with ``device_idle_share`` in a feed-bound cell.
Also leaves the whole per-step breakdown on stderr."""

from benchmark import program_spans


def read(run):
    program_spans.log_train_summary(run)
    return program_spans.train_share(run, "train/next_batch")
