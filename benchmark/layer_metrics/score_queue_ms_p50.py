"""A request's wait from ``submit()`` to the start of its batch's
execute (ms): median ``serve/queue`` over the window's requests. Also
leaves the coalescer's whole breakdown on stderr."""

from benchmark import program_spans


def read(run):
    program_spans.log_score_summary(run)
    return program_spans.score_median_ms(run, "serve/queue")
