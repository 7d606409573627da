"""Time under ``compile/trace``, ``compile/lower`` and
``compile/backend`` in the whole process before the window (s), the
check's runs' and the reference's own jits included; an instant under two
of them counts once."""

from benchmark import setup_spans


def read(run):
    return setup_spans.compile_s(run)
