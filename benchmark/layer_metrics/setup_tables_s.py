"""Making and placing the tables (s): ``setup/init`` + ``setup/place``
(+ ``setup/install``) inside the cell's own ``setup/run``."""

from benchmark import setup_spans


def read(run):
    return setup_spans.tables_s(run)
