"""Search loop: the `search` spans before the window's, summed: what of set-
up is `Estimator.train` calls (`benchmarks/span_reduce.py`). The program's
span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.setup(record, lambda phases: phases["call"])
