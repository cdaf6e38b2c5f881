"""Search loop: span `iteration.build` of the window's call:
`_build_iteration` (an estimator that has trained keeps its iteration, so
this is a cache hit) (`benchmarks/span_reduce.py`). The program's span, on
the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.window(record, "iteration.build")
