"""Compile caches: over set-up's `Estimator.train` calls, the
`compile.trace` spans of every step program's compile, summed: the
program traced to a jaxpr (`benchmarks/compile_reduce.py`). The program's
span, on the tracer's clock."""

from benchmarks import compile_reduce

UNIT = "s"


def read(record):
    return compile_reduce.setup(record, "trace")
