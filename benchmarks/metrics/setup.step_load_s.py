"""Compile caches: over set-up's `Estimator.train` calls, the
`compile.backend` and `compile.store_load` spans of every step program's
compile, summed: XLA's compile or its persistent cache's load, and the
artifact store's executables (`benchmarks/compile_reduce.py`). The
program's span, on the tracer's clock."""

from benchmarks import compile_reduce

UNIT = "s"


def read(record):
    return compile_reduce.setup(record, "load")
