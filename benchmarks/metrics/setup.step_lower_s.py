"""Compile caches: over set-up's `Estimator.train` calls, the
`compile.lower` spans of every step program's compile, summed: the traced
program lowered to StableHLO, a Pallas kernel's Mosaic lowering with it
(`benchmarks/compile_reduce.py`). The program's span, on the tracer's
clock."""

from benchmarks import compile_reduce

UNIT = "s"


def read(record):
    return compile_reduce.setup(record, "lower")
