"""Compile caches: over set-up's `Estimator.train` calls, the
`compile.key` spans of every step program's compile, summed: the compile
cache's key, the lowered text rendered, canonicalised and hashed, the
trees and devices (`benchmarks/compile_reduce.py`). The program's span, on
the tracer's clock."""

from benchmarks import compile_reduce

UNIT = "s"


def read(record):
    return compile_reduce.setup(record, "key")
