"""Compile caches: JAX's own tracing, lowering and backend compiles in
set-up's `Estimator.train` calls outside every `compile` span (the `jax.s`
attribute of the spans that caused them, the union of the three kinds, so
that work nested across kinds counts once: templates, the real init's
one-op programs, a builder's `jit_init`, the log line's programs), summed
(`benchmarks/compile_reduce.py`). JAX's clock, on the program's spans."""

from benchmarks import compile_reduce

UNIT = "s"


def read(record):
    return compile_reduce.setup(record, "jax_other")
