"""Iteration step: span `checkpoint.fetch` of the window's last
`checkpoint.save`: `jax.device_get` of the state, which waits for every
step in flight (`benchmarks/span_reduce.py`). The program's span, on the
tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.window(record, "fetch")
