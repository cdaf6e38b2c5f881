"""Checkpoint: span `checkpoint.restore` of the window's call:
`restore_pytree` reads, verifies, decodes and places the saved state
(`benchmarks/span_reduce.py`). The program's span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.window(record, "checkpoint.restore")
