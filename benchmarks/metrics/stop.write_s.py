"""Checkpoint: span `checkpoint.write` of the window's last
`checkpoint.save`: serialize, staged write, fsync, rename, digest
(`benchmarks/span_reduce.py`). The program's span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.window(record, "write")
