"""Search loop: span `resume.fsck` of the window's call: `integrity.fsck` and
the store's lease and reconcile (`benchmarks/span_reduce.py`). The
program's span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.window(record, "resume.fsck")
