"""Iteration step: the window's first `train_window` (`first=True`): the step
traced, lowered, loaded from the compile cache and dispatched
(`benchmarks/span_reduce.py`). The program's span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.window(record, "first_step")
