"""Search loop: span `iteration.init_state` of the window's call:
`Iteration.init_state`, `module.init` op by op
(`benchmarks/span_reduce.py`). The program's span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.window(record, "iteration.init_state")
