"""Iteration step: device time a step of operations of the step program under
none of the program's scopes (copies, `step.metrics`, operations with no
`tf_op`), summed over the traced steady span and divided by its whole
steps (`benchmarks/scope_reduce.py`). Profiler trace."""

from benchmarks import scope_reduce

UNIT = "ms"


def read(record):
    return scope_reduce.group_ms(record, "unscoped")
