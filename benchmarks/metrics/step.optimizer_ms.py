"""Iteration step: device time a step of the optimizer updates: operations
under `optimizer.*` and `ensemble_optimizer.*`, summed over the traced
steady span and divided by its whole steps (`benchmarks/scope_reduce.py`).
Profiler trace."""

from benchmarks import scope_reduce

UNIT = "ms"


def read(record):
    return scope_reduce.group_ms(record, "optimizer")
