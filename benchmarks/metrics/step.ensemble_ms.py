"""Ensemble: device time a step of the ensembles' losses and the frozen
members' forward passes: operations under `ensemble.*` and `frozen.*`,
summed over the traced steady span and divided by its whole steps
(`benchmarks/scope_reduce.py`). Profiler trace."""

from benchmarks import scope_reduce

UNIT = "ms"


def read(record):
    return scope_reduce.group_ms(record, "ensemble")
