"""Iteration step: device time a step of the candidates' forward passes:
operations under `jvp(candidate.*)`, summed over the traced steady span
and divided by its whole steps (`benchmarks/scope_reduce.py`). Profiler
trace."""

from benchmarks import scope_reduce

UNIT = "ms"


def read(record):
    return scope_reduce.group_ms(record, "candidate_fwd")
