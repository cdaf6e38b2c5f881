"""Iteration step: device time a step of the candidates' forward and backward
operations of branch kind `1x1` (a module path with a component that ends
in `_1x1` and none of a `_SepConv`, factorized reduction or batch norm):
the cells' input projections (`beginning_1x1`, `prev_1x1`, or the
`shared_1x1` / `single_1x1` scopes that convolve for them) and the
branches' own `*_1x1` (`benchmarks/scope_reduce.py`, `kinds_ms`).
Profiler trace."""

from benchmarks import scope_reduce

UNIT = "ms"


def read(record):
    out = scope_reduce.of_record(record)
    if not out or not out["scopes"]:
        return None
    return dict(out["scopes"]["kinds_ms"]).get("1x1")
