"""Language-model candidate: device time a step under scope
`lm.moe_route` (the layers' expert norm, router, top-k, sort, gather and
scatter-add), forward, recomputed and backward
(`benchmarks/lm_reduce.py`). Profiler trace."""

from benchmarks import lm_reduce

UNIT = "ms"


def read(record):
    return lm_reduce.scope_ms(record, "lm.moe_route")
