"""Language-model candidate: device time a step under scope
`lm.moe_experts` (the grouped products over the held experts), forward,
recomputed and backward (`benchmarks/lm_reduce.py`). Profiler trace."""

from benchmarks import lm_reduce

UNIT = "ms"


def read(record):
    return lm_reduce.scope_ms(record, "lm.moe_experts")
