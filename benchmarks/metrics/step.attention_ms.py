"""Language-model candidate: device time a step under scope
`lm.attention` (the layers' attention norm, projections, RoPE, and the
attention core within), forward, recomputed and backward
(`benchmarks/lm_reduce.py`). Profiler trace."""

from benchmarks import lm_reduce

UNIT = "ms"


def read(record):
    return lm_reduce.scope_ms(record, "lm.attention", "lm.attention_core")
