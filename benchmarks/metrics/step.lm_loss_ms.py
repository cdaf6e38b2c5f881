"""Language-model candidate: device time a step under scopes `lm.loss`
(final norm, head) and `blocked_logits` (mixture and loss blocks, the
candidate's own loss and the ensemble's), forward, recomputed and backward
(`benchmarks/lm_reduce.py`). Profiler trace."""

from benchmarks import lm_reduce

UNIT = "ms"


def read(record):
    return lm_reduce.scope_ms(record, "lm.loss", "blocked_logits")
