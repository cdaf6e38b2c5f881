"""Kernels: device time a step of the candidates' forward and backward
operations whose module path holds a `_SepConv` instance (`*_sep/`): what
`adanet_tpu/ops/sepconv_kernels.py` could replace, its batch norms
included (`benchmarks/scope_reduce.py`). Profiler trace."""

from benchmarks import scope_reduce

UNIT = "ms"


def read(record):
    out = scope_reduce.of_record(record)
    if not out or not out["scopes"]:
        return None
    return out["scopes"]["sepconv_ms"]
