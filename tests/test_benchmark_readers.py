"""The benchmark's two readers of the program's tracing, run with the
repository's own tests: `benchmarks/span_reduce.py` (the span ring) and
`benchmarks/scope_reduce.py` (device time by `named_scope`). Their cases
live beside them under `benchmarks/`; a change to a span's name or
nesting in `core/estimator.py`, or to a scope in `core/iteration.py`,
has to keep them reading.
"""

from benchmarks.test_scope_reduce import *  # noqa: F401,F403
from benchmarks.test_span_reduce import *  # noqa: F401,F403
