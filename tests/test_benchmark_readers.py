"""The benchmark's own fast cases, run with the repository's tests.

The readers of the program's tracing: `benchmarks/span_reduce.py` (the
span ring), `benchmarks/compile_reduce.py` (set-up's compile path in the
same ring) and `benchmarks/scope_reduce.py` (device time by
`named_scope`); a change to a span's name or nesting in
`core/estimator.py` or `core/compile_cache.py`, or to a scope in
`core/iteration.py`, has to keep them reading. Device time from a
profiler trace (`benchmarks/trace_reduce.py`), and `benchmarks/run.py`'s
refusals: a cell of `BENCHMARK.json` does not run off the chip, and a
named file that is missing is an error that names it. The cases live
beside the code under `benchmarks/`; `test_run.py`'s are picked by
name, since its rehearsal cases take minutes.
"""

from benchmarks.test_run import (  # noqa: F401
    copied,
    test_a_cell_of_the_benchmark_does_not_run_off_the_chip,
    test_a_named_file_that_is_missing_is_an_error_that_names_it,
)
from benchmarks.test_compile_reduce import *  # noqa: F401,F403
from benchmarks.test_scope_reduce import *  # noqa: F401,F403
from benchmarks.test_span_reduce import *  # noqa: F401,F403
from benchmarks.test_trace_reduce import (  # noqa: F401
    test_a_plane_with_no_operation_reads_nothing,
    test_steady_span_runs_from_first_to_last_step,
    test_the_recorded_trace,
    test_union_merges_and_clips,
)
