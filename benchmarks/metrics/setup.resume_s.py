"""Search loop: over set-up's `Estimator.train` calls, the five resume spans
of each (fsck, build, `init_state`, restore, first window), summed
(`benchmarks/span_reduce.py`). The program's span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "s"


def read(record):
    return span_reduce.setup(record, span_reduce.resume_sum)
