"""Iteration step: median device duration of the step program's
executions in the traced span, all of them whole (the chip is drained
before the profiler starts and before it stops). Profiler trace."""

import statistics

UNIT = "ms"


def read(record):
    lead = (record["trace"] or {}).get("lead")
    if not lead or not lead.get("step_runs_s"):
        return None
    return statistics.median(lead["step_runs_s"]) * 1e3
