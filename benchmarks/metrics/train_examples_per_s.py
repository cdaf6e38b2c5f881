"""End to end: steps completed by the one timed `Estimator.train` call
times the batch, over the whole wall time of that call, entry to return
(resume, every step, the drain of steps in flight, the final save).
Benchmark clock."""

UNIT = "examples/s"


def read(record):
    return record["steps"] * record["batch"] / (
        record["window_end"] - record["window_start"]
    )
