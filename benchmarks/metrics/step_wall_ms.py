"""Iteration step: wall time of one step with the profiler off, over the
steps the train loop dispatches between two barriers of the harness (the
chip drained at both). What `step_device_ms` has to agree with. Benchmark
clock."""

UNIT = "ms"


def read(record):
    clock = record["clock"]
    if "wall_stop" not in clock:
        return None
    return (
        (clock["wall_stop"] - clock["wall_start"]) * 1e3
        / record["timed_steps"]
    )
