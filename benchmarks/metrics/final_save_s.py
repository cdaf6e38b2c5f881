"""Search loop and checkpoint: stop requested to the return of the window
call (steps in flight finish, the state is fetched and saved). Benchmark
clock."""

UNIT = "s"


def read(record):
    return record["window_end"] - record["clock"]["stop_requested"]
