"""Search loop: entry of the window call to the first batch the train loop
itself asks for (fsck, rebuild, `init_state`, restore, and the dispatch of
the first step on the sample batch). Benchmark clock."""

UNIT = "s"


def read(record):
    first = record["clock"].get("first_train_pull")
    if first is None:
        return None
    return first - record["window_start"]
