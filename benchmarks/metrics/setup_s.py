"""End to end: process start to the start of the window. Benchmark
clock."""

UNIT = "s"


def read(record):
    return record["setup_s"]
