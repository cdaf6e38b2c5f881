"""Input: median `input.next_batch` span over the window's steady pulls, from
the third on: how long the train loop waits for the feed
(`benchmarks/span_reduce.py`). The program's span, on the tracer's clock."""

from benchmarks import span_reduce

UNIT = "ms"


def read(record):
    return span_reduce.input_wait_ms(record)
