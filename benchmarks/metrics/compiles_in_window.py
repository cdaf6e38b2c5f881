"""Compile caches: backend compiles and cache loads (`jax.monitoring`)
that ended inside the window. 0 expected."""

UNIT = "count"


def read(record):
    return record["compiles_in_window"][0]
