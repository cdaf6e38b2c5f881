"""Compile caches: seconds of backend compiles and cache loads
(`jax.monitoring`) during set-up."""

UNIT = "s"


def read(record):
    return record["compiles_in_setup"][1]
