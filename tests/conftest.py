"""Test configuration: force an 8-device virtual CPU mesh.

Tests exercise all sharding paths on virtual CPU devices (the analogue of
the reference's TF_CONFIG localhost clusters,
reference: adanet/core/estimator_distributed_test.py). The jax config
values are set directly, before any backend exists, so the mesh does not
depend on how the caller's environment was prepared.
"""

import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent XLA compilation cache: NASNet-class modules are expensive to
# compile on CPU; repeated test runs reuse compiled executables. The dir
# is keyed by (jax, jaxlib, backend, device count) — a flat shared dir
# segfaulted the suite mid-run when it held executables serialized under
# a different topology/jax build. Initializing the backend here (after
# the platform/device config above) is safe: every test forces CPU.
from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

_CACHE_DIR = enable_persistent_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy workload tests; run with RUN_SLOW=1"
    )


def pytest_collection_modifyitems(config, items):
    import pytest

    if os.environ.get("RUN_SLOW"):
        return
    skip = pytest.mark.skip(reason="slow workload test; set RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


import pytest as _pytest


@_pytest.fixture
def record_gate(request):
    """Appends a gate's MEASURED values to $ADANET_GATES_OUT (JSON lines).

    Round-3 verdict #4: the accuracy gates' measured values must be on
    the driver-visible record each round, not just pass/fail. A RUN_SLOW=1
    pass with ADANET_GATES_OUT=GATES_r<N>.json produces the artifact; with
    the env unset this is a no-op.
    """
    import json

    import numpy as np

    def _record(metrics=None, **extra):
        path = os.environ.get("ADANET_GATES_OUT")
        if not path:
            return
        entry = {"gate": request.node.name}
        for source in (metrics or {}), extra:
            for key, value in source.items():
                if isinstance(value, (bool, int, float, str, list)):
                    entry[key] = value
                elif isinstance(value, (np.floating, np.integer)):
                    entry[key] = float(value)
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")

    return _record
