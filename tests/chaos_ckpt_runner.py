"""Chaos phase A: a writer SIGKILLed mid-checkpoint, leaving a torn file.

Spawned by `test_robustness.py` with `ADANET_FAULTS=
"checkpoint.write:torn:after=2"`: the third payload write (the step-6
mid-iteration checkpoint) writes a truncated prefix DIRECTLY at the
final path — the on-disk result of a crash without atomic-rename
semantics — and SIGKILLs the process. The manifest still points at the
intact step-4 checkpoint; the torn `ckpt-6.msgpack` is an orphan the
resume-side fsck must quarantine.

Shares its search configuration (data, builders, step counts) with
`chaos_multihost_runner.py` and the parent test's oracle run, so the
healed resume must reach the same final architecture.
"""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 8)
except AttributeError:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

enable_persistent_cache()

from chaos_common import build_estimator, input_fn


def main():
    model_dir = sys.argv[1]
    est = build_estimator(model_dir)
    est.train(input_fn, max_steps=100)
    # The armed torn-write fault must have killed us at step 6.
    print("UNEXPECTED COMPLETION", flush=True)


if __name__ == "__main__":
    main()
