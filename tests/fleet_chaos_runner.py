"""Chaos runner: one fleet run, SIGKILLed at the promotion seam.

Spawned by `test_fleet.py` with `ADANET_FAULTS="fleet.promote:kill"`
(optionally `after=K` to pick which rung boundary dies): the fleet
trains rung 0 to completion — durable trial checkpoints, per-iteration
`replay.json` records, published store refs — and is then SIGKILLed at
the entry of the promotion decision. The parent test resumes the SAME
work dir in-process with no faults armed and asserts the fleet
completes with the oracle fleet's winner and an oracle-identical
champion architecture, with the shared store fsck-clean.

Shares `fleet_common.py` with the in-process oracle so the comparison
is meaningful.
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

from fleet_common import build_fleet


def main():
    work_dir = sys.argv[1]
    report = build_fleet(work_dir).run()
    print("DONE winner=%s" % report.winner_id, flush=True)


if __name__ == "__main__":
    main()
