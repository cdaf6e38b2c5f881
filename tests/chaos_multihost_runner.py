"""Chaos phase C: multi-host resume with a transient compile-cache
fault on the chief and a dying peer mid-iteration.

Spawned (2 processes) by `test_robustness.py` on the model_dir phase A
tore: process 0 (chief) resumes the search under multi-host RoundRobin
with `ADANET_FAULTS="compile_cache.read:transient:..."` (the bounded
retry must absorb it); process 1 arms
`ADANET_FAULTS="collective.entry:hang:after=2:delay=600"` — at the
step-6 member sync it stops participating, exactly like a dead peer.
The chief's collective watchdog (`ADANET_COLLECTIVE_TIMEOUT_SECS`, set
low by the parent) must convert the hang into `PeerLostError` within
the deadline, quarantine the lost peer's candidate, finish the
iteration with the survivors, persist it, and stop cleanly.

The chief prints one `CHAOS CHIEF DONE <json>` line with its wall time,
lost peers, quarantined candidates, and compile-cache fault trips for
the parent to assert on. The hung peer never finishes; the parent kills
it.
"""

import faulthandler
import json
import os
import signal
import sys
import time

# Stack dumps on demand: the whole point of this runner is proving the
# absence of hangs, so make any hang diagnosable from the parent.
faulthandler.register(signal.SIGUSR1)

import jax

jax.config.update("jax_platforms", "cpu")


def main():
    model_dir = sys.argv[1]
    process_id = int(sys.argv[2])
    num_processes = int(sys.argv[3])
    local_devices = int(sys.argv[4])
    port = sys.argv[5]

    try:
        jax.config.update("jax_num_cpu_devices", local_devices)
    except AttributeError:
        os.environ["XLA_FLAGS"] = os.environ.get(
            "XLA_FLAGS", ""
        ) + " --xla_force_host_platform_device_count=%d" % local_devices
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except AttributeError:
        pass
    jax.distributed.initialize(
        coordinator_address="localhost:%s" % port,
        num_processes=num_processes,
        process_id=process_id,
    )

    from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

    enable_persistent_cache()

    from adanet_tpu.distributed import RoundRobinStrategy
    from adanet_tpu.robustness import faults

    from chaos_common import build_estimator, input_fn

    est = build_estimator(
        model_dir, placement_strategy=RoundRobinStrategy()
    )
    start = time.monotonic()
    est.train(input_fn, max_steps=100)
    wall = time.monotonic() - start

    if process_id == 0:
        spec = faults.armed().get("compile_cache.read")
        record = {
            "wall_secs": round(wall, 2),
            "iteration_number": est.latest_iteration_number(),
            "global_step": est.latest_global_step(),
            "peer_lost": est._peer_lost is not None,
            "compile_cache_fault_trips": spec.trips if spec else 0,
        }
        print("CHAOS CHIEF DONE %s" % json.dumps(record), flush=True)
    else:
        # The peer also degrades: its own watchdog abandons the armed
        # hang, it quarantines its candidate, waits on the chief's
        # manifest, and exits cleanly.
        print("CHAOS PEER %d DONE" % process_id, flush=True)


if __name__ == "__main__":
    main()
