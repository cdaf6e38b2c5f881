"""Chaos runner: one search publishing into a SHARED artifact store.

Spawned (possibly concurrently with a sibling) by `test_store.py` with
`ADANET_FAULTS` arming `store.put` torn/rot faults:

- `store.put:torn:after=K` tears the K+1-th blob publication at its
  FINAL content-addressed path and SIGKILLs the process — a crash
  mid-publish on a filesystem without atomic-rename semantics. The
  resumed run (and any concurrent sibling putting the same bytes) must
  heal the torn blob via put-time verification.
- `store.put:rot:after=K` silently bit-flips the K+1-th published blob
  and carries on — storage rot the verify-on-read / fsck machinery
  must catch and heal from the ref's recorded sources.

Shares the chaos search configuration (`chaos_common.py`) with the
robustness suite's oracle, so "both searches reach the oracle's final
architecture with the store fsck-clean" is a meaningful assertion.
`export_serving=True` so each completed iteration ALSO publishes a
serving generation ref closure — the SIGKILL lands mid-publish of a
multi-blob closure, the hardest crash window.
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
    model_dir, store_root = sys.argv[1], sys.argv[2]
    est = build_estimator(
        model_dir, artifact_store=store_root, export_serving=True
    )
    est.train(input_fn, max_steps=100)
    assert est.latest_iteration_number() == 2
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
