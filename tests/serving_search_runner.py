"""Searcher subprocess for the serve-while-search tests.

Runs a deterministic multi-iteration AdaNet search with
`export_serving=True` on a shared model dir, publishing one serving
generation per completed iteration while the PARENT process serves
traffic from the same dir. Chaos runs arm fault sites via
`ADANET_FAULTS` (e.g. `checkpoint.write:torn:after=1` to SIGKILL this
process mid-checkpoint-write); a relaunch without faults heals and
resumes from the durable chain.

Usage: serving_search_runner.py MODEL_DIR MAX_ITERATIONS
"""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# Keyed persistent XLA cache: the restarted searcher (and repeat test
# runs) reuse this single-device subprocess's compiled programs.
from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

enable_persistent_cache()

import optax

import adanet_tpu
from adanet_tpu.ensemble import ComplexityRegularizedEnsembler
from adanet_tpu.subnetwork import SimpleGenerator

from helpers import DNNBuilder, linear_dataset


def main():
    model_dir = sys.argv[1]
    max_iterations = int(sys.argv[2])

    est = adanet_tpu.Estimator(
        head=adanet_tpu.RegressionHead(),
        subnetwork_generator=SimpleGenerator(
            [DNNBuilder("dnn", 1), DNNBuilder("deep", 2)]
        ),
        max_iteration_steps=4,
        ensemblers=[
            ComplexityRegularizedEnsembler(optimizer=optax.sgd(0.05))
        ],
        max_iterations=max_iterations,
        model_dir=model_dir,
        log_every_steps=0,
        save_checkpoint_steps=None,
        export_serving=True,
    )
    est.train(linear_dataset(), max_steps=10**6)
    print("SEARCH DONE", est.latest_iteration_number(), flush=True)


if __name__ == "__main__":
    main()
