"""Serving-plane preemption runner: serve until SIGTERM, drain, exit 0.

The serving analogue of `sigterm_runner.py`: publishes one tiny
generation, starts the front-end with the SIGTERM handler installed,
keeps a stream of async requests in flight, and prints READY so the
parent test knows when to signal. On SIGTERM the front-end must stop
admitting, answer every accepted request, and exit cleanly — the final
line reports the tally the parent asserts on
(`DRAINED ok=<n> errors=<n> unanswered=<n>`).
"""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

enable_persistent_cache()

import numpy as np
import jax.numpy as jnp

from adanet_tpu import serving


def main():
    model_dir = sys.argv[1]

    def predict_fn(features):
        return {"y": jnp.tanh(features["x"])}

    serving.publish_generation(
        model_dir, 0, predict_fn, {"x": np.zeros((2, 3), np.float32)}
    )
    pool = serving.ModelPool(model_dir)
    pool.poll()
    frontend = serving.ServingFrontend(
        serving.Batcher(pool),
        serving.FrontendConfig(
            default_deadline_secs=30.0, batch_wait_secs=0.001
        ),
    ).start()
    frontend.install_sigterm_handler()

    import time

    features = {"x": np.ones((1, 3), np.float32)}
    pending = []
    sent = 0
    while not frontend._draining:
        pending.append(frontend.submit_async(features))
        sent += 1
        if sent == 50:
            print("READY", flush=True)
        time.sleep(0.001)  # keep a steady stream, not a flood

    drained = frontend.drain(timeout=30.0)
    results = [p.wait(timeout=5.0) for p in pending]
    counts = {}
    for r in results:
        counts[r.status] = counts.get(r.status, 0) + 1
    unanswered = sum(
        1 for r in results if r.status == "deadline_exceeded" and r.error
    )  # _Request.wait timed out = the drain dropped it
    print(
        "DRAINED drained=%s sent=%d counts=%s unanswered=%d"
        % (drained, sent, sorted(counts.items()), unanswered),
        flush=True,
    )
    # Orderly exit: no 5xx, nothing silently dropped, real work served,
    # and everything past the signal was an orderly drain rejection.
    sys.exit(
        0
        if drained
        and counts.get("error", 0) == 0
        and unanswered == 0
        and counts.get("ok", 0) > 0
        else 1
    )


if __name__ == "__main__":
    main()
