"""Counts a language-model configuration's FLOP and bytes, from its sizes.

    python -m benchmarks.count_lm_flops <config> <traffic>

`forward_flops` is the model FLOP of one example's (one sequence's)
forward pass under BALANCED routing: every token's `k` pairs spread evenly,
so the held experts see `k * held / E` pairs a token (uniform ids give that
within about 1%; a cell's check reads the load it met). Counted: the
attention projections of the held heads, scores and values over the keys
each query may see (causal, and the window where a layer slides), the
whole router, the held experts' three products a pair, the head over the
held ids; a multiply-add is 2. The number goes into the configuration's
file (`flops_forward_per_example`) with the commit it was counted at; a
step's model FLOP is batch x 3 x it, recomputation not counted.
`tests/test_moe_lm.py` holds it against the lowered reference forward.

`experts_work` and `attention_core_work` give, for one STEP, the FLOP and
the least HBM bytes of the work of the two kernels that the roofline
metrics read: whatever implements them, the work is this.
"""

from __future__ import annotations

import sys


def visible_keys(seq, window=None):
    """Sum over queries of the keys each may see."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _layer_window(sizes, kind):
    return sizes["sliding_window"] if kind == "sliding_attention" else None


def pairs_per_token(sizes):
    """Token-expert pairs on the held experts, a token, balanced."""
    return (
        sizes["num_experts_per_tok"] * sizes["experts_held"][1]
        / sizes["router_width"]
    )


def attention_core_flops(sizes, seq):
    """Scores and values of one sequence's forward, all layers."""
    per_key = 4 * sizes["head_dim"] * sizes["num_heads"]
    return sum(
        per_key * visible_keys(seq, _layer_window(sizes, kind))
        for kind in sizes["layer_types"]
    )


def forward_flops(sizes, vocab, seq):
    """Model FLOP of one sequence's forward."""
    hidden, depth = sizes["hidden_size"], sizes["head_dim"]
    projections = 2 * hidden * depth * (
        2 * sizes["num_heads"] + 2 * sizes["num_kv_heads"]
    )
    router = 2 * hidden * sizes["router_width"]
    experts = pairs_per_token(sizes) * 3 * 2 * hidden * sizes["expert_width"]
    a_token = len(sizes["layer_types"]) * (
        projections + router + experts
    ) + 2 * hidden * vocab
    return int(round(seq * a_token + attention_core_flops(sizes, seq)))


def experts_work(sizes, tokens):
    """(FLOP, bytes) of the grouped products of one STEP (forward and both
    gradients: 3 x forward FLOP). Bytes: each product's operands read and
    result written once in bfloat16, three passes."""
    hidden, width = sizes["hidden_size"], sizes["expert_width"]
    layers, held = len(sizes["layer_types"]), sizes["experts_held"][1]
    pairs = tokens * pairs_per_token(sizes)
    flops = 3 * layers * pairs * 3 * 2 * hidden * width
    forward_bytes = 2 * (
        2 * pairs * hidden  # the rows in, the rows out
        + 3 * pairs * width  # gate and up out, their product in
        + 3 * held * hidden * width  # the kernels
    )
    return flops, 3 * layers * forward_bytes


def attention_core_work(sizes, batch, seq):
    """(FLOP, bytes) of scores, softmax and values of one STEP. Bytes: q,
    k, v read and the output written once in bfloat16, three passes."""
    flops = 3 * batch * attention_core_flops(sizes, seq)
    depth = sizes["head_dim"]
    forward_bytes = 2 * batch * seq * depth * (
        2 * sizes["num_heads"] + 2 * sizes["num_kv_heads"]
    )
    return flops, 3 * len(sizes["layer_types"]) * forward_bytes


def main(argv):
    from benchmarks.run import load_json

    config, traffic = load_json("configs", argv[1]), load_json(
        "traffic", argv[2]
    )
    for name, member in config["members"].items():
        print(name, forward_flops(
            member["sizes"], config["sizes"]["vocab_size"], traffic["seq"]
        ))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
