"""Device time of a language-model candidate's step by its own scopes,
from the traced run's `.xplane.pb`, over `scope_reduce`'s public
functions.

`adanet_tpu/models/moe_lm.py` and `core/heads.py` open `jax.named_scope`s
beneath `candidate.*` (and, for the loss blocks, beneath `ensemble.*`
too): `lm.attention` (norm, projections, RoPE, and `lm.attention_core`
within it: scores, softmax, values), `lm.moe_route` (norm, router, top-k,
sort, gather, scatter-add), `lm.moe_experts` (the grouped products),
`lm.loss` (final norm, head) and, around the mixture and loss blocks of
`BlockedLogits.reduce_rows`, `blocked_logits`. An operation counts
under the INNERMOST of them on its path, forward, recomputed or backward
alike; SELF time inside `trace_reduce`'s steady span, a step, as
`scope_reduce` counts its groups. A program without these scopes (a parent
commit, another model) reads as nothing.
"""

from __future__ import annotations

import json
import re
import sys

from benchmarks import scope_reduce, trace_reduce

SCOPES = ("lm.attention_core", "lm.attention", "lm.moe_route",
          "lm.moe_experts", "lm.loss", "blocked_logits")


def scope_of(path):
    for part in reversed(path):
        if part in SCOPES:
            return part
    return None


def _rows(path):
    """(rows of the lead device's step program, lo, hi, steps) as
    `scope_reduce.reduce_file` chooses them."""
    best = None
    for plane in scope_reduce.load_space(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = scope_reduce.plane_lines(plane)
        modules = [
            (e["name"], e["start"], e["end"])
            for e in lines.get(trace_reduce.MODULE_LINE, [])
        ]
        ops = lines.get(trace_reduce.OP_LINE, [])
        lead = trace_reduce.reduce_plane(
            modules, [(e["name"], e["start"], e["end"]) for e in ops], []
        )
        if lead.get("steps") and (
            best is None or lead["busy_s"] > best[0]["busy_s"]
        ):
            best = (lead, modules, ops)
    if best is None:
        return None
    lead, modules, ops = best
    starts = sorted(
        s for name, s, _ in modules if name == lead["step_program"]
    )
    match = re.search(r"\((\d+)\)$", lead["step_program"])
    program = int(match.group(1)) if match else None
    rows = [
        e for e in ops
        if program is None or e.get("program_id") in (None, program)
    ]
    return rows, starts[0], starts[-1], lead["steps"]


def reduce_file(path):
    """{"scopes_ms": {scope: ms a step}, "ops_ms": the 60 largest (scope,
    category, operation) by ms a step}, or None where no row carries a scope."""
    found = _rows(path)
    if found is None:
        return None
    rows, lo, hi, steps = found
    scopes, ops = dict.fromkeys(SCOPES, 0.0), {}
    seen = False
    for event, self_ns in scope_reduce.self_times(rows):
        if not lo <= event["start"] < hi:
            continue
        _, parts = scope_reduce.split(event.get("tf_op"))
        scope = scope_of(parts)
        ms = self_ns * 1e-6 / steps
        key = "%s: %s %s" % (
            scope or "(none)", event.get("hlo_category"),
            event["name"].split(" ")[0] if scope is None
            else event["name"].split(".")[0],
        )
        ops[key] = ops.get(key, 0.0) + ms
        if scope is not None:
            seen = True
            scopes[scope] += ms
    if not seen:
        return None
    return {
        "scopes_ms": scopes,
        "ops_ms": sorted(ops.items(), key=lambda kv: -kv[1])[:60],
    }


def of_record(record):
    """The reduction of this run's trace, parsed once and kept in
    `record`; None where the run was not traced, has no device plane or
    ran a program without the scopes."""
    if "lm_reduce" not in record:
        trace = record.get("trace") or {}
        path = (
            scope_reduce.newest_trace(record["trace_dir"])
            if trace.get("device_planes") else None
        )
        out = reduce_file(path) if path else None
        record["lm_reduce"] = out
        if out:
            print("lm_reduce %s" % json.dumps(out), file=sys.stderr)
    return record["lm_reduce"]


def scope_ms(record, *scopes):
    out = of_record(record)
    if not out:
        return None
    return sum(out["scopes_ms"][scope] for scope in scopes)


_CELL = []


def note_cell(cell):
    """The cell's check says which cell this process runs: a record does
    not hold it, and a roofline counts its work from the cell's sizes."""
    _CELL[:] = [cell]


def noted():
    """(the training member's sizes, the traffic) of the noted cell, or
    None."""
    if not _CELL:
        return None
    cell = _CELL[0]
    (name,) = cell.cell["training"]
    return cell.members[name]["sizes"], cell.traffic


def roofline(record, scope, work):
    """The larger of the FLOP and the byte share of the chip's peaks that
    `work` = (FLOP, bytes) a step is of the scope's device time, %."""
    ms = scope_ms(record, scope)
    if not ms or not record["peaks"]:
        return None
    flops, moved = work
    seconds = ms * 1e-3
    return 100.0 * max(
        flops / seconds / record["peaks"]["bf16_flops_per_s"],
        moved / seconds / record["peaks"]["hbm_bytes_per_s"],
    )
