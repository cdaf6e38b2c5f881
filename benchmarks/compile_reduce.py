"""From the program's own span ring (`adanet_tpu.observability.tracer()`)
to what set-up's `Estimator.train` calls spent on their compile path.

`core/compile_cache.py` makes every step program's compile one `compile`
span whose children are its stages: `compile.trace`, `compile.lower`,
`compile.key`, then `compile.store_load` (with a store), `compile.backend`
(XLA's compile or its persistent cache's load) and `compile.store_save`.
JAX's own compile work anywhere else lands as attributes on the span that
caused it (`observability/spans.py`): templates, the real init's one-op
programs, a builder's `jit_init`, the log line's programs. `jax.s`, the
union of its traces, lowerings and backend compiles, is what this reads:
JAX reports some traces from inside a lowering and some eager compiles
from inside a trace, so the sum of the per-kind unions (`jax.trace_s`,
`jax.lower_s`, `jax.compile_s`) would count that work twice.

As in `span_reduce.py`, the LAST `search` span of the process is the
window's call and those before it are set-up's; a span belongs to a call
by structure (`parent_id`), never by clock. The `compile` spans lie
beneath `train_window` (or `iteration.init_state`), never directly under
`search`, so `span_reduce`'s reading of a call's children is unchanged.
"""

from __future__ import annotations

import json
import sys

#: Stage of the step programs' compile path -> the `compile` children
#: that make it.
STAGES = {
    "trace": ("compile.trace",),
    "lower": ("compile.lower",),
    "key": ("compile.key",),
    "load": ("compile.backend", "compile.store_load"),
}
JAX_SECONDS = "jax.s"


def _seconds(event):
    return event.end - event.start


def call(search, children_of):
    """One `search` span -> its compile path: each stage's seconds over
    its `compile` spans, their seconds and the least share of one that
    its children cover; JAX's seconds on its spans outside every
    `compile` span (None where no span carries any), in all and by span
    name; its first `train_window` and the `compile` spans beneath it."""
    stages = dict.fromkeys(STAGES, 0.0)
    compiles = []
    other, other_by_name = None, {}
    first_window = min(
        (e for e in children_of.get(search.span_id, [])
         if e.name == "train_window" and e.attrs.get("first")),
        key=lambda e: e.start, default=None,
    )
    beneath_first = 0.0
    pending = [(search, False)]
    while pending:
        event, under_first = pending.pop()
        under_first = under_first or event is first_window
        if event.name == "compile":
            compiles.append(event)
            if under_first:
                beneath_first += _seconds(event)
            for stage in children_of.get(event.span_id, []):
                for name, parts in STAGES.items():
                    if stage.name in parts:
                        stages[name] += _seconds(stage)
            continue  # JAX's work inside is the stages' own
        found = event.attrs.get(JAX_SECONDS)
        if found is not None:
            other = (other or 0.0) + found
            other_by_name[event.name] = (
                other_by_name.get(event.name, 0.0) + found
            )
        pending.extend(
            (child, under_first)
            for child in children_of.get(event.span_id, [])
        )
    covered = [
        sum(_seconds(e) for e in children_of.get(span.span_id, []))
        / _seconds(span)
        for span in compiles if _seconds(span) > 0
    ]
    return {
        "compiles": len(compiles),
        "compile_s": sum(_seconds(e) for e in compiles),
        "least_covered": min(covered, default=None),
        "stages": stages if compiles else None,
        "outcomes": sorted(
            "%s:%s" % (e.attrs.get("program"), e.attrs.get("outcome"))
            for e in compiles
        ),
        "jax_other": other,
        "jax_other_by_name": other_by_name,
        "first_window": None if first_window is None
        else _seconds(first_window),
        "first_window_compile": beneath_first,
    }


def reduce_events(events):
    """{"setup": [call, ...]} of set-up's calls, from a snapshot of the
    ring; None where it holds no `search` span."""
    children_of = {}
    for event in events:
        children_of.setdefault(event.parent_id, []).append(event)
    searches = sorted(
        (e for e in events if e.name == "search" and e.end > e.start),
        key=lambda e: e.start,
    )
    if not searches:
        return None
    return {"setup": [call(s, children_of) for s in searches[:-1]]}


def of_record(record):
    """The reduction of this process's ring, made once and kept in
    `record`."""
    if "compile_reduce" not in record:
        from adanet_tpu.observability import tracer

        out = reduce_events(tracer().events())
        record["compile_reduce"] = out
        if out:
            report(out)
    return record["compile_reduce"]


def report(out):
    """The first windows' identities and the calls, on stderr: what
    PERF.md quotes."""
    for number, phases in enumerate(out["setup"], 1):
        window = phases["first_window"]
        if not window or not phases["compiles"]:
            continue
        named = phases["first_window_compile"]
        print(
            "compile_reduce identity: set-up call %d first train_window "
            "%.4f s, compile spans %.4f s, remainder %.3f%%" % (
                number, window, named, 100.0 * (window - named) / window,
            ), file=sys.stderr,
        )
    print("compile_reduce %s" % json.dumps(out), file=sys.stderr)


def setup(record, stage):
    """A stage's seconds summed over set-up's calls (`jax_other` for JAX's
    work outside the `compile` spans); None where no call has any."""
    out = of_record(record)
    found = []
    for phases in out["setup"] if out else []:
        if stage == "jax_other":
            found.append(phases["jax_other"])
        elif phases["stages"] is not None:
            found.append(phases["stages"][stage])
    found = [value for value in found if value is not None]
    return sum(found) if found else None
