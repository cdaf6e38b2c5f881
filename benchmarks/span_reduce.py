"""From the program's own span ring (`adanet_tpu.observability.tracer()`)
to the numbers the metrics read.

Every `Estimator.train` call is one `search` span, entry to return, and
what it did is its children: `resume.fsck`, `input.next_batch`,
`iteration.build`, `iteration.init_state`, `checkpoint.restore`,
`input.place_batch`, `train_window` (the first of an iteration carries
`first=True`), `train.log`, `checkpoint.save` with `checkpoint.fetch` (the
drain of the steps in flight) and `checkpoint.write` inside it. The ring
is process-wide and the benchmark's process trains nothing after the
window, so the LAST `search` span is the window's call and those before
it are set-up's. Structure ties a span to a call (`parent_id`), not its
clock: the ring reads `time.monotonic`, the harness `perf_counter`.
"""

from __future__ import annotations

import json
import statistics
import sys

RESUME = ("resume.fsck", "iteration.build", "iteration.init_state",
          "checkpoint.restore")
STEADY_FROM = 2  # pulls 0 and 1 are the sample batch and its replay


def call(search, children_of):
    """One `search` span -> its phases in seconds (None where the call
    has no such span)."""
    children = sorted(children_of.get(search.span_id, []),
                      key=lambda e: e.start)

    def named(name, parent=children):
        return [e for e in parent if e.name == name]

    def first(events):
        return events[0].end - events[0].start if events else None

    out = {name: first(named(name)) for name in RESUME}
    out["first_step"] = first(
        [e for e in named("train_window") if e.attrs.get("first")]
    )
    pulls = [e.end - e.start for e in named("input.next_batch")]
    out["first_pull"] = pulls[0] if pulls else None
    out["pulls"] = pulls
    saves = named("checkpoint.save")
    inside = sorted(children_of.get(saves[-1].span_id, []),
                    key=lambda e: e.start) if saves else []
    out["fetch"] = first(named("checkpoint.fetch", inside))
    out["write"] = first(named("checkpoint.write", inside))
    out["call"] = search.end - search.start
    by_name = {}
    for event in children:
        by_name[event.name] = (
            by_name.get(event.name, 0.0) + event.end - event.start
        )
    out["by_name"] = by_name
    # What no child names: the call's own lines between its spans.
    out["self"] = out["call"] - sum(by_name.values())
    # After the last save: the flight dump, the replay record, the
    # lease's release, the signal handler's restoration.
    out["after_save"] = search.end - saves[-1].end if saves else None
    return out


def reduce_events(events):
    """{"window": call, "setup": [call, ...]} from a snapshot of the
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
    calls = [call(search, children_of) for search in searches]
    return {"window": calls[-1], "setup": calls[:-1]}


def resume_sum(phases):
    """The five resume spans of one call, absent ones as nothing; None
    where the call has none of them."""
    found = [
        phases[name] for name in RESUME + ("first_step",)
        if phases[name] is not None
    ]
    return sum(found) if found else None


def of_record(record):
    """The reduction of this process's ring, made once and kept in
    `record`."""
    if "span_reduce" not in record:
        from adanet_tpu.observability import tracer

        out = reduce_events(tracer().events())
        record["span_reduce"] = out
        if out:
            report(out, record)
    return record["span_reduce"]


def report(out, record):
    """The identity and the calls, on stderr: what PERF.md quotes."""
    window = out["window"]
    first_pull = record["clock"].get("first_train_pull")
    if first_pull is not None and window["first_pull"] is not None:
        resume_s = first_pull - record["window_start"]
        named = (resume_sum(window) or 0.0) + window["first_pull"]
        print(
            "span_reduce identity: resume spans and first pull %.4f s, "
            "resume_s %.4f s, remainder %.3f%%" % (
                named, resume_s, 100.0 * (resume_s - named) / resume_s,
            ), file=sys.stderr,
        )

    def short(phases):
        return {k: v for k, v in phases.items() if k != "pulls"}

    print("span_reduce %s" % json.dumps({
        "window": short(window),
        "setup": [short(phases) for phases in out["setup"]],
        "window_pulls": len(window["pulls"]),
    }), file=sys.stderr)


def window(record, name):
    out = of_record(record)
    return out["window"][name] if out else None


def window_spans(record):
    """The window call's spans whose seconds grow with the state (what
    an untraced run prints, so that a set of runs shows WHICH second
    varied), the log line's wait and the window's length; None where the
    ring holds no call."""
    out = of_record(record)
    if not out:
        return None
    phases = out["window"]
    return {
        "resume.fsck": phases["resume.fsck"],
        "checkpoint.restore": phases["checkpoint.restore"],
        "train_window.first": phases["first_step"],
        "train.log": phases["by_name"].get("train.log"),
        "checkpoint.fetch": phases["fetch"],
        "checkpoint.write": phases["write"],
        "window_s": record["window_end"] - record["window_start"],
    }


def input_wait_ms(record):
    out = of_record(record)
    steady = out["window"]["pulls"][STEADY_FROM:] if out else []
    return statistics.median(steady) * 1e3 if steady else None


def setup(record, total):
    """A sum over set-up's calls, None where there were none."""
    out = of_record(record)
    found = [total(phases) for phases in out["setup"]] if out else []
    found = [value for value in found if value is not None]
    return sum(found) if found else None
