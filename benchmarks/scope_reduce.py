"""Device time of the step program by `jax.named_scope`, from the raw
`.xplane.pb`.

`jax.profiler.ProfileData` gives events and their own stats, not the stats
of an event's METADATA, and that is where the TPU profiler puts what
locates an operation in the program: `tf_op` (the HLO `op_name`, so the
`named_scope`s of `core/iteration.py` and Flax's module path beneath
them), `hlo_category`, `program_id`. So this file parses the proto itself,
through a descriptor of the seven messages built here with
`google.protobuf` (importing TensorFlow's `xplane_pb2` costs nine seconds
and a second runtime in the process that holds the chip).

A `tf_op` reads `jit(adanet_train_step)/jit(main)/transpose(jvp(
candidate.NAME))/NasNetA/cell_7/block0_left_sep/depthwise_0/conv...:`.
`split` strips the wrappers into a direction and a path; `group` gives
the path's group: candidate forward, candidate backward, optimizer,
ensemble (with the frozen members), or unscoped. Device time is the
operation's SELF time on the "XLA Ops" line (an operation that holds
others, a `while`, is charged what its children leave), summed inside
the steady span `trace_reduce` defines (first to last start of the step
program) over the operations of that program, and divided by its whole
steps: so the five groups sum to the step's busy time.

What it cannot see: XLA fuses across module boundaries, and a fusion
carries the `op_name` of ONE of its operations (its root); a batch norm
fused into the convolution before it is charged to the convolution.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re
import statistics
import sys

from benchmarks import trace_reduce

GROUPS = ("candidate_fwd", "candidate_bwd", "optimizer", "ensemble",
          "unscoped")
# The spans of `adanet_tpu/core/estimator.py` that a traced step shows.
HOST_SPANS = ("input.next_batch", "input.place_batch", "train_window")
# Components that only say how the program was called, not where in it.
_CALL_WRAPPERS = ("jit", "pjit", "closed_call", "core_call", "remat",
                  "checkpoint", "custom_jvp_call", "custom_vjp_call")
_LOOP_WORDS = ("while", "body", "cond", "scan", "branch")
_WRAPPED = re.compile(r"^([A-Za-z_][A-Za-z0-9_]*)\((.*)\)$")
_BN = r"bn_\d+$|.*_bn\d*$"
_KINDS = (  # (kind, test on the path's components); the first that holds
    ("aux_head", lambda parts: "aux_head" in parts),
    ("sep.bn", lambda parts: _has(parts, r".*_sep$") and _has(parts, _BN)),
    ("sep.conv", lambda parts: _has(parts, r".*_sep$")),
    ("factorized_reduction",
     lambda parts: _has(parts, r"reduce_prev$|reduction_\d+$")),
    ("batch_norm", lambda parts: _has(parts, _BN)),
    ("1x1", lambda parts: _has(parts, r".*_1x1$")),
    ("pool", lambda parts: _has(
        parts[-1:], r"reduce_window.*|select_and_scatter.*|.*_pool$")),
)


def _has(parts, pattern):
    return any(re.match(pattern, part) for part in parts)


# ------------------------------------------------------------- the proto


@functools.lru_cache(maxsize=None)
def _messages():
    """The class of `XSpace`, with its parts (tsl `xplane.proto`), from
    a descriptor built here in a pool of its own."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "string": F.TYPE_STRING, "bytes": F.TYPE_BYTES,
              "double": F.TYPE_DOUBLE}
    package = "adanet_bench_xplane"
    file = descriptor_pb2.FileDescriptorProto(
        name=package + ".proto", package=package, syntax="proto3"
    )

    def message(name, fields, oneof=None, maps=()):
        msg = file.message_type.add(name=name)
        if oneof:
            msg.oneof_decl.add(name=oneof[0])
        for number, field, kind, repeated in fields:
            f = msg.field.add(
                name=field, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
            )
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, ".%s.%s" % (
                    package, kind)
            if oneof and field in oneof[1]:
                f.oneof_index = 0
        for number, field, value in maps:
            entry = msg.nested_type.add(
                name="".join(w.title() for w in field.split("_")) + "Entry"
            )
            entry.options.map_entry = True
            entry.field.add(name="key", number=1, type=F.TYPE_INT64,
                            label=F.LABEL_OPTIONAL)
            entry.field.add(
                name="value", number=2, type=F.TYPE_MESSAGE,
                type_name=".%s.%s" % (package, value),
                label=F.LABEL_OPTIONAL,
            )
            msg.field.add(
                name=field, number=number, type=F.TYPE_MESSAGE,
                label=F.LABEL_REPEATED,
                type_name=".%s.%s.%s" % (package, name, entry.name),
            )

    message("XStat", [
        (1, "metadata_id", "int64", False),
        (2, "double_value", "double", False),
        (3, "uint64_value", "uint64", False),
        (4, "int64_value", "int64", False),
        (5, "str_value", "string", False),
        (6, "bytes_value", "bytes", False),
        (7, "ref_value", "uint64", False),
    ], oneof=("value", ("double_value", "uint64_value", "int64_value",
                        "str_value", "bytes_value", "ref_value")))
    message("XEvent", [
        (1, "metadata_id", "int64", False),
        (2, "offset_ps", "int64", False),
        (5, "num_occurrences", "int64", False),
        (3, "duration_ps", "int64", False),
        (4, "stats", "XStat", True),
    ], oneof=("data", ("offset_ps", "num_occurrences")))
    message("XLine", [
        (1, "id", "int64", False),
        (10, "display_id", "int64", False),
        (2, "name", "string", False),
        (11, "display_name", "string", False),
        (3, "timestamp_ns", "int64", False),
        (9, "duration_ps", "int64", False),
        (4, "events", "XEvent", True),
    ])
    message("XEventMetadata", [
        (1, "id", "int64", False),
        (2, "name", "string", False),
        (4, "display_name", "string", False),
        (3, "metadata", "bytes", False),
        (5, "stats", "XStat", True),
        (6, "child_id", "int64", True),
    ])
    message("XStatMetadata", [
        (1, "id", "int64", False),
        (2, "name", "string", False),
        (3, "description", "string", False),
    ])
    message("XPlane", [
        (1, "id", "int64", False),
        (2, "name", "string", False),
        (3, "lines", "XLine", True),
        (6, "stats", "XStat", True),
    ], maps=[(4, "event_metadata", "XEventMetadata"),
             (5, "stat_metadata", "XStatMetadata")])
    message("XSpace", [
        (1, "planes", "XPlane", True),
        (2, "errors", "string", True),
        (3, "warnings", "string", True),
        (4, "hostnames", "string", True),
    ])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(package + ".XSpace")
    )


def load_space(path):
    space = _messages()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stat_values(plane, stats, wanted):
    out = {}
    for stat in stats:
        name = plane.stat_metadata[stat.metadata_id].name
        if name not in wanted:
            continue
        which = stat.WhichOneof("value")
        value = getattr(stat, which) if which else None
        if which == "ref_value":
            value = plane.stat_metadata[value].name
        out[name] = value
    return out


def plane_lines(plane):
    """{line name: [event]} with each event a dict of `name`, `start`
    and `end` in ns on the trace's own axis, and the metadata's `tf_op`,
    `hlo_category` and `program_id`."""
    wanted = ("tf_op", "hlo_category", "program_id")
    metadata = {}
    lines = {}
    for line in plane.lines:
        events = []
        for event in line.events:
            if event.metadata_id not in metadata:
                meta = plane.event_metadata[event.metadata_id]
                metadata[event.metadata_id] = dict(
                    _stat_values(plane, meta.stats, wanted),
                    name=meta.name,
                )
            start = line.timestamp_ns + event.offset_ps * 1e-3
            events.append(dict(
                metadata[event.metadata_id], start=start,
                end=start + event.duration_ps * 1e-3,
            ))
        lines[line.name] = events
    return lines


# ------------------------------------------------------------- the names


def _components(tf_op):
    """`a/f(b/c)/d` -> [`a`, `f(b/c)`, `d`]: split at the slashes outside
    any bracket."""
    parts, depth, current = [], 0, []
    for char in tf_op:
        if char == "/" and depth == 0:
            parts.append("".join(current))
            current = []
            continue
        depth += char == "("
        depth -= char == ")"
        current.append(char)
    parts.append("".join(current))
    return [part for part in parts if part]


def split(tf_op):
    """(direction, path) of one `tf_op`: direction is "backward" under a
    `transpose(...)`, "forward" under a `jvp(...)` alone, else None; path
    is the components with every wrapper taken off, calls (`jit(main)`)
    and loop words (`while/body`) before the first scope left out."""
    direction, path = None, []
    for part in _components((tf_op or "").rsplit(":", 1)[0]):
        wrappers = []
        while True:
            match = _WRAPPED.match(part)
            if not match:
                break
            wrappers.append(match.group(1))
            part = match.group(2)
        if "transpose" in wrappers:
            direction = "backward"
        elif "jvp" in wrappers and direction is None:
            direction = "forward"
        if any(w in _CALL_WRAPPERS for w in wrappers):
            continue
        if not path and part in _LOOP_WORDS + _CALL_WRAPPERS:
            continue
        # A wrapper may hold a path of its own: `jvp(a/b)`.
        path.extend(_components(part))
    return direction, path


def group(direction, path):
    """One of GROUPS for a split `tf_op`: by the first component that is
    a scope of `core/iteration.py`."""
    for part in path:
        if part.startswith("candidate."):
            return (
                "candidate_bwd" if direction == "backward"
                else "candidate_fwd"
            )
        if part.startswith(("optimizer.", "ensemble_optimizer.")):
            return "optimizer"
        if part.startswith(("ensemble.", "frozen.")):
            return "ensemble"
        if part == "step.metrics":
            return "unscoped"
    return "unscoped"


def kind(path):
    """The branch kind of a NASNet module path, for PERF.md's table."""
    for name, test in _KINDS:
        if test(path):
            return name
    return "other"


def _module_path(path, depth=3):
    """The path beneath the scope, cut at `depth` modules, which is the
    cell for improve_nas (`_NasNetSubnetworkModule/nasnet/cell_7`); the
    scope itself where nothing is beneath."""
    for index, part in enumerate(path):
        if part.startswith("candidate."):
            return "/".join(path[index + 1:-1][:depth]) or part
    return "(no scope)"


# ----------------------------------------------------------- the numbers


def self_times(events):
    """[(event, self ns)]: each event's duration less what the events
    inside it take (one line of one core: events nest, never cross)."""
    out, stack = [], []
    for event in sorted(events, key=lambda e: (e["start"], -e["end"])):
        while stack and stack[-1][0]["end"] <= event["start"]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= event["end"] - event["start"]
        stack.append([event, event["end"] - event["start"]])
    out.extend(tuple(item) for item in stack)
    return out


def reduce_rows(rows, lo, hi, steps):
    """`rows`: events of the step program (dicts with `tf_op`, `start`,
    `end`); the steady span [lo, hi) holds `steps` whole steps. Returns
    None where no row carries a scope of the program."""
    groups = dict.fromkeys(GROUPS, 0.0)
    sepconv, scoped = 0.0, False
    modules, kinds, directions, unscoped = {}, {}, {}, {}
    for event, self_ns in self_times(rows):
        if not lo <= event["start"] < hi:
            continue
        direction, path = split(event.get("tf_op"))
        name = group(direction, path)
        scoped = scoped or name != "unscoped" or "step.metrics" in path
        ms = self_ns * 1e-6 / steps
        groups[name] += ms
        if name.startswith("candidate"):
            branch = kind(path)
            if branch.startswith("sep."):
                sepconv += ms
            for table, key in (
                (modules, _module_path(path)),
                (kinds, branch),
                (directions, "%s %s" % (branch, direction)),
            ):
                table[key] = table.get(key, 0.0) + ms
        elif name == "unscoped":
            key = "%s: %s" % (
                event.get("hlo_category") or "(no category)",
                "/".join(path[:2]) or "(no tf_op)",
            )
            unscoped[key] = unscoped.get(key, 0.0) + ms
    if not scoped:
        return None

    def largest(table, count):
        return sorted(table.items(), key=lambda kv: -kv[1])[:count]

    return {
        "groups_ms": groups, "sepconv_ms": sepconv,
        "total_ms": sum(groups.values()),
        "modules_ms": largest(modules, 10),
        "kinds_ms": largest(kinds, 10),
        "kinds_by_direction_ms": largest(directions, 20),
        "unscoped_ms": largest(unscoped, 10),
    }


def reduce_file(path):
    """The lead device's step program by scope, and the program's own
    host spans read back from the same trace."""
    space = load_space(path)
    best, host = None, []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = plane_lines(plane)
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
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    name = plane.event_metadata[event.metadata_id].name
                    name = name.split("#", 1)[0]
                    if name in HOST_SPANS:
                        start = line.timestamp_ns + event.offset_ps * 1e-3
                        host.append(
                            (name, start, start + event.duration_ps * 1e-3)
                        )
    out = {"host_spans": None, "dispatch_lead_ms": None, "scopes": None}
    if best is None:
        return out
    lead, modules, ops = best
    # The program's spans on the device lanes' own axis. The host runs
    # ahead of the chip: it has dispatched every traced step before the
    # first of them starts, so a span counts if it starts before the
    # device's last op ends, and the lead says by how much it runs ahead.
    out["host_spans"] = {
        name: sum(
            1 for span, start, _ in host
            if span == name and start <= lead["last_ns"]
        )
        for name in HOST_SPANS
    }
    dispatches = [start for span, start, _ in host if span == "train_window"]
    out["dispatch_lead_ms"] = (
        (lead["first_ns"] - min(dispatches)) * 1e-6 if dispatches else None
    )
    starts = sorted(s for name, s, _ in modules
                    if name == lead["step_program"])
    match = re.search(r"\((\d+)\)$", lead["step_program"])
    program = int(match.group(1)) if match else None
    rows = [
        e for e in ops
        if program is None or e.get("program_id") in (None, program)
    ]
    out["scopes"] = reduce_rows(rows, starts[0], starts[-1], lead["steps"])
    out["step_device_ms"] = statistics.median(lead["step_runs_s"]) * 1e3
    return out


def newest_trace(trace_dir):
    """The `.xplane.pb` of this process's traced steps, which `run.py`
    keeps in `record["trace_dir"]` until the metrics are read."""
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def of_record(record):
    """The reduction of this run's trace, parsed once and kept in
    `record`; None where the run was not traced or has no device plane."""
    if "scope_reduce" not in record:
        trace = record.get("trace") or {}
        path = (
            newest_trace(record["trace_dir"])
            if trace.get("device_planes") else None
        )
        out = reduce_file(path) if path else None
        record["scope_reduce"] = out
        if out:
            report(out)
    return record["scope_reduce"]


def report(out):
    """The identity and the tables, on stderr: what PERF.md quotes."""
    scopes = out.get("scopes")
    if scopes:
        device = out["step_device_ms"]
        print(
            "scope_reduce identity: groups sum %.4f ms, step_device_ms "
            "%.4f ms, gap %.3f%%" % (
                scopes["total_ms"], device,
                100.0 * (scopes["total_ms"] - device) / device,
            ), file=sys.stderr,
        )
    if out.get("host_spans"):
        print(
            "scope_reduce host plane: %s beside the device lanes; the first "
            "traced step was dispatched %s ms before the chip started it" % (
                ", ".join(
                    "%d %s" % (count, name)
                    for name, count in out["host_spans"].items()
                ), out["dispatch_lead_ms"],
            ), file=sys.stderr,
        )
    print("scope_reduce %s" % json.dumps(out), file=sys.stderr)


def group_ms(record, name):
    out = of_record(record)
    if not out or not out["scopes"]:
        return None
    return out["scopes"]["groups_ms"][name]
