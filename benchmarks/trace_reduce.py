"""From the profiler's `.xplane.pb` to the numbers the metrics read.

Started from `adanet_tpu/utils/device_timing.py::trace_device_seconds`
(the sum of the "XLA Modules" lane), and extended: the union of the device
operations' intervals, the idle share of a steady span, device time per
program, the operations with most time, and the longest idle gaps named by
the benchmark's own host annotation that covers them. Read with
`jax.profiler.ProfileData` alone.

The steady span runs from the start of the first to the start of the last
execution of the step program (the program with most device time), so it
holds whole steps and neither resume nor save.
"""

from __future__ import annotations

import glob
import os

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ANNOTATIONS = ("pull_batch", "train_call", "harness_barrier")


def _events(line):
    # A device operation is named by its whole HLO line: keep the name.
    return [
        (
            e.name.split(" = ")[0].lstrip("%")[:120],
            float(e.start_ns),
            float(e.start_ns + e.duration_ns),
        )
        for e in line.events
    ]


def union(intervals, lo=None, hi=None):
    """Merged, sorted intervals, clipped to [lo, hi]."""
    merged = []
    for start, end in sorted(intervals):
        if lo is not None:
            start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _covering(annotations, at):
    for name, start, end in annotations:
        if start <= at <= end:
            return name
    return "host_other"


def reduce_plane(modules, ops, annotations):
    """One device's lanes -> busy time, steady span and breakdown.

    `modules`, `ops`, `annotations`: lists of (name, start_ns, end_ns).
    """
    out = {"step_program": None, "steps": 0}
    work = ops or modules
    if not work:
        return out
    lo = min(s for _, s, _ in work)
    hi = max(e for _, _, e in work)
    busy = union([(s, e) for _, s, e in work])
    out["busy_s"] = sum(e - s for s, e in busy) * 1e-9
    out["first_ns"], out["last_ns"] = lo, hi

    per_program = {}
    for name, start, end in modules:
        per_program.setdefault(name, []).append((start, end))
    out["program_s"] = {
        name: sum(e - s for s, e in runs) * 1e-9
        for name, runs in per_program.items()
    }
    if per_program:
        step = max(out["program_s"], key=out["program_s"].get)
        runs = sorted(per_program[step])
        out["step_program"] = step
        out["step_runs_s"] = [(e - s) * 1e-9 for s, e in runs]
        if len(runs) >= 2:
            lo, hi = runs[0][0], runs[-1][0]
            inside = union([(s, e) for _, s, e in work], lo, hi)
            out["steps"] = len(runs) - 1
            out["span_s"] = (hi - lo) * 1e-9
            out["span_busy_s"] = sum(e - s for s, e in inside) * 1e-9
            gaps, cursor = [], lo
            for start, end in inside + [[hi, hi]]:
                if start > cursor:
                    gaps.append((start - cursor, (start + cursor) / 2.0))
                cursor = max(cursor, end)
            out["idle_gaps"] = [
                [_covering(annotations, mid), length * 1e-9]
                for length, mid in sorted(gaps, reverse=True)[:5]
            ]
    totals = {}
    for name, start, end in work:
        totals[name] = totals.get(name, 0.0) + (end - start) * 1e-9
    out["device_ops"] = [
        [name, seconds]
        for name, seconds in sorted(
            totals.items(), key=lambda kv: kv[1], reverse=True
        )[:10]
    ]
    return out


def reduce_file(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations, host_lo, host_hi = [], [], None, None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lanes = {line.name: _events(line) for line in plane.lines}
            devices.append(
                (plane.name, lanes.get(MODULE_LINE, []),
                 lanes.get(OP_LINE, []))
            )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, start, end in _events(line):
                    host_lo = start if host_lo is None else min(host_lo, start)
                    host_hi = end if host_hi is None else max(host_hi, end)
                    if name in ANNOTATIONS:
                        annotations.append((name, start, end))
    planes = [
        dict(reduce_plane(modules, ops, annotations), plane=name)
        for name, modules, ops in devices
    ]
    planes = [p for p in planes if "busy_s" in p]
    out = {"device_planes": [p["plane"] for p in planes], "planes": planes}
    if planes:
        lo = min([p["first_ns"] for p in planes] + [host_lo or float("inf")])
        hi = max([p["last_ns"] for p in planes] + [host_hi or 0.0])
        out["busy_s"] = sum(p["busy_s"] for p in planes) / len(planes)
        out["window_s"] = (hi - lo) * 1e-9
        lead = max(planes, key=lambda p: p["busy_s"])
        out["lead"] = lead
        out["breakdown"] = {
            "device_ops": lead["device_ops"],
            "idle_gaps": lead.get("idle_gaps", []),
        }
    return out


def reduce_dir(trace_dir):
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not paths:
        return {"device_planes": [], "planes": []}
    return reduce_file(paths[-1])
