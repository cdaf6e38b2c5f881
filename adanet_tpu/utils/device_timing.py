"""Device-clock step timing via the JAX profiler's XLA Modules lane.

A host wall clock around asynchronous dispatch measures the enqueue, not
the device. Profiler traces carry the DEVICE's own execution timeline:
the "XLA Modules" lane records one event
per executable dispatch with its on-device duration. Summing that lane
yields timing that is self-consistent with hardware limits (validated
against a peak-bound 4096^3 bf16 matmul chain: ~707 us/step measured vs
~700 us ideal on TPU v5e — ~99% MFU, exactly where a pure matmul lands).

Used by bench.py for honest MFU accounting.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
from typing import Callable, Optional, Tuple


def trace_device_seconds(trace_dir: str) -> Tuple[float, int]:
    """Total device-execution seconds and dispatch count in a trace.

    Reads the chrome-trace export the profiler writes and sums the
    duration of every event on a device process's "XLA Modules" lane
    (one event per executable dispatch on device).
    """
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    if not paths:
        raise FileNotFoundError(
            "No trace.json.gz under %s; profiler produced no trace."
            % trace_dir
        )
    data = json.loads(gzip.open(sorted(paths)[-1]).read())
    events = data.get("traceEvents", [])
    device_pids = set()
    module_lanes = set()
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name" and "device:" in str(
            e.get("args", {}).get("name", "")
        ):
            device_pids.add(e["pid"])
        if e.get("name") == "thread_name" and e.get("args", {}).get(
            "name"
        ) == "XLA Modules":
            module_lanes.add((e["pid"], e["tid"]))
    total_us = 0.0
    count = 0
    for e in events:
        if e.get("ph") != "X":
            continue
        key = (e.get("pid"), e.get("tid"))
        if key in module_lanes and e.get("pid") in device_pids:
            total_us += float(e.get("dur", 0.0))
            count += 1
    return total_us * 1e-6, count


def time_steps_on_device(
    run_steps: Callable[[], None],
    expected_dispatches: Optional[int] = None,
) -> Tuple[float, int]:
    """Profiles `run_steps()` and returns (device_seconds, dispatches).

    `run_steps` must block until its work completes (block_until_ready).
    When `expected_dispatches` is given and the trace shows a different
    dispatch count, a ValueError explains the discrepancy (e.g. stray
    compilation inside the profiled window).
    """
    import shutil

    import jax

    trace_dir = tempfile.mkdtemp(prefix="adanet_device_timing_")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            run_steps()
        finally:
            jax.profiler.stop_trace()
        seconds, count = trace_device_seconds(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if count == 0 or seconds <= 0.0:
        raise ValueError(
            "Trace recorded no device-lane executable events (e.g. CPU "
            "backend traces have no XLA Modules device lane); use a host "
            "clock instead."
        )
    if expected_dispatches is not None and count != expected_dispatches:
        raise ValueError(
            "Profiled window recorded %d device dispatches, expected %d; "
            "warm the executable up before timing (stray compiles or "
            "helper programs pollute the module lane)."
            % (count, expected_dispatches)
        )
    return seconds, count
