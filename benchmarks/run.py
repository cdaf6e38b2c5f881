"""One cell of the benchmark, once, in one process.

    python -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Every cell is "build the search from a factory, let the cell's check
prepare it (plant the benchmark's own weights, follow the first steps),
warm up, run one timed `Estimator.train`, judge". The cell, its
configuration, its traffic, its feed, its factory, its check, its
references and every metric, end-to-end or per-layer, are files of their
own, found by name; adding one edits nothing here.

The window is stated in steps: the traffic file's `window_steps` is the
pull at which the window asks the program to stop, so every run of a cell
completes the same steps. `--seconds` measures nothing. It arms a guard
at four times its value (120 s at the least) that ends a window which
has not ended itself, and such a run is not correct.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(kind, name):
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.exists(path):
        raise SystemExit("benchmarks: no %s file %s" % (kind, path))
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """The file `<kind>/<name>.py`, by path: a name may hold a dot."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit("benchmarks: no %s file %s" % (kind, path))
    spec = importlib.util.spec_from_file_location(
        "benchmarks.%s.%s" % (kind, name.replace(".", "_")), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_readers(cell_name, kind):
    """[(name, reader)] of the metrics of one kind ("end_to_end" or
    "per_layer") that `BENCHMARK.json` lists for this cell: each a file
    `metrics/<name>.py` with a `UNIT` and a `read(record)`."""
    manifest = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")
    with open(manifest) as f:
        entries = json.load(f)[kind]
    return [
        (entry["name"], load_module("metrics", entry["name"]))
        for entry in entries
        if cell_name in entry.get("workloads", [cell_name])
    ]


class CompileLog:
    """Every backend compile or cache load, with the time it ended."""

    def __init__(self, jax):
        self.events = []
        self.cache = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), duration))

    def _event(self, event, **_):
        for key, name in (
            ("requests", "compile_requests_use_cache"),
            ("hits", "cache_hits"),
            ("misses", "cache_misses"),
        ):
            if event == "/jax/compilation_cache/" + name:
                self.cache[key] += 1

    def between(self, start, end):
        inside = [d for t, d in self.events if start <= t <= end]
        return len(inside), sum(inside)


class Cell:
    """A cell's files, found by name and loaded."""

    def __init__(self, name):
        self.name = name
        self.cell = load_json("workloads", name)
        self.config = load_json("configs", self.cell["config"])
        self.traffic = load_json("traffic", self.cell["traffic"])
        check_window(self.traffic, "traffic/%s.json" % self.cell["traffic"])
        self.feed = load_module("feeds", self.config.get("feed", "images"))
        self.factory = load_module("factories", self.config["factory"])
        self.check = load_module("checks", self.cell["check"])
        self.members = self.config["members"]
        for member in self.members.values():
            member["sizes"] = {
                **self.config["sizes"], **self.config["ensemble"],
                **member["sizes"],
            }
        unknown = sorted(
            set(self.cell["training"] + self.cell["frozen"])
            - set(self.members)
        )
        if unknown:
            raise SystemExit(
                "benchmarks: cell %s names members %s that configuration %s "
                "does not have" % (name, unknown, self.cell["config"])
            )

    def flop_per_step(self):
        """Model FLOP of one step: a candidate in training costs three
        forwards an example, a frozen member one."""
        forward = {
            name: member["flops_forward_per_example"]
            for name, member in self.members.items()
        }
        return self.traffic["batch"] * (
            3 * sum(forward[name] for name in self.cell["training"])
            + sum(forward[name] for name in self.cell["frozen"])
        )


def check_window(traffic, where):
    """A window is a count of steps, and the traced pulls lie inside it."""
    if "window_steps" not in traffic:
        raise SystemExit(
            "benchmarks: %s states no window_steps: a window is a count of "
            "steps, not a time" % where
        )
    pulls = (traffic["trace_skip_pulls"] + traffic["trace_wall_steps"]
             + traffic["trace_steps"])
    if pulls > traffic["window_steps"]:
        raise SystemExit(
            "benchmarks: %s traces to pull %d (trace_skip_pulls + "
            "trace_wall_steps + trace_steps), past its window_steps %d"
            % (where, pulls, traffic["window_steps"])
        )


def tree_files(root):
    """Every file under `root`, as a path."""
    return sorted(
        os.path.join(folder, name)
        for folder, _, names in os.walk(root) for name in names
    )


class Guard:
    """Ends a window that has not ended itself: `stop` is called
    `4 x --seconds` after `start` (120 s at the least), and a window that
    it ended is not correct. `timer` is `threading.Timer`'s signature."""

    def __init__(self, seconds, stop, timer=threading.Timer):
        self.limit_s = max(4.0 * seconds, 120.0)
        self.fired = False
        self._stop = stop
        self._timer = timer(self.limit_s, self._fire)
        self._timer.daemon = True

    def _fire(self):
        self.fired = True
        self._stop()

    def start(self):
        self._timer.start()

    def cancel(self):
        self._timer.cancel()

    def failure(self, pulls, window_steps):
        if not self.fired:
            return None
        return (
            "window guard: the window had not reached its window_steps "
            "(pull %d of %d) %.0f s after it began, and the guard ended it"
            % (pulls, window_steps, self.limit_s)
        )


def empty_trace_refusal(platform, trace, trace_dir, clock):
    """What a traced run on the chip says, in place of a result, where its
    trace holds no device plane; None where the run may print."""
    if platform != "tpu" or trace["device_planes"]:
        return None
    held = [os.path.relpath(path, trace_dir) for path in tree_files(trace_dir)]
    return (
        "benchmarks: the trace in %s holds no device plane (files: %s); "
        "the window's pulls got to %s. No result is printed."
        % (trace_dir, held or "none", json.dumps(clock, sort_keys=True))
    )


class Search:
    """One search of a cell from one seed: the program's estimator fed by
    the benchmark's feed. The cell's check hangs what it reads on it."""

    def __init__(self, cell, seed, annotate=False):
        from benchmarks.feed import Feed

        self.cell, self.seed = cell, seed
        self.feed = Feed(
            seed, cell.traffic, cell.config["sizes"], annotate,
            ring=cell.feed.ring,
        )
        self.model_dir = tempfile.mkdtemp(prefix="bench_model_")
        self.estimator, self.far = cell.factory.build(
            cell.config, cell.traffic, seed & 0x7FFFFFFF, self.model_dir
        )

    def train(self, position, max_steps, on_pull=None):
        """One `Estimator.train` call fed from batch `position` on."""
        self.feed.position, self.feed.on_pull = position, on_pull
        try:
            self.estimator.train(self.feed.input_fn, max_steps=max_steps)
        finally:
            self.feed.close_span()
            self.feed.on_pull = None

    def stop(self):
        """Ends the `train` call under way as the window is ended."""
        stop_self()

    def free(self):
        """Drops the program's state from the chip."""
        self.estimator = None
        gc.collect()

    def close(self):
        shutil.rmtree(self.model_dir, ignore_errors=True)


def stop_self():
    os.kill(os.getpid(), signal.SIGTERM)


def start_jax(cell):
    """JAX with the chip looked for, the compile cache placed and every
    compile logged. Returns (jax, devices, peaks, cache_dir, compiles), or
    None where the cell may not run here."""
    import jax

    devices = jax.local_devices()
    on_chip = devices[0].platform == "tpu"
    if not cell.config.get("rehearsal") and (
        not on_chip or len(devices) < cell.cell["chips"]
    ):
        print(
            "benchmarks: cell %s needs %d TPU chip(s), found %d %s"
            % (cell.name, cell.cell["chips"], len(devices),
               devices[0].platform),
            file=sys.stderr,
        )
        return None
    peaks = None
    if on_chip:
        table = load_json(".", "peaks")
        if devices[0].device_kind not in table:
            raise SystemExit(
                "benchmarks: no peaks for device kind %r in peaks.json"
                % devices[0].device_kind
            )
        peaks = table[devices[0].device_kind]

    from adanet_tpu.utils.compile_cache_dir import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    # In this process only: keep the sub-second compiles of the op-by-op
    # sections too, so that every run after the first reads them back.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileLog(jax)
    # A signal that arrives outside `train` must not end the process.
    signal.signal(signal.SIGTERM, lambda *_: None)
    return jax, devices, peaks, cache_dir, compiles


def profiler_options(jax, wanted):
    options = jax.profiler.ProfileOptions()
    for key, value in wanted.items():
        setattr(options, key, value)
    return options


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument(
        "--seconds", type=float, required=True,
        help="arms the guard only: a window still open after 4 x this "
        "(120 s at the least) is ended and is not correct; the window's "
        "length is the traffic file's window_steps",
    )
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = Cell(args.workload)
    traffic, checker = cell.traffic, cell.check
    readers = metric_readers(
        args.workload, "per_layer" if args.trace else "end_to_end"
    )
    started = start_jax(cell)
    if started is None:
        return 3
    jax, devices, peaks, cache_dir, compiles = started

    from benchmarks import check, ckpt_io, span_reduce, trace_reduce

    # The harness's own barrier: a program that the chip runs after every
    # step dispatched before it. Traced runs alone use it.
    tick = jax.jit(lambda x: x + 1)

    def barrier():
        with jax.profiler.TraceAnnotation("harness_barrier"):
            tick(0.0).block_until_ready()
        return time.perf_counter()

    search = Search(cell, args.seed, annotate=bool(args.trace))
    model_dir = search.model_dir
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        checker.prepare(search)
        if args.trace:
            barrier()
        # Warm up, ended the way the window is ended.
        pulls = [0]

        def stop_after_warmup():
            pulls[0] += 1
            if pulls[0] == traffic["warmup_steps"]:
                stop_self()

        step_before = ckpt_io.global_step(model_dir)
        search.train(step_before, search.far, on_pull=stop_after_warmup)
        step_before = ckpt_io.global_step(model_dir)
        window_start = time.perf_counter()
        setup_s = window_start - _PROCESS_START
        # The window: one whole call, entry to return.
        clock = {"pulls": 0}
        skip = traffic["trace_skip_pulls"]
        timed, traced = traffic["trace_wall_steps"], traffic["trace_steps"]
        window_steps = traffic["window_steps"]

        def on_window_pull():
            clock["pulls"] += 1
            if clock["pulls"] == 2:
                clock["first_train_pull"] = time.perf_counter()
            if args.trace:
                trace_at_pull()
            # The step of this pull is the window's last: the program
            # dispatches it, finishes what is in flight, saves, returns.
            if clock["pulls"] == window_steps:
                clock["stop_requested"] = time.perf_counter()
                stop_self()

        def trace_at_pull():
            # A traced run first times `timed` steps between two barriers
            # with the profiler off, then traces `traced` whole steps, the
            # chip drained before and after, so that no step is cut.
            if clock["pulls"] == skip:
                clock["wall_start"] = barrier()
            elif clock["pulls"] == skip + timed:
                clock["wall_stop"] = barrier()
                jax.profiler.start_trace(
                    trace_dir, profiler_options=profiler_options(
                        jax, traffic["trace_options"]
                    ),
                )
                clock["trace_start"] = time.perf_counter()
            elif clock["pulls"] == skip + timed + traced:
                barrier()
                jax.profiler.stop_trace()
                clock["trace_stop"] = time.perf_counter()

        guard = Guard(args.seconds, stop_self)
        failure = None
        guard.start()
        try:
            search.train(step_before, search.far, on_pull=on_window_pull)
        except Exception as exc:  # the run reports it and is not correct
            import traceback

            traceback.print_exc()
            failure = "%s: %s" % (type(exc).__name__, exc)
        finally:
            guard.cancel()
            if "trace_start" in clock and "trace_stop" not in clock:
                jax.profiler.stop_trace()
                clock["trace_stop"] = time.perf_counter()
        window_end = time.perf_counter()
        clock.setdefault("stop_requested", window_end)
        failure = failure or guard.failure(clock["pulls"], window_steps)
        steps = ckpt_io.global_step(model_dir) - step_before
        numbers = checker.after_window(search)
        peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices
        )
        cache_bytes = sum(
            os.path.getsize(os.path.join(cache_dir, f))
            for f in os.listdir(cache_dir)
        ) if os.path.isdir(cache_dir) else 0
        print(json.dumps({
            "cache_dir": cache_dir, "cache_bytes": cache_bytes,
            "model_dir": model_dir, "model_dir_bytes": sum(
                map(os.path.getsize, tree_files(model_dir))
            ),
            "cache_in_setup": compiles.cache,
            "window_s": window_end - window_start, "steps": steps,
        }))

        # The program's state goes before the reference takes the chip.
        search.free()
        numbers = {**checker.judge(search), **numbers}
        numbers["window_steps_short"] = [float(steps < 1), 0.0]
        correct = failure is None and check.passed(numbers)

        record = {
            "clock": clock, "window_start": window_start,
            "window_end": window_end, "steps": steps,
            "batch": traffic["batch"], "setup_s": setup_s,
            "timed_steps": timed,
            "compiles_in_window": compiles.between(window_start, window_end),
            "compiles_in_setup": compiles.between(0.0, window_start),
            "peaks": peaks, "trace": None, "trace_dir": trace_dir,
            "flop_per_step": cell.flop_per_step(),
        }
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        }
        result = {"correct": correct, "attempted": steps,
                  "failed": steps if failure
                  else int(numbers["unsound_steps"][0])}
        if args.trace:
            record["trace"] = trace_reduce.reduce_dir(trace_dir)
            lead = record["trace"].get("lead", {})
            print(json.dumps({"trace": {
                key: lead.get(key) for key in
                ("plane", "step_program", "steps", "span_s", "span_busy_s",
                 "busy_s", "program_s", "step_runs_s")
            }}))
            refusal = empty_trace_refusal(
                devices[0].platform, record["trace"], trace_dir, clock
            )
            if refusal:
                print(refusal, file=sys.stderr)
                return 4
            if record["trace"]["device_planes"]:
                device["busy_s"] = record["trace"]["busy_s"]
                device["window_s"] = record["trace"]["window_s"]
                result["breakdown"] = record["trace"]["breakdown"]
        print(json.dumps({"window_spans": span_reduce.window_spans(record)}))
        metrics = {}
        for name, reader in readers:
            value = reader.read(record)
            if value is not None:
                metrics[name] = {"value": value, "unit": reader.UNIT}
        result.update(metrics=metrics, device=device)
        if failure:
            result["failure"] = failure
        result["checks"] = numbers
        for name, (value, limit) in numbers.items():
            print("check %s %.6g limit %.6g" % (name, value, limit),
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return 0
    finally:
        search.close()
        shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
