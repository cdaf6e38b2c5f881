"""The jaxlint perf pack: JL010-JL012 + JL016, MFU-campaign rules.

ROADMAP item 1 (NASNet MFU 0.107 -> 0.35+) is an audit problem as much
as a kernel problem: dtype upcasts that silently drag a bf16 compute
path back to f32, loop-invariant constructors re-executed inside every
`lax.scan` iteration, and per-step device->host transfers in the host
training loop each burn a slice of the hardware the profile then shows
as "idle". These rules make those patterns un-mergeable instead of
re-discovered per profiling round. JL016 guards the telemetry plane's
clock discipline (wall-clock reads must stay outside traced code). All
are interprocedural over `tools.jaxlint.callgraph`.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.jaxlint.callgraph import dotted_name, module_walk
from tools.jaxlint.engine import FileContext, Finding, ProjectContext
from tools.jaxlint.rules import (
    Rule,
    _scope_walk,
    _short_name,
    param_names,
)

# ---------------------------------------------------------------- JL010


class DtypePromotionRule(Rule):
    """f32 upcasts on bf16 compute paths; f64 on any compute path.

    End-to-end bf16 training (params f32, compute bf16) only pays off if
    the WHOLE step stays in bf16 — one `astype(jnp.float32)` inside a
    branch re-promotes every downstream op and halves MXU throughput.
    In a module that has opted into bf16 (mentions `bfloat16`), an
    explicit f32 cast reachable from a jit entry is a policy violation;
    float64 on a traced path is flagged everywhere (TPUs emulate f64 at
    ~1/10th rate). Interprocedural: the upcast is found however deep
    below the jit entry it hides, with the call chain reported.
    """

    rule_id = "JL010"
    summary = "dtype promotion (f32 upcast / f64) on a bf16 compute path"
    project = True

    _F32 = {"float32", "f32"}
    _F64 = {"float64", "f64", "double"}
    #: The policy is "params f32, COMPUTE bf16" — initialization paths
    #: legitimately build f32 parameters and are exempt from the f32
    #: branch (f64 is still flagged everywhere).
    _INIT_NAME = re.compile(r"init|param")

    def check_project(self, proj: ProjectContext) -> List[Finding]:
        from tools.jaxlint import dataflow

        graph = proj.graph
        if not graph.jit_entries:
            return []
        chains = dataflow.reach_with_chains(
            graph.edges, graph.jit_entries
        )
        # A module opts into the bf16 policy by USING bfloat16 in code —
        # an AST mention, not a comment/docstring substring (a TODO
        # about bf16 must not turn the module's f32 annotations into
        # findings).
        bf16_files = {
            path
            for path, ctx in proj.files.items()
            if self._uses_bf16(ctx.tree)
        }
        findings: List[Finding] = []
        for qual in sorted(chains):
            info = graph.functions.get(qual)
            if info is None:
                continue
            ctx = proj.files[info.path]
            chain = chains[qual]
            via = (
                " [call chain: %s]" % dataflow.render_chain(graph, chain)
                if len(chain) > 1
                else ""
            )
            for node in _scope_walk(info.node):
                hit = self._dtype_mention(node)
                if hit is None:
                    continue
                kind, name = hit
                if kind == "f64":
                    findings.append(
                        ctx.finding(
                            node,
                            self.rule_id,
                            "%s on the compute path of jitted %r: TPUs "
                            "have no native f64 — this runs at a "
                            "fraction of MXU rate%s"
                            % (name, _short_name(chain[0]), via),
                        )
                    )
                elif info.path in bf16_files and not self._INIT_NAME.search(
                    info.name
                ):
                    findings.append(
                        ctx.finding(
                            node,
                            self.rule_id,
                            "explicit %s upcast on the compute path of "
                            "jitted %r in a bf16 module: every "
                            "downstream op re-promotes to f32 (keep "
                            "compute in bf16; upcast only at the loss/"
                            "reduction boundary with a jaxlint "
                            "suppression stating why)%s"
                            % (name, _short_name(chain[0]), via),
                        )
                    )
        return findings

    @staticmethod
    def _uses_bf16(tree: ast.Module) -> bool:
        for node in module_walk(tree):
            if isinstance(node, ast.Attribute) and node.attr == "bfloat16":
                return True
            if isinstance(node, ast.Name) and node.id == "bfloat16":
                return True
            if isinstance(node, ast.Constant) and node.value == "bfloat16":
                return True
        return False

    def _dtype_mention(
        self, node: ast.AST
    ) -> Optional[Tuple[str, str]]:
        """(kind, rendered name) when `node` forces f32/f64, else None.

        Forms: `x.astype(jnp.float32)`, `x.astype("float32")`,
        `jnp.asarray(v, jnp.float64)`, `dtype=jnp.float32` keywords,
        `jnp.float64(v)` calls.
        """
        if not isinstance(node, ast.Call):
            return None
        # x.astype(<dtype>)
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "astype"
            and node.args
        ):
            kind = self._dtype_of(node.args[0])
            if kind:
                return kind, "astype(%s)" % self._render(node.args[0])
        # jnp.float64(v) / np.float64(v)
        name = dotted_name(node.func) or ""
        last = name.split(".")[-1]
        if last in self._F64 and name != last:
            return "f64", name
        # jnp.asarray(x, jnp.float64) / jnp.array(x, ...): dtype is the
        # second POSITIONAL argument of the array constructors.
        if last in {"asarray", "array"} and len(node.args) >= 2:
            kind = self._dtype_of(node.args[1])
            if kind:
                return kind, "dtype=%s" % self._render(node.args[1])
        # dtype=... keyword on any call
        for kw in node.keywords:
            if kw.arg == "dtype":
                kind = self._dtype_of(kw.value)
                if kind:
                    return kind, "dtype=%s" % self._render(kw.value)
        return None

    def _dtype_of(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if node.value in self._F64:
                return "f64"
            if node.value in self._F32:
                return "f32"
            return None
        name = dotted_name(node) or ""
        last = name.split(".")[-1]
        if last in self._F64:
            return "f64"
        if last in self._F32 and name != last:
            # require a namespace (jnp.float32) so a local variable
            # named `float32` doesn't trip the rule
            return "f32"
        return None

    @staticmethod
    def _render(node: ast.AST) -> str:
        if isinstance(node, ast.Constant):
            return repr(node.value)
        return dotted_name(node) or "<expr>"


# ---------------------------------------------------------------- JL011


class LoopInvariantScanRule(Rule):
    """Loop-invariant constructors inside scan/loop body functions.

    `lax.scan`/`fori_loop`/`while_loop` bodies execute per iteration ON
    DEVICE; a `jnp.arange(...)`, `jnp.eye(...)`, or `jax.random.PRNGKey`
    whose arguments don't depend on the carry re-materializes identical
    values every step. Hoist it above the loop (XLA sometimes rescues
    the scalar cases, never the big-iota ones — and the NASNet cell
    kernel budget has no room for luck).
    """

    rule_id = "JL011"
    summary = "loop-invariant constructor inside a scan/loop body"
    project = True

    _LOOP_CALLS = {"scan": 0, "fori_loop": 2, "while_loop": 1}
    _CONSTRUCTORS = {
        "zeros",
        "ones",
        "full",
        "arange",
        "eye",
        "linspace",
        "tri",
        "PRNGKey",
    }

    def check_project(self, proj: ProjectContext) -> List[Finding]:
        graph = proj.graph
        findings: List[Finding] = []
        for path in sorted(proj.files):
            ctx = proj.files[path]
            mod = graph.modules.get(path)
            if mod is None:
                continue
            for node in module_walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                last = name.split(".")[-1]
                if last not in self._LOOP_CALLS:
                    continue
                body_pos = self._LOOP_CALLS[last]
                if len(node.args) <= body_pos:
                    continue
                body_arg = node.args[body_pos]
                body = self._body_function(graph, mod, node, body_arg)
                if body is None:
                    continue
                findings.extend(
                    self._check_body(ctx, proj, graph, last, body)
                )
        return findings

    def _body_function(self, graph, mod, call, body_arg):
        if isinstance(body_arg, ast.Lambda):
            return body_arg
        target = dotted_name(body_arg)
        if not target:
            return None
        scope = graph._enclosing_function(mod, call)
        resolved = graph.resolve(target, mod, scope)
        if resolved is None:
            return None
        return graph.functions[resolved].node

    def _check_body(
        self, ctx, proj, graph, loop_kind, body
    ) -> List[Finding]:
        if isinstance(body, ast.Lambda):
            params = {
                a.arg
                for a in list(body.args.args)
                + list(body.args.posonlyargs)
                + list(body.args.kwonlyargs)
            }
        else:
            params = set(param_names(body))
        body_ctx = ctx
        body_path = graph.qualname_of_node.get(id(body))
        if body_path is not None:
            info = graph.functions[body_path]
            body_ctx = proj.files[info.path]
        # Names bound inside the body (they may depend on the carry).
        bound: Set[str] = set(params)
        for sub in _scope_walk(body):
            if isinstance(sub, ast.Name) and isinstance(
                sub.ctx, ast.Store
            ):
                bound.add(sub.id)
        findings = []
        for sub in _scope_walk(body):
            if not isinstance(sub, ast.Call):
                continue
            name = dotted_name(sub.func) or ""
            parts = name.split(".")
            if parts[-1] not in self._CONSTRUCTORS or len(parts) < 2:
                continue
            used = {
                n.id
                for arg in list(sub.args)
                + [kw.value for kw in sub.keywords]
                for n in ast.walk(arg)
                if isinstance(n, ast.Name)
            }
            if used & bound:
                continue  # depends on the carry/loop state — not invariant
            findings.append(
                body_ctx.finding(
                    sub,
                    self.rule_id,
                    "%s inside a lax.%s body is loop-invariant: it "
                    "re-materializes identical values every iteration "
                    "— hoist it above the loop and close over it"
                    % (name, loop_kind),
                )
            )
        return findings


# ---------------------------------------------------------------- JL012


class HostLoopTransferRule(Rule):
    """Per-step device->host transfers inside the host training loop.

    The host loop that dispatches jitted steps is the pacing thread of
    the whole machine: a `device_get`/`np.asarray`/`.item()` in its body
    synchronously drains the device pipeline EVERY step, so the TPU
    idles for a host round-trip per dispatch (the profile signature
    behind MFU 0.107). Batch metrics on device and fetch every K steps,
    or fetch asynchronously. A loop qualifies when its body calls a
    function from which a jit entry is reachable; logging/summary/
    checkpoint helper calls inside it are exempt (host-side by design,
    amortized by their callers).
    """

    rule_id = "JL012"
    summary = "per-step device->host transfer in the host training loop"
    project = True

    _TRANSFERS = {"item", "tolist"}
    _TRANSFER_CALLS = {
        "np.asarray",
        "np.array",
        "numpy.asarray",
        "numpy.array",
        "jax.device_get",
        "device_get",
    }

    def check_project(self, proj: ProjectContext) -> List[Finding]:
        from tools.jaxlint import dataflow

        graph = proj.graph
        if not graph.jit_entries:
            return []
        # Functions from which a jit entry is reachable = dispatchers.
        rev = dataflow.callers_of(graph.edges)
        dispatchers = set(
            dataflow.reach_with_chains(rev, graph.jit_entries)
        )
        findings: List[Finding] = []
        for qual in sorted(graph.functions):
            info = graph.functions[qual]
            if isinstance(info.node, ast.Lambda):
                continue
            if qual in set(graph.jit_entries):
                continue  # inside jit JL002 owns the diagnosis
            mod = graph.modules[info.path]
            ctx = proj.files[info.path]
            for loop in _scope_walk(info.node):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                if not self._dispatches_step(
                    graph, mod, info, loop, dispatchers
                ):
                    continue
                findings.extend(
                    self._flag_transfers(ctx, info, loop)
                )
        return findings

    def _dispatches_step(
        self, graph, mod, info, loop, dispatchers
    ) -> bool:
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            target = dotted_name(node.func)
            resolved = (
                graph.resolve(target, mod, info) if target else None
            )
            if resolved in dispatchers or resolved in set(
                graph.jit_entries
            ):
                return True
            # Attr-wrapper dispatch (`self._train_step(...)`).
            if target and target.split(".")[0] in ("self", "cls"):
                attr = target.split(".")[-1]
                if attr in mod.attr_wrappers:
                    return True
        return False

    def _flag_transfers(self, ctx, info, loop) -> List[Finding]:
        findings = []
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            if self._inside_helper_call(loop, node):
                continue
            name = dotted_name(node.func) or ""
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in self._TRANSFERS
            ):
                what = ".%s()" % node.func.attr
            elif name in self._TRANSFER_CALLS:
                what = name
            else:
                continue
            findings.append(
                ctx.finding(
                    node,
                    self.rule_id,
                    "%s inside the step-dispatch loop of %r drains the "
                    "device pipeline every step — batch on device and "
                    "fetch every K steps (device_put/donate keep the "
                    "loop async)" % (what, info.name),
                )
            )
        return findings

    def _inside_helper_call(self, loop, node) -> bool:
        """True when `node` sits in a logging/summary/checkpoint helper
        call's arguments (exempt: host-side by design)."""
        from tools.jaxlint.rules import HostSyncRule

        for parent in ast.walk(loop):
            if not isinstance(parent, ast.Call) or parent is node:
                continue
            pname = dotted_name(parent.func) or ""
            if not HostSyncRule._host_helper_name(
                pname.split(".")[-1]
            ):
                continue
            for sub in ast.walk(parent):
                if sub is node:
                    return True
        return False


# ---------------------------------------------------------------- JL016


class WallClockOnTracedPathRule(Rule):
    """Wall-clock reads reachable from jit-traced code, repo-wide.

    `time.time()`/`perf_counter()`/`monotonic()` inside traced code does
    not measure the device: it executes ONCE at trace time and the value
    is constant-folded into the program, so the "timestamp" is frozen at
    compile and every cached execution reuses it — a silently wrong
    metric. Telemetry belongs OUTSIDE traced code (the observability
    tracer's injected clock); on-device timing belongs to the profiler
    trace (`benchmarks/trace_reduce.py`). Interprocedural like JL002: a
    clock read buried two helpers below the jit entry is attributed to
    the entry with the full call chain.
    """

    rule_id = "JL016"
    summary = "wall-clock read on a jit-traced path"
    project = True

    #: Dotted call names that read a host clock.
    _CLOCK_CALLS = {
        "time.time",
        "time.time_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.process_time",
        "datetime.now",
        "datetime.utcnow",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
    }
    #: Bare names covering `from time import perf_counter` style (the
    #: ambiguous bare `time` is excluded — too collision-prone).
    _CLOCK_BARE = {
        "perf_counter",
        "perf_counter_ns",
        "monotonic",
        "monotonic_ns",
        "process_time",
    }

    def check_project(self, proj: ProjectContext) -> List[Finding]:
        from tools.jaxlint import dataflow
        from tools.jaxlint.rules import HostSyncRule

        graph = proj.graph
        if not graph.jit_entries:
            return []
        # The same host-helper boundary as JL002: traversal never enters
        # a helper whose name declares it host-side (logging/summary/
        # checkpoint helpers run between steps, not under trace).
        pruned = {
            qual: {
                c
                for c in callees
                if not HostSyncRule._host_helper_name(_short_name(c))
            }
            for qual, callees in graph.edges.items()
        }
        roots = [
            q
            for q in graph.jit_entries
            if not HostSyncRule._host_helper_name(_short_name(q))
        ]
        chains = dataflow.reach_with_chains(pruned, roots)
        findings: List[Finding] = []
        for qual in sorted(chains):
            info = graph.functions.get(qual)
            if info is None:
                continue
            ctx = proj.files[info.path]
            chain = chains[qual]
            via = (
                " [call chain: %s]" % dataflow.render_chain(graph, chain)
                if len(chain) > 1
                else ""
            )
            for node in _scope_walk(info.node):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                if not (
                    name in self._CLOCK_CALLS
                    or (
                        isinstance(node.func, ast.Name)
                        and name in self._CLOCK_BARE
                    )
                ):
                    continue
                findings.append(
                    ctx.finding(
                        node,
                        self.rule_id,
                        "%s() in %r (reached from jitted %r) reads the "
                        "wall clock at TRACE time — the value freezes "
                        "into the compiled program; time outside traced "
                        "code with an injected clock (observability."
                        "spans) or use the profiler's device lanes%s"
                        % (name, info.name, _short_name(chain[0]), via),
                    )
                )
        return findings


PERF_RULES: List[Rule] = [
    DtypePromotionRule(),
    LoopInvariantScanRule(),
    HostLoopTransferRule(),
    WallClockOnTracedPathRule(),
]
