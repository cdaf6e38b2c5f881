"""Checkpoint verification, quarantine, and rollback (`ckpt_fsck`).

The self-healing half of the checkpoint contract (docs/robustness.md):
`fsck` walks a model dir's durable artifacts — the manifest chain, the
per-iteration `architecture-<t>.json` + `frozen-<t>.msgpack` pairs, the
mid-iteration `ckpt-<step>.msgpack`, and retained
`iteration-final-<t>.msgpack` states — verifying each against its
SHA-256 digest (or, for legacy files without one, a decode check). A
corrupt file degrades to "resume from the previous generation":

- corrupt mid-iteration state → quarantined (`*.corrupt`); the run
  restarts the CURRENT iteration from its first step (global step rolls
  back to the previous iteration's end);
- corrupt frozen/architecture at iteration t → quarantined; the manifest
  rolls back to iteration t (iterations 0..t-1 stay frozen; t retrains),
  and now-orphaned later-iteration artifacts are retired (`*.stale`) so
  a future manifest reconstruction can never resurrect a mixed chain;
- orphaned `ckpt-*` payloads that fail verification (the torn leftovers
  of a crash mid-write) → quarantined.

`Estimator.train` runs `fsck(repair=is_chief)` before restoring, so a
torn or bit-rotted file costs re-training one iteration, never a crash
or silent garbage. `tools/ckpt_fsck.py` is the operator CLI over the
same engine.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
from typing import List, Optional, Sequence

from adanet_tpu.core import checkpoint as ckpt

_LOG = logging.getLogger("adanet_tpu")

STALE_SUFFIX = ".stale"

#: Serving-generation contract (mirrors `core/export.py`'s
#: SERVING_FILE/SIGNATURE_FILE — not imported: the robustness layer must
#: stay loadable without the export stack). A published
#: `serving/gen-<t>/` directory must carry both files, their digest
#: sidecars, and a checksummed `generation.json` binding them.
GENERATION_MANIFEST = "generation.json"
REQUIRED_SERVING_FILES = ("serving.stablehlo", "serving_signature.json")

#: Exit-code contract shared by `tools/ckpt_fsck.py`, CI, and the
#: elastic scheduler's pre-restore check (usage errors exit 64/EX_USAGE
#: so 2 is unambiguous).
EXIT_CLEAN = 0
EXIT_HEALED = 1
EXIT_UNRECOVERABLE = 2


@dataclasses.dataclass
class FsckReport:
    """The outcome of one verification/heal pass."""

    ok: bool = True
    fresh: bool = False
    issues: List[str] = dataclasses.field(default_factory=list)
    quarantined: List[str] = dataclasses.field(default_factory=list)
    retired: List[str] = dataclasses.field(default_factory=list)
    rolled_back_to_iteration: Optional[int] = None
    rolled_back_global_step: Optional[int] = None
    manifest_rewritten: bool = False
    info: Optional[ckpt.CheckpointInfo] = None

    @property
    def verdict(self) -> str:
        """"clean" | "healed" | "unrecoverable".

        Deterministic given the dir contents whether or not `repair` ran
        (report-only mode computes the identical rollback), so CI's
        verify pass and the chief's heal pass agree. "healed" means a
        usable resume point survives the (actual or would-be) repair;
        "unrecoverable" means the heal rolls all the way back to
        iteration 0 / global step 0 — every trained generation was lost
        and resuming is training from scratch.
        """
        if self.ok or self.fresh:
            return "clean"
        if (
            self.rolled_back_to_iteration == 0
            and not self.rolled_back_global_step
            and self.info is not None
            and self.info.iteration_state_file is None
        ):
            return "unrecoverable"
        return "healed"

    @property
    def exit_code(self) -> int:
        return {
            "clean": EXIT_CLEAN,
            "healed": EXIT_HEALED,
            "unrecoverable": EXIT_UNRECOVERABLE,
        }[self.verdict]

    def to_json(self) -> dict:
        obj = dataclasses.asdict(self)
        info = obj.pop("info")
        if info is not None:
            obj["iteration_number"] = info["iteration_number"]
            obj["global_step"] = info["global_step"]
            obj["generation"] = info["generation"]
        obj["verdict"] = self.verdict
        obj["exit_code"] = self.exit_code
        return obj


def _payload_intact(
    model_dir: str, filename: str, info: ckpt.CheckpointInfo
) -> bool:
    """Digest verdict, falling back to a decode check for legacy files."""
    verdict = ckpt.verify_file(
        model_dir, filename, expected=info.digests.get(filename)
    )
    if verdict is not None:
        return verdict
    # Legacy payload without a recorded digest: decoding is the only
    # structural check available (catches truncation, not bit flips in
    # valid msgpack). OSError covers a file the chief's concurrent
    # repair pass just quarantined out from under this process.
    try:
        ckpt.restore_payload(model_dir, filename)
        return True
    except (ckpt.CheckpointCorruptionError, OSError):
        return False


def _arch_global_step(model_dir: str, iteration: int) -> Optional[int]:
    try:
        with open(
            os.path.join(
                model_dir, ckpt.architecture_filename(iteration)
            )
        ) as f:
            return int(json.load(f).get("global_step", 0))
    except (OSError, ValueError):
        return None


def end_step_of(info: ckpt.CheckpointInfo, model_dir: str, t: int) -> int:
    """Global step at the end of completed iteration t-1 (0 for t == 0).

    Public: the estimator's restore-time corruption handler applies the
    same rollback rule fsck does.
    """
    if t <= 0:
        return 0
    for entry in reversed(info.history):
        if int(entry.get("iteration_number", -1)) == t - 1:
            return int(entry.get("global_step", 0))
    step = _arch_global_step(model_dir, t - 1)
    return step if step is not None else 0


def _retire(
    model_dir: str,
    filename: str,
    report: FsckReport,
    repair: bool,
    reason: str = "orphaned by rollback",
) -> None:
    """Renames an intact-but-orphaned artifact to `<name>.stale`."""
    path = os.path.join(model_dir, filename)
    if not os.path.exists(path):
        return
    report.issues.append("%s: %s" % (reason, filename))
    if not repair:
        return
    target = filename + STALE_SUFFIX
    n = 0
    while os.path.exists(os.path.join(model_dir, target)):
        n += 1
        target = "%s%s.%d" % (filename, STALE_SUFFIX, n)
    try:
        os.replace(path, os.path.join(model_dir, target))
    except FileNotFoundError:
        return  # a concurrent heal won the rename
    try:
        os.replace(
            ckpt.digest_path(model_dir, filename),
            os.path.join(model_dir, target + ckpt.DIGEST_SUFFIX),
        )
    except OSError:
        pass
    report.retired.append(target)


def _quarantine(
    model_dir: str, filename: str, report: FsckReport, repair: bool
) -> None:
    if repair:
        name = ckpt.quarantine_file(model_dir, filename)
        if name:
            report.quarantined.append(name)
    else:
        report.issues.append("would quarantine: %s" % filename)


def rotted_sharded_states(model_dir: str) -> List[str]:
    """The sharded states of a model dir of which some leaf fails its
    digest: every leaf of every one is read and hashed, which `fsck`
    leaves to a state's readers. `tools/ckpt_fsck.py` hands the names to
    `fsck(condemned=...)`."""
    try:
        entries = sorted(os.listdir(model_dir))
    except OSError:
        return []
    return [
        name for name in entries
        # Live payloads only: what was set aside (`.corrupt`, `.stale`)
        # has lost its shards' directory with it.
        if name.endswith(".msgpack")
        and ckpt.corrupt_shards(model_dir, name)
    ]


def fsck(
    model_dir: str, repair: bool = False, condemned: Sequence[str] = ()
) -> FsckReport:
    """Verifies a model dir; with `repair`, quarantines and rolls back.

    Deterministic given the dir contents, so every process of a
    multi-host run computes the same healed `info`; only the chief
    passes `repair=True` and persists it.

    Of a sharded state (`core/checkpoint.py`) this pass verifies the
    index and that every shard file is there and as long as it says; the
    restore that follows in `Estimator.train` reads and verifies every
    leaf before it uses one and, where one fails, quarantines the state
    and rolls back as this pass would. `condemned` names payloads that
    count as corrupt whatever their digests say: what a caller learned
    from a deeper read (`rotted_sharded_states`).
    """
    report = FsckReport()
    # Report-only mode (and non-chief processes) must not mutate the
    # dir: only the repair pass may quarantine the corrupt main copy.
    info = ckpt.read_manifest(model_dir, quarantine=repair)
    if info is None:
        report.fresh = True
        return report
    report.info = info
    dirty = False
    main = os.path.join(model_dir, ckpt.MANIFEST)
    if not os.path.exists(main):
        # read_manifest recovered from .prev or reconstructed from the
        # artifact chain (quarantining the corrupt main copy); persist
        # the recovered state so the next reader takes the fast path.
        report.issues.append(
            "main manifest missing/corrupt (recovered from fallback)"
        )
        dirty = True
    elif not repair and not ckpt.manifest_intact(model_dir):
        # Without repair the corrupt main copy stays in place; report
        # what the repair pass would do.
        report.issues.append(
            "would quarantine: %s (corrupt; recovered from fallback)"
            % ckpt.MANIFEST
        )
        dirty = True

    # ------------------------- completed-iteration chain (frozen + arch)
    rollback: Optional[int] = None
    for t in range(info.iteration_number):
        arch_name = ckpt.architecture_filename(t)
        frozen_name = ckpt.frozen_filename(t)
        arch_ok = _arch_global_step(model_dir, t) is not None
        frozen_ok = os.path.exists(
            os.path.join(model_dir, frozen_name)
        ) and _payload_intact(model_dir, frozen_name, info)
        if arch_ok and frozen_ok:
            continue
        rollback = t
        if not arch_ok:
            report.issues.append(
                "architecture chain broken at iteration %d (%s)"
                % (t, arch_name)
            )
            _quarantine(model_dir, arch_name, report, repair)
        if not frozen_ok:
            report.issues.append(
                "frozen payload corrupt/missing at iteration %d (%s)"
                % (t, frozen_name)
            )
            _quarantine(model_dir, frozen_name, report, repair)
        break

    if rollback is not None:
        # Retire the now-orphaned artifacts of iterations beyond the
        # rollback point so no reconstruction can mix two chains.
        for t in range(rollback, info.iteration_number):
            for name in (
                ckpt.architecture_filename(t),
                ckpt.frozen_filename(t),
                ckpt.final_state_filename(t),
            ):
                # Corrupt files at the break point were quarantined
                # above (renamed away); whatever still exists here is
                # intact but belongs to the abandoned chain.
                _retire(model_dir, name, report, repair)
        if info.iteration_state_file:
            # Any mid-iteration state belongs to the rolled-back future.
            _retire(
                model_dir, info.iteration_state_file, report, repair
            )
            info.iteration_state_file = None
        info.iteration_number = rollback
        info.replay_indices = info.replay_indices[:rollback]
        info.history = [
            entry
            for entry in info.history
            if int(entry.get("iteration_number", -1)) < rollback
        ]
        info.global_step = end_step_of(info, model_dir, rollback)
        report.rolled_back_to_iteration = rollback
        report.rolled_back_global_step = info.global_step
        dirty = True
        _LOG.error(
            "Checkpoint chain broken at iteration %d: rolled back to "
            "iteration %d, global step %d (corrupt files quarantined).",
            rollback,
            rollback,
            info.global_step,
        )

    # ------------------------------------------- mid-iteration state file
    if info.iteration_state_file:
        name = info.iteration_state_file
        if name in condemned or not _payload_intact(model_dir, name, info):
            report.issues.append(
                "mid-iteration state corrupt (%s)" % name
            )
            _quarantine(model_dir, name, report, repair)
            info.iteration_state_file = None
            info.global_step = end_step_of(
                info, model_dir, info.iteration_number
            )
            if report.rolled_back_to_iteration is None:
                report.rolled_back_to_iteration = info.iteration_number
            report.rolled_back_global_step = info.global_step
            dirty = True
            _LOG.error(
                "Mid-iteration state %s corrupt: iteration %d restarts "
                "from global step %d.",
                name,
                info.iteration_number,
                info.global_step,
            )

    # -------------------------------------------------- orphaned payloads
    try:
        entries = sorted(os.listdir(model_dir))
    except OSError:
        entries = []
    for name in entries:
        if not re.fullmatch(r"ckpt-\d+\.msgpack", name):
            continue
        if name == info.iteration_state_file:
            continue
        if _payload_intact(model_dir, name, info):
            # Intact but unreferenced (a crash between the payload write
            # and the manifest update): retire it so repeated repair
            # runs converge to a clean verdict instead of flagging the
            # same file forever.
            _retire(
                model_dir, name, report, repair,
                reason="intact orphan payload",
            )
            continue
        report.issues.append(
            "orphan payload failed verification (torn write?): %s" % name
        )
        _quarantine(model_dir, name, report, repair)

    # Shard directories that no index names: a kill between the shards
    # and the publish of a sharded state leaves one, whole and unused.
    live = set()
    for name in entries:
        index = ckpt._read_index(os.path.join(model_dir, name))
        if index is not None:
            live.add(index["directory"])
    for name in entries:
        if (
            re.fullmatch(r"ckpt-\d+\.msgpack\.shards-[^.]+", name)
            and name not in live
            and os.path.isdir(os.path.join(model_dir, name))
        ):
            _retire(
                model_dir, name, report, repair,
                reason="shards no index names",
            )

    # Retained per-iteration final states: corruption never blocks the
    # search (they serve post-hoc eval), but garbage must not be served.
    for t in range(info.iteration_number):
        name = ckpt.final_state_filename(t)
        if os.path.exists(os.path.join(model_dir, name)):
            if name in condemned or not _payload_intact(
                model_dir, name, info
            ):
                report.issues.append(
                    "retained candidate state corrupt (%s)" % name
                )
                _quarantine(model_dir, name, report, repair)

    if dirty and repair:
        ckpt.write_manifest(model_dir, info)
        report.manifest_rewritten = True
    report.ok = not report.issues
    report.info = info
    return report


# ------------------------------------------------- serving generation audit


def verify_serving_generation(gen_dir: str) -> List[str]:
    """Verifies one published `serving/gen-<t>/` directory.

    Returns the list of issues; empty means the generation is eligible
    to serve. This is the exact verify-on-load check
    `serving.model_pool.ModelPool` runs before a flip, exposed here so
    `ckpt_fsck --json` audits the same verdict the server would reach.
    """
    issues: List[str] = []
    manifest_path = os.path.join(gen_dir, GENERATION_MANIFEST)
    try:
        with open(manifest_path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as exc:
        return ["generation manifest unreadable: %s" % exc]
    if not isinstance(obj, dict) or "digests" not in obj:
        return ["generation manifest malformed (no digests map)"]
    # The self-checksum is REQUIRED: the publisher always writes one,
    # so its absence means the manifest was rewritten — accepting it
    # would let a rewritten digests map launder rotted artifacts.
    checksum = obj.pop("checksum", None)
    if checksum is None:
        return ["generation manifest missing checksum"]
    expected = ckpt.sha256_hex(
        json.dumps(obj, sort_keys=True).encode()
    )
    if checksum != expected:
        return ["generation manifest checksum mismatch"]
    digests = dict(obj.get("digests", {}))
    for name in REQUIRED_SERVING_FILES:
        if name not in digests:
            issues.append("required serving file not recorded: %s" % name)
    for name, digest in sorted(digests.items()):
        verdict = ckpt.verify_file(gen_dir, name, expected=digest)
        if verdict is not True:
            issues.append(
                "digest mismatch or missing file: %s" % name
                if verdict is False
                else "no digest verdict for: %s" % name
            )
    return issues


def serving_report(model_dir: str) -> dict:
    """Per-generation serving eligibility for a model dir.

    `selected_generation` is the generation a freshly started serving
    plane would flip to (the NEWEST eligible one — `ModelPool` applies
    the same rule), so operators can audit a flip before it happens.
    """
    # Local import (not at module top): serving.publisher is a pure
    # stdlib/lister module, but keeping robustness->serving edges lazy
    # preserves the layering for import-time-sensitive callers.
    from adanet_tpu.serving import publisher

    generations = []
    selected = None
    for t, path in publisher.list_generations(model_dir):
        issues = verify_serving_generation(path)
        generations.append(
            {
                "iteration_number": t,
                "serving_eligible": not issues,
                "issues": issues,
            }
        )
        if not issues:
            selected = t
    return {"generations": generations, "selected_generation": selected}


# --------------------------------------------------- artifact store audit


def store_report(
    store_root: str,
    repair: bool = False,
    gc_dry_run: bool = False,
) -> dict:
    """The `store` section of `ckpt_fsck --json`.

    Thin wiring over `adanet_tpu.store.fsck_store` (lazy import — the
    checkpoint-chain fsck must stay usable without the store package):
    blob census (count/bytes), corrupt and quarantined blobs, dangling
    refs, lease census, and — under `--gc --dry-run` — the would-GC
    set. `repair` quarantines corrupt blobs and heals them from any
    duplicate referencer, the same path a live `store.get` takes.
    """
    from adanet_tpu.store import ArtifactStore, fsck_store

    return fsck_store(
        ArtifactStore(store_root), repair=repair, gc_dry_run=gc_dry_run
    )
