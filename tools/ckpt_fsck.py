"""Checkpoint fsck: verify and repair an AdaNet model directory.

Operator CLI over `adanet_tpu.robustness.integrity.fsck` (the same
engine `Estimator.train` runs before restoring). Verifies every durable
artifact — the manifest chain, per-iteration architecture + frozen
payload pairs, the mid-iteration state, retained candidate states —
against the recorded SHA-256 digests, and with `--repair` quarantines
corrupt files (`*.corrupt`), retires artifacts orphaned by a rollback
(`*.stale`), and rewrites the manifest at the newest intact generation.

Usage:
    python -m tools.ckpt_fsck MODEL_DIR            # verify, report
    python -m tools.ckpt_fsck MODEL_DIR --repair   # quarantine + roll back
    python -m tools.ckpt_fsck MODEL_DIR --json     # machine-readable

Exit status (`integrity.EXIT_*`, identical with and without --repair —
report-only mode computes the same heal it would apply, so CI's verify
job and the chief's repair pass agree):
    0  clean: nothing to do (also a fresh dir with no manifest)
    1  healed: issues found, but a usable resume point survives the
       (actual or would-be) repair
    2  unrecoverable: the heal rolls back to iteration 0 / step 0 —
       every trained generation was lost
    64 usage errors (EX_USAGE; argparse's default of 2 would collide
       with "unrecoverable")

The --json report carries the same answer in its `verdict` and
`exit_code` fields for consumers that want one parse path, plus a
`serving` section auditing the model dir's published serving
generations: `serving_eligible` per generation and
`selected_generation` — the generation a freshly started serving plane
(`adanet_tpu.serving.ModelPool`) would flip to, so a flip can be vetted
before it happens. Serving eligibility never affects the exit code
(the training chain is the fsck contract; serving artifacts are
re-publishable).

With `--store PATH` (auto-detected at `<model_dir>/store` when
present), the report also grows a `store` section over the shared
content-addressed artifact store (`adanet_tpu.store`): blob count and
bytes, corrupt/quarantined blobs, dangling refs, lease census, and —
under `--gc --dry-run` — the set of blobs a collection pass would
remove. `--repair` extends to the store (quarantine + heal from
duplicate referencers); `--gc` WITHOUT `--dry-run` actually runs the
lease-guarded collection. Store health, like serving, never affects
the exit code: store artifacts are re-publishable by construction.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


class _Parser(argparse.ArgumentParser):
    def error(self, message):
        self.print_usage(sys.stderr)
        self.exit(64, "%s: error: %s\n" % (self.prog, message))


def main(argv=None) -> int:
    parser = _Parser(
        prog="ckpt_fsck", description=__doc__.split("\n\n")[0]
    )
    parser.add_argument("model_dir", help="AdaNet model directory")
    parser.add_argument(
        "--repair",
        action="store_true",
        help="quarantine corrupt files and roll the manifest back to the "
        "newest intact generation",
    )
    parser.add_argument(
        "--json", action="store_true", help="emit the report as JSON"
    )
    parser.add_argument(
        "--store",
        default=None,
        help="artifact store root to audit (default: <model_dir>/store "
        "when that directory exists)",
    )
    parser.add_argument(
        "--gc",
        action="store_true",
        help="run a lease-guarded GC pass on the store (report-only "
        "with --dry-run)",
    )
    parser.add_argument(
        "--dry-run",
        action="store_true",
        help="with --gc: compute the would-GC set without deleting",
    )
    args = parser.parse_args(argv)

    from adanet_tpu.robustness import integrity

    # `fsck` leaves the leaves of a sharded state to whoever reads them;
    # the operator's pass reads and hashes every one.
    report = integrity.fsck(
        args.model_dir,
        repair=args.repair,
        condemned=integrity.rotted_sharded_states(args.model_dir),
    )
    # Serving audit: which generation the serving plane's ModelPool
    # would currently flip to (`serving_eligible` per published
    # generation), so operators can vet a flip BEFORE it happens.
    serving = integrity.serving_report(args.model_dir)

    store_root = args.store
    if store_root is None:
        candidate = os.path.join(args.model_dir, "store")
        if os.path.isdir(candidate):
            store_root = candidate
    store = None
    if store_root is not None:
        store = integrity.store_report(
            store_root,
            repair=args.repair,
            gc_dry_run=args.gc and args.dry_run,
        )
        if args.gc and not args.dry_run:
            from adanet_tpu.store import ArtifactStore, collect

            store["gc"] = collect(
                ArtifactStore(store_root)
            ).to_json()

    if args.json:
        obj = report.to_json()
        obj["serving"] = serving
        if store is not None:
            obj["store"] = store
        print(json.dumps(obj, sort_keys=True))
    else:
        if report.fresh:
            print("fresh model dir (no checkpoint manifest): nothing to do")
        elif report.ok:
            info = report.info
            print(
                "clean: iteration %d, global step %d, generation %d"
                % (
                    info.iteration_number,
                    info.global_step,
                    info.generation,
                )
            )
        for issue in report.issues:
            print("ISSUE: %s" % issue)
        for name in report.quarantined:
            print("quarantined: %s" % name)
        for name in report.retired:
            print("retired: %s" % name)
        if report.rolled_back_to_iteration is not None:
            print(
                "rolled back to iteration %d (global step %d)%s"
                % (
                    report.rolled_back_to_iteration,
                    report.rolled_back_global_step,
                    "" if report.manifest_rewritten else " [dry run]",
                )
            )
        if report.manifest_rewritten:
            print("manifest rewritten")
        if not report.ok and not report.fresh:
            print("verdict: %s" % report.verdict)
        for gen in serving["generations"]:
            print(
                "serving generation %d: %s"
                % (
                    gen["iteration_number"],
                    "eligible"
                    if gen["serving_eligible"]
                    else "INELIGIBLE (%s)" % "; ".join(gen["issues"]),
                )
            )
        if serving["generations"]:
            print(
                "serving plane would select: %s"
                % (
                    "generation %d" % serving["selected_generation"]
                    if serving["selected_generation"] is not None
                    else "nothing (no eligible generation)"
                )
            )
        if store is not None:
            print(
                "store %s: %d blobs (%d bytes), %d refs, %s"
                % (
                    store["root"],
                    store["blob_count"],
                    store["bytes"],
                    store["ref_count"],
                    "clean" if store["clean"] else "NOT CLEAN",
                )
            )
            for digest in store["corrupt_blobs"]:
                print("store ISSUE: corrupt blob %s" % digest)
            for entry in store["dangling_refs"]:
                print("store ISSUE: dangling ref %s" % entry)
            for digest in store["healed_blobs"]:
                print("store healed: %s" % digest)
            if store["quarantined_blobs"]:
                print(
                    "store quarantined copies: %d"
                    % len(store["quarantined_blobs"])
                )
            if "would_gc" in store:
                print(
                    "store GC dry run would remove %d blobs"
                    % len(store["would_gc"])
                )
            if "gc" in store:
                print(
                    "store GC removed %d blobs, pruned %d leases"
                    % (
                        len(store["gc"]["removed"]),
                        len(store["gc"]["pruned_leases"]),
                    )
                )

    return report.exit_code


if __name__ == "__main__":
    # Direct-script invocation (`python tools/ckpt_fsck.py ...`) must
    # find the repo package without an installed distribution; `-m`
    # invocations already have the repo root on sys.path.
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    sys.exit(main())
