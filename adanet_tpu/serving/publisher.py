"""Generation publication: atomic, digest-verified serving exports.

The write side of the serving plane. After the searcher freezes
iteration t's winner, it publishes the servable artifact under the
model dir's generation chain:

    <model_dir>/serving/gen-<t>/
        serving.stablehlo               the hermetic program (core/export.py)
        serving.stablehlo.sha256        digest sidecar
        serving_signature.json          shapes/dtypes/platforms (+ fallback reason)
        serving_signature.json.sha256   digest sidecar
        generation.json                 {iteration_number, digests, checksum}

The export lands in a hidden staging directory first and is renamed
into place, so a reader (the `ModelPool` of a live server, or
`ckpt_fsck --json`) can never observe a half-written generation: the
`gen-<t>` directory either exists completely or not at all — the same
write-then-rename protocol checkpoint payloads use, one level up.
Publication is set-once per iteration: a generation that already exists
is never overwritten (a quarantined `gen-<t>.corrupt` does not block a
fresh publish of the retrained iteration t).
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import tempfile
from typing import Any, Callable, List, Optional, Tuple

from adanet_tpu.core import checkpoint as ckpt
from adanet_tpu.robustness import integrity

_LOG = logging.getLogger("adanet_tpu")

#: Subdirectory of the model dir holding the generation chain.
SERVING_SUBDIR = "serving"

_GEN_RE = re.compile(r"^gen-(\d+)$")


def serving_root(model_dir: str) -> str:
    return os.path.join(model_dir, SERVING_SUBDIR)


def generation_dirname(iteration_number: int) -> str:
    return "gen-%d" % iteration_number


def generation_dir(model_dir: str, iteration_number: int) -> str:
    return os.path.join(
        serving_root(model_dir), generation_dirname(iteration_number)
    )


def list_generations(model_dir: str) -> List[Tuple[int, str]]:
    """(iteration_number, absolute path) of published generations, sorted.

    Quarantined (`*.corrupt`) and staging directories never match the
    `gen-<t>` pattern, so readers only ever see complete publications.
    """
    root = serving_root(model_dir)
    try:
        entries = os.listdir(root)
    except OSError:
        return []
    out = []
    for name in entries:
        match = _GEN_RE.match(name)
        if match and os.path.isdir(os.path.join(root, name)):
            out.append((int(match.group(1)), os.path.join(root, name)))
    return sorted(out)


def write_generation_manifest(gen_dir: str, iteration_number: int) -> None:
    """Records `generation.json` over the artifacts already in `gen_dir`.

    Digest sidecars are written for every regular file present (the
    program and its signature), then the manifest binds them to the
    iteration number with a self-checksum — the contract
    `integrity.verify_serving_generation` checks before any flip.
    """
    digests = {}
    for name in sorted(os.listdir(gen_dir)):
        path = os.path.join(gen_dir, name)
        if not os.path.isfile(path) or name.endswith(ckpt.DIGEST_SUFFIX):
            continue
        if name == integrity.GENERATION_MANIFEST:
            continue
        # jaxlint: disable=JL019(gen_dir is the publisher's private mkdtemp staging dir until the atomic os.replace below; no concurrent writer exists before publication)
        with open(path, "rb") as f:
            data = f.read()
        digests[name] = ckpt.write_digest(gen_dir, name, data)
    missing = [
        name
        for name in integrity.REQUIRED_SERVING_FILES
        if name not in digests
    ]
    if missing:
        raise ValueError(
            "Serving export incomplete; missing %s in %s"
            % (missing, gen_dir)
        )
    obj = {
        "iteration_number": int(iteration_number),
        "digests": digests,
    }
    obj["checksum"] = ckpt.sha256_hex(
        json.dumps(obj, sort_keys=True).encode()
    )
    ckpt.write_json(gen_dir, integrity.GENERATION_MANIFEST, obj)


def publish_generation(
    model_dir: str,
    iteration_number: int,
    predict_fn: Callable,
    sample_features: Any,
    store=None,
    cascade=None,
) -> Optional[str]:
    """Exports and atomically publishes one serving generation.

    Returns the published directory, or None when this generation was
    already published (set-once: concurrent publishers and restarted
    searchers converge on one artifact).

    With a `cascade` (`serving.fleet.cascade.CascadeSpec`), the cheap
    member's program is exported alongside the full ensemble
    (`cascade.stablehlo`) and calibrated on the spec's held-out stream
    at publish time — temperature and confidence threshold land in the
    serving signature's `cascade` block, inside the same digest-sealed
    atomic publication, so a serving replica gets program + policy in
    one verify-on-load unit.

    With an `ArtifactStore` attached, the generation is ALSO published
    as a ref closure (`serving/<dir-id>-gen<t>`): every artifact blob
    lands in the content-addressed store with the gen dir recorded as a
    heal source, so serving pools can lease the closure against GC and
    a rotted file is recoverable from the store (and vice versa). The
    closure publication is idempotent and re-attempted when the gen dir
    already exists but the ref is missing — the crash window of a
    publisher SIGKILLed mid-publish.
    """
    final = generation_dir(model_dir, iteration_number)
    if os.path.isdir(final):
        if store is not None:
            publish_ref_closure(store, model_dir, iteration_number)
        return None
    root = serving_root(model_dir)
    os.makedirs(root, exist_ok=True)
    # Lazy: the export stack pulls in jax.export; pure readers of this
    # module (directory listing, fsck) must not pay for it.
    from adanet_tpu.core import export as export_lib

    staging = tempfile.mkdtemp(prefix=".stage-gen-", dir=root)
    try:
        export_lib.export_serving_program(
            staging, predict_fn, sample_features
        )
        if cascade is not None:
            _export_cascade(staging, predict_fn, sample_features, cascade)
        write_generation_manifest(staging, iteration_number)
        try:
            os.replace(staging, final)
        except OSError:
            # A concurrent publisher won the rename; either artifact is
            # the same deterministic export.
            if os.path.isdir(final):
                shutil.rmtree(staging, ignore_errors=True)
                return None
            raise
    except BaseException:
        shutil.rmtree(staging, ignore_errors=True)
        raise
    if store is not None:
        publish_ref_closure(store, model_dir, iteration_number)
    _LOG.info(
        "Published serving generation %d at %s", iteration_number, final
    )
    return final


def _export_cascade(
    staging: str, predict_fn: Callable, sample_features: Any, cascade
) -> None:
    """Exports + calibrates the cheap member inside the staging dir.

    Runs BEFORE the manifest is written and the directory renamed, so
    the cascade rides the same atomic, digest-sealed publication as
    the full program. Calibration failures abort the whole publish
    (the caller's staging cleanup) — a generation must never land with
    a program but no threshold, or vice versa.
    """
    import numpy as np

    import jax

    from adanet_tpu.core import export as export_lib
    from adanet_tpu.serving.fleet import cascade as cascade_lib

    cheap_dir = tempfile.mkdtemp(prefix=".cascade-", dir=staging)
    try:
        export_lib.export_serving_program(
            cheap_dir, cascade.predict_fn, sample_features
        )
        os.replace(
            os.path.join(cheap_dir, export_lib.SERVING_FILE),
            os.path.join(staging, export_lib.CASCADE_FILE),
        )
    finally:
        shutil.rmtree(cheap_dir, ignore_errors=True)
    features = cascade.calibration_features
    # Jitted: called bare, these forwards run op by op, which on an
    # accelerator is one dispatch (and one small compile) per op of
    # every member.
    cheap_out = jax.device_get(jax.jit(cascade.predict_fn)(features))
    full_out = jax.device_get(jax.jit(predict_fn)(features))

    def leaf(outputs):
        if isinstance(outputs, dict):
            return np.asarray(outputs[cascade.logits_key])
        return np.asarray(outputs)

    record = cascade_lib.calibrate(
        leaf(cheap_out),
        leaf(full_out),
        labels=cascade.calibration_labels,
        target_agreement=cascade.target_agreement,
        logits_key=cascade.logits_key,
        source=getattr(cascade, "source", "member"),
    )
    record["program"] = export_lib.CASCADE_FILE
    signature_path = os.path.join(staging, export_lib.SIGNATURE_FILE)
    with open(signature_path) as f:
        signature = json.load(f)
    signature[cascade_lib.SIGNATURE_KEY] = record
    ckpt.write_json(staging, export_lib.SIGNATURE_FILE, signature)


def serving_ref_name(model_dir: str, iteration_number: int) -> str:
    """Store ref name of one model dir's generation closure."""
    from adanet_tpu.store import keys as store_keys

    dir_id = store_keys.sha256_hex(
        os.path.abspath(model_dir).encode()
    )[:16]
    return store_keys.ref_name(dir_id, "gen%d" % int(iteration_number))


def publish_ref_closure(
    store, model_dir: str, iteration_number: int
) -> Optional[dict]:
    """Publishes a generation's artifacts as a store ref closure.

    Failure-isolated like the export itself: a store outage degrades to
    "this generation is not shared/healable", never a dead searcher.
    Returns the ref document, or None when publication failed or the
    generation dir is incomplete.
    """
    gen_dir = generation_dir(model_dir, iteration_number)
    name = serving_ref_name(model_dir, iteration_number)
    try:
        if store.get_ref("serving", name) is not None:
            return None  # set-once: the closure already landed
        blobs = {}
        sources = []
        for entry in sorted(os.listdir(gen_dir)):
            path = os.path.join(gen_dir, entry)
            if not os.path.isfile(path) or entry.endswith(
                ckpt.DIGEST_SUFFIX
            ):
                continue
            with open(path, "rb") as f:
                blobs[entry] = store.put(f.read())
            sources.append(path)
        if not blobs:
            return None
        return store.put_ref(
            "serving",
            name,
            blobs,
            meta={
                "model_dir": os.path.abspath(model_dir),
                "iteration_number": int(iteration_number),
            },
            sources=sources,
        )
    except Exception:
        _LOG.exception(
            "Store closure publication for serving generation %d "
            "failed; the on-disk generation is unaffected.",
            iteration_number,
        )
        return None
