"""Durable checkpointing for the AdaNet search loop.

TPU-native replacement for the reference's Saver/`tf.train.Checkpoint`
machinery (reference: adanet/core/estimator.py:236-331,
adanet/core/iteration.py:1188-1230). The reference grows a graph and
overwrites checkpoints between iterations; here state is functional, so a
checkpoint is just serialized pytrees plus a JSON manifest:

- `frozen-<t>.msgpack`: the winning ensemble of iteration t (params,
  mixture weights, complexity/shared payloads). One per completed
  iteration, enabling the deterministic rebuild chain: generators are
  replayed with the *restored* previous ensemble, exactly as the reference
  re-runs builders when reconstructing past iterations
  (reference: adanet/core/estimator.py:1785-1882).
- `ckpt-<step>.msgpack`: the full mid-iteration `IterationState` for
  preemption-safe resume (the analogue of `_TrainManager`'s durable state,
  reference: adanet/core/iteration.py:40-118).
  A state of more than `SHARD_THRESHOLD_BYTES` is written leaf by leaf
  instead: the file of that name is then an INDEX (per-leaf SHA-256,
  shape, dtype and place) of shard files in a directory beside it; see
  "sharded states" below.
- `checkpoint.json`: manifest holding iteration_number, global_step, and
  which files are current. The iteration number lives in the checkpoint in
  the reference too (estimator.py:877-879) — it is what lets training
  stop/restart anywhere.

Integrity contract (the self-healing half; see docs/robustness.md):
every payload write leaves a `<file>.sha256` digest sidecar, and the
manifest carries a `digests` map, a monotonically increasing
`generation`, a per-completed-iteration `history` chain, and a
`checksum` of its own canonical content. Reads verify before they
deserialize; corruption raises `CheckpointCorruptionError` instead of
returning garbage, and the restore path (via `robustness.integrity`)
quarantines the corrupt file (`*.corrupt`) and rolls back to the newest
intact generation. The previous manifest is retained at
`checkpoint.json.prev` so a torn manifest degrades to "one write ago",
and a model dir whose manifests are BOTH gone is reconstructed from the
architecture chain rather than silently restarted from scratch.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import hashlib
import json
import logging
import os
import re
import tempfile
from typing import Any, Dict, List, Optional

import jax
from flax import serialization

from adanet_tpu.observability import spans as spans_lib
from adanet_tpu.robustness import faults
from adanet_tpu.robustness.retry import retrying_open_read

_LOG = logging.getLogger("adanet_tpu")

MANIFEST = "checkpoint.json"
MANIFEST_PREV = "checkpoint.json.prev"
DIGEST_SUFFIX = ".sha256"
QUARANTINE_SUFFIX = ".corrupt"


class CheckpointCorruptionError(RuntimeError):
    """A checkpoint artifact failed verification or deserialization.

    Never retried (retrying cannot un-corrupt bytes); the restore path
    catches it, quarantines the file, and rolls back.
    """

    def __init__(self, path: str, reason: str):
        self.path = path
        self.reason = reason
        super().__init__("%s: %s" % (path, reason))


@dataclasses.dataclass
class CheckpointInfo:
    """Parsed manifest contents.

    `generation` increments on every manifest write (the write chain);
    `history` records one entry per COMPLETED iteration
    (`{"iteration_number", "global_step", "generation"}`) so rollback
    knows each iteration's end step; `digests` maps payload filenames to
    their SHA-256 hex digests (duplicated in sidecar files so either
    survives alone).

    Manifest v3 adds `store_refs`: payload filename -> the blob digest
    published to the shared content-addressed artifact store
    (`adanet_tpu.store`), making every checkpoint payload a store ref —
    healable from the store and shareable across searches. v2 manifests
    (no `version`/`store_refs` fields) read compatibly: the maps simply
    start empty.
    """

    iteration_number: int = 0
    global_step: int = 0
    iteration_state_file: Optional[str] = None
    replay_indices: List[int] = dataclasses.field(default_factory=list)
    generation: int = 0
    digests: Dict[str, str] = dataclasses.field(default_factory=dict)
    history: List[Dict[str, int]] = dataclasses.field(default_factory=list)
    version: int = 3
    store_refs: Dict[str, str] = dataclasses.field(default_factory=dict)


def _atomic_write_bytes(path: str, data: bytes) -> None:
    """Write-then-rename with fsync, so a host crash cannot leave the
    manifest pointing at a payload that never reached disk."""
    directory = os.path.dirname(path) or "."
    fd, tmp = tempfile.mkstemp(dir=directory)
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    try:
        dir_fd = os.open(directory, os.O_RDONLY)
    except OSError:  # pragma: no cover - exotic filesystems
        return
    try:
        os.fsync(dir_fd)
    finally:
        os.close(dir_fd)


def _atomic_write_json(path: str, obj) -> None:
    _atomic_write_bytes(path, json.dumps(obj, sort_keys=True).encode())


def write_json(model_dir: str, filename: str, obj) -> str:
    """Atomic (fsync'd) strict-JSON artifact write under `model_dir`."""
    path = os.path.join(model_dir, filename)
    _atomic_write_json(path, obj)
    return path


def read_json(model_dir: str, filename: str):
    """Reads a JSON artifact written by `write_json`; None when absent."""
    path = os.path.join(model_dir, filename)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


# ------------------------------------------------------------- integrity ops


def sha256_hex(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def digest_path(model_dir: str, filename: str) -> str:
    return os.path.join(model_dir, filename + DIGEST_SUFFIX)


def read_digest(model_dir: str, filename: str) -> Optional[str]:
    """The recorded SHA-256 of a payload file; None when no sidecar."""
    path = digest_path(model_dir, filename)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            text = f.read().strip()
    except OSError:
        return None
    return text if re.fullmatch(r"[0-9a-f]{64}", text) else None


def write_digest(model_dir: str, filename: str, data: bytes) -> str:
    """Writes `data`'s SHA-256 sidecar for `filename`; returns the hex.

    Public: the serving publisher records the same sidecars for exported
    generation artifacts so `verify_file` covers them too.
    """
    digest = sha256_hex(data)
    _atomic_write_bytes(
        digest_path(model_dir, filename), digest.encode()
    )
    return digest


def remove_digest(model_dir: str, filename: str) -> None:
    """Drops a payload's digest sidecar (rewrite protocol / cleanup).

    Payload writes go remove-sidecar -> payload -> sidecar: a crash in
    either window leaves NO sidecar (the decode check still validates
    the payload), never a stale digest that would falsely quarantine an
    intact file.
    """
    try:
        os.unlink(digest_path(model_dir, filename))
    except OSError:
        pass


def verify_file(
    model_dir: str, filename: str, expected: Optional[str] = None
) -> Optional[bool]:
    """Checks a payload against its recorded digest.

    Returns True/False on a verdict, or None when the file exists but no
    digest is recorded (legacy dirs: content checks must decide). A
    missing file is False. Of a sharded state the file is the INDEX: an
    intact one must also find every shard file there and as long as it
    says. The leaves' own digests are their readers': `restore_pytree`
    verifies every leaf before it uses one, and `corrupt_shards` reads
    them all for `tools/ckpt_fsck.py`, so that gigabytes are not read and
    hashed twice in a row at every resume.
    """
    path = os.path.join(model_dir, filename)
    expected = expected or read_digest(model_dir, filename)
    digest = hashlib.sha256()
    try:
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                digest.update(chunk)
    except FileNotFoundError:
        return False
    if expected is None:
        return None
    if digest.hexdigest() != expected:
        return False
    index = _read_index(path)
    return index is None or all(
        _file_holds(
            os.path.join(model_dir, index["directory"], entry["file"]),
            entry["offset"] + entry["bytes"],
        )
        for entry in index["leaves"] if "file" in entry
    )


def _file_holds(path: str, size: int) -> bool:
    try:
        return os.path.getsize(path) >= size
    except OSError:
        return False


def quarantine_file(model_dir: str, filename: str) -> Optional[str]:
    """Renames a corrupt artifact to `<name>.corrupt` (kept, diagnosable).

    Returns the quarantined name, or None when the file is absent. The
    digest sidecar rides along so post-mortems can see what was expected.
    """
    path = os.path.join(model_dir, filename)
    if not os.path.exists(path):
        return None
    target = filename + QUARANTINE_SUFFIX
    n = 0
    while os.path.exists(os.path.join(model_dir, target)):
        n += 1
        target = "%s%s.%d" % (filename, QUARANTINE_SUFFIX, n)
    index = _read_index(path)
    if index is not None:
        # The shards of a sharded state go aside with their index.
        shards = os.path.join(model_dir, index["directory"])
        try:
            # jaxlint: disable=JL013(moves already-landed shards aside with their index; nothing is written)
            os.replace(shards, shards + QUARANTINE_SUFFIX)
        except OSError:
            pass
    try:
        # jaxlint: disable=JL013(quarantine moves already-landed corrupt bytes aside; no payload is written, so there is nothing to stage or fsync)
        os.replace(path, os.path.join(model_dir, target))
    except FileNotFoundError:
        # Concurrent healing (several processes of a multi-host run all
        # read the same corrupt file): one process wins the rename, the
        # rest observe the file already gone — same outcome.
        return None
    sidecar = digest_path(model_dir, filename)
    try:
        # jaxlint: disable=JL013(sidecar rides along with the quarantined artifact; same no-payload rename)
        os.replace(
            sidecar, os.path.join(model_dir, target + DIGEST_SUFFIX)
        )
    except OSError:
        pass
    _LOG.error(
        "Quarantined corrupt checkpoint artifact %s -> %s", filename, target
    )
    return target


# --------------------------------------------------------------- manifest IO


def _manifest_obj(info: CheckpointInfo) -> Dict[str, Any]:
    obj = {
        "iteration_number": info.iteration_number,
        "global_step": info.global_step,
        "iteration_state_file": info.iteration_state_file,
        "replay_indices": info.replay_indices,
        "generation": info.generation,
        "digests": info.digests,
        "history": info.history,
        "version": info.version,
        "store_refs": info.store_refs,
    }
    obj["checksum"] = sha256_hex(
        json.dumps(obj, sort_keys=True).encode()
    )
    return obj


def _parse_manifest(data: bytes, path: str) -> CheckpointInfo:
    try:
        obj = json.loads(data)
    except ValueError as exc:
        raise CheckpointCorruptionError(path, "unparseable JSON: %s" % exc)
    if not isinstance(obj, dict) or "iteration_number" not in obj:
        raise CheckpointCorruptionError(path, "not a manifest object")
    checksum = obj.pop("checksum", None)
    if checksum is not None:
        expected = sha256_hex(json.dumps(obj, sort_keys=True).encode())
        if checksum != expected:
            raise CheckpointCorruptionError(
                path, "manifest checksum mismatch"
            )
    return CheckpointInfo(
        iteration_number=int(obj["iteration_number"]),
        global_step=int(obj["global_step"]),
        iteration_state_file=obj.get("iteration_state_file"),
        replay_indices=list(obj.get("replay_indices", [])),
        generation=int(obj.get("generation", 0)),
        digests=dict(obj.get("digests", {})),
        history=list(obj.get("history", [])),
        # v2 manifests carry neither field; they parse as an empty
        # store-ref map under version 2 (read-compat contract).
        version=int(obj.get("version", 2)),
        store_refs=dict(obj.get("store_refs", {})),
    )


def read_manifest(
    model_dir: str, quarantine: bool = True
) -> Optional[CheckpointInfo]:
    """Reads the manifest, healing over a corrupt main copy.

    Order: `checkpoint.json` (checksum-verified) → `checkpoint.json.prev`
    (the retained previous generation) → reconstruction from the
    architecture chain. A corrupt main manifest is quarantined unless
    `quarantine` is False (fsck's report-only mode and non-chief
    processes of a multi-host run read without mutating the dir; the
    chief's repair pass quarantines for everyone). Returns None only for
    a genuinely fresh model dir.
    """
    faults.trip("manifest.read")
    path = os.path.join(model_dir, MANIFEST)
    if os.path.exists(path):
        try:
            return _parse_manifest(
                retrying_open_read(path, label="manifest read"), path
            )
        except FileNotFoundError:
            # A concurrent heal (the chief's repair pass) quarantined
            # the corrupt file between the exists check and the read;
            # fall through to the same fallbacks it used.
            pass
        except CheckpointCorruptionError as exc:
            _LOG.error("Manifest corrupt (%s); trying fallbacks.", exc)
            if quarantine:
                quarantine_file(model_dir, MANIFEST)
    prev = os.path.join(model_dir, MANIFEST_PREV)
    if os.path.exists(prev):
        try:
            info = _parse_manifest(
                retrying_open_read(prev, label="manifest.prev read"), prev
            )
            _LOG.warning(
                "Recovered manifest from previous generation %d "
                "(checkpoint.json.prev).",
                info.generation,
            )
            return info
        except FileNotFoundError:
            pass
        except CheckpointCorruptionError as exc:
            _LOG.error("Previous manifest also corrupt (%s).", exc)
            if quarantine:
                quarantine_file(model_dir, MANIFEST_PREV)
    return _reconstruct_manifest(model_dir)


def manifest_intact(model_dir: str) -> bool:
    """True when `checkpoint.json` exists and parses checksum-clean."""
    path = os.path.join(model_dir, MANIFEST)
    try:
        _parse_manifest(
            retrying_open_read(path, label="manifest check"), path
        )
        return True
    except (FileNotFoundError, CheckpointCorruptionError):
        return False


def _reconstruct_manifest(model_dir: str) -> Optional[CheckpointInfo]:
    """Last-resort manifest from the on-disk artifact chain.

    Uses the longest contiguous prefix of parseable
    `architecture-<t>.json` files (each carries the global step at its
    iteration's end and the replay chain) plus the newest
    digest-verified `ckpt-*.msgpack` beyond that step. Returns None when
    the dir holds no artifacts at all (a fresh run).
    """
    if not os.path.isdir(model_dir):
        return None
    t = 0
    last_arch = None
    while True:
        path = os.path.join(model_dir, architecture_filename(t))
        if not os.path.exists(path):
            break
        try:
            with open(path) as f:
                last_arch = json.load(f)
        except (OSError, ValueError):
            break
        t += 1
    state_file = None
    global_step = int(last_arch.get("global_step", 0)) if last_arch else 0
    best_step = global_step
    for name in os.listdir(model_dir):
        match = re.fullmatch(r"ckpt-(\d+)\.msgpack", name)
        if not match:
            continue
        step = int(match.group(1))
        if step >= best_step and verify_file(model_dir, name):
            best_step = step
            state_file = name
    if t == 0 and state_file is None:
        return None
    info = CheckpointInfo(
        iteration_number=t,
        global_step=best_step if state_file else global_step,
        iteration_state_file=state_file,
        replay_indices=(
            list(last_arch.get("replay_indices", [])) if last_arch else []
        ),
    )
    _LOG.error(
        "Both manifests unusable; reconstructed from artifacts: "
        "iteration %d, global step %d, state file %s. Run "
        "tools/ckpt_fsck.py --repair to persist and verify.",
        info.iteration_number,
        info.global_step,
        info.iteration_state_file,
    )
    return info


def write_manifest(model_dir: str, info: CheckpointInfo) -> None:
    """Writes the manifest (atomic), retaining the previous generation.

    Bumps `info.generation`; the superseded manifest bytes move to
    `checkpoint.json.prev` so one torn/bit-rotted write never loses the
    whole chain.
    """
    os.makedirs(model_dir, exist_ok=True)
    path = os.path.join(model_dir, MANIFEST)
    if os.path.exists(path):
        try:
            _atomic_write_bytes(
                os.path.join(model_dir, MANIFEST_PREV),
                retrying_open_read(path, label="manifest backup"),
            )
        except OSError as exc:  # keep the write going; .prev is a bonus
            _LOG.warning("Could not retain previous manifest: %s", exc)
    info.generation += 1
    # Every write emits the current format (a restored v2 manifest is
    # upgraded in place; `store_refs` may legitimately be empty).
    info.version = max(int(info.version), 3)
    # Digests for files that no longer exist are dead weight (superseded
    # ckpt-* files are deleted); drop them as we go.
    info.digests = {
        name: digest
        for name, digest in info.digests.items()
        if os.path.exists(os.path.join(model_dir, name))
    }
    _atomic_write_json(path, _manifest_obj(info))


# ------------------------------------------------------------ payload IO


def save_pytree(
    model_dir: str,
    filename: str,
    payload: Any,
    shard_threshold_bytes: Optional[int] = None,
) -> str:
    """Serializes a pytree (flax state-dict encoding) atomically.

    A payload of more than `SHARD_THRESHOLD_BYTES` is written leaf by
    leaf (`_save_sharded`); `shard_threshold_bytes` lowers that for a
    test. Returns the SHA-256 hex digest of the file named `filename`
    (also written to the sidecar), for callers recording it in the
    manifest."""
    os.makedirs(model_dir, exist_ok=True)
    tracer = spans_lib.tracer()
    if shard_threshold_bytes is None:
        shard_threshold_bytes = SHARD_THRESHOLD_BYTES
    size = sum(
        getattr(leaf, "nbytes", 0)
        for leaf in jax.tree_util.tree_leaves(payload)
    )
    if size > shard_threshold_bytes:
        # The drain of the dispatch queue, as below; nothing is copied.
        with tracer.span("checkpoint.fetch", bytes=size):
            jax.block_until_ready(payload)
        with tracer.span("checkpoint.write") as write_span:
            return _save_sharded(model_dir, filename, payload, write_span)
    # The fetch waits for every step still in flight on the device: it
    # is the drain of the dispatch queue, and apart from the write.
    with tracer.span("checkpoint.fetch") as fetch_span:
        host = jax.device_get(payload)
        if tracer.enabled:
            fetch_span.set(
                bytes=sum(
                    getattr(leaf, "nbytes", 0)
                    for leaf in jax.tree_util.tree_leaves(host)
                )
            )
    with tracer.span("checkpoint.write") as write_span:
        data = serialization.to_bytes(host)
        write_span.set(bytes=len(data))
        path = os.path.join(model_dir, filename)
        faults.trip("checkpoint.write", path=path, data=data)
        remove_digest(model_dir, filename)
        _atomic_write_bytes(path, data)
        return write_digest(model_dir, filename, data)


# ---------------------------------------------------------- sharded states
#
# A state of gigabytes is not serialized whole (three host copies of it,
# hashed and written by one thread). Its leaves go, each from its own
# buffer, into shard files of about `SHARD_FILE_BYTES` in a directory of
# their own, a few files at a time on a small thread pool; every file is
# fsync'd, then the directory. The file the manifest names is then an
# INDEX: a magic line and JSON holding the state dict's skeleton, and for
# every leaf its shard, offset, bytes, dtype, shape and SHA-256. The
# index's own digest is the manifest's and the sidecar's, as for any
# payload, and its atomic rename is what publishes the state: until then
# the previous generation is what a reader finds. A reader verifies the
# index, then every leaf against it, before a byte is used.

SHARD_THRESHOLD_BYTES = 1 << 30
SHARD_FILE_BYTES = 64 << 20
SHARD_THREADS = 8
SHARD_MAGIC = b"ADANET-SHARDED-STATE 1\n"
_SHARDS_INFIX = ".shards-"


def _shard_threads() -> int:
    return max(1, min(SHARD_THREADS, os.cpu_count() or 1))


def _shard_pool():
    return concurrent.futures.ThreadPoolExecutor(_shard_threads())


def _read_index(path: str) -> Optional[Dict[str, Any]]:
    """The index a sharded state's file holds; None for any other file
    (and for one that cannot be read: its digest check says so)."""
    try:
        with open(path, "rb") as f:
            if f.read(len(SHARD_MAGIC)) != SHARD_MAGIC:
                return None
            return json.loads(f.read())
    except (OSError, ValueError):
        return None


def shard_stats(model_dir: str, filename: str) -> Dict[str, int]:
    """{bytes, leaves, threads} of the state a file names, for spans."""
    path = os.path.join(model_dir, filename)
    index = _read_index(path)
    if index is None:
        return {"bytes": os.path.getsize(path)}
    return {
        "bytes": sum(leaf.get("bytes", 0) for leaf in index["leaves"]),
        "leaves": len(index["leaves"]),
        "threads": _shard_threads(),
    }


def shard_directories(model_dir: str, filename: str) -> List[str]:
    """The shard directories written for `filename` that are still there
    under their own names (not set aside as `.corrupt` or `.stale`)."""
    try:
        names = os.listdir(model_dir)
    except OSError:
        return []
    live = re.compile(re.escape(filename + _SHARDS_INFIX) + r"[^.]+")
    return sorted(
        name for name in names
        if live.fullmatch(name)
        and os.path.isdir(os.path.join(model_dir, name))
    )


def _skeleton(tree, leaves):
    """The state dict with each leaf replaced by its number in `leaves`."""
    if isinstance(tree, dict):
        return {key: _skeleton(value, leaves) for key, value in tree.items()}
    leaves.append(tree)
    return len(leaves) - 1


def _fill(skeleton, values):
    if isinstance(skeleton, dict):
        return {key: _fill(value, values) for key, value in skeleton.items()}
    return values[skeleton]


def _leaf_paths(skeleton, prefix=""):
    if isinstance(skeleton, dict):
        out = {}
        for key, value in skeleton.items():
            out.update(_leaf_paths(value, prefix + "/" + key if prefix else key))
        return out
    return {skeleton: prefix}


def _save_sharded(model_dir, filename, payload, span) -> str:
    import numpy as np

    leaves: List[Any] = []
    skeleton = _skeleton(serialization.to_state_dict(payload), leaves)
    paths = _leaf_paths(skeleton)
    entries: List[Dict[str, Any]] = []
    files: List[List[int]] = [[]]
    held = 0
    for number, leaf in enumerate(leaves):
        if not hasattr(leaf, "dtype") or not hasattr(leaf, "shape"):
            # A Python scalar or None rides in the index itself.
            entries.append({"path": paths[number], "value": leaf})
            continue
        size = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        if files[-1] and held + size > SHARD_FILE_BYTES:
            files.append([])
            held = 0
        entries.append({
            "path": paths[number],
            "file": "%05d.bin" % (len(files) - 1),
            "offset": held,
            "bytes": size,
            "dtype": np.dtype(leaf.dtype).name,
            "shape": [int(d) for d in leaf.shape],
        })
        files[-1].append(number)
        held += size
    directory = tempfile.mkdtemp(
        dir=model_dir, prefix=filename + _SHARDS_INFIX
    )

    def write_file(numbers):
        if not numbers:
            return
        path = os.path.join(directory, entries[numbers[0]]["file"])
        # jaxlint: disable=JL013(a shard lands in a directory that no index names yet; it is fsync'd here and the index's atomic rename publishes it)
        with open(path, "wb") as f:
            for number in numbers:
                # One leaf at a time: fetched, hashed and written from
                # the one host buffer, which then goes.
                raw = np.ascontiguousarray(
                    np.asarray(leaves[number])
                ).reshape(-1).view(np.uint8)
                entries[number]["sha256"] = hashlib.sha256(raw).hexdigest()
                f.write(raw)
            f.flush()
            os.fsync(f.fileno())

    try:
        with _shard_pool() as pool:
            list(pool.map(write_file, files))
        dir_fd = os.open(directory, os.O_RDONLY)
        try:
            os.fsync(dir_fd)
        finally:
            os.close(dir_fd)
        data = SHARD_MAGIC + json.dumps({
            "directory": os.path.basename(directory),
            "tree": skeleton,
            "leaves": entries,
        }, sort_keys=True).encode()
        span.set(
            bytes=sum(entry.get("bytes", 0) for entry in entries),
            leaves=len(entries), threads=_shard_threads(), files=len(files),
        )
        path = os.path.join(model_dir, filename)
        # Between the shards and the publish: a kill here leaves the
        # previous generation as it was.
        faults.trip("checkpoint.write", path=path, data=data)
        remove_digest(model_dir, filename)
        _atomic_write_bytes(path, data)
        digest = write_digest(model_dir, filename, data)
    except BaseException:
        import shutil

        shutil.rmtree(directory, ignore_errors=True)
        raise
    # Shards of an earlier save under the same name are now unreferenced.
    remove_shards(model_dir, filename, keep=os.path.basename(directory))
    return digest


def remove_shards(
    model_dir: str, filename: str, keep: Optional[str] = None
) -> None:
    """Deletes the shard directories of `filename` (all but `keep`)."""
    import shutil

    for name in shard_directories(model_dir, filename):
        if name != keep:
            shutil.rmtree(os.path.join(model_dir, name), ignore_errors=True)


def _dtype(name: str):
    """The numpy dtype of a name an index holds (bfloat16 and its kin are
    `ml_dtypes`' and not numpy's own)."""
    import numpy as np

    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _read_shard_file(model_dir, index, name, take=None):
    """Verifies the leaves of one shard file, in file order. `take(entry,
    array)` receives each verified leaf as a read-only view of the file's
    mapping, which lasts until what `take` returned of this file's leaves
    is ready (a transfer to the device has read it). Raises
    `CheckpointCorruptionError` naming the shard at the first leaf whose
    bytes are missing or do not hash to the index's digest.

    The file is MAPPED, not read into fresh memory: on the chip machine a
    GiB read into a new buffer costs 1.2 s on 1 thread and on 8 (the page
    faults of the new memory), hashed from a mapping of the page cache's
    own pages 0.11 s on 8 threads (PERF.md section 6, PR 34)."""
    import mmap

    import numpy as np

    path = os.path.join(model_dir, index["directory"], name)
    wanted = sorted(
        (entry for entry in index["leaves"] if entry.get("file") == name),
        key=lambda entry: entry["offset"],
    )
    taken = []
    try:
        with open(path, "rb") as f:
            size = os.fstat(f.fileno()).st_size
            held = (
                mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
                if size else b""
            )
        for entry in wanted:
            if entry["offset"] + entry["bytes"] > size:
                raise CheckpointCorruptionError(
                    path, "leaf %s is cut short (%d of %d bytes)"
                    % (
                        entry["path"], max(0, size - entry["offset"]),
                        entry["bytes"],
                    )
                )
            raw = np.frombuffer(
                held, np.uint8, entry["bytes"], entry["offset"]
            )
            digest = hashlib.sha256(raw).hexdigest()
            if digest != entry["sha256"]:
                raise CheckpointCorruptionError(
                    path,
                    "SHA-256 mismatch in leaf %s (expected %s..., got "
                    "%s...): torn write or bit rot"
                    % (entry["path"], entry["sha256"][:12], digest[:12]),
                )
            if take is not None:
                taken.append(take(
                    entry,
                    raw.view(_dtype(entry["dtype"])).reshape(entry["shape"]),
                ))
    except OSError as exc:
        raise CheckpointCorruptionError(path, "unreadable shard: %s" % exc)
    finally:
        # The mapping goes with its last view; nothing may still read it.
        jax.block_until_ready(taken)


def _shard_files(index):
    return sorted({e["file"] for e in index["leaves"] if "file" in e})


def corrupt_shards(model_dir: str, filename: str) -> List[str]:
    """Messages of the shard files of the sharded state `filename` whose
    leaves fail verification (every leaf read and hashed against the
    index, files in parallel); none for a state in one file."""
    index = _read_index(os.path.join(model_dir, filename))
    if index is None:
        return []

    def check(name):
        try:
            _read_shard_file(model_dir, index, name)
        except CheckpointCorruptionError as exc:
            _LOG.error("Corrupt shard: %s", exc)
            return str(exc)
        return None

    with _shard_pool() as pool:
        return [m for m in pool.map(check, _shard_files(index)) if m]


def _restore_sharded(model_dir, index):
    """The state dict of a sharded state: every leaf verified, then put on
    the device (single-process runs), a few files at a time."""
    place = jax.process_count() == 1
    values: Dict[int, Any] = {}
    numbers = {id(entry): n for n, entry in enumerate(index["leaves"])}

    def take(entry, array):
        # Never an alias of the file's mapping, on any backend.
        value = (
            jax.device_put(array, may_alias=False) if place
            else array.copy()
        )
        values[numbers[id(entry)]] = value
        return value

    for number, entry in enumerate(index["leaves"]):
        if "file" not in entry:
            values[number] = entry["value"]
    with _shard_pool() as pool:
        list(pool.map(
            lambda name: _read_shard_file(model_dir, index, name, take),
            _shard_files(index),
        ))
    return _fill(index["tree"], values)


def _read_verified(model_dir: str, filename: str) -> bytes:
    path = os.path.join(model_dir, filename)
    data = retrying_open_read(path, label="checkpoint read")
    expected = read_digest(model_dir, filename)
    if expected is not None and sha256_hex(data) != expected:
        raise CheckpointCorruptionError(
            path,
            "SHA-256 mismatch (expected %s..., got %s...): torn write or "
            "bit rot" % (expected[:12], sha256_hex(data)[:12]),
        )
    return data


def restore_pytree(model_dir: str, filename: str, target: Any) -> Any:
    """Restores a pytree saved by `save_pytree` onto a matching target.

    The target gives the STRUCTURE, and each leaf its shape and dtype:
    real arrays or `jax.ShapeDtypeStruct`s (`Iteration.state_template`)
    serve alike, and nothing of the target is fetched. Every restored
    leaf is checked against the target's shape and dtype, since a
    caller holding a template has no real arrays left to disagree with.

    Verifies the payload digest before deserializing; wraps decode
    failures in `CheckpointCorruptionError`. Legacy NASNet checkpoints
    missing the `batch_stats` `count` leaf (written before the
    warmup-scheduled BatchNorm) are migrated in flight: the template
    tells us exactly which count leaves are expected, and absent ones
    are injected as converged (see `_inject_missing_count`).
    """
    path = os.path.join(model_dir, filename)
    data = _read_verified(model_dir, filename)
    if data.startswith(SHARD_MAGIC):
        try:
            index = json.loads(data[len(SHARD_MAGIC):])
        except ValueError as exc:
            raise CheckpointCorruptionError(
                path, "unparseable shard index: %s" % exc
            ) from exc
        state_dict = _restore_sharded(model_dir, index)
        try:
            restored = serialization.from_state_dict(target, state_dict)
            _check_leaves(target, restored)
        except Exception as exc:
            raise CheckpointCorruptionError(
                path, "state does not match target structure: %s" % exc
            ) from exc
        return restored
    try:
        state_dict = serialization.msgpack_restore(data)
    except Exception as exc:
        raise CheckpointCorruptionError(
            path, "undecodable msgpack: %s" % exc
        ) from exc
    state_dict, injected = _inject_missing_count(
        state_dict, serialization.to_state_dict(target)
    )
    if injected:
        _LOG.warning(
            "Migrated legacy checkpoint %s: injected %d missing "
            "batch_stats `count` leaves (legacy statistics treated as "
            "converged).",
            filename,
            injected,
        )
    try:
        restored = serialization.from_state_dict(target, state_dict)
        _check_leaves(target, restored)
    except Exception as exc:
        raise CheckpointCorruptionError(
            path, "state does not match target structure: %s" % exc
        ) from exc
    return restored


def _check_leaves(target, restored) -> None:
    """Raises ValueError where a restored leaf's shape or dtype is not
    its target's. Python scalars carry no dtype of their own (msgpack
    keeps a float as a float) and pass on their shape."""
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten_with_path(target)
    for (key_path, want), got in zip(
        leaves, treedef.flatten_up_to(restored)
    ):
        if not hasattr(want, "shape") or not hasattr(want, "dtype"):
            continue
        if not isinstance(
            got, (np.ndarray, np.generic, jax.Array, int, float)
        ):
            fault = "holds a %s" % type(got).__name__
        elif np.shape(got) != tuple(want.shape):
            fault = "has shape %s" % (np.shape(got),)
        elif hasattr(got, "dtype") and got.dtype != want.dtype:
            fault = "has dtype %s" % got.dtype
        else:
            continue
        raise ValueError(
            "%s %s, the target an array of shape %s and dtype %s"
            % (
                jax.tree_util.keystr(key_path),
                fault,
                tuple(want.shape),
                want.dtype,
            )
        )


def save_payload(model_dir: str, filename: str, payload: Any) -> str:
    """Serializes a plain payload (dicts/lists/arrays) without re-keying.

    Unlike `save_pytree`, lists stay lists (`to_bytes` would convert them to
    string-keyed dicts via the state-dict encoding). Returns the
    payload's SHA-256 hex digest, like `save_pytree`.
    """
    os.makedirs(model_dir, exist_ok=True)
    data = serialization.msgpack_serialize(jax.device_get(payload))
    path = os.path.join(model_dir, filename)
    faults.trip("checkpoint.write", path=path, data=data)
    remove_digest(model_dir, filename)
    _atomic_write_bytes(path, data)
    return write_digest(model_dir, filename, data)


def write_payload_bytes(model_dir: str, filename: str, data: bytes) -> str:
    """Lands already-serialized payload bytes with the full protocol
    (remove sidecar -> atomic write -> sidecar); returns the digest.

    Public for the warm-start replay path (`adanet_tpu.store`): a
    payload fetched from the content-addressed store is grafted into a
    model dir byte-identically, so digests — and therefore store blob
    identity — are preserved across the round trip.
    """
    os.makedirs(model_dir, exist_ok=True)
    path = os.path.join(model_dir, filename)
    faults.trip("checkpoint.write", path=path, data=data)
    remove_digest(model_dir, filename)
    _atomic_write_bytes(path, data)
    return write_digest(model_dir, filename, data)


def restore_payload(model_dir: str, filename: str) -> Any:
    """Restores a payload as plain dicts/lists (no target structure needed).

    Used for frozen-ensemble payloads, which are plain nested dicts of
    arrays/primitives by construction. Digest-verified like
    `restore_pytree`.
    """
    path = os.path.join(model_dir, filename)
    data = _read_verified(model_dir, filename)
    try:
        return serialization.msgpack_restore(data)
    except Exception as exc:
        raise CheckpointCorruptionError(
            path, "undecodable msgpack: %s" % exc
        ) from exc


# ----------------------------------------------- legacy batch_stats shim


def _legacy_converged_count() -> float:
    """The `count` at which the warmup-scheduled BatchNorm momentum has
    converged to its asymptote: checkpoints from before the count leaf
    existed carry long-run statistics, so "converged" is the faithful
    migration (ADVICE r5)."""
    try:
        from adanet_tpu.models.nasnet import legacy_batch_stats_count

        return float(legacy_batch_stats_count())
    except Exception:  # models extra not importable: use the defaults
        momentum, warmup = 0.9997, 10.0
        return warmup * momentum / (1.0 - momentum)


def _inject_missing_count(state_dict, template):
    """Template-guided migration of legacy BatchNorm statistics.

    Wherever the TEMPLATE has a `{"mean", "var", "count"}` stats dict
    and the restored state has the mean/var but no count (a pre-round-5
    NASNet checkpoint), a converged count scalar is injected. Guided by
    the template, so collections that legitimately lack a count (e.g.
    `nn.BatchNorm`) are never touched. Returns (migrated, n_injected).
    """
    import numpy as np

    injected = 0

    def walk(state, tmpl):
        nonlocal injected
        if not isinstance(state, dict) or not isinstance(tmpl, dict):
            return state
        if (
            "count" in tmpl
            and "count" not in state
            and "mean" in tmpl
            and "var" in tmpl
            and "mean" in state
            and "var" in state
        ):
            state = dict(state)
            state["count"] = np.asarray(
                _legacy_converged_count(), np.float32
            )
            injected += 1
        return {
            key: (
                walk(value, tmpl[key]) if key in tmpl else value
            )
            for key, value in state.items()
        }

    return walk(state_dict, template), injected


# ------------------------------------------------------------- file naming


def frozen_filename(iteration_number: int) -> str:
    return "frozen-%d.msgpack" % iteration_number


def iteration_state_filename(global_step: int) -> str:
    return "ckpt-%d.msgpack" % global_step


def final_state_filename(iteration_number: int) -> str:
    """Retained end-of-iteration candidate state (all candidates, not just
    the frozen winner), enabling per-candidate evaluation after the
    iteration completes — the analogue of the reference's per-candidate
    eval dirs surviving every bookkeeping phase
    (reference: adanet/core/estimator.py:1683-1723)."""
    return "iteration-final-%d.msgpack" % iteration_number


def candidate_metrics_filename(iteration_number: int) -> str:
    """Per-candidate selection metrics persisted at every iteration end BY
    DEFAULT (params-free, a few hundred bytes) — the always-available half
    of the reference's per-candidate eval dirs
    (reference: adanet/core/estimator.py:1683-1723);
    `keep_candidate_states=True` additionally retains full states for
    post-hoc re-evaluation on new data."""
    return "candidate-metrics-%d.json" % iteration_number


def architecture_filename(iteration_number: int) -> str:
    """Reference layout: `<model_dir>/architecture-<t>.json`
    (reference: adanet/core/estimator.py:1725-1747)."""
    return "architecture-%d.json" % iteration_number


# ------------------------------------------------------ frozen (de)serialize


def frozen_to_payload(frozen) -> Dict[str, Any]:
    """Host-side serializable payload of a `FrozenEnsemble`.

    Modules and the architecture are NOT stored: they are rebuilt
    deterministically from the generator + architecture JSON; this payload
    restores the numeric state onto that rebuilt skeleton.
    """
    members = []
    for ws in frozen.weighted_subnetworks:
        members.append(
            {
                "params": jax.device_get(ws.subnetwork.params),
                "weight": (
                    {}
                    if ws.weight is None
                    else {"value": jax.device_get(ws.weight)}
                ),
                "complexity": float(ws.subnetwork.complexity),
                "shared": (
                    {}
                    if ws.subnetwork.shared is None
                    else {"value": jax.device_get(ws.subnetwork.shared)}
                ),
            }
        )
    return {
        "members": members,
        "ensembler_params": (
            {}
            if frozen.ensembler_params is None
            else {"value": jax.device_get(frozen.ensembler_params)}
        ),
        # Optional-field encoding ({} = unset), like `weight`/`shared`
        # above; older payloads used an inf sentinel, still read below.
        "final_ema": (
            {}
            if frozen.final_ema is None
            else {"value": float(frozen.final_ema)}
        ),
    }


def payload_into_frozen(payload: Dict[str, Any], frozen) -> None:
    """Grafts a restored payload's values onto a rebuilt `FrozenEnsemble`.

    `frozen` must have the same member structure (same builders rebuilt in
    the same order); its placeholder params are replaced in-place.
    """
    members = payload["members"]
    if len(members) != len(frozen.weighted_subnetworks):
        raise ValueError(
            "Checkpoint has %d members but rebuilt ensemble has %d. The "
            "generator is not deterministic or the model_dir is stale."
            % (len(members), len(frozen.weighted_subnetworks))
        )
    for entry, ws in zip(members, frozen.weighted_subnetworks):
        ws.subnetwork.params = entry["params"]
        ws.weight = entry["weight"].get("value")
        ws.subnetwork.complexity = entry["complexity"]
        shared = entry["shared"]
        ws.subnetwork.shared = shared.get("value") if shared else None
    frozen.ensembler_params = payload["ensembler_params"].get("value")
    ema = payload.get("final_ema")
    if isinstance(ema, dict):
        frozen.final_ema = (
            float(ema["value"]) if "value" in ema else None
        )
    else:  # legacy inf-sentinel payloads (round 1)
        frozen.final_ema = (
            None if ema is None or ema == float("inf") else float(ema)
        )
