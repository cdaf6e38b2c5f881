"""Reads and plants a state that `Estimator.train` saved leaf by leaf.

A state of more than a gibibyte is not one msgpack file: the file the
manifest names (`iteration_state_file`) is then an INDEX: the line
`ADANET-SHARDED-STATE 1` and JSON with `directory` (the shard files'
directory beside it), `tree` (the state dict with each leaf replaced by
its number) and `leaves` (for each: `path`, and either `value` or `file`,
`offset`, `bytes`, `dtype`, `shape`, `sha256`). The index's SHA-256 is in
the manifest's `digests` and in the `.sha256` sidecar. This file writes
that format down for the benchmark, beside `ckpt_io.py` (which keeps the
one-file form); a state in the one-file form is read and planted through
it, so a check need not know which form it met.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import json
import os
import shutil
import tempfile

import numpy as np

from benchmarks import ckpt_io

MAGIC = b"ADANET-SHARDED-STATE 1\n"
THREADS = 8


def _index_path(model_dir):
    return os.path.join(
        model_dir, ckpt_io.read_manifest(model_dir)["iteration_state_file"]
    )


def read_index(model_dir):
    """The newest state's index, or None where it is one msgpack file."""
    with open(_index_path(model_dir), "rb") as f:
        if f.read(len(MAGIC)) != MAGIC:
            return None
        return json.loads(f.read())


def _dtype(name):
    import jax.numpy as jnp

    return jnp.dtype(name)


def _read_file(directory, entries, want):
    out = {}
    with open(os.path.join(directory, entries[0]["file"]), "rb") as f:
        for entry in entries:
            if not want(entry["path"]):
                continue
            raw = np.empty((entry["bytes"],), np.uint8)
            f.seek(entry["offset"])
            if f.readinto(raw) != entry["bytes"] or (
                hashlib.sha256(raw).hexdigest() != entry["sha256"]
            ):
                raise SystemExit(
                    "benchmarks: leaf %s of %s does not hash to its index"
                    % (entry["path"], directory)
                )
            out[entry["path"]] = raw.view(_dtype(entry["dtype"])).reshape(
                entry["shape"]
            )
    return out


def _by_file(index):
    files = {}
    for entry in index["leaves"]:
        if "file" in entry:
            files.setdefault(entry["file"], []).append(entry)
    return [
        sorted(entries, key=lambda e: e["offset"])
        for _, entries in sorted(files.items())
    ]


def read_leaves(model_dir, want=lambda path: True):
    """{path: array or Python value} of the newest state's leaves whose
    path `want` accepts, each verified against the index."""
    index = read_index(model_dir)
    if index is None:
        flat = ckpt_io.flatten(ckpt_io.read_state(model_dir))
        return {k: v for k, v in flat.items() if want(k)}
    directory = os.path.join(model_dir, index["directory"])
    out = {
        entry["path"]: entry["value"] for entry in index["leaves"]
        if "file" not in entry and want(entry["path"])
    }
    with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
        for part in pool.map(
            lambda entries: _read_file(directory, entries, want),
            _by_file(index),
        ):
            out.update(part)
    return out


def _seal(model_dir, data):
    """Lands the state file's new bytes and re-seals sidecar and manifest."""
    manifest = ckpt_io.read_manifest(model_dir)
    name = manifest["iteration_state_file"]
    digest = hashlib.sha256(data).hexdigest()
    with open(os.path.join(model_dir, name), "wb") as f:
        f.write(data)
    with open(os.path.join(model_dir, name + ".sha256"), "w") as f:
        f.write(digest)
    manifest["digests"][name] = digest
    manifest.pop("checksum", None)
    manifest["checksum"] = hashlib.sha256(
        json.dumps(manifest, sort_keys=True).encode()
    ).hexdigest()
    with open(os.path.join(model_dir, ckpt_io.MANIFEST), "w") as f:
        json.dump(manifest, f, sort_keys=True)


def write_leaves(model_dir, replace):
    """Replaces the leaves `{path: array}` of the newest state (same shape
    and dtype) and re-seals it. A shard file that holds none of them is
    linked, not copied."""
    index = read_index(model_dir)
    if index is None:
        state = ckpt_io.read_state(model_dir)
        for path, value in replace.items():
            ckpt_io.set_leaf(state, path, value)
        ckpt_io.write_state(model_dir, state)
        return
    missing = set(replace) - {entry["path"] for entry in index["leaves"]}
    if missing:
        raise SystemExit(
            "benchmarks: the state has no leaves %s" % sorted(missing)[:5]
        )
    name = os.path.basename(_index_path(model_dir))
    old = os.path.join(model_dir, index["directory"])
    new = tempfile.mkdtemp(dir=model_dir, prefix=name + ".shards-")

    def rewrite(entries):
        target = os.path.join(new, entries[0]["file"])
        if not any(entry["path"] in replace for entry in entries):
            os.link(os.path.join(old, entries[0]["file"]), target)
            return
        kept = _read_file(
            old, entries, lambda path: path not in replace
        )
        with open(target, "wb") as f:
            for entry in entries:
                value = replace.get(entry["path"], kept.get(entry["path"]))
                raw = np.ascontiguousarray(
                    np.asarray(value, _dtype(entry["dtype"]))
                ).reshape(-1).view(np.uint8)
                assert raw.size == entry["bytes"], entry["path"]
                entry["sha256"] = hashlib.sha256(raw).hexdigest()
                f.write(raw)

    with concurrent.futures.ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(rewrite, _by_file(index)))
    index["directory"] = os.path.basename(new)
    _seal(model_dir, MAGIC + json.dumps(index, sort_keys=True).encode())
    shutil.rmtree(old, ignore_errors=True)
