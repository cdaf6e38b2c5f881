"""Reads and rewrites what `Estimator.train` leaves in its `model_dir`.

The format is the program's durable interface, written down here so that
the benchmark touches no code of the program for it: `checkpoint.json`
(JSON with a SHA-256 `checksum` over its other keys, `digests` per payload
file, `iteration_state_file`), the state file itself (flax msgpack of the
`IterationState` state-dict) and a `<file>.sha256` sidecar.
"""

from __future__ import annotations

import hashlib
import json
import os

from flax import serialization

MANIFEST = "checkpoint.json"


def read_manifest(model_dir):
    with open(os.path.join(model_dir, MANIFEST)) as f:
        return json.load(f)


def global_step(model_dir):
    if not os.path.exists(os.path.join(model_dir, MANIFEST)):
        return 0
    return int(read_manifest(model_dir)["global_step"])


def read_state(model_dir):
    """The newest mid-iteration state as nested dicts of numpy arrays."""
    name = read_manifest(model_dir)["iteration_state_file"]
    with open(os.path.join(model_dir, name), "rb") as f:
        return serialization.msgpack_restore(f.read())


def write_state(model_dir, state):
    """Replaces the newest state file and re-seals manifest and sidecar."""
    manifest = read_manifest(model_dir)
    name = manifest["iteration_state_file"]
    data = serialization.msgpack_serialize(state)
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
    with open(os.path.join(model_dir, MANIFEST), "w") as f:
        json.dump(manifest, f, sort_keys=True)


def flatten(tree, prefix=""):
    """{"a": {"b": x}} -> {"a/b": x}."""
    flat = {}
    for key, value in tree.items():
        path = "%s/%s" % (prefix, key) if prefix else str(key)
        if isinstance(value, dict):
            flat.update(flatten(value, path))
        else:
            flat[path] = value
    return flat


def set_leaf(tree, path, value):
    keys = path.split("/")
    for key in keys[:-1]:
        tree = tree[key]
    tree[keys[-1]] = value


def find_subtree(tree, name):
    """The first dict stored under key `name`, depth first."""
    for key, value in tree.items():
        if key == name and isinstance(value, dict):
            return value
        if isinstance(value, dict):
            found = find_subtree(value, name)
            if found is not None:
                return found
    return None
