"""The feed is found by name: `feeds/<name>.py` gives the ring, `Feed`
keeps the position, the pull hook and the spans.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_feed.py -q

The `images` feed gives, byte for byte, the arrays that `Feed` made itself
before the feed had a name (the digests are of the parent's, c27f8fd).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.feed import Feed

BEFORE = {
    3: "823f0d9f5171b6c683210bbefca2e81a9dd6dd764a077b6e5774d9cb61c383a8",
    2**31 + 11:
        "a20ee7c763b0e336ab9232675e275d8b75f13f6199af00e9f8dbc3cd34f18196",
}


def _digest(ring):
    digest = hashlib.sha256()
    for features, labels in ring:
        digest.update(features["image"].tobytes())
        digest.update(labels.tobytes())
    return digest.hexdigest()


@pytest.mark.parametrize("seed", sorted(BEFORE))
def test_the_images_feed_gives_the_arrays_it_gave_before(seed):
    cell = run.Cell("rehearsal_tiny")
    assert cell.feed.__name__.endswith("feeds.images")
    named = Feed(seed, cell.traffic, cell.config["sizes"], ring=cell.feed.ring)
    default = Feed(seed, cell.traffic, cell.config["sizes"])
    assert _digest(named.ring) == _digest(default.ring) == BEFORE[seed]


def test_the_feed_keeps_position_and_pull_hook_whatever_the_ring():
    pulls = []

    def tokens(rng, traffic, sizes):
        return [
            ({"tokens": rng.integers(0, 9, (traffic["batch"], 5))}, None)
            for _ in range(traffic["ring"])
        ]

    feed = Feed(1, {"batch": 2, "ring": 3}, {}, ring=tokens)
    feed.position, feed.on_pull = 4, lambda: pulls.append(feed.position)
    features, _ = next(feed)
    assert pulls == [4] and feed.position == 5
    np.testing.assert_array_equal(
        features["tokens"], feed.batch_at(4)[0]["tokens"]
    )
    assert feed.batch_at(4) is feed.ring[1]


def test_a_configuration_that_names_a_feed_with_no_file_is_an_error(
    tmp_path, monkeypatch
):
    here = tmp_path / "benchmarks"
    shutil.copytree(run.HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"
    ))
    path = here / "configs" / "rehearsal_nasnet_tiny.json"
    config = json.loads(path.read_text())
    config["feed"] = "tokens"
    path.write_text(json.dumps(config))
    monkeypatch.setattr(run, "HERE", str(here))
    with pytest.raises(SystemExit) as err:
        run.Cell("rehearsal_tiny")
    assert os.path.join("feeds", "tokens.py") in str(err.value)
