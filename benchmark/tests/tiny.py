"""The tiny mirror of BENCHMARK.json that every rehearsal reads, built anew
from the manifest and never kept: the manifest whole, but for each
configuration's `file` and each cell's `traffic`, which name their tiny twins
where those are.

A new cell brings a tiny twin of its configuration, `tests/data/configs/
<config>.json`, and may bring one of its mix, `tests/data/traffic/<mix>.json`.
"""

import json
import os

from conftest import HERE, REPO

DATA = os.path.join(HERE, "data")


def twin(kind, name):
    """The path of the tiny twin of `benchmark/<kind>/<name>.json`."""
    return os.path.join(DATA, kind, name + ".json")


def build():
    """The mirror as a dict. A configuration without a twin keeps its own
    file: its cell's mirror test fails and names it, and no other does."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for c in manifest["configs"]:
        if os.path.isfile(twin("configs", c["name"])):
            c["file"] = os.path.relpath(twin("configs", c["name"]), REPO)
    for w in manifest["workloads"]:
        if os.path.isfile(twin("traffic", w["traffic"])):
            w["traffic"] = "../tests/data/traffic/" + w["traffic"]
    return manifest


def manifest_path(directory, manifest=None):
    """Writes the mirror (or `manifest`) as BENCHMARK.json in `directory`
    and returns its path."""
    path = os.path.join(str(directory), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(build() if manifest is None else manifest, f, indent=1)
    return path
