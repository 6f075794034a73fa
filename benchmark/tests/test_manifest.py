"""BENCHMARK.json against the contract's rules that a file can be held to,
and against the files it names."""

import json
import os
import re

import pytest

from conftest import BENCH, REPO

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_reporting(manifest, metric):
    return [w["name"] for w in manifest["workloads"]
            if run.metric_applies(metric, w["name"])]


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            names.append(entry["name"])
            assert NAME.match(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                    assert "\n" not in entry[key] and "\t" not in entry[key]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(set(names)) == len(names)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


def test_bounds(manifest):
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= manifest["run_seconds"] <= 51


def test_every_moves_is_reported_by_its_cells(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in cells_reporting(manifest, m):
            assert cell in cells_reporting(manifest, e2e[m["moves"]]), \
                (m["name"], cell)
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_every_cell_has_its_metrics(manifest):
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in manifest["end_to_end"]
               if run.metric_applies(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(run.metric_applies(m, w["name"])
                   for m in manifest["per_layer"])
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_every_named_file_exists(manifest):
    roots = manifest["paths"]
    assert manifest["command"][1].split("/")[0] in roots
    assert os.path.isfile(os.path.join(REPO, manifest["command"][1]))
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["file"].split("/")[0] in roots and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in manifest["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    for m in manifest["per_layer"]:
        assert callable(run.load_layer(m["name"]).read), m["name"]
    for dirpath, _, names in os.walk(BENCH):
        if ".cache" in dirpath or "__pycache__" in dirpath:
            continue
        for n in names:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", n), os.path.join(dirpath, n)


def manifest_cells():
    """The cells of BENCHMARK.json, read when the tests are collected."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return sorted(w["name"] for w in json.load(f)["workloads"])


@pytest.mark.parametrize("cell", manifest_cells())
def test_the_tiny_manifest_is_the_manifest_with_tiny_files(
        manifest, cell, tiny_manifest):
    """The mirror every rehearsal reads (`tiny.py`): BENCHMARK.json whole,
    but for each configuration's `file` and a mix that has a tiny twin
    (named from traffic/ by a relative path). Each cell's
    tiny configuration is its own with at most min(8, its) shards, fewer
    rows, bits and columns, and its mix has the same templates, weights
    and PQL."""
    tiny = run.read_json(tiny_manifest)
    assert set(tiny) == set(manifest)
    for key in manifest:
        if key == "configs":
            assert [{k: v for k, v in c.items() if k != "file"}
                    for c in tiny[key]] == [
                {k: v for k, v in c.items() if k != "file"}
                for c in manifest[key]]
        elif key == "workloads":
            assert [{k: v for k, v in w.items() if k != "traffic"}
                    for w in tiny[key]] == [
                {k: v for k, v in w.items() if k != "traffic"}
                for w in manifest[key]]
            for w, full in zip(tiny[key], manifest[key]):
                assert w["traffic"] in (full["traffic"],
                                        "../tests/data/traffic/"
                                        + full["traffic"])
        else:
            assert tiny[key] == manifest[key], key
    _, small_cell, cfg, mix = run.find_cell(cell, tiny_manifest)
    _, full_cell, full, full_mix = run.find_cell(cell)
    name = full_cell["config"]
    small_file = next(c["file"] for c in tiny["configs"] if c["name"] == name)
    assert small_file.startswith("benchmark/tests/data/"), (
        f"configuration {name!r} has no tiny twin "
        f"benchmark/tests/data/configs/{name}.json")
    assert small_cell["chips"] == full_cell["chips"]
    assert cfg["shards"] <= min(8, full["shards"])
    assert {k: v for k, v in cfg.items() if k not in ("shards", "fields")} \
        == {k: v for k, v in full.items() if k not in ("shards", "fields")}
    sized = ("rows", "bits", "columns")
    assert [{k: v for k, v in f.items() if k not in sized}
            for f in cfg["fields"]] == [
        {k: v for k, v in f.items() if k not in sized} for f in full["fields"]]
    assert all(set(f) == set(g) and all(f[k] <= g[k] for k in sized if k in g)
               for f, g in zip(cfg["fields"], full["fields"]))
    assert [(t["name"], t["weight"], t["pql"]) for t in mix["templates"]] \
        == [(t["name"], t["weight"], t["pql"]) for t in full_mix["templates"]]
    # The writer-owned rows are the last of their field, there as here.
    for small, big in ((cfg, mix), (full, full_mix)):
        rows = {f["name"]: f["rows"] for f in small["fields"] if "rows" in f}
        for field, (lo, hi) in big["writer_rows"].items():
            assert hi == rows[field] - 1 and hi - lo + 1 == big["clients"]


def test_peaks_table():
    peaks = run.read_json(BENCH, "peaks.json")
    v5e = run.peak_of(peaks, "TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["source"]
    with pytest.raises(KeyError):
        run.peak_of(peaks, "TPU v9 imaginary")
