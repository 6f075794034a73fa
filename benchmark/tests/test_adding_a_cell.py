"""A cell is added by new files and entries appended to BENCHMARK.json alone:
in a copy of `benchmark/` and the manifest, a configuration with int fields,
its tiny twin under `tests/data/configs/`, a mix whose probe names its wave,
a reader and the entries that name them leave the manifest's tests, the host
metrics' list test and the readers' empty-context test green, with no file
that was there edited. Without the tiny twin the mirror test fails and names
the configuration. No server runs."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

CONFIG = {
    "name": "int-demo",
    "source": "Star Schema Benchmark (O'Neil, O'Neil, Chen, Revilak, rev. 3, "
              "2009), flight 1's columns: lo_discount, lo_quantity, year",
    "reduced": [],
    "index": "intdemo",
    "shards": 16,
    "fields": [
        {"name": "f", "draw": "zipf_bits", "rows": 24, "bits": 2000000,
         "row_exponent": 1.01, "row_ratio": 0.25, "column_exponent": 1.01,
         "column_ratio": 0.25},
        {"name": "year", "draw": "one_row_per_column", "columns": 16000000,
         "rows": 7},
        {"name": "discount", "draw": "int_uniform", "columns": 16000000,
         "min": 0, "max": 10,
         "options": {"type": "int", "min": 0, "max": 10}},
        {"name": "quantity", "draw": "int_uniform", "columns": 16000000,
         "min": 1, "max": 50,
         "options": {"type": "int", "min": 1, "max": 50}},
    ],
    "server_flags": [],
}
TWIN_SIZES = {"f": {"bits": 120000}, "year": {"columns": 1500000},
              "discount": {"columns": 1500000},
              "quantity": {"columns": 1500000}}
CELL = "int-demo.int-demo-mix"
READER = '''"""The share of the probe's capture in which the device was busy."""


def read(ctx):
    prof = (ctx.probe or {}).get("profile")
    if not prof or not prof.get("window_s"):
        return None
    return 100.0 * prof["busy_s"] / prof["window_s"]
'''
CHECKS = ["benchmark/tests/test_manifest.py",
          "benchmark/tests/test_rehearsal_cpu.py::"
          "test_the_cpu_readers_are_listed_for_their_cells",
          "benchmark/tests/test_parts.py::"
          "test_readers_return_nothing_where_there_is_nothing_to_read"]


def walk(top):
    """The files under `top` but caches, relative to it."""
    for dirpath, dirs, names in os.walk(top):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
        for n in names:
            yield os.path.relpath(os.path.join(dirpath, n), top)


def write_json(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture
def copy(tmp_path):
    """A copy of the benchmark with the int-demo cell added by new files
    and appended entries; returns its root."""
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)

    write_json(CONFIG, bench, "configs", "int-demo.json")
    twin = dict(CONFIG, shards=2, fields=[dict(f, **TWIN_SIZES[f["name"]])
                                          for f in CONFIG["fields"]])
    write_json(twin, bench, "tests", "data", "configs", "int-demo.json")
    with open(bench / "tests" / "data" / "tiny-sum.json") as f:
        mix = json.load(f)
    # {w} names a read row of `f`, so each wave asks new questions with the
    # programs of the first: a Range constant is a key of its program, and
    # {w} there would build one in every wave.
    mix["probe"] = {"pql": "Sum(Intersect(Row(year={i}), Row(f={w}), "
                           "Range(quantity < 50)), field=discount)",
                    "width": 7, "waves": 8, "leaves": 3}
    write_json(mix, bench, "traffic", "int-demo-mix.json")
    (bench / "layers" / "demo.bsi_share.py").write_text(READER)

    with open(root / "BENCHMARK.json") as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "int-demo", "source": CONFIG["source"],
        "file": "benchmark/configs/int-demo.json", "reduced": [],
        "why": "int fields read by Sum under Intersect(Row, Range, Range)"})
    manifest["workloads"].append({
        "name": CELL, "config": "int-demo", "traffic": "int-demo-mix",
        "chips": 1, "why": "16 closed-loop clients: Sum, Min, Max over Ranges"})
    manifest["per_layer"].append({
        "name": "demo.bsi_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "ops/ kernels",
        "moves": "ops_per_s", "workloads": [CELL]})
    # A host metric's list may take the new cell, or (the other four) not.
    next(m for m in manifest["per_layer"]
         if m["name"] == "host.cpu_ms_per_op")["workloads"].append(CELL)
    write_json(manifest, root, "BENCHMARK.json")

    # Every file that was there is as it was: the cell is new files alone.
    for path in walk(BENCH):
        assert filecmp.cmp(os.path.join(BENCH, path), bench / path,
                           shallow=False), path
    return root


def pytest_in(root, *tests):
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *tests], cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_a_cell_added_by_new_files_keeps_the_tests_green(copy):
    out = pytest_in(copy, "-rA", *CHECKS)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    # The new cell has a mirror case of its own, and it passed.
    assert ("PASSED benchmark/tests/test_manifest.py::test_the_tiny_manifest_"
            f"is_the_manifest_with_tiny_files[{CELL}]" in out.stdout), \
        out.stdout[-4000:]
    for test in CHECKS[1:]:
        assert f"PASSED {test}" in out.stdout, out.stdout[-4000:]


def test_a_cell_without_a_tiny_twin_fails_the_mirror_test(copy):
    os.remove(copy / "benchmark" / "tests" / "data" / "configs"
              / "int-demo.json")
    out = pytest_in(copy, CHECKS[0])
    assert out.returncode == 1, out.stdout[-4000:] + out.stderr[-2000:]
    assert "1 failed" in out.stdout
    assert ("FAILED benchmark/tests/test_manifest.py::test_the_tiny_manifest_"
            "is_the_manifest_with_tiny_files[int-demo.int-demo-mix]"
            in out.stdout)
    assert "configuration 'int-demo' has no tiny twin" in out.stdout
