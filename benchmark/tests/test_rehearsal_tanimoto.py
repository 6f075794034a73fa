"""A similarity search rehearsed on the CPU, with no cell added to any
manifest: `tests/data/tiny-chem.json` (1 shard; 2,000 molecules and 16
writer-owned rows drawn by `fingerprint_families` over 4,096 columns, a
rank cache that holds every row) under `tests/data/tiny-tanimoto.json`
(TopN under `tanimotoThreshold` at the six published thresholds, with and
without n, a plain filtered TopN, a write pair).

First its parts alone: a CPU server loaded with the drawn molecules answers
TopN at each threshold as the reference does, over a few dozen query
molecules. Then the whole of a run through `run.run_cell`, on a manifest
made in a temporary directory from the tiny mirror (`tiny.py`) with this
configuration and cell added: every answer agrees, and one tanimoto answer
altered where it is produced, by a row that fails the threshold, ends it
`correct` false."""

import argparse
import os
import shutil
import tempfile
import threading

import pytest

from conftest import REPO

import client
import generate
import loader
import reference
import run
import tiny

CONFIG = "benchmark/tests/data/tiny-chem.json"
CELL = "tiny-chem.tanimoto"
SEED = 2**31 + 4646
THRESHOLDS = (50, 70, 75, 80, 85, 90)
QUERIES = range(0, 2000, 50)


def manifest_with_the_chem_cell(directory):
    manifest = tiny.build()
    manifest["configs"].append({
        "name": "tiny-chem", "source": CONFIG, "file": CONFIG, "reduced": [],
        "why": "a rehearsal of a similarity search on the CPU"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-chem",
        "traffic": "../tests/data/tiny-tanimoto", "chips": 1,
        "why": "TopN under tanimotoThreshold, plain TopN, and writes"})
    return tiny.manifest_path(directory, manifest)


@pytest.fixture(scope="module")
def served():
    """(server, reference) over tiny-chem at one seed, nothing written."""
    cfg = run.read_json(REPO, CONFIG)
    data = generate.Data(cfg, SEED)
    ref = reference.build(data, {})
    tmp = tempfile.mkdtemp(prefix="bench_chem_")
    srv = client.Server(REPO, os.path.join(tmp, "data"),
                        os.path.join(tmp, "server.log"),
                        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    srv.start()
    try:
        loader.create_schema(srv, cfg)
        loader.load(srv, cfg, data)
        yield srv, ref
    finally:
        srv.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def ask(srv, pql):
    return srv.request("POST", "/index/chem/query", pql)["results"][0]


@pytest.mark.parametrize("t", THRESHOLDS)
def test_each_threshold_ranks_as_the_reference_does(served, t):
    srv, ref = served
    found = []
    for q in QUERIES:
        for n in ("", "n=10, "):
            pql = f"TopN(m, Row(m={q}), {n}tanimotoThreshold={t})"
            got, want = ask(srv, pql), ref.answer(pql)
            assert reference.agrees(got, want), (pql, got)
            found.append(len(got))
    # The molecule itself always passes; its analogues have to as well.
    assert min(found) >= 1 and sum(found) > 1.2 * len(found)


def args(seconds=2.0):
    return argparse.Namespace(workload=CELL, seed=SEED, seconds=seconds,
                              trace=0)


def test_the_chem_cell_agrees_on_every_answer(tmp_path):
    result = run.run_cell(args(), require_tpu=False,
                          manifest_path=manifest_with_the_chem_cell(tmp_path))
    assert result["attempted"] > 50 and result["failed"] == 0
    # Every check 0 but the device's: the CPU is never a measurement.
    failing = sorted(k for k, (got, limit) in result["checks"].items()
                     if got != limit)
    assert failing == ["not_on_tpu"] and result["correct"] is False
    assert set(result["facts"]["latency_ms_by_template"]) == {
        "tanimoto", "tanimoto_n", "topn", "write_pair"}


def test_a_row_under_the_threshold_is_not_correct(tmp_path):
    """The first tanimoto answer of the window that leaves out a read row
    under its threshold gets that row back with its true count: the one
    that intersects the query most among them."""
    cfg = run.read_json(REPO, CONFIG)
    ref = reference.build(generate.Data(cfg, SEED), {})
    lock, seen = threading.Lock(), []

    def tamper(sent):
        if sent.status != 200 or "tanimoto" not in sent.pql:
            return
        _, pos, _ = reference.parse(sent.pql)
        with lock:
            if seen:
                return
            c = ref.row_counts("m", ref.bitmap(pos[1]))
            passing = ref.answer(sent.pql)[1]
            under = [(c[r], r) for r in range(2000)
                     if c[r] > 0 and not passing[r]]
            if not under:
                return
            count, r = max(under)
            seen.append(sent.pql)
        sent.result.append({"id": r, "count": count})
        sent.result.sort(key=lambda p: -p["count"])

    result = run.run_cell(args(seconds=1.0), require_tpu=False, tamper=tamper,
                          manifest_path=manifest_with_the_chem_cell(tmp_path))
    assert seen and result["checks"]["wrong_answers"] == [1, 0]
    assert result["failed"] == 1 and result["correct"] is False
