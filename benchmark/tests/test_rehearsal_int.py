"""An integer cell rehearsed on the CPU, with no cell added to any manifest:
`tests/data/tiny-int.json` (2 shards; a `zipf_bits` field with writer-owned
rows, a `one_row_per_column` field of 7 rows, two `int_uniform` fields, 0..10
and 1..50) under `tests/data/tiny-sum.json` (Sum, Min and Max under
`Intersect(Row, Range, Range)`, a Count of a Range, a write pair; the Range
constants drawn from `choice` lists).

First its parts alone: a CPU server loaded with the drawn fields answers
every Range operation, Sum, Min and Max as the reference does. Then the whole
of a run through `run.run_cell`, on a manifest made in a temporary directory
from the tiny mirror (`tiny.py`) with this configuration and cell added: every
answer agrees, and one Sum altered where it is produced ends it `correct`
false."""

import argparse
import os
import shutil
import tempfile

import pytest

from conftest import REPO

import client
import generate
import loader
import reference
import run
import tiny

CONFIG = "benchmark/tests/data/tiny-int.json"
CELL = "tiny-int.sum"
SEED = 2**31 + 4242


def manifest_with_the_int_cell(directory):
    manifest = tiny.build()
    manifest["configs"].append({
        "name": "tiny-int", "source": "benchmark/tests/data/tiny-int.json",
        "file": CONFIG, "reduced": [],
        "why": "a rehearsal of int fields on the CPU"})
    manifest["workloads"].append({
        "name": CELL, "config": "tiny-int", "traffic": "../tests/data/tiny-sum",
        "chips": 1, "why": "Sum, Min, Max and Count over Ranges, and writes"})
    return tiny.manifest_path(directory, manifest)


@pytest.fixture(scope="module")
def served():
    """(server, reference) over tiny-int at one seed."""
    cfg = run.read_json(REPO, CONFIG)
    data = generate.Data(cfg, SEED)
    ref = reference.build(data, {})
    tmp = tempfile.mkdtemp(prefix="bench_int_")
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
    return srv.request("POST", "/index/tinyint/query", pql)["results"][0]


# Constants below, at and inside both ends of each field's range, and above.
CONSTANTS = {"discount": (-1, 0, 1, 5, 10, 11),
             "quantity": (0, 1, 2, 25, 49, 50, 51)}
MIN = {"discount": 0, "quantity": 1}


@pytest.mark.parametrize("op", ["<", "<=", ">", ">=", "==", "!="])
def test_every_comparison_counts_as_the_reference_does(served, op):
    srv, ref = served
    for field, constants in CONSTANTS.items():
        for c in constants:
            if op == "<" and c == MIN[field]:
                continue    # the program's fault, held apart below
            pql = f"Count(Range({field} {op} {c}))"
            assert ask(srv, pql) == ref.answer(pql), pql
            pql = f"Count(Intersect(Row(f=2), Range({field} {op} {c})))"
            assert ask(srv, pql) == ref.answer(pql), pql


@pytest.mark.xfail(strict=True, reason="the program's `<` at the field's "
                   "minimum answers the columns that hold the minimum")
@pytest.mark.parametrize("field", sorted(MIN))
def test_less_than_the_minimum_is_empty(served, field):
    srv, ref = served
    pql = f"Count(Range({field} < {MIN[field]}))"
    assert ref.answer(pql) == 0
    # The program's own `<=` one below says the same as the reference.
    assert ask(srv, f"Count(Range({field} <= {MIN[field] - 1}))") == 0
    assert ask(srv, pql) == 0


def test_between_counts_as_the_reference_does(served):
    srv, ref = served
    for field in CONSTANTS:
        for a, b in ((0, 10), (1, 3), (3, 3), (-5, 0), (50, 60), (4, 2),
                     (-9, 99), (11, 12)):
            pql = f"Count(Range({field} >< [{a}, {b}]))"
            assert ask(srv, pql) == ref.answer(pql), pql


@pytest.mark.parametrize("call", ["Sum", "Min", "Max"])
def test_sum_min_max_as_the_reference_does(served, call):
    srv, ref = served
    for field in CONSTANTS:
        for filt in ("", "Row(year=3), ", "Row(f=5), ",
                     "Intersect(Row(year=2), Range(discount >< [1, 3]), "
                     "Range(quantity < 25)), ",
                     "Intersect(Row(year=6), Range(discount != 4), "
                     "Range(quantity >= 49)), ",
                     "Range(quantity > 50), "):
            pql = f"{call}({filt}field={field})"
            got, want = ask(srv, pql), ref.answer(pql)
            assert got == want, pql
            assert want["count"] > 0 or "> 50" in filt


def args(seconds=2.0):
    return argparse.Namespace(workload=CELL, seed=SEED, seconds=seconds,
                              trace=0)


def test_the_int_cell_agrees_on_every_answer(tmp_path):
    result = run.run_cell(args(), require_tpu=False,
                          manifest_path=manifest_with_the_int_cell(tmp_path))
    assert result["attempted"] > 50 and result["failed"] == 0
    # Every check 0 but the device's: the CPU is never a measurement.
    failing = sorted(k for k, (got, limit) in result["checks"].items()
                     if got != limit)
    assert failing == ["not_on_tpu"] and result["correct"] is False
    assert set(result["facts"]["latency_ms_by_template"]) == {
        "sum3", "min3", "max3", "count_between", "write_pair"}


def test_an_altered_sum_is_not_correct(tmp_path):
    seen = []

    def tamper(sent):
        if sent.pql.startswith("Sum(") and sent.status == 200 and not seen:
            seen.append(sent.pql)
            sent.result["value"] += 1

    result = run.run_cell(args(seconds=1.0), require_tpu=False, tamper=tamper,
                          manifest_path=manifest_with_the_int_cell(tmp_path))
    assert seen and result["checks"]["wrong_answers"] == [1, 0]
    assert result["failed"] == 1 and result["correct"] is False
