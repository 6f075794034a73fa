"""The per-node engine on a mesh of more than one device, through the
served path and in process.

One server child with four virtual CPU devices and `--engine-mesh-devices
4` answers the benchmark's `adhoc` mix from eight concurrent clients; every
answer is held to `benchmark/reference.py`, and `/debug/vars` has to show
four devices each holding its block of the planes, launches that spanned
them, and no rung below the device. The child has a time limit of its own
and is killed at it: concurrent multi-device programs on the CPU backend
can interleave their rendezvous (`EngineConfig.mesh_devices`), and a hang
must fail this one test, not the suite's clock.

In process, the same questions at mesh widths 1, 2 and 4: the same
answers before and after a Set (the delta scatter on a sharded plane), the
counters `mesh_launches` and `h2d_bytes`, where the planes lie, and the
span `engine.place`.
"""

import importlib.util
import os
import sys
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.obs import ObsConfig, TraceRecorder
from pilosa_tpu.obs import trace as obs_trace
from pilosa_tpu.parallel import EngineConfig
from pilosa_tpu.parallel.engine import DELTA_MIN_UPDATES
from pilosa_tpu.pql.parser import parse

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(REPO, "benchmark")
SEED = 2**31 + 30
CHILD_LIMIT_S = 120.0
CFG = {
    "index": "zipf", "shards": 8,
    "fields": [
        {"name": "f", "draw": "zipf_bits", "rows": 48, "bits": 120000,
         "row_exponent": 1.01, "row_ratio": 0.25,
         "column_exponent": 1.01, "column_ratio": 0.25},
        {"name": "g", "draw": "zipf_bits", "rows": 32, "bits": 108000,
         "row_exponent": 1.01, "row_ratio": 0.25,
         "column_exponent": 1.01, "column_ratio": 0.25},
    ],
}


@pytest.fixture(scope="module")
def bench():
    """benchmark/run.py loaded by path; its siblings (`client`, `generate`,
    `loader`, `reference`) hang off it. It puts benchmark/ on sys.path to
    find them, which is taken off again."""
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    before = list(sys.path)
    try:
        spec.loader.exec_module(run)
    finally:
        sys.path[:] = before
    return run


@pytest.fixture(scope="module")
def data(bench):
    return bench.generate.Data(CFG, SEED)


# ------------------------------------------------- the served path, a child


def test_four_device_server_agrees_with_the_reference(bench, data, tmp_path):
    mix = dict(bench.read_json(BENCH, "traffic", "adhoc.json"), clients=8)
    ref = bench.reference.build(data, mix)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    srv = bench.client.Server(REPO, str(tmp_path / "data"),
                              str(tmp_path / "server.log"),
                              ["--engine-mesh-devices", "4"], env)
    killed = threading.Event()

    def out_of_time():
        killed.set()
        srv.kill()

    limit = threading.Timer(CHILD_LIMIT_S, out_of_time)
    limit.start()
    try:
        srv.start()
        bench.loader.create_schema(srv, CFG)
        bench.loader.load(srv, CFG, data)
        # Every value of every placeholder once (all four operations, every
        # row), then a deck of the mix from each client.
        streams = []
        for k in range(mix["clients"]):
            s = bench.generate.Requests(mix, CFG, SEED, k)
            streams.append(bench.generate.Fixed(
                s.sweep(mix["clients"]) + [s.next() for _ in range(18)]))
        sent, _, _ = bench.client.closed_loop(
            srv.port, CFG["index"], streams, CHILD_LIMIT_S)
        v = srv.vars()
        stopped = srv.stop()
    finally:
        limit.cancel()
        srv.kill()
    assert not killed.is_set(), (
        f"the server child was killed at its {CHILD_LIMIT_S:.0f} s limit:\n"
        + srv.log_tail())

    asked = [s.pql for mine in sent for s in mine]
    assert len(asked) >= 8 * 20
    for op in ("Intersect", "Union", "Xor", "Difference"):
        assert any(p.startswith(f"Count({op}(Row(f=") for p in asked), op
    for part in ("Union(Row(g=", "TopN(f, Row(g=", "Set(", "Count(Row(f="):
        assert any(part in p for p in asked), part
    judged = bench.judge(ref, [[] for _ in sent], sent)
    assert judged["first_wrong"] is None
    assert (judged["wrong_answers"], judged["unanswered"]) == (0, 0)

    dev, ec = v["device"], v["engine_cache"]
    assert dev["n_devices"] == 4 and dev["mesh_shape"] == {"shards": 4}
    held = dev["cached_plane_bytes"]
    assert len(held) == 4 and min(held) > 0 and max(held) == min(held)
    assert ec["mesh_launches"] > 0 and ec["h2d_bytes"] > 0
    assert ec["mesh_launches"] >= ec["count_dispatches"] > 0
    assert bench.client.ladder_nonzero(ec) == {}
    assert v["batcher"]["fallbacks"] == 0
    assert stopped


# ------------------------------------------------------------- in process

QUESTIONS = (
    [f"Count({op}(Row(f={a}), Row(g={b})))"
     for op in ("Intersect", "Union", "Xor", "Difference")
     for a, b in ((0, 0), (5, 31), (31, 7))]
    + ["Count(Intersect(Row(f=3), Union(Row(g=1), Row(g=30))))",
       "Count(Row(f=40))", "TopN(f, Row(g=2), n=10)",
       "TopN(f, Row(g=31), n=10)"])
SETS = [f"Set({c}, f=40)" for c in (5, SHARD_WIDTH + 9, 3 * SHARD_WIDTH + 1,
                                    7 * SHARD_WIDTH + 77, 7 * SHARD_WIDTH + 78)]


def loaded_holder(path, data):
    h = Holder(str(path / "data"))
    h.open()
    idx = h.create_index(CFG["index"])
    for name, rows in data.cols.items():
        fld = idx.create_field(name)
        fld.import_bits(
            np.concatenate([np.full(len(c), r, np.uint64)
                            for r, c in enumerate(rows)]),
            np.concatenate(rows).astype(np.uint64))
    return h


@pytest.fixture
def holder(tmp_path, data):
    h = loaded_holder(tmp_path, data)
    yield h
    h.close()


def executor(holder, width):
    return Executor(holder, workers=0,
                    engine_config=EngineConfig(mesh_devices=width))


def plain(result):
    """An executor's result as the server's JSON would give it."""
    if isinstance(result, list):
        return [{"id": p.id, "count": p.count} for p in result]
    return result


def ask(ex, pqls):
    return [plain(ex.execute(CFG["index"], p)[0]) for p in pqls]


@pytest.fixture(scope="module")
def width_one(tmp_path_factory, data, bench):
    """What a one-device engine answers, before and after the Sets; held
    to the reference here, so that the wider meshes are held to it too."""
    h = loaded_holder(tmp_path_factory.mktemp("width1"), data)
    ex = executor(h, 1)
    try:
        before = ask(ex, QUESTIONS)
        assert ask(ex, SETS) == [True] * len(SETS)
        after = ask(ex, QUESTIONS)
        counters = dict(ex.engine.counters)
    finally:
        ex.close()
        h.close()
    ref = bench.reference.build(data, {"writer_rows": {"f": [32, 47]}})
    ref.expect_sets(SETS)
    for got, pql in zip(before, QUESTIONS):
        assert bench.reference.agrees(got, ref.answer(pql)), pql
    for pql in SETS:
        ref.answer(pql)
    ref.memo.clear()
    for got, pql in zip(after, QUESTIONS):
        assert bench.reference.agrees(got, ref.answer(pql)), pql
    assert before != after
    return before, after, counters


@pytest.mark.parametrize("width", [1, 2, 4])
def test_a_mesh_answers_as_one_device_does(holder, width_one, width):
    before, after, _ = width_one
    ex = executor(holder, width)
    try:
        assert ex.engine.n_devices == width
        assert ask(ex, QUESTIONS) == before
        hits = ex.engine.counters["leaf_delta_hits"]
        assert ask(ex, SETS) == [True] * len(SETS)
        assert ask(ex, QUESTIONS) == after
        # The written row's plane was refreshed where it lies, by a
        # scatter, and not gathered anew.
        assert ex.engine.counters["leaf_delta_hits"] > hits
    finally:
        ex.close()


@pytest.mark.parametrize("width", [1, 2, 4])
def test_a_batch_on_a_mesh_answers_as_single_counts_do(holder, width_one,
                                                       width):
    """The fused batched Count (one stacked tensor, the program the
    batcher launches) on a sharded stack, before and after a Set."""
    counts = [q for q in QUESTIONS if q.startswith("Count(Intersect(Row(f=")
              and "Union" not in q]
    calls = [parse(q).calls[0].children[0] for q in counts]
    shards = tuple(range(CFG["shards"]))
    ex = executor(holder, width)
    try:
        for step in (0, 1):
            want = [width_one[step][QUESTIONS.index(q)] for q in counts]
            got = ex.engine.count_batch(CFG["index"], calls, shards)
            assert got.tolist() == want
            ask(ex, SETS[:1 + step])
    finally:
        ex.close()


@pytest.mark.parametrize("width", [1, 2, 4])
def test_mesh_launches_and_h2d_bytes(holder, width_one, width):
    ex = executor(holder, width)
    try:
        ask(ex, QUESTIONS)
        ask(ex, SETS)
        ask(ex, QUESTIONS)
        c = dict(ex.engine.counters)
        info = ex.engine.device_info()
    finally:
        ex.close()
    launches = c["count_dispatches"]
    assert launches > 0
    if width == 1:
        assert c["mesh_launches"] == 0
    else:
        # Every launch _note_launch counts spans the mesh: the Counts and
        # the TopN programs, which have no launch counter of their own.
        assert c["mesh_launches"] > launches
    # What the refresh paths handed to the device, whichever ran.
    assert c["h2d_bytes"] == (c["full_refresh_bytes"] + c["delta_bytes"]
                              + c["tier_promote_bytes"]) > 0
    assert c["h2d_bytes"] == width_one[2]["h2d_bytes"]
    # Each device holds its block of every cached plane, and no more.
    held = info["cached_plane_bytes"]
    assert len(held) == width == info["n_devices"]
    assert min(held) == max(held) > 0
    assert sum(held) == ex.engine._leaf_bytes + ex.engine._stack_bytes


def test_engine_place_is_a_child_of_gather_on_a_refresh_only(holder):
    rec = TraceRecorder(ObsConfig(sample_rate=1.0))
    ex = executor(holder, 4)

    def traced(pql):
        t = rec.maybe_start(CFG["index"], pql)
        token = obs_trace.activate(t)
        try:
            ex.execute(CFG["index"], pql)
        finally:
            obs_trace.deactivate(token)
        rec.finish(t)
        return t.to_dict()["spans"]

    try:
        plane = CFG["shards"] * SHARD_WIDTH // 8
        cold = traced("Count(Intersect(Row(f=1), Row(g=1)))")
        places = [s for s in cold if s["name"] == "engine.place"]
        gathers = {s["id"] for s in cold if s["name"] == "gather"}
        assert len(places) == 2 and {s["parent"] for s in places} <= gathers
        assert all(s["tags"] == {"bytes": plane, "devices": 4}
                   for s in places)
        # A different question over the same planes: leaf-cache hits.
        hit = traced("Count(Union(Row(f=1), Row(g=1)))")
        assert not [s for s in hit
                    if s["name"] in ("engine.place", "gather")]
        # A write to the row, then the refresh: a scatter's few bytes, its
        # (row, col, value) int32 triples padded to DELTA_MIN_UPDATES.
        ex.execute(CFG["index"], "Set(12, f=1)")
        delta = traced("Count(Xor(Row(f=1), Row(g=1)))")
        places = [s for s in delta if s["name"] == "engine.place"]
        assert len(places) == 1
        assert 0 < places[0]["tags"]["bytes"] <= DELTA_MIN_UPDATES * 3 * 4
    finally:
        ex.close()


# ------------------- what the first four-chip run showed wrong (PR 30, C.4)


@pytest.mark.parametrize("budget, fallbacks", [(None, 0), (0.05, 1)])
def test_a_follower_waits_for_a_slow_leader(holder, monkeypatch, budget,
                                            fallbacks):
    """A leader whose launch is slow (cold planes, a compile) is not a
    wedged one: its follower waits for it as long as the engine's build
    gate would, and falls back only past that. With the bound cut under
    the launch's length the same pair does fall back, and is counted."""
    from pilosa_tpu.sched import batcher as batcher_mod

    # engine._gate steals a build after 30 waits of 10 s.
    assert batcher_mod.FOLLOWER_BUDGET_S >= 300.0
    if budget is not None:
        monkeypatch.setattr(batcher_mod, "FOLLOWER_BUDGET_S", budget)
    monkeypatch.setenv("PILOSA_MEMO_ENTRIES", "0")
    ex = executor(holder, 4)
    engine = ex.engine
    real = engine.count_batch

    def slow_count_batch(*args, **kw):
        time.sleep(0.5)
        return real(*args, **kw)

    monkeypatch.setattr(engine, "count_batch", slow_count_batch)
    # The leader holds its window until the second query has joined.
    ex.batcher = batcher_mod.MicroBatcher(
        lambda: engine, window=0.001, window_max=0.002, batch_max=2,
        depth_fn=lambda: 2,
        wait_window=lambda group, window: group.full.wait(30))
    pqls = ["Count(Intersect(Row(f=0), Row(g=0)))",
            "Count(Intersect(Row(f=5), Row(g=31)))"]
    got = {}

    def client(pql):
        got[pql] = ex.execute(CFG["index"], pql)[0]

    threads = [threading.Thread(target=client, args=(p,)) for p in pqls]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        plain_ex = executor(holder, 1)
        try:
            assert got == dict(zip(pqls, ask(plain_ex, pqls)))
        finally:
            plain_ex.close()
        assert ex.batcher.counters["fallbacks"] == fallbacks
        assert ex.batcher.counters["coalesced"] == 1
    finally:
        ex.close()
