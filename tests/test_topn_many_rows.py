"""A filtered TopN over a field of many rows, through the normal path, at
two byte budgets of the candidate phase (executor.py `_topn_chunk`): one of
512 rows at one shard (`PILOSA_TOPN_CHUNK_BYTES`), so that the chunk loop
and the deadline between its chunks are run, and the default, under which
the same rows are one program.

One shard, 1,100 + 16 rows from a seeded numpy draw: three chunks (512,
512, 92) under the small budget, one of 1,116 (a stack padded to 1,536
rows, parallel/engine.py padded_rows) under the default. Every answer is
held to a plain numpy reference written here: for every row the popcount
of row AND filter, sorted, cut at n. And what the chunk loop says of
itself: the counters `topn_queries`, `topn_chunks` and
`topn_candidate_rows` (`/debug/vars` group `executor`) and the spans
`topn.rank`, `topn.chunk` (one a program) and `topn.replay`.
"""

import importlib.util
import os
import sys
import threading
import types

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.constants import WORDS_PER_ROW
from pilosa_tpu.executor import ExecOptions, Executor, _topn_chunk
from pilosa_tpu.obs import ObsConfig, TraceRecorder
from pilosa_tpu.obs import trace as obs_trace
from pilosa_tpu.parallel import EngineConfig
from pilosa_tpu.sched.deadline import Deadline, DeadlineExceededError

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BENCH = os.path.join(REPO, "benchmark")
INDEX = "i"
READ_ROWS, WRITER_ROWS = 1100, 16
ROWS = READ_ROWS + WRITER_ROWS
# The chunks of the candidate phase at each byte budget: one of 512 rows'
# planes at one shard, and the default (2 GiB: 16,384 rows at one shard).
BUDGETS = {"512rows": 512 * WORDS_PER_ROW * 4, "default": None}
CHUNKS = {"512rows": [512, 512, 92], "default": [ROWS]}
COLS = 4096     # the columns the draw uses, of the shard's 2^20
G_ROWS = 3
FILTERS = {"row": "Row(g=0)", "tree": "Intersect(Row(g=0), Row(g=1))"}
CHILD_LIMIT_S = 120.0


def draw(seed):
    """(f, g) as dense boolean matrices, rows x COLS. f's rows thin out
    with their number (row 0 is the likeliest, as the benchmark's zipf
    draw has it) and come in runs of equal density, so that counts tie
    across the cut; g's rows hold a third to a half of the columns."""
    rng = np.random.default_rng(seed)
    density = 0.30 - 0.25 * (np.arange(ROWS) // 4 * 4) / ROWS
    f = rng.random((ROWS, COLS)) < density[:, None]
    g = rng.random((G_ROWS, COLS)) < np.array([0.5, 0.4, 0.33])[:, None]
    return f, g


def mask(g, which):
    return g[0] if which == "row" else g[0] & g[1]


def top(f, filt, n):
    """The plain reference: [(row, count)] by falling count, then rising
    row, of the rows that count over 0; the first n where n is over 0."""
    counts = (f & filt).sum(axis=1)
    order = sorted((r for r in range(len(counts)) if counts[r]),
                   key=lambda r: (-counts[r], r))
    return [(r, int(counts[r])) for r in (order[:n] if n else order)]


def fill(holder, f, g):
    idx = holder.create_index(INDEX)
    for name, m in (("f", f), ("g", g)):
        rows, cols = np.nonzero(m)
        idx.create_field(name).import_bits(
            rows.astype(np.uint64), cols.astype(np.uint64))


def executor(holder):
    # Serial gathers: no pool thread for the leak guard to find.
    ex = Executor(holder, workers=0,
                  engine_config=EngineConfig(gather_workers=1))
    assert ex.engine is not None
    return ex


def ask(ex, which, n):
    got = ex.execute(INDEX, f"TopN(f, {FILTERS[which]}, n={n})")[0]
    return [(p.id, p.count) for p in got]


@pytest.fixture(scope="module")
def worlds():
    """A holder and an engine a budget for the module's in-process cases,
    built here (before conftest's per-test tracker could close an engine
    under the next case). Cases that write set bits in their budget's
    `f`."""
    made = {}
    for budget in BUDGETS:
        f, g = draw(3801)
        holder = Holder(None)
        holder.open()
        fill(holder, f, g)
        made[budget] = holder, (executor(holder), f, g)
    yield {budget: world for budget, (_, world) in made.items()}
    for holder, (ex, _, _) in made.values():
        ex.close()
        holder.close()


@pytest.fixture(params=list(BUDGETS))
def budget(request, monkeypatch):
    """The candidate phase's byte budget, by its name in BUDGETS."""
    if BUDGETS[request.param] is None:
        monkeypatch.delenv("PILOSA_TOPN_CHUNK_BYTES", raising=False)
    else:
        monkeypatch.setenv("PILOSA_TOPN_CHUNK_BYTES",
                           str(BUDGETS[request.param]))
    return request.param


@pytest.fixture
def served(worlds, budget):
    return worlds[budget]


def test_the_shape_of_the_chunks(budget):
    chunk = _topn_chunk(1)
    assert chunk == {"512rows": 512, "default": 16384}[budget]
    assert [min(chunk, ROWS - i)
            for i in range(0, ROWS, chunk)] == CHUNKS[budget]


@pytest.mark.parametrize("n", [1, 10, 2000])
@pytest.mark.parametrize("which", list(FILTERS))
def test_filtered_topn_agrees_with_numpy(served, which, n):
    ex, f, g = served
    want = top(f, mask(g, which), n)
    assert len(want) == min(n, ROWS)
    assert ask(ex, which, n) == want


# A row of the last chunk (a writer-owned one, as the benchmark's mix has
# them) and a row of the first, each set in columns the filter holds: the
# chunk's stack is refreshed by a scatter, the others are republished, and
# the fragment's ranking is built anew.
@pytest.mark.parametrize("row", [ROWS - 1, READ_ROWS, 3, 511, 512],
                         ids=lambda r: f"row{r}")
@pytest.mark.parametrize("which", list(FILTERS))
def test_topn_after_sets_on_a_ranked_row(served, which, row):
    ex, f, g = served
    filt = mask(g, which)
    before = ask(ex, which, 10)
    assert before == top(f, filt, 10)
    free = np.flatnonzero(filt & ~f[row])[:700]
    for col in free.tolist():
        assert ex.execute(INDEX, f"Set({col}, f={row})")[0] is True
        f[row, col] = True
    # 700 bits more under the filter lift any row into the first ten.
    after = ask(ex, which, 10)
    assert after == top(f, filt, 10) and after != before
    assert row in [r for r, _ in after]
    assert ask(ex, which, 2000) == top(f, filt, 2000)
    assert ask(ex, which, 1) == top(f, filt, 1)


@pytest.mark.parametrize("which", list(FILTERS))
def test_topn_after_a_restart_of_the_holder(tmp_path, budget, which):
    f, g = draw(3802)
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    fill(holder, f, g)
    ex = executor(holder)
    try:
        for row, col in ((ROWS - 2, 7), (5, 9), (600, 11)):
            f[row, col] = True
            ex.execute(INDEX, f"Set({col}, f={row})")
        assert ask(ex, which, 10) == top(f, mask(g, which), 10)
    finally:
        ex.close()
        holder.close()
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    ex = executor(holder)
    try:
        for n in (1, 10, 2000):
            assert ask(ex, which, n) == top(f, mask(g, which), n)
    finally:
        ex.close()
        holder.close()


@pytest.mark.parametrize("which", list(FILTERS))
def test_the_counters_count_the_candidate_phase(served, budget, which):
    ex, f, g = served
    chunks = len(CHUNKS[budget])
    was = (ex.topn_queries, ex.topn_chunks, ex.topn_candidate_rows,
           ex.topn_array_walks)
    for k in (1, 2):
        ask(ex, which, 10)
        # A query is two runner calls (candidates, then the refetch of the
        # winners); only the first counts here, also when the engine's memo
        # answered its chunks.
        assert (ex.topn_queries, ex.topn_chunks, ex.topn_candidate_rows,
                ex.topn_array_walks) == (
            was[0] + k, was[1] + chunks * k, was[2] + ROWS * k,
            was[3] + 2 * k)
    # No filter: the host's rank cache answers, no runner, no chunk.
    ex.execute(INDEX, "TopN(f, n=10)")
    assert (ex.topn_queries, ex.topn_chunks) == (
        was[0] + 2, was[1] + 2 * chunks)


# Where a budget spent by the first program of the candidate phase stops
# the query: between its chunks, or with one chunk, before the refetch.
STOPPED_AT = {"512rows": "between TopN chunks",
              "default": "between TopN phases"}


def test_a_spent_deadline_stops_the_candidate_phase(served, budget,
                                                    monkeypatch):
    ex, _, _ = served
    now = [0.0]
    real = ex._topn_counts_laddered

    def spending(*a):
        out = real(*a)
        now[0] = 100.0
        return out

    monkeypatch.setattr(ex, "_topn_counts_laddered", spending)
    opt = ExecOptions(deadline=Deadline(5.0, clock=lambda: now[0]))
    with pytest.raises(DeadlineExceededError, match=STOPPED_AT[budget]):
        ex.execute(INDEX, "TopN(f, Row(g=1), n=10)", opt=opt)


def spans_of(ex, pql):
    rec = TraceRecorder(ObsConfig(sample_rate=1.0))
    t = rec.maybe_start(INDEX, pql)
    token = obs_trace.activate(t)
    try:
        ex.execute(INDEX, pql)
    finally:
        obs_trace.deactivate(token)
    rec.finish(t)
    return t.to_dict()["spans"]


@pytest.mark.parametrize("which", list(FILTERS))
def test_the_spans_of_a_chunked_topn(served, budget, which):
    ex, _, _ = served
    spans = spans_of(ex, f"TopN(f, {FILTERS[which]}, n=10)")
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    fanouts = {s["id"] for s in by_name["executor.fanout"]}
    assert len(fanouts) == 2    # the candidates, and the refetch
    rank, = by_name["topn.rank"]
    # One shard: it re-ranked only if a write dropped its ranking.
    assert rank["tags"].pop("rebuilt") in (0, 1)
    assert rank["tags"] == {"shards": 1, "rows": ROWS}
    chunks = sorted(by_name["topn.chunk"], key=lambda s: s["start_ms"])
    assert [s["tags"] for s in chunks] == [
        {"rows": r, "shards": 1} for r in CHUNKS[budget]]
    first, second = sorted(by_name["topn.replay"],
                           key=lambda s: s["start_ms"])
    assert first["tags"] == {"rows": ROWS, "shards": 1}
    assert 10 <= second["tags"]["rows"] < ROWS     # the winners, refetched
    for s in [rank, first, second] + chunks:
        assert s["parent"] in fanouts, s["name"]
    # Every device program of the candidate phase runs under its chunk;
    # the refetch's one is the second fan-out's own child: four under the
    # small budget, two under the default.
    dispatches = by_name["device.dispatch"]
    assert len(dispatches) == len(chunks) + 1
    assert sorted(s["parent"] for s in dispatches[:-1]) == sorted(
        s["id"] for s in chunks)
    assert dispatches[-1]["parent"] == second["parent"]
    # A Count opens none of them.
    names = {s["name"] for s in spans_of(
        ex, "Count(Intersect(Row(f=1), Row(g=0)))")}
    assert "executor.fanout" in names
    assert not names & {"topn.rank", "topn.chunk", "topn.replay"}


# ------------------------------------------------- the served path, a child


@pytest.fixture(scope="module")
def bench():
    """benchmark/run.py loaded by path, as tests/test_mesh_served.py loads
    it; the `sys.path` entry it adds is taken off again."""
    spec = importlib.util.spec_from_file_location(
        "bench_run_topn", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    before = list(sys.path)
    try:
        spec.loader.exec_module(run)
    finally:
        sys.path[:] = before
    return run


def test_a_live_server_answers_and_counts_the_chunks(bench, budget,
                                                     tmp_path):
    f, g = draw(3803)
    cfg = {"index": INDEX, "fields": [{"name": "f"}, {"name": "g"}]}
    data = types.SimpleNamespace(shards=1, cols={
        name: [np.flatnonzero(row).astype(np.uint32) for row in m]
        for name, m in (("f", f), ("g", g))})
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    srv = bench.client.Server(
        REPO, str(tmp_path / "data"), str(tmp_path / "server.log"),
        ["--obs-sample-rate", "1"], env)
    killed = threading.Event()

    def out_of_time():
        killed.set()
        srv.kill()

    limit = threading.Timer(CHILD_LIMIT_S, out_of_time)
    limit.start()
    try:
        srv.start()
        bench.loader.create_schema(srv, cfg)
        bench.loader.load(srv, cfg, data)

        def query(pql):
            return srv.request("POST", f"/index/{INDEX}/query",
                               pql)["results"][0]

        # The first TopN touches every row of f: two spans a cold plane,
        # more than a trace keeps (obs/trace.py SPANS_MAX), so its trace
        # is not among those looked at below.
        query("TopN(f, Row(g=2), n=10)")
        answers = {}
        for which in FILTERS:
            got = query(f"TopN(f, {FILTERS[which]}, n=10)")
            answers[which] = [(p["id"], p["count"]) for p in got]
        assert query(f"Set(5, f={ROWS - 1})") in (True, False)
        f[ROWS - 1, 5] = True
        got = query("TopN(f, Row(g=0), n=2000)")
        answers["all"] = [(p["id"], p["count"]) for p in got]
        v = srv.vars()
        traces = srv.request("GET", "/debug/traces?limit=100")["traces"]
        stopped = srv.stop()
    finally:
        limit.cancel()
        srv.kill()
    assert not killed.is_set(), (
        f"the server child was killed at its {CHILD_LIMIT_S:.0f} s limit:\n"
        + srv.log_tail())
    f[ROWS - 1, 5] = False
    for which in FILTERS:
        assert answers[which] == top(f, mask(g, which), 10), which
    f[ROWS - 1, 5] = True
    assert answers["all"] == top(f, g[0], 2000)
    assert {k: v["executor"][k] for k in (
        "topn_queries", "topn_chunks", "topn_candidate_rows")} == {
        "topn_queries": 4, "topn_chunks": 4 * len(CHUNKS[budget]),
        "topn_candidate_rows": 4 * ROWS}
    assert bench.client.ladder_nonzero(v["engine_cache"]) == {}
    topns = [t for t in traces if t.get("pql", "").startswith("TopN(")]
    assert len(topns) == 4
    cold = [t for t in topns if t.get("spans_dropped")]
    assert [t["pql"] for t in cold] == ["TopN(f, Row(g=2), n=10)"]
    for t in topns:
        if t in cold:
            continue
        names = [s["name"] for s in t["spans"]]
        assert (names.count("topn.rank"), names.count("topn.chunk"),
                names.count("topn.replay")) == (1, len(CHUNKS[budget]), 2)
        # The self times still add up to the request (each is rounded to
        # a microsecond).
        root, = [s for s in t["spans"] if s["name"] == "request"]
        assert sum(s["self_ms"] for s in t["spans"]) == pytest.approx(
            root["dur_ms"], abs=0.001 * len(t["spans"]))
    assert stopped
