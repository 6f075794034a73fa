"""The six readers of CPU time (PR 40): `host.cpu_ms_per_op` and
`host.gc_ms_per_op` from the `host` group of /debug/vars, and the four that
read a span's `cpu_ms` / `self_cpu_ms`. Each on a made-up window, each
absent on a program without the field (the parent's), and all of them in
the line of a traced rehearsal of the tiny TopN cell on the CPU, where they
are counts that the readers found something and no speeds."""

import argparse

import pytest

import run

CELL = "zipf-1x8k.topn"
WRITTEN_FOR = ("zipf-64.adhoc", "zipf-4x64.adhoc", CELL)
EVERY_CELL = ("host.cpu_ms_per_op", "host.gc_ms_per_op",
              "server.request_cpu_ms", "host.off_cpu_share",
              "engine.device_wait_off_cpu_ms")
NEW = EVERY_CELL + ("topn.host_cpu_ms",)


def reader(name):
    return run.load_layer(name).read


def test_the_cpu_readers_are_listed_for_their_cells():
    """Each list names cells of the manifest and holds the cells it was
    written for; a cell added since may be in the lists or not."""
    manifest = run.read_json(run.REPO, "BENCHMARK.json")
    cells = {w["name"] for w in manifest["workloads"]}
    listed = {m["name"]: m["workloads"] for m in manifest["per_layer"]
              if m["name"] in NEW}
    assert set(listed) == set(NEW)
    assert all(set(mine) <= cells for mine in listed.values()), listed
    assert all(set(listed[n]) >= set(WRITTEN_FOR) for n in EVERY_CELL)
    assert listed["topn.host_cpu_ms"] == [CELL]


def vars_with(admitted, host):
    out = {"scheduler": {"admitted": admitted}}
    if host is not None:
        out["host"] = host
    return out


def test_the_counter_readers_are_ms_an_answer_and_absent_without_the_group():
    cpu, gc = reader("host.cpu_ms_per_op"), reader("host.gc_ms_per_op")
    ctx = run.Context(
        before=vars_with(1000, {"cpu_s": 50.0, "gc_s": 1.0}),
        after=vars_with(11000, {"cpu_s": 90.0, "gc_s": 3.5}))
    assert cpu(ctx) == pytest.approx(4.0)
    assert gc(ctx) == pytest.approx(0.25)
    # No answer in the window: nothing to divide by.
    still = run.Context(before=vars_with(7, {"cpu_s": 1.0, "gc_s": 0.0}),
                        after=vars_with(7, {"cpu_s": 2.0, "gc_s": 0.0}))
    assert cpu(still) is None and gc(still) is None
    # The parent's program: no `host` group, or no /debug/vars read.
    for before, after in ((vars_with(0, None), vars_with(9, None)),
                          ({}, {}), (None, None)):
        old = run.Context(before=before, after=after)
        assert cpu(old) is None and gc(old) is None


def span(name, dur, self_ms, cpu=None, self_cpu=None):
    s = {"name": name, "dur_ms": dur, "self_ms": self_ms}
    if cpu is not None:
        s.update(cpu_ms=cpu, self_cpu_ms=self_cpu)
    return s


def a_topn(cpu=True):
    """One traced TopN and one traced Count, with the CPU readings or, as
    the parent's program gives them, without."""
    c = (lambda *v: v) if cpu else (lambda *v: (None, None))
    return [
        {"spans": [
            span("request", 100.0, 2.0, *c(20.0, 1.0)),
            span("sched.wait", 30.0, 30.0, *c(0.0, 0.0)),
            span("topn.rank", 20.0, 20.0, *c(8.0, 8.0)),
            span("engine.device_wait", 10.0, 10.0, *c(0.5, 0.5)),
            span("engine.device_wait", 6.0, 6.0, *c(0.25, 0.25)),
            span("topn.replay", 10.0, 10.0, *c(4.0, 4.0)),
            span("topn.replay", 2.0, 2.0, *c(10.0, 10.0)),  # a whole tick
        ]},
        {"spans": [
            span("request", 10.0, 6.0, *c(4.0, 3.0)),
            span("engine.device_wait", 4.0, 4.0, *c(1.0, 1.0)),
        ]},
    ]


def test_the_span_readers_on_a_made_up_window():
    ctx = run.Context(traces=a_topn())
    assert reader("server.request_cpu_ms")(ctx) == pytest.approx(12.0)
    # Of `request`, `topn.rank` and `topn.replay` (self 2 + 20 + 10 + 2 + 6
    # = 40 ms) the threads ran 1 + 8 + 4 + 10 + 3 = 26: the waits by
    # nature (`sched.wait`, `engine.device_wait`) are left out, and the
    # span that a coarse clock charged a whole tick (10 ms of CPU in 2 ms)
    # counts with its tick: the sums are what is read, not each span.
    assert reader("host.off_cpu_share")(ctx) == pytest.approx(35.0)
    # More CPU than self time in all (ticks on a thin window): 0, not less.
    ticked = run.Context(traces=[{"spans": [span("parse", 0.1, 0.1,
                                                 10.0, 10.0)]}])
    assert reader("host.off_cpu_share")(ticked) == 0.0
    # (10 - 0.5) + (6 - 0.25) in the TopN, 4 - 1 in the Count.
    assert reader("engine.device_wait_off_cpu_ms")(ctx) == pytest.approx(
        (15.25 + 3.0) / 2)
    # 8 + 4 + 10 in the one query that has such spans.
    assert reader("topn.host_cpu_ms")(ctx) == pytest.approx(22.0)


@pytest.mark.parametrize("name", NEW[2:])
def test_a_span_reader_finds_nothing_in_spans_without_cpu(name):
    assert reader(name)(run.Context(traces=a_topn(cpu=False))) is None
    assert reader(name)(run.Context(traces=[])) is None


def test_the_traced_line_of_the_tiny_topn_cell_has_all_six(tiny_manifest):
    args = argparse.Namespace(workload=CELL, seed=2**31 + 40, seconds=3.0,
                              trace=1)
    result = run.run_cell(args, require_tpu=False,
                          manifest_path=tiny_manifest)
    assert result["attempted"] > 50 and result["failed"] == 0
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(value)
    assert all(value[n] >= 0 for n in NEW)
    assert 0 < value["host.off_cpu_share"] <= 100
    assert value["topn.host_cpu_ms"] <= value["topn.host_self_ms"]
    # The process's CPU an answer holds the serving thread's.
    assert value["host.cpu_ms_per_op"] >= value["server.request_cpu_ms"] > 0
