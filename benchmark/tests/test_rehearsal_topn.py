"""The cell `zipf-1x8k.topn` rehearsed on the CPU at 600 + 16 rows (one
shard, so one candidate program a TopN) through the whole of a run, as
`test_rehearsal.py` rehearses `zipf-64.adhoc`: every answer agrees and the
run is still no measurement; an answer altered underneath ends with
`correct` false; and the four readers this cell brought return numbers,
or nothing on a program without their counters and spans (the parent's)."""

import argparse

import pytest

import run

CELL = "zipf-1x8k.topn"
ROWS = 616
NEW = ("topn.chunks_per_op", "topn.candidate_rows_per_op",
       "topn.host_self_ms", "kernel.topn_roofline")
# The per-layer metrics of the cell that this rehearsal was written for, but
# the device's own and those read from its trace, which the CPU backend
# gives nothing for.
WRITTEN_FOR = (
    "client.floor_ms", "parse_plan.ms", "sched.hold_ms",
    "engine.fn_builds_in_window", "topn.chunks_per_op",
    "topn.candidate_rows_per_op", "topn.host_self_ms", "host.cpu_ms_per_op",
    "host.gc_ms_per_op", "server.request_cpu_ms", "host.off_cpu_share",
    "engine.device_wait_off_cpu_ms", "topn.host_cpu_ms")


def args(trace=0, seconds=2.0):
    return argparse.Namespace(workload=CELL, seed=2**31 + 38,
                              seconds=seconds, trace=trace)


def test_cell_agrees_on_every_answer_and_is_no_measurement(tiny_manifest):
    result = run.run_cell(args(trace=1, seconds=3.0), require_tpu=False,
                          manifest_path=tiny_manifest)
    assert result["attempted"] > 50 and result["failed"] == 0
    failing = sorted(k for k, (got, limit) in result["checks"].items()
                     if got != limit)
    assert failing == ["not_on_tpu"] and result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    by_template = result["facts"]["latency_ms_by_template"]
    assert set(by_template) == {"topn_filtered", "topn_tree0", "topn_tree1",
                                "count2", "write_pair"}
    # The per-layer metrics this rehearsal was written for are in the line,
    # and none that the manifest does not list for the cell.
    manifest = run.read_json(tiny_manifest)
    listed = {m["name"] for m in manifest["per_layer"]
              if run.metric_applies(m, CELL)}
    assert set(WRITTEN_FOR) <= set(result["metrics"]) <= listed
    value = {k: v["value"] for k, v in result["metrics"].items()}
    # 616 candidate rows a TopN, in one program.
    assert value["topn.chunks_per_op"] == 1.0
    assert value["topn.candidate_rows_per_op"] == float(ROWS)
    assert value["topn.host_self_ms"] > 0


def test_an_altered_topn_is_not_correct(tiny_manifest):
    seen = []

    def tamper(sent):
        if isinstance(sent.result, list) and sent.result and not seen:
            seen.append(sent.pql)
            sent.result[0]["count"] += 1

    result = run.run_cell(args(), require_tpu=False, tamper=tamper,
                          manifest_path=tiny_manifest)
    assert seen and seen[0].startswith("TopN(f, ")
    assert result["checks"]["wrong_answers"] == [1, 0]
    assert result["failed"] == 1 and result["correct"] is False


def reader(name):
    return run.load_layer(name).read


def vars_with(queries, chunks, rows):
    ex = {"assign_hits": 0}
    if queries is not None:
        ex.update(topn_queries=queries, topn_chunks=chunks,
                  topn_candidate_rows=rows)
    return {"executor": ex}


def test_the_counter_readers_are_ratios_and_absent_without_the_counters():
    chunks, rows = (reader(n) for n in NEW[:2])
    ctx = run.Context(before=vars_with(10, 170, 82080),
                      after=vars_with(110, 1870, 902880))
    assert chunks(ctx) == 17.0 and rows(ctx) == 8208.0
    # No TopN in the window: nothing to divide by.
    still = run.Context(before=vars_with(10, 170, 82080),
                        after=vars_with(10, 170, 82080))
    assert chunks(still) is None and rows(still) is None
    # The parent's program: an `executor` group without the counters, or
    # none at all.
    for before, after in ((vars_with(None, 0, 0), vars_with(None, 0, 0)),
                          ({}, {}), (None, None)):
        old = run.Context(before=before, after=after)
        assert chunks(old) is None and rows(old) is None


def trace(*spans):
    return {"spans": [{"name": n, "self_ms": ms, "dur_ms": ms + 1.0}
                      for n, ms in spans]}


def test_host_self_ms_sums_rank_and_replay_of_the_queries_that_have_them():
    read = reader("topn.host_self_ms")
    ctx = run.Context(traces=[
        trace(("request", 0.4), ("topn.rank", 2.0), ("topn.chunk", 9.0),
              ("topn.replay", 1.0), ("topn.replay", 0.5)),
        trace(("request", 0.4), ("topn.rank", 4.0), ("topn.replay", 0.5)),
        trace(("request", 0.3), ("executor.fanout", 0.03)),     # a Count
    ])
    assert read(ctx) == pytest.approx((3.5 + 4.5) / 2)
    assert read(run.Context(traces=[trace(("request", 0.3))])) is None
    assert read(run.Context(traces=[])) is None


PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}


def test_topn_roofline_is_the_probes_bytes_over_the_busy_time():
    read = reader("kernel.topn_roofline")
    probe = {"waves_inside": 8, "width": 1, "leaves": 8209,
             "profile": {"busy_s": 0.04}}
    ctx = run.Context(cfg={"shards": 1}, peaks=PEAKS,
                      device={"kind": "TPU v5 lite"}, probe=probe)
    # 8 waves x 8,209 planes x 128 KiB = 8.0 GiB, 10.5 ms at 819 GB/s.
    least_s = 8 * 8209 * 131072 / 819e9
    assert least_s == pytest.approx(0.010510, rel=1e-3)
    assert read(ctx) == pytest.approx(100 * least_s / 0.04)
    # A device that did nothing but read the planes at the peak reads 100.
    probe["profile"]["busy_s"] = least_s
    assert read(ctx) == pytest.approx(100.0)
    for nothing in (None, dict(probe, profile=None),
                    dict(probe, profile={"busy_s": 0.0}),
                    dict(probe, waves_inside=0)):
        ctx.probe = nothing
        assert read(ctx) is None
    ctx.probe, ctx.device = probe, {"kind": "TPU v9 imaginary"}
    assert read(ctx) is None
