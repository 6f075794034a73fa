"""The cell rehearsed on the CPU at 2 shards through the whole of a run:
server child, loader, warm-up, window, traces, crash and restart,
comparison. The rehearsal
skips only the look for a chip (`require_tpu=False`); the command itself
refuses the run as a measurement. And the timed path broken underneath:
an answer altered where it is produced, the device path failing so that
the host rungs serve, and an acknowledged Set gone from the disk after the
crash, each have to end with `correct` false."""

import argparse
import glob
import os

import pytest

import run


def args(workload, trace=0, seconds=2.0):
    return argparse.Namespace(workload=workload, seed=2**31 + 77,
                              seconds=seconds, trace=trace)


def failing(result):
    return sorted(k for k, (got, limit) in result["checks"].items()
                  if got != limit)


CELL = "zipf-64.adhoc"
# The per-layer metrics of the cell that this rehearsal was written for, but
# the device's own and those read from its trace: the CPU backend keeps no
# peak and writes no device trace, and their readers return nothing.
WRITTEN_FOR = (
    "client.floor_ms", "parse_plan.ms", "sched.hold_ms",
    "batcher.coalesce_ratio", "executor.dispatch_ms", "engine.gather_ms",
    "engine.memo_hit_share", "engine.fn_builds_in_window",
    "server.request_self_ms", "executor.dispatch_self_ms",
    "batcher.launch_ms", "engine.memo_probe_ms", "engine.stack_ms",
    "engine.device_wait_ms", "engine.restack_mb_per_launch",
    "engine.fp_walks_per_op", "executor.fanout_self_ms",
    "executor.assign_walks_per_op", "executor.topn_shard_replays_per_op",
    "host.cpu_ms_per_op", "host.gc_ms_per_op", "server.request_cpu_ms",
    "host.off_cpu_share", "engine.device_wait_off_cpu_ms")


def test_cell_agrees_on_every_answer_and_is_no_measurement(tiny_manifest,
                                                          workload=CELL):
    result = run.run_cell(args(workload, trace=1), require_tpu=False,
                          manifest_path=tiny_manifest)
    assert result["attempted"] > 50 and result["failed"] == 0
    # Everything holds but the device: the CPU is never a measurement.
    assert failing(result) == ["not_on_tpu"] and result["correct"] is False
    assert list(result)[-1] == "checks"
    # The line holds every metric this rehearsal was written for (a traced
    # run that lacks one is refused) and none that the manifest does not
    # list for the cell. A metric appended since may
    # find nothing to read on the CPU, and is not asked for.
    manifest = run.read_json(tiny_manifest)
    listed = {m["name"] for m in manifest["per_layer"]
              if run.metric_applies(m, workload)}
    assert set(WRITTEN_FOR) <= set(result["metrics"]) <= listed
    assert result["facts"]["restart_s"] > 0
    assert result["device"]["platform"] == "cpu"


def test_the_command_refuses_a_cpu_run(capsys, monkeypatch, tiny_manifest):
    monkeypatch.setattr(run, "find_cell",
                        lambda name, _=None, f=run.find_cell:
                        f(name, tiny_manifest))
    status = run.main(["--workload", CELL, "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert status == 3 and out.out == "" and "not a measurement" in out.err


def test_an_altered_answer_is_not_correct(tiny_manifest):
    seen = []

    def tamper(sent):
        if isinstance(sent.result, int) and not isinstance(sent.result, bool) \
                and not seen:
            seen.append(sent.pql)
            sent.result += 1

    result = run.run_cell(args(CELL), require_tpu=False,
                          tamper=tamper, manifest_path=tiny_manifest)
    assert seen and result["checks"]["wrong_answers"] == [1, 0]
    assert result["failed"] == 1 and result["correct"] is False
    assert set(result["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                      "latency_p95_ms", "setup_s"}


def test_host_rungs_serving_is_not_correct(tiny_manifest):
    """With every device program failing to build, the executor's ladder
    answers from the host: every answer is still right, and the run must
    not pass for one of the device path."""
    result = run.run_cell(
        args(CELL, seconds=1.0), require_tpu=False,
        server_env={"PILOSA_TPU_FAILPOINTS": "device-compile=error"},
        manifest_path=tiny_manifest)
    assert result["checks"]["wrong_answers"] == [0, 0]
    assert result["checks"]["ladder_nonzero"][0] > 0
    assert result["correct"] is False


def test_an_acknowledged_set_gone_from_the_disk_is_not_correct(
        tiny_manifest):
    """Between the kill and the restart the last record of every op log of
    the written field is cut off (13 bytes: type, position, checksum): the
    window's answers were all right, and the read-back has to miss a Set."""
    cut = []

    def disk_fault(data_dir):
        for path in glob.glob(os.path.join(
                data_dir, "indexes", "*", "f", "views", "standard",
                "fragments", "*")):
            if os.path.isfile(path) and "." not in os.path.basename(path):
                os.truncate(path, os.path.getsize(path) - 13)
                cut.append(path)

    result = run.run_cell(args(CELL, seconds=1.0), require_tpu=False,
                          disk_fault=disk_fault,
                          manifest_path=tiny_manifest)
    assert cut and result["checks"]["wrong_answers"] == [0, 0]
    assert result["checks"]["lost_over_restart"][0] > 0
    assert result["correct"] is False
