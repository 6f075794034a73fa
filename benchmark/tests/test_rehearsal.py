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

from conftest import HERE

import run

TINY = os.path.join(HERE, "data", "BENCHMARK.tiny40.json")


def args(workload, trace=0, seconds=2.0):
    return argparse.Namespace(workload=workload, seed=2**31 + 77,
                              seconds=seconds, trace=trace)


def failing(result):
    return sorted(k for k, (got, limit) in result["checks"].items()
                  if got != limit)


CELL = "zipf-64.adhoc"


def test_cell_agrees_on_every_answer_and_is_no_measurement(workload=CELL):
    result = run.run_cell(args(workload, trace=1), require_tpu=False,
                          manifest_path=TINY)
    assert result["attempted"] > 50 and result["failed"] == 0
    # Everything holds but the device: the CPU is never a measurement.
    assert failing(result) == ["not_on_tpu"] and result["correct"] is False
    assert list(result)[-1] == "checks"
    # Every per-layer metric the manifest lists for this cell is in the
    # line (the driver refuses a traced run that lacks one), but for the
    # device's own and the one read from its trace: the CPU backend keeps
    # no peak and writes no device trace, and their readers return nothing.
    manifest = run.read_json(TINY)
    listed = {m["name"]: m for m in manifest["per_layer"]
              if run.metric_applies(m, workload)}
    assert set(result["metrics"]) == {
        name for name, m in listed.items()
        if m["layer"] != "device" and m["source"] != "device_trace"}
    assert result["facts"]["restart_s"] > 0
    assert result["device"]["platform"] == "cpu"


def test_the_command_refuses_a_cpu_run(capsys, monkeypatch):
    monkeypatch.setattr(run, "find_cell",
                        lambda name, _=None, f=run.find_cell: f(name, TINY))
    status = run.main(["--workload", CELL, "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert status == 3 and out.out == "" and "not a measurement" in out.err


def test_an_altered_answer_is_not_correct():
    seen = []

    def tamper(sent):
        if isinstance(sent.result, int) and not isinstance(sent.result, bool) \
                and not seen:
            seen.append(sent.pql)
            sent.result += 1

    result = run.run_cell(args(CELL), require_tpu=False,
                          tamper=tamper, manifest_path=TINY)
    assert seen and result["checks"]["wrong_answers"] == [1, 0]
    assert result["failed"] == 1 and result["correct"] is False
    assert set(result["metrics"]) == {"ops_per_s", "latency_p50_ms",
                                      "latency_p95_ms", "setup_s"}


def test_host_rungs_serving_is_not_correct():
    """With every device program failing to build, the executor's ladder
    answers from the host: every answer is still right, and the run must
    not pass for one of the device path."""
    result = run.run_cell(
        args(CELL, seconds=1.0), require_tpu=False,
        server_env={"PILOSA_TPU_FAILPOINTS": "device-compile=error"},
        manifest_path=TINY)
    assert result["checks"]["wrong_answers"] == [0, 0]
    assert result["checks"]["ladder_nonzero"][0] > 0
    assert result["correct"] is False


def test_an_acknowledged_set_gone_from_the_disk_is_not_correct():
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
                          disk_fault=disk_fault, manifest_path=TINY)
    assert cut and result["checks"]["wrong_answers"] == [0, 0]
    assert result["checks"]["lost_over_restart"][0] > 0
    assert result["correct"] is False
