"""The four-chip cell rehearsed on the CPU: `zipf-4x64.adhoc` at 8 shards
through the whole of a run, the server child on four virtual CPU devices
with the configuration's own `--engine-mesh-devices 4`. And the four
`mesh.*` readers on what a run would hand them, a program without the
counters (the parent's) among it."""

import argparse

import pytest

import run

CELL = "zipf-4x64.adhoc"
FOUR_DEVICES = {"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
# The per-layer metrics of the cell that this rehearsal was written for, but
# the device's own and those read from its trace, which the CPU backend gives
# nothing for (`mesh.hbm_balance` among them: its allocator reports no bytes).
WRITTEN_FOR = (
    "client.floor_ms", "parse_plan.ms", "sched.hold_ms",
    "engine.fn_builds_in_window", "mesh.launches_per_op",
    "executor.fanout_self_ms", "executor.assign_walks_per_op",
    "executor.topn_shard_replays_per_op", "host.cpu_ms_per_op",
    "host.gc_ms_per_op", "server.request_cpu_ms", "host.off_cpu_share",
    "engine.device_wait_off_cpu_ms")


def test_the_mesh_cell_agrees_on_every_answer_and_is_no_measurement(
        tiny_manifest):
    result = run.run_cell(
        argparse.Namespace(workload=CELL, seed=2**31 + 30, seconds=2.0,
                           trace=1),
        require_tpu=False, server_env=FOUR_DEVICES,
        manifest_path=tiny_manifest)
    assert result["attempted"] > 50 and result["failed"] == 0
    failing = sorted(k for k, (got, limit) in result["checks"].items()
                     if got != limit)
    assert failing == ["not_on_tpu"] and result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4
    # The per-layer metrics this rehearsal was written for are in the line,
    # and none that the manifest does not list for the cell.
    manifest = run.read_json(tiny_manifest)
    listed = {m["name"] for m in manifest["per_layer"]
              if run.metric_applies(m, CELL)}
    assert set(WRITTEN_FOR) <= set(result["metrics"]) <= listed
    # Every launch spans the four devices; of a deck's 20 requests 18 reach
    # the device at most twice each.
    assert 0.3 < result["metrics"]["mesh.launches_per_op"]["value"] < 2.0
    grew = result["facts"]["window_counters"]
    assert grew["engine_cache.mesh_launches"] > 0
    assert grew["engine_cache.h2d_bytes"] > 0


def reader(name):
    return run.load_layer(name).read


PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}


def probe(busy_s, planes, waves_inside=8):
    return {"waves_inside": waves_inside, "width": 32, "leaves": 2,
            "profile": {"busy_s": busy_s, "device_planes": planes}}


def test_count_roofline_is_reckoned_per_chip():
    read = reader("mesh.count_roofline")
    ctx = run.Context(cfg={"shards": 256}, peaks=PEAKS,
                      device={"kind": "TPU v5 lite"},
                      probe=probe(busy_s=0.02, planes=4))
    # 8 waves x 64 planes x 256 shards x 128 KiB = 16 GiB, 4 GiB a chip.
    least_s = 8 * 64 * 256 * 131072 / 4 / 819e9
    assert read(ctx) == pytest.approx(100 * least_s / 0.02)
    # One chip's bandwidth for all four chips' bytes is what
    # kernel.count_roofline would read: four times as much.
    assert reader("kernel.count_roofline")(ctx) == pytest.approx(
        4 * read(ctx))
    # A chip that did nothing but read its rows at the peak reads 100.
    ctx.probe = probe(busy_s=least_s, planes=4)
    assert read(ctx) == pytest.approx(100.0)
    for nothing in (None, {"waves_inside": 8, "profile": None},
                    probe(0.0, 4), probe(0.02, 4, waves_inside=0)):
        ctx.probe = nothing
        assert read(ctx) is None


def test_collective_share_counts_the_cross_chip_operations():
    read = reader("mesh.collective_share")
    by_name = {"convert_reduce_fusion": 0.6, "copy-start": 0.2,
               "all-reduce": 0.05, "all-reduce-start": 0.01,
               "all-reduce-done": 0.04, "all-gather": 0.1, "psum": 0.1,
               "all-reduce-fusion-like": 0.4}
    got = read(run.Context(profile={"by_name": by_name}))
    assert got == pytest.approx(100 * 0.3 / 1.5)
    # One chip: no collective in the capture reads 0.0, not "absent".
    assert read(run.Context(profile={"by_name": {"copy": 1.0}})) == 0.0
    assert read(run.Context(profile=None)) is None
    assert read(run.Context(profile={"by_name": {}})) is None


def vars_with(mesh_launches, admitted):
    ec = {} if mesh_launches is None else {"mesh_launches": mesh_launches}
    return {"engine_cache": ec, "scheduler": {"admitted": admitted}}


def test_launches_per_op_is_absent_on_a_program_without_the_counter():
    read = reader("mesh.launches_per_op")
    assert read(run.Context(before=vars_with(10, 100),
                            after=vars_with(90, 200))) == 0.8
    assert read(run.Context(before=vars_with(0, 100),
                            after=vars_with(0, 200))) == 0.0
    assert read(run.Context(before=vars_with(None, 100),
                            after=vars_with(None, 200))) is None
    assert read(run.Context(before=vars_with(0, 100),
                            after=vars_with(5, 100))) is None


def test_hbm_balance_is_fullest_over_emptiest():
    read = reader("mesh.hbm_balance")

    def after(*in_use):
        return {"device": {"devices": [{"id": i, "bytes_in_use": b}
                                       for i, b in enumerate(in_use)]}}

    assert read(run.Context(after=after(2e9, 2e9, 2e9, 2e9))) == 1.0
    assert read(run.Context(after=after(3e9, 2e9, 2e9, 2.5e9))) == 1.5
    assert read(run.Context(after=after(4e9))) == 1.0
    assert read(run.Context(after=after(None, None))) is None
    assert read(run.Context(after=None)) is None
