"""chip_smoke.py rehearsed on the CPU backend at a tiny size.

Two facts are pinned. On the CPU the script does everything — load, every
query against the numpy reference, the coalescing wave, the /debug/vars
verdict, the restart — and then REFUSES the run, with `device` the one
failed check: there is no way to make a CPU run pass. And with a compile
failpoint armed in the server child every answer is still right (the
fallback ladder serves them, bit-exact) while the ladder checks go false:
a fallback cannot pass the smoke.
"""

import importlib.util
import json
import os
import subprocess
import sys

SMOKE = os.path.join(os.path.dirname(__file__), os.pardir, "chip_smoke.py")


def _run(tmp_path, **extra_env):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        # The smoke's own directory holds the default cache; a test run
        # keeps its litter under tmp_path.
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        **extra_env,
    )
    r = subprocess.run(
        [sys.executable, SMOKE, "--shards", "4", "--rows", "4"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    # A refused run prints no result on stdout; its summary is the last
    # line of stderr.
    assert r.returncode == 1, (r.returncode, r.stderr[-2000:])
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
    summary = json.loads(r.stderr.strip().splitlines()[-1])
    assert summary["ok"] is False
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    return summary


def test_cpu_run_does_everything_and_fails_only_on_device(tmp_path):
    summary = _run(tmp_path)
    failed = [k for k, ok in summary["checks"].items() if not ok]
    assert failed == ["device"], summary["failed"]
    assert summary["checks"]["completed"]
    assert summary["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert summary["cut"] == {"shards": {"full": 256, "ran": 4},
                              "rows": {"full": 32, "ran": 4}}
    assert summary["facts"]["counters"]["batcher"]["coalesced"] > 0
    assert summary["facts"]["compile_cache"]["dir"] == str(
        tmp_path / "jax_cache")


def test_fallback_ladder_cannot_pass(tmp_path):
    summary = _run(tmp_path, PILOSA_TPU_FAILPOINTS="device-compile=error")
    checks = summary["checks"]
    # The ladder did its job: every answer equals the reference ...
    for name in ("count_row", "count_intersect", "count_union3",
                 "topn_filtered", "bsi_sum", "row_columns",
                 "count_after_set", "wave_answers"):
        assert checks[name], (name, summary["failed"])
    # ... and the smoke refuses the run because of who answered.
    for name in ("device", "ladder_counters_zero", "device_plane_closed"):
        assert not checks[name], name
    assert summary["failed"]["ladder_counters_zero"][
        "device_dispatch_errors"] > 0


def test_result_line_has_the_contract_keys_and_no_others():
    # A CPU run can never print it, so the last stdout line of a passing
    # run is pinned on the function that writes it.
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    summary = {"ok": True, "claim": None, "checks": {"device": True},
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 4}}
    assert json.loads(smoke.result_line(summary)) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
