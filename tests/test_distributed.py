"""Multi-host collective backend: REAL multi-process jax.distributed.

Two OS processes each own half the shards, join one jax.distributed job
(CPU backend, 2 virtual devices per process), build globally-sharded plane
arrays from process-local data, and produce identical all-reduced counts —
the TPU-native analog of the reference's cross-host scatter-gather RPC
(executor.go:1393-1440), with the reduce riding XLA collectives instead of
Python. Run as subprocesses because jax.distributed binds one process_id
per OS process.
"""

import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest


def free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


WORKER = textwrap.dedent("""
    import os, sys
    import numpy as np

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    )
    coordinator, n_proc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

    import jax

    jax.config.update("jax_platforms", "cpu")

    from pilosa_tpu.parallel import distributed as dist

    assert dist.initialize(coordinator, n_proc, pid)

    assert jax.process_count() == n_proc, jax.process_count()
    assert jax.device_count() == 2 * n_proc

    # 8 shards, 64 words per plane; shard s has popcount (s+1) in row 0 and
    # bit pattern overlapping row 1 only on even shards.
    n_shards, w = 8, 64
    padded, lo, hi = dist.process_shard_slots(n_shards)
    assert padded == 8
    a_local = np.zeros((hi - lo, w), dtype=np.uint32)
    b_local = np.zeros((hi - lo, w), dtype=np.uint32)
    for s in range(lo, hi):
        a_local[s - lo, 0] = (1 << (s + 1)) - 1     # popcount s+1
        b_local[s - lo, 0] = 0xFFFFFFFF if s % 2 == 0 else 0

    mesh = dist.global_mesh()
    A = dist.make_global_planes(a_local, padded, mesh)
    B = dist.make_global_planes(b_local, padded, mesh)

    total = dist.global_count(A)
    want_total = sum(s + 1 for s in range(n_shards))
    assert total == want_total, (total, want_total)

    inter = dist.global_and_count(A, B)
    want_inter = sum(s + 1 for s in range(n_shards) if s % 2 == 0)
    assert inter == want_inter, (inter, want_inter)
    print(f"WORKER_OK pid={pid} total={total} inter={inter}")
""")


@pytest.mark.parametrize("n_proc", [2])
def test_two_process_global_mesh_counts(tmp_path, n_proc):
    import os

    port = free_port()
    coordinator = f"localhost:{port}"
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ,
           "PYTHONPATH": repo_root + os.pathsep + os.environ.get("PYTHONPATH", "")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coordinator, str(n_proc), str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for pid in range(n_proc)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=180)
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err[-2000:]}"
        assert "WORKER_OK" in out
    # Every process materialized the same all-reduced scalars.
    totals = {line for _, out, _ in outs for line in out.splitlines()
              if "WORKER_OK" in line}
    assert len({t.split("total=")[1] for t in totals}) == 1


def test_collective_count_endpoint(tmp_path):
    """Leader-driven collective count through the real server/API on a
    single-process job (the degenerate case: no peers to broadcast to, the
    local mesh is the global mesh). Cross-checks against the PQL path."""
    import json
    import urllib.request

    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    s = Server(data_dir=str(tmp_path / "n0"), cache_flush_interval=0)
    s.open()
    try:
        client = InternalClient()
        h = f"localhost:{s.port}"
        client.create_index(h, "cc")
        client.create_field(h, "cc", "f")
        from pilosa_tpu.constants import SHARD_WIDTH

        for col in [1, 5, SHARD_WIDTH + 3]:
            client.query(h, "cc", f"Set({col}, f=7)")
            client.query(h, "cc", f"Set({col}, f=9)")
        client.query(h, "cc", f"Set(2, f=9)")

        req = urllib.request.Request(
            f"http://{h}/internal/collective/count",
            data=json.dumps({"index": "cc", "field": "f", "rows": [7]}).encode(),
            method="POST",
        )
        got = json.load(urllib.request.urlopen(req))["count"]
        assert got == 3
        # Intersect of two rows across the mesh.
        req = urllib.request.Request(
            f"http://{h}/internal/collective/count",
            data=json.dumps({"index": "cc", "field": "f", "rows": [7, 9]}).encode(),
            method="POST",
        )
        assert json.load(urllib.request.urlopen(req))["count"] == 3
        want = client.query(h, "cc", "Count(Intersect(Row(f=7), Row(f=9)))")
        assert want["results"][0] == 3
    finally:
        s.close()


def test_single_process_degenerates_to_local(monkeypatch):
    """initialize() without a coordinator is a no-op and the helpers work
    on the local (virtual 8-device) mesh."""
    from pilosa_tpu.parallel import distributed as dist

    monkeypatch.delenv("PILOSA_JAX_COORDINATOR", raising=False)
    assert not dist.initialize()
    n_shards = 8
    padded, lo, hi = dist.process_shard_slots(n_shards)
    assert lo == 0 and hi == padded >= n_shards
    planes = np.zeros((hi - lo, 16), dtype=np.uint32)
    planes[3, 0] = 0b1011
    A = dist.make_global_planes(planes, padded)
    assert dist.global_count(A) == 3
