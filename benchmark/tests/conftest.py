"""The benchmark's own tests: `python -m pytest benchmark/tests -q` with
JAX_PLATFORMS=cpu. The modules under benchmark/ import one another by bare
name, as `run.py` makes them when it is the command."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def tiny_manifest(tmp_path_factory):
    """The path of the tiny mirror of BENCHMARK.json (`tiny.py`)."""
    import tiny
    return tiny.manifest_path(tmp_path_factory.mktemp("tiny"))


class ProbeServer:
    """Stands in for the server under `run.kernel_probe`: a capture is
    granted and every question answered with 0."""

    def request(self, method, path, body=None):
        if path.startswith("/debug/profile"):
            return {"path": ""}
        return {"results": [0]}


def probe_requests(cfg, mix, seed):
    """Every request `run.kernel_probe` sends, wave by wave and in the
    order it sends them (each wave's Set, then its questions)."""
    import run
    lead, run.PROBE_LEAD_S = run.PROBE_LEAD_S, 0.0
    try:
        probe = run.kernel_probe(ProbeServer(), cfg, mix, seed)
    finally:
        run.PROBE_LEAD_S = lead
    return [pql for pql, _ in probe["answers"]]
