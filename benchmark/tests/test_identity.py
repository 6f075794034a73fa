"""The cells that were there before integer fields came in draw, send, load
and are judged exactly as they were: at the cells' own sizes and one seed,
a digest of the drawn index (`Data.cols`), of the sweep and the first 200
request groups of clients 0 and 15, of every request body the loader
posts, and of the reference's answers to those groups. The digests were
taken from the harness as it stood before field options, draw files, int
fields and the BSI calls were added, and are pinned here. Beside them, a
digest of the kernel probe's requests across its waves, taken before a
probe's question could name its wave (`{w}`).

    python3 benchmark/tests/test_identity.py     # prints the digests
"""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import generate  # noqa: E402
import loader  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from conftest import probe_requests  # noqa: E402

SEED = 3000004201
GROUPS = 200
CLIENTS = (0, 15)

PINNED = {
    "zipf-64.adhoc": {
        "cols": "9eebcc3b019a8848d2ec31c6e1a3007287d913daf6cfa13e12df82a779cee31d",
        "groups": "1326a60b999e84ec23767bdd4f584211fc64f1386ded8511b28024e2da78f364",
        "loader": "4706e7b98f2937fcea414172458d4a082d46e534ae190b8fc25829d14731be0b",
        "answers": "f495c357b5bf8c902deaf7c87631fc32efa35e60164f4c87d13a53eb486fbd0d"},
    "zipf-4x64.adhoc": {
        "cols": "9d6e01a02988b8ef3dde15e319b1ec725ce02ccee53dd124e30d6615eb19f7dd",
        "groups": "066d07338a5b12e336ccd814f487b2946742d35802a72ab1465525c09986bab1",
        "loader": "9d20a9edc374d4fb9cf06a717e35230d125a27c032431312812aeac065a651a0",
        "answers": "ad4d48f32b68d6686274d4886f3ac4b95210309b8fd65e09083232caf45cf7d1"},
    "zipf-1x8k.topn": {
        "cols": "31034b0c8113b31250fe8af6eb737acb336e864bed71aad974b65686a3ad04b4",
        "groups": "a7474d65c36e7d61d3b9ff1d51f3d6c5546289cb21f196960bb07d5eefa82127",
        "loader": "db057faa23bf72024b0f7213456e54056c9f0597879284264f954c40039ae9be",
        "answers": "573639ff0b4c78a6c923c0f7269ca13c6bb48e30c6db55ee658c877f4927d58e"},
}


class Recorder:
    """Stands in for the server: keeps a digest of each request's body by
    its path (the loader posts from several threads, in no fixed order)."""

    def __init__(self):
        self.bodies = {}

    def request(self, method, path, body=None):
        if isinstance(body, str):
            body = body.encode()
        self.bodies[f"{method} {path}"] = hashlib.sha256(body or b"").hexdigest()


def digest(obj):
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()


def cols_digest(data):
    h = hashlib.sha256()
    for name in sorted(data.cols):
        h.update(name.encode())
        for r, c in enumerate(data.cols[name]):
            h.update(f"{r}:{len(c)}:".encode())
            h.update(np.ascontiguousarray(c, dtype="<u4").tobytes())
    return h.hexdigest()


def fingerprint(cell):
    _, _, cfg, mix = run.find_cell(cell)
    data = generate.Data(cfg, SEED)
    out = {"cols": cols_digest(data)}
    groups = {}
    for k in CLIENTS:
        stream = generate.Requests(mix, cfg, SEED, k)
        groups[k] = stream.sweep(mix["clients"]) + [
            stream.next() for _ in range(GROUPS)]
    out["groups"] = digest({str(k): g for k, g in groups.items()})
    srv = Recorder()
    loader.create_schema(srv, cfg)
    loader.load(srv, cfg, data)
    out["loader"] = digest(srv.bodies)
    ref = reference.build(data, mix)
    ref.expect_sets(pql for k in CLIENTS for _, g in groups[k] for pql in g)
    answers = [ref.answer(pql) for k in CLIENTS for _, g in groups[k]
               for pql in g]
    out["answers"] = digest(answers)
    return out


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_the_cell_draws_sends_loads_and_is_judged_as_before(cell):
    assert fingerprint(cell) == PINNED[cell]


def probe_digest(cell):
    _, _, cfg, mix = run.find_cell(cell)
    return digest(probe_requests(cfg, mix, SEED))


# Taken from the harness as it stood before a probe could name its wave.
PINNED_PROBES = {
    "zipf-64.adhoc":
        "84c713aa7bd31b46d082f15344be8c47a9c91d3de0bc4b95812319be060edb42",
    "zipf-4x64.adhoc":
        "45dde87473e10cc49e47a4701b32f7a6206d18a2b0fc8e2359341384dd9d070c",
    "zipf-1x8k.topn":
        "d0f6ad4b821797bff2f902492ffde19b338f8933a027405c53871c67b80c32ba",
}


@pytest.mark.parametrize("cell", sorted(PINNED_PROBES))
def test_the_cells_probe_asks_as_before(cell):
    assert probe_digest(cell) == PINNED_PROBES[cell]


if __name__ == "__main__":
    manifest = run.read_json(run.REPO, "BENCHMARK.json")
    for w in manifest["workloads"]:
        print(json.dumps({w["name"]: dict(fingerprint(w["name"]),
                                          probe=probe_digest(w["name"]))}),
              flush=True)
