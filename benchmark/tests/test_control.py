"""The control of `correct`, and the faults the comparison has to catch.

The system runs no model and states no precision, so the control is the
reference put in the program's place with one stated guarantee broken:

  lost_write    a Set is acknowledged and not applied ("a Set that
                returned ... is read back by the next query of the same
                client"); also: a step that leaves its state unchanged
  stale_shard   answers come from the index without its last shard
                ("answers: exact"); also: part of the batch left out
  altered       one answer in a hundred is off by one where it is produced

Each has to come out as not correct through the harness's own `judge`; the
sound program (the reference itself, a second instance) has to come out
with nothing wrong. `python benchmark/tests/test_control.py <workload>
<seed>...` runs the same at the cell's own size, for the record in PERF.md;
it needs no server, since the control is numpy.
"""

import copy
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import client  # noqa: E402
import generate  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

CONTROLS = ("sound", "lost_write", "stale_shard", "altered")


def served_value(want):
    """The reference's answer as the server's JSON would carry it."""
    if isinstance(want, tuple):
        _, counts, n, _ = want
        order = sorted(range(len(counts)), key=lambda r: -counts[r])
        pairs = [{"id": r, "count": counts[r]} for r in order if counts[r] > 0]
        return pairs[:n] if n else pairs
    return copy.deepcopy(want)


def without_last_shard(cfg, data):
    """The same index with its last shard's bits gone."""
    cut = (cfg["shards"] - 1) * generate.SHARD_WIDTH
    stale = copy.copy(data)
    stale.cols = {f: [c[:np.searchsorted(c, cut)] for c in rows]
                  for f, rows in data.cols.items()}
    return stale


def control_run(cfg, mix, seed, control, groups=120):
    """`groups` request groups per client, answered by the control, judged
    by the harness. Returns (wrong, attempted)."""
    data = generate.Data(cfg, seed)
    ref = reference.build(data, mix)
    served = reference.build(
        without_last_shard(cfg, data) if control == "stale_shard" else data,
        mix)
    sent = []
    n = 0
    for k in range(mix["clients"]):
        stream = generate.Requests(mix, cfg, seed, k)
        mine = []
        for _ in range(groups):
            template, group = stream.next()
            for pql in group:
                s = client.Sent(k, template, pql)
                s.status = 200
                if control == "lost_write" and pql.startswith("Set("):
                    s.result = True
                else:
                    s.result = served_value(served.answer(pql))
                n += 1
                if control == "altered" and n % 100 == 0 \
                        and isinstance(s.result, int) \
                        and not isinstance(s.result, bool):
                    s.result += 1
                mine.append(s)
        sent.append(mine)
    judged = run.judge(ref, [[] for _ in sent], sent)
    return judged["wrong_answers"] + judged["unanswered"], n


def test_controls_come_out_not_correct(tiny_manifest):
    _, _, cfg, mix = run.find_cell("zipf-64.adhoc", tiny_manifest)
    for control in CONTROLS:
        wrong, n = control_run(cfg, mix, 5, control, groups=60)
        assert n > 900
        assert (wrong == 0) == (control == "sound"), (control, wrong)


if __name__ == "__main__":
    _, _, cfg, mix = run.find_cell(sys.argv[1])
    for seed in map(int, sys.argv[2:]):
        for control in CONTROLS:
            wrong, n = control_run(cfg, mix, seed, control)
            print(json.dumps({"workload": sys.argv[1], "seed": seed,
                              "control": control, "wrong": wrong,
                              "attempted": n, "limit": 0}), flush=True)
