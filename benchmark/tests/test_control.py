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
with nothing wrong.

A TopN under `tanimotoThreshold` states its rule (100 c > t (a + b - c),
upstream's ceil(100 c / (a + b - c)) > t), so its controls break that rule,
on `tests/data/tiny-chem.json` under `tests/data/tiny-tanimoto.json` at a
seed where some read row lies exactly on a threshold and another less than
half a point above one:

  at_threshold  `>=` in the place of `>`
  rounded       `round` in the place of `ceil`
  left_out      one passing analogue left out of one tanimoto answer in
                a hundred

`python benchmark/tests/test_control.py <workload> <seed>...` runs the
`CONTROLS` at the cell's own size, for the record in PERF.md; it needs no
server, since the control is numpy.
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
THRESHOLD_CONTROLS = ("sound", "at_threshold", "rounded", "left_out")
# tiny-chem under tiny-tanimoto at this seed: its questions find read rows
# exactly on a threshold and read rows less than half a point above one
# (`threshold_edges`).
THRESHOLD_SEED = 2**31 + 4647


class AtThreshold(reference.Reference):
    """`>=` in the place of `>`: a row exactly on the threshold passes."""

    @staticmethod
    def tanimoto_passes(c, a, b, t):
        return (c > 0) & (100 * c >= t * (a + b - c))


class Rounded(reference.Reference):
    """`round` in the place of `ceil`: a row less than half a point above
    the threshold fails."""

    @staticmethod
    def tanimoto_passes(c, a, b, t):
        return (c > 0) & (np.rint(100.0 * c / (a + b - c)) > t)


RULES = {"at_threshold": AtThreshold, "rounded": Rounded}


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


def serving(cfg, data, mix, control):
    """The reference that stands in the program's place."""
    if control == "stale_shard":
        return reference.build(without_last_shard(cfg, data), mix)
    if control in RULES:
        return RULES[control](data, mix.get("writer_rows", {}))
    return reference.build(data, mix)


def leave_out_an_analogue(pql, pairs, mix):
    """Drops the least similar read row other than the query molecule from
    a tanimoto answer; False where it holds none."""
    _, pos, _ = reference.parse(pql)
    query = next(iter(pos[1][2].values()))
    writers = [range(lo, hi + 1) for lo, hi in mix["writer_rows"].values()]
    analogues = [p for p in pairs if p["id"] != query
                 and not any(p["id"] in w for w in writers)]
    if not analogues:
        return False
    pairs.remove(analogues[-1])
    return True


def control_run(cfg, mix, seed, control, groups=120):
    """`groups` request groups per client, answered by the control, judged
    by the harness. Returns (wrong, attempted)."""
    data = generate.Data(cfg, seed)
    ref = reference.build(data, mix)
    served = serving(cfg, data, mix, control)
    sent = []
    n = similar = 0
    due = False
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
                if control == "left_out" and "tanimotoThreshold" in pql:
                    similar += 1
                    due = due or similar % 100 == 0
                    if due and leave_out_an_analogue(pql, s.result, mix):
                        due = False
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


def threshold_edges(cfg, mix, seed, groups=120):
    """(rows exactly on a threshold, rows less than half a point above
    one): read rows with c > 0 over the distinct tanimoto questions that
    `control_run` asks."""
    ref = reference.build(generate.Data(cfg, seed), mix)
    streams = [generate.Requests(mix, cfg, seed, k)
               for k in range(mix["clients"])]
    asked = {pql for stream in streams for _ in range(groups)
             for pql in stream.next()[1] if "tanimotoThreshold" in pql}
    read = mix["writer_rows"]["m"][0]
    a = np.array([ref.row_count("m", r) for r in range(read)])
    on = above = 0
    for pql in asked:
        _, pos, kw = reference.parse(pql)
        filt = ref.bitmap(pos[1])
        c = np.bitwise_count(ref.matrix("m")[:read] & filt).sum(axis=1)
        d = a + ref.count(filt) - c
        t = kw["tanimotoThreshold"]
        on += int(np.sum((c > 0) & (100 * c == t * d)))
        above += int(np.sum((c > 0) & (100 * c > t * d)
                            & (200 * c < (2 * t + 1) * d)))
    return on, above


def test_threshold_controls_come_out_not_correct():
    cfg = run.read_json(run.REPO, "benchmark/tests/data/tiny-chem.json")
    mix = run.read_json(HERE, "data", "tiny-tanimoto.json")
    on, above = threshold_edges(cfg, mix, THRESHOLD_SEED)
    assert on > 0 and above > 0
    for control in THRESHOLD_CONTROLS:
        wrong, n = control_run(cfg, mix, THRESHOLD_SEED, control)
        assert n > 2000
        assert (wrong == 0) == (control == "sound"), (control, wrong)


if __name__ == "__main__":
    _, _, cfg, mix = run.find_cell(sys.argv[1])
    for seed in map(int, sys.argv[2:]):
        for control in CONTROLS:
            wrong, n = control_run(cfg, mix, seed, control)
            print(json.dumps({"workload": sys.argv[1], "seed": seed,
                              "control": control, "wrong": wrong,
                              "attempted": n, "limit": 0}), flush=True)
