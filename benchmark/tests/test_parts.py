"""The yardstick's parts, each against something independent of it: the
loader's roaring file against the program's own reader, the generator's
law against its formula, the reference against plain Python sets, the traffic generator against its
mix file, the readers against a hand-made context."""

import collections
import json
import os

import numpy as np
import pytest

from conftest import BENCH, HERE

import generate
import loader
import reference
import run


def tiny(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def mix_of(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def zipf():
    cfg = tiny("tiny-zipf.json")
    return cfg, generate.Data(cfg, 11)


def columns_of(data, field, row):
    return set(data.cols[field][row].tolist())


def test_zipf_bits_follow_the_generators_law(zipf):
    cfg, data = zipf
    f = cfg["fields"][0]
    v = generate.zipf_offset(f["rows"], f["row_exponent"], f["row_ratio"])
    law = [(v + r) ** -f["row_exponent"] for r in range(f["rows"])]
    assert law[-1] / law[0] == pytest.approx(f["row_ratio"])
    sizes = [len(c) for c in data.cols["f"]]
    assert sum(sizes) <= f["bits"] and sum(sizes) > 0.98 * f["bits"]
    # The floored continuous law gives row r the mass of [r, r + 1).
    e = 1.0 - f["row_exponent"]
    edge = [(v + r) ** e for r in range(f["rows"] + 1)]
    for r, n in enumerate(sizes):
        want = f["bits"] * (edge[r] - edge[r + 1]) / (edge[0] - edge[-1])
        assert abs(n - want) < 6 * want ** 0.5 + 0.02 * want, r
    assert all(np.all(np.diff(c.astype(np.int64)) > 0) and c[-1] < data.n
               for c in data.cols["f"])
    # Columns are skewed too, and by one permutation for every field: rows
    # of two fields share more columns than independent draws would.
    a, b = data.cols["f"][0], data.cols["g"][0]
    assert len(np.intersect1d(a, b)) > 1.1 * len(a) * len(b) / data.n
    again = generate.Data(cfg, 11)
    assert all(np.array_equal(x, y)
               for x, y in zip(data.cols["g"], again.cols["g"]))
    assert not np.array_equal(generate.Data(cfg, 12).cols["g"][0], b)


def test_roaring_body_reads_back_through_the_programs_reader(zipf):
    from pilosa_tpu.storage.bitmap import Bitmap

    cfg, data = zipf
    name = cfg["fields"][0]["name"]
    pos = loader.fragment_positions(data, name, 1)
    body = loader.roaring_body(pos)
    assert int.from_bytes(body[:8], "little") == len(body) - 8
    bm = Bitmap.from_bytes(body[8:])
    assert bm.count() == len(pos)
    got = np.sort(np.asarray(bm.slice(), dtype=np.uint64))
    assert np.array_equal(got, pos)
    want = {(int(p) >> 20, (int(p) & 0xFFFFF) + generate.SHARD_WIDTH)
            for p in pos[:: max(1, len(pos) // 500)]}
    for row, col in want:
        assert col in columns_of(data, name, row)


def test_bitsets_against_python_sets(zipf):
    cfg, data = zipf
    ref = reference.build(data, mix_of("adhoc"))
    f1, g2, g3 = (columns_of(data, "f", 1), columns_of(data, "g", 2),
                  columns_of(data, "g", 3))
    assert ref.answer("Count(Intersect(Row(f=1), Row(g=2)))") == len(f1 & g2)
    assert ref.answer("Count(Union(Row(f=1), Row(g=2)))") == len(f1 | g2)
    assert ref.answer("Count(Xor(Row(f=1), Row(g=2)))") == len(f1 ^ g2)
    assert ref.answer("Count(Difference(Row(f=1), Row(g=2)))") == len(f1 - g2)
    assert ref.answer(
        "Count(Intersect(Row(f=1), Union(Row(g=2), Row(g=3))))") \
        == len(f1 & (g2 | g3))
    _, counts, n, slack = ref.answer("TopN(f, Row(g=0), n=10)")
    assert not any(slack)
    g0 = columns_of(data, "g", 0)
    assert n == 10 and counts == [len(columns_of(data, "f", r) & g0)
                                  for r in range(48)]


def test_writer_rows_are_replayed_exactly(zipf):
    cfg, data = zipf
    ref = reference.build(data, mix_of("adhoc"))
    before = ref.answer("Count(Row(f=33))")
    assert before == len(columns_of(data, "f", 33))
    fresh = next(c for c in range(data.n) if c not in columns_of(data, "f", 33))
    held = int(data.cols["f"][33][0])
    assert ref.answer(f"Set({fresh}, f=33)") is True
    assert ref.answer(f"Set({fresh}, f=33)") is False
    assert ref.answer(f"Set({held}, f=33)") is False
    assert ref.answer("Count(Row(f=33))") == before + 1
    with pytest.raises(ValueError):
        ref.answer("Set(5, f=3)")


def test_agrees_holds_topn_to_pairs():
    want = ("topn", [5, 9, 9, 0, 2], 2, [0] * 5)
    assert reference.agrees([{"id": 1, "count": 9}, {"id": 2, "count": 9}], want)
    assert reference.agrees([{"id": 2, "count": 9}, {"id": 1, "count": 9}], want)
    assert not reference.agrees([{"id": 1, "count": 9}], want)
    assert not reference.agrees([{"id": 1, "count": 9}, {"id": 1, "count": 9}], want)
    assert not reference.agrees([{"id": 1, "count": 9}, {"id": 0, "count": 9}], want)
    assert not reference.agrees(None, want)
    assert reference.agrees([{"id": 1, "count": 9}, {"id": 2, "count": 9},
                             {"id": 0, "count": 5}, {"id": 4, "count": 2}],
                            ("topn", [5, 9, 9, 0, 2], 0, [0] * 5))
    # Row 4 is written during the run, twice: it may count 2, 3 or 4, and
    # so may or may not reach the cut before row 3 does.
    racing = ("topn", [5, 9, 3, 3, 2], 3, [0, 0, 0, 0, 2])
    for third in ({"id": 2, "count": 3}, {"id": 3, "count": 3},
                  {"id": 4, "count": 4}, {"id": 4, "count": 3}):
        assert reference.agrees([{"id": 1, "count": 9}, {"id": 0, "count": 5},
                                 third], racing)
    for third in ({"id": 4, "count": 5}, {"id": 4, "count": 2},
                  {"id": 2, "count": 4}):
        assert not reference.agrees(
            [{"id": 1, "count": 9}, {"id": 0, "count": 5}, third], racing)
    assert not reference.agrees(True, 1) and reference.agrees(7, 7)


def test_traffic_follows_its_mix_file():
    cfg, mix = tiny("tiny-zipf.json"), mix_of("adhoc")
    a = generate.Requests(mix, cfg, 2**31 + 12345, 3)
    b = generate.Requests(mix, cfg, 2**31 + 12345, 3)
    seen = collections.Counter()
    for _ in range(4000):
        k, group = a.next()
        assert (k, group) == b.next()
        seen[k] += 1
        for pql in group:
            reference.parse(pql)
    # Dealt from a deck: after whole passes the shares are the weights'.
    total = sum(t["weight"] for t in mix["templates"])
    assert len(a.cards) == 18 and sum(seen.values()) == 4000
    for k, t in enumerate(mix["templates"]):
        # 4,000 draws are 222 whole passes of the deck and 4 cards more.
        assert abs(seen[k] * total - 4000 * t["weight"]) <= 4 * total, \
            t["name"]
    other = generate.Requests(mix, cfg, 2**31 + 12345, 4)
    assert [other.next() for _ in range(20)] != [b.next() for _ in range(20)]
    (field, (lo, hi)), = mix["writer_rows"].items()
    sets = [p for _ in range(300) for p in a.next()[1] if p.startswith("Set(")]
    assert sets and all(p.endswith(f"{field}={lo + 3})") for p in sets)
    assert lo + mix["clients"] - 1 <= hi


def test_the_sweep_names_every_row_a_template_can_name():
    cfg, mix = tiny("tiny-zipf.json"), mix_of("adhoc")
    clients = mix["clients"]
    sweeps = [generate.Requests(mix, cfg, 7, k).sweep(clients)
              for k in range(clients)]
    named = collections.defaultdict(set)    # template -> its PQL strings
    for mine in sweeps:
        for k, group in mine:
            named[k].add(" ".join(group))
    for k, t in enumerate(mix["templates"]):
        text = " ".join(named[k])
        for name, how in t["draw"].items():
            (kind, arg), = how.items()
            if kind == "uniform":
                row = t["pql"][0].split("{" + name + "}")[0].split("(")[-1]
                for r in range(arg[0], arg[1] + 1):
                    assert f"({row}{r})" in text, (t["name"], name, r)
            elif kind == "choice":
                assert all(str(c) in text for c in arg), t["name"]
            elif kind == "client_row":
                # Every client sweeps its own row, and no other's.
                for c, mine in enumerate(sweeps):
                    sets = [p for j, g in mine if j == k for p in g
                            if p.startswith("Set(")]
                    assert sets and all(f"={arg + c})" in p for p in sets)
    # Dealt round: no client sends the whole of it.
    assert max(map(len, sweeps)) < sum(map(len, sweeps)) / 2
    stream = generate.Fixed(sweeps[0])
    assert [stream.next() for _ in sweeps[0]] == sweeps[0]
    assert stream.next() is None


def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = run.Context(cfg={"shards": 64}, device={"kind": "TPU v5 lite"},
                      peaks=run.read_json(BENCH, "peaks.json"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for m in manifest["per_layer"]:
        assert run.load_layer(m["name"]).read(ctx) is None, m["name"]


def test_readers_on_a_hand_made_context():
    traces = [
        {"spans": [{"name": "parse", "dur_ms": 1.0},
                   {"name": "plan.compile", "dur_ms": 0.5},
                   {"name": "device.dispatch", "dur_ms": 4.0,
                    "children": [{"name": "gather", "dur_ms": 2.0}]}]},
        {"spans": [{"name": "parse", "dur_ms": 3.0},
                   {"name": "sched.wait", "dur_ms": 2.0}]},
    ]
    ctx = run.Context(
        cfg={"shards": 64}, traces=traces, floor_ms=0.4,
        capture_ops_per_s=50.0,
        device={"kind": "TPU v5 lite", "memory_peak_bytes": 2_500_000_000},
        peaks=run.read_json(BENCH, "peaks.json"),
        before={"batcher": {"enqueued": 10, "launches": 10},
                "engine_cache": {"memo_hits": 1, "memo_misses": 1,
                                 "fn_cache_builds": 7}},
        after={"batcher": {"enqueued": 40, "launches": 20},
               "engine_cache": {"memo_hits": 4, "memo_misses": 10,
                                "fn_cache_builds": 7}},
        profile={"busy_s": 1.0, "window_s": 4.0},
        probe={"waves": 9, "waves_inside": 8, "width": 32, "leaves": 2,
               "profile": {"busy_s": 0.02, "window_s": 10.0}})
    read = lambda name: run.load_layer(name).read(ctx)  # noqa: E731
    assert read("client.floor_ms") == 0.4
    assert read("parse_plan.ms") == pytest.approx((1.5 + 3.0) / 2)
    assert read("sched.hold_ms") == 2.0
    assert read("executor.dispatch_ms") == 4.0
    assert read("engine.gather_ms") == 2.0
    assert read("batcher.coalesce_ratio") == 3.0
    assert read("engine.memo_hit_share") == pytest.approx(25.0)
    assert read("engine.fn_builds_in_window") == 0
    # Busy a quarter of the capture, in which 50 answers a second came.
    assert read("device.busy_ms_per_op") == pytest.approx(5.0)
    assert read("device.hbm_peak_gb") == 2.5
    # 8 waves inside the capture (the ninth was cut off) x 64 planes x 64
    # shards x 128 KiB = 4 GiB; at 819 GB/s 5.24 ms.
    assert read("kernel.count_roofline") == pytest.approx(
        100 * (8 * 64 * 64 * 131072 / 819e9) / 0.02)
