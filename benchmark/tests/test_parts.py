"""The yardstick's parts, each against something independent of it: the
loader's roaring file against the program's own reader, the generator's
law against its formula, the reference against plain Python sets, the traffic generator against its
mix file, the readers against a hand-made context. And the parts that
integer fields brought: the draw files' laws, a draw found by name, field
options in the schema, a BSI fragment read back to its values, and the
reference's Range, Sum, Min and Max against plain Python. And those of the
similarity search: the fingerprint draw's families, and the reference's
TopN under `tanimotoThreshold` against plain Python sets."""

import collections
import importlib.util
import json
import math
import os

import numpy as np
import pytest

from conftest import BENCH, HERE, probe_requests

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
    cfg = tiny("configs/zipf-64.json")
    return cfg, generate.Data(cfg, 11)


@pytest.fixture(scope="module")
def ints():
    cfg = tiny("tiny-int.json")
    return cfg, generate.Data(cfg, 2**31 + 42)


@pytest.fixture(scope="module")
def chem():
    cfg = tiny("tiny-chem.json")
    return cfg, generate.Data(cfg, 2**31 + 46)


def columns_of(data, field, row):
    return set(data.cols[field][row].tolist())


def field_of(cfg, name):
    return next(f for f in cfg["fields"] if f["name"] == name)


def chi_square_999(df):
    """The 99.9th percentile of chi-square with df degrees of freedom, by
    Wilson and Hilferty's cube-root approximation."""
    z, a = 3.0902, 2.0 / (9.0 * df)
    return df * (1.0 - a + z * a ** 0.5) ** 3


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
    cfg, mix = tiny("configs/zipf-64.json"), mix_of("adhoc")
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
    cfg, mix = tiny("configs/zipf-64.json"), mix_of("adhoc")
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


def test_a_probe_that_names_its_wave_asks_no_question_twice():
    cfg = tiny("configs/zipf-64.json")
    mix = mix_of("adhoc")

    def waves(pql):
        mix["probe"] = {"pql": pql, "width": 4, "waves": 8, "leaves": 2}
        sent = probe_requests(cfg, mix, 2**31 + 43)
        assert all(p.startswith("Set(") for p in sent[::5])
        return [sent[k + 1:k + 5] for k in range(0, len(sent), 5)]

    named = waves("Count(Intersect(Row(f={i}), Row(g={w})))")
    asked = [q for wave in named for q in wave]
    assert len(asked) == 32 and len(set(asked)) == 32
    assert named[3][2] == "Count(Intersect(Row(f=2), Row(g=3)))"
    # Without {w} (and {j}) every wave asks the same questions.
    same = waves("Count(Intersect(Row(f={i}), Row(g=1)))")
    assert all(wave == same[0] for wave in same)


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


@pytest.mark.parametrize("name", ["discount", "quantity"])
def test_int_uniform_holds_one_value_a_column_uniform_over_its_range(
        ints, name):
    cfg, data = ints
    f = field_of(cfg, name)
    cols, values = data.values[name]
    assert cols.dtype == np.uint32 and values.dtype == np.int64
    # The first `columns` columns, each once: a fact table's records.
    assert np.array_equal(cols, np.arange(f["columns"], dtype=np.uint32))
    assert values.min() == f["min"] and values.max() == f["max"]
    seen = np.bincount(values - f["min"])
    k = f["max"] - f["min"] + 1
    assert len(seen) == k
    want = len(values) / k
    assert ((seen - want) ** 2 / want).sum() < chi_square_999(k - 1)
    again = generate.Data(cfg, 2**31 + 42)
    assert np.array_equal(again.values[name][1], values)
    assert not np.array_equal(generate.Data(cfg, 7).values[name][1], values)


def test_one_row_per_column_puts_each_column_in_one_row(ints):
    cfg, data = ints
    f = field_of(cfg, "year")
    rows = data.cols["year"]
    assert len(rows) == f["rows"] == 7
    every = np.concatenate(rows)
    assert np.array_equal(np.sort(every),
                          np.arange(f["columns"], dtype=np.uint32))
    assert all(np.all(np.diff(c.astype(np.int64)) > 0) for c in rows)
    sizes = np.array([len(c) for c in rows])
    want = f["columns"] / f["rows"]
    assert ((sizes - want) ** 2 / want).sum() < chi_square_999(f["rows"] - 1)
    # By the zipf law where the field gives one, floored as `zipf_ranks`
    # floors it: row r has the mass of [r, r + 1).
    skewed = dict(f, row_exponent=1.01, row_ratio=0.25)
    data2 = generate.Data(dict(cfg, fields=[skewed]), 3)
    sizes = np.array([len(c) for c in data2.cols["year"]])
    v = generate.zipf_offset(f["rows"], 1.01, 0.25)
    edge = np.array([(v + r) ** -0.01 for r in range(f["rows"] + 1)])
    want = f["columns"] * -np.diff(edge) / (edge[0] - edge[-1])
    assert ((sizes - want) ** 2 / want).sum() < chi_square_999(f["rows"] - 1)
    assert sizes.sum() == f["columns"] and sizes[0] > 1.2 * sizes[-1]


def test_a_draw_is_found_by_name_and_an_unknown_one_refused(
        tmp_path, monkeypatch):
    assert generate.load_draw("int_uniform").__module__ == "draw_int_uniform"
    with pytest.raises(ValueError, match="unknown draw 'no_such_draw'"):
        generate.Data({"shards": 1, "fields": [
            {"name": "x", "draw": "no_such_draw"}]}, 1)
    # A draw may read the fields drawn before it, in the file's order.
    (tmp_path / "month_of.py").write_text(
        "def draw(data, field, rng):\n"
        "    src = data.cols[field['from']]\n"
        "    data.cols[field['name']] = [c for c in src for _ in range(12)]\n")
    monkeypatch.setattr(generate, "DRAWS", str(tmp_path))
    cfg = {"shards": 1, "fields": [
        {"name": "year", "draw": "zipf_bits", "rows": 3, "bits": 500,
         "row_exponent": 1.01, "row_ratio": 0.25, "column_exponent": 1.01,
         "column_ratio": 0.25},
        {"name": "month", "draw": "month_of", "from": "year"}]}
    data = generate.Data(cfg, 9)
    assert len(data.cols["month"]) == 36
    assert data.cols["month"][12] is data.cols["year"][1]


class Recorder:
    def __init__(self):
        self.sent = []

    def request(self, method, path, body=None):
        self.sent.append((method, path, body))


def test_field_options_are_posted_and_a_field_without_them_posts_nothing(
        ints):
    cfg, data = ints
    srv = Recorder()
    loader.create_schema(srv, cfg)
    bodies = {path: body for _, path, body in srv.sent}
    assert bodies["/index/tinyint"] == "{}"
    assert bodies["/index/tinyint/field/f"] == "{}"
    assert bodies["/index/tinyint/field/year"] == "{}"
    assert json.loads(bodies["/index/tinyint/field/quantity"]) == {
        "options": {"type": "int", "min": 1, "max": 50}}
    srv = Recorder()
    loader.load(srv, cfg, data)
    views = collections.Counter(
        path.split("&view=")[1].split("&")[0] for _, path, _ in srv.sent)
    assert views == {"standard": 4, "bsig_discount": 2, "bsig_quantity": 2}


@pytest.mark.parametrize("lo, hi, depth", [(0, 10, 4), (1, 50, 6), (0, 0, 0),
                                           (0, 1, 1), (-8, 7, 4), (0, 16, 5)])
def test_bsi_depth_is_upstreams(lo, hi, depth):
    assert loader.bsi_depth({"min": lo, "max": hi}) == depth
    assert hi - lo < 1 << depth and (depth == 0 or hi - lo >= 1 << depth - 1)


@pytest.mark.parametrize("name", ["discount", "quantity"])
def test_bsi_fragment_reads_back_to_the_drawn_values(ints, name):
    from pilosa_tpu.storage.bitmap import Bitmap

    cfg, data = ints
    f = field_of(cfg, name)
    depth = loader.bsi_depth(f["options"])
    cols, values = data.values[name]
    for shard in range(cfg["shards"]):
        body = loader.roaring_body(loader.bsi_positions(data, f, shard))
        pos = np.asarray(Bitmap.from_bytes(body[8:]).slice(), dtype=np.uint64)
        row, col = pos >> np.uint64(20), pos & np.uint64(0xFFFFF)
        planes = np.zeros((depth + 1, generate.SHARD_WIDTH), dtype=np.int64)
        planes[row.astype(np.int64), col.astype(np.int64)] = 1
        mine = (cols >= shard * generate.SHARD_WIDTH) \
            & (cols < (shard + 1) * generate.SHARD_WIDTH)
        here = (cols[mine] - shard * generate.SHARD_WIDTH).astype(np.int64)
        assert np.array_equal(np.flatnonzero(planes[depth]), here)
        got = (planes[:depth] << np.arange(depth)[:, None]).sum(axis=0)
        assert np.array_equal(got[here] + f["options"]["min"], values[mine])
    with pytest.raises(ValueError, match="outside"):
        loader.bsi_positions(data, dict(f, options=dict(f["options"],
                                                        max=5)), 0)


def test_range_sum_min_max_against_plain_python(ints):
    cfg, data = ints
    ref = reference.build(data, {})
    d = dict(zip(*(a.tolist() for a in data.values["discount"])))
    q = dict(zip(*(a.tolist() for a in data.values["quantity"])))
    year2 = columns_of(data, "year", 2)
    f1 = columns_of(data, "f", 1)
    for op, test in (("<", lambda v, c: v < c), ("<=", lambda v, c: v <= c),
                     (">", lambda v, c: v > c), (">=", lambda v, c: v >= c),
                     ("==", lambda v, c: v == c), ("!=", lambda v, c: v != c)):
        for c in (-1, 0, 4, 10, 11):
            want = sum(test(v, c) for v in d.values())
            assert ref.answer(f"Count(Range(discount {op} {c}))") == want
    # A column with no value never matches, `!=` included: the rows of f
    # reach past the last record.
    assert max(f1) >= len(q)
    assert ref.answer("Count(Intersect(Row(f=1), Range(quantity != 7)))") \
        == sum(1 for c in f1 if c in q and q[c] != 7)
    assert ref.answer("Count(Range(quantity >< [10, 12]))") \
        == sum(10 <= v <= 12 for v in q.values())
    sel = [c for c in year2 if 1 <= d[c] <= 3 and q[c] < 25]
    pql = ("{}(Intersect(Row(year=2), Range(discount >< [1, 3]), "
           "Range(quantity < 25)), field=quantity)")
    assert ref.answer(pql.format("Sum")) == {
        "value": sum(q[c] for c in sel), "count": len(sel)}
    low = min(q[c] for c in sel)
    assert ref.answer(pql.format("Min")) == {
        "value": low, "count": sum(q[c] == low for c in sel)}
    high = max(q[c] for c in sel)
    assert ref.answer(pql.format("Max")) == {
        "value": high, "count": sum(q[c] == high for c in sel)}
    assert ref.answer("Sum(field=discount)") == {
        "value": sum(d.values()), "count": len(d)}
    for call in ("Sum", "Min", "Max"):
        assert ref.answer(f"{call}(Range(quantity > 50), field=quantity)") \
            == {"value": 0, "count": 0}
    big = np.array([2**62, 2**62, -5], dtype=np.int64)
    assert reference.exact_sum(big) == 2**63 - 5
    with pytest.raises(ValueError):
        reference.parse("Count(Range(quantity >< 3))")


def fingerprint_families():
    spec = importlib.util.spec_from_file_location(
        "fingerprint_families",
        os.path.join(generate.DRAWS, "fingerprint_families.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tanimoto_matrix(rows, columns):
    held = np.zeros((len(rows), columns), dtype=np.float32)
    for i, c in enumerate(rows):
        held[i, c] = 1.0
    inter = held @ held.T
    size = held.sum(axis=1)
    return inter / (size[:, None] + size[None, :] - inter)


def test_fingerprints_are_drawn_from_the_seed_over_their_columns(chem):
    cfg, data = chem
    f = field_of(cfg, "m")
    rows = data.cols["m"]
    assert len(rows) == f["rows"]
    assert all(len(c) and c.dtype == np.uint32 and c[-1] < f["columns"]
               and np.all(np.diff(c.astype(np.int64)) > 0) for c in rows)
    again = generate.Data(cfg, 2**31 + 46).cols["m"]
    assert all(np.array_equal(a, b) for a, b in zip(rows, again))
    other = generate.Data(cfg, 2**31 + 47).cols["m"]
    assert not all(np.array_equal(a, b) for a, b in zip(rows, other))
    sizes = [len(c) for c in rows]
    assert abs(np.median(sizes) - f["scaffold_bits"]) \
        <= 0.15 * f["scaffold_bits"]
    # A few substructure bits lie in most molecules.
    held = np.bincount(np.concatenate(rows), minlength=f["columns"])
    assert np.sort(held)[-3] > 0.5 * len(rows)


def test_fingerprints_come_in_families_of_analogues(chem):
    cfg, data = chem
    f = field_of(cfg, "m")
    rows, family = fingerprint_families().families(
        f, np.random.default_rng([2**31 + 46, 0]))
    assert all(np.array_equal(a, b) for a, b in zip(rows, data.cols["m"]))
    tan = tanimoto_matrix(rows, f["columns"])
    same = family[:, None] == family[None, :]
    np.fill_diagonal(same, False)
    across = family[:, None] != family[None, :]
    assert np.median(tan[same]) >= 0.6
    assert np.median(tan[across]) <= 0.3
    read = f["rows"] - f["writer_rows"]
    sizes = np.bincount(family[:read])
    lo, hi = f["family_size"]
    assert np.all(np.diff(family[:read]) >= 0)      # families lie in blocks
    assert sizes[:-1].min() >= lo and sizes.max() <= hi
    # What a search finds at each published threshold, the query itself
    # left out: analogues at the low end, fewer at the high end.
    found = {t: np.median((tan[:read, :read] > t / 100).sum(axis=1) - 1)
             for t in (50, 90)}
    assert found[50] >= 10 and found[50] > found[90]


def test_the_writer_rows_come_last_and_are_analogues(chem):
    cfg, data = chem
    f = field_of(cfg, "m")
    mix = tiny("tiny-tanimoto.json")
    lo, hi = mix["writer_rows"]["m"]
    read = f["rows"] - f["writer_rows"]
    # As tiny.py's twin rule asks: the clients' rows end the field.
    assert (lo, hi) == (read, f["rows"] - 1)
    assert hi - lo + 1 == mix["clients"] == f["writer_rows"]
    rows, family = fingerprint_families().families(
        f, np.random.default_rng([2**31 + 46, 0]))
    assert set(family[read:]) <= set(family[:read])
    tan = tanimoto_matrix(rows, f["columns"])
    kin = family[read:, None] == family[None, :read]
    assert np.median(np.where(kin, tan[read:, :read], 0).max(axis=1)) >= 0.6


def test_tanimoto_topn_against_plain_python(chem):
    cfg, data = chem
    mix = tiny("tiny-tanimoto.json")
    ref = reference.build(data, mix)
    writers = range(mix["writer_rows"]["m"][0], mix["writer_rows"]["m"][1] + 1)
    ref.expect_sets([f"Set(7, m={writers[0]})"] * 3)
    rows = [columns_of(data, "m", r) for r in range(len(data.cols["m"]))]
    for q in (0, 37, 1999):
        for t in (50, 75, 90):
            _, counts, n, slack = ref.answer(
                f"TopN(m, Row(m={q}), n=5, tanimotoThreshold={t})")
            assert n == 5
            for r, cols in enumerate(rows):
                c = len(cols & rows[q])
                coeff = math.ceil(c * 100 / (len(cols) + len(rows[q]) - c))
                if r in writers:
                    extra = 3 if r == writers[0] else 0
                    assert (counts[r], slack[r]) == (0, c + extra)
                else:
                    assert counts[r] == (c if c and coeff > t else 0)
                    assert slack[r] == 0
            assert counts[q] == len(rows[q])
    # The plain TopN is answered as it was, writer rows at their loaded
    # count with their Sets as slack.
    _, counts, _, slack = ref.answer(f"TopN(m, Row(m={q}), n=5)")
    assert counts == [len(c & rows[q]) for c in rows]
    assert slack[writers[0]] == 3 and sum(slack) == 3


@pytest.mark.parametrize("pql, match", [
    ("TopN(m, Row(m=1), tanimotoThreshold=0)", "from 1 to 100"),
    ("TopN(m, Row(m=1), tanimotoThreshold=101)", "from 1 to 100"),
    ("TopN(m, tanimotoThreshold=50)", "under a filter"),
    ("TopN(m, Row(m=2003), tanimotoThreshold=50)", "writer-owned"),
    ("TopN(m, Intersect(Row(m=1), Row(m=2015)), tanimotoThreshold=50)",
     "writer-owned"),
])
def test_tanimoto_questions_the_reference_refuses(chem, pql, match):
    _, data = chem
    ref = reference.build(data, tiny("tiny-tanimoto.json"))
    with pytest.raises(ValueError, match=match):
        ref.answer(pql)


def test_a_field_ranked_past_its_cache_size_is_refused(chem):
    cfg, _ = chem
    small = json.loads(json.dumps(cfg))
    small["fields"][0]["options"]["cacheSize"] = 2015
    ref = reference.build(generate.Data(small, 2**31 + 46), {})
    with pytest.raises(ValueError, match="'m' holds 2016 rows and ranks 2015"):
        ref.answer("TopN(m, Row(m=1), tanimotoThreshold=50)")
    del small["fields"][0]["options"]["cacheSize"]
    assert reference.build(generate.Data(small, 2**31 + 46), {}).cache_size(
        "m") == reference.DEFAULT_CACHE_SIZE == 50000
