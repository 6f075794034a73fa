"""The plain reference: the same PQL on the same drawn data, in numpy.

Imports nothing of the program and takes nothing the program made. One
packed bitset per row over the columns that hold anything at all (the
others can only ever count 0), so a two-leaf Count is one pass over ~1.5 MB
and not 8 MiB.

The harness (`run.judge`) asks `answer` for each client's requests in the
order it sent them: rows named in the mix's `writer_rows` are written by
one client only, so what that client reads back is exact.

Int fields, as upstream's BSI calls read them:
  Range(f op v)         op one of < <= > >= == !=; Range(f >< [a, b]) is
                        a <= value <= b. Only columns that hold a value
                        match: `f != v` is every column with a value other
                        than v (upstream's not-null minus equal), never a
                        column without one.
  Sum(filter?, field=f) {"value": the exact sum, "count": the columns
                        with a value} over the filter's columns
  Min(...), Max(...)    {"value": the least (greatest) value, "count": how
                        many of those columns hold it}
With no column to read, each is {"value": 0, "count": 0}, as the server
answers it.

TopN(f, filter, tanimotoThreshold=t), upstream's similarity search: with
a = |r| for a row r of f, b = |filter| and c = |r & filter|, r passes when
c > 0 and 100 c > t (a + b - c), the whole-number form of upstream's
ceil(100 c / (a + b - c)) > t; its bounds on a need no rule of their own,
since a row outside them fails this test too. The answer is the tuple of
any TopN, with c where a row passes and 0 where it fails, so `agrees`
judges it as it judges the others. A row that clients write during the run
may appear or not: where it does, it counts from 1 to its loaded c plus
the run's Sets on it, so its count is 0 and its slack that much. Refused
(ValueError), so that a mix cannot be judged against another question:
t outside 1..100, a TopN without a filter, a filter that names a
writer-owned row (b would move under the writes), and a field with more
rows than its `cacheSize` (default 50,000), since upstream ranks only the
rows its rank cache holds and this reference ranks every row.
"""

import operator
import re

import numpy as np

_TOKEN = re.compile(
    r"\s*([A-Za-z_][A-Za-z_0-9]*|-?\d+|><|[<>=!]=|[<>(),=\[\]])")
_COMPARE = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
            ">=": operator.ge, "==": operator.eq, "!=": operator.ne}
# Rows a field's rank cache holds where its options do not say (upstream's
# field.go DefaultCacheSize).
DEFAULT_CACHE_SIZE = 50000


def parse(pql):
    """One PQL call as (name, [positional], {keyword})."""
    toks = _TOKEN.findall(pql)
    if "".join(toks) != re.sub(r"\s+", "", pql):
        raise ValueError(f"cannot read PQL: {pql!r}")
    call, rest = _call(toks, 0)
    if rest != len(toks):
        raise ValueError(f"trailing text in PQL: {pql!r}")
    return call


def _int(tok):
    return int(tok) if tok.lstrip("-").isdigit() else tok


def _call(toks, i):
    """One call from toks[i]; a condition `f op v` is the keyword
    f: (op, v), with v a tuple for `><`."""
    name = toks[i]
    if toks[i + 1] != "(":
        raise ValueError(f"expected ( after {name}")
    i += 2
    pos, kw = [], {}
    while toks[i] != ")":
        if toks[i] == ",":
            i += 1
        elif toks[i + 1] == "(":
            sub, i = _call(toks, i)
            pos.append(sub)
        elif toks[i + 1] == "=":
            kw[toks[i]] = _int(toks[i + 2])
            i += 3
        elif toks[i + 1] == "><":
            if toks[i + 2] != "[" or toks[i + 4] != "," or toks[i + 6] != "]":
                raise ValueError(f"expected [a, b] after {toks[i]} ><")
            kw[toks[i]] = ("><", (int(toks[i + 3]), int(toks[i + 5])))
            i += 7
        elif toks[i + 1] in _COMPARE:
            kw[toks[i]] = (toks[i + 1], int(toks[i + 2]))
            i += 3
        else:
            pos.append(_int(toks[i]))
            i += 1
    return (name, pos, kw), i + 1


def _matches(values, op, v):
    """Which of an int field's values a Range condition takes."""
    if op == "><":
        return (values >= v[0]) & (values <= v[1])
    return _COMPARE[op](values, v)


def exact_sum(values):
    """The sum of int64 values as a Python integer, with no overflow."""
    top = max(abs(int(values.min())), abs(int(values.max())))
    if top * len(values) < 1 << 63:
        return int(values.sum(dtype=np.int64))
    return sum(values.tolist())


class Reference:
    """The drawn index, answering parsed calls."""

    def __init__(self, data, writer_rows):
        self.data = data
        present = np.zeros(data.n, dtype=bool)
        for rows in data.cols.values():
            for c in rows:
                present[c] = True
        for c, _ in data.values.values():
            present[c] = True
        # rank[c]: the place of column c among the columns that hold a bit.
        rank = np.cumsum(present, dtype=np.uint32)
        rank -= np.uint32(1)
        self.m = int(rank[-1]) + 1 if data.n else 0
        del present
        self.rows = {(name, r): self._pack(rank[c])
                     for name, rows in data.cols.items()
                     for r, c in enumerate(rows)}
        # An int field as (places of its columns, their values).
        self.ints = {name: (rank[c].astype(np.intp), v)
                     for name, (c, v) in data.values.items()}
        del rank
        self.memo = {}
        self.ranges = {}        # (field, op, v) -> packed bitset
        self.matrices = {}      # field -> its packed rows, stacked
        # (field, row) -> [count, set of columns added], for rows that one
        # client alone writes.
        self.written = {}
        # (field, row) -> how many Sets the whole run sends to it: by how
        # much a TopN that races them may count that row above its loaded
        # state. `expect_sets` fills it before the first answer.
        self.sets_on = {}
        for name, (lo, hi) in writer_rows.items():
            for r in range(lo, hi + 1):
                self.written[name, r] = [self.row_count(name, r), set()]

    def _pack(self, places):
        mask = np.zeros((self.m + 63) // 64 * 64, dtype=bool)
        mask[places] = True
        return np.packbits(mask, bitorder="little").view(np.uint64)

    def expect_sets(self, pqls):
        """Count the run's Sets by the row they write."""
        for pql in pqls:
            if pql.startswith("Set("):
                _, _, kw = parse(pql)
                key = next(iter(kw.items()))
                self.sets_on[key] = self.sets_on.get(key, 0) + 1

    def answer(self, pql):
        """The expected result of one request, as the server's JSON gives
        it; TopN as ("topn", counts of every row, n, slack of every row)."""
        name, pos, kw = call = parse(pql)
        if name == "Set":
            return self._set(pos[0], *next(iter(kw.items())))
        if name == "Count" and pos[0][0] == "Row":
            key = next(iter(pos[0][2].items()))
            if key in self.written:
                return self.written[key][0]
        if pql not in self.memo:
            self.memo[pql] = self._eval_top(call)
        return self.memo[pql]

    def _set(self, col, field, row):
        state = self.written.get((field, row))
        if state is None:
            raise ValueError(f"Set on {field}={row}, which the mix does not "
                             "list under writer_rows")
        if col in state[1] or self.has_bit(field, row, col):
            return False
        state[1].add(col)
        state[0] += 1
        return True

    def _eval_top(self, call):
        name, pos, kw = call
        if name == "Count":
            return self.count(self.bitmap(pos[0]))
        if name == "TopN" and "tanimotoThreshold" in kw:
            return self._tanimoto_top(pos, kw)
        if name == "TopN":
            filt = self.bitmap(pos[1]) if len(pos) > 1 else None
            counts = self.row_counts(pos[0], filt)
            slack = [self.sets_on.get((pos[0], r), 0)
                     for r in range(len(counts))]
            return ("topn", counts, kw.get("n", 0), slack)
        if name in ("Sum", "Min", "Max"):
            return self.val_count(name, kw["field"],
                                  self.bitmap(pos[0]) if pos else None)
        raise ValueError(f"the reference does not answer {name}")

    def _tanimoto_top(self, pos, kw):
        field, t = pos[0], kw["tanimotoThreshold"]
        if not 1 <= t <= 100:
            raise ValueError(f"tanimotoThreshold={t} is not from 1 to 100")
        if len(pos) < 2:
            raise ValueError("the reference answers tanimotoThreshold only "
                             "under a filter")
        named = [key for key in self._rows_named(pos[1])
                 if key in self.written]
        if named:
            raise ValueError(f"a tanimoto filter names the writer-owned row "
                             f"{named[0]}: draw the query from the read rows")
        n_rows = len(self.data.cols[field])
        cache = self.cache_size(field)
        if n_rows > cache:
            raise ValueError(f"field {field!r} holds {n_rows} rows and ranks "
                             f"{cache} (cacheSize): upstream would rank "
                             "fewer rows than the reference does")
        filt = self.bitmap(pos[1])
        rows = self.matrix(field)
        a = np.bitwise_count(rows).sum(axis=1, dtype=np.int64)
        c = np.bitwise_count(rows & filt).sum(axis=1, dtype=np.int64)
        keep = self.tanimoto_passes(c, a, self.count(filt), t)
        counts = np.where(keep, c, 0)
        slack = np.zeros(n_rows, dtype=np.int64)
        for f, r in self.written:
            if f == field:
                counts[r] = 0
                slack[r] = c[r] + self.sets_on.get((f, r), 0)
        return ("topn", counts.tolist(), kw.get("n", 0), slack.tolist())

    @staticmethod
    def tanimoto_passes(c, a, b, t):
        """Which rows reach the threshold: c > 0 and 100 c > t (a + b - c),
        element by element, in whole numbers."""
        return (c > 0) & (100 * c > t * (a + b - c))

    def _rows_named(self, call):
        name, pos, kw = call
        if name == "Row":
            yield next(iter(kw.items()))
        for p in pos:
            if isinstance(p, tuple):
                yield from self._rows_named(p)

    def cache_size(self, field):
        spec = next(f for f in self.data.cfg["fields"] if f["name"] == field)
        return spec.get("options", {}).get("cacheSize", DEFAULT_CACHE_SIZE)

    def matrix(self, field):
        """A set field's packed rows as one (rows, words) array."""
        if field not in self.matrices:
            self.matrices[field] = np.stack(
                [self.rows[field, r]
                 for r in range(len(self.data.cols[field]))])
        return self.matrices[field]

    def val_count(self, name, field, filt):
        places, values = self.ints[field]
        if filt is not None:
            held = np.unpackbits(filt.view(np.uint8), bitorder="little")
            values = values[held[places] == 1]
        if not len(values):
            return {"value": 0, "count": 0}
        if name == "Sum":
            return {"value": exact_sum(values), "count": len(values)}
        v = values.min() if name == "Min" else values.max()
        return {"value": int(v), "count": int(np.count_nonzero(values == v))}

    def bitmap(self, call):
        name, pos, kw = call
        if name == "Row":
            (field, row), = kw.items()
            return self.rows[field, row]
        if name == "Range":
            (field, (op, v)), = kw.items()
            key = field, op, v
            if key not in self.ranges:
                places, values = self.ints[field]
                self.ranges[key] = self._pack(places[_matches(values, op, v)])
            return self.ranges[key]
        kids = [self.bitmap(c) for c in pos]
        out = kids[0]
        for k in kids[1:]:
            if name == "Intersect":
                out = out & k
            elif name == "Union":
                out = out | k
            elif name == "Xor":
                out = out ^ k
            elif name == "Difference":
                out = out & ~k
            else:
                raise ValueError(f"the reference does not know {name}")
        return out

    def row_count(self, field, row):
        return len(self.data.cols[field][row])

    def has_bit(self, field, row, col):
        c = self.data.cols[field][row]
        i = int(np.searchsorted(c, col))
        return i < len(c) and int(c[i]) == col

    def count(self, bits):
        return int(np.bitwise_count(bits).sum())

    def row_counts(self, field, filt):
        n = len(self.data.cols[field])
        if filt is None:
            return [self.row_count(field, r) for r in range(n)]
        return [self.count(self.rows[field, r] & filt) for r in range(n)]


def build(data, mix):
    return Reference(data, mix.get("writer_rows", {}))


def agrees(got, want):
    """Whether the server's result equals the reference's. TopN compares
    (id, count) pairs, so a tie at the cut cannot fail it: every id's count
    is its own, in falling order, and no row left out holds more than the
    last one returned. A row that clients write during the run may count up
    to its Sets above its loaded state; every other row is held exactly."""
    if isinstance(want, tuple) and want[0] == "topn":
        _, counts, n, slack = want
        try:
            ids = [p["id"] for p in got]
            cs = [p["count"] for p in got]
            floor = cs[-1] if n and len(got) == n else 0
            return (len(set(ids)) == len(ids)
                    and cs == sorted(cs, reverse=True)
                    and all(c > 0 and counts[i] <= c <= counts[i] + slack[i]
                            for i, c in zip(ids, cs))
                    and all(c <= floor for r, c in enumerate(counts)
                            if r not in ids))
        except (TypeError, KeyError, IndexError):
            return False
    return type(got) is type(want) and got == want
