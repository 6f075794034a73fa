"""Data and traffic from `--seed`, for any configuration and mix file.

numpy only. A configuration (`configs/<name>.json`) lists fields and how
each is drawn; a mix (`traffic/<name>.json`) lists weighted PQL templates
and how each placeholder is drawn. Nothing here knows a configuration or a
mix by name: a later PR adds a cell by adding data files.

Field draws (a field's `draw` names one; its other keys are the draw's):
  zipf_bits   upstream pilosa/tools' `bench zipf`: `bits` times one bit at
              (row, column), each id drawn by a Zipf-Mandelbrot law,
              P(k) ~ (v + k)^-exponent over k = 0..range-1 with v such
              that P(range-1) / P(0) = ratio, and the ids then permuted.
              Rows keep their order (row 0 is the likeliest), so that a
              mix can name rows by rank; columns go through one seeded
              affine permutation, the same for every field.
              Takes `rows`, `bits`, `row_exponent`, `row_ratio`,
              `column_exponent`, `column_ratio`.
  <name>      any other draw is the file draws/<name>.py, whose
              `draw(data, field, rng)` fills `data.cols[field["name"]]` (a
              set field) or `data.values[field["name"]]` (an int field).
              Fields are drawn in the file's order, so a draw may read the
              fields drawn before it. The files shipped:
    int_uniform          `columns`, `min`, `max`: columns 0..columns-1 each
                         hold one value, uniform over [min, max]
    one_row_per_column   `columns`, `rows`, optionally `row_exponent` and
                         `row_ratio`: columns 0..columns-1 each lie in one
                         of `rows` rows, uniform or by the zipf law above
    fingerprint_families `rows`, `columns`, `family_size`, `scaffold_bits`,
                         `keep`, `own_bits`, `column_exponent`,
                         `column_ratio`, optionally `writer_rows`: each row
                         a molecule's fingerprint over columns
                         0..columns-1, drawn in families of analogues
                         around a scaffold, column popularity by the zipf
                         law above; the writer rows last

A field may also carry `options`, posted as they are when the field is
created (`{"type": "int", "min": 0, "max": 10}` makes an int field).

Placeholder draws:
  {"choice": [...]}     uniform over the list
  {"uniform": [lo, hi]} whole number, both ends included
  {"column": true}      uniform over the index's columns
  {"client_row": base}  base + the client's number (a row only it writes)
"""

import functools
import importlib.util
import math
import os
import random

import numpy as np

SHARD_WIDTH = 1 << 20
DRAWS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "draws")


def zipf_offset(n, exponent, ratio):
    """The v of P(k) ~ (v + k)^-exponent for which the last of n ids is
    `ratio` times as likely as the first."""
    return (n - 1) / (ratio ** (-1.0 / exponent) - 1.0)


def zipf_ranks(rng, count, n, exponent, ratio):
    """`count` ranks in 0..n-1 by that law, through the inverse of its
    continuous distribution function, floored."""
    v = zipf_offset(n, exponent, ratio)
    e = 1.0 - exponent
    lo, hi = v ** e, (v + n) ** e
    x = (lo + rng.random(count) * (hi - lo)) ** (1.0 / e) - v
    return np.minimum(x.astype(np.int64), n - 1)


def load_draw(name):
    """The `draw` function of draws/<name>.py; ValueError where there is
    no such file."""
    path = os.path.join(DRAWS, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown draw {name!r}")
    spec = importlib.util.spec_from_file_location("draw_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.draw


class Data:
    """The index as drawn from the seed: cols[field][row] holds the row's
    sorted, distinct uint32 columns; values[field] = (columns, values) of
    an int field, sorted distinct uint32 columns and their int64 values."""

    def __init__(self, cfg, seed):
        self.cfg = cfg
        self.shards = cfg["shards"]
        self.n = self.shards * SHARD_WIDTH
        self.cols = {}
        self.values = {}
        # rank -> column, the same for every field (a column that is
        # likely in one field is likely in all): a * rank + b mod n, a
        # coprime with n.
        rng = np.random.default_rng([seed, len(cfg["fields"])])
        a = int(rng.integers(1, self.n)) | 1
        while math.gcd(a, self.n) != 1:
            a += 2
        self.permutation = a, int(rng.integers(0, self.n))
        for k, field in enumerate(cfg["fields"]):
            rng = np.random.default_rng([seed, k])
            draw = getattr(self, "_draw_" + field["draw"], None)
            if draw is None:
                load_draw(field["draw"])(self, field, rng)
            else:
                draw(field, rng)

    def _draw_zipf_bits(self, field, rng):
        bits, rows = field["bits"], field["rows"]
        row = zipf_ranks(rng, bits, rows, field["row_exponent"],
                         field["row_ratio"])
        rank = zipf_ranks(rng, bits, self.n, field["column_exponent"],
                          field["column_ratio"])
        a, b = self.permutation
        col = (rank * a + b) % self.n
        order = np.argsort(row, kind="stable")
        col = col[order].astype(np.uint32)
        ends = np.searchsorted(row[order], np.arange(rows + 1))
        self.cols[field["name"]] = [np.unique(col[ends[r]:ends[r + 1]])
                                    for r in range(rows)]

    def bits(self):
        """Bits held, for the record."""
        return sum(len(c) for rows in self.cols.values() for c in rows)


class Requests:
    """One client's endless stream of request groups, from (seed, client).

    A group is the list of PQL strings of one template draw, sent one after
    the other by that client: [query], or [Set, Count] for a write pair.

    Templates are dealt from a deck that holds each in proportion to its
    weight (weights 45, 25, 10, 10 make a deck of 18), shuffled anew
    for every pass: every seed sends the same mix of work in another
    order. Drawn freely, the share of a 10% template swings by a twentieth
    of itself from seed to seed in a window of 4,000 requests, and with it
    the tail."""

    def __init__(self, mix, cfg, seed, client):
        self.rng = random.Random(f"{seed}/{client}")
        self.client = client
        self.n = cfg["shards"] * SHARD_WIDTH
        self.templates = mix["templates"]
        weights = [t["weight"] for t in self.templates]
        unit = functools.reduce(math.gcd, weights)
        self.cards = [k for k, w in enumerate(weights) for _ in range(w // unit)]
        self.deck = []

    def _draw(self, how):
        (kind, arg), = how.items()
        if kind == "choice":
            return self.rng.choice(arg)
        if kind == "uniform":
            return self.rng.randint(arg[0], arg[1])
        if kind == "column":
            return self.rng.randrange(self.n)
        if kind == "client_row":
            return arg + self.client
        raise ValueError(f"unknown placeholder draw {kind!r}")

    def next(self):
        """(template number, [pql, ...])."""
        if not self.deck:
            self.deck = self.cards[:]
            self.rng.shuffle(self.deck)
        k = self.deck.pop()
        t = self.templates[k]
        values = {name: self._draw(how) for name, how in t["draw"].items()}
        return k, [p.format(**values) for p in t["pql"]]

    def _domain(self, how):
        """Every value of a placeholder that names rows or constants; None
        where it draws freely."""
        (kind, arg), = how.items()
        if kind == "choice":
            return list(arg)
        if kind == "uniform":
            return list(range(arg[0], arg[1] + 1))
        return None

    def sweep(self, clients):
        """This client's share of the pass that warm-up sends first, as
        (template number, [pql, ...]) groups: every template until each
        value of each of its placeholders was named once, so that a row the
        mix names once in a thousand requests is not first touched inside
        the window. A template with a `client_row` is swept by every client
        for its own row; the others are dealt round among the clients."""
        out = []
        for k, t in enumerate(self.templates):
            finite = {name: d for name, how in t["draw"].items()
                      if (d := self._domain(how))}
            own = any("client_row" in how for how in t["draw"].values())
            longest = max((len(d) for d in finite.values()), default=1)
            for i in range(longest):
                if not own and i % clients != self.client:
                    continue
                values = {name: d[i % len(d)] for name, d in finite.items()}
                for name, how in t["draw"].items():
                    if name not in values:
                        values[name] = self._draw(how)
                out.append((k, [p.format(**values) for p in t["pql"]]))
        return out


class Fixed:
    """A stream that ends: the groups of a list, one after the other."""

    def __init__(self, groups):
        self.groups = list(reversed(groups))

    def next(self):
        return self.groups.pop() if self.groups else None
