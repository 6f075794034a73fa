"""Draw `fingerprint_families`, for a set field of molecules: each row is
one molecule's fingerprint, its bits over the first `columns` columns of
shard 0 (a Morgan fingerprint folded to 4,096 bits), as upstream's
"Chemical similarity search" keeps ChEMBL's molecules. Molecules come in
families of analogues, the series a medicinal-chemistry programme makes
around one scaffold, so that a query molecule has neighbours to find.

No ChEMBL data is read: every number below is an assumption, and the
file of the configuration says so too.

  rows           molecules, the field's rows, writer-owned ones included
  columns        fingerprint bits (4,096)
  family_size    [lo, hi]: a family's size, uniform over both ends
  scaffold_bits  a scaffold's median number of bits; scaffolds spread
                 about it by a log-normal law of sigma 0.3 (a guess: 22 to
                 73 bits at two sigmas at 40)
  keep           the chance that a member keeps a scaffold bit, at the
                 member's mean distance from its scaffold
  own_bits       the bits a member adds of its own, at that same distance
  column_exponent, column_ratio
                 how popular a column is: the zipf law of
                 `generate.zipf_ranks` over the columns, ranks then put
                 through a seeded permutation. A steep law (a small ratio)
                 makes a few substructure bits lie in most molecules.
  writer_rows    optional, default 0: the last rows of the field, which
                 the mix's clients write. Each is one more analogue of a
                 family drawn uniformly among the others: a molecule
                 registered into a series that is still growing.

A member's distance from its scaffold is u, drawn from an exponential law
of mean 1 (a guess): it loses each scaffold bit with chance (1 - keep) u
and adds a Poisson number of own bits of mean own_bits u, drawn by the
column law. Close analogues (small u) and far ones stand in one family.
With keep (1 - keep) * scaffold_bits = own_bits a member holds about as
many bits as its scaffold, so the median molecule holds about
scaffold_bits. The values in the configurations (40 bits, keep 0.9, 4 own
bits, families of 8 to 64) are chosen to give what a search needs: at
thresholds of 50 to 90 a query finds from one to a few dozen analogues,
and not only itself; pairs within a family have a median Tanimoto
coefficient over 0.6, pairs across families under 0.3.
"""

import numpy as np

from generate import zipf_ranks

SCAFFOLD_SIGMA = 0.3


def distinct_ranks(rng, field, count):
    """`count` distinct column ranks by the column law, in the order drawn."""
    out = np.empty(0, dtype=np.int64)
    while len(out) < count:
        more = zipf_ranks(rng, 3 * count, field["columns"],
                          field["column_exponent"], field["column_ratio"])
        both = np.concatenate([out, more])
        _, first = np.unique(both, return_index=True)
        out = both[np.sort(first)]
    return out[:count]


def analogue(rng, field, scaffold):
    """One member's ranks, at a distance drawn for it."""
    u = rng.exponential()
    kept = scaffold[rng.random(len(scaffold)) >= (1.0 - field["keep"]) * u]
    own = zipf_ranks(rng, rng.poisson(field["own_bits"] * u),
                     field["columns"], field["column_exponent"],
                     field["column_ratio"])
    mine = np.union1d(kept, own)
    return mine if len(mine) else scaffold[:1]


def families(field, rng):
    """(rows, family): each row's sorted uint32 columns, and the number of
    the family each row belongs to."""
    n, columns = field["rows"], field["columns"]
    writers = field.get("writer_rows", 0)
    lo, hi = field["family_size"]
    perm = rng.permutation(columns).astype(np.uint32)
    scaffolds, ranks, family = [], [], []
    while len(ranks) < n - writers:
        size = min(int(rng.integers(lo, hi, endpoint=True)),
                   n - writers - len(ranks))
        bits = max(1, round(field["scaffold_bits"]
                            * np.exp(SCAFFOLD_SIGMA * rng.standard_normal())))
        scaffold = distinct_ranks(rng, field, min(bits, columns))
        for _ in range(size):
            ranks.append(analogue(rng, field, scaffold))
            family.append(len(scaffolds))
        scaffolds.append(scaffold)
    for _ in range(writers):
        k = int(rng.integers(len(scaffolds)))
        ranks.append(analogue(rng, field, scaffolds[k]))
        family.append(k)
    rows = [np.sort(perm[r]) for r in ranks]
    return rows, np.asarray(family)


def draw(data, field, rng):
    if not 0 < field["columns"] <= data.n:
        raise ValueError(f"{field['name']}: {field['columns']} columns in an "
                         f"index of {data.n}")
    if not 0 <= field.get("writer_rows", 0) < field["rows"]:
        raise ValueError(f"{field['name']}: writer_rows must leave a read row")
    data.cols[field["name"]], _ = families(field, rng)
