"""Draw `int_uniform`, for an int field: each of the first `columns`
columns of the index holds one value, uniform over [`min`, `max`], both
ends included. A fact table's records are numbered densely from 0; this is
the Star Schema Benchmark's law for `lo_quantity` (1..50) and
`lo_discount` (0..10)."""

import numpy as np


def draw(data, field, rng):
    n = field["columns"]
    if not 0 < n <= data.n:
        raise ValueError(f"{field['name']}: {n} columns in an index of "
                         f"{data.n}")
    values = rng.integers(field["min"], field["max"], size=n,
                          dtype=np.int64, endpoint=True)
    data.values[field["name"]] = (np.arange(n, dtype=np.uint32), values)
