"""Draw `one_row_per_column`, for a set field: each of the first `columns`
columns of the index lies in exactly one of `rows` rows, a categorical
attribute such as an order's year. The row is uniform, or, where the field
gives `row_exponent` and `row_ratio`, drawn by the zipf law of
`generate.zipf_ranks` (row 0 the likeliest)."""

import numpy as np

from generate import zipf_ranks


def draw(data, field, rng):
    n, rows = field["columns"], field["rows"]
    if not 0 < n <= data.n:
        raise ValueError(f"{field['name']}: {n} columns in an index of "
                         f"{data.n}")
    if "row_exponent" in field:
        row = zipf_ranks(rng, n, rows, field["row_exponent"],
                         field["row_ratio"])
    else:
        row = rng.integers(0, rows, size=n)
    # A stable sort keeps each row's columns in increasing order.
    order = np.argsort(row.astype(np.uint16 if rows <= 1 << 16 else np.int64),
                       kind="stable")
    ends = np.searchsorted(row[order], np.arange(rows + 1))
    cols = order.astype(np.uint32)
    data.cols[field["name"]] = [cols[ends[r]:ends[r + 1]]
                                for r in range(rows)]
