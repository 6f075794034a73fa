"""executor.py ladder: candidate rows a filtered TopN handed to the device,
summed over its chunks (`executor.topn_candidate_rows`), per TopN the
batched candidate phase answered (`executor.topn_queries`), over the
window: the rows a TopN has to rank, which with the shards gives the bytes
it reads (rows x shards x 128 KiB). None where the program has no such
counters, or the window held no such TopN."""


def read(ctx):
    rows = ctx.delta("executor", "topn_candidate_rows")
    queries = ctx.delta("executor", "topn_queries")
    if rows is None or not queries:
        return None
    return rows / queries
