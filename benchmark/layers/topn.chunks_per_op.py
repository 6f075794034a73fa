"""executor.py ladder: device programs a filtered TopN's candidate phase
launched (`executor.topn_chunks`: one per chunk of `_topn_chunk` rows of
the union of candidates) per TopN that phase answered
(`executor.topn_queries`), over the window. `_topn_chunk` is bounded by
bytes alone (16,384 rows at one shard), so 8,208 rows at one shard are
one program: 1.0. The refetch of the winners (phase 2) is one program
more and is in neither counter. None where the program has no such counters, or
the window held no such TopN."""


def read(ctx):
    chunks = ctx.delta("executor", "topn_chunks")
    queries = ctx.delta("executor", "topn_queries")
    if chunks is None or not queries:
        return None
    return chunks / queries
