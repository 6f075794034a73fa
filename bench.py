"""Benchmark: PQL Count(Intersect) + TopN throughput on device vs host.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "...", "vs_baseline": N, "detail": {...}}

The workload is BASELINE.md's north-star shape scaled to one chip: a
multi-shard index, Count(Intersect(Row,Row)) and TopN served from the
sharded device engine. vs_baseline compares against the same queries
executed with the STRONGEST available host path — the native C kernel
(and_count_words over packed planes, pilosa_tpu/native/bitmap_ops.cpp) when
it loads, else a numpy fallback — measured in this same process. >1.0 means
the device path is faster.

The bench measures the chip: outside BENCH_SMOKE=1 a default jax backend
that is not `tpu` is an error (exit 1) — there is no CPU fallback and no
probe or child process, because a chip belongs to the one process that
touches jax first.

Env knobs: BENCH_SHARDS (default 8), BENCH_ROWS (default 128),
BENCH_DENSITY (default 0.02), BENCH_ITERS (default 1024, capped at
BENCH_ROWS*(BENCH_ROWS-1) so batches contain no duplicate queries),
BENCH_HBM_GIB (resident-stack size for the bandwidth stanza; default 8 on
TPU / 0.125 on CPU), BENCH_BIG_{SHARDS,ROWS,ITERS} (HBM-resident headline
stanza; default 256x128 = 4 GiB on TPU / 16x32 on CPU), and
BENCH_{HBM,BIG,SCALE,OPEN,IMPORT,SERVING,SCHED,TOPN_BSI,TIME_RANGE,MIXED}=0
to skip a stanza (the Pallas-vs-XLA kernel race lives inside the HBM
stanza; SCHED measures the query scheduler's cross-query micro-batching
— dispatches/query with >= 8 concurrent clients; MIXED measures the
delta-refresh path under interleaved writes+reads, delta on vs off).

BENCH_SMOKE=1 runs EVERY stanza at micro scale on the CPU backend
(second-scale workloads): it validates that the bench
itself executes end-to-end and emits a parseable JSON line — the tier-1
smoke test runs it at PR time so bench breakage is caught before a
measurement round burns its deadline on it.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

# Micro-scale mode: every stanza shrinks its workload and its timed-loop
# floors so the full suite completes in seconds. Scale knobs that already
# have env overrides are defaulted in main(); hardcoded stanza constants
# consult this flag directly.
SMOKE = os.environ.get("BENCH_SMOKE") == "1"
# (min loop iterations, min timed seconds) for the open-ended timing loops.
_LOOP_MIN, _LOOP_SECS = (2, 0.05) if SMOKE else (3, 1.5)


def _device_info():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform,
            "device_kind": getattr(d, "device_kind", "?"),
            "n_devices": len(jax.devices())}


def _on_tpu_platform():
    import jax

    return jax.devices()[0].platform == "tpu"


# ------------------------------------------------------------- main bench


def build(n_shards, n_rows, density):
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    holder = Holder(None)
    holder.open()
    idx = holder.create_index("bench")
    fld = idx.create_field("f")
    rng = np.random.default_rng(42)
    bits_per_row_shard = int(SHARD_WIDTH * density)
    all_rows, all_cols = [], []
    for row in range(n_rows):
        for shard in range(n_shards):
            cols = rng.choice(SHARD_WIDTH, size=bits_per_row_shard, replace=False)
            all_rows.append(np.full(bits_per_row_shard, row, dtype=np.uint64))
            all_cols.append(cols.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH))
    fld.import_bits(np.concatenate(all_rows), np.concatenate(all_cols))
    return holder, Executor(holder, workers=0)


def _distinct_pairs(n_rows, iters):
    """`iters` DISTINCT (a, b) row pairs: offset-k ring pairs (i, i+k).

    Distinctness matters for honesty: the engine's within-batch
    memoization collapses duplicate queries (at full counted weight), so a
    batch of repeats would measure dict lookups, not device work. With
    n*(n-1) distinct ordered pairs available, batch sizes far beyond
    n_rows stay duplicate-free."""
    pairs = []
    for off in range(1, n_rows):
        for i in range(n_rows):
            pairs.append((i, (i + off) % n_rows))
            if len(pairs) == iters:
                return pairs
    return pairs


def bench_device(ex, n_rows, n_shards, iters):
    from pilosa_tpu.pql.parser import parse

    engine = ex.engine
    shards = list(range(n_shards))
    pairs = _distinct_pairs(n_rows, iters)
    calls = [
        parse(f"Count(Intersect(Row(f={a}), Row(f={b})))").calls[0].children[0]
        for a, b in pairs
    ]
    # Warmup: compile the batch program + populate the device leaf cache.
    warm = engine.count_batch("bench", calls, shards)
    ex.execute("bench", "TopN(f, n=5)")

    # Correctness guard on the exact path being timed (on TPU this is the
    # Pallas gather kernel): spot-check batched counts against host math.
    rng_chk = np.random.default_rng(7)
    for qi in rng_chk.choice(len(calls), size=min(4, len(calls)), replace=False):
        a, b = pairs[qi]
        want = 0
        for s in range(n_shards):
            frag = ex.holder.fragment("bench", "f", "standard", s)
            want += int(np.bitwise_count(np.bitwise_and(
                frag.plane_np(a), frag.plane_np(b))).sum())
        assert int(warm[qi]) == want, (
            f"device batch count mismatch q{qi}: {int(warm[qi])} != {want}")

    # Pipelined serving: keep several batches in flight so device compute
    # and host<->device transfer overlap (a serving loop with concurrent
    # clients does exactly this).
    depth = int(os.environ.get("BENCH_PIPELINE", "4"))
    min_batches, min_secs = (2, 0.05) if SMOKE else (8, 1.0)
    done = 0
    inflight = []
    start = time.perf_counter()
    while True:
        inflight.append(engine.count_batch_async("bench", calls, shards))
        if len(inflight) >= depth:
            np.asarray(inflight.pop(0))
            done += iters
        if done >= min_batches * iters and time.perf_counter() - start > min_secs:
            break
    for r in inflight:
        np.asarray(r)
        done += iters
    count_qps = done / (time.perf_counter() - start)

    start = time.perf_counter()
    topn_iters = 2 if SMOKE else max(3, min(iters // 4, 32))
    for _ in range(topn_iters):
        ex.execute("bench", "TopN(f, n=5)")
    topn_qps = topn_iters / (time.perf_counter() - start)
    return count_qps, topn_qps


def bench_host(holder, n_rows, n_shards, iters):
    """Same Count(Intersect) math on the strongest host path available.

    Primary baseline: the native C kernel `and_count_words` over packed
    uint32 planes (pilosa_tpu/native/bitmap_ops.cpp:45) — the closest moral
    equivalent of the reference's Go popcount loops. A numpy value-list
    intersect is also measured; the FASTER of the two is the baseline so
    vs_baseline never flatters the device. Returns (qps, detail)."""
    from pilosa_tpu import native
    from pilosa_tpu.constants import SHARD_WIDTH

    frags = [
        holder.fragment("bench", "f", "standard", s) for s in range(n_shards)
    ]

    results = {}

    lib = native.load()
    if lib is not None:
        # Pre-coerce once so the timed loop exercises the typed wrapper
        # (native.and_count_words) without per-call copies.
        planes = {
            row: [np.ascontiguousarray(f.plane_np(row), dtype=np.uint32)
                  for f in frags]
            for row in range(n_rows)
        }
        done = 0
        start = time.perf_counter()
        while done < _LOOP_MIN or time.perf_counter() - start < _LOOP_SECS:
            a, b = done % n_rows, (done + 1) % n_rows
            total = 0
            for pa, pb in zip(planes[a], planes[b]):
                total += native.and_count_words(pa, pb)
            done += 1
        results["native_c_qps"] = done / (time.perf_counter() - start)

    # numpy value-list baseline (pre-extracted sorted column arrays).
    def host_row(frag, row):
        start_pos = row * SHARD_WIDTH
        return frag.storage.slice_range(start_pos, start_pos + SHARD_WIDTH)

    cache = {row: [host_row(f, row) for f in frags] for row in range(n_rows)}
    done = 0
    start = time.perf_counter()
    while done < _LOOP_MIN or time.perf_counter() - start < _LOOP_SECS:
        a, b = done % n_rows, (done + 1) % n_rows
        total = 0
        for sa, sb in zip(cache[a], cache[b]):
            total += len(np.intersect1d(sa, sb, assume_unique=True))
        done += 1
    results["numpy_qps"] = done / (time.perf_counter() - start)

    best = max(results, key=results.get)
    return results[best], {"method": best,
                           **{k: round(v, 2) for k, v in results.items()}}


# ---------------------------------------- HBM-bandwidth / kernel stanza


# Chip peak HBM bandwidth (GB/s) by device_kind, for pct-of-peak
# reporting (public spec sheets; v5 lite == v5e).
_PEAK_GBS = {
    "TPU v2": 700, "TPU v3": 900, "TPU v4": 1228, "TPU v4 lite": 614,
    "TPU v5 lite": 819, "TPU v5e": 819, "TPU v5": 2765, "TPU v5p": 2765,
    "TPU v6 lite": 1640, "TPU v6e": 1640,
}


def _measure_rtt():
    """Round-trip of a trivial dispatch+fetch — the per-call tax every
    blocking device result pays. Subtracted from in-program-loop timings
    so the kernel numbers measure the device, not the dispatch."""
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: x + 1)
    v = int(tiny(jnp.int32(1)))
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        v = int(tiny(jnp.int32(v)))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_hbm():
    """Batched-count throughput on an HBM-resident leaf stack at real scale
    (BASELINE.md north-star shape scaled to one chip's memory).

    Builds a device-resident (U, S, W) uint32 stack (default 8 GiB on TPU
    — PRNG-generated on device; pushing 8 GiB of real fragments through
    the host import path would measure the host, and the serving stanzas
    already exercise the full engine on real fragments), then runs the
    EXACT batched-count program shapes the engine compiles
    (parallel/engine.py:_count_batch_setops): Q gathered 2-leaf
    Intersect counts per iteration, R iterations inside one compiled
    program (lax.fori_loop) so the per-dispatch RTT amortizes.

    Reports achieved GB/s (gather traffic / time, RTT-subtracted) and the
    fraction of the chip's peak HBM bandwidth for:
      - stream: popcount over the whole stack (the no-gather ceiling)
      - xla_gather: the engine's XLA fallback formulation
      - pallas_gather: ops/pallas_kernels.batched_gather_expr_count
    plus per-path effective queries/sec and the Pallas-vs-XLA ratio.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.constants import WORDS_PER_ROW
    from pilosa_tpu.ops import pallas_kernels as pk

    on_tpu = _on_tpu_platform()
    default_gib = "8" if on_tpu else "0.125"
    gib = float(os.environ.get("BENCH_HBM_GIB", default_gib))
    s, w = 8, WORDS_PER_ROW
    u = max(16, int(gib * 2**30 / (s * w * 4)))
    u = -(-u // 8) * 8  # multiple of 8: the stack builds in 8 donated chunks
    q = min(1024, u)
    r = 2 if SMOKE else 16
    out = {"stack_gib": round(u * s * w * 4 / 2**30, 3),
           "shape": [u, s, w], "batch_q": q, "loop_r": r}

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    t0 = time.perf_counter()
    # Chunked fill with buffer donation: one jax.random.bits call for the
    # whole stack peaks at ~2x its size (PRNG counter buffers), which OOMs
    # a 16 GiB chip at the 8 GiB default. Donating the accumulator keeps
    # peak at stack + one chunk.
    n_chunks = 8
    cu = u // n_chunks

    def fill(buf, ck, i):
        chunk = jax.random.bits(ck, (cu, s, w), dtype=jnp.uint32)
        return jax.lax.dynamic_update_slice(buf, chunk, (i * cu, 0, 0))

    fill = jax.jit(fill, donate_argnums=(0,))
    stacked = jnp.zeros((u, s, w), dtype=jnp.uint32)
    for i, ck in enumerate(jax.random.split(k1, n_chunks)):
        stacked = fill(stacked, ck, jnp.int32(i))
    stacked.block_until_ready()
    out["build_s"] = round(time.perf_counter() - t0, 1)
    ia = jax.random.randint(k2, (r, q), 0, u, dtype=jnp.int32)
    ib = jax.random.randint(k3, (r, q), 0, u, dtype=jnp.int32)
    rtt = _measure_rtt()
    out["rtt_ms"] = round(rtt * 1e3, 1)
    peak = _PEAK_GBS.get(_device_info()["device_kind"])
    expr = lambda planes: jnp.bitwise_and(planes[0], planes[1])

    def record(label, fn, nbytes):
        try:
            t0 = time.perf_counter()
            got = int(fn())
            compile_s = time.perf_counter() - t0
            best = 1e9
            for _ in range(1 if SMOKE else 3):
                t0 = time.perf_counter()
                int(fn())
                best = min(best, time.perf_counter() - t0)
            dt = max(best - rtt, 1e-9)
            gbs = nbytes / dt / 1e9
            entry = {"ms": round(best * 1e3, 1), "gbs": round(gbs, 1),
                     "compile_s": round(compile_s, 1)}
            if peak:
                entry["pct_of_peak"] = round(gbs / peak * 100, 1)
            if label != "stream":
                entry["qps"] = round(r * q / dt, 0)
            out[label] = entry
            return got
        except Exception as e:
            out[label] = {"error": f"{type(e).__name__}: {e}"[:400]}
            return None

    # --- ceiling: stream the whole stack R times (popcount+reduce). The
    # body depends on the carry so XLA cannot hoist it out of the loop.
    @jax.jit
    def stream(stacked):
        flat = stacked.reshape(-1)

        def body(i, acc):
            x = flat + acc.astype(jnp.uint32)
            return acc + jnp.sum(lax.population_count(x).astype(jnp.int32))

        return lax.fori_loop(0, r, body, jnp.int32(0))

    record("stream", lambda: stream(stacked), r * u * s * w * 4)

    gather_bytes = r * q * 2 * s * w * 4

    @jax.jit
    def xla_gather(stacked, ia, ib):
        def body(i, acc):
            leaves = (stacked[ia[i]], stacked[ib[i]])  # (Q, S, W) each
            plane = expr(leaves)
            counts = jnp.sum(
                lax.population_count(plane).astype(jnp.int32), axis=(1, 2)
            )
            return acc + jnp.sum(counts)

        return lax.fori_loop(0, r, body, jnp.int32(0))

    got_xla = record("xla_gather", lambda: xla_gather(stacked, ia, ib),
                     gather_bytes)

    if on_tpu:
        @jax.jit
        def pallas_gather(stacked, ia, ib):
            def body(i, acc):
                counts = pk.batched_gather_expr_count(
                    stacked, (ia[i], ib[i]), expr, interpret=False
                )
                return acc + jnp.sum(counts)

            return lax.fori_loop(0, r, body, jnp.int32(0))

        got_pl = record("pallas_gather", lambda: pallas_gather(stacked, ia, ib),
                        gather_bytes)
        if got_xla is not None and got_pl is not None:
            out["verified"] = bool(got_xla == got_pl)
            if "ms" in out.get("xla_gather", {}) and "ms" in out.get("pallas_gather", {}):
                out["pallas_vs_xla"] = round(
                    (out["xla_gather"]["ms"] - out["rtt_ms"])
                    / max(out["pallas_gather"]["ms"] - out["rtt_ms"], 1e-9), 3
                )
    else:
        out["pallas_gather"] = {
            "skipped": "interpret mode would not validate the kernel"
        }
    return out


# --------------------------------------------- HBM-pressure / cache stanza


def bench_scale():
    """Leaf-cache eviction under an artificially tight byte budget
    (SURVEY §7 hard part (a)): touch 2x the budget of distinct row planes
    (cold, thrashing) then a working set that fits (warm), and report hit
    rate / eviction counts / cold-vs-warm latency."""
    from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel.engine import ShardedQueryEngine
    from pilosa_tpu.pql.parser import parse

    n_rows, n_shards = (24, 2) if SMOKE else (192, 4)
    plane_bytes = n_shards * WORDS_PER_ROW * 4
    budget = (n_rows // 2) * plane_bytes  # half the touched set fits

    holder = Holder(None)
    holder.open()
    idx = holder.create_index("scale")
    fld = idx.create_field("f")
    rng = np.random.default_rng(9)
    rows, cols = [], []
    for row in range(n_rows):
        for shard in range(n_shards):
            c = rng.choice(SHARD_WIDTH, size=512, replace=False)
            rows.append(np.full(512, row, dtype=np.uint64))
            cols.append(c.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH))
    fld.import_bits(np.concatenate(rows), np.concatenate(cols))

    old = os.environ.get("PILOSA_LEAF_CACHE_BYTES")
    os.environ["PILOSA_LEAF_CACHE_BYTES"] = str(budget)
    try:
        engine = ShardedQueryEngine(holder)
    finally:
        if old is None:
            os.environ.pop("PILOSA_LEAF_CACHE_BYTES", None)
        else:
            os.environ["PILOSA_LEAF_CACHE_BYTES"] = old
    shards = list(range(n_shards))
    calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}

    # Cold sweep: every plane touched once, evicting under pressure.
    t0 = time.perf_counter()
    for r in range(n_rows):
        engine.count("scale", calls[r], shards)
    cold_s = time.perf_counter() - t0
    cold_counters = dict(engine.counters)

    # Warm working set: fits in budget. A repeat query is answered by the
    # host result memo (O(dict lookup), no device round trip at all) —
    # this is the hot-query serving path, so measure it as such, then
    # bypass the memo to measure the device leaf-cache-hit path too.
    warm_rows = list(range(n_rows // 4))
    for r in warm_rows:
        engine.count("scale", calls[r], shards)  # populate memo + caches
    base = dict(engine.counters)
    t0 = time.perf_counter()
    for r in warm_rows:
        engine.count("scale", calls[r], shards)
    memo_s = time.perf_counter() - t0
    memo_hits = engine.counters["memo_hits"] - base["memo_hits"]

    # The memo populate pass above never touched the leaf cache (memo
    # short-circuits), so load the planes once, then measure dispatches
    # against a warm device cache (count_async skips the memo: every
    # query pays a real dispatch).
    for r in warm_rows:
        np.asarray(engine.count_async("scale", calls[r], shards))
    base = dict(engine.counters)
    t0 = time.perf_counter()
    for r in warm_rows:
        np.asarray(engine.count_async("scale", calls[r], shards))
    warm_s = time.perf_counter() - t0
    warm_hits = engine.counters["leaf_hits"] - base["leaf_hits"]
    warm_misses = engine.counters["leaf_misses"] - base["leaf_misses"]

    holder.close()
    return {
        "budget_mib": round(budget / 2**20, 1),
        "touched_mib": round(n_rows * plane_bytes / 2**20, 1),
        "cold_ms_per_query": round(cold_s / n_rows * 1e3, 2),
        "memo_ms_per_query": round(memo_s / len(warm_rows) * 1e3, 3),
        "memo_hit_rate": round(memo_hits / len(warm_rows), 3),
        "warm_ms_per_query": round(warm_s / len(warm_rows) * 1e3, 2),
        "cold_evictions": cold_counters["leaf_evictions"],
        "warm_hit_rate": round(warm_hits / max(warm_hits + warm_misses, 1), 3),
    }


# ------------------------------------------- HBM-resident headline stanza


def bench_big():
    """HBM-resident, win-by-a-lot headline: a multi-GiB dense index served
    from device memory — Count(Intersect) batched qps and TopN qps vs the
    host native-C kernel (and_count_words) on the SAME planes — plus
    leaf-cache eviction behavior under a halved byte budget at scale.

    Default shape: 256 shards x 128 rows = 4 GiB resident on TPU
    (BENCH_BIG_SHARDS/BENCH_BIG_ROWS override; 16 x 32 = 256 MiB on CPU
    so the stanza still validates there). Fragments are built by direct
    dense-container injection: this stanza measures SERVING at scale —
    bench_import owns the ingest path, and multi-GiB through bulk_import
    would measure the host parser, not the chip."""
    from pilosa_tpu import native
    from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.bitmap import Container

    on_tpu = _on_tpu_platform()
    n_shards = int(os.environ.get("BENCH_BIG_SHARDS", "256" if on_tpu else "16"))
    n_rows = int(os.environ.get("BENCH_BIG_ROWS", "128" if on_tpu else "32"))
    n_containers = SHARD_WIDTH >> 16
    plane_bytes = n_shards * WORDS_PER_ROW * 4
    stack_bytes = n_rows * plane_bytes
    out = {"shards": n_shards, "rows": n_rows,
           "stack_gib": round(stack_bytes / 2**30, 3),
           # ~50% density random planes: the set-bit count positions this
           # stanza against the reference's 1B+-row workloads
           # (docs/examples.md:16 NYC taxi).
           "set_bits_approx": int(stack_bytes * 8 * 0.5)}

    rng = np.random.default_rng(11)
    holder = Holder(None)
    holder.open()
    idx = holder.create_index("big")
    fld = idx.create_field("f")
    view = fld.create_view_if_not_exists("standard")
    t0 = time.perf_counter()
    for shard in range(n_shards):
        frag = view.create_fragment_if_not_exists(shard, broadcast=False)
        words = rng.integers(
            0, 1 << 64, size=(n_rows, n_containers, 1024), dtype=np.uint64
        )
        counts = np.bitwise_count(words).sum(axis=2)
        for row in range(n_rows):
            for ci in range(n_containers):
                frag.storage.containers[row * n_containers + ci] = Container(
                    bits=words[row, ci], n=int(counts[row, ci])
                )
            frag.cache.bulk_add(row, int(counts[row].sum()))
        frag.cache.invalidate(force=True)
    out["build_s"] = round(time.perf_counter() - t0, 1)

    # Engine caches must hold the whole stack for the resident phase; the
    # batched count path and TopN each keep their own stacked copy.
    budget = str(int(stack_bytes * 1.25))
    env_keys = ("PILOSA_LEAF_CACHE_BYTES", "PILOSA_STACK_CACHE_BYTES")
    saved = {k: os.environ.get(k) for k in env_keys}
    for k in env_keys:
        os.environ[k] = budget
    try:
        ex = Executor(holder, workers=0)
        engine = ex.engine
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    from pilosa_tpu.pql.parser import parse

    shards = list(range(n_shards))

    # --- Count(Intersect) batched serving on the resident stack.
    iters = min(int(os.environ.get("BENCH_BIG_ITERS", "256")),
                n_rows * (n_rows - 1))
    pairs = _distinct_pairs(n_rows, iters)
    calls = [
        parse(f"Count(Intersect(Row(f={a}), Row(f={b})))").calls[0].children[0]
        for a, b in pairs
    ]
    warm = engine.count_batch("big", calls, shards)
    # Spot-check the exact timed path against host C math on one pair.
    a, b = pairs[0]
    want = 0
    for s in shards:
        frag = holder.fragment("big", "f", "standard", s)
        want += int(np.bitwise_count(np.bitwise_and(
            frag.plane_np(a), frag.plane_np(b))).sum())
    assert int(warm[0]) == want, f"big count mismatch: {int(warm[0])} != {want}"

    t0 = time.perf_counter()
    reps = 1 if SMOKE else 4
    for _ in range(reps):
        np.asarray(engine.count_batch_async("big", calls, shards))
    dt = time.perf_counter() - t0
    out["count_qps_device"] = round(reps * iters / dt, 1)
    out["count_gbs"] = round(reps * iters * 2 * plane_bytes / dt / 1e9, 1)

    # --- Host native-C baseline on the same planes (pre-coerced once).
    # Few pairs: the ~2s timed loop touches a handful, and every
    # pre-coerced row costs plane_bytes of extra host RSS (32 MiB at the
    # 256-shard default — 64 rows would double the container store).
    lib = native.load()
    host_planes = {}
    for row in {r for p in pairs[:8] for r in p}:
        host_planes[row] = [
            np.ascontiguousarray(
                holder.fragment("big", "f", "standard", s).plane_np(row),
                dtype=np.uint32)
            for s in shards
        ]
    host_pairs = [p for p in pairs[:8] if p[0] in host_planes and p[1] in host_planes]

    def host_once(i):
        pa, pb = host_planes[host_pairs[i][0]], host_planes[host_pairs[i][1]]
        if lib is not None:
            return sum(native.and_count_words(x, y) for x, y in zip(pa, pb))
        return sum(int(np.bitwise_count(np.bitwise_and(x, y)).sum())
                   for x, y in zip(pa, pb))

    done = 0
    t0 = time.perf_counter()
    while done < _LOOP_MIN or time.perf_counter() - t0 < (0.1 if SMOKE else 2.0):
        host_once(done % len(host_pairs))
        done += 1
    host_qps = done / (time.perf_counter() - t0)
    out["count_qps_host"] = round(host_qps, 2)
    out["host_method"] = "native_c" if lib is not None else "numpy"
    out["count_vs_host"] = round(out["count_qps_device"] / max(host_qps, 1e-9), 1)

    # --- TopN at scale (full candidate set rides the resident stack).
    cyc = {"i": 0}

    def next_topn():
        cyc["i"] += 1
        return ex.execute("big", f"TopN(f, Row(f={cyc['i'] % n_rows}), n=10)")

    next_topn()  # compile + stack build
    t0 = time.perf_counter()
    reps = 2 if SMOKE else 6
    for _ in range(reps):
        next_topn()
    out["topn_qps_device"] = round(reps / (time.perf_counter() - t0), 2)

    # --- Eviction under pressure: budget halved, sweep every row once.
    for k in env_keys:
        os.environ[k] = str(int(stack_bytes * 0.5))
    try:
        from pilosa_tpu.parallel.engine import ShardedQueryEngine

        tight = ShardedQueryEngine(holder)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    row_calls = [parse(f"Row(f={r})").calls[0] for r in range(n_rows)]
    t0 = time.perf_counter()
    for call in row_calls:
        tight.count("big", call, shards)
    out["evict_sweep_ms_per_query"] = round(
        (time.perf_counter() - t0) / n_rows * 1e3, 2)
    out["evictions"] = tight.counters["leaf_evictions"]
    holder.close()
    return out


# ----------------------------------------------- concurrent-serving stanza


def bench_serving():
    """48 parallel HTTP clients against a live in-process server:
    end-to-end concurrent serving qps through the real threaded HTTP
    stack, with the host result memo both off (every request pays a real
    dispatch) and on (the production zipf-repeat regime).

    A transparent query coalescer was removed in r5 after three rounds of
    driver-captured losses (r3 0.39x remote, r5 0.71x host — concurrent
    blocking clients pipeline their own round trips / host threads
    parallelize dispatches across cores); this stanza now tracks the
    serving path that actually ships."""
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    n_rows, n_clients, per_client = (8, 6, 3) if SMOKE else (32, 48, 12)
    rng = np.random.default_rng(11)
    out = {}
    for label, memo in (("memo_off", "0"), ("memo_on", "8192")):
        os.environ["PILOSA_MEMO_ENTRIES"] = memo
        s = Server(cache_flush_interval=0, member_monitor_interval=0)
        s.open()
        try:
            idx = s.holder.create_index("serve")
            fld = idx.create_field("f")
            rows, cols = [], []
            for row in range(n_rows):
                c = rng.choice(SHARD_WIDTH, size=2048, replace=False)
                rows.append(np.full(2048, row, dtype=np.uint64))
                cols.append(c.astype(np.uint64))
            fld.import_bits(np.concatenate(rows), np.concatenate(cols))
            h = f"localhost:{s.port}"

            def worker(wid):
                local = InternalClient()
                for i in range(per_client):
                    local.query(h, "serve", f"Count(Row(f={(wid + i) % n_rows}))")

            # Warm: compile programs + fill leaf cache (and memo when on),
            # so the timed pass measures steady-state serving.
            with ThreadPoolExecutor(max_workers=n_clients) as pool:
                list(pool.map(worker, range(n_clients)))
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=n_clients) as pool:
                list(pool.map(worker, range(n_clients)))
            qps = n_clients * per_client / (time.perf_counter() - t0)
            out[f"qps_{label}"] = round(qps, 1)
        finally:
            s.close()
            os.environ.pop("PILOSA_MEMO_ENTRIES", None)
    if out.get("qps_memo_off"):
        out["memo_speedup"] = round(
            out["qps_memo_on"] / out["qps_memo_off"], 2
        )
    return out


# --------------------------------------------- scheduler/coalescing stanza


def bench_sched():
    """Concurrent clients through the query scheduler's micro-batcher:
    dispatches/query for >= 8 simultaneous same-shape Count queries over
    one resident stack (the ISSUE-1 acceptance metric), plus qps with the
    batch window on vs. off. Unlike the r5-removed transparent coalescer,
    the batcher holds a dispatch ONLY under concurrent pressure (a lone
    query pays zero added latency), so the win condition is fewer engine
    launches per query at equal-or-better qps. The result memo is off so
    every request would otherwise be its own device dispatch."""
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.sched import SchedulerConfig
    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    n_rows, n_clients, per_client = (8, 4, 4) if SMOKE else (16, 16, 16)
    rng = np.random.default_rng(23)
    out = {}
    prev_memo = os.environ.get("PILOSA_MEMO_ENTRIES")
    os.environ["PILOSA_MEMO_ENTRIES"] = "0"
    try:
        for label, window_max in (("batch_off", 0.0), ("batch_on", 0.002)):
            s = Server(
                cache_flush_interval=0, member_monitor_interval=0,
                scheduler_config=SchedulerConfig(
                    interactive_concurrency=n_clients,
                    batch_window=0.0005, batch_window_max=window_max,
                ),
            )
            s.open()
            try:
                idx = s.holder.create_index("sched")
                fld = idx.create_field("f")
                rows, cols = [], []
                for row in range(n_rows):
                    c = rng.choice(SHARD_WIDTH, size=2048, replace=False)
                    rows.append(np.full(2048, row, dtype=np.uint64))
                    cols.append(c.astype(np.uint64))
                fld.import_bits(np.concatenate(rows), np.concatenate(cols))
                h = f"localhost:{s.port}"

                def worker(wid):
                    local = InternalClient()
                    for i in range(per_client):
                        local.query(
                            h, "sched", f"Count(Row(f={(wid + i) % n_rows}))")

                with ThreadPoolExecutor(max_workers=n_clients) as pool:
                    list(pool.map(worker, range(n_clients)))  # warm/compile
                with urllib.request.urlopen(f"http://{h}/debug/vars") as r:
                    before = json.load(r)["engine_cache"]["count_dispatches"]
                t0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=n_clients) as pool:
                    list(pool.map(worker, range(n_clients)))
                elapsed = time.perf_counter() - t0
                with urllib.request.urlopen(f"http://{h}/debug/vars") as r:
                    dv = json.load(r)
                n_q = n_clients * per_client
                dpq = (dv["engine_cache"]["count_dispatches"] - before) / n_q
                out[label] = {
                    "qps": round(n_q / elapsed, 1),
                    "dispatches_per_query": round(dpq, 3),
                }
                if label == "batch_on":
                    out[label]["batcher"] = dv.get("batcher", {})
            finally:
                s.close()
    finally:
        # Restore (not pop): a user-exported memo size must still govern
        # the stanzas that run after this one.
        if prev_memo is None:
            os.environ.pop("PILOSA_MEMO_ENTRIES", None)
        else:
            os.environ["PILOSA_MEMO_ENTRIES"] = prev_memo
    if "batch_on" in out and "batch_off" in out:
        out["coalesced_ok"] = out["batch_on"]["dispatches_per_query"] < 1.0
        off = out["batch_off"]["qps"]
        if off:
            out["qps_ratio"] = round(out["batch_on"]["qps"] / off, 2)
    return out


# --------------------------------------------- tracing-overhead stanza


def bench_obs():
    """Per-query tracing cost + slow-query log (docs/observability.md):
    the SCHED-stanza workload (concurrent same-shape Counts, memo off so
    every request pays a real dispatch) with the trace recorder at
    sample-rate 1.0 vs disabled. The acceptance gate is qps within 5% of
    untraced — the disabled path is one conditional per stage, and the
    enabled path must stay cheap enough to run at 1.0 in production.
    Each mode takes the best of two timed passes (the gate is about
    tracing cost, not scheduler jitter on a loaded box). A final phase
    injects a 30 ms device-dispatch latency failpoint under a 5 ms
    slow-query threshold and asserts the slow-query log line fires with
    the full stage breakdown."""
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu import failpoints
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.logger import BufferLogger
    from pilosa_tpu.obs import ObsConfig
    from pilosa_tpu.sched import SchedulerConfig
    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    n_rows, n_clients, per_client = (8, 4, 25) if SMOKE else (16, 16, 16)
    passes = 4 if SMOKE else 3
    rng = np.random.default_rng(29)
    out = {}
    prev_memo = os.environ.get("PILOSA_MEMO_ENTRIES")
    os.environ["PILOSA_MEMO_ENTRIES"] = "0"
    try:
        # ONE server, modes interleaved by flipping the recorder's sample
        # rate between passes: two separate servers measured box-load
        # drift and jit-cache luck, not tracing (smoke runs swung 0.45x
        # to 1.7x on the same code). Best-of-N per mode, alternating, so
        # both modes sample the same load window.
        s = Server(
            cache_flush_interval=0, member_monitor_interval=0,
            scheduler_config=SchedulerConfig(
                interactive_concurrency=n_clients),
            obs_config=ObsConfig(sample_rate=1.0, ring_size=256),
        )
        s.open()
        try:
            idx = s.holder.create_index("obs")
            fld = idx.create_field("f")
            rows, cols = [], []
            for row in range(n_rows):
                c = rng.choice(SHARD_WIDTH, size=2048, replace=False)
                rows.append(np.full(2048, row, dtype=np.uint64))
                cols.append(c.astype(np.uint64))
            fld.import_bits(np.concatenate(rows), np.concatenate(cols))
            h = f"localhost:{s.port}"

            def worker(wid):
                local = InternalClient()
                for i in range(per_client):
                    local.query(
                        h, "obs", f"Count(Row(f={(wid + i) % n_rows}))")

            def timed_pass():
                t0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=n_clients) as pool:
                    list(pool.map(worker, range(n_clients)))
                return n_clients * per_client / (time.perf_counter() - t0)

            with ThreadPoolExecutor(max_workers=n_clients) as pool:
                list(pool.map(worker, range(n_clients)))  # warm/compile

            def traces_finished():
                with urllib.request.urlopen(f"http://{h}/debug/vars") as r:
                    return json.load(r)["obs"]["traces_finished"]

            # DELTA across the timed traced passes, not the absolute
            # counter: the warm pass runs at sample-rate 1.0 and alone
            # satisfies an absolute threshold — the gate must prove the
            # MEASURED passes actually traced.
            traces_before = traces_finished()
            best = {"untraced": 0.0, "traced": 0.0}
            ratios = []
            for rep in range(passes):
                # Back-to-back pair per round, order alternating, and the
                # gate judges the BEST pairwise ratio: tracing cannot
                # make queries faster, so one clean round at parity
                # proves the overhead bound; independent best-of-N per
                # mode still flaked on loaded boxes (2x pass-to-pass
                # swings dwarf any real 5% signal).
                modes = [("untraced", 0.0), ("traced", 1.0)]
                if rep % 2:
                    modes.reverse()
                qps = {}
                for label, rate in modes:
                    s.trace_recorder.config.sample_rate = rate
                    qps[label] = timed_pass()
                    best[label] = max(best[label], qps[label])
                ratios.append(qps["traced"] / qps["untraced"])
            out["untraced"] = {"qps": round(best["untraced"], 1)}
            out["traced"] = {"qps": round(best["traced"], 1)}
            out["pair_ratios"] = [round(r, 3) for r in ratios]
            out["traced"]["traces_finished"] = (
                traces_finished() - traces_before)
        finally:
            s.close()

        # --- slow-query phase: injected latency must fire the log.
        log = BufferLogger()
        s = Server(
            cache_flush_interval=0, member_monitor_interval=0, logger=log,
            obs_config=ObsConfig(sample_rate=1.0, slow_query_ms=5.0),
        )
        s.open()
        try:
            idx = s.holder.create_index("obs")
            fld = idx.create_field("f")
            fld.import_bits(np.zeros(256, dtype=np.uint64),
                            np.arange(256, dtype=np.uint64))
            h = f"localhost:{s.port}"
            client = InternalClient()
            failpoints.configure("device-dispatch", "latency", arg=30.0)
            try:
                client.query(h, "obs", "Count(Row(f=0))")
            finally:
                failpoints.reset()
            with urllib.request.urlopen(f"http://{h}/debug/vars") as r:
                slow = json.load(r)["obs"]["slow_queries"]
            slow_lines = [ln for _lvl, ln in log.lines
                          if "[obs] slow query" in ln]
            out["slow_query"] = {
                "slow_queries": slow,
                "logged": bool(slow_lines),
                "has_breakdown": bool(
                    slow_lines and "device.dispatch" in slow_lines[0]),
            }
            out["slow_query_logged"] = bool(slow_lines) and slow >= 1
        finally:
            s.close()
    finally:
        if prev_memo is None:
            os.environ.pop("PILOSA_MEMO_ENTRIES", None)
        else:
            os.environ["PILOSA_MEMO_ENTRIES"] = prev_memo
    if out.get("untraced", {}).get("qps"):
        out["qps_ratio"] = round(
            out["traced"]["qps"] / out["untraced"]["qps"], 3)
        out["obs_ok"] = max(out["pair_ratios"]) >= 0.95
        # Every query of every TIMED traced pass landed a trace.
        out["traced_all"] = (
            out["traced"].get("traces_finished", 0)
            >= passes * n_clients * per_client)
    return out


# --------------------------------------------- mixed read/write stanza


def bench_mixed():
    """Mixed ingest+serve — the delta-refresh tentpole's target regime:
    batched Counts over a resident leaf stack while a deterministic write
    stream dirties the planes (writes_per_batch single-bit sets applied
    between query batches, round-robin over resident rows, so both runs
    see byte-identical traffic). Reports qps and bytes moved host->device
    with the delta path on (default) vs forced off
    (PILOSA_TPU_ENGINE_DELTA_MAX_FRACTION=0: every write costs a full plane walk +
    re-upload + restack). The win condition is bytes_to_device collapsing
    by orders of magnitude at equal-or-better qps."""
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel.engine import ShardedQueryEngine
    from pilosa_tpu.pql.parser import parse

    n_shards, n_rows, reps = (2, 8, 4) if SMOKE else (8, 32, 24)
    writes_per_batch = int(os.environ.get("BENCH_MIXED_WRITES", "4"))
    rng = np.random.default_rng(17)
    holder = Holder(None)
    holder.open()
    idx = holder.create_index("mix")
    fld = idx.create_field("f")
    rows, cols = [], []
    for row in range(n_rows):
        for shard in range(n_shards):
            c = rng.choice(SHARD_WIDTH, size=1024, replace=False)
            rows.append(np.full(1024, row, dtype=np.uint64))
            cols.append(c.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH))
    fld.import_bits(np.concatenate(rows), np.concatenate(cols))
    shards = list(range(n_shards))
    iters = min(n_rows * (n_rows - 1), 64)
    pairs = _distinct_pairs(n_rows, iters)
    calls = [
        parse(f"Count(Intersect(Row(f={a}), Row(f={b})))").calls[0].children[0]
        for a, b in pairs
    ]
    out = {"shards": n_shards, "rows": n_rows, "batches": reps,
           "writes_per_batch": writes_per_batch, "batch_q": iters}
    prev = os.environ.get("PILOSA_TPU_ENGINE_DELTA_MAX_FRACTION")
    # One monotone write stream ACROSS both runs: re-setting an already-set
    # bit is a no-op (no generation bump), so a per-run counter would hand
    # the second run a write stream of phantoms and zero cache churn.
    wcol = {"i": 0}

    def write_burst():
        for k in range(writes_per_batch):
            wcol["i"] += 1
            fld.set_bit(wcol["i"] % n_rows,
                        (wcol["i"] * 7919) % SHARD_WIDTH)

    try:
        for label, frac in (("delta_on", None), ("delta_off", "0")):
            if frac is None:
                os.environ.pop("PILOSA_TPU_ENGINE_DELTA_MAX_FRACTION", None)
            else:
                os.environ["PILOSA_TPU_ENGINE_DELTA_MAX_FRACTION"] = frac
            engine = ShardedQueryEngine(holder)

            # Warm: build the resident stack, compile the count AND the
            # delta-scatter programs so the timed loop is steady state.
            np.asarray(engine.count_batch_async("mix", calls, shards))
            write_burst()
            np.asarray(engine.count_batch_async("mix", calls, shards))
            base = dict(engine.counters)
            t0 = time.perf_counter()
            for _ in range(reps):
                write_burst()
                np.asarray(engine.count_batch_async("mix", calls, shards))
            dt = time.perf_counter() - t0
            moved = (engine.counters["delta_bytes"]
                     + engine.counters["full_refresh_bytes"]
                     - base["delta_bytes"] - base["full_refresh_bytes"])
            engine.close()  # release the cold-gather thread pool
            out[label] = {
                "qps": round(reps * iters / dt, 1),
                "bytes_to_device": int(moved),
                "delta_bytes": engine.counters["delta_bytes"] - base["delta_bytes"],
                "leaf_delta_hits":
                    engine.counters["leaf_delta_hits"] - base["leaf_delta_hits"],
                "stack_delta_hits":
                    engine.counters["stack_delta_hits"] - base["stack_delta_hits"],
                "full_refresh_bytes":
                    engine.counters["full_refresh_bytes"]
                    - base["full_refresh_bytes"],
            }
    finally:
        if prev is None:
            os.environ.pop("PILOSA_TPU_ENGINE_DELTA_MAX_FRACTION", None)
        else:
            os.environ["PILOSA_TPU_ENGINE_DELTA_MAX_FRACTION"] = prev
    holder.close()
    on, off = out["delta_on"], out["delta_off"]
    out["bytes_ratio_off_over_on"] = round(
        off["bytes_to_device"] / max(on["bytes_to_device"], 1), 1)
    out["qps_ratio_on_over_off"] = round(
        on["qps"] / max(off["qps"], 1e-9), 2)
    out["delta_ok"] = (on["bytes_to_device"] < off["bytes_to_device"]
                       and on["stack_delta_hits"] > 0)
    return out


# --------------------------------------------- peer fault / brown-out stanza


def bench_fault():
    """Scripted peer brown-out through the resilience layer (docs/
    fault-tolerance.md): a 3-node replica_n=2 cluster serves Count
    queries from node0 while one peer's link degrades in phases —
    healthy -> flaky(0.5) (brown-out) -> drop (blackhole) -> healed.
    Reports per-phase qps and p50/p99 latency, the recovery time from
    fault-clear to converged routing (every breaker re-closed, a full
    clean query round), and node0's breaker/retry/hedge counters as
    evidence that a blackholed peer stops costing connect attempts and
    replica retries stayed inside the budget."""
    import shutil
    import socket
    import tempfile

    from pilosa_tpu import failpoints
    from pilosa_tpu.cluster.hash import ModHasher
    from pilosa_tpu.cluster.health import CLOSED, ResilienceConfig
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.errors import PilosaError
    from pilosa_tpu.server.client import ClientError, InternalClient
    from pilosa_tpu.server.server import Server

    n_rows, per_phase = (2, 6) if SMOKE else (4, 50)
    n_shards = 2 if SMOKE else 4

    def free_port():
        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="bench-fault-")
    ports = [free_port() for _ in range(3)]
    hosts = [f"localhost:{p}" for p in ports]
    servers = []
    out = {"shards": n_shards, "rows": n_rows, "queries_per_phase": per_phase}
    try:
        for i, port in enumerate(ports):
            s = Server(
                data_dir=os.path.join(tmp, f"node{i}"),
                port=port,
                cluster_hosts=hosts,
                replica_n=2,
                hasher=ModHasher(),
                cache_flush_interval=0,
                anti_entropy_interval=0,
                member_monitor_interval=0,  # convergence driven below
                resilience_config=ResilienceConfig(
                    breaker_backoff=0.1, breaker_backoff_max=0.5,
                ),
            )
            s.open()
            servers.append(s)
        client = InternalClient(timeout=10.0)
        client.create_index(hosts[0], "ft")
        client.create_field(hosts[0], "ft", "f")
        time.sleep(0.05)
        for row in range(n_rows):
            for shard in range(n_shards):
                client.query(
                    hosts[0], "ft",
                    f"Set({shard * SHARD_WIDTH + row + 1}, f={row})",
                )
        # Query head: a node that does NOT own some shard, so full-index
        # queries must fan out remotely; fault target: that shard's
        # preferred owner. (Each shard excludes exactly one of the three
        # nodes, so such a pair always exists.)
        s0 = target = None
        for s in servers:
            for shard in range(n_shards):
                owners = s.cluster.shard_nodes("ft", shard)
                if all(n.id != s.node.id for n in owners):
                    s0, target = s, owners[0].uri
                    break
            if s0 is not None:
                break
        assert s0 is not None, "placement gave every node every shard"
        h0 = s0.node.uri

        def run_phase(n):
            lat = []
            ok = err = 0
            t0 = time.perf_counter()
            for i in range(n):
                q0 = time.perf_counter()
                try:
                    client.query(h0, "ft", f"Count(Row(f={i % n_rows}))")
                    ok += 1
                    lat.append(time.perf_counter() - q0)
                except (ClientError, PilosaError):
                    err += 1
            dt = time.perf_counter() - t0
            lat.sort()
            pick = (lambda q: round(
                lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 2
            )) if lat else (lambda q: None)
            return {"qps": round(ok / dt, 1) if dt else 0.0,
                    "p50_ms": pick(0.50), "p99_ms": pick(0.99),
                    "ok": ok, "errors": err}

        out["healthy"] = run_phase(per_phase)
        failpoints.seed(7)
        failpoints.configure(f"client-send@{target}", "flaky", arg=0.5)
        out["brownout_flaky"] = run_phase(per_phase)
        failpoints.configure(f"client-send@{target}", "drop")
        out["blackhole"] = run_phase(per_phase)
        failpoints.reset()

        # Recovery: from fault-clear to converged routing — breakers
        # re-closed everywhere and one fully clean, correct query round.
        t0 = time.perf_counter()
        deadline = t0 + 30.0
        recovered = False
        while time.perf_counter() < deadline and not recovered:
            for s in servers:
                s._monitor_members()
            try:
                for row in range(n_rows):
                    got = client.query(h0, "ft", f"Count(Row(f={row}))")
                    assert got["results"][0] == n_shards
            except (ClientError, PilosaError, AssertionError):
                time.sleep(0.02)
                continue
            snap = s0.cluster.health.snapshot()
            recovered = all(
                p["state"] == CLOSED for p in snap["peers"].values()
            )
        out["recovery_s"] = round(time.perf_counter() - t0, 3)
        out["recovered"] = recovered
        snap = s0.cluster.health.snapshot()
        out["breaker"] = {k: snap[k] for k in (
            "breaker_opened", "breaker_closed", "breaker_short_circuits",
            "half_open_probes", "retries_spent", "retries_denied",
            "hedges_fired", "hedges_won",
        )}
        out["fault_ok"] = bool(
            recovered
            and out["healthy"]["errors"] == 0
            and snap["breaker_opened"] >= 1
        )
    finally:
        failpoints.reset()
        for s in servers:
            try:
                s.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# ------------------------------------------ durable write replication stanza


def bench_replication():
    """Durable write replication (docs/durability.md "Write-path
    consistency"): a 3-node replica_n=3 cluster under
    write-consistency=quorum, with node2 running as a SEPARATE PROCESS
    so it can be SIGKILLed mid-stream. Phases: healthy quorum writes ->
    kill -9 node2 and keep writing (every write still acks at quorum on
    the two survivors; each missed forward costs a hint append — counters
    prove the breaker-open path never pays a connect timeout) -> restart
    node2 -> measure hint-drain time -> verify ZERO lost acked writes on
    the restarted replica and byte-identical fragments vs the survivor."""
    import io
    import shutil
    import signal
    import socket
    import subprocess
    import sys
    import tempfile
    import textwrap

    from pilosa_tpu.cluster.hash import ModHasher
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.cluster.hints import ReplicationConfig
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.errors import PilosaError
    from pilosa_tpu.server.client import ClientError, InternalClient
    from pilosa_tpu.server.server import Server

    n_shards, per_phase = (2, 20) if SMOKE else (4, 120)

    def free_port():
        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="bench-repl-")
    ports = [free_port() for _ in range(3)]
    hosts = [f"localhost:{p}" for p in ports]
    out = {"shards": n_shards, "writes_per_phase": per_phase,
           "level": "quorum"}
    servers = []
    child = None

    child_src = textwrap.dedent("""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        from pilosa_tpu.cluster.hash import ModHasher
        from pilosa_tpu.cluster.health import ResilienceConfig
        from pilosa_tpu.cluster.hints import ReplicationConfig
        from pilosa_tpu.server.server import Server
        import time
        s = Server(
            data_dir=sys.argv[1], port=int(sys.argv[2]),
            cluster_hosts=sys.argv[3].split(","), replica_n=3,
            hasher=ModHasher(), cache_flush_interval=0,
            anti_entropy_interval=0, member_monitor_interval=0,
            executor_workers=0,
            resilience_config=ResilienceConfig(
                breaker_backoff=0.1, breaker_backoff_max=0.5),
            replication_config=ReplicationConfig(
                write_consistency="quorum", deliver_interval=0.2),
        )
        s.open()
        print("ready", flush=True)
        while True:
            time.sleep(3600)
    """)

    def spawn_child():
        p = subprocess.Popen(
            [sys.executable, "-c", child_src,
             os.path.join(tmp, "node2"), str(ports[2]), ",".join(hosts)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        line = p.stdout.readline()
        if "ready" not in line:
            err = p.stderr.read()
            raise RuntimeError(f"replication child failed to open: {err[-400:]}")
        return p

    def run_writes(client, h0, start, n, row=7):
        lat = []
        acked = []
        t0 = time.perf_counter()
        for i in range(start, start + n):
            col = (i % n_shards) * SHARD_WIDTH + 10 + i
            q0 = time.perf_counter()
            client.query(h0, "repl", f"Set({col}, f={row})")
            lat.append(time.perf_counter() - q0)
            acked.append(col)
        dt = time.perf_counter() - t0
        lat.sort()
        pick = lambda q: round(lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 2)  # noqa: E731
        return acked, {"qps": round(n / dt, 1) if dt else 0.0,
                       "p50_ms": pick(0.50), "p99_ms": pick(0.99)}

    try:
        for i in range(2):
            s = Server(
                data_dir=os.path.join(tmp, f"node{i}"),
                port=ports[i],
                cluster_hosts=hosts,
                replica_n=3,
                hasher=ModHasher(),
                cache_flush_interval=0,
                anti_entropy_interval=0,
                member_monitor_interval=0,  # convergence driven below
                executor_workers=0,
                resilience_config=ResilienceConfig(
                    breaker_backoff=0.1, breaker_backoff_max=0.5),
                replication_config=ReplicationConfig(
                    write_consistency="quorum", deliver_interval=0.2),
            )
            s.open()
            servers.append(s)
        child = spawn_child()
        s0 = servers[0]
        peer2 = None
        client = InternalClient(timeout=10.0)
        h0 = hosts[0]
        client.create_index(h0, "repl")
        client.create_field(h0, "repl", "f")
        time.sleep(0.1)
        for n in s0.cluster.nodes:
            if str(ports[2]) in n.id:
                peer2 = n.id
        assert peer2 is not None

        acked = []
        a, out["healthy"] = run_writes(client, h0, 0, per_phase)
        acked += a

        # SIGKILL node2 mid-stream; every later write still acks at
        # quorum (2/3) on the survivors, missed forwards become hints.
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=30)
        counters0 = dict(s0.stats.snapshot()["counters"])
        a, out["during_outage"] = run_writes(client, h0, per_phase, per_phase)
        acked += a
        counters1 = dict(s0.stats.snapshot()["counters"])
        delta = {k: counters1.get(k, 0) - counters0.get(k, 0)
                 for k in ("WriteForwardFailed", "WriteForwardHinted",
                           "WriteForwardSkipped", "WriteConsistencyUnmet")}
        out["outage_counters"] = delta
        out["pending_hints"] = s0.hints.pending(peer2)
        # The breaker-open write path: exactly the breaker-detection
        # writes pay a transport failure; everything else is a hint
        # append, and NO write missed its quorum level.
        out["hinted_ok"] = bool(
            delta["WriteConsistencyUnmet"] == 0
            and delta["WriteForwardHinted"] >= per_phase - 2
            and delta["WriteForwardFailed"] <= 2
        )

        # Restart node2 and measure the hint drain (delivery daemon on
        # node0; member probes driven here so recovery detection isn't
        # the thing being measured).
        child = spawn_child()
        t0 = time.perf_counter()
        deadline = t0 + 60.0
        while time.perf_counter() < deadline and s0.hints.pending(peer2):
            for s in servers:
                s._monitor_members()
            time.sleep(0.05)
        out["hint_drain_s"] = round(time.perf_counter() - t0, 3)
        out["drained"] = s0.hints.pending(peer2) == 0
        out["replication_vars"] = {
            k: v for k, v in s0.hints.snapshot().items()
            if isinstance(v, (int, str))
        }

        # Zero lost acked writes: every acked bit is present on the
        # RESTARTED replica, and its fragments are byte-identical to the
        # survivor's.
        lost = 0
        byte_identical = True
        for shard in range(n_shards):
            frag0 = s0.holder.fragment("repl", "f", "standard", shard)
            if frag0 is None:
                continue
            b0 = io.BytesIO()
            frag0.write_to(b0)
            try:
                remote = client.retrieve_shard_from_uri(
                    hosts[2], "repl", "f", "standard", shard)
            except (ClientError, PilosaError):
                byte_identical = False
                lost += sum(1 for c in acked
                            if c // SHARD_WIDTH == shard)
                continue
            if remote != b0.getvalue():
                byte_identical = False
            # Every acked col must be a set bit (row 7) on the
            # coordinator; the byte compare above extends the proof to
            # the restarted replica.
            want = {7 * SHARD_WIDTH + (c % SHARD_WIDTH)
                    for c in acked if c // SHARD_WIDTH == shard}
            have = {int(p) for p in frag0.storage.slice()}
            lost += len(want - have)
        out["lost_acked_writes"] = lost
        out["byte_identical"] = byte_identical
        out["replication_ok"] = bool(
            out["drained"] and out["hinted_ok"] and lost == 0
            and byte_identical)
    finally:
        for s in servers:
            try:
                s.close()
            except Exception:
                pass
        if child is not None:
            try:
                child.kill()
                child.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# ------------------------------------------------------------- CDC stanza


def bench_cdc():
    """Change-data-capture acceptance (docs/cdc.md): one node with change
    capture on. tail: a consumer long-polls the change stream while the
    writer streams Set() ops — per-record delivery lag (write ack ->
    consumer decode), a dense-position proof (zero gaps or renumbers),
    and a byte-exact replay of the streamed op bytes against the live
    fragment. pit: at-position reads vs answers frozen at each
    checkpoint, cold materialization vs the LRU-warm repeat. standing:
    one registered Count must re-push within ONE evaluator sweep of a
    write that changed its answer, and must NOT re-push for a write that
    didn't."""
    import shutil
    import tempfile
    import threading

    from pilosa_tpu.cdc import CdcConfig
    from pilosa_tpu.cdc.log import decode_cdc_records
    from pilosa_tpu.server.server import Server
    from pilosa_tpu.storage.bitmap import Bitmap, replay_ops

    n_writes = 400 if SMOKE else 4000
    tmp = tempfile.mkdtemp(prefix="bench-cdc-")
    out = {"writes": n_writes}
    s = Server(data_dir=tmp, cache_flush_interval=0,
               member_monitor_interval=0,
               cdc_config=CdcConfig(enabled=True, standing_interval=0))
    s.holder.open()
    try:
        idx = s.holder.create_index("cdc")
        idx.create_field("f")

        # ---- tail: lag, dense positions, byte-exact replay
        write_t = {1: time.perf_counter()}
        s.api.query("cdc", "Set(0, f=1)")
        frag = idx.fields["f"].views["standard"].fragments[0]
        last = n_writes + 1
        positions, lags = [], []
        bm = Bitmap()
        done = threading.Event()

        def consume():
            cur, inc = 0, None
            while positions[-1:] != [last]:
                data, cur, inc = s.cdc.stream("cdc", cur, inc, timeout=5)
                now = time.perf_counter()
                for rec, _ in decode_cdc_records(data):
                    positions.append(rec.position)
                    replay_ops(bm, rec.ops)
                    lags.append(now - write_t[rec.position])
            done.set()

        t = threading.Thread(target=consume)
        t.start()
        t0 = time.perf_counter()
        for i in range(n_writes):
            write_t[i + 2] = time.perf_counter()
            frag.set_bit(1, i + 1)
        write_s = time.perf_counter() - t0
        delivered = done.wait(timeout=120)
        t.join(timeout=10)
        lags.sort()
        pick = lambda q: round(  # noqa: E731
            lags[min(len(lags) - 1, int(len(lags) * q))] * 1e3, 3) \
            if lags else None
        out["tail"] = {
            "delivered": len(positions),
            "dense": positions == list(range(1, last + 1)),
            "bit_exact": delivered
            and bm.to_bytes() == frag.storage.to_bytes(),
            "lag_p50_ms": pick(0.50),
            "lag_p99_ms": pick(0.99),
            "writes_per_s": round(n_writes / write_s, 1) if write_s else 0.0,
        }

        # ---- pit: frozen-twin answers, cold vs LRU-warm materialization
        checkpoints = []
        for b in range(4):
            for i in range(25):
                s.api.query("cdc", f"Set({b * 25 + i}, f=2)")
            checkpoints.append((s.cdc.log("cdc").last_pos,
                                int(s.api.query("cdc",
                                                "Count(Row(f=2))")[0])))
        exact = True
        cold, warm = [], []
        for pos, frozen in checkpoints:
            q0 = time.perf_counter()
            got = int(s.api.query("cdc", "Count(Row(f=2))",
                                  at_position=pos)[0])
            cold.append(time.perf_counter() - q0)
            exact = exact and got == frozen
            q0 = time.perf_counter()
            again = int(s.api.query("cdc", "Count(Row(f=2))",
                                    at_position=pos)[0])
            warm.append(time.perf_counter() - q0)
            exact = exact and again == frozen
        pit = s.cdc.pit
        out["pit"] = {
            "bit_exact": exact,
            "checkpoints": len(checkpoints),
            "cold_ms_p50": round(sorted(cold)[len(cold) // 2] * 1e3, 3),
            "warm_ms_p50": round(sorted(warm)[len(warm) // 2] * 1e3, 3),
            "cache_hits": pit.hits, "cache_misses": pit.misses,
        }

        # ---- standing: re-push within one sweep, only on real change
        sq, _ = s.cdc.standing.register("cdc", "Count(Row(f=1))")
        s.cdc.standing.evaluate_once()  # prime the first result
        v0 = sq.version
        s.api.query("cdc", f"Set({n_writes + 10}, f=1)")
        q0 = time.perf_counter()
        s.cdc.standing.evaluate_once()
        sweep_ms = (time.perf_counter() - q0) * 1e3
        pushed = sq.version == v0 + 1
        s.api.query("cdc", "Set(11, f=3)")  # unrelated row, epoch bumps
        s.cdc.standing.evaluate_once()
        unrelated_push = sq.version != v0 + 1
        out["standing"] = {
            "pushed_on_change": pushed,
            "pushed_on_unrelated": unrelated_push,
            "sweep_ms": round(sweep_ms, 3),
            "evals": sq.evals, "pushes": sq.pushes, "stale": sq.stale,
        }
        out["cdc_ok"] = bool(
            out["tail"]["dense"] and out["tail"]["bit_exact"]
            and exact and pushed and not unrelated_push)
    finally:
        try:
            s.cdc.close()
            s.holder.close()
        except Exception:
            pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# --------------------------------------- device-plane degradation stanza


def bench_degrade():
    """Device-fault degraded ladder (docs/fault-tolerance.md, device
    section): one node serves Count queries while the device plane is
    scripted through healthy -> device-fault (every engine dispatch
    raises; the plane breaker opens and queries answer from the
    host/compressed-domain ladder) -> healed (half-open probe re-closes
    the breaker). Reports per-phase qps/p50/p99, correctness of the
    degraded phase (bit-exact vs healthy — the acceptance bar: a device
    fault is a performance event, not an availability event), an
    injected-OOM probe (backpressure + retry, no client error), and the
    recovery time from fault-clear to a re-closed breaker with queries
    proven back on the device path by the dispatch counter."""
    import shutil
    import socket
    import tempfile

    from pilosa_tpu import failpoints
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.errors import PilosaError
    from pilosa_tpu.server.client import ClientError, InternalClient
    from pilosa_tpu.server.server import Server

    n_rows, per_phase = (3, 8) if SMOKE else (6, 60)
    n_shards = 2 if SMOKE else 4

    def free_port():
        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="bench-degrade-")
    port = free_port()
    host = f"localhost:{port}"
    out = {"shards": n_shards, "rows": n_rows, "queries_per_phase": per_phase}
    # Memos off for the whole stanza: a memo hit dispatches nothing, so
    # the fault phase would never exercise the ladder (the engine reads
    # this env at lazy construction).
    old_memo = os.environ.get("PILOSA_MEMO_ENTRIES")
    os.environ["PILOSA_MEMO_ENTRIES"] = "0"
    server = None
    try:
        server = Server(
            data_dir=os.path.join(tmp, "node0"),
            port=port,
            cluster_hosts=[host],
            cache_flush_interval=0,
            anti_entropy_interval=0,
            member_monitor_interval=0,
            resilience_config=ResilienceConfig(
                device_breaker_failures=2, device_breaker_backoff=0.05,
                device_breaker_backoff_max=0.5, device_sig_backoff=0.05),
        )
        server.open()
        client = InternalClient(timeout=10.0)
        client.create_index(host, "dg")
        client.create_field(host, "dg", "f")
        for row in range(n_rows):
            for shard in range(n_shards):
                for k in range(4 + row):
                    client.query(
                        host, "dg",
                        f"Set({shard * SHARD_WIDTH + row * 31 + k * 7}, "
                        f"f={row})")

        def run_phase(n):
            lat, values = [], []
            ok = err = 0
            t0 = time.perf_counter()
            for i in range(n):
                q0 = time.perf_counter()
                try:
                    r = client.query(
                        host, "dg", f"Count(Row(f={i % n_rows}))")
                    values.append((i % n_rows, r["results"][0]))
                    ok += 1
                    lat.append(time.perf_counter() - q0)
                except (ClientError, PilosaError):
                    err += 1
            dt = time.perf_counter() - t0
            lat.sort()
            pick = (lambda q: round(
                lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 2
            )) if lat else (lambda q: None)
            return {"qps": round(ok / dt, 1) if dt else 0.0,
                    "p50_ms": pick(0.50), "p99_ms": pick(0.99),
                    "ok": ok, "errors": err}, dict(values)

        out["healthy"], baseline = run_phase(per_phase)

        # Device-fault phase: EVERY dispatch raises; after
        # device-breaker-failures the plane breaker opens and queries are
        # host-routed without touching the device at all.
        failpoints.configure("device-dispatch", "error")
        out["device_fault"], degraded = run_phase(per_phase)
        out["correct"] = bool(baseline) and degraded == baseline
        engine = server.executor._engine
        dp = engine.device_health.snapshot()
        out["fault_detail"] = {
            "plane_state": dp["plane_state"],
            "plane_opened": dp["plane_opened"],
            "host_counts": engine.counters["host_counts"],
            "dispatch_failures": dp["dispatch_failures"],
        }

        # OOM probe: one injected RESOURCE_EXHAUSTED must be absorbed by
        # backpressure (budget shrink + demote + retry), never a client
        # error. Run it healed so the dispatch actually happens.
        failpoints.reset()
        deadline = time.perf_counter() + 20.0
        while (time.perf_counter() < deadline
               and engine.device_health.plane_state() != "closed"):
            try:
                client.query(host, "dg", "Count(Row(f=0))")
            except (ClientError, PilosaError):
                pass
            time.sleep(0.02)
        failpoints.configure("device-dispatch", "oom", count=1)
        oom_phase, _ = run_phase(max(2, n_rows))
        out["oom"] = {
            "errors": oom_phase["errors"],
            "backpressure": engine.counters["oom_backpressure"],
            "retries": engine.counters["oom_retries"],
        }
        failpoints.reset()

        # Recovery: breaker re-closed AND dispatch counter climbing again
        # (the proof queries are back on the device, not the ladder).
        failpoints.configure("device-dispatch", "error", count=3)
        for i in range(4):
            try:
                client.query(host, "dg", f"Count(Row(f={i % n_rows}))")
            except (ClientError, PilosaError):
                pass
        failpoints.reset()
        t0 = time.perf_counter()
        recovered = False
        # Generous bound: smoke runs on loaded CI boxes, and the breaker
        # convergence itself is ~50ms — the window absorbs scheduler
        # stalls, not protocol time.
        deadline = t0 + 30.0
        while time.perf_counter() < deadline and not recovered:
            base_dispatch = engine.counters["count_dispatches"]
            try:
                for row in range(n_rows):
                    client.query(host, "dg", f"Count(Row(f={row}))")
            except (ClientError, PilosaError):
                time.sleep(0.02)
                continue
            recovered = (
                engine.device_health.plane_state() == "closed"
                and engine.counters["count_dispatches"] > base_dispatch
            )
            if not recovered:
                time.sleep(0.02)
        out["recovery_s"] = round(time.perf_counter() - t0, 3)
        out["recovered"] = recovered
        out["healed"], healed_vals = run_phase(per_phase)
        out["healed_correct"] = healed_vals == baseline
        out["degrade_ok"] = bool(
            out["correct"]
            and out["device_fault"]["errors"] == 0
            and out["oom"]["errors"] == 0
            and recovered
        )
    finally:
        failpoints.reset()
        if old_memo is None:
            os.environ.pop("PILOSA_MEMO_ENTRIES", None)
        else:
            os.environ["PILOSA_MEMO_ENTRIES"] = old_memo
        if server is not None:
            try:
                server.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# ---------------------------------------------------- rebalance stanza


def bench_rebalance():
    """Online elastic rebalance (docs/rebalance.md) vs the legacy
    stop-the-world resizeJob: a node joins a 2-node serving cluster with
    data while a reader and a writer keep hammering it. Reports read
    qps/p99 and write success DURING the migration for both modes, plus
    time-to-rebalance — the stop-the-world path flips the whole cluster
    to RESIZING (every API call rejected) while the online path keeps
    serving on per-shard routing epochs."""
    import shutil
    import socket
    import tempfile
    import threading

    from pilosa_tpu.cluster.hash import ModHasher
    from pilosa_tpu.cluster.rebalance import RebalanceConfig
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.errors import PilosaError
    from pilosa_tpu.server.client import ClientError, InternalClient
    from pilosa_tpu.server.server import Server

    n_shards = 2 if SMOKE else 4
    bits_per_shard = 2_000 if SMOKE else 50_000
    throttle = 0.0  # unthrottled: measure the natural migration window

    def free_port():
        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def run_mode(online: bool) -> dict:
        tmp = tempfile.mkdtemp(prefix="bench-rebalance-")
        ports = [free_port() for _ in range(3)]
        hosts = [f"localhost:{p}" for p in ports]
        cfg = RebalanceConfig(online=online, max_bytes_per_sec=throttle)
        servers = []
        try:
            for i in range(2):
                s = Server(
                    data_dir=os.path.join(tmp, f"node{i}"),
                    port=ports[i],
                    cluster_hosts=hosts[:2],
                    hasher=ModHasher(),
                    cache_flush_interval=0,
                    anti_entropy_interval=0,
                    member_monitor_interval=0,
                    rebalance_config=cfg,
                )
                s.open()
                servers.append(s)
            client = InternalClient(timeout=10.0)
            h0 = servers[0].node.uri
            client.create_index(h0, "rb")
            client.create_field(h0, "rb", "f")
            time.sleep(0.05)
            # Dense base injected directly (the base is scenery): real
            # migration bytes, not a toy handful of bits.
            rng = np.random.default_rng(11)
            for s in servers:
                for shard in range(n_shards):
                    frag = None
                    if any(n.id == s.node.id
                           for n in s.cluster.shard_nodes("rb", shard)):
                        fld = s.holder.field("rb", "f")
                        view = fld.create_view_if_not_exists("standard")
                        frag = view.create_fragment_if_not_exists(
                            shard, broadcast=False)
                    if frag is not None:
                        cols = rng.choice(SHARD_WIDTH, size=bits_per_shard,
                                          replace=False).astype(np.uint64)
                        frag.bulk_import(
                            np.ones(bits_per_shard, dtype=np.uint64), cols)
                    idx = s.holder.index("rb")
                    idx.set_remote_max_shard(n_shards - 1)

            stop = threading.Event()
            lat: list = []
            counters = {"read_ok": 0, "read_err": 0,
                        "write_ok": 0, "write_err": 0}
            rc = InternalClient(timeout=10.0)
            wc = InternalClient(timeout=10.0)

            def reader():
                while not stop.is_set():
                    q0 = time.perf_counter()
                    try:
                        rc.query(h0, "rb", "Count(Row(f=1))")
                        counters["read_ok"] += 1
                        lat.append(time.perf_counter() - q0)
                    except (ClientError, PilosaError):
                        counters["read_err"] += 1
                    time.sleep(0.001)

            def writer():
                col = 0
                while not stop.is_set():
                    target = (col % n_shards) * SHARD_WIDTH + (col % 1000)
                    try:
                        wc.query(h0, "rb", f"Set({target}, f=2)")
                        counters["write_ok"] += 1
                    except (ClientError, PilosaError):
                        counters["write_err"] += 1
                    col += 1
                    time.sleep(0.002)

            threads = [threading.Thread(target=reader, daemon=True),
                       threading.Thread(target=writer, daemon=True)]
            for t in threads:
                t.start()
            time.sleep(0.1)

            t0 = time.perf_counter()
            s2 = Server(
                data_dir=os.path.join(tmp, "node2"),
                port=ports[2], join_addr=h0, is_coordinator=False,
                hasher=ModHasher(), cache_flush_interval=0,
                anti_entropy_interval=0, member_monitor_interval=0,
                rebalance_config=cfg,
            )
            s2.open()
            servers.append(s2)
            deadline = time.time() + 120
            while time.time() < deadline:
                if (len(servers[0].cluster.nodes) == 3
                        and servers[0].cluster.state == "NORMAL"
                        and servers[0].cluster.next_nodes is None):
                    break
                time.sleep(0.01)
            dt = time.perf_counter() - t0
            stop.set()
            for t in threads:
                t.join(timeout=5)
            lat.sort()
            pick = (lambda q: round(
                lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 2
            )) if lat else (lambda q: None)
            return {
                "time_to_rebalance_s": round(dt, 3),
                "read_qps": round(counters["read_ok"] / dt, 1) if dt else 0.0,
                "read_p50_ms": pick(0.50), "read_p99_ms": pick(0.99),
                "read_errors": counters["read_err"],
                "write_ok": counters["write_ok"],
                "write_errors": counters["write_err"],
            }
        finally:
            for s in servers:
                try:
                    s.close()
                except Exception:
                    pass
            shutil.rmtree(tmp, ignore_errors=True)

    out = {"shards": n_shards, "bits_per_shard": bits_per_shard}
    out["online"] = run_mode(True)
    out["stop_the_world"] = run_mode(False)
    # The stanza's pass condition: the online path kept serving (reads
    # succeeded during the migration) and the job completed.
    out["rebalance_ok"] = bool(
        out["online"]["read_qps"] > 0
        and out["online"]["time_to_rebalance_s"] < 120
    )
    return out


# ------------------------------------------------------- ingest stanza


def bench_ingest():
    """WAL-amortized bulk imports (docs/ingest.md) vs the old
    snapshot-per-batch discipline, on a fragment with a realistic
    existing file: the old path rewrote the WHOLE file after every
    batch (O(fragment) per batch), the amortized path appends one bulk
    WAL record (O(batch)) and lets the background snapshotter rewrite
    by policy. Also reports read latency DURING ingest — reads are
    lock-free and snapshots run off-mutex, so p99 must stay flat."""
    import tempfile
    import threading

    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.storage import StorageConfig
    from pilosa_tpu.storage.bitmap import Container

    # Shape: a loaded production fragment — DENSE base containers (built
    # by direct injection, as bench_big does: the base is scenery, not
    # the thing measured) taking small column-local batches. This is the
    # regime where the old snapshot-per-batch discipline paid O(fragment
    # file) for every O(batch) of work.
    n_rows, n_batches = (32, 24) if SMOKE else (64, 64)
    per_batch = 250 if SMOKE else 2_000
    batch_rows = 8
    n_containers = SHARD_WIDTH >> 16
    out = {"rows": n_rows,
           "base_mib": round(n_rows * n_containers * 8192 / 2**20, 2),
           "bits_per_batch": per_batch, "batches": n_batches}
    results = {}
    for label in ("amortized", "snapshot_per_batch"):
        rng = np.random.default_rng(29)  # identical streams per mode
        with tempfile.TemporaryDirectory() as d:
            # fsync=never in BOTH modes: the stanza measures the
            # STRUCTURAL write-amplification contrast (one appended
            # record vs a whole-file rewrite per batch); the [storage]
            # fsync policy applies identically to both paths, and CI
            # filesystems' bimodal fsync latency (100ms+ under load)
            # otherwise swamps the thing being measured.
            holder = Holder(
                os.path.join(d, "indexes"),
                storage_config=StorageConfig(
                    snapshot_interval=0, fsync="never"),
            )
            holder.open()
            fld = holder.create_index("ing").create_field("f")
            view = fld.create_view_if_not_exists("standard")
            frag = view.create_fragment_if_not_exists(0, broadcast=False)
            words = rng.integers(
                0, 1 << 64, size=(n_rows * n_containers, 1024),
                dtype=np.uint64)
            counts = np.bitwise_count(words).sum(axis=1)
            for ci in range(n_rows * n_containers):
                frag.storage.containers[ci] = Container(
                    bits=words[ci], n=int(counts[ci]))
            for row in range(n_rows):
                frag.cache.bulk_add(row, int(
                    counts[row * n_containers:(row + 1) * n_containers].sum()))
            frag.cache.invalidate(force=True)
            frag.snapshot()

            lat = []
            stop = threading.Event()

            def reader():
                while not stop.is_set():
                    t0 = time.perf_counter()
                    frag.row_count(1)
                    lat.append(time.perf_counter() - t0)
                    time.sleep(0.001)

            rt = threading.Thread(target=reader, daemon=True)
            rt.start()
            # Batches have column locality (a sliding "recent columns"
            # window, the shape time-ordered ingest produces): cost is
            # the containers a batch TOUCHES, and the contrast under test
            # is O(touched) vs the old O(whole fragment file) per batch.
            # Per-batch times are reported as MEDIANS: fsync latency on CI
            # filesystems is bimodal, and totals whipsawed across runs.
            window = min(SHARD_WIDTH, 1 << 17)
            batch_s = []
            for i in range(n_batches):
                brows = np.repeat(
                    np.arange(batch_rows, dtype=np.uint64),
                    per_batch // batch_rows)
                bcols = (rng.integers(0, window, brows.size, dtype=np.uint64)
                         + np.uint64((i * window) % (SHARD_WIDTH - window + 1)))
                t0 = time.perf_counter()
                fld.import_bits(brows, bcols)
                if label == "snapshot_per_batch":
                    frag.snapshot()  # the pre-amortization discipline
                batch_s.append(time.perf_counter() - t0)
            stop.set()
            rt.join(timeout=5)
            snaps = dict(holder.ingest_stats())
            holder.close()
            lat.sort()
            batch_s.sort()
            med = batch_s[len(batch_s) // 2]
            pick = (lambda q: round(
                lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 3
            )) if lat else (lambda q: None)
            results[label] = {
                "batch_ms_p50": round(med * 1e3, 2),
                "batch_ms_p90": round(
                    batch_s[int(len(batch_s) * 0.9)] * 1e3, 2),
                "bits_per_s": round(per_batch / med, 0),
                "read_p50_ms": pick(0.50),
                "read_p99_ms": pick(0.99),
                "reads": len(lat),
            }
            if label == "amortized":
                results[label]["background_snapshots"] = snaps.get(
                    "snapshots_taken", 0)
    out.update(results)
    out["amortized_vs_snapshot"] = round(
        results["snapshot_per_batch"]["batch_ms_p50"]
        / max(results["amortized"]["batch_ms_p50"], 1e-9), 2)
    out["ingest_ok"] = out["amortized_vs_snapshot"] >= 5.0
    return out


# ------------------------------------------------------- import stanza


def bench_import():
    """Bulk-import + snapshot throughput (BASELINE.md rows: Fragment
    Import / Snapshot, reference fragment_internal_test.go:1146-1240).
    Random bits exercise the scatter/union path; contiguous bits must
    runify (run-form compression) instead of inflating host memory."""
    import tempfile

    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.storage.bitmap import _as_container

    rng = np.random.default_rng(21)
    out = {}
    with tempfile.TemporaryDirectory() as d:
        # Random scatter: n_rows x bits_per_row over the full shard width.
        n_rows, per_row = (8, 4000) if SMOKE else (64, 80_000)
        rows = np.repeat(np.arange(n_rows, dtype=np.uint64), per_row)
        cols = rng.integers(0, SHARD_WIDTH, rows.size, dtype=np.uint64)
        f = Fragment(os.path.join(d, "rand"), "i", "f", "standard", 0)
        f.open()
        t0 = time.perf_counter()
        f.bulk_import(rows, cols)
        dt = time.perf_counter() - t0
        out["random_mbits_per_s"] = round(rows.size / dt / 1e6, 2)
        t0 = time.perf_counter()
        f.snapshot()
        out["snapshot_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        out["random_file_mib"] = round(
            os.path.getsize(os.path.join(d, "rand")) / 2**20, 2)
        # Merkle block checksums (BASELINE.md row: Fragment Blocks scan,
        # reference fragment_internal_test.go:1020-1039) — cold then
        # cached (the anti-entropy sweep hits the cache).
        t0 = time.perf_counter()
        n_blocks = len(f.blocks())
        out["blocks_cold_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        t0 = time.perf_counter()
        f.blocks()
        out["blocks_cached_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        out["blocks_n"] = n_blocks
        f.close()

        # Contiguous: the adversarial-RLE shape; must land as runs.
        n_bits = n_rows * per_row
        rows2 = np.repeat(np.arange(8, dtype=np.uint64), n_bits // 8)
        cols2 = np.tile(np.arange(n_bits // 8, dtype=np.uint64), 8)
        f2 = Fragment(os.path.join(d, "contig"), "i", "f", "standard", 0)
        f2.open()
        t0 = time.perf_counter()
        f2.bulk_import(rows2, cols2)
        dt = time.perf_counter() - t0
        out["contig_mbits_per_s"] = round(rows2.size / dt / 1e6, 2)
        run_containers = sum(
            1 for c in f2.storage.containers.values()
            if _as_container(c).runs is not None
        )
        out["contig_run_containers"] = run_containers
        out["contig_file_kib"] = round(
            os.path.getsize(os.path.join(d, "contig")) / 1024, 1)
        f2.close()
    return out


# --------------------------------------------- north-star ladder stanzas


def _qps(fn, reps):
    """Warm once (compile + caches), then best-effort steady-state qps."""
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return reps / (time.perf_counter() - t0)


def bench_topn_bsi():
    """BASELINE.md north-star config 3: TopN with ranked cache + BSI
    Sum/Min/Max under a bitmap filter, device batched paths vs the host
    per-fragment numpy path (frag.sum/min/max + cache-candidate top — the
    same per-shard loop shape the reference runs per goroutine)."""
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.fragment import TopOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.pql.parser import parse

    n_shards, n_rows = (2, 32) if SMOKE else (8, 256)
    bits_per_row_shard = 512 if SMOKE else 4096
    vals_per_shard = 2048 if SMOKE else 65536
    rng = np.random.default_rng(5)

    holder = Holder(None)
    holder.open()
    idx = holder.create_index("ns3")
    fld = idx.create_field("f")
    vfld = idx.create_field("v", FieldOptions(type="int", min=0, max=100000))
    rows, cols = [], []
    for row in range(n_rows):
        for shard in range(n_shards):
            c = rng.choice(SHARD_WIDTH, size=bits_per_row_shard, replace=False)
            rows.append(np.full(bits_per_row_shard, row, dtype=np.uint64))
            cols.append(c.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH))
    fld.import_bits(np.concatenate(rows), np.concatenate(cols))
    for shard in range(n_shards):
        c = rng.choice(SHARD_WIDTH, size=vals_per_shard, replace=False)
        vals = rng.integers(0, 100000, vals_per_shard)
        vfld.import_value(
            c.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH),
            vals.astype(np.uint64),
        )
    ex = Executor(holder, workers=0)
    shards = list(range(n_shards))
    out = {"shards": n_shards, "rows": n_rows,
           "bsi_cols": n_shards * vals_per_shard}

    # --- TopN with ranked cache + src filter (device batched phase-1+2).
    # Distinct src rows per timed call: identical repeats are answered by
    # the composite-result memo (host dict work, no device) and would
    # measure the memo, not the TopN path.
    q_topn = "TopN(f, Row(f=3), n=10)"
    device_topn = ex.execute("ns3", q_topn)[0]
    cyc = {"i": 0}

    def next_topn():
        cyc["i"] += 1
        return ex.execute("ns3", f"TopN(f, Row(f={3 + cyc['i'] % 16}), n=10)")

    out["topn_qps_device"] = round(_qps(next_topn, 2 if SMOKE else 8), 2)

    # Host: per-fragment candidate top with numpy popcount intersections
    # (cache candidates -> plane AND+popcount per shard).
    bsig = vfld.bsi_group("v")
    depth = bsig.bit_depth()

    def host_topn():
        from pilosa_tpu.core.cache import Pair, add_pairs, sort_pairs

        pairs = []
        for s in shards:
            frag = holder.fragment("ns3", "f", "standard", s)
            src_plane = frag.plane_np(3)
            cands = frag.top_candidates(TopOptions(n=10))
            counts = {}
            for r, _ in cands:
                plane = frag.plane_np(r)
                counts[r] = int(
                    np.bitwise_count(np.bitwise_and(plane, src_plane)).sum()
                )
            pairs = add_pairs(pairs, frag.top(
                TopOptions(n=10), inter_counts=counts))
        return sort_pairs(pairs)[:10]

    host_pairs = host_topn()
    assert [(p.id, p.count) for p in host_pairs] == \
        [(p.id, p.count) for p in device_topn[:10]], "topn host/device diverge"
    out["topn_qps_host"] = round(_qps(host_topn, 2 if SMOKE else 4), 2)
    out["topn_vs_host"] = round(out["topn_qps_device"] / out["topn_qps_host"], 2)

    # --- BSI Sum/Min/Max under a Row filter (device: one batched program
    # over all shards; host: per-fragment frag.sum/min/max numpy loop).
    for kind, q in (("sum", "Sum(Row(f=3), field=v)"),
                    ("min", "Min(Row(f=3), field=v)"),
                    ("max", "Max(Row(f=3), field=v)")):
        device_val = ex.execute("ns3", q)[0]
        kcyc = {"i": 0}

        def next_val(kind=kind, kcyc=kcyc):
            kcyc["i"] += 1
            kname = kind.capitalize()
            return ex.execute(
                "ns3", f"{kname}(Row(f={3 + kcyc['i'] % 16}), field=v)")

        out[f"{kind}_qps_device"] = round(_qps(next_val, 2 if SMOKE else 8), 2)

        filter_call = parse("Row(f=3)").calls[0]

        def host_val(kind=kind):
            total_sum = total_cnt = 0
            best = None
            for s in shards:
                frag = holder.fragment("ns3", "v", "bsig_v", s)
                if frag is None:
                    continue
                f_frag = holder.fragment("ns3", "f", "standard", s)
                filter_row = f_frag.row(3)
                if kind == "sum":
                    vsum, vcount = frag.sum(filter_row, depth)
                    total_sum += vsum
                    total_cnt += vcount
                elif kind == "min":
                    v, cnt = frag.min(filter_row, depth)
                    if cnt and (best is None or v < best):
                        best = v
                else:
                    v, cnt = frag.max(filter_row, depth)
                    if cnt and (best is None or v > best):
                        best = v
            return (total_sum, total_cnt) if kind == "sum" else best

        host_result = host_val()
        if kind == "sum":
            assert host_result[0] + host_result[1] * bsig.min == device_val.val
        out[f"{kind}_qps_host"] = round(_qps(host_val, 2 if SMOKE else 4), 2)
        out[f"{kind}_vs_host"] = round(
            out[f"{kind}_qps_device"] / out[f"{kind}_qps_host"], 2)
    holder.close()
    return out


def bench_time_range():
    """BASELINE.md north-star config 4: time-quantum Range (union of YMD
    views) feeding a row-attribute-filtered TopN, vs the host per-view
    numpy union."""
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.executor import Executor

    n_shards, n_rows, n_days = (2, 8, 10) if SMOKE else (4, 32, 30)
    bits_per_day = 64 if SMOKE else 512
    rng = np.random.default_rng(13)
    holder = Holder(None)
    holder.open()
    idx = holder.create_index("ns4")
    tfld = idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
    from pilosa_tpu.timeq import parse_timestamp

    rows, cols, stamps = [], [], []
    for row in range(n_rows):
        for day in range(n_days):
            ts = parse_timestamp(f"2018-01-{day % 28 + 1:02d}T00:00")
            for shard in range(n_shards):
                c = rng.choice(SHARD_WIDTH, size=bits_per_day, replace=False)
                rows.append(np.full(bits_per_day, row, dtype=np.uint64))
                cols.append(c.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH))
                stamps.extend([ts] * bits_per_day)
    tfld.import_bits(np.concatenate(rows), np.concatenate(cols), stamps)
    for row in range(n_rows):
        tfld.row_attr_store.set_attrs(
            row, {"team": "a" if row % 2 == 0 else "b"})
    ex = Executor(holder, workers=0)
    out = {"shards": n_shards, "rows": n_rows, "days": n_days}

    q_range = "Count(Range(t=3, 2018-01-05T00:00, 2018-01-15T00:00))"
    device_count = ex.execute("ns4", q_range)[0]

    # Distinct windows per timed call: a repeated identical Count is
    # answered by the host result memo (a dict hit, no device work), which
    # would measure the memo, not the range path.
    windows = [
        f"Count(Range(t=3, 2018-01-{d:02d}T00:00, 2018-01-{d+10:02d}T00:00))"
        for d in range(2, 18)
    ]
    state = {"i": 0}

    def next_window():
        q = windows[state["i"] % len(windows)]
        state["i"] += 1
        return ex.execute("ns4", q)

    out["range_count_qps_device"] = round(_qps(next_window, 2 if SMOKE else 8), 2)

    # Host: numpy OR of the day-view planes, popcounted.
    from pilosa_tpu.timeq import views_by_time_range

    def host_range():
        t1 = parse_timestamp("2018-01-05T00:00")
        t2 = parse_timestamp("2018-01-15T00:00")
        total = 0
        for s in range(n_shards):
            acc = None
            for view in views_by_time_range("standard", t1, t2, "YMD"):
                frag = holder.fragment("ns4", "t", view, s)
                if frag is None:
                    continue
                plane = frag.plane_np(3)
                acc = plane if acc is None else np.bitwise_or(acc, plane)
            if acc is not None:
                total += int(np.bitwise_count(acc).sum())
        return total

    assert host_range() == device_count, "range host/device diverge"
    out["range_count_qps_host"] = round(_qps(host_range, 2 if SMOKE else 4), 2)
    out["range_vs_host"] = round(
        out["range_count_qps_device"] / out["range_count_qps_host"], 2)

    # Row-attribute-filtered TopN over the standard view (the docs'
    # segmentation pattern: TopN(t, attrName=..., attrValues=[...])).
    q_topn = 'TopN(t, n=8, attrName="team", attrValues=["a"])'
    pairs = ex.execute("ns4", q_topn)[0]
    assert pairs and all(p.id % 2 == 0 for p in pairs)
    out["attr_topn_qps_device"] = round(
        _qps(lambda: ex.execute("ns4", q_topn), 2 if SMOKE else 8), 2)
    holder.close()
    return out


# ------------------------------------------------------- open-time stanza


def bench_open():
    """Fragment open cost on a sizable on-disk file: the shipped lazy mmap
    parse (Bitmap.from_buffer copy=False; open is O(container headers))
    vs the eager full parse it replaced (every payload copied at open)."""
    import tempfile

    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.storage.bitmap import Bitmap

    rng = np.random.default_rng(3)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "frag.0")
        f = Fragment(path, "i", "f", "standard", 0)
        f.open()
        # dense bitset containers
        n_rows, bits_per_row = (8, 20_000) if SMOKE else (64, 160_000)
        rows = np.repeat(np.arange(n_rows, dtype=np.uint64), bits_per_row)
        cols = rng.integers(0, SHARD_WIDTH, rows.size, dtype=np.uint64)
        f.bulk_import(rows, cols)
        f.close()
        size_mib = os.path.getsize(path) / 2**20

        t0 = time.perf_counter()
        f2 = Fragment(path, "i", "f", "standard", 0)
        f2.open()
        lazy_ms = (time.perf_counter() - t0) * 1e3
        # Prove the lazy open still serves reads.
        count = f2.row_count(1)
        f2.close()
        assert count > 0

        with open(path, "rb") as fh:
            data = fh.read()
        t0 = time.perf_counter()
        Bitmap.from_bytes(data)
        eager_ms = (time.perf_counter() - t0) * 1e3
    return {
        "file_mib": round(size_mib, 1),
        "lazy_open_ms": round(lazy_ms, 2),
        "eager_parse_ms": round(eager_ms, 2),
        "speedup": round(eager_ms / max(lazy_ms, 1e-6), 1),
    }


# --------------------------------------------- tiered plane storage stanza


def bench_tier():
    """Tiered eviction vs drop-and-regather under HBM pressure
    (docs/tiered-storage.md): the working set is ~3x the leaf-cache
    budget, so every sweep over the planes evicts. With the tier manager
    on, an eviction demotes the plane container-compressed into host RAM
    and the next touch decodes it back (one streaming pass) instead of
    re-walking every shard's live containers — the qps gap between the
    two modes is the price of drop-and-regather.

    Reports per-mode qps/p50/p99 plus promotion/demotion counts, asserts
    zero full regathers after the warm-up sweep in tiered mode (every
    re-touch must be an HBM hit or a tier promotion), and proves writes
    that stay within the delta bound fold on promotion instead of forcing
    a regather."""
    from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_ROW
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.parallel import EngineConfig
    from pilosa_tpu.parallel.engine import ShardedQueryEngine
    from pilosa_tpu.pql.parser import parse
    from pilosa_tpu.tier import TierConfig

    n_rows, n_shards, per_row, sweeps, batch = (
        (18, 2, 512, 4, 6) if SMOKE else (96, 4, 4096, 3, 8))
    plane_bytes = n_shards * WORDS_PER_ROW * 4
    budget = n_rows * plane_bytes // 3  # working set ~3x the HBM budget

    holder = Holder(None)
    holder.open()
    idx = holder.create_index("tier")
    fld = idx.create_field("f")
    rng = np.random.default_rng(17)
    rows, cols = [], []
    for row in range(n_rows):
        for shard in range(n_shards):
            c = rng.choice(SHARD_WIDTH, size=per_row, replace=False)
            rows.append(np.full(per_row, row, dtype=np.uint64))
            cols.append(c.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH))
    fld.import_bits(np.concatenate(rows), np.concatenate(cols))

    shards = list(range(n_shards))
    calls = {r: parse(f"Row(f={r})").calls[0] for r in range(n_rows)}

    out = {
        "planes": n_rows,
        "plane_mib": round(plane_bytes / 2**20, 2),
        "budget_mib": round(budget / 2**20, 2),
    }

    def run_mode(tier_on: bool):
        # Prefetch off during the measured sweeps: both modes pay their
        # misses on the query path, so the comparison isolates what a
        # miss COSTS (the prefetcher's job of hiding misses entirely is
        # measured separately below).
        tc = TierConfig(
            host_bytes=(1 << 30) if tier_on else 0, disk_bytes=0,
            prefetch_interval=0)
        # Memos off (env wins over config): a repeat count is answered
        # host-side by the result memo with zero gathers, which is a
        # different serving path (measured in the SCALE stanza) — this
        # stanza measures what a leaf-cache MISS costs under pressure.
        old_memo = os.environ.get("PILOSA_MEMO_ENTRIES")
        os.environ["PILOSA_MEMO_ENTRIES"] = "0"
        try:
            engine = ShardedQueryEngine(
                holder,
                config=EngineConfig(leaf_cache_bytes=budget,
                                    stack_cache_bytes=budget),
                tier_config=tc)
        finally:
            if old_memo is None:
                os.environ.pop("PILOSA_MEMO_ENTRIES", None)
            else:
                os.environ["PILOSA_MEMO_ENTRIES"] = old_memo
        # Batched counts (the engine's serving bread and butter): B rows
        # per dispatch, so per-query host assembly — the cost the tier
        # changes — is what the comparison measures, not the fixed
        # dispatch/transfer tax both modes pay identically.
        def sweep_groups(s):
            # Rotate the batch composition per sweep: same planes, fresh
            # batch/stack/memo keys, so every sweep pays real gathers
            # (a repeated identical batch is answered by the host result
            # memo — a different serving path than the one under test).
            rot = [(r + s) % n_rows for r in range(n_rows)]
            return [rot[g : g + batch] for g in range(0, n_rows, batch)]

        mode = {}
        try:
            # Warm-up sweep: every plane gathered cold once; the budget
            # forces ~2/3 of them out (demoted or dropped).
            for grp in sweep_groups(sweeps):
                np.asarray(engine.count_batch(
                    "tier", [calls[r] for r in grp], shards))
            if tier_on:
                engine.tier.drain()
            base = dict(engine.counters)
            lat = []
            t0 = time.perf_counter()
            for s in range(sweeps):
                for grp in sweep_groups(s):
                    t1 = time.perf_counter()
                    np.asarray(engine.count_batch(
                        "tier", [calls[r] for r in grp], shards))
                    lat.append(time.perf_counter() - t1)
                if tier_on:
                    # Settle the demote queue between sweeps (inside the
                    # measured window: the worker's serialization is part
                    # of the tier's total cost) so the zero-full-regather
                    # assertion is deterministic, not a race.
                    engine.tier.drain()
            dt = time.perf_counter() - t0
            lat.sort()
            mode["qps"] = round(len(lat) * batch / dt, 1)
            mode["p50_ms"] = round(lat[len(lat) // 2] * 1e3, 2)
            mode["p99_ms"] = round(lat[int(len(lat) * 0.99)] * 1e3, 2)
            mode["hbm_hits"] = engine.counters["leaf_hits"] - base["leaf_hits"]
            mode["full_regathers"] = (
                engine.counters["leaf_misses"] - base["leaf_misses"])
            if tier_on:
                mode["tier_promotions"] = (
                    engine.counters["leaf_tier_hits"]
                    - base["leaf_tier_hits"])
                snap = engine.tier.snapshot()
                mode["demotions"] = snap["demotions_host"]
                mode["host_mib"] = round(snap["host_bytes"] / 2**20, 3)
                mode["compression_x"] = round(
                    snap["host_entries"] * plane_bytes
                    / max(snap["host_bytes"], 1), 1)
                # Delta-fold proof: a small write to every currently
                # demoted plane, then re-touch — the journal folds at
                # promotion time, so STILL zero full regathers.
                writes = 0
                pre = dict(engine.counters)
                for wr in range(0, n_rows, 7):
                    fld.set_bit(wr, wr * 31 % SHARD_WIDTH)
                    writes += 1
                engine.tier.drain()
                for r in range(n_rows):
                    np.asarray(engine.count_async("tier", calls[r], shards))
                mode["writes_folded"] = writes
                mode["post_write_full_regathers"] = (
                    engine.counters["leaf_misses"] - pre["leaf_misses"])
                mode["delta_folds"] = engine.tier.snapshot()["delta_folds"]
        finally:
            engine.close()
        return mode

    out["tiered"] = run_mode(True)
    out["drop_regather"] = run_mode(False)
    out["qps_ratio"] = round(
        out["tiered"]["qps"] / max(out["drop_regather"]["qps"], 1e-9), 2)

    # Predictive prefetch: a roomy engine (the whole working set fits)
    # whose planes all start DEMOTED — the traffic signal marks the index
    # hot, and the prefetcher promotes into free headroom before any
    # query touches a plane, so the serving sweep afterwards must see
    # zero query-path promotions or regathers for the prefetched keys.
    from pilosa_tpu.parallel.engine import Leaf

    tc = TierConfig(host_bytes=1 << 30, disk_bytes=0,
                    prefetch_interval=0.02, prefetch_batch=16)
    traffic = {"n": 1}
    engine = ShardedQueryEngine(
        holder, config=EngineConfig(leaf_cache_bytes=4 * n_rows * plane_bytes),
        tier_config=tc, traffic_fn=lambda: {"tier": traffic["n"]})
    try:
        for r in range(n_rows):
            engine.tier.demote(("tier", Leaf("f", "standard", r),
                               tuple(shards)))
        engine.tier.drain()
        deadline = time.time() + (10 if SMOKE else 30)
        while time.time() < deadline:
            traffic["n"] += 1  # the index stays "hot" every sweep
            if engine.tier.snapshot()["prefetch_promotions"] >= n_rows:
                break
            time.sleep(0.02)
        snap = engine.tier.snapshot()
        base = dict(engine.counters)
        t0 = time.perf_counter()
        for r in range(n_rows):
            np.asarray(engine.count_async("tier", calls[r], shards))
        dt = time.perf_counter() - t0
        out["prefetch"] = {
            "promotions": snap["prefetch_promotions"],
            "serving_qps": round(n_rows / dt, 1),
            "query_path_promotions": (
                engine.counters["leaf_tier_hits"] - base["leaf_tier_hits"]),
            "query_path_regathers": (
                engine.counters["leaf_misses"] - base["leaf_misses"]),
            "hits": engine.counters["leaf_hits"] - base["leaf_hits"],
        }
    finally:
        engine.close()
    holder.close()
    return out


def bench_compile():
    """Query-plan compiler (docs/query-compiler.md): whole PQL trees
    lowered into ONE fused, batched device program vs the reference
    per-op/per-shard dispatch walk — the ROADMAP item 2 acceptance
    metric. The pool holds deep trees in several commutative/associative
    respellings, so the canonical plan maps every respelling onto one
    compiled program and one memo space; the per-op path re-walks each
    spelling op by op, shard by shard. Also asserts compiled results
    bit-exact against the host ladder, including a seed-pinned chaos leg
    where the fused program's SIGNATURE breaker opens mid-run
    (device-sig-failures=1, one injected dispatch error) and the ladder
    keeps serving the same answers."""
    from pilosa_tpu import failpoints
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.plan import snapshot as plan_snapshot
    from pilosa_tpu.pql.parser import parse

    n_shards = 2 if SMOKE else 8
    n_rows = 8 if SMOKE else 64
    density = float(os.environ.get("BENCH_DENSITY", "0.02"))
    holder, ex = build(n_shards, n_rows, density)
    shards = list(range(n_shards))
    out = {"shards": n_shards, "rows": n_rows}
    # Read NOW, restored in the outer finally; the dispatch-floor leg
    # below overrides it (engines read the env at lazy construction).
    old_memo = os.environ.get("PILOSA_MEMO_ENTRIES")
    # Seed-pinned: the chaos leg below replays the identical workload.
    rng = np.random.default_rng(1103)

    pool = []
    for _ in range(8):
        a, b, c, d = (int(x) for x in
                      rng.choice(n_rows, size=4, replace=False))
        pool.append((
            f"Count(Intersect(Union(Row(f={a}), Row(f={b})), "
            f"Row(f={c}), Row(f={d})))",
            f"Count(Intersect(Row(f={d}), Union(Row(f={b}), Row(f={a})), "
            f"Row(f={c})))",
            f"Count(Intersect(Intersect(Row(f={c}), Row(f={d})), "
            f"Union(Row(f={a}), Row(f={b}))))",
        ))
    queries = [q for group in pool for q in group]
    child_trees = [parse(q).calls[0].children[0] for q in queries]

    plan0 = plan_snapshot()
    eng0 = ex.engine.snapshot()

    def run_fused():
        return [int(ex.execute("bench", q)[0]) for q in queries]

    def run_per_op():
        # The reference walk the compiler replaces: one dispatch per op
        # per shard, merged pairwise on the host.
        res = []
        for t in child_trees:
            total = 0
            for s in shards:
                total += ex._execute_bitmap_call_shard("bench", t, s).count()
            res.append(total)
        return res

    try:
        fused0 = run_fused()  # warmup: compiles the canonical program(s)
        per0 = run_per_op()
        host = [ex.engine.host_count("bench", t, shards)
                for t in child_trees]
        out["bit_exact"] = fused0 == per0 == host

        def timed(fn):
            done = 0
            t0 = time.perf_counter()
            while (done < _LOOP_MIN * len(queries)
                   or time.perf_counter() - t0 < _LOOP_SECS):
                fn()
                done += len(queries)
            return round(done / (time.perf_counter() - t0), 1)

        # Headline: the PRODUCTION fused path, memo on. The canonical-
        # signature result memo is part of what the compiler buys (all
        # respellings share one entry — per-op dispatch structurally has
        # no equivalent), so the serving-shape ratio includes it.
        out["fused_qps"] = timed(run_fused)
        out["per_op_qps"] = timed(run_per_op)
        out["fused_vs_per_op"] = round(
            out["fused_qps"] / max(out["per_op_qps"], 1e-9), 2)
        plan1 = plan_snapshot()
        eng1 = ex.engine.snapshot()
        out["plan"] = {k: plan1[k] - plan0.get(k, 0) for k in plan1}
        # All 24 respellings canonicalize onto ONE signature, so the
        # compiled-program cache builds once and hits thereafter.
        out["fn_cache_builds"] = (eng1["fn_cache_builds"]
                                  - eng0.get("fn_cache_builds", 0))

        # ---- dispatch floor, memo OFF: a regression that makes the
        # lowered program itself slower could hide behind memo hits in
        # the headline ratio, so ALSO measure the raw per-query fused
        # dispatch (every query a real compiled-program launch) and gate
        # it against per-op as a floor. The engine reads the env at lazy
        # construction, hence a fresh executor; the chaos executor below
        # rides the same override (a memo hit dispatches nothing and
        # would starve the breaker of evidence).
        os.environ["PILOSA_MEMO_ENTRIES"] = "0"
        ex_nm = Executor(holder)
        try:
            nm = [int(ex_nm.execute("bench", q)[0]) for q in queries]
            assert nm == fused0  # warmup, and the dispatch path agrees
            out["fused_dispatch_qps"] = timed(
                lambda: [ex_nm.execute("bench", q) for q in queries])
            out["dispatch_vs_per_op"] = round(
                out["fused_dispatch_qps"] / max(out["per_op_qps"], 1e-9), 2)
        finally:
            ex_nm.close()

        # ---- chaos leg: signature breaker opens MID-RUN, ladder serves
        # the same answers. Fresh executor so the sig-breaker config is
        # in place before ITS engine lazily constructs.
        ex2 = Executor(holder)
        try:
            ex2.cluster.health.configure(ResilienceConfig(
                device_sig_failures=1, device_sig_backoff=60.0).validate())
            baseline = [int(ex2.execute("bench", q)[0]) for q in queries]
            failpoints.configure("device-dispatch", "error", count=1)
            chaos = [int(ex2.execute("bench", q)[0]) for q in queries]
            dh = ex2.engine.device_health.snapshot()
            out["chaos"] = {
                "bit_exact": chaos == baseline == fused0,
                "sig_quarantined": dh.get("sig_quarantined", 0),
            }
        finally:
            failpoints.reset()
            ex2.close()
    finally:
        if old_memo is None:
            os.environ.pop("PILOSA_MEMO_ENTRIES", None)
        else:
            os.environ["PILOSA_MEMO_ENTRIES"] = old_memo
        ex.close()
        holder.close()
    return out


# ------------------------------------------- multi-chip collective stanza

_MULTICHIP_CHILD = r'''
import json, os, re, sys, threading, time

# The collective plane's acceptance mesh is 8 CPU devices (MULTICHIP_r05
# dry-run shape): replace any inherited device-count flag — duplicates
# are ambiguous.
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
               os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Memos off on BOTH paths: the comparison is the steady-state DISPATCH
# cost (resident-stack fused collective vs per-node fan-out), and a memo
# hit dispatches nothing (same rationale as the DEGRADE/COMPILE stanzas).
os.environ["PILOSA_MEMO_ENTRIES"] = "0"

import numpy as np

from pilosa_tpu import failpoints
from pilosa_tpu.cluster.hash import ModHasher
from pilosa_tpu.cluster.health import ResilienceConfig
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.parallel import CollectiveConfig, EngineConfig
from pilosa_tpu.sched import SchedulerConfig
from pilosa_tpu.server.client import InternalClient
from pilosa_tpu.server.server import Server

# Per-node engines pinned to ONE device: concurrent sharded programs
# whose reductions lower to cross-device all-reduces can interleave
# their rendezvous on the multi-device CPU backend and deadlock
# (observed here as two stuck 8-way rendezvous holding every device
# thread hostage). With mesh-devices=1 per-node programs carry no
# collectives at all; ONLY the collective plane — whose entries the
# runner serializes — uses the 8-device mesh. This is also the fan-out
# side's fastest CPU configuration (no pointless 8-way reduce of
# 2-shard data), so the comparison is against its best self.
ENGINE_ONE_DEVICE = EngineConfig(mesh_devices=1)

import socket
import tempfile


def free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


n_shards = int(sys.argv[1])
n_rows = int(sys.argv[2])
clients = int(sys.argv[3])
per_client = int(sys.argv[4])

tmp = tempfile.mkdtemp(prefix="bench-multichip-")
out = {"shards": n_shards, "rows": n_rows, "clients": clients,
       "queries_per_client": per_client}

# Deterministic data, identical on both clusters.
rng = np.random.default_rng(12)
rows_cols = {}
for row in range(n_rows):
    cols = []
    for s in range(n_shards):
        local = sorted(int(c) for c in rng.choice(2048, size=24, replace=False))
        cols.extend(s * SHARD_WIDTH + c for c in local)
    rows_cols[row] = set(cols)

pairs = [(a, b) for a in range(n_rows) for b in range(n_rows) if a != b]
queries = [f"Count(Intersect(Row(f={a}), Row(f={b})))" for a, b in pairs]
expected = [len(rows_cols[a] & rows_cols[b]) for a, b in pairs]

# Generous per-request timeout: the smoke child shares a loaded box
# with the rest of the tier-1 suite (a 15s timeout flaked there), and
# compile-heavy warmup happens via DIRECT executor/backend calls below
# so no HTTP request ever waits on a first-touch jit compile.
client = InternalClient(timeout=120.0)


def import_data(host):
    client.create_index(host, "mc")
    client.create_field(host, "mc", "f")
    for row, cols in rows_cols.items():
        # One batched import per row rides the normal cluster write path
        # (jump-hash placement on the fan-out cluster).
        client.import_bits(host, "mc", "f", [(row, c) for c in sorted(cols)])


def run_concurrent(host, qs):
    """C client threads, each issuing its slice of `qs`; returns
    (qps, answers-in-order, errors)."""
    answers = [None] * len(qs)
    errors = [0]
    lock = threading.Lock()
    idx = [0]

    def worker():
        while True:
            with lock:
                i = idx[0]
                if i >= len(qs):
                    return
                idx[0] += 1
            try:
                got = client.query(host, "mc", qs[i])
                answers[i] = int(got["results"][0])
            except Exception:
                with lock:
                    errors[0] += 1

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    return round(len(qs) / dt, 1), answers, errors[0]


workload = [queries[i % len(queries)] for i in range(clients * per_client)]
want = [expected[i % len(queries)] for i in range(clients * per_client)]

# ---- HTTP fan-out cluster: 2 nodes, shards split by placement, the
# reference-style scatter-gather path the collective plane replaces.
ports = [free_port(), free_port()]
hosts = [f"localhost:{p}" for p in ports]
fan_servers = []
for i, port in enumerate(ports):
    s = Server(
        data_dir=os.path.join(tmp, f"fan{i}"), port=port,
        cluster_hosts=hosts, replica_n=1, hasher=ModHasher(),
        cache_flush_interval=0, anti_entropy_interval=0,
        member_monitor_interval=0,
        engine_config=ENGINE_ONE_DEVICE,
    )
    s.open()
    fan_servers.append(s)
import_data(hosts[0])
# Remote shards must exist, or "fan-out" measures a single node.
head = fan_servers[0]
remote_shards = [s for s in range(n_shards)
                 if all(n.id != head.node.id
                        for n in head.cluster.shard_nodes("mc", s))]
out["fanout_remote_shards"] = len(remote_shards)

# Warmup + correctness reference. Compiles happen via direct executor
# calls first (each node's engine), socket-free; the HTTP loop then
# establishes the reference answers without first-touch compile stalls.
from pilosa_tpu.pql.parser import parse
for s in fan_servers:
    for q in queries:
        s.executor.execute("mc", q)
fan_answers = [int(client.query(hosts[0], "mc", q)["results"][0])
               for q in queries]
_, wa, werr = run_concurrent(hosts[0], workload[: clients * 2])
fan_qps, fan_conc, fan_err = run_concurrent(hosts[0], workload)
out["fanout"] = {"qps": fan_qps, "errors": fan_err}

# ---- collective pod: one process, one node, all shards local, the
# 8-device mesh serving whole-index Counts as ONE fused SPMD program per
# micro-batch (resident sharded stacks + batched launches).
pod_port = free_port()
pod_host = f"localhost:{pod_port}"
pod = Server(
    data_dir=os.path.join(tmp, "pod"), port=pod_port,
    cluster_hosts=[pod_host], replica_n=1,
    cache_flush_interval=0, anti_entropy_interval=0,
    member_monitor_interval=0,
    # The pod's PER-NODE engine (the chaos leg's fallback rung) is also
    # one-device; the collective plane's global mesh stays 8-wide.
    engine_config=ENGINE_ONE_DEVICE,
    collective_config=CollectiveConfig(single_process=1),
    resilience_config=ResilienceConfig(
        collective_breaker_failures=2, collective_breaker_backoff=0.2,
        collective_breaker_backoff_max=1.0),
    scheduler_config=SchedulerConfig(batch_max=8),
)
pod.open()
import_data(pod_host)
assert pod.collective.active(), "collective plane inactive on the pod"

# Warm every compiled shape DIRECTLY (no sockets): each unique query's
# resident leaves + the pow2 batch programs (1/2/4) the micro-batcher
# can launch, plus the fan-out fallback path the chaos leg will take.
calls = [parse(q).calls[0].children[0] for q in queries]
for c in calls:
    pod.collective.count("mc", c)
for n in (2, 4, 8):
    pod.collective.count_batch("mc", (calls * 2)[:n])
pod.executor.engine.count("mc", calls[0], list(range(n_shards)))
coll_answers = [int(client.query(pod_host, "mc", q)["results"][0])
                for q in queries]
_, _, _ = run_concurrent(pod_host, workload[: clients * 2])

coll_qps, coll_conc, coll_err = run_concurrent(pod_host, workload)
snap = pod.collective.snapshot()
out["collective"] = {
    "qps": coll_qps, "errors": coll_err,
    "served_count": snap["served_count"],
    "batched_entries": snap["batched_entries"],
    "batched_launches": snap["batched_launches"],
    "resident_hits": snap["resident_hits"],
    "full_refreshes": snap["full_refreshes"],
    "fallbacks": snap["fallbacks"],
}
out["collective_vs_fanout"] = round(coll_qps / max(fan_qps, 1e-9), 2)
# Bit-exactness NEVER retried: both paths must equal the host-computed
# reference, warm and under concurrency.
out["bit_exact"] = bool(
    fan_answers == expected == coll_answers
    and fan_conc == want and coll_conc == want
    and fan_err == 0 and coll_err == 0)
# The fast path must actually have served (a silent fallback would make
# the ratio meaningless).
out["collective_served"] = snap["served_count"] > len(queries)

# ---- per-device-count scaling curve: the SAME fused collective count
# program over meshes of 1/2/4/8 devices (direct backend loop — no HTTP,
# so the curve isolates the SPMD program itself).
import jax
curve = {}
loops = max(per_client, 8)
for d in (1, 2, 4, 8):
    if d > len(jax.devices()):
        continue
    pod.collective.mesh_devices = d
    q = calls[0]
    assert pod.collective.count("mc", q) == expected[0]  # warm + verify
    t0 = time.perf_counter()
    for _ in range(loops):
        pod.collective.count("mc", q)
    curve[str(d)] = round(loops / (time.perf_counter() - t0), 1)
pod.collective.mesh_devices = None
out["scaling_qps_by_devices"] = curve

# ---- chaos leg: barrier timeouts. Every entry fails at the barrier;
# the plane breaker opens after 2 and queries fall back to the fan-out
# rung INSTANTLY (no per-query barrier wait), bit-exact throughout; when
# the fault clears, a half-open probe re-closes the plane and the fast
# path resumes.
failpoints.configure("collective-barrier", "error")
chaos_qps, chaos_answers, chaos_err = run_concurrent(pod_host, workload)
chaos_snap = pod.collective.snapshot()
failpoints.reset()
served_before_recovery = pod.collective.counters["served_count"]
recovered = False
t0 = time.perf_counter()
while time.perf_counter() - t0 < 20.0 and not recovered:
    got = int(client.query(pod_host, "mc", queries[0])["results"][0])
    assert got == expected[0]
    recovered = (
        pod.collective.counters["served_count"] > served_before_recovery
        and pod.collective.health.plane_state() == "closed")
    if not recovered:
        time.sleep(0.05)
out["chaos"] = {
    "qps_during_fault": chaos_qps,
    "errors": chaos_err,
    "wrong_answers": sum(1 for a, w in zip(chaos_answers, want) if a != w),
    "barrier_timeouts": chaos_snap["barrier_timeouts"],
    "plane_opened": chaos_snap["health"]["plane_opened"],
    "breaker_short_circuits": chaos_snap["breaker_short_circuits"],
    "recovered": recovered,
    "recovery_s": round(time.perf_counter() - t0, 3),
}

for s in fan_servers + [pod]:
    try:
        s.close()
    except Exception as e:
        print(f"close: {e}", file=sys.stderr)

print("MULTICHIP_JSON " + json.dumps(out), flush=True)
'''


def bench_multichip():
    """The collective plane as the primary read path (docs/multichip.md):
    a child process with an 8-device CPU mesh serves the SAME whole-index
    Count workload two ways — a 2-node HTTP fan-out cluster (the
    reference scatter-gather path) vs a one-pod collective plane
    (resident sharded stacks + micro-batched SPMD launches) — and
    reports qps for both, bit-exactness of every answer against a
    host-computed reference, a per-device-count scaling curve of the
    fused collective program, and a barrier-timeout chaos leg proving
    clean instant fallback (breaker open, zero wrong answers) and
    post-fault re-close. Child process so the device count is pinned
    regardless of how the parent's backend was brought up."""
    import tempfile

    # Concurrency is the point of the comparison: the collective side
    # amortizes ONE barrier + ONE SPMD program across each coalesced
    # batch, while the fan-out pays a per-query HTTP hop that nothing
    # coalesces.
    n_shards, n_rows = (2, 4) if SMOKE else (8, 8)
    clients, per_client = (8, 8) if SMOKE else (8, 50)
    script = os.path.join(tempfile.mkdtemp(prefix="bench-mc-"), "child.py")
    with open(script, "w") as f:
        f.write(_MULTICHIP_CHILD)
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, script,
         str(n_shards), str(n_rows), str(clients), str(per_client)],
        capture_output=True, text=True, timeout=240 if SMOKE else 1200,
        env=env,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"multichip child rc={r.returncode}: {r.stderr[-800:]}")
    for line in reversed(r.stdout.strip().splitlines()):
        if line.startswith("MULTICHIP_JSON "):
            return json.loads(line[len("MULTICHIP_JSON "):])
    raise RuntimeError(
        f"multichip child produced no result line: {r.stdout[-500:]}")


# ------------------------------------------------------------- GEO stanza


def bench_geo():
    """Geo replication (docs/geo-replication.md): two clusters on one
    box — the leader as a SEPARATE PROCESS (SIGKILL-able), the follower
    in-process tailing its CDC feed. Phases: sustained ingest on the
    leader with replication-lag sampling (p50/p99 from leader-stamped
    times, never follower wall clocks) and bounded-staleness serving ->
    catch-up -> kill -9 the leader -> promote the follower (fenced
    epoch bump) -> keep writing on the new leader -> restart the old
    leader (the fence demotes it and it re-tails) -> verify ZERO lost
    acked writes on BOTH clusters and byte-identical fragments."""
    import io
    import shutil
    import signal
    import socket
    import subprocess
    import sys
    import tempfile
    import textwrap

    from pilosa_tpu.cdc import CdcConfig
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.errors import PilosaError, StaleReadError
    from pilosa_tpu.geo import GeoConfig
    from pilosa_tpu.server.client import ClientError, InternalClient
    from pilosa_tpu.server.server import Server

    n_shards, per_phase = (2, 20) if SMOKE else (2, 120)

    def free_port():
        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="bench-geo-")
    ports = [free_port(), free_port()]
    hosts = [f"localhost:{p}" for p in ports]
    out = {"shards": n_shards, "writes_per_phase": per_phase}
    follower = None
    child = None

    child_src = textwrap.dedent("""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        from pilosa_tpu.cdc import CdcConfig
        from pilosa_tpu.geo import GeoConfig
        from pilosa_tpu.server.server import Server
        import time
        s = Server(
            data_dir=sys.argv[1], port=int(sys.argv[2]),
            cache_flush_interval=0, anti_entropy_interval=0,
            member_monitor_interval=0, executor_workers=0,
            cdc_config=CdcConfig(enabled=True),
            geo_config=GeoConfig(role="leader"),
        )
        s.open()
        print("ready", flush=True)
        while True:
            time.sleep(3600)
    """)

    def spawn_child():
        p = subprocess.Popen(
            [sys.executable, "-c", child_src,
             os.path.join(tmp, "leader"), str(ports[0])],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        line = p.stdout.readline()
        if "ready" not in line:
            err = p.stderr.read()
            raise RuntimeError(f"geo leader failed to open: {err[-400:]}")
        return p

    def col_of(i):
        return (i % n_shards) * SHARD_WIDTH + 10 + i

    try:
        child = spawn_child()
        follower = Server(
            data_dir=os.path.join(tmp, "follower"), port=ports[1],
            cache_flush_interval=0, anti_entropy_interval=0,
            member_monitor_interval=0, executor_workers=0,
            cdc_config=CdcConfig(enabled=True),
            geo_config=GeoConfig(role="follower", leader=hosts[0],
                                 backoff=0.1),
        )
        follower.open()
        client = InternalClient(timeout=10.0)
        client.create_index(hosts[0], "geo")
        client.create_field(hosts[0], "geo", "f")
        # The follower learns the index from its next schema sync; gate
        # phase 1 on that so lag samples measure replication, not the
        # sync cadence.
        deadline = time.perf_counter() + 30.0
        while (time.perf_counter() < deadline
               and follower.holder.index("geo") is None):
            time.sleep(0.05)
        assert follower.holder.index("geo") is not None

        # Phase 1: sustained ingest on the leader; sample follower lag
        # after every acked write; serve bounded-staleness reads locally.
        acked = []
        lags = []
        served = refused = 0
        t0 = time.perf_counter()
        for i in range(per_phase):
            client.query(hosts[0], "geo", f"Set({col_of(i)}, f=7)")
            acked.append(col_of(i))
            lag = follower.geo.lag()
            if lag != float("inf"):
                lags.append(lag)
            try:
                follower.api.query("geo", "Count(Row(f=7))",
                                   max_staleness=30.0)
                served += 1
            except StaleReadError:
                refused += 1
        out["ingest_qps"] = round(per_phase / (time.perf_counter() - t0), 1)
        lags.sort()
        pick = lambda q: round(lags[min(len(lags) - 1, int(len(lags) * q))] * 1e3, 2)  # noqa: E731
        out["lag_samples"] = len(lags)
        out["lag_p50_ms"] = pick(0.50) if lags else None
        out["lag_p99_ms"] = pick(0.99) if lags else None
        out["staleness"] = {"served": served, "refused": refused}

        # Catch-up, then prove the 409 arm: a zero bound can never be
        # satisfied (lag includes time since last leader contact).
        deadline = time.perf_counter() + 30.0
        while (time.perf_counter() < deadline
               and follower.api.query("geo", "Count(Row(f=7))")[0]
               != len(acked)):
            time.sleep(0.05)
        out["caught_up"] = (
            follower.api.query("geo", "Count(Row(f=7))")[0] == len(acked))
        try:
            follower.api.query("geo", "Count(Row(f=7))", max_staleness=0.0)
            out["stale_409_seen"] = False
        except StaleReadError:
            out["stale_409_seen"] = True

        # Leader loss: kill -9, promote the follower (epoch fence), keep
        # ingesting on the new leader.
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=30)
        st = follower.geo.promote()
        out["promoted_epoch"] = st["epoch"]
        for i in range(per_phase, 2 * per_phase):
            follower.api.query("geo", f"Set({col_of(i)}, f=7)")
            acked.append(col_of(i))

        # Old leader rejoins: the pending fence demotes it (it adopts the
        # new epoch and re-tails the promoted follower from scratch).
        child = spawn_child()
        t0 = time.perf_counter()
        deadline = t0 + 60.0
        demoted = False
        while time.perf_counter() < deadline and not demoted:
            try:
                demoted = client.geo_status(hosts[0])["role"] == "follower"
            except (ClientError, OSError):
                pass
            if not demoted:
                time.sleep(0.1)
        out["fence_s"] = round(time.perf_counter() - t0, 3)
        out["demoted"] = demoted
        t0 = time.perf_counter()
        deadline = t0 + 60.0
        converged = False
        while time.perf_counter() < deadline and not converged:
            try:
                got = client.query(hosts[0], "geo",
                                   "Count(Row(f=7))")["results"][0]
                converged = got == len(acked)
            except (ClientError, PilosaError, OSError):
                pass
            if not converged:
                time.sleep(0.1)
        out["converge_s"] = round(time.perf_counter() - t0, 3)
        out["converged"] = converged

        # Zero lost acked writes on BOTH clusters, byte-identical
        # fragments: the set compare proves the promoted leader, the
        # byte compare extends the proof to the re-tailed old leader.
        lost = 0
        byte_identical = True
        for shard in range(n_shards):
            frag = follower.holder.fragment("geo", "f", "standard", shard)
            if frag is None:
                lost += sum(1 for c in acked if c // SHARD_WIDTH == shard)
                byte_identical = False
                continue
            b0 = io.BytesIO()
            frag.write_to(b0)
            try:
                remote = client.retrieve_shard_from_uri(
                    hosts[0], "geo", "f", "standard", shard)
            except (ClientError, PilosaError):
                byte_identical = False
                continue
            if remote != b0.getvalue():
                byte_identical = False
            want = {7 * SHARD_WIDTH + (c % SHARD_WIDTH)
                    for c in acked if c // SHARD_WIDTH == shard}
            have = {int(p) for p in frag.storage.slice()}
            lost += len(want - have)
        out["lost_acked_writes"] = lost
        out["byte_identical"] = byte_identical
        out["geo_ok"] = bool(
            out["caught_up"] and out["stale_409_seen"] and demoted
            and converged and lost == 0 and byte_identical)
    finally:
        if follower is not None:
            try:
                follower.close()
            except Exception:
                pass
        if child is not None:
            try:
                child.kill()
                child.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# ------------------------------------- multi-tenant QoS / autoscale stanza


def bench_multitenant():
    """Multi-tenant QoS + trace-driven autoscale (docs/scheduler.md
    "Tenancy", docs/rebalance.md "Autoscaler"): three legs.
    ISOLATION — a quiet tenant's interactive p99 is measured solo, then
    again while a noisy tenant floods the same server from several
    threads; the ledger sheds the noisy tenant (typed 429 with a
    per-tenant Retry-After and the X-Pilosa-Tenant header) and parks its
    over-budget queries behind in-budget traffic, so the quiet tenant's
    p99 may not move past the gated ratio and must see ZERO 429s.
    AUTOSCALE — sustained traffic on a 1-node cluster with a registered
    standby trips the controller's hysteresis window: scale-out join +
    online rebalance with NO operator action, proven by membership and
    the .autoscale.json checkpoint.
    CHAOS — a fresh scale-out is aborted mid-migration (byte-throttled
    stream + a deterministic per-delta latency failpoint hold the window
    open); the armed revert contract must restore the prior placement
    exactly: original membership, no partial routing state, ZERO lost
    acked writes, and new writes landing after the revert."""
    import http.client
    import shutil
    import socket
    import tempfile
    import threading

    from pilosa_tpu import failpoints
    from pilosa_tpu.cluster.autoscale import (
        STATE_FILE, AutoscaleConfig, AutoscaleController)
    from pilosa_tpu.cluster.hash import ModHasher
    from pilosa_tpu.cluster.hash import partition as partition_of
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.cluster.rebalance import RebalanceConfig
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.sched import QosConfig, SchedulerConfig
    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    quiet_n = 30 if SMOKE else 200
    n_shards = 4
    out = {}

    def free_port():
        s = socket.socket()
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def post(port, path, body, headers=None):
        conn = http.client.HTTPConnection(f"localhost:{port}", timeout=30)
        try:
            conn.request("POST", path, body=body.encode(),
                         headers=headers or {})
            resp = conn.getresponse()
            hdrs = {k.lower(): v for k, v in resp.getheaders()}
            return resp.status, hdrs, resp.read()
        finally:
            conn.close()

    def p99_ms(lats):
        if not lats:
            return None
        lats = sorted(lats)
        return round(lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3, 2)

    # ---------------------------------------------------- leg 1: isolation
    tmp = tempfile.mkdtemp(prefix="bench-mt-")
    srv = None
    try:
        # Memoization off for this server: a memo hit (or a coalesced
        # rider) dispatches nothing, so its measured cost settles to ~0
        # and the noisy bucket would never drain — the leg must bill
        # real device work.
        os.environ["PILOSA_MEMO_ENTRIES"] = "0"
        try:
            srv = Server(
                data_dir=os.path.join(tmp, "solo"),
                cache_flush_interval=0, anti_entropy_interval=0,
                member_monitor_interval=0,
                scheduler_config=SchedulerConfig(
                    interactive_concurrency=2, max_queue=32,
                    retry_after=0.5),
                qos_config=QosConfig(rate=100.0, burst=300.0,
                                     interactive_cap=2.0, estimate_ms=2.0),
            )
            srv.open()
        finally:
            os.environ.pop("PILOSA_MEMO_ENTRIES", None)
        client = InternalClient(timeout=10.0)
        host = f"localhost:{srv.port}"
        client.create_index(host, "mt")
        client.create_field(host, "mt", "f")
        # Each client gets its own row so identical-count coalescing
        # cannot turn noisy queries into free riders of one dispatch.
        for row in (1, 3, 4, 5):
            client.query(host, "mt", f"Set(7, f={row})")
        # The operator isolation knob: the quiet tenant buys headroom so
        # its own spend can never push it over budget during the run.
        srv.qos.set_share("quiet", 8.0)

        def quiet_run():
            lats = []
            errs = 0
            for _ in range(quiet_n):
                q0 = time.perf_counter()
                st, _, _ = post(srv.port, "/index/mt/query",
                                "Count(Row(f=1))",
                                {"X-Pilosa-Tenant": "quiet"})
                if st == 200:
                    lats.append(time.perf_counter() - q0)
                else:
                    errs += 1
                time.sleep(0.01)
            return lats, errs

        # Warm the dispatch path (first-query compile would otherwise BE
        # the solo p99 at smoke sample counts).
        for _ in range(5):
            post(srv.port, "/index/mt/query", "Count(Row(f=1))",
                 {"X-Pilosa-Tenant": "quiet"})
        solo_lats, solo_errs = quiet_run()

        stop = threading.Event()
        noisy = {"ok": 0, "shed": 0, "typed": 0}

        def note_429(hdrs):
            try:
                typed = (hdrs.get("x-pilosa-tenant") == "noisy"
                         and float(hdrs.get("retry-after", "0")) > 0)
            except ValueError:
                typed = False
            noisy["shed"] += 1
            noisy["typed"] += 1 if typed else 0

        def noisy_reader(row):
            while not stop.is_set():
                st, hdrs, _ = post(srv.port, "/index/mt/query",
                                   f"Count(Row(f={row}))",
                                   {"X-Pilosa-Tenant": "noisy"})
                if st == 200:
                    noisy["ok"] += 1
                elif st == 429:
                    note_429(hdrs)

        def noisy_importer():
            col = 100
            while not stop.is_set():
                payload = json.dumps(
                    {"shard": 0, "rowIDs": [2], "columnIDs": [col]})
                st, hdrs, _ = post(
                    srv.port, "/index/mt/field/f/import", payload,
                    {"Content-Type": "application/json",
                     "X-Pilosa-Tenant": "noisy"})
                if st == 429:
                    note_429(hdrs)
                col += 1
                time.sleep(0.002)

        threads = [threading.Thread(target=noisy_reader, args=(row,),
                                    daemon=True)
                   for row in (3, 4, 5)]
        threads.append(threading.Thread(target=noisy_importer, daemon=True))
        for t in threads:
            t.start()
        time.sleep(0.1)
        cont_lats, cont_errs = quiet_run()
        stop.set()
        for t in threads:
            t.join(timeout=10)
        snap = srv.qos.snapshot()
        solo_p99, cont_p99 = p99_ms(solo_lats), p99_ms(cont_lats)
        out["isolation"] = {
            "solo_p99_ms": solo_p99,
            "contended_p99_ms": cont_p99,
            # The timing gate: noisy load may not move quiet's p99 past
            # the bound. The bound is ratio OR absolute — at micro scale
            # a solo query is ~2ms while ANY concurrency legitimately
            # opens the micro-batcher's coalescing window, so the honest
            # claim is "bounded head-of-line wait, never starvation"
            # (an unpoliced flood parks 30+ queries ahead and pushes the
            # quiet tenant to multi-second p99s).
            "quiet_p99_ratio": (
                round(cont_p99 / max(solo_p99, 1.0), 2)
                if solo_p99 and cont_p99 else None),
            "quiet_p99_bounded": bool(
                solo_p99 is not None and cont_p99 is not None
                and cont_p99 <= max(8.0 * solo_p99, 500.0)),
            "quiet_429": solo_errs + cont_errs,
            "noisy_ok": noisy["ok"],
            "noisy_shed": noisy["shed"],
            "typed_429": noisy["shed"] >= 1 and noisy["typed"] == noisy["shed"],
            "ledger": {
                "shed_batch": snap["shed_batch"],
                "shed_interactive": snap["shed_interactive"],
                "deferred": snap["deferred"],
            },
        }
    finally:
        if srv is not None:
            try:
                srv.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)

    # ------------------------------------- cluster harness for legs 2 + 3
    def scale_ports(index, min_gains):
        """A (coordinator, standby) port pair whose 1->2 placement hands
        the standby >= min_gains shards (node ids derive from the random
        ports; an arbitrary pair can be a no-op placement)."""
        for _ in range(64):
            ports = [free_port(), free_port()]
            hosts = [f"localhost:{p}" for p in ports]
            ordered = sorted(hosts)
            gains = [sh for sh in range(n_shards)
                     if ordered[partition_of(index, sh, 256) % 2]
                     == hosts[1]]
            if min_gains <= len(gains) < n_shards:
                return ports, hosts, gains
        raise RuntimeError("no scaling port pair found")

    def make_node(tmp, name, port, **kw):
        kw.setdefault("rebalance_config", RebalanceConfig(
            catchup_threshold_bytes=256, max_catchup_rounds=8,
            cutover_pause_max=2.0))
        s = Server(
            data_dir=os.path.join(tmp, name), port=port, hasher=ModHasher(),
            cache_flush_interval=0, anti_entropy_interval=0,
            member_monitor_interval=0, executor_workers=0,
            resilience_config=ResilienceConfig(
                breaker_backoff=0.1, breaker_backoff_max=0.5,
                retry_budget=100.0, retry_refill=1.0),
            **kw)
        s.open()
        return s

    def wait_for(cond, timeout=60):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if cond():
                return True
            time.sleep(0.03)
        return False

    def load_base(client, h0, index):
        client.create_index(h0, index)
        client.create_field(h0, index, "f")
        time.sleep(0.05)
        for sh in range(n_shards):
            client.query(h0, index, f"Set({sh * SHARD_WIDTH + 7}, f=1)")

    # ---------------------------------------------------- leg 2: autoscale
    tmp = tempfile.mkdtemp(prefix="bench-mt-scale-")
    servers = []
    try:
        ports, hosts, gains = scale_ports("mta", 1)
        h0srv = make_node(tmp, "n0", ports[0], cluster_hosts=[hosts[0]])
        standby = make_node(tmp, "s1", ports[1], cluster_hosts=[hosts[1]],
                            is_coordinator=True)
        servers = [h0srv, standby]
        client = InternalClient(timeout=10.0)
        h0 = h0srv.node.uri
        load_base(client, h0, "mta")
        ctrl = AutoscaleController(h0srv, AutoscaleConfig(
            interval=1.0, window=1, scale_out_qps=5.0, scale_in_qps=0.1,
            cooldown=0.0, standby=hosts[1]))
        ctrl.step()  # seeds the traffic baseline
        time.sleep(0.05)
        for _ in range(200):
            h0srv.scheduler.note_index("mta")
        t0 = time.perf_counter()
        decision = ctrl.step()
        stats = h0srv.rebalance_stats.counters
        scaled = decision == "out" and wait_for(
            lambda: stats.get("jobs_completed", 0) >= 1
            and len(h0srv.cluster.nodes) == 2
            and h0srv.cluster.next_nodes is None)
        dt = time.perf_counter() - t0
        served = client.query(
            h0, "mta", "Count(Row(f=1))")["results"][0] == n_shards
        try:
            with open(os.path.join(h0srv.data_dir, STATE_FILE)) as f:
                checkpoint = json.load(f).get("added", [])
        except OSError:
            checkpoint = None
        out["autoscale"] = {
            "decision": decision,
            "scaled_out": bool(scaled),
            "time_to_scale_s": round(dt, 3),
            "nodes": len(h0srv.cluster.nodes),
            "standby_gained_shards": len(gains),
            "served_through": bool(served),
            "checkpointed": checkpoint == [standby.node.id],
        }
    except Exception as e:
        out["autoscale"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        for s in servers:
            try:
                s.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)

    # ------------------------------------- leg 3: chaos abort, full revert
    tmp = tempfile.mkdtemp(prefix="bench-mt-chaos-")
    servers = []
    try:
        ports, hosts, gains = scale_ports("mtc", 2)
        throttled = RebalanceConfig(
            catchup_threshold_bytes=256, max_catchup_rounds=8,
            cutover_pause_max=2.0, max_bytes_per_sec=8192)
        h0srv = make_node(tmp, "n0", ports[0], cluster_hosts=[hosts[0]],
                          rebalance_config=throttled)
        standby = make_node(tmp, "s1", ports[1], cluster_hosts=[hosts[1]],
                            is_coordinator=True, rebalance_config=throttled)
        servers = [h0srv, standby]
        client = InternalClient(timeout=10.0)
        h0 = h0srv.node.uri
        load_base(client, h0, "mtc")
        # Fatten the LAST gaining shard so it streams for seconds under
        # the byte throttle while the first commits quickly — a wide,
        # deterministic abort window between the two cutovers.
        fat = gains[-1]
        offs = [o for o in range(0, 200000, 10) if o != 7]
        client.import_bits(
            h0, "mtc", "f",
            [(1, fat * SHARD_WIDTH + o) for o in offs])
        acked = n_shards + len(offs)
        ctrl = AutoscaleController(h0srv, AutoscaleConfig(
            interval=1.0, window=1, scale_out_qps=5.0, scale_in_qps=0.1,
            cooldown=0.0, standby=hosts[1]))
        ctrl.step()
        time.sleep(0.05)
        for _ in range(200):
            h0srv.scheduler.note_index("mtc")
        # Deterministic abort window: the per-instruction byte throttle is
        # SHARED, so both shard streams can drain together and their
        # cutovers cluster at job end. A count=1 latency delays exactly
        # ONE shard's catch-up pull — the other commits >= 1.5s before
        # the job can complete, whatever the stream interleaving.
        failpoints.configure("migrate-delta", "latency", count=1,
                             arg=1500.0)
        decision = ctrl.step()
        coord = h0srv.rebalance_coordinator
        armed = (decision == "out" and coord is not None
                 and coord.revert_on_abort is True)

        def committed_one():
            job = coord.job
            return (job is not None and not job.revert
                    and len(job.committed) >= 1)

        window = armed and wait_for(committed_one, timeout=90)
        if window:
            # A PLAIN abort — the armed contract escalates it to revert.
            coord.abort("chaos: injected mid-migration abort")
        stats = h0srv.rebalance_stats.counters
        reverted = window and wait_for(
            lambda: stats.get("jobs_reverted", 0) >= 1
            and coord.job is None)
        routing_restored = (
            reverted and len(h0srv.cluster.nodes) == 1
            and h0srv.cluster.next_nodes is None
            and h0srv.cluster.migrated == set()
            and all(
                [n.id for n in h0srv.cluster.shard_nodes("mtc", sh)]
                == [h0srv.node.id] for sh in range(n_shards)))
        failpoints.reset()
        got = client.query(h0, "mtc", "Count(Row(f=1))")["results"][0]
        client.query(h0, "mtc", f"Set({fat * SHARD_WIDTH + 3}, f=1)")
        after = client.query(h0, "mtc", "Count(Row(f=1))")["results"][0]
        out["chaos"] = {
            "armed": bool(armed),
            "abort_window_caught": bool(window),
            "reverted": bool(reverted),
            "routing_restored": bool(routing_restored),
            "lost_acked_writes": acked - got,
            "write_after_revert": after == acked + 1,
        }
    except Exception as e:
        out["chaos"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        failpoints.reset()
        for s in servers:
            try:
                s.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)

    iso = out.get("isolation", {})
    asc = out.get("autoscale", {})
    chaos = out.get("chaos", {})
    # Correctness verdict (never retried); the quiet-p99 RATIO is judged
    # separately by the smoke as a timing gate with one isolation rerun.
    out["multitenant_ok"] = bool(
        iso.get("typed_429") and iso.get("quiet_429") == 0
        and asc.get("scaled_out") and asc.get("checkpointed")
        and chaos.get("reverted") and chaos.get("routing_restored")
        and chaos.get("lost_acked_writes") == 0
        and chaos.get("write_after_revert"))
    return out


# --------------------------------------------- internal transport stanza


def bench_transport():
    """pmux vs HTTP on the internal hop (docs/transport.md "Measured"):
    a 3-node replica_n=2 cluster where the SAME query_node workload runs
    twice from the coordinator — once with its client's mux detached
    (plain keep-alive HTTP) and once over the multiplexed transport —
    so the only variable is the transport. Reports per-hop p50/p99 and
    fan-out qps for both legs plus the mux frame/byte counters, then
    two correctness-shaped legs entirely over mux: a REPLICATION-shaped
    pass (healthy replicated writes -> peer link dropped, writes keep
    acking with hints appended -> heal -> hints drain over mux ->
    replica count converges) and a REBALANCE-shaped pass (migration-
    stream-style full-shard retrieval whose bytes must be identical on
    both transports). `mux_vs_http_qps` is the gated fan-out ratio."""
    import shutil
    import socket
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu import failpoints
    from pilosa_tpu.cluster.hash import ModHasher
    from pilosa_tpu.cluster.health import ResilienceConfig
    from pilosa_tpu.constants import SHARD_WIDTH
    from pilosa_tpu.errors import PilosaError
    from pilosa_tpu.server.client import ClientError, InternalClient
    from pilosa_tpu.server.mux import TransportConfig
    from pilosa_tpu.server.server import Server

    n_rows = 2
    n_shards = 2 if SMOKE else 4
    per_hop_n = 40 if SMOKE else 400
    fanout_n = 80 if SMOKE else 800
    fanout_conc = 4
    repl_writes = 12 if SMOKE else 100

    mux_off = 2000

    def free_port_pair():
        for _ in range(64):
            s = socket.socket()
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
            s.close()
            if port + mux_off > 65000:
                continue
            try:
                probe = socket.socket()
                probe.bind(("localhost", port + mux_off))
                probe.close()
            except OSError:
                continue
            return port
        raise RuntimeError("no free http+mux port pair")

    tmp = tempfile.mkdtemp(prefix="bench-transport-")
    ports = [free_port_pair() for _ in range(3)]
    hosts = [f"localhost:{p}" for p in ports]
    servers = []
    out = {"shards": n_shards, "per_hop_n": per_hop_n, "fanout_n": fanout_n}
    try:
        for i, port in enumerate(ports):
            s = Server(
                data_dir=os.path.join(tmp, f"node{i}"),
                port=port,
                cluster_hosts=hosts,
                replica_n=2,
                hasher=ModHasher(),
                cache_flush_interval=0,
                anti_entropy_interval=0,
                member_monitor_interval=0,
                transport_config=TransportConfig(
                    enabled=True, port_offset=mux_off),
                resilience_config=ResilienceConfig(
                    breaker_backoff=0.1, breaker_backoff_max=0.5,
                ),
            )
            s.open()
            servers.append(s)
        harness = InternalClient(timeout=10.0)
        harness.create_index(hosts[0], "tx")
        harness.create_field(hosts[0], "tx", "f")
        time.sleep(0.05)
        for row in range(n_rows):
            for shard in range(n_shards):
                harness.query(
                    hosts[0], "tx",
                    f"Set({shard * SHARD_WIDTH + row + 1}, f={row})")

        s0 = servers[0]
        peers = [n for n in s0.cluster.nodes if n.id != s0.node.id]
        # Shards each peer owns, so the hop is a real data-serving hop.
        peer_shards = {
            n.id: [sh for sh in range(n_shards)
                   if any(o.id == n.id
                          for o in s0.cluster.shard_nodes("tx", sh))]
            for n in peers
        }
        peers = [n for n in peers if peer_shards[n.id]]
        assert peers, "placement left the coordinator's peers shardless"

        def one_hop(i):
            node = peers[i % len(peers)]
            row = i % n_rows
            got = s0.client.query_node(
                node, "tx", f"Count(Row(f={row}))",
                shards=peer_shards[node.id])
            assert got[0] == len(peer_shards[node.id])

        def run_leg(n, conc):
            lat = []
            lat_mu = threading.Lock()
            err = 0

            def call(i):
                q0 = time.perf_counter()
                one_hop(i)
                dt = time.perf_counter() - q0
                with lat_mu:
                    lat.append(dt)

            t0 = time.perf_counter()
            if conc == 1:
                for i in range(n):
                    try:
                        call(i)
                    except (ClientError, PilosaError):
                        err += 1
            else:
                with ThreadPoolExecutor(max_workers=conc) as pool:
                    futs = [pool.submit(call, i) for i in range(n)]
                    for f in futs:
                        try:
                            f.result()
                        except (ClientError, PilosaError):
                            err += 1
            dt = time.perf_counter() - t0
            lat.sort()
            pick = (lambda q: round(
                lat[min(len(lat) - 1, int(len(lat) * q))] * 1e3, 3
            )) if lat else (lambda q: None)
            return {"qps": round(len(lat) / dt, 1) if dt else 0.0,
                    "p50_ms": pick(0.50), "p99_ms": pick(0.99),
                    "ok": len(lat), "errors": err}

        # ---- HTTP leg: detach the coordinator's mux so the identical
        # workload rides the keep-alive HTTP pool.
        mux = s0.client.mux
        s0.client.mux = None
        for i in range(4):
            one_hop(i)  # warm the HTTP pool
        out["per_hop_http"] = run_leg(per_hop_n, 1)
        out["fanout_http"] = run_leg(fanout_n, fanout_conc)

        # ---- mux leg: same workload over the multiplexed transport.
        s0.client.mux = mux
        before = s0.transport_stats.snapshot()
        for i in range(4):
            one_hop(i)  # dial + handshake outside the timed window
        out["per_hop_mux"] = run_leg(per_hop_n, 1)
        out["fanout_mux"] = run_leg(fanout_n, fanout_conc)
        after = s0.transport_stats.snapshot()
        out["mux_counters"] = {
            k: after[k] - before.get(k, 0)
            for k in ("frames_sent", "frames_received", "bytes_sent",
                      "bytes_received", "batched_frames", "requests_mux",
                      "requests_http", "handshake_fallbacks")
        }
        http_qps = out["fanout_http"]["qps"] or 1e-9
        out["mux_vs_http_qps"] = round(out["fanout_mux"]["qps"] / http_qps, 3)
        p50h, p50m = out["per_hop_http"]["p50_ms"], out["per_hop_mux"]["p50_ms"]
        if p50h is not None and p50m is not None:
            out["per_hop_p50_saved_ms"] = round(p50h - p50m, 3)

        # ---- REPLICATION-shaped leg over mux: peer link drops, writes
        # keep acking with hints; heal; hints DRAIN over mux; the
        # replica's local count converges to the survivor's. The shard
        # must be CO-OWNED by the coordinator: only a local apply
        # captures op payloads for the hint log — a non-owner
        # coordinator writes marker hints (sync-priority only) whose
        # repair rides anti-entropy, not hint delivery, and this leg
        # measures hint delivery over mux.
        vshard = victim = None
        for sh in range(n_shards + 16):
            sowners = s0.cluster.shard_nodes("tx", sh)
            if any(o.id == s0.node.id for o in sowners):
                vshard = sh
                victim = next(
                    o for o in sowners if o.id != s0.node.id)
                break
        assert victim is not None, "placement gave node0 no shard"
        # Seeded shards carry one pre-existing row-0 bit; a shard past
        # the seeded range starts empty.
        vbase = 1 if vshard < n_shards else 0
        failpoints.seed(11)
        failpoints.configure(f"client-send@{victim.uri}", "drop")
        wrote = 0
        for i in range(repl_writes):
            col = vshard * SHARD_WIDTH + 1000 + i
            try:
                harness.query(hosts[0], "tx", f"Set({col}, f=0)")
                wrote += 1
            except (ClientError, PilosaError):
                pass
        hinted = sum(
            s.hints.pending(victim.id) for s in servers
            if s.node.id != victim.id)
        failpoints.reset()
        t0 = time.perf_counter()
        drained = False
        deadline = t0 + 30.0
        while time.perf_counter() < deadline and not drained:
            for s in servers:
                s._monitor_members()
                if s.node.id != victim.id:
                    s.hints.deliver_once(s.cluster, s.client)
            drained = all(
                s.hints.pending(victim.id) == 0 for s in servers
                if s.node.id != victim.id)
        out["replication_leg"] = {
            "writes_acked": wrote,
            "writes_attempted": repl_writes,
            "hints_appended": hinted,
            "hint_drain_s": round(time.perf_counter() - t0, 3),
            "drained": drained,
        }
        # Converged: the victim's OWN copy matches the surviving owner's
        # (replica agreement) and contains every ACKED write (a write
        # that timed out at the harness under box load may still have
        # been partially applied + hinted, so an absolute `1 + wrote`
        # equality would flag phantom loss — replica agreement is the
        # durable invariant).
        survivor = next(
            o for o in s0.cluster.shard_nodes("tx", vshard)
            if o.id != victim.id)
        vc = s0.client.query_node(
            victim, "tx", "Count(Row(f=0))", shards=[vshard])[0]
        sc = s0.client.query_node(
            survivor, "tx", "Count(Row(f=0))", shards=[vshard])[0]
        out["replication_leg"]["replica_count_ok"] = (
            vc == sc and vc >= vbase + wrote)
        total = harness.query(
            hosts[0], "tx", "Count(Row(f=0))")["results"][0]
        out["replication_leg"]["total_count_ok"] = (
            total == (n_shards - vbase) + vc)

        # ---- REBALANCE-shaped leg over mux: migration-stream-style
        # whole-shard retrieval; bytes must be transport-invariant.
        t0 = time.perf_counter()
        mux_bytes = s0.client.retrieve_shard_from_uri(
            victim.uri, "tx", "f", "standard", vshard)
        mux_dt = time.perf_counter() - t0
        s0.client.mux = None
        http_bytes = s0.client.retrieve_shard_from_uri(
            victim.uri, "tx", "f", "standard", vshard)
        s0.client.mux = mux
        out["rebalance_leg"] = {
            "shard_bytes": len(mux_bytes),
            "retrieve_ms": round(mux_dt * 1e3, 2),
            "bit_exact": mux_bytes == http_bytes and len(mux_bytes) > 0,
        }

        snap = s0.transport_stats.snapshot()
        out["transport_ok"] = bool(
            out["mux_counters"]["requests_mux"] > 0
            and out["mux_counters"]["handshake_fallbacks"] == 0
            and out["per_hop_http"]["errors"] == 0
            and out["per_hop_mux"]["errors"] == 0
            and out["replication_leg"]["drained"]
            and out["replication_leg"]["replica_count_ok"]
            and out["replication_leg"]["total_count_ok"]
            and out["rebalance_leg"]["bit_exact"]
        )
        out["final_counters"] = {
            k: snap[k] for k in ("requests_mux", "requests_http",
                                 "batched_frames", "inflight_hwm")}
    finally:
        failpoints.reset()
        for s in servers:
            try:
                s.close()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    return out


# Every optional stanza, in run order. THE registry: main() runs exactly
# these, the FINAL JSON line carries a key per entry (lowercased), and
# tests/test_bench_smoke.py asserts every name is present — a stanza
# added here can never silently fall out of the final line again
# (sched/mixed went missing twice that way).
STANZAS = (
    ("HBM", bench_hbm),
    ("BIG", bench_big),
    ("SCALE", bench_scale),
    ("OPEN", bench_open),
    ("IMPORT", bench_import),
    ("INGEST", bench_ingest),
    ("SERVING", bench_serving),
    ("SCHED", bench_sched),
    ("COMPILE", bench_compile),
    ("OBS", bench_obs),
    ("MIXED", bench_mixed),
    ("FAULT", bench_fault),
    ("REPLICATION", bench_replication),
    ("CDC", bench_cdc),
    ("DEGRADE", bench_degrade),
    ("REBALANCE", bench_rebalance),
    ("TIER", bench_tier),
    ("MULTICHIP", bench_multichip),
    ("TOPN_BSI", bench_topn_bsi),
    ("TIME_RANGE", bench_time_range),
    ("GEO", bench_geo),
    ("MULTITENANT", bench_multitenant),
    ("TRANSPORT", bench_transport),
)


def _write_bench_out(line):
    """Atomically (re)write the BENCH_OUT file, fsynced, so whatever ran
    to completion survives even a kill -9 of the bench itself. Best-effort:
    an unwritable BENCH_OUT must never abort the bench — stdout still
    carries every checkpoint line."""
    out_path = os.environ.get("BENCH_OUT")
    if not out_path:
        return
    try:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out_path)
    except OSError as e:
        print(f"bench: cannot write BENCH_OUT={out_path}: {e}",
              file=sys.stderr)


def main():
    # Deadline watchdog: a device call that never returns would leave the
    # driver with no bench at all. At BENCH_DEADLINE seconds (default 40
    # min) the watchdog prints the JSON line with everything collected so
    # far and exits.
    import threading

    deadline = float(os.environ.get("BENCH_DEADLINE", "2400"))
    partial = {
        "metric": "count_intersect_qps_8shards",
        "value": 0,
        "unit": "queries/sec",
        "vs_baseline": 0,
        "detail": {"partial": "deadline watchdog fired"},
    }
    state = {"done": False}

    def emit_partial(note):
        """Persist everything collected SO FAR: a JSON line on stdout (the
        driver parses the LAST parseable line, so a driver-side timeout —
        rc=124 — still records completed stanzas instead of nothing) and,
        when BENCH_OUT names a file, an atomic rewrite of that file. The
        `partial` marker tells downstream consumers this line is a
        checkpoint, not the final verdict. Called before the backend comes
        up and before/after every stanza, so a run killed at the driver's
        timeout still leaves a parseable line."""
        snap = json.loads(json.dumps(partial))
        snap["detail"]["partial"] = note
        line = json.dumps(snap)
        print(line, flush=True)
        _write_bench_out(line)

    def watchdog():
        time.sleep(deadline)
        if state["done"]:
            return
        partial["detail"]["error"] = (
            f"BENCH_DEADLINE {deadline}s exceeded; results are partial "
            "(a device call likely never returned)"
        )
        line = json.dumps(partial)
        print(line, flush=True)
        try:
            _write_bench_out(line)
        except OSError:
            pass
        os._exit(3)

    if deadline > 0:
        threading.Thread(target=watchdog, daemon=True).start()

    if SMOKE:
        # Micro-scale everything: smoke validates that the bench EXECUTES
        # (every stanza, parseable JSON line), not what the hardware
        # measures. The CPU backend is pinned below.
        for k, v in (
            ("BENCH_SHARDS", "2"),
            ("BENCH_ROWS", "8"), ("BENCH_ITERS", "16"),
            ("BENCH_HBM_GIB", "0.002"), ("BENCH_BIG_SHARDS", "2"),
            ("BENCH_BIG_ROWS", "8"), ("BENCH_BIG_ITERS", "8"),
            ("BENCH_PIPELINE", "2"),
        ):
            os.environ.setdefault(k, v)

    n_shards = int(os.environ.get("BENCH_SHARDS", "8"))
    n_rows = int(os.environ.get("BENCH_ROWS", "128"))
    density = float(os.environ.get("BENCH_DENSITY", "0.02"))
    # Cap batch size at the number of distinct ordered row pairs: every
    # query in a batch is then distinct, so the engine's within-batch
    # memoization cannot inflate throughput by collapsing duplicates
    # while still counting them at full weight.
    iters = min(int(os.environ.get("BENCH_ITERS", "1024")), n_rows * (n_rows - 1))

    # First checkpoint BEFORE any backend work: even a backend that wedges
    # past the driver's deadline leaves a parseable FINAL-shaped line.
    emit_partial("before backend start")

    import jax

    if SMOKE:
        # The explicit CPU self-test (tools/check.sh, tier-1).
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        # No fallback: a CPU number under a device metric's name is worse
        # than no number.
        print(f"bench: default jax backend is {jax.default_backend()!r}, "
              "not 'tpu'; run on the chip, or BENCH_SMOKE=1 for the CPU "
              "self-test", file=sys.stderr)
        sys.exit(1)

    device = _device_info()
    partial["detail"]["device"] = device
    emit_partial("backend selected; building headline index")
    holder, ex = build(n_shards, n_rows, density)
    count_qps, topn_qps = bench_device(ex, n_rows, n_shards, iters)
    host_qps, host_detail = bench_host(holder, n_rows, n_shards, iters)
    partial["value"] = round(count_qps, 2)
    partial["vs_baseline"] = round(count_qps / host_qps, 3)
    partial["detail"]["host_cpu_qps"] = round(host_qps, 2)
    # Release the headline stanza's device caches before the multi-GiB
    # stanzas (bench_hbm builds an 8 GiB stack, bench_big up to ~10 GiB
    # of leaf+stack cache on a 16 GiB chip — leftovers are the margin).
    ex.close()
    holder.close()
    del holder, ex

    emit_partial("headline stanza complete")

    def stanza(name, fn):
        """Run one optional stanza; a crash records the error instead of
        killing the whole bench line, and every completion checkpoints the
        results collected so far (two consecutive rounds of rc=124 drivers
        recorded `parsed: null` because all output waited for the end)."""
        if os.environ.get(f"BENCH_{name}") == "0":
            return {"skipped": f"BENCH_{name}=0"}
        # Checkpoint BEFORE the stanza too: when a stanza wedges past the
        # driver's deadline, the last parseable line now NAMES it (r05's
        # `parsed: null` left no clue which stanza died).
        emit_partial(f"entering stanza {name}")
        try:
            out = fn()
        except Exception as e:
            out = {"error": f"{type(e).__name__}: {e}"[:500]}
        partial["detail"][name.lower()] = out
        emit_partial(f"through stanza {name}")
        return out

    # THE stanza registry drives the run: every entry lands in the FINAL
    # line under its lowercased name (test_bench_smoke asserts this).
    results = {}
    for name, fn in STANZAS:
        results[name.lower()] = stanza(name, fn)
    hbm = results["hbm"]

    # Kernel-tier verdict derived from the HBM race: the shipped Pallas
    # kernel must beat the XLA formulation at serving-realistic sizes.
    if isinstance(hbm, dict) and "gbs" in hbm.get("pallas_gather", {}):
        pallas = {"batched_gather_expr_count": {
            "vs_xla": hbm.get("pallas_vs_xla"),
            "gbs": hbm["pallas_gather"]["gbs"],
            "verified": hbm.get("verified"),
        }}
    else:
        pallas = {"note": "kernel validation needs a TPU; see detail.hbm"}

    state["done"] = True
    final_line = json.dumps({
        "metric": "count_intersect_qps_8shards",
        "value": round(count_qps, 2),
        "unit": "queries/sec",
        "vs_baseline": round(count_qps / host_qps, 3),
        "detail": {
            "topn_qps": round(topn_qps, 2),
            "host_cpu_qps": round(host_qps, 2),
            "host_baseline": host_detail,
            "shards": n_shards,
            "rows": n_rows,
            "iters": iters,
            "density": density,
            "platform": device["platform"],
            "device": device,
            # Every registered stanza rides the FINAL line (the driver
            # parses the LAST line; sched/mixed once lived only in
            # checkpoint lines and were lost).
            **results,
            "pallas": pallas,
        },
    })
    print(final_line, flush=True)
    _write_bench_out(final_line)


if __name__ == "__main__":
    main()
