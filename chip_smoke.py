#!/usr/bin/env python3
"""The served path, end to end, on the chip: the quickest proof that the
system still starts there.

Starts `python -m pilosa_tpu.cli server` as a child, loads an index through
the HTTP import endpoints (full 2^20 shard width, 256 shards, two set
fields of 32 heavy-tailed rows, one BSI int field), asks one of every kind
of query the device serves plus waves of concurrent Counts that must
coalesce into the batched program, compares every answer exactly with a
numpy reference built from the same seed, reads /debug/vars and refuses a
run in which any rung below the device served anything, then restarts the
server on the same data directory and asks again.

This process is stdlib + numpy and never imports jax or pilosa_tpu: the
chip belongs to the server child. It passes its environment through.

A passing run prints two JSON lines at the end of stdout and exits 0: the
summary (sizes, what was cut, every check by name, wall-time facts,
counters, `"claim": null`) and then, as the LAST line, the result —
exactly `{"ok": true, "device": {"platform", "kind", "count"}}`, the device
as jax reports it to the server. A failing run — any check false, the CPU
backend included: the `device` check wants `tpu` and nothing switches it
off — prints no result on stdout; the summary goes to stderr and the exit
status is 1. `--shards/--rows/--seed` size a rehearsal; what they cut is in
the summary.
"""

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SHARD_WIDTH = 1 << 20
FULL_SHARDS, FULL_ROWS = 256, 32
INDEX = "smoke"
V_MAX = 65535
WAVE, WAVE_THREADS, MAX_WAVES = 64, 16, 8
# Every HTTP call is bounded: the dispatch watchdog is off by default, so
# a hung device call would otherwise hang the smoke with it.
HTTP_TIMEOUT = 600.0
START_TIMEOUT = 300.0
STOP_TIMEOUT = 120.0

# /debug/vars counters that say a rung below the device served something,
# or that the device path failed and was papered over. All must stay 0.
LADDER_ZERO = (
    "device_dispatch_errors", "host_counts", "host_topn", "host_cold_counts",
    "oom_backpressure", "oom_batch_splits", "watchdog_timeouts",
    "tier_promote_errors",
)
MUST_GROW = ("count_dispatches", "bitmap_dispatches", "fn_cache_builds")


def popcount(words):
    return int(np.bitwise_count(words).sum())


def ladder_nonzero(engine_cache):
    return {k: engine_cache.get(k) for k in LADDER_ZERO
            if engine_cache.get(k) != 0}


class Reference:
    """The index as plain numpy: one packed uint64 bitset per (field, row)
    and the BSI field as (columns, values). Independent of pilosa_tpu."""

    def __init__(self, shards, rows, seed):
        rng = np.random.default_rng(seed)
        self.shards, self.rows = shards, rows
        self.n = shards * SHARD_WIDTH
        self.cols = {}
        self.bits = {}
        for field in ("f", "g"):
            for r in range(rows):
                # Row r at about 2%/(r+1): dense and sparse rows share a
                # stack. Drawn with replacement, then deduplicated.
                k = int(self.n * 0.02 / (r + 1))
                c = np.unique(rng.integers(0, self.n, k, dtype=np.uint32))
                self.cols[field, r] = c
                self.bits[field, r] = self._pack(c)
        k = int(self.n * 0.01)
        self.v_cols = np.unique(rng.integers(0, self.n, k, dtype=np.uint32))
        self.v_vals = rng.integers(0, V_MAX + 1, len(self.v_cols),
                                   dtype=np.int64)

    def _pack(self, cols):
        out = np.zeros(self.n >> 6, dtype=np.uint64)
        word = cols >> 6
        bit = np.uint64(1) << (cols & 63).astype(np.uint64)
        uniq, start = np.unique(word, return_index=True)
        out[uniq] = np.bitwise_or.reduceat(bit, start)
        return out

    def member(self, key, cols):
        """Boolean mask: which of `cols` are set in row `key`."""
        b = self.bits[key]
        return ((b[cols >> 6] >> (cols & 63).astype(np.uint64))
                & np.uint64(1)).astype(bool)

    def set_bit(self, key, col):
        self.bits[key][col >> 6] |= np.uint64(1) << np.uint64(col & 63)

    def by_shard(self, field):
        """Per shard, the (rowIDs, columnIDs) of a set field's import."""
        cols = np.concatenate([self.cols[field, r] for r in range(self.rows)])
        rows = np.concatenate([
            np.full(len(self.cols[field, r]), r, dtype=np.uint32)
            for r in range(self.rows)])
        order = np.argsort(cols >> 20, kind="stable")
        cols, rows = cols[order], rows[order]
        bounds = np.searchsorted(cols >> 20, np.arange(self.shards + 1))
        for s in range(self.shards):
            lo, hi = bounds[s], bounds[s + 1]
            yield s, rows[lo:hi], cols[lo:hi]

    def values_by_shard(self):
        bounds = np.searchsorted(self.v_cols >> 20,
                                 np.arange(self.shards + 1))
        for s in range(self.shards):
            lo, hi = bounds[s], bounds[s + 1]
            yield s, self.v_cols[lo:hi], self.v_vals[lo:hi]


class Server:
    """The server child: started, asked over HTTP, stopped."""

    def __init__(self, data_dir, log_path):
        self.data_dir = data_dir
        self.log_path = log_path
        self.proc = None
        self.port = None

    def start(self):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            self.port = s.getsockname()[1]
        t0 = time.monotonic()
        log = open(self.log_path, "ab")
        try:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu.cli", "server",
                 "--data-dir", self.data_dir,
                 "--bind", f"localhost:{self.port}"],
                cwd=HERE, stdout=subprocess.PIPE, stderr=log)
        finally:
            log.close()
        ready = threading.Event()

        def pump():
            for line in self.proc.stdout:
                if b"listening on" in line:
                    ready.set()

        threading.Thread(target=pump, daemon=True).start()
        deadline = t0 + START_TIMEOUT
        while not ready.wait(0.2):
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited {self.proc.returncode} before listening")
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"server not listening after {START_TIMEOUT:.0f}s")
        return time.monotonic() - t0

    def stop(self):
        """SIGTERM and wait; True when the server exited by itself."""
        if self.proc is None or self.proc.poll() is not None:
            return True
        self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(STOP_TIMEOUT)
            return True
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            return False

    def request(self, method, path, body=None):
        """One bounded HTTP call; the parsed JSON response. 429 (admission
        shed) is retried after the advertised delay; anything else but 200
        raises."""
        if isinstance(body, str):
            body = body.encode()
        for _ in range(60):
            conn = http.client.HTTPConnection(
                "localhost", self.port, timeout=HTTP_TIMEOUT)
            try:
                conn.request(method, path, body)
                resp = conn.getresponse()
                data = resp.read()
            finally:
                conn.close()
            if resp.status == 429:
                time.sleep(float(resp.getheader("Retry-After") or 1.0))
                continue
            if resp.status != 200:
                raise RuntimeError(
                    f"{method} {path}: HTTP {resp.status}: {data[:300]!r}")
            return json.loads(data) if data else None
        raise RuntimeError(f"{method} {path}: still shed after 60 tries")

    def query(self, pql):
        return self.request("POST", f"/index/{INDEX}/query", pql)["results"]

    def vars(self):
        return self.request("GET", "/debug/vars")


def in_threads(n, fn, items):
    """fn(item) for every item from n threads; results in order. The first
    exception is re-raised once all threads have ended."""
    out = [None] * len(items)
    errors = []
    gate = threading.Barrier(n)

    def work(k):
        try:
            gate.wait(60)
            for i in range(k, len(items), n):
                out[i] = fn(items[i])
        except Exception as e:  # re-raised below, in the caller's thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out


class Smoke:
    def __init__(self, args):
        self.args = args
        self.checks = {}
        self.facts = {}
        self.detail = {}
        self.asked = {}

    def check(self, name, ok, detail=None):
        self.checks[name] = bool(ok)
        if not ok:
            self.detail[name] = detail
            print(f"chip_smoke: CHECK FAILED {name}: {detail}",
                  file=sys.stderr, flush=True)

    def say(self, msg):
        print(f"chip_smoke: {msg}", flush=True)

    # ---------------------------------------------------------- phases

    def load(self, srv, ref):
        t0 = time.monotonic()
        srv.request("POST", f"/index/{INDEX}", "{}")
        for field in ("f", "g"):
            srv.request("POST", f"/index/{INDEX}/field/{field}", "{}")
        srv.request("POST", f"/index/{INDEX}/field/v", json.dumps(
            {"options": {"type": "int", "min": 0, "max": V_MAX}}))
        jobs = []
        for field in ("f", "g"):
            for s, rows, cols in ref.by_shard(field):
                jobs.append((field, {"shard": s, "rowIDs": rows,
                                     "columnIDs": cols}))
        for s, cols, vals in ref.values_by_shard():
            jobs.append(("v", {"shard": s, "columnIDs": cols,
                               "values": vals}))

        def send(job):
            field, req = job
            body = json.dumps({k: v if k == "shard" else v.tolist()
                               for k, v in req.items()})
            srv.request("POST", f"/index/{INDEX}/field/{field}/import", body)

        in_threads(4, send, jobs)
        self.facts["import_requests"] = len(jobs)
        self.facts["import_s"] = round(time.monotonic() - t0, 2)

    def ask(self, srv, name, pql, want, got_of=lambda r: r):
        self.asked[name] = (pql, want, got_of)
        t0 = time.monotonic()
        got = got_of(srv.query(pql)[0])
        self.facts.setdefault("ask_s", {})[name] = round(
            time.monotonic() - t0, 3)
        self.check(name, got == want, {"pql": pql, "got": str(got)[:200],
                                       "want": str(want)[:200]})
        return got

    def singles(self, srv, ref):
        R = ref.rows
        b = ref.bits
        f0, f1, f2 = b["f", 0], b["f", 1], b["f", 2]
        g0, g1, g2 = b["g", 0], b["g", 1], b["g", 2]
        self.ask(srv, "count_row", "Count(Row(f=0))", popcount(f0))
        self.ask(srv, "count_intersect",
                 "Count(Intersect(Row(f=1), Row(g=2)))",
                 popcount(f1 & g2))
        self.ask(srv, "count_union3",
                 "Count(Union(Row(f=0), Row(g=1), Row(f=2)))",
                 popcount(f0 | g1 | f2))
        self.ask(srv, "count_difference",
                 "Count(Difference(Row(f=0), Row(g=0)))", popcount(f0 & ~g0))
        self.ask(srv, "count_xor",
                 "Count(Xor(Row(f=1), Row(g=1)))",
                 popcount(f1 ^ g1))

        # Filtered TopN: the unfiltered form is answered from the rank
        # cache with no device work. Compared as (id, count) pairs against
        # the reference's counts, so a tie at the cut cannot fail it.
        inter = {r: popcount(b["f", r] & g0) for r in range(R)}
        top = sorted(inter.values(), reverse=True)[:5]
        top = [c for c in top if c > 0]

        def pairs_ok(pairs):
            return ([p["count"] for p in pairs] == top
                    and all(inter.get(p["id"]) == p["count"] for p in pairs))

        self.ask(srv, "topn_filtered", "TopN(f, Row(g=0), n=5)", True,
                 got_of=pairs_ok)

        in_f1 = ref.member(("f", 1), ref.v_cols)
        self.ask(srv, "bsi_sum", "Sum(Row(f=1), field=v)",
                 {"value": int(ref.v_vals[in_f1].sum()),
                  "count": int(in_f1.sum())})
        lo = int(ref.v_vals.min())
        self.ask(srv, "bsi_min", "Min(field=v)",
                 {"value": lo, "count": int((ref.v_vals == lo).sum())})
        in_g0 = ref.v_vals[ref.member(("g", 0), ref.v_cols)]
        hi = int(in_g0.max())
        self.ask(srv, "bsi_max", "Max(Row(g=0), field=v)",
                 {"value": hi, "count": int((in_g0 == hi).sum())})
        self.ask(srv, "bsi_range_gt", "Count(Range(v > 40000))",
                 int((ref.v_vals > 40000).sum()))
        self.ask(srv, "bsi_range_between", "Count(Range(v >< [1000, 20000]))",
                 int(((ref.v_vals >= 1000) & (ref.v_vals <= 20000)).sum()))

        # The sparsest row, column for column.
        self.ask(srv, "row_columns", f"Row(f={R - 1})",
                 ref.cols["f", R - 1].tolist(),
                 got_of=lambda r: r["columns"])

        # An acknowledged write, read back through the delta refresh of the
        # plane the first Count left resident: exactly one more.
        key = ("f", min(5, R - 1))
        col = ref.n - 1
        while ref.member(key, np.array([col], dtype=np.uint32))[0]:
            col -= 1
        before = self.ask(srv, "count_before_set", f"Count(Row(f={key[1]}))",
                          popcount(b[key]))
        self.ask(srv, "set_acknowledged", f"Set({col}, f={key[1]})", True)
        ref.set_bit(key, col)
        self.ask(srv, "count_after_set", f"Count(Row(f={key[1]}))",
                 before + 1)

    def waves(self, srv, ref):
        """Concurrent same-signature Counts until some coalesced into the
        batched program — the one place the gather kernel runs. Each query
        is asked once, so no memo can stand in for the device."""
        R = ref.rows
        pairs = [(a, c) for a in range(R) for c in range(R)]
        ops = (("Intersect", np.bitwise_and), ("Union", np.bitwise_or),
               ("Xor", np.bitwise_xor))
        plan = [(op, fn, pairs[i:i + WAVE]) for op, fn in ops
                for i in range(0, len(pairs), WAVE)][:MAX_WAVES]
        base = srv.vars()["batcher"]["coalesced"]
        coalesced = 0
        wrong = []
        t0 = time.monotonic()
        n_waves = 0
        for op, fn, chunk in plan:
            n_waves += 1
            got = in_threads(
                WAVE_THREADS,
                lambda p: srv.query(
                    f"Count({op}(Row(f={p[0]}), Row(g={p[1]})))")[0],
                chunk)
            for (a, c), n in zip(chunk, got):
                want = popcount(fn(ref.bits["f", a], ref.bits["g", c]))
                if n != want:
                    wrong.append((op, a, c, n, want))
            coalesced = srv.vars()["batcher"]["coalesced"] - base
            self.say(f"wave {n_waves} ({op}, {len(chunk)} queries): "
                     f"coalesced so far {coalesced}")
            if coalesced > 0 or wrong:
                break
        self.facts["waves"] = n_waves
        self.facts["waves_s"] = round(time.monotonic() - t0, 2)
        self.check("wave_answers", not wrong, wrong[:5])
        self.check("wave_coalesced", coalesced > 0,
                   f"batcher.coalesced did not grow in {n_waves} waves")

    def ladder(self, v, budgets_at_start):
        """The /debug/vars verdict: the device served, and nothing else."""
        ec, plane, batcher = v["engine_cache"], v["device_plane"], v["batcher"]
        dev = v["device"]
        self.check("device", dev["platform"] == "tpu", dev["platform"])
        bad = ladder_nonzero(ec)
        self.check("ladder_counters_zero", not bad, bad)
        flat = {k: ec.get(k) for k in MUST_GROW if not ec.get(k)}
        self.check("device_counters_grew", not flat, flat)
        self.check(
            "device_plane_closed",
            plane.get("dispatch_failures") == 0
            and plane.get("plane_state") == "closed"
            and plane.get("sigs_open") == 0,
            {k: plane.get(k) for k in
             ("dispatch_failures", "plane_state", "sigs_open")})
        self.check("batcher_no_fallbacks", batcher.get("fallbacks") == 0,
                   batcher.get("fallbacks"))
        self.check("budgets_unchanged",
                   v["engine_budgets"] == budgets_at_start,
                   {"start": budgets_at_start, "end": v["engine_budgets"]})
        self.check("native_loaded", v["native"]["loaded"], v["native"])
        if dev["platform"] == "tpu":
            # The kernel is chosen by platform, so only there can the
            # coalesced waves be held to having gone through it.
            self.check("wave_used_gather_kernel",
                       ec.get("gather_kernel_dispatches", 0) > 0,
                       ec.get("gather_kernel_dispatches"))
        if dev["n_devices"] > 1:
            used = [d["bytes_in_use"] or 0 for d in dev["devices"]]
            self.check("every_device_holds_planes",
                       min(used) > 0 and max(used) <= 2 * min(used), used)
        self.facts["counters"] = {
            "engine_cache": {k: ec.get(k) for k in LADDER_ZERO + MUST_GROW
                             + ("gather_kernel_dispatches",
                                "leaf_misses", "stack_misses",
                                "leaf_delta_hits", "stack_delta_hits",
                                "leaf_evictions", "stack_evictions")},
            "device_plane": {k: plane.get(k) for k in
                             ("dispatch_failures", "plane_state",
                              "sigs_open")},
            "batcher": {k: batcher.get(k) for k in
                        ("enqueued", "launches", "coalesced", "fallbacks")},
            "engine_budgets": v["engine_budgets"],
        }

    def restarted(self, srv, device_before):
        """Restart durability — and the start that can hit the compile
        cache. Three of the first start's questions again; the Count
        includes the bit Set before the restart."""
        for name in ("count_after_set", "count_intersect", "bsi_sum"):
            self.ask(srv, "restart_" + name, *self.asked[name])
        v = srv.vars()
        same = [{k: d[k] for k in ("platform", "device_kind", "n_devices")}
                for d in (device_before, v["device"])]
        self.check("restart_same_device", same[0] == same[1], same)
        bad = ladder_nonzero(v["engine_cache"])
        self.check("restart_ladder_counters_zero", not bad, bad)

    # ------------------------------------------------------------- run

    def run(self):
        args = self.args
        tmp = tempfile.mkdtemp(prefix="chip_smoke_")
        log_path = os.path.join(tmp, "server.log")
        srv = Server(os.path.join(tmp, "data"), log_path)
        device_vars = None
        try:
            t0 = time.monotonic()
            ref = Reference(args.shards, args.rows, args.seed)
            self.facts["reference_s"] = round(time.monotonic() - t0, 2)
            self.facts["bits"] = {
                fld: int(sum(len(ref.cols[fld, r]) for r in range(ref.rows)))
                for fld in ("f", "g")}
            self.facts["values"] = int(len(ref.v_cols))
            self.say(f"reference built: {self.facts['bits']} bits, "
                     f"{self.facts['values']} values")

            self.facts["start_s"] = round(srv.start(), 2)
            v = srv.vars()
            device_vars = v["device"]
            budgets = v["engine_budgets"]
            self.say(f"server up on {device_vars['platform']} "
                     f"({device_vars['device_kind']} x "
                     f"{device_vars['n_devices']})")
            self.load(srv, ref)
            self.say(f"imported in {self.facts['import_s']}s")
            t0 = time.monotonic()
            self.singles(srv, ref)
            self.facts["singles_s"] = round(time.monotonic() - t0, 2)
            self.waves(srv, ref)
            v = srv.vars()
            device_vars = v["device"]
            self.ladder(v, budgets)

            self.check("server_stopped", srv.stop(),
                       f"no exit within {STOP_TIMEOUT:.0f}s of SIGTERM")
            self.facts["restart_s"] = round(srv.start(), 2)
            t0 = time.monotonic()
            self.restarted(srv, device_vars)
            self.facts["restart_asks_s"] = round(time.monotonic() - t0, 2)
            cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
                os.path.join(HERE, ".jax_cache")
            n_cached = len(os.listdir(cache_dir)) \
                if os.path.isdir(cache_dir) else 0
            self.facts["compile_cache"] = {"dir": cache_dir,
                                           "entries": n_cached}
            self.check("compile_cache_not_empty", n_cached > 0, cache_dir)
            self.check("completed", True)
        except Exception as e:
            # Not swallowed: recorded as a failed check, so the run cannot
            # exit 0. The server is still stopped below.
            self.check("completed", False, f"{type(e).__name__}: {e}")
        finally:
            stopped = srv.stop()
            if "server_stopped" not in self.checks or not stopped:
                self.check("server_stopped", stopped, "killed after SIGTERM")
            ok = bool(self.checks) and all(self.checks.values())
            if not ok and os.path.exists(log_path):
                with open(log_path, "rb") as f:
                    tail = f.read()[-6000:].decode("utf-8", "replace")
                print("chip_smoke: ---- server log tail ----\n" + tail,
                      file=sys.stderr, flush=True)
            shutil.rmtree(tmp, ignore_errors=True)

        dv = device_vars or {}
        cut = {k: {"full": full, "ran": ran} for k, full, ran in (
            ("shards", FULL_SHARDS, args.shards),
            ("rows", FULL_ROWS, args.rows)) if full != ran}
        return ok, {
            "ok": ok,
            "device": {"platform": dv.get("platform"),
                       "kind": dv.get("device_kind"),
                       "count": dv.get("n_devices")},
            "device_vars": dv,
            "sizes": {"shards": args.shards, "rows": args.rows,
                      "shard_width": SHARD_WIDTH,
                      "columns": args.shards * SHARD_WIDTH,
                      "seed": args.seed},
            "cut": cut,
            "checks": self.checks,
            "failed": self.detail,
            "facts": self.facts,
            "claim": None,
        }


def result_line(summary):
    """The last line of a passing run's stdout: these keys and no others."""
    dev = summary["device"]
    return json.dumps({
        "ok": summary["ok"],
        "device": {"platform": str(dev["platform"]),
                   "kind": str(dev["kind"]),
                   "count": int(dev["count"])}})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shards", type=int, default=FULL_SHARDS)
    ap.add_argument("--rows", type=int, default=FULL_ROWS)
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args()
    if args.shards < 1 or args.rows < 3:
        ap.error("need --shards >= 1 and --rows >= 3")
    if not os.path.isdir(os.path.join(HERE, "pilosa_tpu")):
        print("chip_smoke: no pilosa_tpu package beside this script; "
              "nothing to run", file=sys.stderr)
        return 2
    ok, summary = Smoke(args).run()
    if not ok:
        print(json.dumps(summary), file=sys.stderr, flush=True)
        return 1
    print(json.dumps(summary), flush=True)
    print(result_line(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
