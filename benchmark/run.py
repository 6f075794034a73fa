#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served path.

    python3 benchmark/run.py --workload <config>.<mix> --seed N --seconds S --trace 0|1

This process is stdlib + numpy and never imports jax or pilosa_tpu: the
chip belongs to the `python -m pilosa_tpu.cli server` child. It starts
that child and meanwhile draws the data and the reference from --seed,
loads the index over HTTP, warms this cell's templates until nothing new
compiles and no plane is still cold (all of that is `setup_s`), drives the
mix's closed loop for --seconds, then kills the server (SIGKILL), starts it
again on what it left on disk and reads the written rows back, compares
every answer of the window and of the read-back with the reference and
prints the result as the last line of stdout. There is no CPU fallback: a server that does not report
`tpu` with the chips the cell asks for ends the run with exit status 3 and
no result. Alone in a directory (no pilosa_tpu beside benchmark/): 2.

Everything a cell is made of is found by name: configs/<config>.json,
traffic/<mix>.json, layers/<metric>.py, and draws/<draw>.py for a field
drawn by a law that generate.py does not have. See PERF.md, "How a cell
is added".
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import client  # noqa: E402
import generate  # noqa: E402
import loader  # noqa: E402
import reference  # noqa: E402

WARM_ROUND_S = 1.5
WARM_QUIET_ROUNDS = 3
WARM_MAX_ROUNDS = 60
WARM_SWEEP_MAX_S = 600.0
PROFILE_WINDOW_S = 5.0
# The profiler's tracer takes hold a fraction of a second after the capture
# was asked for (0.2 s by the count of answers); the requests answered in
# the capture are counted from this long after the asking.
CAPTURE_SETTLE_S = 1.5
# The kernel probe's capture: long enough for its waves under the
# profiler's tracer (2.3 s as a rule, 5.9 s seen), begun PROBE_LEAD_S before
# the first wave. Only waves answered PROBE_MARGIN_S before its end count.
PROFILE_PROBE_S = 10.0
PROBE_LEAD_S = 1.5
PROBE_MARGIN_S = 0.25
# stack_misses is left out: every coalesced batch stacks the planes it names,
# so a mix of changing rows never stops missing there. That is the window's
# own work, not warm-up.
WARM_COUNTERS = ("fn_cache_builds", "leaf_misses")


class NoChip(Exception):
    """The server does not run on what the cell asks for."""


def say(msg):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name, manifest_path=None):
    """(manifest, cell, config file's content, mix file's content)."""
    manifest = read_json(manifest_path or os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = read_json(REPO, config["file"])
    mix = read_json(HERE, "traffic", cell["traffic"] + ".json")
    return manifest, cell, cfg, mix


def metric_applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_layer(name):
    path = os.path.join(HERE, "layers", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def spans_of(trace):
    """Every span of one traced query, at any depth."""
    stack = list(trace.get("spans", ()))
    while stack:
        s = stack.pop()
        yield s
        stack.extend(s.get("children", ()))


def slowest_spans(traces, top=8):
    """The longest span of each name among the traced queries, with its
    tags and its query: what a stall inside the window was."""
    worst = {}
    for t in traces:
        for s in spans_of(t):
            if s["name"] not in worst or s["dur_ms"] > worst[s["name"]][0]:
                worst[s["name"]] = (s["dur_ms"], s.get("tags"), t.get("pql"))
    return [{"name": n, "dur_ms": d, "tags": tags, "pql": (pql or "")[:100]}
            for n, (d, tags, pql) in sorted(worst.items(),
                                            key=lambda kv: -kv[1][0])[:top]]


class Context:
    """What a layer's reader may read. A reader returns None where it
    finds nothing to read, and the metric is then left out of the line."""

    def __init__(self, **kw):
        self.cfg = self.mix = self.peaks = None
        self.before = self.after = None     # /debug/vars around the window
        self.traces = []                    # /debug/traces of the window
        self.profile = None                 # xplane.reduce of the window
        self.probe = None                   # the kernel probe's record
        self.floor_ms = None
        self.capture_ops_per_s = None       # answers a second, in the capture
        self.__dict__.update(kw)

    def delta(self, group, key):
        """Growth of one /debug/vars counter over the window."""
        try:
            return self.after[group][key] - self.before[group][key]
        except (KeyError, TypeError):
            return None

    def span_mean_ms(self, *names):
        """Mean over the window's traced queries of the summed duration of
        the named spans, at any depth; None where no query has any."""
        totals = []
        for t in self.traces:
            mine = [s["dur_ms"] for s in spans_of(t) if s["name"] in names]
            if mine:
                totals.append(sum(mine))
        return statistics.fmean(totals) if totals else None


def quantile(sorted_values, q):
    """Nearest-rank quantile of an ascending list."""
    k = math.ceil(q * len(sorted_values)) - 1
    return sorted_values[max(0, min(len(sorted_values) - 1, k))]


def warm(srv, cfg, streams, replay):
    """This cell's own mix: first the sweep (`generate.Requests.sweep`:
    every row its templates can name, and every question of a template
    that has few), then closed-loop rounds until no program was built and
    no plane was cold for WARM_QUIET_ROUNDS rounds in a row."""
    def counters():
        ec = srv.vars()["engine_cache"]
        return tuple(ec.get(k, 0) for k in WARM_COUNTERS)

    def drive(some_streams, seconds):
        sent, _, _ = client.closed_loop(srv.port, cfg["index"], some_streams,
                                        seconds)
        for k, mine in enumerate(sent):
            bad = [s for s in mine if s.status != 200]
            if bad:
                raise RuntimeError(f"warm-up request failed: {bad[0].pql!r} "
                                   f"-> HTTP {bad[0].status}")
            replay[k].extend(mine)

    drive([generate.Fixed(s.sweep(len(streams))) for s in streams],
          WARM_SWEEP_MAX_S)
    last, quiet, rounds = counters(), 0, 0
    while quiet < WARM_QUIET_ROUNDS:
        if rounds >= WARM_MAX_ROUNDS:
            raise RuntimeError(f"still compiling or gathering after {rounds} "
                               f"warm rounds: {dict(zip(WARM_COUNTERS, last))}")
        drive(streams, WARM_ROUND_S)
        rounds += 1
        now = counters()
        quiet = quiet + 1 if now == last else 0
        last = now
    return rounds


def judge(ref, replay, sent, probe_answers=(), reread=()):
    """The comparison that decides `correct`: every answer of the window
    against the reference, client by client in the order sent. The Sets of
    the warm-up (`replay`) come first, being part of the state, then the
    probe's answers, and last what the restarted server read back
    (`reread`), held to the state after every acknowledged Set. Returns
    the numbers compared, by name, and `first_wrong`, the first wrong
    answer of the window or None. A TopN read back after the crash is
    counted apart (`topn_after_crash`: wrong, asked) and not held: the
    program loses its rank caches to a kill -9 (PERF.md, Open questions)."""
    wrong = unanswered = 0
    first_wrong = None
    ref.expect_sets(s.pql for part in (replay, sent) for mine in part
                    for s in mine)
    ref.expect_sets(pql for pql, _ in probe_answers)
    for k, mine in enumerate(sent):
        for s in replay[k]:
            if s.pql.startswith("Set("):
                ref.answer(s.pql)
        for s in mine:
            want = ref.answer(s.pql)
            if s.status != 200:
                unanswered += 1
            elif not reference.agrees(s.result, want):
                wrong += 1
                if first_wrong is None:
                    first_wrong = {"pql": s.pql, "got": str(s.result)[:200],
                                   "want": str(want)[:200]}
    probe_wrong = sum(not reference.agrees(got, ref.answer(pql))
                      for pql, got in probe_answers)
    lost, topn = 0, [0, 0]
    for pql, got in reread:
        ok = reference.agrees(got, ref.answer(pql))
        if pql.startswith("TopN("):
            topn[0] += not ok
            topn[1] += 1
        elif not ok:
            lost += 1
            say("after the restart: " + json.dumps(
                {"pql": pql, "got": str(got)[:200],
                 "want": str(ref.answer(pql))[:200]}))
    return {"wrong_answers": wrong, "unanswered": unanswered,
            "probe_wrong": probe_wrong, "lost_over_restart": lost,
            "topn_after_crash": topn, "first_wrong": first_wrong}


def read_back(srv, cfg, mix, sent, disk_fault=None):
    """Kill the server as a crash would (SIGKILL: nothing is flushed on the
    way out), start it again on what it left on disk, and ask again: the
    count of every writer-owned row, which holds every Set that was
    acknowledged, and each client's last Count and last TopN of the window.
    Returns [(pql, result)]."""
    srv.kill()
    if disk_fault:
        disk_fault(srv.data_dir)
    srv.start()
    asks = [f"Count(Row({field}={r}))"
            for field, (lo, hi) in mix.get("writer_rows", {}).items()
            for r in range(lo, hi + 1)]
    for mine in sent:
        for call in ("Count(", "TopN("):
            asks += [s.pql for s in reversed(mine)
                     if s.pql.startswith(call)][:1]

    def ask(pql):
        got = srv.request("POST", f"/index/{cfg['index']}/query", pql)
        return pql, got["results"][0]

    return client.in_threads(4, ask, asks)


def grown(before, after, groups=("engine_cache", "batcher", "executor")):
    """The /debug/vars counters of those groups that moved over the
    window, each with by how much: what a slow run did that the others did
    not (a plane first touched, an eviction, a program built)."""
    out = {}
    for g in groups:
        for k, v in after.get(g, {}).items():
            was = before.get(g, {}).get(k)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and isinstance(was, (int, float)) and v != was:
                out[f"{g}.{k}"] = v - was
    return out


def by_template(mix, window):
    """{template: [answers, median ms, 95th percentile ms]} of the window:
    which family of requests a run's tail sat in."""
    out = {}
    for k, t in enumerate(mix["templates"]):
        ms = sorted(1000.0 * (s.end - s.start) for s in window
                    if s.template == k and s.status == 200)
        if ms:
            out[t["name"]] = [len(ms), quantile(ms, 0.50), quantile(ms, 0.95)]
    return out


def peak_of(peaks, kind):
    """The table's entry for a device kind; an unknown kind is an error,
    not a default."""
    if kind not in peaks:
        raise KeyError(f"benchmark/peaks.json has no device kind {kind!r}")
    return peaks[kind]


def profile_later(srv, delay, seconds, out):
    """A side thread that asks the server for a profiler capture."""
    def work():
        time.sleep(delay)
        out["seconds"] = seconds
        out["asked_at"] = time.monotonic()
        try:
            out["path"] = srv.request(
                "POST", f"/debug/profile?seconds={seconds}")["path"]
        except Exception as e:  # the traced metrics then read nothing
            out["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=work)
    t.start()
    return t


def kernel_probe(srv, cfg, mix, seed):
    """Waves of concurrent two-leaf Counts over leaf planes that differ in
    every query, under a profiler capture. Whatever program serves a wave
    has to read each of its planes from HBM at least once; the reader
    divides that least time by the device's busy time. Before each wave
    one Set on a writer-owned row makes every memo entry stale.

    The mix's `probe.pql` is formatted with `i` (the question's place in
    its wave), `j` (a permutation of the places) and `w` (the wave's
    number, from 0). A probe whose answers that Set cannot stale (a Sum
    that reads no writer-owned row) must name `{w}`, so that no wave is
    answered from the memo; a string that does not name it ignores it."""
    # A probe over answers that its Set cannot stale names {w}.
    spec = mix.get("probe")
    if not spec:
        return None
    rng = random.Random(f"{seed}/probe")
    width, waves = spec["width"], spec["waves"]
    writer = next(iter(mix.get("writer_rows", {}).items()), None)
    n_cols = cfg["shards"] * generate.SHARD_WIDTH
    prof = {}
    side = profile_later(srv, 0.0, PROFILE_PROBE_S, prof)
    time.sleep(PROBE_LEAD_S)    # the capture has to run before the waves
    answers = []                # (pql, result), judged with the window's
    ends = []                   # when each wave's last answer came

    def ask(pql):
        got = srv.request("POST", f"/index/{cfg['index']}/query", pql)
        return pql, got["results"][0]

    t0 = time.monotonic()
    for w in range(waves):
        if writer:
            answers.append(ask(
                f"Set({rng.randrange(n_cols)}, {writer[0]}={writer[1][0]})"))
        perm = list(range(width))
        rng.shuffle(perm)
        answers += client.in_threads(
            width, ask,
            [spec["pql"].format(i=i, j=perm[i], w=w) for i in range(width)])
        ends.append(time.monotonic())
    waves_s = time.monotonic() - t0
    side.join()
    # The server starts its trace after it has the request and keeps it for
    # the seconds asked, so a wave answered before `asked_at` + those
    # seconds lies wholly inside the capture. A wave cut off by its end is
    # left out of the bytes and stays in the busy time: the share then
    # reads low, never over what the device did.
    closes = prof["asked_at"] + PROFILE_PROBE_S - PROBE_MARGIN_S
    return {"waves": waves, "waves_inside": sum(e <= closes for e in ends),
            "width": width, "leaves": spec["leaves"], "waves_s": waves_s,
            "capture": prof, "answers": answers}


def reduce_profile(capture, sample_out=None):
    """xplane.py over one capture, in a process that cannot take a chip.
    `sample_out` also keeps a short recorded stretch of it (the tests')."""
    if not capture or "path" not in capture:
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "xplane.py"), capture["path"]]
        + ([sample_out] if sample_out else []),
        env=env, capture_output=True, text=True, timeout=240)
    if out.returncode != 0:
        say(f"xplane.py failed: {out.stderr[-500:]}")
        return None
    return json.loads(out.stdout.strip().splitlines()[-1])


def ops_in_capture(window, capture):
    """Answers a second while the profiler's tracer ran: those that came
    between CAPTURE_SETTLE_S after the capture was asked for and its end.
    None where none came."""
    if "asked_at" not in capture:
        return None
    lo = capture["asked_at"] + CAPTURE_SETTLE_S
    hi = capture["asked_at"] + capture["seconds"]
    n = sum(1 for s in window if s.status == 200 and lo < s.end <= hi)
    return n / (hi - lo) if n and hi > lo else None


def traced_values(manifest, cell, result, device, facts, window, t0,
                  capture_from, capture, ctx):
    """A traced run's per-layer values, by each metric's reader; fills in
    the device's busy time, the breakdown and the facts on the way."""
    probe = ctx.probe
    ctx.profile = reduce_profile(capture)
    if probe:
        probe["profile"] = reduce_profile(probe.pop("capture"))
    values = {m["name"]: load_layer(m["name"]).read(ctx)
              for m in manifest["per_layer"]
              if metric_applies(m, cell["name"])}
    if ctx.profile:
        device["busy_s"] = ctx.profile["busy_s"]
        device["window_s"] = ctx.profile["window_s"]
        result["breakdown"] = {"device_ops": ctx.profile["device_ops"],
                               "idle_gaps": ctx.profile["idle_gaps"]}
    if capture_from > 0:
        facts["ops_per_s_before_capture"] = sum(
            1 for s in window
            if s.status == 200 and s.end - t0 <= capture_from) / capture_from
    facts["ops_per_s_in_capture"] = ctx.capture_ops_per_s
    facts["slowest_spans"] = slowest_spans(ctx.traces)
    facts["probe"] = probe
    facts["profile"] = ctx.profile and {
        k: v for k, v in ctx.profile.items() if k != "by_name"}
    if probe and probe.get("profile"):
        probe["gather_kernel_s"] = probe["profile"].pop("by_name").get(
            "batched_gather_expr_count")
    return values


def run_cell(args, require_tpu=True, tamper=None, server_env=None,
             manifest_path=None, disk_fault=None):
    """The whole run; returns the result line as a dict. `require_tpu`,
    `tamper`, `server_env`, `manifest_path` and `disk_fault` (called with
    the data directory between the kill and the restart) are the tests'
    hooks: the command line has none of them."""
    manifest, cell, cfg, mix = find_cell(args.workload, manifest_path)
    trace = bool(args.trace)
    tmp = tempfile.mkdtemp(prefix="bench_")
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(HERE, ".cache", "jax"))
    env.update(server_env or {})
    flags = list(cfg.get("server_flags", ()))
    if trace:
        flags += ["--obs-sample-rate", "1", "--obs-ring-size", "200000"]
    srv = client.Server(REPO, os.path.join(tmp, "data"),
                        os.path.join(tmp, "server.log"), flags, env)
    facts = {}
    try:
        started = {}
        starter = threading.Thread(
            target=lambda: started.update(s=srv.start()))
        starter.start()
        try:
            t = time.monotonic()
            data = generate.Data(cfg, args.seed)
            ref = reference.build(data, mix)
            facts["generate_s"] = time.monotonic() - t
        finally:
            starter.join()
        if "s" not in started:
            raise RuntimeError("the server child did not start:\n"
                               + srv.log_tail())
        facts["server_start_s"] = started["s"]
        v = srv.vars()
        dev = v["device"]
        if require_tpu and (dev["platform"] != "tpu"
                            or dev["n_devices"] < cell["chips"]):
            raise NoChip(f"the server runs on {dev['platform']} x "
                         f"{dev['n_devices']}; the cell asks for tpu x "
                         f"{cell['chips']}")
        budgets = v["engine_budgets"]

        t = time.monotonic()
        loader.create_schema(srv, cfg)
        facts["load_bytes"] = loader.load(srv, cfg, data)
        facts["load_s"] = time.monotonic() - t
        facts["bits"] = data.bits()

        streams = [generate.Requests(mix, cfg, args.seed, k)
                   for k in range(mix["clients"])]
        replay = [[] for _ in streams]
        t = time.monotonic()
        facts["warm_rounds"] = warm(srv, cfg, streams, replay)
        facts["warm_s"] = time.monotonic() - t
        floor = client.floor_ms(srv.port) if trace else None
        before = srv.vars()
        setup_s = time.monotonic() - T_PROCESS

        # The profiler's Python tracer slows the server several times
        # over, so the capture takes the window's last seconds and the
        # spans are read from the requests before it.
        capture, side = {}, None
        capture_from = max(0.0, args.seconds - PROFILE_WINDOW_S - 1.0)
        if trace:
            side = profile_later(srv, capture_from,
                                 min(PROFILE_WINDOW_S, args.seconds), capture)
        wall0 = time.time()
        sent, t0, t1 = client.closed_loop(
            srv.port, cfg["index"], streams, args.seconds, tamper=tamper)
        wall1 = time.time()
        after = srv.vars()
        if side:
            side.join()

        probe, traces = None, []
        if trace:
            probe = kernel_probe(srv, cfg, mix, args.seed)
            got = srv.request("GET", "/debug/traces?limit=200000")["traces"]
            traces = [x for x in got if wall0 <= x.get("start", 0) <= wall1]
            calm = [x for x in traces if x["start"] < wall0 + capture_from]
            facts["traces"], facts["traces_before_capture"] = (
                len(traces), len(calm))
            traces = calm or traces
            after_all = srv.vars()
        else:
            after_all = after
        peak = max((d.get("peak_bytes_in_use") or 0)
                   for d in after_all["device"]["devices"])
        t = time.monotonic()
        reread = read_back(srv, cfg, mix, sent, disk_fault)
        facts["restart_s"] = time.monotonic() - t
        stopped = srv.stop()

        t = time.monotonic()
        window = [s for mine in sent for s in mine]
        judged = judge(ref, replay, sent,
                       probe.pop("answers") if probe else (), reread)
        facts["check_s"] = time.monotonic() - t
        facts["topn_after_crash"] = judged.pop("topn_after_crash")
        first_wrong = judged.pop("first_wrong")
        if first_wrong:
            say(f"first wrong answer: {json.dumps(first_wrong)}")

        ec = after_all["engine_cache"]
        ladder = client.ladder_nonzero(ec)
        if ladder:
            say(f"ladder counters not 0: {ladder}")
        checks = {
            **{name: [got, 0] for name, got in judged.items()},
            "ladder_nonzero": [len(ladder), 0],
            "batcher_fallbacks": [after_all["batcher"].get("fallbacks"), 0],
            "budgets_changed": [int(after_all["engine_budgets"] != budgets),
                                0],
            "stop_timed_out": [int(not stopped), 0],
            "not_on_tpu": [int(after_all["device"]["platform"] != "tpu"), 0],
        }
        correct = all(v[0] == v[1] for v in checks.values())

        done = sorted(s.end - s.start for s in window if s.status == 200)
        in_window = sum(1 for s in window
                        if s.status == 200 and s.end <= t1)
        e2e = {
            "ops_per_s": in_window / args.seconds,
            "latency_p50_ms": 1000.0 * quantile(done, 0.50) if done else None,
            "latency_p95_ms": 1000.0 * quantile(done, 0.95) if done else None,
            "setup_s": setup_s,
        }
        device = {"platform": dev["platform"], "kind": dev["device_kind"],
                  "count": dev["n_devices"], "memory_peak_bytes": peak}
        result = {"correct": correct, "attempted": len(window),
                  "failed": judged["wrong_answers"] + judged["unanswered"]}
        units = {m["name"]: m["unit"]
                 for m in manifest["end_to_end"] + manifest["per_layer"]}

        if trace:
            peaks = read_json(HERE, "peaks.json")
            if require_tpu:
                peak_of(peaks, device["kind"])
            values = traced_values(
                manifest, cell, result, device, facts, window, t0,
                capture_from, capture,
                Context(cfg=cfg, mix=mix, before=before, after=after,
                        traces=traces, floor_ms=floor, probe=probe,
                        capture_ops_per_s=ops_in_capture(window, capture),
                        peaks=peaks, device=device))
        else:
            values = {m["name"]: e2e[m["name"]]
                      for m in manifest["end_to_end"]
                      if metric_applies(m, cell["name"])}
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in values.items() if v is not None}
        result["device"] = device
        facts["window_counters"] = grown(before, after)
        facts["latency_ms_by_template"] = by_template(mix, window)
        # Answers by 5 s slice of the window: a stall shows as a thin slice,
        # a slow machine as a thin run.
        slices = [0] * math.ceil(args.seconds / 5.0)
        for s in window:
            if s.status == 200 and s.end < t1:
                slices[int((s.end - t0) / 5.0)] += 1
        facts["answers_by_5s"] = slices
        result["facts"] = facts
        result["checks"] = checks
        return result
    finally:
        if not srv.stop():
            say("server killed after SIGTERM")
        if sys.exc_info()[0] is not None:
            say("---- server log tail ----\n" + srv.log_tail())
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "pilosa_tpu")):
        say("no pilosa_tpu package beside benchmark/; nothing to run")
        return 2
    try:
        result = run_cell(args)
    except NoChip as e:
        say(f"not a measurement: {e}")
        return 3
    for name, (got, limit) in result["checks"].items():
        say(f"check {name}: {got} (limit {limit})")
    say(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
