"""Trace recorder core: spans, context propagation, ring, slow-query log.

Threading model: the ACTIVE trace rides a contextvar installed at handler
ingress, so serving-path stages (parse, admission, batching, fan-out,
gathers) record spans without any plumbing — obs.span("name") is a no-op
singleton when nothing is active, which is the whole disabled-path cost.
Code that hops threads (the executor's hedged remote legs) captures the
Trace object once and calls trace.span() directly; Trace state is
lock-protected so spans may complete on any thread.

Cross-node: the coordinator stamps X-Pilosa-Trace on forwarded requests;
the peer adopts the id, records its own spans, and returns a size-bounded
JSON summary in X-Pilosa-Trace-Summary. The caller splices that summary
as CHILD spans of its remote:<peer> span. Child offsets stay relative to
the hop (the peer's own trace start), never converted through wall
clocks, so peer clock skew cannot corrupt the tree.

The tree: every span has an `id` (unique in its trace) and a `parent`, the
span that was open on the same context when it began; the open span rides
a second contextvar. A landed trace reckons each span's `self_ms` when it
is first read: its length less what its direct children cover, so a
layer's own cost can be told from what it waited for below it.

CPU time: a span also reads its thread's CPU clock where it begins and
ends, so `cpu_ms` says how much of `dur_ms` its thread ran and
`self_ms - self_cpu_ms` how long it stood: parked, or runnable without
the interpreter lock (docs/observability.md, "What a span's CPU says").
That clock is a system call, and on some hosts a slow one (5.8 us a read
on the TPU hosts of PERF.md's runs, 0.25 us elsewhere), so the recorder
times it once and reads it in one trace of every `cpu_period`, all of
that trace's spans or none: the reads then cost a span CPU_READ_BUDGET_S
on average whatever the host.

The profiler's clock: while a /debug/profile capture runs (`capturing`),
every span also opens an annotation of its name in the profiler's trace,
through the factory the server handed in at start-up (obs/ stays jax-free).
Outside a capture that costs one flag read per span.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import random
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..stats import Histogram

_current: contextvars.ContextVar[Optional["Trace"]] = contextvars.ContextVar(
    "pilosa_tpu_trace", default=None
)
# The innermost span open on this context: the parent of the next one.
_open: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "pilosa_tpu_span", default=None
)

# jax.profiler.TraceAnnotation, handed in by the server at start-up; None
# in a process that never starts one (obs/ imports no jax).
_annotation = None
# True while a /debug/profile capture runs: the one flag a span reads.
capturing = False
# The calling thread's CPU time in ns; None on a platform without one.
THREAD_CPU_NS = getattr(time, "thread_time_ns", None)
_thread_ident = threading.get_ident
# What a span's two reads of the CPU clock may cost on average, in
# seconds, against some 3 us to record the span (docs/observability.md,
# "Overhead"): where two reads cost more, fewer traces read the clock.
CPU_READ_BUDGET_S = 1e-6


def cpu_sample_period(cpu_clock, clock) -> int:
    """In one trace of how many the CPU clock is read, so that a span's
    two reads cost CPU_READ_BUDGET_S on average: 1 (every trace) where
    the clock is cheap. The cost is the least of a few short runs: a run
    that another thread cut into says too much, never too little."""
    if cpu_clock is None:
        return 1
    reads, cost = 16, float("inf")
    for _ in range(5):
        t0 = clock()
        for _ in range(reads):
            cpu_clock()
        cost = min(cost, (clock() - t0) / reads)
    return max(1, int(2.0 * cost / CPU_READ_BUDGET_S))
# Spans that only park their thread. In the profiler's trace their name
# ends in `wait`, which is how a reader of idle gaps tells a thread that
# waited from the one that worked (`engine.device_wait` says so itself).
PARKED_SPANS = frozenset({"batch.hold", "cdc.tail"})


def set_annotation(factory) -> None:
    """The profiler's annotation class: `factory(name, **stats)` gives a
    context manager. Called once by the server; None switches it off."""
    global _annotation
    _annotation = factory


def mark(name: str, **stats) -> None:
    """One instantaneous annotation in the profiler's trace; nothing
    where no factory was handed in."""
    if _annotation is not None:
        with _annotation(name, **stats):
            pass


def capture_began() -> None:
    """A profiler capture runs from here on: spans annotate themselves,
    and an `obs.clock` mark lays the trace on the host's two clocks."""
    global capturing
    capturing = _annotation is not None
    mark("obs.clock", wall_ns=time.time_ns(), mono_ns=time.monotonic_ns())


def capture_ended() -> None:
    global capturing
    mark("obs.clock", wall_ns=time.time_ns(), mono_ns=time.monotonic_ns())
    capturing = False

# Spans kept per trace; a runaway query (thousands of shards) truncates
# its own trace rather than growing without bound.
SPANS_MAX = 512
# Serialized peer-summary budget, both as sent (header built under it)
# and as accepted (a peer advertising a bigger one is truncated, not an
# error — the header must never be the thing that fails a query).
SUMMARY_MAX_BYTES = 4096


def current() -> Optional["Trace"]:
    """The trace active on this thread/context, or None."""
    return _current.get()


def current_span() -> Optional["Span"]:
    """The innermost span open on this context, or None. Code that hops
    threads captures it beside current() and hands it on as `parent`."""
    return _open.get()


def activate(trace: Optional["Trace"]):
    """Install `trace` as the context's active trace; returns the reset
    token for deactivate()."""
    return _current.set(trace)


def deactivate(token) -> None:
    _current.reset(token)


class _NopSpan:
    """Shared do-nothing span: the disabled path allocates NOTHING —
    obs.span() returns this one module singleton when no trace is
    active, and every method is a constant-cost no-op."""

    __slots__ = ()

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def tag(self, **kw) -> None:
        pass

    def splice(self, raw) -> None:
        pass

    def wire_id(self) -> str:
        return ""


NOP_SPAN = _NopSpan()


def span(name: str, **tags):
    """Context manager recording one stage span into the active trace.
    With no active trace this returns NOP_SPAN (no allocation)."""
    t = _current.get()
    if t is None:
        return NOP_SPAN
    return Span(t, name, tags or None)


def record(name: str, dur_ms: float, **tags) -> None:
    """Record a pre-measured span into the active trace (for stages whose
    duration is already computed, e.g. the scheduler's admission wait)."""
    t = _current.get()
    if t is not None:
        t.record(name, dur_ms, **tags)


class Span:
    """One named stage interval. Use as a context manager; completes into
    its trace on exit (from whichever thread ran it). `parent` is the id
    of the span that was open on the same context when this one began, or
    the one its creator passed in (a span opened on another thread)."""

    __slots__ = ("_trace", "name", "start_ms", "dur_ms", "tags", "children",
                 "_t0", "id", "parent", "self_ms", "amount", "_above",
                 "_ann", "_closed", "cpu_ms", "self_cpu_ms", "_c0", "_tid")

    def __init__(self, trace: "Trace", name: str,
                 tags: Optional[Dict[str, Any]] = None,
                 parent: Optional["Span"] = None):
        self._trace = trace
        self.name = name
        self.tags = tags or None
        self.children: Optional[List] = None
        self.start_ms = 0.0
        self.dur_ms = 0.0
        self.self_ms = 0.0
        # CPU time of the thread that ran the span, between its two ends;
        # None where they lie on two threads or there is no such clock.
        self.cpu_ms: Optional[float] = None
        self.self_cpu_ms: Optional[float] = None
        self.id = next(trace._ids)
        self.parent = parent.id if parent is not None else None
        # True for a span whose dur_ms is an amount and no interval
        # (`qos.charge`, a bill): it has no self time and covers nothing.
        self.amount = False
        self._t0 = None
        self._c0 = None
        self._tid = None
        self._above = None
        self._ann = None
        self._closed = False

    def __enter__(self) -> "Span":
        # The span open on this context until now is open again when this
        # one ends; it is this one's parent unless the creator named one
        # (or it belongs to another trace).
        above = self._above = _open.get()
        if self.parent is None and above is not None \
                and above._trace is self._trace:
            self.parent = above.id
        _open.set(self)
        if capturing:
            name = self.name + ".wait" if self.name in PARKED_SPANS \
                else self.name
            self._ann = _annotation(name, trace=self._trace.trace_id,
                                    span=self.id)
            self._ann.__enter__()
        t = self._trace
        self._t0 = t._clock()
        # Read inside the monotonic interval, so cpu_ms <= dur_ms.
        cpu = t._cpu_clock
        if cpu is not None:
            self._tid = _thread_ident()
            self._c0 = cpu()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._closed:
            return False
        self._closed = True
        t = self._trace
        if self._c0 is not None and self._tid == _thread_ident():
            self.cpu_ms = (t._cpu_clock() - self._c0) / 1e6
        now = t._clock()
        t0 = self._t0 if self._t0 is not None else now
        self.start_ms = (t0 - t._start) * 1000.0
        self.dur_ms = (now - t0) * 1000.0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if _open.get() is self:
            # (Not so where a span is closed on another context than it
            # was opened on: that one's open span is none of its business.)
            _open.set(self._above)
        if exc_type is not None:
            self.tag(error=exc_type.__name__)
        t._append(self)
        return False

    def close(self) -> None:
        """End the span now; a later __exit__ does nothing. For the
        handler's root span, which must end before its trace is landed."""
        self.__exit__(None, None, None)

    def tag(self, **kw) -> None:
        if self.tags is None:
            self.tags = {}
        self.tags.update(kw)

    def wire_id(self) -> str:
        """The X-Pilosa-Trace header value for a hop made under this
        span: `<trace id>:1` (the :1 marks the sampling decision so the
        peer records without re-rolling its own sampler)."""
        return f"{self._trace.trace_id}:1"

    def splice(self, raw: str) -> None:
        """Attach a peer's X-Pilosa-Trace-Summary as child spans of this
        hop. Defensive by contract: an oversized or malformed summary is
        truncated/dropped with a tag, never an error — observability must
        not fail the query it observes. Child span offsets are kept
        relative to the hop (the peer's trace start), so peer clock skew
        never enters the tree."""
        if not raw:
            return
        if len(raw) > SUMMARY_MAX_BYTES:
            self.tag(summary_truncated=True)
            return
        try:
            data = json.loads(raw)
            spans = data.get("spans", [])
            if not isinstance(spans, list):
                raise TypeError("spans is not a list")
            children = []
            for s in spans[:SPANS_MAX]:
                name, start_ms, dur_ms = s[0], float(s[1]), float(s[2])
                tags = s[3] if len(s) > 3 and isinstance(s[3], dict) else None
                children.append((str(name), start_ms, dur_ms, tags))
        except (ValueError, TypeError, KeyError, IndexError) as e:
            self.tag(summary_error=type(e).__name__)
            return
        self.children = children
        if data.get("truncated"):
            self.tag(peer_truncated=int(data["truncated"]))

    def to_dict(self) -> dict:
        out: Dict[str, Any] = {
            "name": self.name,
            "id": self.id,
            "parent": self.parent,
            "start_ms": round(self.start_ms, 3),
            "dur_ms": round(self.dur_ms, 3),
            "self_ms": round(self.self_ms, 3),
        }
        if self.cpu_ms is not None:
            out["cpu_ms"] = round(self.cpu_ms, 3)
        if self.self_cpu_ms is not None:
            out["self_cpu_ms"] = round(self.self_cpu_ms, 3)
        if self.tags:
            out["tags"] = dict(self.tags)
        if self.children:
            out["children"] = [
                {"name": n, "start_ms": round(s, 3), "dur_ms": round(d, 3),
                 **({"tags": tg} if tg else {})}
                for n, s, d, tg in self.children
            ]
        return out


class Trace:
    """One query's span tree. Created by TraceRecorder; spans may be
    recorded from any thread (state is lock-protected)."""

    __slots__ = ("trace_id", "index", "pql", "adopted", "start_wall",
                 "_start", "_clock", "_cpu_clock", "spans", "duration_ms",
                 "status", "finished", "spans_dropped", "tags", "_lock",
                 "_ids", "_reckoned")

    def __init__(self, trace_id: str, index: str = "", pql: str = "",
                 adopted: bool = False, clock=time.monotonic,
                 cpu_clock=THREAD_CPU_NS):
        self.trace_id = trace_id
        self.index = index
        self.pql = pql
        self.adopted = adopted
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._start = clock()
        self.start_wall = time.time()
        self.spans: List[Span] = []
        self.duration_ms = 0.0
        self.status = "ok"
        self.finished = False
        self.spans_dropped = 0
        self.tags: Optional[Dict[str, Any]] = None
        self._lock = threading.Lock()
        # Span ids: next() on a count is atomic under the interpreter lock.
        self._ids = itertools.count(1)
        self._reckoned = False

    # ----------------------------------------------------------- recording

    def span(self, name: str, parent: Optional[Span] = None, **tags) -> Span:
        """A span of this trace. `parent` is for code that opens it on
        another thread than the request's: there no open span is found."""
        return Span(self, name, tags or None, parent)

    def tag(self, **kw) -> None:
        """Trace-level tags (e.g. the QoS tenant): request attributes
        that belong to the whole query, not one stage."""
        with self._lock:
            if self.finished:
                return
            if self.tags is None:
                self.tags = {}
            self.tags.update(kw)

    def record(self, name: str, dur_ms: float, parent: Optional[Span] = None,
               amount: bool = False, **tags) -> None:
        """Append a pre-measured span ending now, under the span open on
        this context (or `parent`, for a caller on another thread).
        `amount` marks a dur_ms that is a quantity and no interval. Such a
        span is a wait or an amount by construction: its cpu_ms is 0."""
        if parent is None:
            parent = _open.get()
            if parent is not None and parent._trace is not self:
                parent = None
        sp = Span(self, name, tags or None, parent)
        sp.amount = amount
        sp.cpu_ms = 0.0
        now = self._clock()
        sp.dur_ms = float(dur_ms)
        sp.start_ms = max(0.0, (now - self._start) * 1000.0 - sp.dur_ms)
        self._append(sp)

    def _append(self, sp: Span) -> None:
        # A finished span lets go of its trace and of the span above it:
        # the trace holds its spans, and a reference back would make every
        # landed trace a cycle that only the garbage collector frees. With
        # thousands of traces a minute that is most of what the collector
        # has to look at in a serving process; without it a trace that
        # leaves the ring is freed at once.
        sp._trace = sp._above = None
        with self._lock:
            if self.finished or len(self.spans) >= SPANS_MAX:
                # finished: a straggler (an abandoned hedge leg completing
                # after the winning leg answered) must not mutate a trace
                # already published to the ring / histograms / summary
                # header — two scrapes of one trace id must agree.
                self.spans_dropped += 1
                return
            self.spans.append(sp)

    def wire_id(self) -> str:
        return f"{self.trace_id}:1"

    # --------------------------------------------------------- serializing

    def to_dict(self) -> dict:
        with self._lock:
            if self.finished and not self._reckoned:
                # On the first read and not in finish(): a landed trace
                # no longer changes, most are never read, and the pass
                # costs as much as recording a third of the spans.
                reckon_self_times(self.spans)
                self._reckoned = True
            spans = [s.to_dict() for s in self.spans]
        out = {
            "id": self.trace_id,
            "index": self.index,
            "pql": self.pql,
            "start": self.start_wall,
            "duration_ms": round(self.duration_ms, 3),
            "status": self.status,
            "spans": spans,
        }
        with self._lock:
            if self.tags:
                out["tags"] = dict(self.tags)
        if self.spans_dropped:
            out["spans_dropped"] = self.spans_dropped
        return out

    def summary_header(self, max_bytes: int = SUMMARY_MAX_BYTES) -> str:
        """The X-Pilosa-Trace-Summary value: this node's spans as compact
        JSON, tail-truncated to fit `max_bytes` (the header must stay a
        bounded cost on every forwarded response)."""
        with self._lock:
            spans = list(self.spans)
        rows = []
        for s in spans:
            row: List[Any] = [s.name, round(s.start_ms, 3), round(s.dur_ms, 3)]
            if s.tags:
                row.append(s.tags)
            rows.append(row)
        # One-pass size cut: serialize each row once and keep a prefix
        # that fits the budget (envelope + truncated-field reserve),
        # then dump the payload once. Re-serializing the whole payload
        # per dropped row was O(n^2) — paid on every traced forwarded
        # response, worst exactly when a degraded path fattens traces.
        row_strs = [json.dumps(r, separators=(",", ":")) for r in rows]
        reserve = 64  # '{"id":...,"ms":...,"spans":[],"truncated":N}'
        budget = max_bytes - (len(self.trace_id) + reserve)
        keep, used = 0, 0
        for r in row_strs:
            if used + len(r) + 1 > budget:
                break
            used += len(r) + 1
            keep += 1
        while True:
            payload: Dict[str, Any] = {
                "id": self.trace_id,
                "ms": round(self.duration_ms, 3),
                "spans": rows[:keep],
            }
            if keep < len(rows):
                payload["truncated"] = len(rows) - keep
            out = json.dumps(payload, separators=(",", ":"))
            # The reserve makes overshoot all but impossible; the
            # fallback pop guarantees the bound regardless.
            if len(out) <= max_bytes or keep == 0:
                return out
            keep -= 1


def reckon_self_times(spans: List[Span]) -> None:
    """Set each span's self_ms: its length less the union of its direct
    children's intervals, each clipped to it. Where every span ran on the
    request's context the children of one parent do not overlap, and the
    self times of a trace add up to its root's length.

    And its self_cpu_ms: its cpu_ms less that of the direct children that
    ran on its thread (one thread runs one span at a time, so nothing
    overlaps); a child on another thread spent a CPU time of its own.
    The self CPU times of a thread's spans add up to its outermost's."""
    kids: Dict[int, List[Span]] = {}
    for s in spans:
        if s.parent is not None and not s.amount:
            kids.setdefault(s.parent, []).append(s)
    for s in spans:
        if s.cpu_ms is not None:
            below = sum(k.cpu_ms for k in kids.get(s.id, ())
                        if k.cpu_ms is not None and k._tid == s._tid)
            # (max: the floats of three differences need not add up.)
            s.self_cpu_ms = max(0.0, s.cpu_ms - below)
        if s.amount:
            s.self_ms = 0.0
            continue
        lo, hi = s.start_ms, s.start_ms + s.dur_ms
        covered, end = 0.0, lo
        for k in sorted(kids.get(s.id, ()), key=lambda k: k.start_ms):
            a = max(k.start_ms, end)
            b = min(k.start_ms + k.dur_ms, hi)
            if b > a:
                covered += b - a
                end = b
        s.self_ms = max(0.0, s.dur_ms - covered)


class TraceRecorder:
    """Sampling recorder + bounded completed-trace ring + per-stage
    histograms + slow-query log. One per server process."""

    def __init__(self, config=None, stats=None, logger=None,
                 clock=time.monotonic, seed: Optional[int] = None,
                 cpu_clock=THREAD_CPU_NS):
        from . import ObsConfig

        self.config = config or ObsConfig()
        self.stats = stats
        self.logger = logger
        self.clock = clock
        self.cpu_clock = cpu_clock
        self.cpu_period = cpu_sample_period(cpu_clock, clock)
        self._lock = threading.Lock()
        # Seeded sampler: chaos runs pin the seed so the sampled
        # set replays bit-identically.
        self._rng = random.Random(seed)
        self._ring: deque = deque(maxlen=max(1, self.config.ring_size))
        self._hists: Dict[str, Histogram] = {}
        self.counters: Dict[str, int] = {
            "traces_started": 0, "traces_adopted": 0, "traces_finished": 0,
            "slow_queries": 0, "spans_dropped": 0,
        }

    @property
    def enabled(self) -> bool:
        return self.config.sample_rate > 0.0

    # ----------------------------------------------------------- lifecycle

    def maybe_start(self, index: str = "", pql: str = "") -> Optional[Trace]:
        """Sample an ingress query: a Trace when this one is traced, else
        None (the common path: one float compare + one RNG draw)."""
        rate = self.config.sample_rate
        if rate <= 0.0:
            return None
        with self._lock:
            if rate < 1.0 and self._rng.random() >= rate:
                return None
            trace_id = f"{self._rng.getrandbits(64):016x}"
            self.counters["traces_started"] += 1
            cpu_clock = self._cpu_clock_for_next()
        return Trace(trace_id, index=index, pql=pql, clock=self.clock,
                     cpu_clock=cpu_clock)

    def _cpu_clock_for_next(self):
        """The CPU clock for one trace in every `cpu_period`, drawn (a
        fixed stride could fall in step with a client's cycle of
        queries); None for the others. Under the recorder's lock."""
        if self.cpu_period > 1 and self._rng.randrange(self.cpu_period):
            return None
        return self.cpu_clock

    def adopt(self, header: str, index: str = "", pql: str = "",
              ) -> Optional[Trace]:
        """Adopt a coordinator-stamped X-Pilosa-Trace header
        (`<id>[:sampled]`). The upstream sampler already decided, so the
        local rate is not re-rolled; a malformed header is ignored."""
        if not header:
            return None
        trace_id, _, flag = header.partition(":")
        trace_id = trace_id.strip()
        if (not trace_id or len(trace_id) > 64
                or not trace_id.replace("-", "").isalnum()):
            return None
        if flag and flag.strip() not in ("1", "true"):
            return None
        with self._lock:
            self.counters["traces_adopted"] += 1
            cpu_clock = self._cpu_clock_for_next()
        return Trace(trace_id, index=index, pql=pql, adopted=True,
                     clock=self.clock, cpu_clock=cpu_clock)

    def finish(self, trace: Optional[Trace], status: str = "ok") -> None:
        """Land a completed trace: ring, per-stage histograms, slow-query
        log. Idempotent — the handler's error paths and its summary-header
        path may both reach here."""
        if trace is None:
            return
        with trace._lock:
            # The finished flag flips under the trace lock so a straggler
            # span (abandoned hedge leg) racing this finish either lands
            # before the snapshot below or is dropped by _append — never
            # mutates the published trace.
            if trace.finished:
                return
            trace.finished = True
            spans = list(trace.spans)
            dropped = trace.spans_dropped
        trace.status = status
        trace.duration_ms = (self.clock() - trace._start) * 1000.0
        with self._lock:
            self.counters["traces_finished"] += 1
            self.counters["spans_dropped"] += dropped
            if self.config.ring_size > 0:
                self._ring.append(trace)
            for s in spans:
                h = self._hists.get(s.name)
                if h is None:
                    h = self._hists[s.name] = Histogram()
                h.observe(s.dur_ms)
        slow_ms = self.config.slow_query_ms
        if slow_ms > 0 and trace.duration_ms >= slow_ms:
            with self._lock:
                self.counters["slow_queries"] += 1
            if self.stats is not None:
                self.stats.count("SlowQueries", 1)
            if self.logger is not None:
                # Each stage's length and, beside it, the CPU time of its
                # thread in it: whether a slow stage worked or waited.
                breakdown = "; ".join(
                    f"{s.name}={s.dur_ms:.1f}ms" + (
                        "" if s.cpu_ms is None else f" cpu={s.cpu_ms:.1f}")
                    for s in spans)
                self.logger.info(
                    "[obs] slow query %.1fms > slow-query-ms %.1f "
                    "trace=%s index=%s pql=%s stages: %s",
                    trace.duration_ms, slow_ms, trace.trace_id, trace.index,
                    trace.pql, breakdown or "(no spans)")

    # ------------------------------------------------------------- reading

    def traces(self, min_ms: float = 0.0, index: Optional[str] = None,
               limit: int = 64) -> List[dict]:
        """Completed traces, newest first, filtered by minimum duration
        and/or index (the GET /debug/traces contract)."""
        with self._lock:
            candidates = list(self._ring)
        out = []
        for t in reversed(candidates):
            if t.duration_ms < min_ms:
                continue
            if index and t.index != index:
                continue
            out.append(t.to_dict())
            if len(out) >= max(1, limit):
                break
        return out

    def stage_histograms(self) -> Dict[str, dict]:
        """Per-stage log-bucketed latency snapshots (feeds /metrics)."""
        with self._lock:
            return {name: h.snapshot() for name, h in self._hists.items()}

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self.counters)
            out["ring"] = len(self._ring)
        out["sample_rate"] = self.config.sample_rate
        out["cpu_period"] = self.cpu_period
        out["slow_query_ms"] = self.config.slow_query_ms
        return out
