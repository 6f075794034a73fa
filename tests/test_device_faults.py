"""Device-plane fault tolerance (docs/fault-tolerance.md, device section).

Proves the degraded execution ladder end to end: dispatch failures are
classified (oom / compile / runtime / timeout), the per-signature and
plane-wide breakers route around the fused device path (per-shard XLA
walk, then full host/compressed-domain execution), HBM OOM gets
backpressure + retries instead of a client error, and half-open probes
re-close the breakers once faults clear — with dispatch counters as the
proof that serving actually returned to the device path.

The chaos test at the bottom is THE tier-1 combination proof: seed-pinned
device failpoints + tier demote churn + routing-epoch (cutover) churn,
asserting correct-or-clean-error during faults and full convergence
(breakers closed, device path re-promoted, zero host-ladder reads) after
they clear.
"""

import random

import numpy as np
import pytest

from pilosa_tpu import failpoints
from pilosa_tpu.cluster.health import ResilienceConfig
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import EngineConfig
from pilosa_tpu.parallel.device_health import (
    CLOSED, COMPILE, DeviceDispatchError, DeviceDispatchTimeout,
    DevicePlaneHealth, HALF_OPEN, OOM, OPEN, RUNTIME, TIMEOUT,
    classify_device_error,
)
from pilosa_tpu.parallel.engine import Leaf, ShardedQueryEngine, _pop_elems
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.tier import TierConfig

N_SHARDS = 2
SHARDS = tuple(range(N_SHARDS))


@pytest.fixture
def holder():
    h = Holder(None)
    h.open()
    idx = h.create_index("i")
    fld = idx.create_field("f")
    rng = np.random.default_rng(11)
    for row in range(6):
        for shard in SHARDS:
            cols = rng.choice(4096, size=60 + 13 * row, replace=False)
            for c in cols:
                fld.set_bit(row, shard * SHARD_WIDTH + int(c))
    yield h
    h.close()


def call(q):
    return parse(q).calls[0]


# Verbatim from libtpu 0.0.34 compiling a Pallas kernel for a v5e
# topology (jax.experimental.topologies, no chip needed).
VMEM_REFUSAL = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
    "allocating on stack for %f.1 = u32[64,32768]{1,0:T(8,128)} "
    "custom-call(%x.1, %y.1), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={u32[64,32768]{1,0}, u32[64,32768]{1,0}}, "
    "frontend_attributes={kernel_metadata={}}, "
    "metadata={op_name=\"jit(f)/pallas_call\" stack_frame_id=2}. Scoped "
    "allocation with size 24.00M and limit 16.00M exceeded scoped vmem "
    "limit by 8.00M. It should not be possible to run out of scoped vmem "
    "-  see go/compile-time-vmem-oom#kernel-vmem-stack-oom for more "
    "information.")


# ------------------------------------------------------ classification


class TestClassify:
    def test_oom_spellings(self):
        for msg in ("RESOURCE_EXHAUSTED: out of memory allocating",
                    "Out of memory while trying to allocate",
                    "injected HBM OOM at failpoint 'device-dispatch'"):
            assert classify_device_error(RuntimeError(msg)) == OOM

    def test_compile_spellings(self):
        for msg in ("INVALID_ARGUMENT: bad operand",
                    "Compilation failure: unsupported op",
                    "Mosaic lowering failed"):
            assert classify_device_error(RuntimeError(msg)) == COMPILE

    def test_vmem_refusal_is_compile_not_oom(self):
        # The v5e compiler's own words for a kernel that does not fit
        # vector memory. The status word is the OOM one; the failure is a
        # property of the program, and must never reach backpressure.
        assert classify_device_error(RuntimeError(VMEM_REFUSAL)) == COMPILE
        assert classify_device_error(RuntimeError(
            "RESOURCE_EXHAUSTED: Mosaic failed to compile TPU kernel: "
            "out of memory")) == COMPILE

    def test_timeout_by_type(self):
        assert classify_device_error(DeviceDispatchTimeout("x")) == TIMEOUT
        assert classify_device_error(TimeoutError()) == TIMEOUT
        from concurrent.futures import TimeoutError as FutTimeout

        assert classify_device_error(FutTimeout()) == TIMEOUT

    def test_generic_is_runtime(self):
        assert classify_device_error(RuntimeError("boom")) == RUNTIME


# ------------------------------------------------------ breaker lifecycle


class TestDevicePlaneHealth:
    def _dh(self, fake_clock, **kw):
        cfg = ResilienceConfig(**kw).validate()
        return DevicePlaneHealth(cfg, clock=fake_clock)

    def test_plane_opens_after_failures_and_probes_reclose(self, fake_clock):
        dh = self._dh(fake_clock, device_breaker_failures=3,
                      device_breaker_backoff=2.0)
        for _ in range(2):
            dh.record_failure(("a",), RUNTIME)
        assert dh.plane_state() == CLOSED and dh.plan() == "device"
        dh.record_failure(("a",), RUNTIME)
        assert dh.plane_state() == OPEN
        assert dh.plan() == "host"  # inside backoff: short circuit
        assert dh.snapshot()["plane_short_circuits"] == 1
        fake_clock.advance(2.0)
        assert dh.plan() == "device"  # THE half-open probe
        assert dh.plane_state() == HALF_OPEN
        assert dh.plan() == "host"  # probe in flight: others degrade
        dh.record_success(("a",))
        assert dh.plane_state() == CLOSED
        snap = dh.snapshot()
        assert snap["plane_opened"] == 1 and snap["plane_closed"] == 1

    def test_failed_probe_doubles_backoff(self, fake_clock):
        dh = self._dh(fake_clock, device_breaker_failures=1,
                      device_breaker_backoff=2.0,
                      device_breaker_backoff_max=5.0)
        dh.record_failure(None, RUNTIME)
        fake_clock.advance(2.0)
        assert dh.plan() == "device"
        dh.record_failure(None, RUNTIME)  # probe failed
        assert dh.plane_state() == OPEN
        fake_clock.advance(3.9)
        assert dh.plan() == "host"  # doubled to 4.0: not yet
        fake_clock.advance(0.1)
        assert dh.plan() == "device"
        dh.record_failure(None, RUNTIME)
        fake_clock.advance(4.9)  # capped at max 5.0
        assert dh.plan() == "host"
        fake_clock.advance(0.1)
        assert dh.plan() == "device"

    def test_sig_quarantine_routes_shard_only_that_sig(self, fake_clock):
        dh = self._dh(fake_clock, device_breaker_failures=100,
                      device_sig_failures=2, device_sig_backoff=10.0)
        bad, good = ("bad",), ("good",)
        dh.record_failure(bad, COMPILE)
        assert dh.plan(bad) == "device"
        dh.record_failure(bad, COMPILE)
        assert dh.plan(bad) == "shard"
        assert dh.plan(good) == "device"
        assert dh.plan() == "device"
        assert dh.sig_state(bad) == OPEN
        fake_clock.advance(10.0)
        assert dh.plan(bad) == "device"  # sig half-open probe
        dh.record_success(bad)
        assert dh.sig_state(bad) == CLOSED
        snap = dh.snapshot()
        assert snap["sig_quarantined"] == 1 and snap["sig_restored"] == 1

    def test_unresolved_probe_reclaims_after_backoff(self, fake_clock):
        # A probing query answered by the memo dispatches nothing; the
        # probe must re-claim after one base backoff, not wedge for
        # probe_ttl.
        dh = self._dh(fake_clock, device_breaker_failures=1,
                      device_breaker_backoff=2.0)
        dh.record_failure(None, RUNTIME)
        fake_clock.advance(2.0)
        assert dh.plan() == "device"  # claimed, never resolved
        fake_clock.advance(1.0)
        assert dh.plan() == "host"
        fake_clock.advance(1.0)
        assert dh.plan() == "device"  # re-claimed

    def test_quarantined_sig_never_serves_as_plane_probe(self, fake_clock):
        # A signature whose program deterministically fails must not be
        # the dispatch that probes an open plane while the sig's own
        # backoff is running: it would re-open a healthy plane on every
        # attempt. A healthy signature probes instead.
        dh = self._dh(fake_clock, device_breaker_failures=2,
                      device_sig_failures=1, device_breaker_backoff=2.0,
                      device_sig_backoff=10.0)
        bad = ("bad",)
        dh.record_failure(bad, COMPILE)
        dh.record_failure(bad, COMPILE)
        assert dh.plane_state() == OPEN and dh.sig_state(bad) == OPEN
        fake_clock.advance(2.0)  # plane backoff elapsed, sig's has not
        assert dh.plan(bad) == "host"  # bad sig routed down, no claim
        assert dh.plan(("good",)) == "device"  # a healthy sig probes
        dh.record_success(("good",))
        assert dh.plane_state() == CLOSED

    def test_single_sig_workload_still_recovers(self, fake_clock):
        # Liveness twin of the test above: when EVERY query shares the
        # quarantined signature, the sig becomes a legitimate JOINT probe
        # once its own backoff elapses — otherwise the plane could never
        # re-close under a single-shape workload.
        dh = self._dh(fake_clock, device_breaker_failures=2,
                      device_sig_failures=1, device_breaker_backoff=2.0,
                      device_sig_backoff=10.0)
        bad = ("only",)
        dh.record_failure(bad, RUNTIME)
        dh.record_failure(bad, RUNTIME)
        assert dh.plane_state() == OPEN
        fake_clock.advance(5.0)
        assert dh.plan(bad) == "host"  # sig backoff (10s) still running
        fake_clock.advance(5.0)
        assert dh.plan(bad) == "device"  # joint probe: both due
        dh.record_success(bad)
        assert dh.plane_state() == CLOSED
        assert dh.sig_state(bad) == CLOSED

    def test_lost_probe_expires_as_failure(self, fake_clock):
        dh = self._dh(fake_clock, device_breaker_failures=1,
                      device_breaker_backoff=2.0, probe_ttl=30.0)
        dh.record_failure(None, RUNTIME)
        fake_clock.advance(2.0)
        assert dh.plan() == "device"
        before = dh.snapshot()["plane_open_count"]
        fake_clock.advance(31.0)
        dh.plan()  # expiry noticed here
        assert dh.snapshot()["plane_open_count"] == before + 1

    def test_sig_backoff_honors_its_own_knob(self, fake_clock):
        # A sig backoff configured ABOVE the plane cap must not collapse
        # after a failed probe: each breaker doubles from (and is capped
        # no lower than) its OWN knob.
        dh = self._dh(fake_clock, device_breaker_failures=100,
                      device_sig_failures=1, device_breaker_backoff=2.0,
                      device_breaker_backoff_max=60.0,
                      device_sig_backoff=300.0)
        bad = ("bad",)
        dh.record_failure(bad, COMPILE)
        fake_clock.advance(299.9)
        assert dh.plan(bad) == "shard"  # 300s quarantine honored
        fake_clock.advance(0.1)
        assert dh.plan(bad) == "device"  # sig probe
        dh.record_failure(bad, COMPILE)  # probe fails: re-quarantined
        fake_clock.advance(299.9)
        # The next window is never SHORTER than the sig's own knob (the
        # bug was a collapse to the 60s plane cap on the first reopen).
        assert dh.plan(bad) == "shard"
        fake_clock.advance(0.2)
        assert dh.plan(bad) == "device"

    def test_counters_by_kind(self, fake_clock):
        dh = self._dh(fake_clock)
        dh.record_failure(None, OOM)
        dh.record_failure(None, COMPILE)
        dh.record_failure(None, TIMEOUT)
        snap = dh.snapshot()
        assert snap["failures_oom"] == 1
        assert snap["failures_compile"] == 1
        assert snap["failures_timeout"] == 1
        assert snap["dispatch_failures"] == 3

    def test_validate_rejects_bad_device_knobs(self):
        with pytest.raises(ValueError):
            ResilienceConfig(device_breaker_failures=0).validate()
        with pytest.raises(ValueError):
            ResilienceConfig(device_sig_backoff=0).validate()
        with pytest.raises(ValueError):
            ResilienceConfig(device_breaker_backoff=2.0,
                             device_breaker_backoff_max=1.0).validate()


# ------------------------------------------------------ failpoint action


class TestOomFailpoint:
    def test_oom_action_grammar_and_classification(self):
        try:
            failpoints.activate("device-dispatch=2*oom")
            assert failpoints.active()["device-dispatch"] == "2*oom"
            with pytest.raises(failpoints.InjectedFault) as ei:
                failpoints.fire("device-dispatch")
            assert classify_device_error(ei.value) == OOM
        finally:
            failpoints.reset()

    def test_oom_action_custom_message_still_classifies_oom(self):
        # A custom message must ride BEHIND the RESOURCE_EXHAUSTED prefix
        # — replacing it would silently turn an OOM-rung test into a
        # generic-failure test.
        try:
            failpoints.activate("device-dispatch=oom(hbm full)")
            with pytest.raises(failpoints.InjectedFault) as ei:
                failpoints.fire("device-dispatch")
            assert "hbm full" in str(ei.value)
            assert classify_device_error(ei.value) == OOM
        finally:
            failpoints.reset()


# ------------------------------------------------------ engine dispatch


class TestEngineFaults:
    def _engine(self, holder, **kw):
        tier = kw.pop("tier_config", TierConfig(host_bytes=1 << 26,
                                                prefetch_interval=0))
        return ShardedQueryEngine(holder, tier_config=tier, **kw)

    def test_dispatch_error_is_typed_and_recorded(self, holder):
        eng = self._engine(holder)
        try:
            failpoints.configure("device-dispatch", "error")
            with pytest.raises(DeviceDispatchError) as ei:
                eng.count("i", call("Count(Row(f=0))").children[0], SHARDS)
            assert ei.value.kind == RUNTIME
            assert eng.counters["device_dispatch_errors"] == 1
            assert eng.device_health.snapshot()["failures_runtime"] == 1
        finally:
            failpoints.reset()
            eng.close()

    def test_oom_backpressure_retry_never_errors(self, holder):
        eng = self._engine(holder)
        try:
            healthy = eng.count("i", call("Row(f=0)"), SHARDS)
            leaf_budget = eng.budgets["leaf_cache_bytes"]
            failpoints.configure("device-dispatch", "oom", count=1)
            got = eng.count("i", call("Row(f=1)"), SHARDS)
            assert got == eng.host_count("i", call("Row(f=1)"), SHARDS)
            assert eng.counters["oom_backpressure"] == 1
            assert eng.counters["oom_retries"] == 1
            assert eng.budgets["leaf_cache_bytes"] == max(
                leaf_budget // 2, 1 << 20)
            # The plane breaker saw a RECOVERED dispatch, not a failure.
            assert eng.device_health.plane_state() == CLOSED
            assert healthy == eng.count("i", call("Row(f=0)"), SHARDS)
        finally:
            failpoints.reset()
            eng.close()

    def test_oom_batch_splits_in_half(self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_MEMO_ENTRIES", "0")  # memo off: the
        # batch must really dispatch, or the failpoint never fires
        eng = self._engine(holder)
        try:
            calls = [call(f"Row(f={r})") for r in range(4)]
            expect = [eng.host_count("i", c, SHARDS) for c in calls]
            # 2*oom: the full batch fails, the same-size retry fails, and
            # the two half-batches succeed (failpoint exhausted).
            failpoints.configure("device-dispatch", "oom", count=2)
            got = eng.count_batch("i", calls, SHARDS)
            assert [int(x) for x in got] == expect
            assert eng.counters["oom_batch_splits"] == 1
            assert eng.counters["oom_backpressure"] >= 1
        finally:
            failpoints.reset()
            eng.close()

    def test_watchdog_times_out_wedged_dispatch(self, holder):
        eng = self._engine(holder, config=EngineConfig(
            dispatch_watchdog=0.05, gather_workers=2))
        try:
            failpoints.configure("device-dispatch", "latency", arg=500)
            with pytest.raises(DeviceDispatchError) as ei:
                eng.count("i", call("Row(f=0)"), SHARDS)
            assert ei.value.kind == TIMEOUT
            assert eng.counters["watchdog_timeouts"] >= 1
            assert eng.device_health.snapshot()["failures_timeout"] >= 1
        finally:
            failpoints.reset()
            eng.close()

    def test_watchdog_inflight_bound_runs_inline(self, holder):
        # With every watchdog-pool slot occupied (parked on a wedged
        # runtime), further dispatches run INLINE instead of queueing —
        # a queued task's timeout would measure pool delay, not the
        # device, and the gather pool (the host ladder's lifeline) is a
        # separate pool entirely.
        eng = self._engine(holder, config=EngineConfig(
            dispatch_watchdog=0.05, gather_workers=2))
        try:
            failpoints.configure("device-dispatch", "latency", arg=150)
            with eng._lock:
                eng._watchdog_inflight = eng._WATCHDOG_WORKERS
            got = eng.count("i", call("Row(f=3)"), SHARDS)  # blocks ~150ms
            assert got == eng.host_count("i", call("Row(f=3)"), SHARDS)
            assert eng.counters["watchdog_timeouts"] == 0
            with eng._lock:  # undo the synthetic occupancy for teardown
                eng._watchdog_inflight = 0
        finally:
            failpoints.reset()
            eng.close()

    def test_watchdog_uses_dedicated_pool_not_gather_pool(self, holder):
        # A wedged dispatch must park a pilosa-dispatch worker, never a
        # pilosa-gather one: the host fallback ladder gathers on that
        # pool and would deadlock behind abandoned dispatches.
        eng = self._engine(holder, config=EngineConfig(
            dispatch_watchdog=0.05, gather_workers=2))
        try:
            failpoints.configure("device-dispatch", "latency", arg=200)
            with pytest.raises(DeviceDispatchError):
                eng.count("i", call("Row(f=2)"), SHARDS)
            assert eng._watchdog_pool is not None
            import threading as _threading

            assert any(t.name.startswith("pilosa-dispatch")
                       for t in _threading.enumerate())
            with eng._lock:
                assert eng._watchdog_inflight >= 1  # still parked
            failpoints.reset()
            # The host ladder still serves while the dispatch is parked.
            assert eng.host_count("i", call("Row(f=2)"), SHARDS) == \
                eng.host_count("i", call("Row(f=2)"), (0, 1))
            # The abandoned task drains once its injected latency AND its
            # first-touch jit compile finish — poll with a deadline (a
            # fixed sleep raced the compile on cold jit caches).
            import time as _t

            deadline = _t.monotonic() + 30.0
            while _t.monotonic() < deadline:
                with eng._lock:
                    if eng._watchdog_inflight == 0:
                        break
                _t.sleep(0.05)
            with eng._lock:
                assert eng._watchdog_inflight == 0
        finally:
            failpoints.reset()
            eng.close()

    def test_compile_failure_classified(self, holder):
        eng = self._engine(holder)
        try:
            failpoints.configure("device-compile", "error")
            with pytest.raises(DeviceDispatchError) as ei:
                eng.count("i", call("Row(f=2)"), SHARDS)
            assert ei.value.kind == COMPILE
            assert eng.device_health.snapshot()["failures_compile"] == 1
        finally:
            failpoints.reset()
            eng.close()

    def test_vmem_refusal_leaves_budgets_untouched(self, holder):
        # A kernel the chip's compiler refuses fails the SAME way on the
        # retry and on both halves of a split batch: treating it as an
        # HBM OOM halved the cache budgets for the life of the process
        # and evicted resident planes for nothing.
        eng = self._engine(holder)
        try:
            eng.count("i", call("Row(f=0)"), SHARDS)  # a resident plane
            budgets = dict(eng.budgets)
            failpoints.configure("device-dispatch", "error",
                                 message=VMEM_REFUSAL)
            calls = [call(f"Row(f={r})") for r in range(1, 5)]
            with pytest.raises(DeviceDispatchError) as ei:
                eng.count_batch("i", calls, SHARDS)
            assert ei.value.kind == COMPILE
            assert eng.budgets == budgets
            assert eng.counters["oom_backpressure"] == 0
            assert eng.counters["oom_batch_splits"] == 0
            assert eng.counters["leaf_evictions"] == 0
            assert eng.device_health.snapshot()["failures_compile"] == 1
            assert eng.device_health.snapshot()["failures_oom"] == 0
        finally:
            failpoints.reset()
            eng.close()

    def test_transfer_stage_failure_engages_breaker(self, holder,
                                                    monkeypatch):
        # A device that dies at the TRANSFER stage (device_put raising,
        # not the compiled call) must be classified + recorded like a
        # dispatch failure — otherwise the plane breaker stays closed and
        # every query 500s forever.
        import jax as _jax

        eng = self._engine(holder)

        def dead_device(*a, **kw):
            raise RuntimeError("UNAVAILABLE: device connection closed")

        try:
            monkeypatch.setattr(_jax, "device_put", dead_device)
            with pytest.raises(DeviceDispatchError) as ei:
                eng.count("i", call("Row(f=0)"), SHARDS)
            assert ei.value.kind == RUNTIME
            assert eng.device_health.snapshot()["dispatch_failures"] == 1
        finally:
            eng.close()

    def test_host_count_bit_exact_vs_device(self, holder):
        eng = self._engine(holder)
        try:
            for q in ("Row(f=0)",
                      "Intersect(Row(f=0), Row(f=1))",
                      "Union(Row(f=0), Row(f=1), Row(f=2))",
                      "Difference(Row(f=3), Row(f=1))",
                      "Xor(Row(f=2), Row(f=4))"):
                dev = eng.count("i", call(q), SHARDS)
                host = eng.host_count("i", call(q), (0, 1))
                assert dev == host, q
        finally:
            eng.close()

    def test_host_count_reads_demoted_tier_bytes(self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_MEMO_ENTRIES", "0")
        eng = self._engine(holder)
        try:
            healthy = eng.count("i", call("Row(f=0)"), SHARDS)
            key = ("i", Leaf("f", "standard", 0), SHARDS)
            eng.tier.demote(key)
            assert eng.tier.drain()
            base = eng.tier.snapshot()["promotions_host"]
            assert eng.host_count("i", call("Row(f=0)"), SHARDS) == healthy
            assert eng.tier.snapshot()["promotions_host"] == base + 1
            assert eng.counters["host_counts"] == 1
        finally:
            eng.close()

    def test_host_topn_matches_device(self, holder):
        eng = self._engine(holder)
        try:
            src = call("Row(f=0)")
            ids = [1, 2, 3, 4]
            d_rc, d_inter, d_src = eng.topn_shard_counts(
                "i", "f", ids, SHARDS, src, need_row_counts=True)
            h_rc, h_inter, h_src = eng.host_topn_shard_counts(
                "i", "f", ids, SHARDS, src, need_row_counts=True)
            assert np.array_equal(np.asarray(d_rc), np.asarray(h_rc))
            assert np.array_equal(np.asarray(d_inter), np.asarray(h_inter))
            assert np.array_equal(np.asarray(d_src), np.asarray(h_src))
        finally:
            eng.close()

    def test_pop_elems_matches_python_popcount(self):
        rng = np.random.default_rng(5)
        arr = rng.integers(0, 2**32, size=(3, 64), dtype=np.uint32)
        want = sum(bin(int(x)).count("1") for x in arr.flat)
        assert int(_pop_elems(arr).sum()) == want


# ------------------------------------------------- compressed-domain cold


class TestColdHostCount:
    def test_cold_count_skips_device_then_promotes_on_repeat(
            self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_MEMO_ENTRIES", "0")
        eng = ShardedQueryEngine(
            holder, tier_config=TierConfig(host_bytes=1 << 26,
                                           prefetch_interval=0))
        try:
            healthy = eng.count("i", call("Row(f=5)"), SHARDS)
            dispatches = eng.counters["count_dispatches"]
            # Evict + demote the plane, then drop the device entry.
            key = ("i", Leaf("f", "standard", 5), SHARDS)
            eng.tier.demote(key)
            assert eng.tier.drain()
            with eng._lock:
                ent = eng._leaf_cache.pop(key, None)
                if ent is not None:
                    eng._leaf_bytes -= ent[1].nbytes
            # First touch: answered compressed-domain, no dispatch.
            got = eng.count("i", call("Row(f=5)"), SHARDS)
            assert got == healthy
            assert eng.counters["host_cold_counts"] == 1
            assert eng.counters["count_dispatches"] == dispatches
            # Second touch: promotes through the tier onto the device.
            tier_hits = eng.counters["leaf_tier_hits"]
            got = eng.count("i", call("Row(f=5)"), SHARDS)
            assert got == healthy
            assert eng.counters["leaf_tier_hits"] == tier_hits + 1
            assert eng.counters["count_dispatches"] == dispatches + 1
        finally:
            eng.close()

    def test_disabled_by_knob(self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_MEMO_ENTRIES", "0")
        eng = ShardedQueryEngine(
            holder, config=EngineConfig(cold_host_count=0),
            tier_config=TierConfig(host_bytes=1 << 26, prefetch_interval=0))
        try:
            key = ("i", Leaf("f", "standard", 4), SHARDS)
            eng.tier.demote(key)
            assert eng.tier.drain()
            eng.count("i", call("Row(f=4)"), SHARDS)
            assert eng.counters["host_cold_counts"] == 0
        finally:
            eng.close()


# ------------------------------------------------------ executor ladder


class TestExecutorLadder:
    def _executor(self, holder, **resilience):
        ex = Executor(holder)
        if resilience:
            ex.cluster.health.configure(
                ResilienceConfig(**resilience).validate())
        return ex

    def test_count_served_by_host_ladder_under_fault(self, holder):
        ex = self._executor(holder)
        try:
            healthy = ex.execute("i", "Count(Intersect(Row(f=1),Row(f=2)))")[0]
            failpoints.configure("device-dispatch", "error")
            # A commutative respelling now canonicalizes onto the same
            # memo entry (docs/query-compiler.md) and must still answer.
            got = ex.execute("i", "Count(Intersect(Row(f=2),Row(f=1)))")[0]
            # A fresh leaf SET busts the memo, so THIS query exercises
            # the faulted dispatch + host-ladder value path.
            fresh = ex.execute("i", "Count(Intersect(Row(f=0),Row(f=1)))")[0]
            healthy2 = ex.execute("i", "Count(Intersect(Row(f=1),Row(f=2)))")[0]
            assert got == healthy == healthy2
            failpoints.reset()
            # Value-check the ladder-served answer against the healthy
            # DEVICE path for the same query — a set+clear bumps the
            # generation so the re-execution cannot be a memo read of
            # the host ladder's own stored value.
            fld = holder.index("i").field("f")
            fld.set_bit(0, 8000)
            fld.clear_bit(0, 8000)
            assert fresh == ex.execute(
                "i", "Count(Intersect(Row(f=0),Row(f=1)))")[0]
            assert ex.engine.counters["host_counts"] >= 1
        finally:
            failpoints.reset()
            ex.close()

    def test_plane_opens_then_host_routed_then_recloses(self, holder):
        ex = self._executor(holder, device_breaker_failures=2,
                            device_breaker_backoff=1.0)
        try:
            queries = [f"Count(Union(Row(f=0),Row(f={r})))" for r in
                       (1, 2, 3, 4)]
            expect = [ex.execute("i", q)[0] for q in queries]
            failpoints.configure("device-dispatch", "error")
            dh = ex.engine.device_health
            # A fresh bit (cols were drawn < 4096) busts every memo AND
            # shifts each Union count by exactly one, so the degraded
            # answers are checkable against the healthy baseline.
            fld = holder.index("i").field("f")
            fld.set_bit(0, 8000)
            got = [ex.execute("i", q)[0] for q in queries]
            assert got == [e + 1 for e in expect]
            fld.clear_bit(0, 8000)
            assert [ex.execute("i", q)[0] for q in queries] == expect
            assert dh.plane_state() == OPEN
            assert ex.engine.counters["host_counts"] >= 2
            # Heal: faults cleared + backoff elapsed -> the next fresh
            # query IS the half-open probe and re-closes the plane.
            failpoints.reset()
            import time as _t

            dh.clock = (lambda base=_t.monotonic: base() + 60.0)
            dispatches = ex.engine.counters["count_dispatches"]
            got = ex.execute("i", "Count(Xor(Row(f=0),Row(f=5)))")[0]
            assert got == ex.engine.host_count(
                "i", call("Xor(Row(f=0),Row(f=5))"), SHARDS)
            assert dh.plane_state() == CLOSED
            assert ex.engine.counters["count_dispatches"] == dispatches + 1
        finally:
            failpoints.reset()
            ex.close()

    def test_sig_quarantine_leaves_other_sigs_on_device(self, holder):
        ex = self._executor(holder, device_breaker_failures=100,
                            device_sig_failures=1)
        try:
            bad = "Count(Difference(Row(f=0),Row(f=2)))"
            good = "Count(Union(Row(f=3),Row(f=4)))"
            expect_bad = ex.engine.host_count(
                "i", call("Difference(Row(f=0),Row(f=2))"), SHARDS)
            # host_count stored the memo: bust it so the query dispatches.
            holder.index("i").field("f").set_bit(0, 8001)
            holder.index("i").field("f").clear_bit(0, 8001)
            failpoints.configure("device-dispatch", "error", count=1)
            assert ex.execute("i", bad)[0] == expect_bad  # in-flight rung
            # The signature is now quarantined: served correctly WITHOUT
            # the engine (failpoint exhausted — a dispatch would succeed,
            # so an unchanged dispatch counter proves the routing).
            dispatches = ex.engine.counters["count_dispatches"]
            holder.index("i").field("f").set_bit(0, 8002)
            holder.index("i").field("f").clear_bit(0, 8002)  # memo-bust
            assert ex.execute("i", bad)[0] == expect_bad
            assert ex.engine.counters["count_dispatches"] == dispatches
            # A different signature still rides the device.
            ex.execute("i", good)
            assert ex.engine.counters["count_dispatches"] == dispatches + 1
        finally:
            failpoints.reset()
            ex.close()

    def test_topn_correct_under_device_fault(self, holder):
        ex = self._executor(holder)
        try:
            q = "TopN(f, Row(f=0), n=3)"
            healthy = ex.execute("i", q)[0]
            failpoints.configure("device-dispatch", "error")
            # Bump generations so the aux memo can't answer the repeat
            # (set+clear leaves the data identical).
            holder.index("i").field("f").set_bit(0, 4500)
            holder.index("i").field("f").clear_bit(0, 4500)
            degraded = ex.execute("i", q)[0]
            assert [(p.id, p.count) for p in degraded] == \
                [(p.id, p.count) for p in healthy]
            assert ex.engine.counters["host_topn"] >= 1
        finally:
            failpoints.reset()
            ex.close()

    def test_topn_with_bsi_src_takes_per_shard_rung(self, holder):
        # A BSI Range src compiles onto the fused path but has NO host
        # twin: with the device faulted, TopN must drop to the per-shard
        # walk (rung 1), never surface the dispatch error.
        from pilosa_tpu.core.field import FieldOptions

        idx = holder.index("i")
        idx.create_field_if_not_exists(
            "v", FieldOptions(type="int", min=0, max=100))
        fld = idx.field("v")
        for col in range(0, 200, 3):
            fld.set_value(col, col % 70)
        q = "TopN(f, Range(v > 10), n=3)"
        ex = self._executor(holder)
        try:
            healthy = ex.execute("i", q)[0]
            assert healthy  # the filter actually selects rows
            holder.index("i").field("f").set_bit(0, 8003)
            holder.index("i").field("f").clear_bit(0, 8003)  # memo-bust
            failpoints.configure("device-dispatch", "error")
            degraded = ex.execute("i", q)[0]
            assert [(p.id, p.count) for p in degraded] == \
                [(p.id, p.count) for p in healthy]
        finally:
            failpoints.reset()
            ex.close()

    def test_bsi_short_circuits_to_per_shard_when_plane_open(self, holder):
        # BSI has no host twin, so its whole degraded ladder is the
        # per-shard walk — and with the plane breaker OPEN, it must be
        # taken BEFORE any dispatch (no failing dispatch, no watchdog
        # stall per query on a known-sick device).
        from pilosa_tpu.core.field import FieldOptions

        idx = holder.index("i")
        idx.create_field_if_not_exists(
            "w", FieldOptions(type="int", min=0, max=50))
        fld = idx.field("w")
        for col in range(0, 60, 2):
            fld.set_value(col, col % 40)
        ex = self._executor(holder, device_breaker_failures=1)
        try:
            healthy = ex.execute("i", "Sum(field=w)")[0].to_dict()
            failpoints.configure("device-dispatch", "error")
            fld.set_value(1, 5)  # busts the aux memo (and shifts the sum)
            want = {"value": healthy["value"] + 5,
                    "count": healthy["count"] + 1}
            degraded = ex.execute("i", "Sum(field=w)")[0].to_dict()
            assert degraded == want  # mid-request rung
            assert ex.engine.device_health.plane_state() == OPEN
            failures = ex.engine.device_health.snapshot()[
                "dispatch_failures"]
            # Plane open: the NEXT Sum never dispatches at all.
            fld.set_value(3, 5)
            want = {"value": want["value"] + 5, "count": want["count"] + 1}
            assert ex.execute("i", "Sum(field=w)")[0].to_dict() == want
            assert ex.engine.device_health.snapshot()[
                "dispatch_failures"] == failures
        finally:
            failpoints.reset()
            ex.close()

    def test_bitmap_falls_back_per_shard(self, holder):
        ex = self._executor(holder)
        try:
            q = "Intersect(Row(f=0), Row(f=1))"
            healthy = ex.execute("i", q)[0]
            failpoints.configure("device-dispatch", "error")
            degraded = ex.execute("i", q)[0]
            assert degraded.count() == healthy.count()
        finally:
            failpoints.reset()
            ex.close()


# --------------------------------------------- deadline between chunks


class TestDeadlineBetweenChunks:
    def test_multichunk_topn_503s_midflight(self, holder, monkeypatch):
        from pilosa_tpu.executor import ExecOptions
        from pilosa_tpu.sched.deadline import (Deadline,
                                               DeadlineExceededError)

        # Force one candidate row per device chunk.
        monkeypatch.setenv("PILOSA_TOPN_CHUNK_BYTES", "1")
        ex = Executor(holder)
        ticks = {"n": 0}

        def clock():
            ticks["n"] += 1
            return float(ticks["n"])

        try:
            opt = ExecOptions(deadline=Deadline(10.0, clock=clock))
            with pytest.raises(DeadlineExceededError):
                ex.execute("i", "TopN(f, Row(f=0), n=5)",
                           shards=list(SHARDS), opt=opt)
        finally:
            ex.close()

    def test_phase_boundary_check_counts(self, holder):
        from pilosa_tpu.executor import ExecOptions
        from pilosa_tpu.sched.deadline import (Deadline,
                                               DeadlineExceededError)
        from pilosa_tpu.stats import new_stats_client

        holder.stats = new_stats_client("inmem", "")
        ex = Executor(holder)
        clock = {"now": 0.0}

        def tick():
            return clock["now"]

        try:
            opt = ExecOptions(deadline=Deadline(5.0, clock=tick))
            # Expire the budget before execution starts the second phase:
            # the phase-2 gate must 503 and count.
            orig = ex._execute_topn_shards

            def expiring(index, c, shards, o):
                out = orig(index, c, shards, o)
                clock["now"] = 100.0
                return out

            ex._execute_topn_shards = expiring
            with pytest.raises(DeadlineExceededError):
                ex.execute("i", "TopN(f, n=3)", shards=list(SHARDS), opt=opt)
            assert holder.stats.snapshot()["counters"].get(
                "DeadlineMidQuery", 0) >= 1
        finally:
            ex.close()


# ------------------------------------------------------------ chaos combo


pytestmark_chaos = pytest.mark.chaos


@pytest.mark.chaos
def test_device_chaos_with_tier_churn_and_cutover(holder, fake_clock):
    """THE combination proof (tier-1, seed-pinned, fake breaker clock):
    device failpoints (error/oom/compile) toggle per round while planes
    churn through the tier (demote + drain every round) and routing
    epochs advance mid-round via rebalance begin/cutover/commit on the
    executor's own cluster (single node: placement never changes, the
    epoch re-read gates still fire). Every query must be CORRECT — the
    ladder never surfaces a device fault — and after faults clear the
    breakers re-close, serving returns to the device path, and a final
    round runs with zero host-ladder reads."""
    seed = 1234
    rng = random.Random(seed)
    ex = Executor(holder)
    ex.cluster.health.configure(ResilienceConfig(
        device_breaker_failures=2, device_breaker_backoff=1.0,
        device_sig_failures=2).validate())
    eng = ex.engine
    eng.device_health.clock = fake_clock
    queries = [
        "Count(Row(f=0))",
        "Count(Intersect(Row(f=0),Row(f=1)))",
        "Count(Union(Row(f=1),Row(f=2),Row(f=3)))",
        "Count(Difference(Row(f=4),Row(f=0)))",
        "Count(Xor(Row(f=2),Row(f=5)))",
    ]
    expect = [ex.execute("i", q)[0] for q in queries]
    fld = holder.index("i").field("f")
    node = ex.cluster.node
    try:
        for rnd in range(8):
            # Fault schedule for this round (seed-pinned).
            failpoints.reset()
            action = rng.choice(["none", "error", "oom", "compile", "error"])
            if action == "error":
                failpoints.configure("device-dispatch", "error",
                                     count=rng.randint(1, 3))
            elif action == "oom":
                failpoints.configure("device-dispatch", "oom",
                                     count=rng.randint(1, 2))
            elif action == "compile":
                failpoints.configure("device-compile", "error",
                                     count=rng.randint(1, 2))
            # Tier churn: demote a couple of planes and settle the worker.
            for row in rng.sample(range(6), 2):
                eng.tier.demote(("i", Leaf("f", "standard", row), SHARDS))
            eng.tier.drain()
            # Cutover churn: advance the routing epoch mid-round.
            ex.cluster.begin_rebalance([node])
            ex.cluster.apply_cutover("i", rng.randrange(N_SHARDS))
            # A tiny write pair busts memos so queries really execute.
            col = 4097 + rnd
            fld.set_bit(0, col)
            fld.clear_bit(0, col)
            for q, want in zip(queries, expect):
                got = ex.execute("i", q)[0]  # correct, never a 500
                assert got == want, (rnd, action, q)
            ex.cluster.commit_topology([node])
            fake_clock.advance(rng.choice([0.2, 1.1, 2.5]))
        # Faults clear; breakers converge through half-open probes.
        failpoints.reset()
        for _ in range(6):
            fake_clock.advance(2.0)
            fld.set_bit(0, 5000)
            fld.clear_bit(0, 5000)
            for q, want in zip(queries, expect):
                assert ex.execute("i", q)[0] == want
            if eng.device_health.plane_state() == CLOSED:
                break
        assert eng.device_health.plane_state() == CLOSED
        # Fully converged: a fresh round serves from the device with ZERO
        # host-ladder reads and climbing dispatch counters.
        host_before = eng.counters["host_counts"] + eng.counters["host_topn"]
        dispatches = eng.counters["count_dispatches"]
        fld.set_bit(0, 5001)
        fld.clear_bit(0, 5001)
        for q, want in zip(queries, expect):
            assert ex.execute("i", q)[0] == want
        assert eng.counters["host_counts"] + eng.counters["host_topn"] \
            == host_before
        assert eng.counters["count_dispatches"] > dispatches
    finally:
        failpoints.reset()
        ex.close()
