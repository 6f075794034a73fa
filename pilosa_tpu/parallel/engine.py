"""Sharded query engine: one XLA program per query shape over all shards.

This replaces the reference's goroutine-per-shard map loop
(executor.go:1558-1593) for local shards. A PQL bitmap call tree is
compiled once per *structure* into a jitted function over a stacked leaf
tensor of shape (L, S, W) — L leaf rows, S shards sharded over the device
mesh, W bitplane words. XLA fuses the whole tree into one fused
elementwise+popcount kernel per device and inserts ICI collectives for the
scalar reductions. Leaf planes are cached on device between queries and
invalidated by what the writers say: every view keeps a change journal
(core/fragment.py ChangeJournal) that each write to one of its fragments
notes its shard and row in. A cache entry carries the journal's stamp,
one comparison tells a probe whether anything was written to the view
since, and if so the journal names the cells: an entry none of whose
rows was written is fresh again as it stands, another is patched in
those cells alone. Only where the journal cannot say (it has forgotten
that far back, a fragment came or went, a whole fragment was replaced)
are the view's fragments asked one by one, as they all used to be.

Supported fast-path calls: Row / Intersect / Union / Difference / Xor /
Range(BSI) compositions, Count(...) and per-row TopN candidate counting.
Everything else falls back to the executor's per-shard path.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import failpoints
from ..constants import VIEW_BSI_GROUP_PREFIX, VIEW_STANDARD, WORDS_PER_ROW
from ..obs import NOP_SPAN, current as obs_current, span as obs_span
from ..core.row import Row
from ..errors import QueryError
from ..ops import bitplane as bp
from ..plan.signature import (
    CompiledPlan, Leaf, cached_plan, resolve_time_range as
    _resolve_time_range,
)
from ..pql.ast import Call
from . import EngineConfig
from .device_health import (
    COMPILE, DeviceDispatchError, DeviceDispatchTimeout, DevicePlaneHealth,
    OOM, classify_device_error,
)
from .mesh import (
    SHARD_AXIS, default_mesh, pad_shards, shard_sharding, stack_fold,
)


def _place_compile_cache() -> None:
    """Give JAX's persistent compilation cache a fixed home before the
    first compile. JAX_COMPILATION_CACHE_DIR is the interface: when it is
    set JAX has already read it and nothing is set here. Otherwise the
    cache lives at <checkout>/.jax_cache — the path is part of the cache
    key, so it must not move between runs. Every program is cached, not
    only the slow compiles: a restarted server pays none of them again."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _pop_elems(a: np.ndarray) -> np.ndarray:
    """Elementwise popcounts of a uint32 array for the host execution
    ladder, returned over the uint16 view (same leading shape, last axis
    doubled) so callers sum over the trailing axis/axes for plane
    popcounts. np.bitwise_count is used unconditionally, matching the
    storage/wire layers (storage/bitmap.py, server/wire.py)."""
    return np.bitwise_count(a.view(np.uint16))


# Members of a restack's piece: a stack of more is built as pieces of this
# many joined by one concatenate, and a padded stack of more pads to a
# multiple of it (padded_rows).
STACK_PIECE = 512


def padded_rows(n: int) -> int:
    """Rows a padded stack of n members holds, so that nearby member counts
    share one compiled program: the next power of two up to STACK_PIECE,
    the next multiple of STACK_PIECE above. A power of two above it would
    pad TopN's 8,208 candidate rows to 16,384 (2 GiB at one shard, half of
    it copies of leaf 0); a multiple pads them to 8,704 (1.06 GiB)."""
    if n <= STACK_PIECE:
        return 1 << (n - 1).bit_length() if n else 0
    return -(-n // STACK_PIECE) * STACK_PIECE


# Fewest entries a delta scatter is padded to (_pad_updates): the usual
# refresh, a write or a few, then takes one program a cached shape, where
# each power of two under 64 was a program of its own and the rarer ones
# were first met inside a measured window (PERF.md, PR 41). A scatter
# copies its whole plane or stack, so 64 entries cost it nothing more.
DELTA_MIN_UPDATES = 64


# The kinds of device program the engine builds: the first element of a
# program-cache signature. Each has a flat `fn_builds_<kind>` counter beside
# `fn_cache_builds` (registered at 0, so that a reader of counter growth
# sees a kind's first build too) and is the `kind` tag of `engine.fn_build`.
FN_KINDS = (
    "count", "count_batch", "count_batch_setops", "leaf_delta",
    "stack_delta", "bitmap", "bitmap_batch", "topn_shard", "topn_shard_src",
    "topn_src", "topn", "bsi",
)

class _FirstCall:
    """A freshly built program on its builder's way to the first call. A
    jitted function compiles (or loads from the persistent cache) when it
    is first called, not when it is wrapped, so that call is the
    `engine.fn_build` span; the program cache keeps the bare function."""

    __slots__ = ("fn", "kind")

    def __init__(self, fn: Callable, kind: str):
        self.fn = fn
        self.kind = kind

    def __call__(self, *args):
        with obs_span("engine.fn_build", kind=self.kind):
            return self.fn(*args)


def _lower_ir(ir: tuple) -> Callable:
    """Canonical plan IR (plan/signature.py) -> jnp closure over the
    (L, S, W) leaf tuple. The IR is already canonicalized (commutative
    operands sorted, associative chains flattened to k-ary nodes), so
    the lowered program reduces all k operands of a node in one chained
    pass — XLA fuses the whole thing into a single elementwise kernel —
    and a Difference pays ONE complement for its whole subtracting set
    (head AND NOT(OR(tail))) instead of one per operand."""
    kind = ir[0]
    if kind == "leaf":
        i = ir[1]
        return lambda leaves: leaves[i]
    if kind in ("Intersect", "Union", "Xor"):
        subs = [_lower_ir(ch) for ch in ir[1]]
        op = {
            "Intersect": jnp.bitwise_and,
            "Union": jnp.bitwise_or,
            "Xor": jnp.bitwise_xor,
        }[kind]

        def fn(leaves, subs=subs, op=op):
            out = subs[0](leaves)
            for s in subs[1:]:
                out = op(out, s(leaves))
            return out

        return fn
    if kind == "Difference":
        head = _lower_ir(ir[1])
        tails = [_lower_ir(ch) for ch in ir[2]]
        if not tails:
            return head

        def fn(leaves, head=head, tails=tails):
            mask = tails[0](leaves)
            for t in tails[1:]:
                mask = jnp.bitwise_or(mask, t(leaves))
            return jnp.bitwise_and(head(leaves), jnp.bitwise_not(mask))

        return fn
    if kind == "timerange":
        idxs = ir[1]

        def fn(leaves, idxs=idxs):
            out = leaves[idxs[0]]
            for i in idxs[1:]:
                out = jnp.bitwise_or(out, leaves[i])
            return out

        return fn
    if kind == "zero":
        i = ir[1]
        return lambda leaves: jnp.zeros_like(leaves[i])
    if kind == "notnull":
        i = ir[1]
        return lambda leaves: leaves[i]
    if kind == "between":
        idxs, depth, lo, hi = ir[1], ir[2], ir[3], ir[4]
        return lambda leaves: bp.bsi_range_between(
            jnp.stack([leaves[i] for i in idxs]), depth, lo, hi)
    if kind == "cmp":
        _, op, idxs, depth, base = ir

        def fn(leaves, op=op, idxs=idxs, depth=depth, base=base):
            planes = jnp.stack([leaves[i] for i in idxs])
            if op == "eq":
                return bp.bsi_range_eq(planes, depth, base)
            if op == "neq":
                return bp.bsi_range_neq(planes, depth, base)
            if op in ("lt", "lte"):
                return bp.bsi_range_lt(planes, depth, base, op == "lte")
            return bp.bsi_range_gt(planes, depth, base, op == "gte")

        return fn
    raise QueryError(f"unknown plan IR node: {kind!r}")


def _plan_expr(plan: CompiledPlan) -> Callable:
    """Lowered closure for a plan, cached on the plan object (plans are
    themselves cached on the Call tree, so a query's expression lowers
    once per epoch, not once per dispatch site). Benign publication
    race: concurrent lowerings produce equivalent closures."""
    expr = plan.expr
    if expr is None:
        expr = plan.expr = _lower_ir(plan.ir)
    return expr


class _Compiler:
    """Facade over the canonical plan compiler (plan/signature.py),
    keeping the historical (comp, expr) surface: `comp.signature` (the
    single-entry canonical-IR list), `comp.leaves` (canonical slot
    order), `comp.plan`. Query structures that differ only by
    commutative operand order or associative nesting now produce the
    SAME signature and leaf binding, so they share one compiled program,
    one memo space, one micro-batcher group, and one device breaker."""

    def __init__(self, holder, index: str, field_cache: Optional[Dict] = None,
                 plan_cache: bool = True):
        self.holder = holder
        self.index = index
        self.leaves: List[Leaf] = []
        self.signature: List = []
        self.plan: Optional[CompiledPlan] = None
        # Shared across one batch's compilers: a 1024-query batch would
        # otherwise repeat the same holder field-existence lookups per call.
        self._field_cache = field_cache
        self._plan_cache = plan_cache

    def compile(self, c: Call) -> Callable:
        plan = cached_plan(self.holder, self.index, c,
                           field_cache=self._field_cache,
                           enabled=self._plan_cache)
        self.plan = plan
        self.leaves = plan.leaves
        self.signature = plan.signature
        return _plan_expr(plan)


class ShardedQueryEngine:
    def __init__(self, holder, mesh=None, config: Optional[EngineConfig] = None,
                 tier_config=None, traffic_fn=None, resilience_config=None):
        self.holder = holder
        _place_compile_cache()
        if mesh is None:
            # [engine] mesh-devices: a positive N pins the engine to the
            # first N local devices (see EngineConfig for the concurrent-
            # all-reduce rationale); 0 = all local devices.
            md = int(getattr(config, "mesh_devices", 0) or 0) if config \
                else int(os.environ.get("PILOSA_TPU_ENGINE_MESH_DEVICES",
                                        "0"))
            if md > 0:
                mesh = default_mesh(jax.local_devices()[:md])
            else:
                mesh = default_mesh()
        self.mesh = mesh
        # The platform the engine's arrays live on. "tpu" is the
        # accelerator; anything else (the CPU backend of the tests) gets
        # the smaller cache defaults and runs the Pallas kernel in
        # interpret mode.
        self.platform = self.mesh.devices.flat[0].platform
        if config is None:
            # No resolved config (library/test use): honor the env
            # spellings directly. When a Config DID resolve these knobs,
            # flags > env > TOML precedence already happened there —
            # re-reading env here would let a stray export silently beat
            # an explicit --engine-* flag.
            config = EngineConfig(
                delta_max_fraction=float(os.environ.get(
                    "PILOSA_TPU_ENGINE_DELTA_MAX_FRACTION",
                    EngineConfig.delta_max_fraction)),
                gather_workers=int(os.environ.get(
                    "PILOSA_TPU_ENGINE_GATHER_WORKERS",
                    EngineConfig.gather_workers)),
                leaf_cache_bytes=int(os.environ.get(
                    "PILOSA_TPU_ENGINE_LEAF_CACHE_BYTES", 0)),
                stack_cache_bytes=int(os.environ.get(
                    "PILOSA_TPU_ENGINE_STACK_CACHE_BYTES", 0)),
                memo_entries=int(os.environ.get(
                    "PILOSA_TPU_ENGINE_MEMO_ENTRIES", 0)),
                aux_memo_entries=int(os.environ.get(
                    "PILOSA_TPU_ENGINE_AUX_MEMO_ENTRIES", 0)),
                dispatch_watchdog=float(os.environ.get(
                    "PILOSA_TPU_ENGINE_DISPATCH_WATCHDOG",
                    EngineConfig.dispatch_watchdog)),
                cold_host_count=int(os.environ.get(
                    "PILOSA_TPU_ENGINE_COLD_HOST_COUNT",
                    EngineConfig.cold_host_count)),
                plan_cache=int(os.environ.get(
                    "PILOSA_TPU_ENGINE_PLAN_CACHE",
                    EngineConfig.plan_cache)),
            )
        if tier_config is None:
            # Same env-only fallback for the [tier] section.
            from ..tier import TierConfig

            tier_config = TierConfig.from_env()
        # Delta-refresh budget: a stale resident tensor is refreshed by a
        # scattered (indices, values) upload only while the changed 32-bit
        # words stay under this fraction of the tensor; 0 disables deltas.
        self._delta_max_fraction = float(config.delta_max_fraction)
        # Device-plane fault state (device_health.py): every dispatch
        # reports its outcome here, and the executor consults plan()
        # before routing work at the device. The watchdog bounds how long
        # a dispatch may block a serving thread (0 = off).
        self.device_health = DevicePlaneHealth(resilience_config)
        self._watchdog_s = float(getattr(config, "dispatch_watchdog", 0.0))
        # Watchdogged dispatches run on their own small pool, NOT the
        # gather pool: an abandoned (wedged) dispatch parks its worker
        # until the runtime answers, and parking gather workers would
        # starve the host gathers the fallback ladder itself serves
        # from. `_watchdog_inflight` counts submitted-but-unfinished
        # dispatches (incremented at submit, decremented by a done
        # callback); at the pool bound, further dispatches run INLINE
        # unwatchdogged — slower to detect a wedge, but never a deadlock
        # and never a queued task misread as a device timeout.
        self._watchdog_pool = None
        self._watchdog_inflight = 0
        self._cold_host = bool(int(getattr(config, "cold_host_count", 1)))
        # On-Call canonical-plan caching (plan/signature.py cached_plan):
        # 0 recompiles at every dispatch site — the escape hatch if a
        # workload ever hits a stale-plan bug; the epoch token makes
        # that structurally unlikely.
        self._plan_cache_enabled = bool(int(getattr(config, "plan_cache", 1)))
        # Leaf sets already answered once by the cold-host path: the
        # second touch promotes normally so repeat traffic climbs back
        # into HBM instead of re-decoding per query. Bounded crudely —
        # losing the set only costs one extra host answer per leaf set.
        self._cold_seen: set = set()
        # Cold-gather host parallelism (per-shard container walks).
        gw = int(config.gather_workers)
        self._gather_workers = gw if gw > 0 else min(8, os.cpu_count() or 1)
        self._gather_pool = None  # lazy ThreadPoolExecutor
        # (index, leaf, shards) -> (its view's stamp, sharded device array,
        # per-shard (incarnation, generation) pairs of the last walk: what
        # the safe rung compares when the journal cannot say)
        self._leaf_cache: Dict[Tuple, Tuple[Tuple, jax.Array, Tuple]] = {}
        self._leaf_bytes = 0
        # (index, leaves, shards, U) -> (fingerprint, stacked (U, S, W) array,
        # kept as (U, S*k, W//k) where stack_fold(S, devices) is k > 1;
        # {view: {row: [(u,), ...]}}, where each row lies in it: what a
        # stale entry asks its journals about, worked out once a build)
        self._stack_cache: Dict[Tuple, Tuple[Tuple, jax.Array, Dict]] = {}
        self._stack_bytes = 0
        # Device-cache budgets (bytes, LRU-evicted). The stacked tensors
        # duplicate the per-leaf planes they're built from, so both caches
        # need a byte bound, not an entry bound — one TopN candidate list
        # can be 1000x the size of a 2-leaf count stack. Defaults are
        # sized for a serving chip (v5e: 16 GiB HBM): a 256-candidate x
        # 8-shard TopN stack alone is ~268 MiB, so sub-GiB budgets thrash
        # on every ranked-cache TopN.
        default_budget = (3 << 30) if self.platform == "tpu" else (1 << 29)
        if tier_config.hbm_bytes > 0:
            # [tier] hbm-bytes is the COMBINED device-cache budget, split
            # evenly; an explicit [engine] budget or legacy env var for
            # one cache still wins for that cache.
            default_budget = max(1, int(tier_config.hbm_bytes) // 2)

        def budget(env_name: str, cfg_val: int, default: int) -> int:
            v = os.environ.get(env_name)
            if v is not None:
                return int(v)
            return int(cfg_val) if cfg_val > 0 else default

        self._leaf_budget = budget(
            "PILOSA_LEAF_CACHE_BYTES", config.leaf_cache_bytes, default_budget)
        self._stack_budget = budget(
            "PILOSA_STACK_CACHE_BYTES", config.stack_cache_bytes,
            default_budget)
        self._stack_jit: Optional[Callable] = None
        self._join_jit: Optional[Callable] = None
        self._count_fns: Dict[Tuple, Callable] = {}
        self._bitmap_fns: Dict[Tuple, Callable] = {}
        # Compiled-program caches are LRU-bounded by entry count: each entry
        # pins an XLA executable, and a long-lived server seeing varied query
        # shapes would otherwise accumulate them without bound.
        self._fn_budget = int(os.environ.get("PILOSA_FN_CACHE_ENTRIES", 256))
        # key -> (Event, builder thread); see _gate.
        self._building: Dict[Tuple, Tuple] = {}
        # The server handles requests on ThreadingHTTPServer threads, so
        # every cache (LRU touch included) mutates under concurrency. One
        # lock guards dict + byte-counter state; device work (gather,
        # device_put, jit) happens outside it.
        self._lock = threading.RLock()
        # Host-side hot-query result memo: (index, structure signature,
        # leaves, shards) -> (generation fingerprint, count). A repeat query
        # whose fragments haven't changed skips the device round trip
        # entirely — O(dict lookup + generation check) instead of a device
        # dispatch. Invalidated by the same per-fragment generation
        # counters as the leaf cache.
        self._memo: Dict[Tuple, Tuple[Tuple, int]] = {}
        self._memo_budget = budget(
            "PILOSA_MEMO_ENTRIES", config.memo_entries, 8192)
        # Composite-result memo (TopN per-shard matrices, BSI val counts):
        # a repeat TopN pays zero device round trips — phase-1 AND the
        # phase-2 refetch hit here. Bounded by entries (values are small
        # (R,S) host arrays); shares the memo hit/miss counters.
        self._aux_memo: Dict[Tuple, Tuple[Tuple, object]] = {}
        # (field, the requested row ids' bytes) -> _topn_rows' answer. A
        # TopN over thousands of rows asks for the same chunks of 512 at
        # every query, so the per-row Python of a launch (canonical order,
        # a Leaf a row) is paid once a chunk and not once a launch.
        # Dropped whole when it outgrows its bound: no lock, no LRU walk.
        self._topn_rows_memo: Dict[Tuple, Tuple] = {}
        self._aux_budget = budget(
            "PILOSA_AUX_MEMO_ENTRIES", config.aux_memo_entries, 512)
        # Effective cache bounds after env > config > tier > default
        # resolution, surfaced verbatim in /debug/vars (engine_budgets) so
        # a deployment can SEE what its knobs resolved to.
        self.budgets = {
            "leaf_cache_bytes": self._leaf_budget,
            "stack_cache_bytes": self._stack_budget,
            "memo_entries": self._memo_budget,
            "aux_memo_entries": self._aux_budget,
            "fn_cache_entries": self._fn_budget,
        }
        # Observable cache behavior (hit rate / eviction pressure) for
        # /debug/vars (the `engine_cache` group).
        self.counters = {
            "leaf_hits": 0, "leaf_misses": 0, "leaf_evictions": 0,
            "stack_hits": 0, "stack_misses": 0, "stack_evictions": 0,
            "memo_hits": 0, "memo_misses": 0,
            # Stale entries (leaf, stack) and how their question "what
            # was written since my stamp?" was answered: fp_journal_reads
            # asked the view's change journal (bumped without the lock,
            # so it may undercount), fp_walks fell to asking every
            # fragment of the view because the journal could not say.
            # leaf_republished / stack_republished: stale entries none of
            # whose rows had been written, made fresh with no fragment
            # touched.
            "fp_journal_reads": 0, "fp_walks": 0,
            "leaf_republished": 0, "stack_republished": 0,
            # Compiled-program (XLA executable) cache traffic: the proof
            # that canonicalized query shapes SHARE programs is
            # fn_cache_hits climbing while fn_cache_builds stays flat
            # across commutative/associative respellings of one tree.
            "fn_cache_hits": 0, "fn_cache_builds": 0,
            # fn_cache_builds by kind of program (they sum to it): which
            # program a window built, readable from counter growth alone.
            **{f"fn_builds_{kind}": 0 for kind in FN_KINDS},
            # Device-program launches (memo hits dispatch nothing). The
            # scheduler's coalescing proof is dispatches/query < 1, so the
            # counters must distinguish a launch from an answered query.
            "count_dispatches": 0, "bitmap_dispatches": 0,
            # Bytes of resident planes handed to the programs launched
            # (planes x padded shards x 131,072 B each): what a launch
            # gives the device to read. restack_bytes: bytes of planes
            # copied into a fresh (U, S, W) stack on the device, the
            # copies that are most of the device's busy time under writes.
            "plane_bytes_read": 0, "restack_bytes": 0,
            # On a mesh of more than one device: launches (those that
            # _note_launch counts) whose program spans the devices, with
            # the all-reduces XLA puts in or the gather kernel's psum. 0
            # on one device. h2d_bytes: host bytes handed to the device by
            # the refresh paths, whichever ran (a cold or tier-promoted
            # plane's device_put, a delta scatter's indices and values).
            "mesh_launches": 0, "h2d_bytes": 0,
            # Batched-count launches that went through the Pallas gather
            # kernel rather than the XLA formulation (a subset of
            # count_dispatches): the only outside evidence of which of
            # the two served a coalesced batch.
            "gather_kernel_dispatches": 0,
            # Launches over a resident stack that is kept folded onto the
            # sublanes (parallel/mesh.py stack_fold: fewer than 8 shards a
            # device). 0 for good from 8 shards a device up.
            "folded_launches": 0,
            # Delta-refresh accounting: delta hits refreshed a stale
            # resident tensor with a scattered update (delta_bytes of
            # host->device traffic) instead of a full host walk + re-upload
            # (full_refresh_bytes counts those); tests/test_delta.py holds
            # delta_bytes << full_refresh_bytes at equal correctness under
            # mixed read/write traffic.
            "leaf_delta_hits": 0, "stack_delta_hits": 0,
            "delta_bytes": 0, "full_refresh_bytes": 0,
            # Tiered-storage accounting: an HBM miss answered by
            # decompressing a demoted plane from the host/disk tier
            # (leaf_tier_hits) instead of a cold container walk
            # (leaf_misses). Memo/aux evictions close the observability
            # gap the leaf/stack caches never had.
            "leaf_tier_hits": 0, "tier_promote_bytes": 0,
            "memo_evictions": 0, "aux_evictions": 0,
            # _byte_cache_put's explicit oversized-entry policy: an entry
            # bigger than its whole budget is admitted ALONE (everything
            # else evicts) and counted here — rejecting it would make the
            # largest plane permanently uncacheable (regather per query),
            # strictly worse than holding it.
            "oversized_admits": 0,
            # Background tier-hook failures (promotion gather / demotion
            # capture) and fast-path compile-gate refusals: each swallows
            # the exception by design (the caller has a correct fallback),
            # so the COUNT is the only externally visible trace.
            "tier_promote_errors": 0, "tier_demote_errors": 0,
            "compile_gate_refusals": 0,
            # Device-fault ladder accounting (docs/fault-tolerance.md):
            # host_counts/host_topn are queries answered entirely on the
            # host (degraded ladder), host_cold_counts the healthy
            # compressed-domain path for one-off queries on demoted
            # planes; oom_backpressure counts budget shrinks, oom_retries
            # dispatches that succeeded after one, oom_batch_splits
            # reduced-batch retries, watchdog_timeouts dispatches the
            # watchdog abandoned, device_dispatch_errors every classified
            # dispatch failure (per-kind detail in device_plane).
            "host_counts": 0, "host_topn": 0, "host_cold_counts": 0,
            "oom_backpressure": 0, "oom_retries": 0, "oom_batch_splits": 0,
            "watchdog_timeouts": 0, "device_dispatch_errors": 0,
        }
        # Tier manager (tier/manager.py): owns the host-RAM + disk tiers
        # below the device caches. Leaf evictions demote through it and
        # cold gathers probe it before paying the container walk.
        self.tier = None
        if tier_config.enabled():
            from ..tier.manager import TierManager

            self.tier = TierManager(
                self.holder, tier_config, traffic_fn=traffic_fn)
            self.tier.bind(
                promote_fn=self._tier_promote_key,
                headroom_fn=self._hbm_headroom,
                resident_fn=self._tier_resident,
            )

    def stack_generation(self, index: str) -> int:
        """O(1) write epoch of an index's resident leaf stacks (bumped by
        every fragment mutation, core/fragment.py WriteEpoch). The micro-
        batcher keys coalescing groups on it so one fused launch never
        mixes queries that straddle a visible write."""
        idx = self.holder.index(index)
        return -1 if idx is None else idx.write_epoch.value

    def _note_launch(self, planes, counter: Optional[str] = None,
                     kernel: bool = False, stack=None) -> None:
        """One device-program launch over `planes`, the resident arrays
        handed to it; `counter` is the launch counter of its family (the
        TopN and BSI programs have none); `stack` is the resident stack
        among them, where the program reads one."""
        nbytes = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(planes))
        folded = stack is not None and bp.fold_of(stack) > 1
        with self._lock:
            if counter is not None:
                self.counters[counter] += 1
            self.counters["plane_bytes_read"] += nbytes
            self.counters["folded_launches"] += folded
            self.counters["gather_kernel_dispatches"] += kernel
            self.counters["mesh_launches"] += self.n_devices > 1

    def snapshot(self) -> dict:
        """Wholesale counter export for /debug/vars (the `engine_cache`
        group). Every key in self.counters is observable through here —
        pilint R4 relies on that, so new counters need no wiring."""
        with self._lock:
            return dict(self.counters)

    def device_info(self) -> dict:
        """What this engine runs on, as JAX reports it (/debug/vars
        `device`): the mesh's platform, kind, size and shape, and each
        device's allocator figures where the backend has any (the CPU
        backend reports none). `cached_plane_bytes`, one figure a device
        in the same order, is where the leaf and stack caches' arrays
        really lie."""
        devices = list(self.mesh.devices.flat)
        with self._lock:
            cached = [e[1] for cache in (self._leaf_cache, self._stack_cache)
                      for e in cache.values()]
        held = dict.fromkeys(devices, 0)
        for arr in cached:
            sharding = arr.sharding
            piece = arr.dtype.itemsize * math.prod(
                sharding.shard_shape(arr.shape))
            for d in sharding.device_set:
                held[d] += piece
        per_device = []
        for d in devices:
            stats = d.memory_stats() or {}
            per_device.append({
                "id": d.id,
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            })
        return {
            "platform": self.platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices),
            "mesh_shape": dict(self.mesh.shape),
            "devices": per_device,
            "cached_plane_bytes": [held[d] for d in devices],
        }

    def close(self) -> None:
        """Release host-side serving resources (the cold-gather thread
        pool — its workers are non-daemon, so an embedder that opens and
        closes executors repeatedly would otherwise leak them). The tier
        manager stops FIRST so its prefetch thread can't race the pool
        shutdown with a promotion."""
        if self.tier is not None:
            self.tier.close()
        with self._lock:
            pool, self._gather_pool = self._gather_pool, None
            wpool, self._watchdog_pool = self._watchdog_pool, None
        if pool is not None:
            pool.shutdown(wait=False)
        if wpool is not None:
            wpool.shutdown(wait=False)

    # ----------------------------------------------------- tier integration
    #
    # The leaf cache is the TOP tier of the three-tier plane hierarchy
    # (docs/tiered-storage.md): evicted planes demote into the manager's
    # compressed host tier instead of vanishing, cold gathers probe the
    # manager before paying the container walk, and the manager's prefetch
    # thread re-promotes demoted planes of hot indexes through the hooks
    # below. All three hooks are engine-lock-cheap; the manager never
    # calls them while holding its own lock with ours taken.

    def _tier_promote_key(self, key) -> bool:
        """Prefetch hook: make `key` HBM-resident via the normal gather
        path (which consumes the tier entry and installs the plane)."""
        index, leaf, shards = key
        try:
            self._gather_leaf(index, leaf, shards)
            return True
        except Exception:
            with self._lock:
                self.counters["tier_promote_errors"] += 1
            return False

    def _hbm_headroom(self) -> int:
        with self._lock:
            return self._leaf_budget - self._leaf_bytes

    def _tier_resident(self, key) -> bool:
        with self._lock:
            return key in self._leaf_cache

    def _demote_keys(self, keys) -> None:
        """Demote freshly-evicted leaf planes into the host tier. Runs
        OUTSIDE the engine lock (demotion takes fragment mutexes and
        serializes containers — far too heavy for the cache lock)."""
        if not keys or self.tier is None:
            return
        for key in keys:
            try:
                self.tier.demote(key)
            except Exception:
                # The evicted plane simply stays cold (next read regathers
                # from the fragments); the count is the trace.
                with self._lock:
                    self.counters["tier_demote_errors"] += 1

    # ------------------------------------------------------------ caches
    #
    # All device caches (compiled programs, leaf planes, stacked tensors)
    # are mutated from concurrent ThreadingHTTPServer threads. `self._lock`
    # guards dict + byte-counter state; `_gate` /
    # `_release` dedupe expensive cold builds (XLA trace/compile, host
    # gathers, device_put) so N concurrent misses on a key do the work
    # once instead of N times (compile stampede).

    def _gate(self, key, probe: Callable):
        """Return probe()'s non-None value, or None once the caller holds
        the build gate for `key` — the caller then MUST publish a value and
        `_release(key)`, even on failure (_release runs in the builder's
        finally). Waiters re-probe when the builder releases. Ownership is
        stolen ONLY if the builder thread is no longer alive (interpreter
        teardown — finally makes a leaked gate otherwise impossible):
        stealing on a mere timeout would re-run 20-40s TPU compiles once
        per waiter during a cold-start stampede."""
        waited = 0
        while True:
            val = probe()
            if val is not None:
                return val
            with self._lock:
                entry = self._building.get(key)
                if entry is None:
                    self._building[key] = (
                        threading.Event(), threading.current_thread())
                    return None
                ev, builder = entry
            if ev.wait(timeout=10.0):
                continue
            waited += 1
            # Liveness escape hatch for a WEDGED (alive) builder — e.g. a
            # device call stuck in a hung runtime: complain at 1 minute,
            # steal at 5 (a redundant compile is the least of the problems
            # then). A dead builder (interpreter teardown) steals at once.
            if waited == 6:
                self.counters["gate_stalls"] = \
                    self.counters.get("gate_stalls", 0) + 1
            if not builder.is_alive() or waited >= 30:
                with self._lock:
                    if self._building.get(key) is entry:
                        self._building[key] = (
                            threading.Event(), threading.current_thread())
                        return None

    def _release(self, key) -> None:
        with self._lock:
            entry = self._building.pop(key, None)
        if entry is not None:
            entry[0].set()

    def _fn_probe(self, cache: Dict[Tuple, Callable], sig: Tuple) -> Optional[Callable]:
        with self._lock:
            fn = cache.get(sig)
            if fn is not None:
                cache[sig] = cache.pop(sig)  # LRU touch
                self.counters["fn_cache_hits"] += 1
            return fn

    def _fn_build(self, cache: Dict[Tuple, Callable], sig: Tuple,
                  build: Callable[[], Callable],
                  health_sig: Optional[Tuple] = None) -> Callable:
        """Get-or-build a compiled program, stampede-gated and LRU-bounded.

        A build failure is a DEVICE fault, not a query error: it is
        classified `compile`, recorded into the device breakers under the
        caller's structure signature (a shape whose program cannot build
        will fail every time — quarantining it to the per-shard path is
        exactly the breaker's job), and re-raised typed so the executor's
        ladder catches it. The `device-compile` failpoint makes the path
        deterministically testable; it fires only on a real cache miss,
        like a real compile failure would."""
        fn = self._gate(sig, lambda: self._fn_probe(cache, sig))
        if fn is not None:
            return fn
        kind = str(sig[0])
        try:
            try:
                failpoints.fire("device-compile")
                fn = build()
                # Counted AFTER a successful build: a failing compile
                # (breaker path) must not inflate the one-build-per-
                # canonical-shape proof counter.
                with self._lock:
                    self.counters["fn_cache_builds"] += 1
                    by_kind = f"fn_builds_{kind}"
                    self.counters[by_kind] = \
                        self.counters.get(by_kind, 0) + 1
            except Exception as e:
                with self._lock:
                    self.counters["device_dispatch_errors"] += 1
                self.device_health.record_failure(health_sig, COMPILE)
                raise DeviceDispatchError(
                    COMPILE, health_sig,
                    f"device program build failed: {e}") from e
            with self._lock:
                cache[sig] = fn
                while len(cache) > self._fn_budget:
                    cache.pop(next(iter(cache)))
        finally:
            self._release(sig)
        return fn if obs_current() is None else _FirstCall(fn, kind)

    # ------------------------------------------------------ dispatch guard
    #
    # Every device dispatch runs through _device_call: the `device-
    # dispatch` failpoint fires at exactly this boundary, the optional
    # watchdog bounds how long the serving thread blocks, failures are
    # classified (device_health.classify_device_error) and recorded into
    # the per-signature + plane breakers, and an HBM OOM gets
    # backpressure (shrink budgets, demote through the tier manager) plus
    # ONE same-size retry before the typed error escapes to the
    # executor's ladder. Gather-stage transfers use the lighter
    # _oom_guard: same backpressure, but non-OOM errors propagate raw
    # (a gather bug must not masquerade as a dispatch fault).

    _WATCHDOG_WORKERS = 4

    def _watchdog_done(self, _fut) -> None:
        with self._lock:
            self._watchdog_inflight -= 1

    def _watchdogged(self, fn: Callable, fire: bool = True):
        def run():
            if fire:
                failpoints.fire("device-dispatch")
            return fn()

        if self._watchdog_s <= 0:
            return run()
        with self._lock:
            if self._watchdog_inflight >= self._WATCHDOG_WORKERS:
                # Every watchdog slot is occupied (normally: parked on
                # wedged dispatches). Dispatch inline unwatchdogged —
                # the breaker still routes around repeated failures; we
                # just can't bound this one call's latency. Submitting
                # instead would queue the task and misread queue delay
                # as a device timeout.
                inline = True
            else:
                if self._watchdog_pool is None:
                    from concurrent.futures import ThreadPoolExecutor

                    self._watchdog_pool = ThreadPoolExecutor(
                        max_workers=self._WATCHDOG_WORKERS,
                        thread_name_prefix="pilosa-dispatch",
                    )
                self._watchdog_inflight += 1
                inline = False
                pool = self._watchdog_pool
        if inline:
            return run()
        from concurrent.futures import TimeoutError as FutTimeout

        fut = pool.submit(run)
        # Fires when the task finishes, is cancelled, or (wedged case)
        # whenever the runtime finally answers — inflight stays elevated
        # exactly while a worker is actually occupied.
        fut.add_done_callback(self._watchdog_done)
        try:
            return fut.result(timeout=self._watchdog_s)
        except FutTimeout:
            if fut.cancel():
                # Never started: the timeout measured pool queueing, not
                # the device. Not a fault — dispatch inline.
                return run()
            # Started and wedged: the task cannot be killed — it keeps
            # its worker parked until the runtime answers. The watchdog
            # frees the SERVING thread; the breaker stops new work from
            # piling onto a wedged device.
            with self._lock:
                self.counters["watchdog_timeouts"] += 1
            raise DeviceDispatchTimeout(
                f"device dispatch exceeded the {self._watchdog_s:.3f}s "
                "watchdog")

    def _device_call(self, health_sig: Optional[Tuple], fn: Callable,
                     fire: bool = True):
        """Run one device dispatch under the fault ladder; returns fn()'s
        value. On failure: classify, record into the breakers, re-raise
        as DeviceDispatchError (the executor's catch point). OOM gets
        backpressure + one retry first — a transient allocation failure
        must never reach a client."""
        try:
            result = self._watchdogged(fn, fire=fire)
        except Exception as e:
            with self._lock:
                self.counters["device_dispatch_errors"] += 1
            kind = classify_device_error(e)
            if kind == OOM:
                self._oom_backpressure()
                try:
                    result = self._watchdogged(fn, fire=fire)
                except Exception as e2:
                    kind2 = classify_device_error(e2)
                    self.device_health.record_failure(health_sig, kind2)
                    raise DeviceDispatchError(
                        kind2, health_sig, str(e2)) from e2
                with self._lock:
                    self.counters["oom_retries"] += 1
                self.device_health.record_success(health_sig)
                return result
            self.device_health.record_failure(health_sig, kind)
            raise DeviceDispatchError(kind, health_sig, str(e)) from e
        self.device_health.record_success(health_sig)
        return result

    def _oom_guard(self, health_sig: Optional[Tuple], fn: Callable):
        """Gather-stage transfer guard (device_put, restack): an HBM OOM
        gets backpressure + one retry; any other failure is a DEVICE
        fault at transfer time (a dead device erroring in device_put) — it
        is classified, recorded into the breakers, and re-raised typed so
        the executor's ladder catches it. Without that, a device that
        dies at the transfer stage would 500 every query forever with the
        plane breaker still CLOSED."""
        try:
            return fn()
        except Exception as e:
            with self._lock:
                self.counters["device_dispatch_errors"] += 1
            kind = classify_device_error(e)
            if kind != OOM:
                self.device_health.record_failure(health_sig, kind)
                raise DeviceDispatchError(kind, health_sig, str(e)) from e
            self._oom_backpressure()
            try:
                return fn()
            except Exception as e2:
                kind = classify_device_error(e2)
                self.device_health.record_failure(health_sig, kind)
                raise DeviceDispatchError(
                    kind, health_sig, str(e2)) from e2

    def _oom_backpressure(self) -> None:
        """HBM pressure response: halve the effective leaf/stack budgets
        (floored at 1 MiB), evict down to them, and demote the evicted
        planes through the tier manager — free real HBM before the retry
        instead of bouncing RESOURCE_EXHAUSTED to the client. The shrink
        is sticky (the budget stays down for the process lifetime): an
        OOM means the configured budget overcommitted this chip."""
        evicted: List = []
        with self._lock:
            self.counters["oom_backpressure"] += 1
            floor = 1 << 20
            self._leaf_budget = max(self._leaf_budget // 2, floor)
            self._stack_budget = max(self._stack_budget // 2, floor)
            self.budgets["leaf_cache_bytes"] = self._leaf_budget
            self.budgets["stack_cache_bytes"] = self._stack_budget
            while self._leaf_bytes > self._leaf_budget and self._leaf_cache:
                key = next(iter(self._leaf_cache))
                self._leaf_bytes -= self._leaf_cache.pop(key)[1].nbytes
                self.counters["leaf_evictions"] += 1
                evicted.append(key)
            while self._stack_bytes > self._stack_budget and self._stack_cache:
                key = next(iter(self._stack_cache))
                self._stack_bytes -= self._stack_cache.pop(key)[1].nbytes
                self.counters["stack_evictions"] += 1
        self._demote_keys(evicted)

    def _byte_cache_put(self, cache: Dict, key, entry: Tuple, budget: int,
                        used: int, evict_counter: str = "",
                        evicted: Optional[List] = None) -> int:
        """Insert (fingerprint, array) at MRU and evict LRU entries past the
        byte budget; returns the updated used-bytes counter. Caller holds
        self._lock.

        Oversized-entry policy (explicit, tested): an entry whose payload
        exceeds the WHOLE budget is admitted alone — every other entry
        evicts and the insert is counted in `oversized_admits`. The
        alternative (reject-and-count) would make the largest plane
        permanently uncacheable and re-gathered per query, strictly worse
        than briefly over-committing; `used` stays exact either way so the
        next insert immediately evicts back under budget.

        `evicted` (when a list) collects the evicted KEYS so the caller
        can demote those planes into the tier manager after releasing the
        lock — eviction is demotion, not loss (docs/tiered-storage.md)."""
        prev = cache.pop(key, None)
        if prev is not None:
            used -= prev[1].nbytes
        used += entry[1].nbytes
        cache[key] = entry
        if entry[1].nbytes > budget:
            self.counters["oversized_admits"] += 1
        while used > budget and len(cache) > 1:
            old_key = next(iter(cache))
            if old_key == key:
                break
            used -= cache.pop(old_key)[1].nbytes
            if evict_counter:
                self.counters[evict_counter] += 1
            if evicted is not None:
                evicted.append(old_key)
        return used

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def _placing(self, nbytes: int):
        """The span around a host-to-device placement on a refresh path
        (a cold plane's device_put, a delta scatter's operands); a cache
        hit never comes here."""
        return obs_span("engine.place", bytes=int(nbytes),
                        devices=self.n_devices)

    # --------------------------------------------------------- leaf tensors

    def _leaf_fragments(self, index: str, leaf: Leaf,
                        shards: Tuple[int, ...]):
        """(fragments, per-shard pairs) of one leaf's view: the walk, one
        holder lookup per shard (None where a shard has no fragment) and
        the (incarnation, generation) pairs READ FROM THOSE fragments.
        Whoever goes on to read the fragments' data (a cold or demoted
        plane in _gather_leaf, _host_plane) comes here, so that the two
        always belong together; so does a stale leaf whose journal cannot
        say what changed (_leaf_delta, which counts it in fp_walks)."""
        fragment = self.holder.fragment
        frags = [fragment(index, leaf.field, leaf.view, s) for s in shards]
        return frags, tuple(
            -1 if f is None else (f.incarnation, f.generation) for f in frags)

    def _journal(self, index: str, field: str, view: str):
        """The change journal of one view (core/fragment.py
        ChangeJournal), or None where there is no such view."""
        idx = self.holder.index(index)
        fld = None if idx is None else idx.field(field)
        v = None if fld is None else fld.view(view)
        return None if v is None else v.journal

    @staticmethod
    def _stamps(journals) -> Tuple:
        return tuple(-1 if j is None else j.stamp for j in journals)

    def _fingerprint(self, index: str, leaves) -> Tuple:
        """The staleness key of every cache (no device work, no fragment
        touched): the `(incarnation, seq)` stamp of the change journal of
        each distinct view among `leaves`, in order of first appearance;
        -1 for a view that is not there. Two fingerprints are compared
        with one comparison per view, whatever the number of shards.

        A stamp moves with every write to ANY fragment of its view, also
        one in a shard outside the shards a caller asked about: on a node
        that owns part of a view an entry is found stale more often than
        the per-shard pairs found it, never less often. The incarnation
        half makes a view that was made again (a deleted field or index
        re-made under the same name) never compare equal to an old entry.

        Why a cache may trust it: a writer moves its fragment's generation,
        then notes the write in the journal (the entry first, the stamp
        that covers it after), and only THEN bumps the index's epoch
        (core/fragment.py `_invalidate_row`, `_invalidate_all`; what adds
        or drops a fragment of a view notes ALL_ROWS after the change). So
        data read after a stamp holds every write the stamp covers, and a
        caller that read the epoch before coming here (memo_probe) holds
        at worst a stamp NEWER than its epoch token, which the next probe
        re-reads; one OLDER than its token it cannot hold."""
        views = dict.fromkeys((leaf.field, leaf.view) for leaf in leaves)
        return self._stamps(self._journal(index, f, v) for f, v in views)

    def _changed(self, journals, old: Tuple, new: Tuple, leads):
        """What was written to an entry's rows between the fingerprint
        `old` it carries and `new`: one `{(shard, row): fp}` per view
        (ChangeJournal.changed), all of them empty where nothing was; None
        where a journal cannot say. `leads` has one dict per view, keyed
        by the rows the entry holds of it. No lock, no fragment touched."""
        self.counters["fp_journal_reads"] += 1
        out = []
        for journal, o, n, lead in zip(journals, old, new, leads):
            cells = {} if o == n else (
                None if journal is None else journal.changed(o, n, lead))
            if cells is None:
                return None
            out.append(cells)
        return out

    def _republish(self, cache: Dict, key, cached: Tuple, fp,
                   counter: str) -> None:
        """`cached`, none of whose rows was written up to `fp`, is fresh
        again under `fp`, unless another thread has put a newer entry in
        its place meanwhile."""
        with self._lock:
            if cache.get(key) is cached:
                cache.pop(key)  # and back in at the MRU end
                cache[key] = (fp,) + cached[1:]
            self.counters[counter] += 1

    def _named_members(self, index: str, field: str, view: str,
                       shards: Tuple[int, ...], cells: Dict, lead: Dict):
        """_collect_updates members for the cells a journal named.
        `lead[row]` lists the leading coordinates of `row` in the cached
        tensor: `[()]` for a leaf, `[(u,), ...]` for a stack."""
        for (shard, row), old in cells.items():
            try:
                i = shards.index(shard)
            except ValueError:
                continue  # a shard the entry does not cover
            frag = self.holder.fragment(index, field, view, shard)
            for coords in lead[row]:
                yield coords + (i,), frag, row, old

    def _gather_leaf(self, index: str, leaf: Leaf, shards: Tuple[int, ...]) -> jax.Array:
        """(S_padded, W) uint32, sharded over the mesh's shard axis.

        A resident plane whose stamp is the journal's is served as it
        stands. One whose stamp is older asks the journal what was written
        since (`_changed`): if nothing names its row it is republished
        under the new stamp from inside the probe (no gate, no span, no
        fragment touched); if cells of its row are named, only those go
        through the delta scatter (`_leaf_delta`). Where the journal cannot
        say, `_leaf_delta` asks the fragments, as it did for every stale
        plane before there was a journal."""
        s_padded = pad_shards(len(shards), self.n_devices)
        key = (index, leaf, shards)
        journal = self._journal(index, leaf.field, leaf.view)
        lead = {leaf.row: ((),)}

        def probe():
            with self._lock:
                # Read under the lock that entries are published under:
                # no entry then carries a stamp newer than this one.
                stamp = -1 if journal is None else journal.stamp
                cached = self._leaf_cache.get(key)
                if cached is None:
                    return None
                fresh = cached[0] == stamp
                if fresh:
                    self._leaf_cache[key] = self._leaf_cache.pop(key)  # LRU touch
                    self.counters["leaf_hits"] += 1
            if not fresh:
                cells = self._changed(
                    (journal,), (cached[0],), (stamp,), (lead,))
                if cells is None or cells[0]:
                    return None
                self._republish(self._leaf_cache, key, cached, stamp,
                                "leaf_republished")
            if self.tier is not None and self.tier.has_prefetched():
                self.tier.note_hbm_hit(key)
            return cached[1]

        arr = self._gate(("leaf", key), probe)
        if arr is not None:
            return arr
        evicted: List = []
        # The gather stage is where a slow query's time hides: the trace
        # span tags WHICH refresh path ran (delta scatter vs compressed-
        # tier promote vs cold container walk) so /debug/traces answers
        # "why was this gather 30 ms" without correlating counters.
        with obs_span("gather") as sp:
            try:
                with self._lock:
                    stale = self._leaf_cache.get(key)
                    # The stamp the refreshed plane will carry, read
                    # BEFORE the fragments' data (the order every cache
                    # relies on) and after the entry it is compared with.
                    stamp = -1 if journal is None else journal.stamp
                # Stale resident entry: try the delta path first — upload
                # only the words the writes changed instead of re-walking
                # every shard's containers and re-shipping the whole plane.
                if stale is not None:
                    arr = self._leaf_delta(key, stale, journal, stamp, lead,
                                           evicted)
                    if arr is not None:
                        sp.tag(kind="delta")
                        return arr
                # Only from here on are the fragments' data read, so only
                # here are they all looked up.
                frags, pairs = self._leaf_fragments(index, leaf, shards)
                # Demoted plane? Decode the compressed host/disk-tier image
                # (journal deltas folded) instead of walking every shard's
                # live containers.
                buf = None
                if self.tier is not None:
                    buf = self.tier.promote(key, frags, pairs, s_padded)
                tier_hit = buf is not None
                if buf is None:
                    buf = self._host_gather(frags, leaf.row, s_padded)
                if sp is not NOP_SPAN:
                    sp.tag(kind="tier-promote" if tier_hit else "cold",
                           bytes=int(buf.nbytes))
                with self._placing(buf.nbytes):
                    arr = self._oom_guard(None, lambda: jax.device_put(
                        buf, shard_sharding(self.mesh, 2)))
                with self._lock:
                    self.counters["h2d_bytes"] += buf.nbytes
                    if tier_hit:
                        self.counters["leaf_tier_hits"] += 1
                        self.counters["tier_promote_bytes"] += buf.nbytes
                    else:
                        self.counters["leaf_misses"] += 1
                        self.counters["full_refresh_bytes"] += buf.nbytes
                    self._leaf_bytes = self._byte_cache_put(
                        self._leaf_cache, key, (stamp, arr, pairs),
                        self._leaf_budget, self._leaf_bytes, "leaf_evictions",
                        evicted,
                    )
            finally:
                self._release(("leaf", key))
                # Evicted planes demote off-lock whichever path installed
                # the fresh entry (full gather, tier promote, or delta
                # refresh).
                self._demote_keys(evicted)
        return arr

    # ------------------------------------------------------- cold gather

    def _pool(self):
        with self._lock:
            if self._gather_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._gather_pool = ThreadPoolExecutor(
                    max_workers=self._gather_workers,
                    thread_name_prefix="pilosa-gather",
                )
            return self._gather_pool

    def _host_gather(self, frags, row: int, s_padded: int) -> np.ndarray:
        """Cold-path host assembly of an (S_padded, W) plane buffer. The
        per-shard container walks are independent pure reads (fragment
        reads are lock-free by design), so they thread-pool; the device
        transfer of leaf k overlaps leaf k+1's walk for free via jax async
        dispatch, so no cross-leaf pipeline is needed on top."""
        buf = np.zeros((s_padded, WORDS_PER_ROW), dtype=np.uint32)
        live = [(i, f) for i, f in enumerate(frags) if f is not None]
        if len(live) > 1 and self._gather_workers > 1:
            def fill(item):
                i, frag = item
                buf[i] = frag.plane_np(row)

            list(self._pool().map(fill, live))
        else:
            for i, frag in live:
                buf[i] = frag.plane_np(row)
        return buf

    # ------------------------------------------------------ delta refresh
    #
    # A write to a resident fragment bumps its generation; without deltas
    # the next query pays a full host container walk over EVERY shard of
    # the leaf plus a full (S, W) re-upload and a restack of every (U, S,
    # W) stack containing it — O(plane) work for a 1-bit write. The dirty-
    # word journal (core/fragment.py) lets stale members report exactly
    # which 64-bit words changed; while the total stays under
    # delta_max_fraction of the tensor, the refresh is a small (indices,
    # values) device_put + one jitted scatter into the cached array.
    #
    # The scatter is functional (.at[].set builds a new on-device array),
    # NOT buffer-donating: concurrent readers that probed before the write
    # may still be dispatching programs against the old buffer, and
    # donation would invalidate it under them. The on-device copy is HBM-
    # bandwidth cheap; the win is eliminating the host walk and the
    # host->device plane transfer.

    def _collect_updates(self, members, size: int):
        """Shared delta collector for the leaf and stack paths (one body so
        the guards/budget/incarnation logic cannot diverge between them).

        `members`: iterable of (coords, frag, row, old_fp) per cache
        member that may have changed — coords are the member's indices in
        the cached tensor ((shard,) for a leaf, (u, shard) for a stack),
        `frag` the fragment as it is looked up now, `old_fp` the
        (incarnation, generation) pair the cached words are at least as
        new as (-1: there was no fragment). `size` is the cached tensor's
        element count (the delta budget base).

        Returns None when only a full regather is safe (missing fragment,
        fragment recreated since the fp was read, journal can't answer,
        budget exceeded), else a list of (coords, col32 indices, uint32
        values) triples — possibly empty, meaning the generation churn came
        from rows outside the cache and zero bytes need to move."""
        out = []
        n32 = 0
        for coords, frag, row, old_fp in members:
            if frag is None or old_fp == -1:
                return None
            if frag.incarnation != old_fp[0]:
                # Different incarnation: the journal's generations are not
                # comparable across it.
                return None
            w = frag.dirty_words_since(row, old_fp[1])
            if w is None:
                return None
            if not len(w):
                continue
            n32 += 2 * len(w)
            if n32 > self._delta_max_fraction * size:
                return None
            cols, vals = self._updates32(w, frag.row_words64(row, w))
            out.append((coords, cols, vals))
        return out

    @staticmethod
    def _updates32(w64: np.ndarray, v64: np.ndarray):
        """Expand 64-bit dirty words into the (col32 indices, uint32
        values) pairs of the device plane layout. The interleave matches
        plane_np's `.view(np.uint32)` on the same host, so the scattered
        words are byte-identical to a regathered plane."""
        cols = np.empty(2 * len(w64), dtype=np.int32)
        cols[0::2] = w64 * 2
        cols[1::2] = w64 * 2 + 1
        return cols, v64.view(np.uint32)

    @staticmethod
    def _pad_updates(arrays):
        """Pad parallel index/value arrays to a pow2 length of at least
        DELTA_MIN_UPDATES by repeating entry 0 (a duplicate scatter of the
        SAME value is deterministic), so varying delta sizes reuse a
        handful of compiled programs."""
        n = len(arrays[0])
        npad = max(DELTA_MIN_UPDATES, 1 << (n - 1).bit_length())
        if npad == n:
            return arrays
        return [np.concatenate([a, np.repeat(a[:1], npad - n)]) for a in arrays]

    def _leaf_delta(self, key, stale, journal, stamp, lead: Dict,
                    evicted: Optional[List] = None):
        """Refresh a stale cached (S, W) leaf up to `stamp`; None = caller
        must full-regather. `evicted` collects evicted keys for demotion.

        The cells to look at are those the journal names. Where it cannot
        say, the safe rung: every fragment of the view is asked
        (`fp_walks`) and each shard whose pair differs from the one the
        entry kept from its last walk is looked at."""
        index, leaf, shards = key
        old_stamp, arr, kept = stale
        if self._delta_max_fraction <= 0:
            return None
        cells = self._changed((journal,), (old_stamp,), (stamp,), (lead,))
        if cells is not None:
            # The kept pairs stand: a cell's words are no older for having
            # been patched, and `dirty_words_since` an older generation
            # names more words, never fewer.
            pairs = kept
            members = self._named_members(
                index, leaf.field, leaf.view, shards, cells[0], lead)
        else:
            with self._lock:
                self.counters["fp_walks"] += 1
            frags, pairs = self._leaf_fragments(index, leaf, shards)
            if len(kept) != len(pairs):
                return None
            members = (((i,), frag, leaf.row, kept[i])
                       for i, frag in enumerate(frags) if kept[i] != pairs[i])
        updates = self._collect_updates(members, arr.size)
        if updates is None:
            return None
        if not updates:
            # Nothing in THIS row changed: republish the same device array
            # under the fresh fingerprint (zero bytes moved).
            new_arr, moved = arr, 0
        else:
            rows, cols, vals = self._pad_updates([
                np.concatenate([np.full(len(c), co[0], np.int32)
                                for co, c, _ in updates]),
                np.concatenate([c for _, c, _ in updates]),
                np.concatenate([v for _, _, v in updates]),
            ])
            sig = ("leaf_delta", arr.shape, len(rows))
            def leaf_delta_scatter(a, r, c, v):
                return a.at[r, c].set(v)

            fn = self._fn_build(self._count_fns, sig, lambda: jax.jit(
                leaf_delta_scatter,
                out_shardings=shard_sharding(self.mesh, 2),
            ))
            moved = int(rows.nbytes + cols.nbytes + vals.nbytes)
            with self._placing(moved):
                new_arr = fn(arr, rows, cols, vals)
        with self._lock:
            self.counters["leaf_delta_hits"] += 1
            self.counters["delta_bytes"] += moved
            self.counters["h2d_bytes"] += moved
            self._leaf_bytes = self._byte_cache_put(
                self._leaf_cache, key, (stamp, new_arr, pairs),
                self._leaf_budget, self._leaf_bytes, "leaf_evictions",
                evicted,
            )
        return new_arr

    def _stack_delta(self, key, stale, journals, fp: Tuple):
        """Refresh a stale (U, S, W) stack up to `fp` with one scattered
        update of the cells the journals name — no host walk, no member
        re-gather, no restack. None = full rebuild: also where a journal
        cannot say, for then each member plane finds out for itself
        (_gather_leaf and its safe rung) and the stack is built of them.
        In a stack kept folded as (U, S*k, W//k) word c of shard r lies at
        [u, r*k + c // (W//k), c % (W//k)]: the same scatter, addressed
        so on the host's small update arrays."""
        index, leaves, shards, _ = key
        old_fp, arr, by_view = stale
        if self._delta_max_fraction <= 0:
            return None
        cells = self._changed(journals, old_fp, fp, by_view.values())
        if cells is None:
            return None
        updates = self._collect_updates(
            (member for ((field, view), lead), named in zip(
                by_view.items(), cells)
             for member in self._named_members(
                 index, field, view, shards, named, lead)),
            arr.size)
        if updates is None:
            return None
        # Padding rows duplicate leaf 0; today no compiled program
        # reads them, but the full-rebuild invariant is pad == leaf 0's
        # CURRENT plane, so replicate leaf-0 updates onto every pad row
        # rather than trusting a comment to keep them unread forever.
        leaf0 = [(co, c, v) for co, c, v in updates if co[0] == 0]
        for pad_u in range(len(leaves), arr.shape[0]):
            updates.extend(((pad_u, co[1]), c, v) for co, c, v in leaf0)
        if not updates:
            new_arr, moved = arr, 0
        else:
            us, rows, cols, vals = self._pad_updates([
                np.concatenate([np.full(len(c), co[0], np.int32)
                                for co, c, _ in updates]),
                np.concatenate([np.full(len(c), co[1], np.int32)
                                for co, c, _ in updates]),
                np.concatenate([c for _, c, _ in updates]),
                np.concatenate([v for _, _, v in updates]),
            ])
            k, wk = bp.fold_of(arr), arr.shape[2]
            if k > 1:
                rows, cols = rows * k + cols // wk, cols % wk
            sig = ("stack_delta", arr.shape, len(us))
            def stack_delta_scatter(a, u, r, c, v):
                return a.at[u, r, c].set(v)

            fn = self._fn_build(self._count_fns, sig, lambda: jax.jit(
                stack_delta_scatter,
                out_shardings=shard_sharding(self.mesh, 3, axis=1),
            ))
            moved = int(us.nbytes + rows.nbytes + cols.nbytes + vals.nbytes)
            with self._placing(moved):
                new_arr = fn(arr, us, rows, cols, vals)
        with self._lock:
            self.counters["stack_delta_hits"] += 1
            self.counters["delta_bytes"] += moved
            self.counters["h2d_bytes"] += moved
            self._stack_bytes = self._byte_cache_put(
                self._stack_cache, key, (fp, new_arr, by_view),
                self._stack_budget, self._stack_bytes, "stack_evictions",
            )
        return new_arr

    def _leaf_tensor(self, index: str, leaves: List[Leaf], shards: Tuple[int, ...]):
        """Tuple of per-leaf (S, W) sharded arrays. Passed as a pytree into
        jitted query fns so each input keeps its NamedSharding (stacking
        outside jit would re-lay-out the data)."""
        return tuple(self._gather_leaf(index, leaf, shards) for leaf in leaves)

    def _stacked_leaf_tensor(
        self, index: str, leaves: List[Leaf], shards: Tuple[int, ...],
        pad: bool = False,
    ) -> jax.Array:
        """One resident (U, S, W) device tensor for a leaf list, rebuilt only
        when a member fragment's generation changes. Where a device holds
        fewer than 8 of the S shards it is STORED as (U, S*k, W//k), k =
        stack_fold(S, devices): the same words in the same order, each
        shard's over k sublane rows, because the chip lays a short shard
        axis out one sublane in eight (parallel/mesh.py stack_fold). The
        programs that read it take k from its shape (ops/bitplane.py
        fold_of) and reduce accordingly; from 8 shards a device up k is 1
        and nothing differs.

        Serving latency for batched queries is dominated by per-call host
        work, not device FLOPs: passing one argument per leaf (dozens of
        arrays) and restacking them inside the program costs far more than
        the popcounts. Keeping the stack resident shrinks every query
        dispatch to (stacked tensor, small index vectors). `pad` pads the
        leading axis with copies of leaf 0 to padded_rows(n) so nearby
        leaf-set sizes reuse one compiled program."""
        n = len(leaves)
        np2 = padded_rows(n) if pad else n
        # Get-or-build as one span; its `kind` says which of the three it
        # was: `hit` (resident and fresh), `delta` (a stale stack refreshed
        # by one scatter) or `restack` (members gathered and copied anew).
        with obs_span("engine.stack", planes=np2) as sp:
            stacked, kind = self._stack_get_or_build(
                index, leaves, shards, n, np2)
            if sp is not NOP_SPAN:
                sp.tag(kind=kind, fold=bp.fold_of(stacked))
        return stacked

    def _stack_get_or_build(self, index: str, leaves: List[Leaf],
                            shards: Tuple[int, ...], n: int, np2: int):
        """(the (np2, S, W) stack, folded where stack_fold says so; how it
        was come by). A stale stack asks the journals of its views what
        was written since its fingerprint, as a stale leaf does
        (_gather_leaf)."""
        key = (index, tuple(leaves), shards, np2)
        # The views in the fingerprint's order, and the journal of each.
        views = dict.fromkeys((leaf.field, leaf.view) for leaf in leaves)
        journals = tuple(self._journal(index, f, v) for f, v in views)

        def probe():
            with self._lock:
                fp = self._stamps(journals)  # under the lock: _gather_leaf
                cached = self._stack_cache.get(key)
                if cached is None:
                    return None
                fresh = cached[0] == fp
                if fresh:
                    self._stack_cache[key] = self._stack_cache.pop(key)  # LRU touch
                    self.counters["stack_hits"] += 1
            if not fresh:
                cells = self._changed(
                    journals, cached[0], fp, cached[2].values())
                if cells is None or any(cells):
                    return None
                self._republish(self._stack_cache, key, cached, fp,
                                "stack_republished")
            return cached[1]

        stacked = self._gate(("stack", key), probe)
        if stacked is not None:
            return stacked, "hit"
        try:
            # Stale resident stack: one scattered update beats regathering
            # every member and restacking the whole (U, S, W) tensor.
            with self._lock:
                stale = self._stack_cache.get(key)
                # Read before the members' planes, which are so at least
                # as new as the stamp the stack will carry.
                fp = self._stamps(journals)
            if stale is not None:
                with obs_span("gather", kind="stack-delta") as sp:
                    stacked = self._stack_delta(key, stale, journals, fp)
                    if sp is not NOP_SPAN:
                        sp.tag(applied=stacked is not None)
                if stacked is not None:
                    return stacked, "delta"
            # Stale or missing: gather member planes (leaf-cache hits are
            # cheap; on a fresh stack hit above no gather happens at all).
            arrs = [self._gather_leaf(index, leaf, shards) for leaf in leaves]
            arrs = arrs + [arrs[0]] * (np2 - n)
            with self._lock:
                if self._stack_jit is None:
                    # The reshape rides inside the program, so a folded
                    # stack is born folded: a device's block stays its
                    # own shards' words and no second copy is resident.
                    def restack_planes(xs, fold):
                        return bp.fold_planes(jnp.stack(xs), fold)

                    # One program of 8,704 parameters took 820 s to
                    # compile for v5e, one of 512 3.4 s (PERF.md, PR 41):
                    # a stack of more than STACK_PIECE members is pieces
                    # of the program above, joined by one of a few.
                    def join_pieces(pieces):
                        return jnp.concatenate(pieces)

                    out = shard_sharding(self.mesh, 3, axis=1)
                    self._stack_jit = jax.jit(
                        restack_planes, static_argnums=1, out_shardings=out)
                    self._join_jit = jax.jit(join_pieces, out_shardings=out)
                stack_jit, join_jit = self._stack_jit, self._join_jit
            fold = stack_fold(len(shards), self.n_devices)

            def restack():
                if np2 <= STACK_PIECE:
                    return stack_jit(tuple(arrs), fold)
                return join_jit(tuple(
                    stack_jit(tuple(arrs[i:i + STACK_PIECE]), fold)
                    for i in range(0, np2, STACK_PIECE)))

            stacked = self._oom_guard(None, restack)
            # {view: {row: [(u,), ...]}}: where each row of each view lies
            # in the stack. Kept with the entry: only a stale stack asks,
            # and one of hundreds of rows is stale after every write to
            # its view.
            by_view: Dict[Tuple, Dict[int, List]] = {v: {} for v in views}
            for u, leaf in enumerate(leaves):
                by_view[(leaf.field, leaf.view)].setdefault(
                    leaf.row, []).append((u,))
            with self._lock:
                self.counters["stack_misses"] += 1
                self.counters["restack_bytes"] += int(stacked.nbytes)
                self._stack_bytes = self._byte_cache_put(
                    self._stack_cache, key, (fp, stacked, by_view),
                    self._stack_budget, self._stack_bytes, "stack_evictions",
                )
        finally:
            self._release(("stack", key))
        return stacked, "restack"

    # ----------------------------------------------------------- query memo

    def _epoch_token(self, index: str):
        """(incarnation, value) of the index's write epoch, or -1 when the
        index doesn't exist. A bare value would let a recreated index whose
        fresh epoch climbs back to a stored entry's number alias the OLD
        index's memoized count; the incarnation pair can't collide."""
        idx = self.holder.index(index)
        if idx is None:
            return -1
        ep = idx.write_epoch
        return (ep.incarnation, ep.value)

    def memo_probe(self, index: str, comp: "_Compiler",
                   shards: Tuple[int, ...]):
        """(memoized count or None, store token) for an already-compiled
        call. A hit is host-only work (dict lookup + generation check).

        The token freezes the generation fingerprint AT PROBE TIME — i.e.
        before the query executes. memo_store(token) must use it, not a
        fresh fingerprint: a write landing during the device round trip
        bumps generations, and stamping the post-write generation onto the
        pre-write count would serve stale results forever. With the probe-
        time fingerprint the entry just misses on the next probe (the safe
        direction, matching the leaf cache's fp-before-read ordering)."""
        with obs_span("engine.memo_probe") as sp:
            hit, token = self._memo_probe(index, comp, shards)
            if sp is not NOP_SPAN:
                sp.tag(hit=hit is not None)
        return hit, token

    def _memo_probe(self, index: str, comp: "_Compiler",
                    shards: Tuple[int, ...]):
        key = (index, comp.plan.sig_tuple, tuple(comp.leaves), shards)
        # O(1) staleness fast path: when the index's write epoch hasn't
        # moved since the entry was stored, NOTHING in the index changed,
        # so the O(U x S) per-fragment fingerprint walk below is pure
        # overhead — on a quiet index a hot repeat query probes in one
        # attribute read + dict lookup. Epoch is read BEFORE the walk /
        # execution (probe-time discipline, see below), so a concurrent
        # write can only make the stored epoch conservatively old.
        epoch = self._epoch_token(index)
        with self._lock:
            ent = self._memo.get(key)
            if ent is not None and epoch != -1 and ent[1] == epoch:
                self._memo[key] = self._memo.pop(key)  # LRU touch
                self.counters["memo_hits"] += 1
                return ent[2], (key, ent[0], epoch)
        fp = self._fingerprint(index, comp.leaves)
        token = (key, fp, epoch)
        with self._lock:
            ent = self._memo.get(key)
            if ent is not None and ent[0] == fp:
                # Epoch moved (a write elsewhere in the index) but these
                # leaves didn't: refresh the stored epoch so the next
                # probe is O(1) again.
                self._memo.pop(key)
                self._memo[key] = (fp, epoch, ent[2])
                self.counters["memo_hits"] += 1
                return ent[2], token
            self.counters["memo_misses"] += 1
        return None, token

    def memo_store(self, token, count: int) -> None:
        key, fp, epoch = token
        with self._lock:
            self._memo.pop(key, None)
            self._memo[key] = (fp, epoch, count)
            while len(self._memo) > self._memo_budget:
                self._memo.pop(next(iter(self._memo)))
                self.counters["memo_evictions"] += 1

    def _aux_probe(self, key, fp):
        """Generation-checked memo for composite results (TopN count
        matrices, BSI val-count outputs). Same probe-time-fingerprint
        discipline as memo_probe; values are small host arrays."""
        with self._lock:
            ent = self._aux_memo.get(key)
            if ent is not None and ent[0] == fp:
                self._aux_memo[key] = self._aux_memo.pop(key)  # LRU touch
                self.counters["memo_hits"] += 1
                return ent[1]
            self.counters["memo_misses"] += 1
        return None

    def _aux_store(self, key, fp, value) -> None:
        with self._lock:
            self._aux_memo.pop(key, None)
            self._aux_memo[key] = (fp, value)
            while len(self._aux_memo) > self._aux_budget:
                self._aux_memo.pop(next(iter(self._aux_memo)))
                self.counters["aux_evictions"] += 1

    # ------------------------------------------------------ host execution
    #
    # The bottom rung of the degraded ladder (docs/fault-tolerance.md) and
    # ROADMAP's compressed-domain cold path, one implementation: evaluate
    # a set-op call tree entirely on the host — planes come from the
    # host-tier compressed roaring bytes (decode_plane_words + journal
    # fold, via TierManager.promote) when the plane is demoted, or a live
    # container walk otherwise, and popcounts are one vectorized numpy
    # pass. Bit-exact vs the device path by construction: the promotion
    # logic is the same one the device gather consumes, and a popcount is
    # a popcount. No device work whatsoever, so a dead/demoted device
    # plane can still answer Count/TopN correctly.

    def host_supports(self, call: Call) -> bool:
        """True when `call` is answerable by the host evaluator: Row /
        Intersect / Union / Difference / Xor trees and time-quantum
        Ranges. BSI Ranges refuse (the bit-sliced kernels are device
        code); the executor's ladder uses the per-shard walk for those."""
        if call.name == "Row":
            return True
        if call.name in ("Intersect", "Union", "Difference", "Xor"):
            return bool(call.children) and all(
                self.host_supports(ch) for ch in call.children)
        if call.name == "Range" and not call.has_condition_arg():
            return True
        return False

    def _host_plane(self, index: str, leaf: Leaf, shards: Tuple[int, ...],
                    cache: Optional[Dict] = None) -> np.ndarray:
        """(len(shards), W) uint32 plane for one leaf, host memory only:
        tier promotion (compressed decode + journal fold) when demoted,
        live container walk otherwise. `cache` dedupes leaves within one
        query tree."""
        key = (index, leaf, shards)
        if cache is not None and key in cache:
            return cache[key]
        frags, fp = self._leaf_fragments(index, leaf, shards)
        buf = None
        if self.tier is not None:
            buf = self.tier.promote(key, frags, fp, len(shards))
        if buf is None:
            buf = self._host_gather(frags, leaf.row, len(shards))
        if cache is not None:
            cache[key] = buf
        return buf

    def _host_eval(self, index: str, call: Call, shards: Tuple[int, ...],
                   cache: Dict) -> np.ndarray:
        """Evaluate a host-supported call tree to its (S, W) plane."""
        if call.name == "Row":
            field_name = call.field_arg()
            row_id, ok = call.uint_arg(field_name)
            if not ok:
                raise QueryError("Row() must specify row")
            return self._host_plane(
                index, Leaf(field_name, VIEW_STANDARD, row_id), shards, cache)
        if call.name in ("Intersect", "Union", "Difference", "Xor"):
            if not call.children:
                raise QueryError(
                    f"empty {call.name} query is currently not supported")
            out = self._host_eval(index, call.children[0], shards, cache)
            op = {
                "Intersect": np.bitwise_and,
                "Union": np.bitwise_or,
                "Xor": np.bitwise_xor,
            }.get(call.name)
            for ch in call.children[1:]:
                rhs = self._host_eval(index, ch, shards, cache)
                if op is None:  # Difference
                    out = np.bitwise_and(out, np.bitwise_not(rhs))
                else:
                    out = op(out, rhs)
            return out
        if call.name == "Range" and not call.has_condition_arg():
            return self._host_time_range(index, call, shards, cache)
        raise QueryError(f"not host-executable: {call.name}")

    def _host_time_range(self, index: str, c: Call, shards: Tuple[int, ...],
                         cache: Dict) -> np.ndarray:
        """Time-quantum Range as a host union over present time views —
        the SHARED _resolve_time_range pruning, so the host answer
        matches the compiled program bit for bit. (This path is reached
        only after the compiled twin accepted the call, so the empty /
        too-many-views refusals don't re-apply here: zeros for empty is
        exactly the fallback's semantics.)"""
        field_name, row_id, views = _resolve_time_range(
            self.holder, index, c)
        out = None
        for v in views:
            p = self._host_plane(
                index, Leaf(field_name, v, row_id), shards, cache)
            out = p if out is None else np.bitwise_or(out, p)
        if out is None:
            out = np.zeros((len(shards), WORDS_PER_ROW), dtype=np.uint32)
        return out

    def host_count(self, index: str, call: Call, shards: Sequence[int],
                   comp_expr=None) -> int:
        """Count(call) answered entirely from host memory — the degraded
        ladder's bottom rung. Shares the generation-checked result memo
        with the device path (the answer is bit-exact, so a host-computed
        entry is as good as a device-computed one)."""
        shards = tuple(shards)
        comp = None
        if comp_expr is not None and comp_expr is not True:
            comp = comp_expr[0]
        if comp is None:
            comp, _ = self._compile(index, call)
        hit, token = self.memo_probe(index, comp, shards)
        if hit is not None:
            return hit
        plane = self._host_eval(index, call, shards, {})
        result = int(_pop_elems(plane).sum())
        with self._lock:
            self.counters["host_counts"] += 1
        self.memo_store(token, result)
        return result

    def host_topn_shard_counts(
        self, index: str, field: str, row_ids: Sequence[int],
        shards: Sequence[int], src_call: Optional[Call] = None,
        need_row_counts: bool = True,
    ):
        """topn_shard_counts with the same result contract, computed from
        host planes with numpy popcounts — the TopN rung of the ladder.
        Unmemoized: this is the degraded path, correctness over speed."""
        shards = tuple(shards)
        req = np.asarray(row_ids, dtype=np.int64)
        canon = np.unique(req)
        sel = np.searchsorted(canon, req)
        cache: Dict = {}
        if len(canon):
            planes = np.stack([
                self._host_plane(
                    index, Leaf(field, VIEW_STANDARD, int(r)), shards, cache)
                for r in canon
            ])  # (R, S, W)
        else:
            planes = np.zeros((0, len(shards), WORDS_PER_ROW), np.uint32)
        row_counts = None
        if need_row_counts:
            row_counts = _pop_elems(planes).sum(axis=2, dtype=np.int64)
        inter = src_counts = None
        if src_call is not None:
            src = self._host_eval(index, src_call, shards, cache)  # (S, W)
            src_counts = _pop_elems(src).sum(axis=1, dtype=np.int64)
            masked = np.bitwise_and(planes, src[None, :, :])
            inter = _pop_elems(masked).sum(axis=2, dtype=np.int64)
        with self._lock:
            self.counters["host_topn"] += 1
        return (
            row_counts[sel] if row_counts is not None else None,
            inter[sel] if inter is not None else None,
            src_counts,
        )

    def _cold_host_candidate(self, index: str, call: Call, comp: "_Compiler",
                             shards: Tuple[int, ...]) -> bool:
        """True when this Count should be answered compressed-domain: the
        tree is host-expressible, every leaf is demoted (none resident in
        HBM, all present in the tier), and this exact leaf set has not
        been host-answered before — the second touch promotes normally so
        hot planes climb back into HBM instead of re-decoding forever."""
        if not self._cold_host or self.tier is None or not comp.leaves:
            return False
        if not self.host_supports(call):
            return False
        keys = [(index, leaf, shards) for leaf in comp.leaves]
        kset = (index, tuple(comp.leaves), shards)
        with self._lock:
            if kset in self._cold_seen:
                return False
            if any(k in self._leaf_cache for k in keys):
                return False
        if not all(self.tier.has(k) for k in keys):
            return False
        with self._lock:
            if len(self._cold_seen) >= 4096:
                self._cold_seen.clear()
            self._cold_seen.add(kset)
        return True

    # -------------------------------------------------------------- queries

    def _compile(self, index: str, call: Call, field_cache: Optional[Dict] = None):
        comp = _Compiler(self.holder, index, field_cache=field_cache,
                         plan_cache=self._plan_cache_enabled)
        expr = comp.compile(call)
        return comp, expr

    def count(self, index: str, call: Call, shards: Sequence[int],
              comp_expr=None) -> int:
        """Count(<bitmap call>) over all shards in one device program."""
        shards = tuple(shards)
        comp, expr = comp_expr if comp_expr is not None else self._compile(index, call)
        hit, token = self.memo_probe(index, comp, shards)
        if hit is not None:
            return hit
        if self._cold_host_candidate(index, call, comp, shards):
            # Compressed-domain cold path: every leaf is demoted and this
            # leaf set is a first touch — one numpy popcount over the
            # host-tier bytes beats decode + device_put for a plane
            # nobody re-reads. A repeat promotes normally.
            plane = self._host_eval(index, call, shards, {})
            result = int(_pop_elems(plane).sum())
            with self._lock:
                self.counters["host_cold_counts"] += 1
            self.memo_store(token, result)
            return result
        hsig = comp.plan.sig_tuple
        sig = ("count", hsig, len(shards))

        def build():
            @jax.jit
            def count_expr(leaves):
                plane = expr(leaves)
                # XLA turns the full-tensor sum over the sharded axis into
                # per-device partial popcounts + an ICI all-reduce.
                return jnp.sum(jax.lax.population_count(plane).astype(jnp.int32))

            return count_expr

        fn = self._fn_build(self._count_fns, sig, build, health_sig=hsig)
        leaves = self._leaf_tensor(index, comp.leaves, shards)
        self._note_launch(leaves, "count_dispatches")
        with obs_span("engine.device_wait"):
            result = int(self._device_call(hsig, lambda: int(fn(leaves))))
        self.memo_store(token, result)
        return result

    def count_async(self, index: str, call: Call, shards: Sequence[int],
                    comp_expr=None):
        """Like count() but returns the unmaterialized device scalar, so
        callers can pipeline many queries before blocking (dispatch latency
        through the host<->device link dominates single-query serving).
        `comp_expr` lets callers that already compiled the call skip the
        second AST walk."""
        shards = tuple(shards)
        comp, expr = comp_expr if comp_expr is not None else self._compile(index, call)
        hsig = comp.plan.sig_tuple
        sig = ("count", hsig, len(shards))

        def build():
            @jax.jit
            def count_expr(leaves):
                plane = expr(leaves)
                return jnp.sum(jax.lax.population_count(plane).astype(jnp.int32))

            return count_expr

        fn = self._fn_build(self._count_fns, sig, build, health_sig=hsig)
        leaves = self._leaf_tensor(index, comp.leaves, shards)
        self._note_launch(leaves, "count_dispatches")
        return self._device_call(hsig, lambda: fn(leaves))

    def count_batch(self, index: str, calls: Sequence[Call], shards: Sequence[int],
                    comps=None) -> np.ndarray:
        """Count Q structurally-identical queries in ONE device program.

        Every bitplane op is elementwise, so the compiled expression applies
        unchanged to each query's leaf set; XLA fuses the whole batch and the
        host pays one dispatch + one transfer for Q results. This is the
        throughput-serving path (amortizes host<->device latency that caps
        per-call serving at ~1/RTT). Queries answered by the result memo
        skip the device entirely; only misses ride the batched program.
        `comps` skips recompiling already-compiled calls (aligned 1:1 with
        `calls` — the micro-batcher compiled each query at enqueue)."""
        shards = tuple(shards)
        if comps is None:
            fcache: Dict = {}
            comps = [self._compile(index, c, field_cache=fcache) for c in calls]
        out = np.empty(len(calls), dtype=np.int64)
        miss = []
        tokens = {}
        for i, (comp, _) in enumerate(comps):
            hit, tokens[i] = self.memo_probe(index, comp, shards)
            if hit is None:
                miss.append(i)
            else:
                out[i] = hit
        if miss:
            def run(sub):
                arr = self.count_batch_async(
                    index, [calls[i] for i in sub], shards,
                    comps=[comps[i] for i in sub],
                )
                # Materialize INSIDE the guard: with jax's async dispatch
                # a real device fault surfaces here, not at the enqueue
                # the dispatch guard already wrapped — unguarded, it
                # would escape as a raw XlaRuntimeError that bypasses
                # classification, the breakers, and the ladder entirely.
                # fire=False: the dispatch already paid the failpoint.
                with obs_span("engine.device_wait"):
                    return self._device_call(
                        tuple(comps[sub[0]][0].signature),
                        lambda: np.asarray(arr)[: len(sub)], fire=False)

            try:
                res = run(miss)
            except DeviceDispatchError as e:
                # Reduced-batch retry: the full-size dispatch already got
                # backpressure + one same-size retry inside _device_call;
                # a batch that STILL OOMs re-dispatches as two halves
                # (half the stacked working set each) before the error is
                # allowed to reach a client.
                if e.kind != OOM or len(miss) < 2:
                    raise
                with self._lock:
                    self.counters["oom_batch_splits"] += 1
                h = len(miss) // 2
                res = np.concatenate([run(miss[:h]), run(miss[h:])])
            for j, i in enumerate(miss):
                out[i] = int(res[j])
                self.memo_store(tokens[i], int(res[j]))
        return out

    def count_batch_async(self, index: str, calls: Sequence[Call],
                          shards: Sequence[int], comps=None) -> jax.Array:
        """count_batch without blocking on the result: returns the device
        array (length ≥ len(calls); first len(calls) entries valid). Lets a
        serving loop keep several batches in flight so device work and
        host<->device transfer overlap instead of serializing on each
        batch's round trip. `comps` skips recompiling already-compiled
        calls (must align 1:1 with `calls`)."""
        shards = tuple(shards)
        if comps is None:
            fcache: Dict = {}
            comps = [self._compile(index, c, field_cache=fcache) for c in calls]
        # List comparison (not per-call tuple()): this runs once per query
        # on the serving hot path.
        sig0_list = comps[0][0].signature
        for comp, _ in comps[1:]:
            if comp.signature != sig0_list:
                raise QueryError("count_batch requires structurally identical queries")
        sig0 = comps[0][0].plan.sig_tuple

        # Set-op trees (Row/Intersect/Union/Difference/Xor) are elementwise,
        # so the whole batch vectorizes: dedupe the batch's leaf rows into one
        # stacked (U, S, W) tensor and gather each query's leaves with a (Q,)
        # index per leaf position. One small take+logic+popcount program, one
        # dispatch, one (Q,) transfer — and because the row choice is an
        # *input* (not baked into the trace), every batch of the same shape
        # reuses the compiled program. The canonical plan carries the gate
        # (setops_only) precomputed.
        if comps[0][0].plan is not None and comps[0][0].plan.setops_only:
            return self._count_batch_setops(index, comps, shards, len(calls))

        sig = ("count_batch", sig0, len(shards), len(calls))

        def build():
            exprs = [e for _, e in comps]

            @jax.jit
            def count_batch_exprs(leavess):
                outs = []
                for lv, e in zip(leavess, exprs):
                    plane = e(lv)
                    outs.append(jnp.sum(jax.lax.population_count(plane).astype(jnp.int32)))
                return jnp.stack(outs)

            return count_batch_exprs

        fn = self._fn_build(self._count_fns, sig, build, health_sig=sig0)
        leavess = tuple(
            self._leaf_tensor(index, comp.leaves, shards) for comp, _ in comps
        )
        self._note_launch(leavess, "count_dispatches")
        return self._device_call(sig0, lambda: fn(leavess))

    @staticmethod
    def _batch_slot_gather(comps, q: int):
        """THE batch-assembly prologue shared by the fused batched count
        and bitmap programs: leaf-slot dict, per-leaf-position (Q,) slot
        vectors, within-batch dedup — structurally identical queries over
        the same leaf slots compute ONCE and fan back out via `inverse`
        (real serving mixes repeat hot queries heavily, zipf) — and
        power-of-two padding so varying batch sizes hit a handful of
        compiled programs. One implementation so the two batched paths
        cannot drift on dedup/pad semantics. Returns
        (slots, idxs, inverse, q_deduped, qp)."""
        slots: Dict[Leaf, int] = {}
        for comp, _ in comps:
            for leaf in comp.leaves:
                slots.setdefault(leaf, len(slots))
        n_pos = len(comps[0][0].leaves)
        idxs = tuple(
            np.array([slots[comp.leaves[j]] for comp, _ in comps],
                     dtype=np.int32)
            for j in range(n_pos)
        )
        inverse = None
        if q > 1:
            mat = np.stack(idxs)  # (L, Q)
            uniq, inv = np.unique(mat, axis=1, return_inverse=True)
            if uniq.shape[1] < q:
                idxs = tuple(np.ascontiguousarray(row) for row in uniq)
                inverse = inv.reshape(-1).astype(np.int32)
                q = uniq.shape[1]
        qp = 1 << (q - 1).bit_length()
        if qp != q:
            idxs = tuple(
                np.concatenate([ix, np.full(qp - q, ix[-1], np.int32)])
                for ix in idxs)
        return slots, idxs, inverse, q, qp

    def _count_batch_setops(self, index: str, comps, shards: Tuple[int, ...],
                            q: int) -> jax.Array:
        """Returns the unmaterialized (Qp,) device counts, Qp ≥ q."""
        slots, idxs, inverse, q, qp = self._batch_slot_gather(comps, q)
        stacked = self._stacked_leaf_tensor(index, list(slots), shards,
                                            pad=True)
        up = stacked.shape[0]

        # The memoized expansion rides inside the same program (a take on
        # the (Qp,) counts): a separate jnp.take would be a second dispatch
        # and a second blocking fetch per batch.
        invp = 0
        inv_in = None
        if inverse is not None:
            invp = 1 << (len(inverse) - 1).bit_length()
            inv_in = np.concatenate(
                [inverse, np.zeros(invp - len(inverse), np.int32)]
            )

        # sig0 is row-independent for set-op trees (Row entries carry leaf
        # positions, not row ids), so one compiled program serves any rows.
        sig = ("count_batch_setops", comps[0][0].plan.sig_tuple,
               len(shards), qp, up, invp)
        use_kernel = self._use_gather_kernel()

        def build():
            expr = comps[0][1]
            if use_kernel:
                from ..ops import pallas_kernels as pk

                interpret = self.platform != "tpu"

                if self.n_devices == 1:
                    def counts_of(stacked, idxs):
                        return pk.batched_gather_expr_count(
                            stacked, idxs, expr, interpret)
                else:
                    # Multi-device: the kernel runs per device on its local
                    # (U, S/d, W) shard-block under shard_map; per-query
                    # partial counts reduce with one psum over the shard
                    # axis (ICI), so no device gathers (Q, S, W) operands.
                    from jax.sharding import PartitionSpec as P

                    def local(stacked_blk, *ix):
                        c = pk.batched_gather_expr_count(
                            stacked_blk, ix, expr, interpret)
                        return jax.lax.psum(c, SHARD_AXIS)

                    # check_vma off: pallas_call inside shard_map cannot
                    # express output variance, and the psum makes the
                    # result replicated by construction.
                    smap = jax.shard_map(
                        local, mesh=self.mesh,
                        in_specs=(P(None, SHARD_AXIS, None),)
                        + (P(),) * len(idxs),
                        out_specs=P(), check_vma=False,
                    )

                    def counts_of(stacked, idxs):
                        return smap(stacked, *idxs)
            else:
                # XLA formulation: each leaf position is a gathered
                # (Q, S, W) operand; partitions over a multi-device mesh
                # by itself. Every leaf comes out of the stack and the
                # sum runs over shards and words, so a folded stack needs
                # nothing here (nor in the kernel, to which it is S*k
                # shards of W//k words).
                def counts_of(stacked, idxs):
                    leaves = tuple(stacked[ix] for ix in idxs)  # each (Q, S, W)
                    plane = expr(leaves)
                    return jnp.sum(
                        jax.lax.population_count(plane).astype(jnp.int32),
                        axis=(1, 2),
                    )

            if invp:
                def count_batch_setops(stacked, idxs, inv):
                    return jnp.take(counts_of(stacked, idxs), inv)
            else:
                def count_batch_setops(stacked, idxs):
                    return counts_of(stacked, idxs)
            return jax.jit(count_batch_setops)

        hsig = comps[0][0].plan.sig_tuple
        fn = self._fn_build(self._count_fns, sig, build, health_sig=hsig)
        self._note_launch(stacked, "count_dispatches", kernel=use_kernel,
                          stack=stacked)
        if inv_in is not None:
            return self._device_call(hsig, lambda: fn(stacked, idxs, inv_in))
        return self._device_call(hsig, lambda: fn(stacked, idxs))

    def _use_gather_kernel(self) -> bool:
        """Pallas gather kernel on TPU (any mesh size: multi-device runs
        the kernel per device under shard_map with a psum reduce), the XLA
        formulation elsewhere. PILOSA_PALLAS_BATCH forces it on (tests:
        interpret mode on the CPU backend) or off."""
        env = os.environ.get("PILOSA_PALLAS_BATCH")
        if env is not None:
            v = env.strip().lower()
            if v in ("1", "true", "yes", "on"):
                return True
            if v in ("", "0", "false", "no", "off"):
                return False
            # Unrecognized value: fall through to the platform default.
        return self.platform == "tpu"

    def bitmap(self, index: str, call: Call, shards: Sequence[int],
               comp_expr=None) -> Row:
        """Evaluate a bitmap call over all shards; returns a Row whose
        segments stay on device (one (W,) plane per shard)."""
        shards = tuple(shards)
        comp, expr = comp_expr if comp_expr is not None else self._compile(index, call)
        hsig = comp.plan.sig_tuple
        sig = ("bitmap", hsig, len(shards))
        def bitmap_expr(leaves):
            return expr(leaves)

        fn = self._fn_build(self._bitmap_fns, sig,
                            lambda: jax.jit(bitmap_expr), health_sig=hsig)
        leaves = self._leaf_tensor(index, comp.leaves, shards)
        self._note_launch(leaves, "bitmap_dispatches")
        # block_until_ready inside the guard: the Row keeps its segments
        # on device (no host transfer), but forcing completion here makes
        # an async device fault surface where it is classified and
        # recorded instead of deep inside a later Row operation.
        with obs_span("engine.device_wait"):
            planes = self._device_call(
                hsig, lambda: fn(leaves).block_until_ready())  # (S_padded, W)
        return Row({shard: planes[i] for i, shard in enumerate(shards)})

    def bitmap_batch(self, index: str, calls: Sequence[Call],
                     shards: Sequence[int], comps=None) -> List[Row]:
        """Evaluate Q same-signature bitmap call trees in ONE device
        program — the micro-batcher's generalized launch for bitmap
        (Row/set-op tree) dispatches, mirroring count_batch. The batch
        vectorizes exactly like _count_batch_setops: dedupe the batch's
        leaf rows into one stacked (U, S, W) tensor and gather each
        query's leaves with a (Q,) slot vector per leaf position, so one
        take+logic program produces all Q result planes and every batch
        of the same canonical shape reuses the compiled program. Trees
        outside the slot-gather shapes (BSI, time ranges) serve per-call
        — identical to the unbatched path."""
        shards = tuple(shards)
        if comps is None:
            fcache: Dict = {}
            comps = [self._compile(index, c, field_cache=fcache) for c in calls]
        plan0 = comps[0][0].plan
        if len(calls) == 1 or plan0 is None or not plan0.setops_only:
            return [self.bitmap(index, c, shards, comp_expr=ce)
                    for c, ce in zip(calls, comps)]
        sig0_list = comps[0][0].signature
        for comp, _ in comps[1:]:
            if comp.signature != sig0_list:
                raise QueryError(
                    "bitmap_batch requires structurally identical queries")
        # Shared prologue with the count path: slot vectors, within-batch
        # dedup (identical queries compute ONE plane; their Rows share
        # the immutable device array), power-of-two padding.
        n_calls = len(calls)
        slots, idxs, inverse, _, qp = self._batch_slot_gather(comps, n_calls)
        stacked = self._stacked_leaf_tensor(index, list(slots), shards,
                                            pad=True)
        up = stacked.shape[0]
        hsig = comps[0][0].plan.sig_tuple
        sig = ("bitmap_batch", hsig, len(shards), qp, up)
        expr = comps[0][1]

        def build():
            @jax.jit
            def bitmap_batch_expr(stacked, idxs):
                leaves = tuple(stacked[ix] for ix in idxs)  # each (Qp, S, W)
                return bp.unfold_planes(expr(leaves), bp.fold_of(stacked))

            return bitmap_batch_expr

        fn = self._fn_build(self._bitmap_fns, sig, build, health_sig=hsig)
        self._note_launch(stacked, "bitmap_dispatches", stack=stacked)
        # block_until_ready inside the guard, like bitmap(): an async
        # device fault must classify here, not inside a later Row op.
        with obs_span("engine.device_wait"):
            planes = self._device_call(
                hsig,
                lambda: fn(stacked, idxs).block_until_ready())  # (Qp, Sp, W)
        return [
            Row({shard: planes[qi if inverse is None else int(inverse[qi]), i]
                 for i, shard in enumerate(shards)})
            for qi in range(n_calls)
        ]

    _TOPN_ROWS_MEMO_ENTRIES = 512

    def _topn_rows(self, field: str, row_ids: Sequence[int]):
        """(rows, leaves, sel) of a TopN launch over `row_ids`: the row
        ids in canonical (sorted, deduped) order as a tuple, a Leaf of
        `field`'s standard view for each, and where each requested id
        lies among them. The stacked tensor and the result memos are
        keyed on the canonical order, so TopN phase-1 (first-seen
        candidate order) and the phase-2 refetch (sorted ids) share one
        device tensor and one memo entry instead of duplicating both."""
        req = np.asarray(row_ids, dtype=np.int64)
        mkey = (field, req.tobytes())
        hit = self._topn_rows_memo.get(mkey)
        if hit is None:
            canon = np.unique(req)
            rows = tuple(canon.tolist())
            hit = (rows,
                   tuple(Leaf(field, VIEW_STANDARD, r) for r in rows),
                   np.searchsorted(canon, req))  # canonical -> requested
            if len(self._topn_rows_memo) >= self._TOPN_ROWS_MEMO_ENTRIES:
                self._topn_rows_memo.clear()
            self._topn_rows_memo[mkey] = hit
        return hit

    def topn_shard_counts(
        self, index: str, field: str, row_ids: Sequence[int],
        shards: Sequence[int], src_call: Optional[Call] = None,
        need_row_counts: bool = True,
    ):
        """Per-(row, shard) count matrices in one device program.

        Returns (row_counts, inter_counts, src_counts): the first two are
        (R, S) int arrays, src_counts is (S,) — popcount of the src bitmap
        per shard, which the tanimoto coefficient needs
        (fragment.go:1008-1027). inter_counts/src_counts are None without a
        src call. Per-shard granularity preserves the reference's per-shard
        MinThreshold semantics (fragment.go:899-990) while batching all
        popcounts.

        `need_row_counts=False` skips the candidate-plane popcount pass and
        returns None row_counts: the executor's TopN phase-1 ranks with
        cache counts and phase-2 at threshold<=1 needs only intersections,
        so the common TopN query never pays for the (R, S, W) popcount —
        only the fused AND+popcount program over the resident stack.

        The resident stack is (Rp, S, W), stored as (Rp, S*k, W//k) where
        a device holds fewer than 8 shards (_stacked_leaf_tensor): both
        programs then sum a shard's k folded rows, and what they return
        is (R, S) either way.
        """
        shards = tuple(shards)
        canon_rows, leaves, sel = self._topn_rows(field, row_ids)
        s_real = len(shards)
        src_sig = None
        comp = expr = None
        if src_call is not None:
            comp, expr = self._compile(index, src_call)
            src_sig = tuple(comp.signature)
        mkey = ("topn_shard", index, field, canon_rows, shards,
                src_sig, tuple(comp.leaves) if comp else None,
                need_row_counts)
        # Every leaf is of the one view: the first says what all would.
        fp = self._fingerprint(index, leaves[:1])
        if comp is not None:
            fp = fp + self._fingerprint(index, comp.leaves)

        def answer(value):
            row_counts, inter, src_counts = value
            return (
                row_counts[sel] if row_counts is not None else None,
                inter[sel] if inter is not None else None,
                src_counts,
            )

        hit = self._aux_probe(mkey, fp)
        if hit is not None:
            return answer(hit)

        # The candidate-plane popcounts (row_counts) are INDEPENDENT of the
        # src call, so they memoize under their own key: a TopN stream with
        # a varying filter (each query a new src row — the ChEMBL serving
        # shape) pays for the (R, S, W) popcount pass at most once, and
        # every subsequent query runs only the fused AND+popcount program
        # below. Without this split each new src re-read the full candidate
        # stack twice (r04: topn_qps 2.69 vs sum_qps 199 at the same shape).
        # pad: phase-2 candidate counts vary per query (each query's
        # winner set differs), so the row axis pads (padded_rows) to keep
        # the compiled-program population at a handful of sizes.
        rows_tensor = self._stacked_leaf_tensor(index, leaves, shards,
                                                pad=True)  # (Rp, S, W)
        r_real = len(canon_rows)
        row_counts = None
        if need_row_counts:
            # Probe-time fingerprint discipline (see memo_probe): fp was
            # computed BEFORE the gather above; its first len(leaves)
            # entries are exactly the candidate-row fingerprints.
            rows_fp = fp[: len(leaves)]
            rkey = ("topn_rows", index, field, canon_rows, shards)
            row_counts = self._aux_probe(rkey, rows_fp)
            if row_counts is None:
                sig = ("topn_shard", len(shards), rows_tensor.shape[0])

                def build():
                    @jax.jit
                    def topn_shard_row_counts(stacked):
                        return bp.shard_sums(jnp.sum(
                            jax.lax.population_count(stacked).astype(jnp.int32), axis=2
                        ), bp.fold_of(stacked))

                    return topn_shard_row_counts

                fn = self._fn_build(self._count_fns, sig, build)
                self._note_launch(rows_tensor, stack=rows_tensor)
                with obs_span("engine.device_wait"):
                    row_counts = self._device_call(
                        None,
                        lambda: np.asarray(fn(rows_tensor))[:r_real, :s_real])
                self._aux_store(rkey, rows_fp, row_counts)

        if src_call is not None:
            src_leaves = self._leaf_tensor(index, comp.leaves, shards)
            sig = ("topn_shard_src", src_sig, len(shards), rows_tensor.shape[0])

            def build():
                @jax.jit
                def topn_shard_src_counts(stacked, src_lv):
                    k = bp.fold_of(stacked)
                    src = expr(src_lv)
                    src_counts = jnp.sum(
                        jax.lax.population_count(src).astype(jnp.int32), axis=1
                    )
                    # AND+popcount+reduce fuses into one pass over the
                    # stack — the masked plane is never materialized. The
                    # (S, W) filter is brought to a folded stack's form
                    # (128 KiB a shard); the stack is read as it lies.
                    masked = jnp.bitwise_and(
                        stacked, bp.fold_planes(src, k)[None, :, :])
                    inter = bp.shard_sums(jnp.sum(
                        jax.lax.population_count(masked).astype(jnp.int32), axis=2
                    ), k)
                    return jnp.concatenate([inter, src_counts[None, :]])

                return topn_shard_src_counts

            fn = self._fn_build(self._count_fns, sig, build)

            def run():
                # One fetch: the src counts ride as the last row.
                packed = np.asarray(fn(rows_tensor, src_leaves))
                return packed[:r_real, :s_real], packed[-1, :s_real]

            self._note_launch((rows_tensor, src_leaves), stack=rows_tensor)
            with obs_span("engine.device_wait"):
                inter, src_counts = self._device_call(None, run)
            value = (row_counts, inter, src_counts)
        else:
            value = (row_counts, None, None)
        self._aux_store(mkey, fp, value)
        return answer(value)

    def topn_counts(
        self, index: str, field: str, row_ids: Sequence[int],
        shards: Sequence[int], src_call: Optional[Call] = None,
    ) -> np.ndarray:
        """Total per-row counts across shards (optionally ∩ src bitmap) in
        one batched program — the distributed TopN inner loop. Canonical
        row ordering + the composite-result memo, as topn_shard_counts."""
        shards = tuple(shards)
        row_ids, leaves, sel = self._topn_rows(field, row_ids)
        src_sig = None
        comp0 = expr0 = None
        if src_call is not None:
            comp0, expr0 = self._compile(index, src_call)
            src_sig = tuple(comp0.signature)
        mkey = ("topn_total", index, field, row_ids, shards, src_sig,
                tuple(comp0.leaves) if comp0 else None)
        fp = self._fingerprint(index, leaves[:1])
        if comp0 is not None:
            fp = fp + self._fingerprint(index, comp0.leaves)
        hit = self._aux_probe(mkey, fp)
        if hit is not None:
            return hit[sel]
        # pad: candidate-id counts vary per query; see topn_shard_counts.
        rows_tensor = self._stacked_leaf_tensor(index, leaves, shards,
                                                pad=True)  # (Rp, S, W)
        r_real = len(row_ids)
        if src_call is not None:
            comp, expr = comp0, expr0  # compiled once above for the memo key
            src_leaves = self._leaf_tensor(index, comp.leaves, shards)
            sig = ("topn_src", tuple(comp.signature), len(shards),
                   rows_tensor.shape[0])

            def build():
                @jax.jit
                def topn_src_counts(stacked, src_lv):
                    src = bp.fold_planes(
                        expr(src_lv), bp.fold_of(stacked))  # (S, W)
                    masked = jnp.bitwise_and(stacked, src[None, :, :])
                    return jnp.sum(
                        jax.lax.population_count(masked).astype(jnp.int32), axis=(1, 2)
                    )

                return topn_src_counts

            fn = self._fn_build(self._count_fns, sig, build)
            self._note_launch((rows_tensor, src_leaves), stack=rows_tensor)
            with obs_span("engine.device_wait"):
                value = self._device_call(
                    None,
                    lambda: np.asarray(fn(rows_tensor, src_leaves))[:r_real])
            self._aux_store(mkey, fp, value)
            return value[sel]

        sig = ("topn", len(shards), rows_tensor.shape[0])

        def build():
            @jax.jit
            def topn_counts(stacked):
                return jnp.sum(
                    jax.lax.population_count(stacked).astype(jnp.int32), axis=(1, 2)
                )

            return topn_counts

        fn = self._fn_build(self._count_fns, sig, build)
        self._note_launch(rows_tensor, stack=rows_tensor)
        with obs_span("engine.device_wait"):
            value = self._device_call(
                None, lambda: np.asarray(fn(rows_tensor))[:r_real])
        self._aux_store(mkey, fp, value)
        return value[sel]

    def bsi_val_count(
        self, index: str, field: str, kind: str, bit_depth: int,
        shards: Sequence[int], filter_call: Optional[Call] = None,
    ):
        """Batched BSI Sum/Min/Max across all shards in one device program.

        kind='sum' returns (depth+1,) per-plane global counts (host composes
        the weighted sum in Python ints). kind='min'/'max' returns
        (bits (depth,), count) — the bit-sliced scan of fragment.go:603-657
        run over the full sharded plane set, so cross-shard min/max needs no
        per-shard ValCount merge.
        """
        shards = tuple(shards)
        view = VIEW_BSI_GROUP_PREFIX + field
        leaves = [Leaf(field, view, i) for i in range(bit_depth + 1)]
        fsig = ()
        comp = expr = None
        if filter_call is not None:
            comp, expr = self._compile(index, filter_call)
            fsig = tuple(comp.signature)
        # Result memo: a repeat Sum/Min/Max over unchanged fragments is
        # host-only work (the val-count outputs are tiny).
        mkey = ("bsi", index, field, kind, bit_depth, shards, fsig,
                tuple(comp.leaves) if comp else None)
        fp = self._fingerprint(index, leaves)
        if comp is not None:
            fp = fp + self._fingerprint(index, comp.leaves)
        hit = self._aux_probe(mkey, fp)
        if hit is not None:
            return hit

        planes = self._stacked_leaf_tensor(index, leaves, shards)  # (D+1, S, W)
        filter_leaves = None
        if filter_call is not None:
            filter_leaves = self._leaf_tensor(index, comp.leaves, shards)
        sig = ("bsi", kind, bit_depth, len(shards), fsig)

        def build():
            def total(x):
                return jnp.sum(jax.lax.population_count(x).astype(jnp.int32))

            if kind == "sum":
                @jax.jit
                def bsi_val_count(planes, flt):
                    stacked = planes  # (D+1, S, W)
                    if expr is not None:
                        stacked = jnp.bitwise_and(stacked, bp.fold_planes(
                            expr(flt), bp.fold_of(planes))[None])
                    return jnp.sum(
                        jax.lax.population_count(stacked).astype(jnp.int32),
                        axis=(1, 2),
                    )
            else:
                maximize = kind == "max"

                @jax.jit
                def bsi_val_count(planes, flt):
                    consider = planes[bit_depth]
                    if expr is not None:
                        consider = jnp.bitwise_and(consider, bp.fold_planes(
                            expr(flt), bp.fold_of(planes)))
                    bits = []
                    for i in range(bit_depth - 1, -1, -1):
                        if maximize:
                            x = jnp.bitwise_and(planes[i], consider)
                        else:
                            x = jnp.bitwise_and(consider, jnp.bitwise_not(planes[i]))
                        nonzero = total(x) > 0
                        bit = jnp.where(nonzero, 1, 0) if maximize else jnp.where(nonzero, 0, 1)
                        bits.append(bit.astype(jnp.int32))
                        consider = jnp.where(nonzero, x, consider)
                    bits = (
                        jnp.stack(bits[::-1]) if bits else jnp.zeros((0,), jnp.int32)
                    )
                    return bits, total(consider)

            return bsi_val_count

        fn = self._fn_build(self._count_fns, sig, build)

        def run():
            # Materialization inside the guard (async-dispatch faults
            # surface here, not at the enqueue).
            out = fn(planes, filter_leaves)
            if kind == "sum":
                return np.asarray(out)
            bits, count = out
            return (np.asarray(bits), int(count))

        self._note_launch(
            planes if filter_leaves is None else (planes, filter_leaves),
            stack=planes)
        with obs_span("engine.device_wait"):
            value = self._device_call(None, run)
        self._aux_store(mkey, fp, value)
        return value

    def supports(self, call: Call, index: Optional[str] = None):
        """Truthy if `call` compiles onto the fast path.

        With `index`, runs the REAL compiler (holder lookups, no device
        work) so the answer is exact — e.g. a time-quantum Range only
        compiles when the field actually has a quantum and the range
        covers views; the syntactic check alone would claim support and
        then diverge from the fallback's empty-Row semantics. The return
        value is then the compiled (comp, expr) pair, which callers pass
        to count()/bitmap() as comp_expr so the gate and the execution
        share ONE AST walk. Without `index` (callers that don't know it
        yet) the check is syntactic (returns True) and time Ranges are
        conservatively refused. Falsy (False) when not supported."""
        try:
            if index is None:
                self._compile_check(call)
                return True
            return self._compile(index, call)
        except Exception:
            # Any compile failure means "not fast-path" and the executor
            # falls back to the reference walk — correct either way, but a
            # climbing refusal count on a workload that should compile is
            # the signal a gate bug would otherwise bury.
            with self._lock:
                self.counters["compile_gate_refusals"] += 1
            return False

    def _compile_check(self, call: Call) -> None:
        if call.name == "Row":
            return
        if call.name in ("Intersect", "Union", "Difference", "Xor"):
            if not call.children:
                raise QueryError("empty")
            for ch in call.children:
                self._compile_check(ch)
            return
        if call.name == "Range" and call.has_condition_arg():
            return
        raise QueryError(f"not fast-path: {call.name}")
