"""Memory & KV-cache observability: block lifecycle accounting + an online
miss-ratio-curve estimator.

ROADMAP items 1 (tiered host/disk KV spill) and 2 (disaggregated prefill /
decode with cross-replica prefix sharing) are capacity-planning problems
before they are engineering problems: nobody can size a host block pool from
an aggregate hit counter. This module is the telemetry that makes those
items sizeable, in two halves:

  * :class:`CacheTelemetry` — per-block lifecycle tracking fed by narrow
    hooks in ``BlockedAllocator`` (allocate / physical free),
    ``PrefixKVCache`` (publish / hit / COW / evict) and ``DSStateManager``
    (occupancy provider). Pre-allocated numpy stamp arrays sized to the pool
    (bounded, no per-block dict entries), local histograms for block age,
    reuse interval and eviction-victim age ("how cold was what we threw
    away"), refcount-class accounting (active / tree-only / free), and
    allocator occupancy/fragmentation gauges. Events mirror onto the
    existing PR 1/5 buses: the metrics registry (when enabled) receives the
    same histogram observations under ``cache/*`` names, evictions leave a
    flight-recorder breadcrumb, and the health exporter renders
    :meth:`CacheTelemetry.gauge_rows` as labelled ``/metrics`` gauges.

  * :class:`MRCEstimator` — SHARDS-style sampled reuse-distance tracking
    (Waldspurger et al., FAST'15) over the radix ``acquire`` lookup stream
    at block-chunk granularity (one reference per full-block token chunk,
    so token-granularity up to the fixed block size), in bounded memory.
    Produces the predicted hit rate at {0.5x, 1x, 2x, 4x, 8x} the current
    block-pool capacity — the miss-ratio curve that answers "how much would
    the hit rate improve if the pool were 4x bigger" from a dashboard
    instead of a guess. Validated against an exact LRU stack-distance
    simulation in ``tests/test_cache_telemetry.py`` and against the live
    measured hit rate by ``tools/serving_load.py``'s ``cache_pressure_bench``.

Zero overhead when the ``ragged.prefix_cache.telemetry`` block is absent:
no CacheTelemetry object exists anywhere, every hook site is a single
``is not None`` check, and no per-block allocations happen (test-enforced,
the PR 5 contract).
"""

import bisect
import time
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ....monitor.flight import get_flight_recorder
from ....monitor.metrics import Histogram, get_metrics
from ....monitor.trace import get_tracer

# seconds-scale buckets for block-lifecycle histograms (ages span from
# sub-millisecond churn in tests to hours of cold residency in production)
AGE_BUCKETS_S = (0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0, 60.0, 300.0,
                 1800.0, 7200.0, 43200.0)


def chunk_key(prev: int, tokens) -> int:
    """Rolling 32-bit key of one block-aligned token chunk, chained on the
    previous chunk's key — the radix-tree PATH identity (two chunks with the
    same tokens under different prefixes get different keys), deterministic
    across processes (crc32, not PYTHONHASHSEED-dependent ``hash``)."""
    return zlib.crc32(np.ascontiguousarray(tokens, dtype=np.int64).tobytes(),
                      prev) & 0xFFFFFFFF


class MRCEstimator:
    """Online miss-ratio-curve estimation from sampled reuse distances.

    The reference stream is block-chunk keys (see :func:`chunk_key`): each
    ``record`` call is one radix lookup's full-block chunks, in order. A
    reference at LRU stack distance ``d`` (distinct keys touched since the
    key's previous access) hits in an LRU cache of ``C`` blocks iff
    ``d < C``; SHARDS samples keys at a fixed rate ``R`` by key hash and
    scales each sampled rank by ``1/R``, so memory is bounded by the sampled
    working set (further capped at ``max_tracked`` — beyond it the coldest
    tracked key is dropped and its next access counts as a cold miss).

    Validity regime (measured in tests/test_cache_telemetry.py): key
    sampling assumes the sampled population is large relative to the hot
    head of the popularity distribution. The chunk-granular stream helps —
    a hot PREFIX is a chain of many chunk keys, each sampled independently
    — but on smoke-scale pools (tens of blocks, hundreds of refs) the
    sampled-key mix dominates the error: use ``sample_rate=1.0`` there
    (still bounded by ``max_tracked``) and reserve sub-1 rates for
    production-scale pools, where 0.25 tracks the exact simulation to
    within a few thousandths.

    Two feed kinds, mirroring what actually consumes pool capacity:

      * ``record(keys, observed_hits)`` — DEMAND references (admission-side
        ``acquire`` lookups): they enter the predicted-hit-rate accounting
        AND update recency. ``observed_hits`` is how many of them the real
        cache served (full-block radix hits), accumulated for the live
        accuracy check ``observed_hit_rate`` vs ``predict()[1.0]``.
      * ``note_insert(keys)`` — capacity-consuming insertions that are not
        demand (publish-side: a request's uncached suffix and generated
        blocks entering the tree). They update recency and push everything
        else deeper in the stack, but are not counted as references — a
        published block nobody ever looks up again must COST capacity in
        the model without inflating the predicted hit rate.
    """

    def __init__(self, capacity_blocks: int, sample_rate: float = 0.25,
                 max_tracked: int = 4096,
                 capacity_mults: Tuple[float, ...] = (0.5, 1.0, 2.0, 4.0, 8.0)):
        if not 0.0 < sample_rate <= 1.0:
            raise ValueError(f"sample_rate must be in (0, 1], got {sample_rate}")
        if capacity_blocks < 1:
            raise ValueError(f"capacity_blocks must be >= 1, got {capacity_blocks}")
        self.capacity_blocks = int(capacity_blocks)
        self.sample_rate = float(sample_rate)
        self.max_tracked = max(16, int(max_tracked))
        self.capacity_mults = tuple(float(m) for m in capacity_mults)
        self._threshold = int(self.sample_rate * (1 << 32))
        self._stamp = 0
        self._last: Dict[int, int] = {}     # sampled key -> last-access stamp
        self._stamps: List[int] = []        # the same stamps, ascending
        self._keys: List[int] = []          # parallel to _stamps
        self._hits = [0] * len(self.capacity_mults)
        self._refs_sampled = 0              # sampled demand refs (cold misses incl.)
        self.refs_total = 0                 # all demand refs, sampled or not
        self.observed_hits = 0              # real-cache full-block hits, same stream

    # -- feeds -------------------------------------------------------------
    def record(self, keys, observed_hits: int = 0) -> None:
        """One lookup's ordered full-block chunk keys + how many of them the
        REAL cache served (its shared full-block hits)."""
        for k in keys:
            self._access(int(k), counted=True)
        self.refs_total += len(keys)
        self.observed_hits += int(observed_hits)

    def note_insert(self, keys) -> None:
        """Capacity-consuming, non-demand accesses (publish-side)."""
        for k in keys:
            self._access(int(k), counted=False)

    def _access(self, key: int, counted: bool) -> None:
        if key >= self._threshold:  # unsampled: invisible to the model
            return
        self._stamp += 1
        prev = self._last.get(key)
        if prev is not None:
            idx = bisect.bisect_left(self._stamps, prev)
            rank = len(self._stamps) - idx - 1  # distinct sampled keys since
            self._stamps.pop(idx)
            self._keys.pop(idx)
            if counted:
                self._refs_sampled += 1
                dist = rank / self.sample_rate
                for i, m in enumerate(self.capacity_mults):
                    if dist < m * self.capacity_blocks:
                        self._hits[i] += 1
        else:
            if counted:
                self._refs_sampled += 1  # cold miss: denominator only
            if len(self._last) >= self.max_tracked:
                # bounded memory: drop the coldest tracked key — its next
                # access reads as a cold miss (a small hit-rate UNDER-
                # estimate at the largest capacities, never an over-promise)
                self._last.pop(self._keys.pop(0), None)
                self._stamps.pop(0)
        # the new stamp is the global max: append keeps _stamps sorted
        self._last[key] = self._stamp
        self._stamps.append(self._stamp)
        self._keys.append(key)

    # -- read side ---------------------------------------------------------
    def predict(self) -> Dict[float, Optional[float]]:
        """Predicted hit rate per capacity multiplier (None before any
        sampled reference lands — no data is not 0% hit rate)."""
        if self._refs_sampled == 0:
            return {m: None for m in self.capacity_mults}
        return {m: self._hits[i] / self._refs_sampled
                for i, m in enumerate(self.capacity_mults)}

    @property
    def observed_hit_rate(self) -> Optional[float]:
        """The REAL cache's full-block hit rate over the same reference
        stream — what ``predict()[1.0]`` claims to estimate."""
        if not self.refs_total:
            return None
        return self.observed_hits / self.refs_total

    @property
    def tracked_keys(self) -> int:
        return len(self._last)

    def reset(self) -> None:
        self._stamp = 0
        self._last.clear()
        self._stamps.clear()
        self._keys.clear()
        self._hits = [0] * len(self.capacity_mults)
        self._refs_sampled = 0
        self.refs_total = 0
        self.observed_hits = 0


class CacheTelemetry:
    """Per-block lifecycle accounting + the MRC estimator, owned by
    :class:`~.ragged_manager.DSStateManager` when the
    ``ragged.prefix_cache.telemetry`` block is enabled.

    All hook entry points are O(blocks touched) with pre-allocated state;
    gauges (occupancy, fragmentation, refcount classes) are computed on
    demand (``gauge_rows`` / ``snapshot``), never per step.
    """

    def __init__(self, kv_cache, config=None, clock=time.perf_counter):
        self.kv = kv_cache
        self._clock = clock
        nb = kv_cache.num_blocks
        self.block_size = kv_cache.block_size
        # per-block stamps: last allocate, last tree touch (publish or hit)
        self._alloc_t = np.zeros(nb, np.float64)
        self._access_t = np.zeros(nb, np.float64)
        self._tree_held = np.zeros(nb, bool)
        # lifetime event counters (ints, monotonic). The demote/promote
        # trio stays zero (and costs nothing) without a host tier.
        self.counters = {"allocated": 0, "freed": 0, "published": 0,
                         "hit_blocks": 0, "evicted": 0,
                         "demote_queued": 0, "demoted": 0, "promoted": 0}
        # local histograms: self-contained and deterministic whether or not
        # the global metrics registry is armed (the registry gets mirrored
        # observations when it is — cumulative Prometheus buckets for free)
        self.block_age_s = Histogram("cache/block_age_s", buckets=AGE_BUCKETS_S)
        self.reuse_interval_s = Histogram("cache/reuse_interval_s", buckets=AGE_BUCKETS_S)
        self.evicted_block_age_s = Histogram("cache/evicted_block_age_s",
                                             buckets=AGE_BUCKETS_S)
        # tier migration latency distributions: promote is the admission-
        # side wait a request actually eats (headline p50/p99 in the
        # ``tools/serving_load.py``'s ``host_tier_ab``); demote is worker-side queue+copy time
        self.promote_latency_s = Histogram("cache/promote_latency_s",
                                           buckets=AGE_BUCKETS_S)
        self.demote_latency_s = Histogram("cache/demote_latency_s",
                                          buckets=AGE_BUCKETS_S)
        # host-tier occupancy-time integral ∫ host_used_blocks dt — the
        # host-pool ground truth the tenant meter's host_kv_s charges must
        # sum to (same conservation contract as the HBM integral below).
        # Advanced with ABSOLUTE used-counts the tier reports on every
        # transition (all under the tree lock, so no extra locking here).
        self._host_occ_blocks = 0
        self._host_occ_last_t = self._clock()
        self._host_occ_integral_s = 0.0
        # occupancy-time integral ∫ occupied_blocks dt (block-seconds),
        # advanced at every allocate/free event: the pool-side ground truth
        # the tenant meter's per-owner KV-block-second charges must sum to
        # (the PR 15 conservation acceptance check)
        self._occ_blocks = 0
        self._occ_last_t = self._clock()
        self._occ_integral_s = 0.0
        sample_rate = getattr(config, "mrc_sample_rate", 0.25) if config else 0.25
        max_tracked = getattr(config, "mrc_max_tracked", 4096) if config else 4096
        mults = getattr(config, "mrc_capacity_mults", None) if config else None
        self.mrc = MRCEstimator(nb, sample_rate=sample_rate, max_tracked=max_tracked,
                                capacity_mults=mults or (0.5, 1.0, 2.0, 4.0, 8.0))
        # (used_token_slots, seq_allocated_blocks) across live sequences —
        # set by the owning DSStateManager; None keeps fragmentation at 0
        self.occupancy_provider = None

    def _advance_occupancy(self, now, delta_blocks) -> None:
        self._occ_integral_s += self._occ_blocks * max(0.0, now - self._occ_last_t)
        self._occ_last_t = now
        self._occ_blocks = max(0, self._occ_blocks + delta_blocks)

    def occupancy_integral_s(self) -> float:
        """Block-seconds of pool occupancy since construction (the partial
        interval of currently-resident blocks included)."""
        now = self._clock()
        return self._occ_integral_s + self._occ_blocks * max(0.0, now - self._occ_last_t)

    # -- allocator hooks ---------------------------------------------------
    def on_allocate(self, blocks) -> None:
        now = self._clock()
        self._advance_occupancy(now, len(blocks))
        self._alloc_t[np.asarray(blocks, np.int64)] = now
        self.counters["allocated"] += len(blocks)

    def on_free(self, blocks) -> None:
        """Physical frees (refcount reached zero): block age = allocate ->
        free, the residency distribution of the whole pool."""
        now = self._clock()
        self._advance_occupancy(now, -len(blocks))
        reg = get_metrics()
        mirror = reg.histogram("cache/block_age_s", buckets=AGE_BUCKETS_S) \
            if reg.enabled else None
        for b in blocks:
            age = now - self._alloc_t[b]
            self.block_age_s.observe(age)
            if mirror is not None:
                mirror.observe(age)
            self._tree_held[b] = False
        self.counters["freed"] += len(blocks)

    # -- prefix-cache hooks (called under the tree lock) -------------------
    def on_publish(self, block: int) -> None:
        b = int(block)
        self._access_t[b] = self._clock()
        self._tree_held[b] = True
        self.counters["published"] += 1

    def on_hit(self, blocks) -> None:
        """A lookup took references on shared tree blocks: the interval
        since each block's previous tree touch is its reuse interval."""
        now = self._clock()
        reg = get_metrics()
        mirror = reg.histogram("cache/reuse_interval_s", buckets=AGE_BUCKETS_S) \
            if reg.enabled else None
        for b in blocks:
            prev = self._access_t[b]
            if prev > 0.0:
                self.reuse_interval_s.observe(now - prev)
                if mirror is not None:
                    mirror.observe(now - prev)
            self._access_t[b] = now
        self.counters["hit_blocks"] += len(blocks)

    def on_evict(self, block: int) -> None:
        """Eviction victim: age since last touch = how cold the LRU leaf we
        threw away actually was (a steadily WARM victim age means the pool
        is too small — the direct item-1 sizing signal)."""
        b = int(block)
        now = self._clock()
        age = now - (self._access_t[b] if self._access_t[b] > 0.0 else self._alloc_t[b])
        self.evicted_block_age_s.observe(age)
        self._tree_held[b] = False
        self.counters["evicted"] += 1
        reg = get_metrics()
        if reg.enabled:
            reg.histogram("cache/evicted_block_age_s", buckets=AGE_BUCKETS_S).observe(age)
        get_flight_recorder().record("cache", "evict", block=b, age_s=round(age, 4))
        tr = get_tracer()
        if tr.enabled:
            tr.instant("cache/evict", tid="serving", block=b, age_s=round(age, 4))

    def on_tree_clear(self, blocks) -> None:
        """Eviction flush (``PrefixKVCache.clear``): the tree reference is
        gone but this was not LRU pressure — no victim-age samples."""
        self._tree_held[np.asarray(list(blocks), np.int64)] = False

    # -- tier hooks (tiered_store.py; all under the tree lock) -------------
    def on_demote_queued(self, block: int) -> None:
        """Eviction handed a block to the migration queue instead of
        dropping it (the HBM block is released NOW; the D2H completes on
        the worker)."""
        self.counters["demote_queued"] += 1
        self._tree_held[int(block)] = False

    def on_demote(self, host_used_blocks: int, wait_s: float = 0.0) -> None:
        """The migration worker finalized one demotion into the host pool:
        ``wait_s`` is enqueue→resident (queue wait + D2H + host write)."""
        self.counters["demoted"] += 1
        self.demote_latency_s.observe(max(0.0, wait_s))
        self.note_host_used(host_used_blocks)
        reg = get_metrics()
        if reg.enabled:
            reg.histogram("cache/demote_latency_s",
                          buckets=AGE_BUCKETS_S).observe(max(0.0, wait_s))

    def on_promote(self, block: int, wait_s: float = 0.0,
                   from_disk: bool = False) -> None:
        """A demoted chain hit was restored to HBM on the admission path:
        ``wait_s`` is the synchronous H2D (+ disk read) the request ate."""
        self.counters["promoted"] += 1
        self._tree_held[int(block)] = True
        self._access_t[int(block)] = self._clock()
        self.promote_latency_s.observe(max(0.0, wait_s))
        reg = get_metrics()
        if reg.enabled:
            reg.histogram("cache/promote_latency_s",
                          buckets=AGE_BUCKETS_S).observe(max(0.0, wait_s))

    def note_host_used(self, used_blocks: int) -> None:
        """Advance the host occupancy-time integral to an ABSOLUTE used
        count (the tier reports after every host-pool transition)."""
        now = self._clock()
        self._host_occ_integral_s += self._host_occ_blocks * max(0.0, now - self._host_occ_last_t)
        self._host_occ_last_t = now
        self._host_occ_blocks = max(0, int(used_blocks))

    def host_occupancy_integral_s(self) -> float:
        """Host-block-seconds of tier occupancy since construction (current
        residents' partial interval included) — what the per-tenant
        ``host_kv_s`` charges must reconcile against."""
        now = self._clock()
        return self._host_occ_integral_s + self._host_occ_blocks * max(0.0, now - self._host_occ_last_t)

    # -- MRC feed (called under the tree lock) -----------------------------
    def record_lookup(self, keys, observed_hits: int) -> None:
        self.mrc.record(keys, observed_hits)

    def record_inserts(self, keys) -> None:
        self.mrc.note_insert(keys)

    # -- read side ---------------------------------------------------------
    def refcount_classes(self) -> Dict[str, int]:
        """Exact pool decomposition by holder class: ``free`` (refcount 0),
        ``tree_only`` (the radix tree is the sole holder — evictable cold
        capacity), ``active`` (some sequence holds it, shared or not)."""
        rc = self.kv.refcount_snapshot()
        free = int((rc == 0).sum())
        tree_only = int(((rc == 1) & self._tree_held).sum())
        return {"free": free, "tree_only": tree_only,
                "active": int(rc.size) - free - tree_only}

    def occupancy(self) -> float:
        total = self.kv.total_blocks
        return (total - self.kv.free_blocks) / total

    def fragmentation(self) -> float:
        """Internal fragmentation of live-sequence allocations: the fraction
        of their allocated token slots not (yet) holding KV — partial tails
        and decode-horizon headroom. Tree-held blocks are full by
        construction, so this is exactly the slack a block-size change or a
        tail-packing scheme could recover."""
        if self.occupancy_provider is None:
            return 0.0
        used, allocated = self.occupancy_provider()
        if allocated == 0:
            return 0.0
        return max(0.0, 1.0 - used / (allocated * self.block_size))

    def gauge_rows(self, labels: Optional[dict] = None):
        """Labelled gauge rows for the health exporter's ``/metrics``
        (``HealthPlane.set_gauge_provider`` shape). ``labels`` are merged
        into every row — a multi-replica gateway passes a per-engine label
        so replicas' series stay distinct instead of colliding."""
        base = dict(labels or {})

        def row(name, extra, v):
            return (name, {**base, **extra}, v)

        rows = []
        for m, v in self.mrc.predict().items():
            if v is not None:
                rows.append(row("serving/mrc_hit_rate", {"capacity_mult": f"{m:g}"}, v))
        ohr = self.mrc.observed_hit_rate
        if ohr is not None:
            rows.append(row("serving/mrc_observed_hit_rate", {}, ohr))
        for cls, n in self.refcount_classes().items():
            rows.append(row("cache/blocks", {"class": cls}, n))
        rows.append(row("cache/occupancy", {}, self.occupancy()))
        rows.append(row("cache/fragmentation", {}, self.fragmentation()))
        rows.append(row("cache/block_age_p50_s", {}, self.block_age_s.percentile(50)))
        rows.append(row("cache/reuse_interval_p50_s", {},
                        self.reuse_interval_s.percentile(50)))
        rows.append(row("cache/evicted_block_age_p50_s", {},
                        self.evicted_block_age_s.percentile(50)))
        if self.counters["demote_queued"] or self._host_occ_blocks:
            rows.append(row("cache/host_blocks_used", {}, self._host_occ_blocks))
            rows.append(row("cache/promote_latency_p50_s", {},
                            self.promote_latency_s.percentile(50)))
        return rows

    def snapshot(self) -> dict:
        """One JSON-able dict: the tool surface (``tools/serving_load.py``'s
        ``cache_pressure_bench`` returns it as ``telemetry``)."""
        return {
            "counters": dict(self.counters),
            "classes": self.refcount_classes(),
            "occupancy": round(self.occupancy(), 4),
            "occupancy_integral_s": round(self.occupancy_integral_s(), 6),
            "fragmentation": round(self.fragmentation(), 4),
            "block_age_s": self.block_age_s.summary(),
            "reuse_interval_s": self.reuse_interval_s.summary(),
            "evicted_block_age_s": self.evicted_block_age_s.summary(),
            "mrc": {f"{m:g}x": (round(v, 4) if v is not None else None)
                    for m, v in self.mrc.predict().items()},
            "mrc_observed_hit_rate": (round(self.mrc.observed_hit_rate, 4)
                                      if self.mrc.observed_hit_rate is not None else None),
            "mrc_refs": self.mrc.refs_total,
            "mrc_tracked_keys": self.mrc.tracked_keys,
            "tiers": {
                "demote_queued": self.counters["demote_queued"],
                "demoted": self.counters["demoted"],
                "promoted": self.counters["promoted"],
                "host_blocks_used": self._host_occ_blocks,
                "host_occupancy_integral_s": round(self.host_occupancy_integral_s(), 6),
                "promote_latency_s": self.promote_latency_s.summary(),
                "demote_latency_s": self.demote_latency_s.summary(),
            },
        }

    def reset(self) -> None:
        """Zero every accumulator (A/B harnesses reset between arms). Stamp
        arrays and tree-held flags are LIVE state, not accumulators — they
        track blocks still resident and survive the reset."""
        self.mrc.reset()
        for k in self.counters:
            self.counters[k] = 0
        self.block_age_s = Histogram("cache/block_age_s", buckets=AGE_BUCKETS_S)
        self.reuse_interval_s = Histogram("cache/reuse_interval_s", buckets=AGE_BUCKETS_S)
        self.evicted_block_age_s = Histogram("cache/evicted_block_age_s",
                                             buckets=AGE_BUCKETS_S)
        self.promote_latency_s = Histogram("cache/promote_latency_s",
                                           buckets=AGE_BUCKETS_S)
        self.demote_latency_s = Histogram("cache/demote_latency_s",
                                          buckets=AGE_BUCKETS_S)
        # the host occupancy INTEGRAL is an accumulator; the current used
        # count is live state and survives (same rule as the stamp arrays)
        self._host_occ_integral_s = 0.0
        self._host_occ_last_t = self._clock()
