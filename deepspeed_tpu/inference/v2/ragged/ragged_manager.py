"""Sequence state manager.

Analog of the reference ``inference/v2/ragged/ragged_manager.py:19``
(``DSStateManager``: tracked sequences → KV block tables, owns the
``BlockedKVCache``). With ``prefix_cache`` enabled it also owns the
:class:`PrefixKVCache` radix tree: sequence creation pre-populates the block
table and ``seen_tokens`` from the longest cached prefix, completed full
blocks are published back on the way out, and every block release routes
through the refcount-aware path (``tools/check_kv_blocks.py`` gates raw
``.free`` calls out of this plane).
"""

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...config import DeepSpeedInferenceConfig  # noqa: F401  (parity import)
from .blocked_allocator import BlockedAllocator  # noqa: F401
from .cache_telemetry import CacheTelemetry
from .kv_cache import BlockedKVCache
from .prefix_cache import PrefixKVCache
from .sequence_descriptor import DSSequenceDescriptor


class DSStateManager:

    def __init__(self, num_layers: int, num_kv_heads: int, head_dim: int, *, max_tracked_sequences: int = 128,
                 num_blocks: int = 256, block_size: int = 64, dtype=jnp.bfloat16, kv_sharding=None,
                 prefix_cache_config=None, kv_entry=None, state_entry=(), state_layers: int = 0, index_entry=()):
        """``kv_entry``: the model's ``TransformerConfig.kv_entry`` (None =
        per-head K and V of ``num_kv_heads`` x ``head_dim``); ``num_layers``:
        the layers that cache it. ``state_entry``: what a sequence holds in
        each of ``state_layers`` state layers (``TransformerConfig.state_entry``;
        ``()``: a model without): one slot a tracked sequence, taken when the
        sequence is created and freed when it is flushed. What takes a
        sequence's state to be its blocks refuses such a model by name:
        ``PrefixKVCache``, ``TieredBlockStore``, a rewind in :meth:`rollback_to`.
        ``index_entry``: the pooled keys a model with a learned block selection
        caches beside K and V (``TransformerConfig.index_entry``), on the
        blocks' own table; the same three refuse it."""
        self.max_tracked_sequences = max_tracked_sequences
        self.block_size = block_size
        self.kv_cache = BlockedKVCache(num_layers, num_kv_heads, head_dim, num_blocks, block_size, dtype=dtype,
                                       sharding=kv_sharding, entry=kv_entry, state_entry=state_entry,
                                       state_layers=state_layers, state_slots=max_tracked_sequences,
                                       index_entry=index_entry)
        self.prefix_cache: Optional[PrefixKVCache] = None
        # host/disk capacity tier under the radix tree (tiered_store.py);
        # None whenever ragged.prefix_cache.host_tier is absent/disabled —
        # the zero-overhead-absent contract
        self.tiered_store = None
        # memory & cache observability plane (``ragged.prefix_cache.telemetry``
        # block): when absent/off, NO telemetry object exists anywhere and
        # every hook in the allocator/tree stays one `is not None` check —
        # the zero-overhead contract tests/test_cache_telemetry.py enforces
        self.cache_telemetry: Optional[CacheTelemetry] = None
        tel_cfg = getattr(prefix_cache_config, "telemetry", None) \
            if prefix_cache_config is not None else None
        if prefix_cache_config is not None and getattr(prefix_cache_config, "enabled", False):
            if tel_cfg is not None and getattr(tel_cfg, "enabled", False):
                self.cache_telemetry = CacheTelemetry(self.kv_cache, config=tel_cfg)
                self.cache_telemetry.occupancy_provider = self._occupancy
                self.kv_cache.set_telemetry(self.cache_telemetry)
            self.prefix_cache = PrefixKVCache(self.kv_cache,
                                              min_hit_blocks=prefix_cache_config.min_hit_blocks,
                                              eviction=prefix_cache_config.eviction,
                                              telemetry=self.cache_telemetry)
            ht_cfg = getattr(prefix_cache_config, "host_tier", None)
            if ht_cfg is not None and getattr(ht_cfg, "enabled", False):
                # host/disk capacity tier (ragged.prefix_cache.host_tier):
                # presence-enabled — this branch is the ONLY place tier
                # objects (and the migration worker thread) come to exist
                from .tiered_store import TieredBlockStore

                self.tiered_store = TieredBlockStore(self.kv_cache, ht_cfg,
                                                     telemetry=self.cache_telemetry)
                self.prefix_cache.attach_tier(self.tiered_store)
        elif tel_cfg is not None and getattr(tel_cfg, "enabled", False):
            # the telemetry plane rides the prefix cache (blocks only have a
            # reuse lifecycle once the radix tree shares them) — an enabled
            # telemetry block under a disabled cache would otherwise vanish
            # silently and cost someone a dashboard-debugging session
            from ....utils.logging import logger

            logger.warning("ragged.prefix_cache.telemetry.enabled=True ignored: "
                           "the prefix cache itself is disabled — enable "
                           "ragged.prefix_cache to arm cache telemetry")
        self._seqs: Dict[int, DSSequenceDescriptor] = {}
        # tenant metering view (serving/metering.py EngineMeterView): set by
        # the engine's set_tenant_meter; None keeps every stamp site below
        # at one attribute check (the zero-overhead-off contract)
        self.tenant_meter = None

    def set_tenant_meter(self, view) -> None:
        """Wire (or with None, unwire) a per-engine tenant-meter view into
        the block lifecycle: the allocator's allocate/free hooks (alongside
        cache telemetry), owner stamping here, and the prefix cache's
        tenant-level publish/hit/evict forwards."""
        self.tenant_meter = view
        self.kv_cache.set_meter(view)
        if self.prefix_cache is not None:
            self.prefix_cache.set_meter(view)

    # -- queries -----------------------------------------------------------
    @property
    def n_tracked_sequences(self) -> int:
        return len(self._seqs)

    @property
    def free_blocks(self) -> int:
        return self.kv_cache.free_blocks

    @property
    def available_blocks(self) -> int:
        """Blocks a new allocation could actually obtain: the free list plus
        what LRU eviction could reclaim from tree-only holders. Admission
        must budget against THIS, not ``free_blocks`` — a warm cache keeps
        the free list near empty by design."""
        free = self.kv_cache.free_blocks
        if self.prefix_cache is not None:
            free += self.prefix_cache.evictable_blocks
        return free

    def _occupancy(self):
        """(used_token_slots, allocated_blocks) over live sequences — the
        cache telemetry's fragmentation numerator/denominator. Tree-held
        blocks are full by construction and excluded; the slack measured
        here is exactly partial tails + decode-horizon headroom."""
        used = allocated = 0
        bs = self.block_size
        # list(): the health exporter thread calls this mid-scrape while the
        # replica driver mutates _seqs — iterating the live dict would raise
        for seq in list(self._seqs.values()):
            allocated += len(seq.kv_blocks)
            used += min(seq.seen_tokens + seq.in_flight_tokens, len(seq.kv_blocks) * bs)
        return used, allocated

    def query(self, uid: Optional[int] = None):
        """Reference ``engine_v2.query``-backing lookup: per-sequence state
        or the (tracked, free-block) summary."""
        if uid is None:
            out = {"tracked": self.n_tracked_sequences, "free_blocks": self.free_blocks}
            if self.prefix_cache is not None:
                out["prefix_cache"] = dict(self.prefix_cache.stats,
                                           cached_blocks=self.prefix_cache.n_cached_blocks,
                                           hit_rate=self.prefix_cache.hit_rate)
            if self.tiered_store is not None:
                out["host_tier"] = self.tiered_store.snapshot()
            return out
        return self._seqs.get(uid)

    # -- lifecycle ---------------------------------------------------------
    def get_sequence(self, uid: int) -> Optional[DSSequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid: int) -> DSSequenceDescriptor:
        """Reference ``ragged_manager.py:135``."""
        seq = self._seqs.get(uid)
        if seq is not None:
            return seq
        return self.create_sequence_with_prefix(uid, None)[0]

    def create_sequence_with_prefix(self, uid: int, prompt_tokens, match=None,
                                    tenant=None) -> Tuple[DSSequenceDescriptor, int]:
        """Create a FRESH sequence, pre-populated from the prefix cache when
        ``prompt_tokens`` (the tokens about to be fed) hit the radix tree:
        the block table starts with the shared run (plus a COW tail copy)
        and ``seen_tokens`` at the hit length, so prefill starts AFTER the
        hit. ``match`` (from a prior pure probe) skips the re-match.
        Returns ``(seq, n_cached_tokens)`` — the caller must skip the
        first ``n_cached_tokens`` of ``prompt_tokens`` when feeding."""
        if uid in self._seqs:
            raise ValueError(f"uid {uid} already tracked: prefix acquisition is create-only")
        if len(self._seqs) >= self.max_tracked_sequences:
            raise RuntimeError(f"already tracking {self.max_tracked_sequences} sequences")
        seq = DSSequenceDescriptor(uid=uid, block_size=self.block_size)
        seq.tenant = tenant
        if self.kv_cache.has_state:
            seq.state_slot = self.kv_cache.reserve_state()
        n_cached = 0
        if self.prefix_cache is not None and prompt_tokens is not None:
            prompt_tokens = np.asarray(prompt_tokens).reshape(-1)
            blocks, n_cached, shared = self.prefix_cache.acquire(prompt_tokens, match=match,
                                                                 tenant=tenant)
            if n_cached:
                seq.kv_blocks = [int(b) for b in blocks]
                seq.seen_tokens = n_cached
                seq.shared_blocks = shared
                seq.prefix_cached_tokens = n_cached
                seq.token_history = [int(t) for t in prompt_tokens[:n_cached]]
        self._seqs[uid] = seq
        return seq, n_cached

    def allocate_blocks(self, seq: DSSequenceDescriptor, new_tokens: int) -> None:
        """Reference ``model.maybe_allocate_kv`` → ``BlockedKVCache.reserve``,
        with the prefix cache as the pressure valve: a dry free list evicts
        LRU tree-only blocks before the reserve."""
        need = seq.blocks_needed(new_tokens)
        if need > 0:
            if self.prefix_cache is not None and need > self.kv_cache.free_blocks:
                self.prefix_cache.evict(need - self.kv_cache.free_blocks)
            fresh = self.kv_cache.reserve(need)
            if self.tenant_meter is not None:
                # block-second attribution: the sequence's owner holds the
                # residency of every block it materializes KV into
                self.tenant_meter.stamp(fresh, seq.tenant)
            seq.extend_blocks(fresh)
        if self.tiered_store is not None:
            # proactive watermark demotion: below low_watermark free HBM,
            # push cold tree-only leaves toward the host tier so demand
            # eviction rarely demotes inline on the admission path. O(1)
            # when above the watermark.
            target = self.tiered_store.demotion_target()
            if target > 0:
                self.prefix_cache.demote_cold(target)

    def note_tokens(self, seq: DSSequenceDescriptor, tokens) -> None:
        """Record the token ids being materialized this forward (put chunk,
        or the fetched results of a decode burst) so completed full blocks
        can be published. Non-contiguous appends (a gap the host never saw)
        permanently stop publishing for this sequence instead of guessing."""
        if self.prefix_cache is None or not seq.history_valid:
            return
        if len(seq.token_history) != seq.seen_tokens:
            seq.history_valid = False
            return
        seq.token_history.extend(int(t) for t in np.asarray(tokens).reshape(-1))

    def publish_sequence(self, seq: DSSequenceDescriptor) -> None:
        """Insert ``seq``'s completed full blocks into the radix tree."""
        if self.prefix_cache is not None and seq.history_valid:
            self.prefix_cache.publish(seq)

    def rollback_to(self, seq: DSSequenceDescriptor, n_tokens: int,
                    final: bool = False) -> int:
        """THE single sequence-rewind primitive for the serving plane
        (speculative-draft rejection, decode-horizon overshoot at early
        finish/cancel — ``tools/check_spec_rollback.py`` gates all other
        rewind sites out): truncate ``token_history``, rewind
        ``seen_tokens`` to ``n_tokens``, and release now-unreferenced tail
        blocks back through the refcount-aware path — a block shared with
        the radix tree (or another sequence) merely loses THIS sequence's
        reference and survives for the other holders. Returns the number of
        tail references released.

        If the rewind lands mid-block in a block that is still SHARED, the
        block is copy-on-write duplicated first: the sequence's next tokens
        will scatter into the tail slots, and writing into a shared block
        would corrupt every other holder's view. The duplicate is reserved
        BEFORE any state mutates, so a dry pool fails the call atomically
        (the sequence is untouched). ``final=True`` skips the COW guard —
        the caller promises the sequence will never be written again (it is
        about to be flushed: finish/cancel paths), so a shared partial tail
        is harmless and a dry pool cannot fail a terminal rewind."""
        n_tokens = int(n_tokens)
        if (self.kv_cache.has_state or self.kv_cache.has_index) and not final and n_tokens != seq.seen_tokens:
            raise NotImplementedError(
                f"rollback_to({n_tokens}) of sequence {seq.uid} at {seq.seen_tokens} tokens: a model with a "
                "recurrent state layer has consumed the tokens to be rewound and keeps no snapshot to return to, and "
                "one with pooled keys (a learned block selection) has pooled the rewound tokens' keys into entries "
                "that earlier tokens share (a terminal rewind, final=True, is allowed: both go with the sequence)")
        if not 0 <= n_tokens <= seq.seen_tokens:
            raise ValueError(f"rollback_to({n_tokens}): sequence {seq.uid} has "
                             f"{seq.seen_tokens} materialized tokens")
        if seq.in_flight_tokens:
            raise RuntimeError(f"rollback_to on sequence {seq.uid} with "
                               f"{seq.in_flight_tokens} tokens in flight: rewinds happen "
                               "BETWEEN forwards only")
        bs = self.block_size
        keep = -(-n_tokens // bs)  # blocks still (partially) holding kept KV
        cow_src = cow_dst = None
        if (not final and n_tokens % bs and keep
                and self.kv_cache.refcount(seq.kv_blocks[keep - 1]) > 1):
            # COW guard: the new tail block is partial AND shared — future
            # appends would scatter into slots other holders read. Reserve
            # + copy first: if the pool is truly dry this raises with the
            # sequence still in its pre-rollback state.
            cow_src = seq.kv_blocks[keep - 1]
            if self.prefix_cache is not None and self.kv_cache.free_blocks < 1:
                self.prefix_cache.evict(1)
            cow_dst = int(self.kv_cache.reserve(1)[0])
            if self.tenant_meter is not None:
                self.tenant_meter.stamp([cow_dst], seq.tenant)
            self.kv_cache.copy_block(cow_src, cow_dst)
        tail = seq.kv_blocks[keep:]
        del seq.kv_blocks[keep:]
        if tail:
            self.kv_cache.release(tail)
        seq.seen_tokens = n_tokens
        if len(seq.token_history) > n_tokens:
            del seq.token_history[n_tokens:]
        seq.published_blocks = min(seq.published_blocks, n_tokens // bs)
        seq.shared_blocks = min(seq.shared_blocks, keep)
        if cow_dst is not None:
            seq.kv_blocks[keep - 1] = cow_dst
            self.kv_cache.release(cow_src)
            seq.shared_blocks = min(seq.shared_blocks, keep - 1)
        return len(tail)

    def commit_speculative(self, seq: DSSequenceDescriptor, n_tokens: int,
                           committed_tokens=None, src_positions=None) -> int:
        """Tree-verification commit: the branched cousin of a plain
        :meth:`rollback_to`. The verify forward materialized the WHOLE
        flattened token tree (every branch at its own flat slot) and noted
        the flat chunk into ``token_history``; the accepted path is in
        general NOT the flat prefix, so three things must happen together
        (same plane, same call — exactly why rollback_to is single-homed):

        1. when ``src_positions`` is given, the winning branch's KV moves
           from its flat tree slots to the canonical contiguous positions
           (``BlockedKVCache.compact_slots`` — dst strictly below src, both
           inside blocks this sequence exclusively owns: publish only ever
           shares FULL blocks, and the tree region starts past the last
           published boundary);
        2. ``rollback_to(n_tokens)`` releases the rejected remainder;
        3. ``committed_tokens`` overwrites the history tail so the radix
           tree can only ever see the VERIFIED stream — a rejected sibling
           branch's tokens must never be publishable.

        Returns rollback_to's released-reference count."""
        if src_positions:
            bs = self.block_size
            src = [seq.kv_blocks[p // bs] * bs + p % bs for p, _ in src_positions]
            dst = [seq.kv_blocks[p // bs] * bs + p % bs for _, p in src_positions]
            self.kv_cache.compact_slots(src, dst)
        released = self.rollback_to(seq, n_tokens)
        if committed_tokens is not None and seq.history_valid:
            m = len(committed_tokens)
            if m and len(seq.token_history) >= n_tokens >= m:
                seq.token_history[n_tokens - m:n_tokens] = [int(t) for t in committed_tokens]
        return released

    def shutdown(self) -> None:
        """Stop the tier's migration worker (engine destroy / test teardown);
        a no-op without a tier."""
        if self.tiered_store is not None:
            self.tiered_store.shutdown()

    def flush_sequence(self, uid: int) -> None:
        """Release a finished sequence's block references (reference
        ``flush:228``): publish completed full blocks first (the tree takes
        its own reference), then drop the sequence's — a block only goes
        physically free when no sequence AND no tree node holds it."""
        seq = self._seqs.pop(uid, None)
        if seq is None:
            return
        self.publish_sequence(seq)
        if seq.kv_blocks:
            self.kv_cache.release(seq.kv_blocks)
        if seq.state_slot >= 0:
            self.kv_cache.free_state(seq.state_slot)
            seq.state_slot = -1
