"""Tiered KV-block store: host-memory (+ optional disk) capacity tier
under the prefix cache's radix tree.

The DeepSpeed ZeRO-Infinity / ``runtime/swap_tensor`` lineage re-idiomized
for the ragged serving plane: the reusable-prefix corpus (system prompts,
tenant few-shot templates, long multi-turn histories) no longer dies at the
HBM pool boundary. ``PrefixKVCache.evict`` DEMOTES cold tree-only blocks
into a host block pool that mirrors the :class:`BlockedKVCache` layouts
(bf16 and the int8+scale variant), and a later radix hit on a demoted chain
PROMOTES the blocks back ahead of prefill. Host-pool pressure optionally
spills further to manifest-checksummed block files on disk.

Threading contract (the whole design hangs on it):

  * ALL device-array operations happen on the replica driver thread — the
    compiled forwards DONATE the KV pools, so a background thread touching
    ``k_pool``/``v_pool`` races buffer invalidation. Demotion therefore
    captures a functional VALUE snapshot of the victim block on the driver
    thread (``BlockedKVCache.read_block`` — jax slices capture the pool
    value at call time) and frees the HBM block immediately; the migration
    worker only ever materializes the snapshot to numpy (``np.asarray`` is
    the D2H copy) and writes host/disk memory. Promotion's H2D
    (``write_block``) likewise runs on the driver thread, inside admission
    (``acquire``), NEVER inside a decode step.
  * the migration queue is depth-bounded (the ResilientSaver discipline
    from ``runtime/resilience/saver.py`` / ``swap_tensor/async_swapper.py``):
    a slow tier back-pressures into plain drops — eviction never waits on
    the worker, decode steps never block on migration.
  * node residency transitions (``hbm -> in_flight -> host -> disk``) are
    finalized under the prefix cache's tree lock; the worker crashing
    mid-demotion (chaos point ``cache/demote``) loses exactly the demoting
    block — the failure callback drops that node (and any host descendants,
    unusable without their parent's KV) and the worker survives.

"Pinned" is aspirational on this runtime: numpy host arrays are not
registered with the TPU driver, but the pool mirrors the device layout so
each block's D2H/H2D is one contiguous memcpy — the slot a real pinned
allocator drops into.
"""

import os
import threading
import time
import zlib
from collections import OrderedDict, deque

import numpy as np

from ....runtime.resilience import chaos

# residency states a radix node moves through (``_Node.res``); kept here so
# every module spells them identically
RES_HBM = "hbm"
RES_IN_FLIGHT = "in_flight"  # demotion queued/running: unusable, unmatched
RES_HOST = "host"
RES_DISK = "disk"


class HostBlockPool:
    """Host mirror of one :class:`BlockedKVCache`'s block layout.

    Same axes as the device pools — ``k/v: [L, HB*bs, nkv, hd]`` in the
    device dtype (int8 included; ``v_pool`` None where the device cache has a
    latent entry, whose one part is ``k``) and, on the quantized layout, fp32 scale
    side pools ``[nkv, L*HB*bs]`` — so a block moves between tiers as one
    contiguous span per pool, no transpose, no re-quantization. All
    mutation goes through the ``host_*`` methods below; like the device
    pool's ``.free``, raw calls outside the sanctioned modules are a
    ``tools/check_kv_blocks.py`` violation.
    """

    def __init__(self, kv_cache, num_blocks: int):
        self.block_size = kv_cache.block_size
        self.num_blocks = int(num_blocks)
        self.num_layers = kv_cache.num_layers
        self.num_kv_heads = kv_cache.num_kv_heads
        self.quantized = kv_cache.quantized
        if self.num_blocks < 1:
            raise ValueError(f"host pool needs >= 1 block, got {num_blocks}")
        dtype = np.dtype(kv_cache.k_pool.dtype)  # ml_dtypes covers bf16
        # one host pool a part of the device cache's entry (K and V, or the one latent part)
        shapes = [(self.num_layers, self.num_blocks * self.block_size, h, w) for h, w in kv_cache.entry]
        self.k_pool = np.zeros(shapes[0], dtype)
        self.v_pool = np.zeros(shapes[1], dtype) if len(shapes) == 2 else None
        self.k_scale = self.v_scale = None
        if self.quantized:
            flat = self.num_layers * self.num_blocks * self.block_size
            self.k_scale = np.zeros((self.num_kv_heads, flat), np.float32)
            self.v_scale = np.zeros((self.num_kv_heads, flat), np.float32)
        # free-list under its own lock: the migration worker reserves/writes
        # while the driver thread frees promoted blocks
        self._mu = threading.Lock()
        self._free = list(range(self.num_blocks - 1, -1, -1))

    @property
    def free_blocks(self) -> int:
        with self._mu:
            return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - self.free_blocks

    def host_reserve(self) -> int:
        """One block at single ownership, ``-1`` when the pool is full (the
        caller spills or drops — never blocks)."""
        with self._mu:
            return self._free.pop() if self._free else -1

    def host_free(self, block: int) -> None:
        with self._mu:
            self._free.append(int(block))

    def _scales(self):
        span = self.num_blocks * self.block_size
        return (self.k_scale.reshape(self.num_kv_heads, self.num_layers, span),
                self.v_scale.reshape(self.num_kv_heads, self.num_layers, span))

    def host_write(self, block: int, k, v, k_scale=None, v_scale=None) -> None:
        """Install one block's KV (shapes of ``BlockedKVCache.read_block``).
        Only the reserving owner may write — a block is never writable in
        two tiers at once (fuzz-enforced in ``tests/test_tiered_store.py``)."""
        bs = self.block_size
        d = int(block) * bs
        self.k_pool[:, d:d + bs] = k
        if self.v_pool is not None:
            self.v_pool[:, d:d + bs] = v
        if self.quantized and k_scale is not None:
            ks, vs = self._scales()
            ks[:, :, d:d + bs] = k_scale
            vs[:, :, d:d + bs] = v_scale

    def host_read(self, block: int):
        """Views of one resident block: ``(k, v, k_scale, v_scale)`` —
        promotion copies them device-side before the block is freed."""
        bs = self.block_size
        s = int(block) * bs
        k = self.k_pool[:, s:s + bs]
        v = None if self.v_pool is None else self.v_pool[:, s:s + bs]
        if not self.quantized:
            return k, v, None, None
        ks, vs = self._scales()
        return k, v, ks[:, :, s:s + bs], vs[:, :, s:s + bs]

    def memory_bytes(self) -> int:
        n = sum(p.nbytes for p in (self.k_pool, self.v_pool) if p is not None)
        if self.quantized:
            n += 2 * self.k_scale.size * 4
        return n


class TieredBlockStore:
    """Migration engine between the HBM pool, a :class:`HostBlockPool`, and
    an optional disk tier. Owned by :class:`PrefixKVCache` (``attach``);
    presence-enabled — when ``ragged.prefix_cache.host_tier`` is absent no
    instance, no worker thread and no per-node residency state exist."""

    def __init__(self, kv_cache, config, telemetry=None, clock=time.monotonic):
        if getattr(kv_cache, "has_state", False):
            raise NotImplementedError(
                "TieredBlockStore for a model with a recurrent state layer: the host tier moves K/V blocks and a "
                "sequence's state is no block; it would have to travel beside them, which is not built")
        if getattr(kv_cache, "has_index", False):
            raise NotImplementedError(
                "TieredBlockStore for a model with pooled keys (a learned block selection): the host tier moves K/V "
                "blocks, and a block's pooled keys would have to travel beside it, which is not built")
        self.kv_cache = kv_cache
        self.config = config
        n = int(getattr(config, "host_blocks", 0) or 0)
        if n <= 0 and getattr(config, "host_pool_bytes", 0):
            n = int(config.host_pool_bytes) // max(1, kv_cache.block_bytes())
        if n <= 0:
            raise ValueError("host_tier needs host_blocks or host_pool_bytes "
                             "sizing at least one block")
        self.pool = HostBlockPool(kv_cache, n)
        self.queue_depth = max(1, int(getattr(config, "queue_depth", 8)))
        self._telemetry = telemetry
        self._meter = None  # EngineMeterView (charge_host_kv), set via set_meter
        self._clock = clock
        self._cache = None  # attach() wires the owning PrefixKVCache
        # host-LRU bookkeeping: node -> host block, insertion order = demote
        # order (touched on promotion-miss only via re-demotion, so plain
        # insertion order is the eviction order we want). Guarded by the
        # TREE lock: every mutator already holds it.
        self._host_nodes = OrderedDict()
        # per-host-block tenant stamp for PR 15 metering: owner + residency
        # start, charged to ``host_kv_s`` when the block leaves the tier
        self._host_stamp = {}
        # disk tier (optional): manifest maps disk_id -> {file, crc, nbytes};
        # `_disk_pending` covers the window where a spill's payload is only
        # in worker memory (a racing promotion reads it from here). `_mu`
        # guards manifest/pending/counters against worker vs driver access.
        self._mu = threading.Lock()
        self._disk_dir = getattr(config, "disk_path", None)
        self._disk_cap = int(getattr(config, "disk_blocks", 0) or 0)
        self._disk_manifest = {}
        self._disk_pending = {}
        self._next_disk_id = 0
        if self._disk_dir is not None:
            self._disk_dir = str(self._disk_dir)
            os.makedirs(self._disk_dir, exist_ok=True)
        self.counters = {"demotions": 0, "demote_failures": 0,
                         "demote_cancelled": 0, "promotions_host": 0,
                         "promotions_disk": 0, "host_evictions": 0,
                         "disk_spills": 0, "disk_corrupt": 0,
                         "disk_drops": 0, "prefetch_enqueued": 0,
                         "prefetch_hits": 0, "prefetch_unused": 0,
                         "host_installs": 0}
        # chain-lookahead staging: node -> materialized payload the worker
        # parked ahead of the driver's promotion walk (``prefetch``), plus
        # the in-flight markers that dedupe enqueues. Guarded by ``_mu``;
        # parking re-checks residency under the TREE lock so a dropped
        # node's payload can never wedge a slot.
        self._prefetched = {}
        self._prefetch_inflight = set()
        self._q = deque()
        self._cv = threading.Condition()
        self._stop = False
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="kv-tier-migrator")
        self._worker.start()

    # -- wiring ------------------------------------------------------------
    def attach(self, prefix_cache) -> None:
        """Bind to the owning tree: residency finalization happens under its
        ``_tree_lock`` through the cache's ``_demote_finalized`` /
        ``_demote_failed`` callbacks."""
        self._cache = prefix_cache

    def set_meter(self, view) -> None:
        self._meter = view

    # -- demotion (driver side: enqueue-only, never blocks) -----------------
    def try_demote(self, node, snapshot) -> bool:
        """Queue one D2H migration. Called under the tree lock from
        ``PrefixKVCache.evict`` with ``snapshot`` = the block's functional
        device slices (``read_block``). Returns False — caller drops the
        block the old way — when the queue is at depth or the store is shut
        down; never waits (the decode-never-blocks rule)."""
        with self._cv:
            if self._stop or len(self._q) >= self.queue_depth:
                return False
            self._q.append(("demote", node, snapshot, self._clock()))
            self._cv.notify()
        return True

    def prefetch(self, node) -> bool:
        """Queue a background materialization of a demoted node's payload —
        the chain-lookahead: while the driver H2Ds chain[i], the worker
        stages chain[i+1]'s host/disk bytes so the next ``promote_payload``
        is a dict pop instead of a copy (+ disk read + crc). Enqueue-only,
        called under the tree lock; depth-bounded like demotion, so a busy
        worker just leaves that promotion synchronous — never blocks, never
        wrong."""
        if node.res not in (RES_HOST, RES_DISK):
            return False
        with self._cv:
            if self._stop or len(self._q) >= self.queue_depth:
                return False
            with self._mu:
                if node in self._prefetched or node in self._prefetch_inflight:
                    return True
                self._prefetch_inflight.add(node)
                self.counters["prefetch_enqueued"] += 1
            self._q.append(("prefetch", node))
            self._cv.notify()
        return True

    @property
    def queued(self) -> int:
        with self._cv:
            return len(self._q)

    # -- promotion (driver side) -------------------------------------------
    def promote_payload(self, node):
        """Host/disk payload of a demoted node for H2D restore:
        ``(k, v, k_scale, v_scale)`` or None when the backing copy is gone
        or fails its checksum — the caller drops the node (a miss, never
        wrong KV). Called under the tree lock on the driver thread. A
        payload the lookahead worker already parked is consumed directly."""
        with self._mu:
            parked = self._prefetched.pop(node, None)
            if parked is not None:
                self.counters["prefetch_hits"] += 1
        if parked is not None:
            return parked
        if node.res == RES_HOST:
            # copy, don't alias: on CPU backends jnp.asarray may wrap the
            # host buffer zero-copy, and host_free can recycle the slot
            # before the async .at[].set consumes it
            return tuple(None if a is None else np.array(a)
                         for a in self.pool.host_read(node.host_block))
        if node.res == RES_DISK:
            with self._mu:
                pending = self._disk_pending.get(node.disk_id)
            if pending is not None:
                return pending
            return self._disk_read(node.disk_id)
        return None

    def note_promoted(self, from_disk: bool) -> None:
        with self._mu:
            self.counters["promotions_disk" if from_disk
                          else "promotions_host"] += 1

    def release_resident(self, node) -> None:
        """Drop a node's host/disk copy (after promotion installed it in
        HBM, or when the node is being discarded). Tree lock held."""
        with self._mu:
            if self._prefetched.pop(node, None) is not None:
                self.counters["prefetch_unused"] += 1
            self._prefetch_inflight.discard(node)
        if node.host_block >= 0:
            self._release_host_block(node.host_block)
            self._host_nodes.pop(node, None)
            node.host_block = -1
        if node.disk_id >= 0:
            self._disk_drop(node.disk_id)
            node.disk_id = -1

    # -- handoff adoption (disaggregated serving) ----------------------------
    def host_install(self, payload) -> int:
        """Reserve a host block and fill it with an externally-produced KV
        payload (``read_block`` shapes) — the landing zone of a
        cross-replica handoff (``serving/handoff.py``). Makes room by
        evicting cold host residents exactly like the demotion worker;
        returns -1 only when the pool holds no evictable leaf. Host-memory
        and file ops only, so it is safe OFF this replica's driver thread
        (the handoff broker runs on the SOURCE replica's driver)."""
        hb = self.pool.host_reserve()
        while hb < 0:
            try:
                self._evict_host_one()
            except RuntimeError:
                return -1
            hb = self.pool.host_reserve()
        k, v, ks, vs = payload
        self.pool.host_write(hb, k, v, ks, vs)
        with self._mu:
            self.counters["host_installs"] += 1
        return hb

    def register_host_node(self, node, host_block: int) -> None:
        """Finalize adoption: bind an installed host block to its new tree
        node as a first-class host resident (LRU-tracked, owner-stamped so
        PR 15's ``host_kv_s`` conservation holds across the handoff). Tree
        lock held by the caller (``PrefixKVCache.install_host_chain``)."""
        node.res = RES_HOST
        node.host_block = int(host_block)
        self._host_nodes[node] = int(host_block)
        self._host_stamp[int(host_block)] = (node.owner, self._clock())
        if self._telemetry is not None:
            self._telemetry.note_host_used(self.pool.used_blocks)

    # -- watermark surface ---------------------------------------------------
    def demotion_target(self) -> int:
        """Blocks proactive demotion should move now: when the HBM free
        fraction is under ``low_watermark``, the shortfall up to
        ``high_watermark`` (0 otherwise — and 0 whenever the queue is full,
        so the check stays O(1) and dropless)."""
        total = self.kv_cache.total_blocks
        free = self.kv_cache.free_blocks
        if total <= 0 or free >= self.config.low_watermark * total:
            return 0
        return max(0, int(self.config.high_watermark * total) - free)

    # -- stats ---------------------------------------------------------------
    def snapshot(self) -> dict:
        with self._mu:
            c = dict(self.counters)
            disk_used = len(self._disk_manifest)
            c["prefetched_parked"] = len(self._prefetched)
        c.update(host_blocks=self.pool.num_blocks,
                 host_used=self.pool.used_blocks,
                 host_bytes=self.pool.memory_bytes(),
                 queue_depth=self.queue_depth, queued=self.queued,
                 disk_blocks=self._disk_cap if self._disk_dir else 0,
                 disk_used=disk_used)
        return c

    def shutdown(self, timeout: float = 5.0) -> None:
        """Stop the worker (drains nothing: queued jobs are cancelled by the
        stop flag and their nodes dropped via the failure path)."""
        with self._cv:
            self._stop = True
            pending = list(self._q)
            self._q.clear()
            self._cv.notify_all()
        self._worker.join(timeout)
        for item in pending:
            if item[0] == "demote":
                self._fail_node(item[1], cancelled=True)
            else:
                with self._mu:
                    self._prefetch_inflight.discard(item[1])

    # -- migration worker -----------------------------------------------------
    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._q and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                item = self._q.popleft()
            if item[0] == "prefetch":
                self._run_prefetch(item[1])
                continue
            _, node, snapshot, t0 = item
            try:
                # chaos point: a hook here simulates the worker dying
                # mid-copy — the except arm below is the blast-radius
                # contract (this block only) the tests pin down
                chaos.fire("cache/demote", {"queued": self.queued})
                hb = self._reserve_host_block(exclude=node)
                k, v, ks, vs = snapshot
                # np.asarray IS the D2H copy — of the functional snapshot,
                # not the live (long since reused) pool slots
                self.pool.host_write(hb, *(None if a is None else np.asarray(a) for a in (k, v, ks, vs)))
                self._finalize_demote(node, hb, t0)
            except Exception:
                with self._mu:
                    self.counters["demote_failures"] += 1
                self._fail_node(node)

    def _run_prefetch(self, node) -> None:
        """Worker half of :meth:`prefetch`: materialize one demoted node's
        payload (host memcpy or disk read, never a device op) and park it.
        Residency is checked under the tree lock both before the read and
        at park time — a node promoted or dropped since enqueue just clears
        its in-flight marker, and a stale payload can never occupy a slot
        (every drop path pops ``_prefetched`` under the same lock)."""
        cache = self._cache
        payload = disk_id = None
        try:
            with cache._tree_lock:
                if node.res == RES_HOST and node.host_block >= 0:
                    payload = tuple(None if a is None else np.array(a)
                                    for a in self.pool.host_read(node.host_block))
                elif node.res == RES_DISK and node.disk_id >= 0:
                    disk_id = node.disk_id
            if disk_id is not None:
                with self._mu:
                    payload = self._disk_pending.get(disk_id)
                if payload is None:
                    payload = self._disk_read(disk_id)
            with cache._tree_lock:
                with self._mu:
                    self._prefetch_inflight.discard(node)
                    if (payload is not None
                            and node.res in (RES_HOST, RES_DISK)
                            and len(self._prefetched) < self.queue_depth):
                        self._prefetched[node] = payload
        except Exception:
            with self._mu:
                self._prefetch_inflight.discard(node)

    def _finalize_demote(self, node, host_block: int, t0: float) -> None:
        cache = self._cache
        with cache._tree_lock:
            if node.res != RES_IN_FLIGHT or node.parent is None:
                # the node was dropped (clear()/shutdown race) while we
                # copied: give the host block back, charge nothing
                with self._mu:
                    self.counters["demote_cancelled"] += 1
                self.pool.host_free(host_block)
                return
            node.res = RES_HOST
            node.host_block = int(host_block)
            self._host_nodes[node] = int(host_block)
            self._host_stamp[int(host_block)] = (node.owner, self._clock())
            with self._mu:
                self.counters["demotions"] += 1
            if self._telemetry is not None:
                self._telemetry.on_demote(self.pool.used_blocks,
                                          wait_s=self._clock() - t0)

    def _fail_node(self, node, cancelled: bool = False) -> None:
        cache = self._cache
        try:
            with cache._tree_lock:
                if node.res == RES_IN_FLIGHT and node.parent is not None:
                    cache._drop_node_subtree(node)
                if cancelled:
                    with self._mu:
                        self.counters["demote_cancelled"] += 1
        except Exception:
            pass  # forensic path: the worker must survive anything here

    def _reserve_host_block(self, exclude=None) -> int:
        """Worker-side host reservation; a full pool spills (or drops) the
        coldest host-resident chain leaf first. Never returns -1."""
        hb = self.pool.host_reserve()
        while hb < 0:
            self._evict_host_one(exclude=exclude)
            hb = self.pool.host_reserve()
        return hb

    def _evict_host_one(self, exclude=None) -> None:
        cache = self._cache
        with cache._tree_lock:
            victim = None
            for node in self._host_nodes:
                if node is exclude:
                    continue
                # only chain leaves leave the host tier: dropping/spilling a
                # mid-chain node under host children would break the
                # root-ward residency ordering the match walk relies on
                if not any(c.res in (RES_HOST, RES_IN_FLIGHT)
                           for c in node.children.values()):
                    victim = node
                    break
            if victim is None:
                raise RuntimeError("host pool full with no evictable chain leaf")
            with self._mu:
                self.counters["host_evictions"] += 1
                disk_ok = (self._disk_dir is not None
                           and len(self._disk_manifest) + len(self._disk_pending)
                           < self._disk_cap)
            if disk_ok:
                payload = tuple(None if a is None else np.array(a)
                                for a in self.pool.host_read(victim.host_block))
                with self._mu:
                    disk_id = self._next_disk_id
                    self._next_disk_id += 1
                    self._disk_pending[disk_id] = payload
                self._release_host_block(victim.host_block)
                self._host_nodes.pop(victim, None)
                victim.host_block = -1
                victim.res = RES_DISK
                victim.disk_id = disk_id
            else:
                if self._disk_dir is not None:
                    with self._mu:
                        self.counters["disk_drops"] += 1
                cache._drop_node_subtree(victim)
                payload = disk_id = None
        if payload is not None:
            self._disk_write(disk_id, payload)

    # -- host-block metering ---------------------------------------------------
    def _release_host_block(self, hb: int) -> None:
        owner, t0 = self._host_stamp.pop(int(hb), (None, None))
        if self._meter is not None and t0 is not None:
            self._meter.charge_host_kv(owner, max(0.0, self._clock() - t0))
        self.pool.host_free(hb)
        if self._telemetry is not None:
            self._telemetry.note_host_used(self.pool.used_blocks)

    # -- disk tier --------------------------------------------------------------
    def _disk_file(self, disk_id: int) -> str:
        return os.path.join(self._disk_dir, f"kvblock_{disk_id:08d}.npz")

    def _disk_write(self, disk_id: int, payload) -> None:
        """Bounded-writer spill (the ``swap_tensor/async_swapper`` lineage:
        one worker, depth-limited in-flight payloads): serialize outside
        every lock, fsync-free tmp+rename commit, crc32 in the manifest so
        a torn/corrupt file reads as a MISS, never as wrong KV."""
        k, v, ks, vs = payload
        path = self._disk_file(disk_id)
        try:
            import io

            buf = io.BytesIO()
            # KV goes to disk as raw bytes (uint8 view) — np.savez has no
            # portable story for ml_dtypes bf16, and the pool dtype is known
            # at read time anyway
            arrs = {name: np.ascontiguousarray(a).view(np.uint8) for name, a in (("k", k), ("v", v))
                    if a is not None}  # a latent entry has the one part
            if ks is not None:
                arrs["ks"], arrs["vs"] = ks, vs
            np.savez(buf, **arrs)
            raw = buf.getvalue()
            crc = zlib.crc32(raw) & 0xFFFFFFFF
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(raw)
            os.replace(tmp, path)
            with self._mu:
                # the id may have been dropped (node discarded) while we
                # wrote: record only a still-wanted file
                if disk_id in self._disk_pending:
                    self._disk_manifest[disk_id] = {
                        "file": os.path.basename(path), "crc": crc,
                        "nbytes": len(raw), "dtype": str(self.pool.k_pool.dtype)}
                    del self._disk_pending[disk_id]
                    self.counters["disk_spills"] += 1
                    self._write_manifest_locked()
                    return
            os.remove(path)
        except Exception:
            # failed spill: the pending payload is the only copy — dropping
            # it turns the node into a permanent miss at next promotion
            with self._mu:
                self._disk_pending.pop(disk_id, None)
                self.counters["disk_corrupt"] += 1

    def _disk_read(self, disk_id: int):
        with self._mu:
            ent = self._disk_manifest.get(disk_id)
        if ent is None:
            return None
        path = os.path.join(self._disk_dir, ent["file"])
        try:
            with open(path, "rb") as f:
                raw = f.read()
            if (zlib.crc32(raw) & 0xFFFFFFFF) != ent["crc"]:
                raise ValueError("crc mismatch")
            import io

            with np.load(io.BytesIO(raw)) as z:
                dtype = np.dtype(self.pool.k_pool.dtype)
                k = np.ascontiguousarray(z["k"]).view(dtype)
                v = np.ascontiguousarray(z["v"]).view(dtype) if "v" in z.files else None
                ks = z["ks"].copy() if "ks" in z.files else None
                vs = z["vs"].copy() if "vs" in z.files else None
                return k, v, ks, vs
        except Exception:
            with self._mu:
                self.counters["disk_corrupt"] += 1
            return None

    def _disk_drop(self, disk_id: int) -> None:
        with self._mu:
            self._disk_pending.pop(disk_id, None)
            ent = self._disk_manifest.pop(disk_id, None)
            if ent is not None:
                self._write_manifest_locked()
        if ent is not None:
            try:
                os.remove(os.path.join(self._disk_dir, ent["file"]))
            except OSError:
                pass

    def _write_manifest_locked(self) -> None:
        import json

        path = os.path.join(self._disk_dir, "MANIFEST.json")
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump({str(i): e for i, e in self._disk_manifest.items()},
                          f, indent=0)
            os.replace(tmp, path)
        except OSError:
            pass
