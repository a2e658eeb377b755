"""Blocked (paged) KV cache on device.

Analog of the reference ``inference/v2/ragged/kv_cache.py:40``
(``BlockedKVCache``: device block pool fronted by a ``BlockedAllocator``).
TPU-native layout: the pool is built from what the MODEL says one token's
entry in one layer is (``TransformerConfig.kv_entry``: ``(heads, width)`` of
each part), one stacked array a part,

    [num_layers, num_blocks * block_size, heads, width]

* per-head K and V (every family but one): two parts ``(num_kv_heads,
  head_dim)``, ``k_pool`` and ``v_pool``;
* a latent entry (latent attention): ONE part ``(1, width)``, ``k_pool``
  alone (``v_pool`` is None): the normed latent and the shared rotated key
  part of a token, whose first lanes are also its value, stored once.

The block dimension is flattened so a token's slot is the flat index
``block_id * block_size + offset`` — scatter (append) and gather (attention)
are then single-index operations that XLA lowers to efficient dynamic-slice /
dynamic-update-slice, and the Pallas paged-attention kernels index the same
flat pool. The pool shards over the ``model`` axis on the kv-head dim (TP).
Every method below walks the parts that exist (:meth:`BlockedKVCache.pools`),
so what rides them (copy-on-write, the host tier, rollback, export and
import) carries a latent block as it carries a K/V block.

A model with STATE layers (linear attention: ``TransformerConfig.state_entry``)
has a second kind of cache beside the blocks: ``state_slots`` slots, one a
tracked sequence, each a fixed ``[state_layers, ...]`` of float32 state and of
the convolution's tail whatever the sequence's length,

    state_pool [state_layers, state_slots, heads, dk, dv]   float32
    tail_pool  [state_layers, state_slots, taps - 1, channels]

handed out by an allocator of their own (:meth:`reserve_state` /
:meth:`free_state`) and threaded through the compiled forwards after the K/V
pools. ``num_layers`` is then the layers that cache K and V alone. A slot is
not a block: nothing shares it, copies it or hashes it, and what moves blocks
about (the prefix cache, the host tier, export) does not know it. A lightning
layer's state is the state pool alone (``state_entry`` of one part: no tail).

A model with a learned block-sparse SELECTION (``TransformerConfig.index_entry``)
has a third kind: one mean-pooled key every ``stride`` tokens a KV head,

    index_pool [num_layers, num_blocks * block_size / stride, heads, width]

ON the K/V blocks' table: a block's pooled keys are the ``block_size / stride``
entries under its id, so they are allocated, freed and tracked with the block
and by nothing else. Like the state it rides the compiled forwards (after the
K/V pools, before the state's) and is unknown to what copies, shares or moves
blocks, which refuse such a model by name.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocked_allocator import BlockedAllocator


class BlockedKVCache:
    """``dtype=jnp.int8`` (or the string ``"int8"``) selects the quantized
    cache (the TPU analog of the reference FastGen quantized KV variants,
    ``csrc/quantization/``): values stored int8 with one fp32 absmax/127
    scale per (token, kv-head) in side pools ``k_scale``/``v_scale``
    [nkv, L*NB*bs] (kv-heads on sublanes, flat slots on lanes — the layout
    the forward's scatter and the Pallas kernel read without a transpose).
    Decode is bound by the KV byte stream, so int8 halves that term (scales
    add 1/(2·head_dim) back)."""

    def __init__(self, num_layers: int, num_kv_heads: int, head_dim: int, num_blocks: int, block_size: int = 64,
                 dtype=jnp.bfloat16, sharding=None, entry=None, state_entry=(), state_layers: int = 0,
                 state_slots: int = 0, index_entry=()):
        """``entry``: the model's ``kv_entry``; None = per-head K and V of
        ``num_kv_heads`` x ``head_dim``. ``num_kv_heads`` / ``head_dim`` are
        then the first part's heads and width. ``state_entry``: the model's
        ``state_entry`` (``()``: none), held by ``state_layers`` layers in
        ``state_slots`` slots; ``num_layers`` counts the K/V layers alone.
        ``index_entry``: the model's ``index_entry`` ``(stride, heads, width)``
        (``()``: no pooled keys)."""
        if entry is None:
            entry = ((num_kv_heads, head_dim), ) * 2
        self.entry = tuple((int(h), int(w)) for h, w in entry)
        self.num_layers = num_layers
        self.num_kv_heads, self.head_dim = self.entry[0]
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        if dtype in ("int8", jnp.int8, np.int8):
            dtype = jnp.int8
        self.dtype = dtype
        self.quantized = dtype == jnp.int8
        if len(self.entry) not in (1, 2):
            raise ValueError(f"a KV entry of one part (a latent) or two (K and V), got {self.entry}")
        if self.quantized and len(self.entry) != 2:
            raise NotImplementedError("an int8 KV cache beside a latent entry: the int8 layout keeps one scale a "
                                      "(token, kv head) of K and of V, and a latent entry has neither")
        self._allocator = BlockedAllocator(num_blocks)
        shapes = [(num_layers, self.num_blocks * self.block_size, h, w) for h, w in self.entry]
        self.k_pool = jnp.zeros(shapes[0], dtype)
        self.v_pool = jnp.zeros(shapes[1], dtype) if len(shapes) == 2 else None
        self.k_scale = self.v_scale = None
        self.state_pool = self.tail_pool = self._state_allocator = self.index_pool = None
        if index_entry:
            stride, heads, width = (int(x) for x in index_entry)
            if self.quantized or sharding is not None or len(self.entry) != 2 or self.block_size % stride:
                raise NotImplementedError("pooled keys (a learned block selection) beside an int8, a sharded or a "
                                          f"latent KV cache, or KV blocks ({self.block_size}) that hold no whole "
                                          f"number of pooling strides ({stride})")
            self.index_pool = jnp.zeros((num_layers, self.num_blocks * self.block_size // stride, heads, width), dtype)
        if state_entry:
            if self.quantized or sharding is not None:
                raise NotImplementedError("a recurrent state beside an int8 or a sharded KV cache: the state is "
                                          "float32 by the model's statement and lives whole on one device")
            state_shape, *tail_shape = state_entry
            self.state_pool = jnp.zeros((state_layers, state_slots) + tuple(state_shape), jnp.float32)
            if tail_shape:  # the delta rule's convolution tail; a lightning layer has none
                self.tail_pool = jnp.zeros((state_layers, state_slots) + tuple(tail_shape[0]), dtype)
            self._state_allocator = BlockedAllocator(state_slots)
        if self.quantized:
            # [nkv, L * NB * bs] — kv-heads on sublanes, slots on lanes: the
            # layout the forward's scatter and the Pallas kernel's scale
            # BlockSpec both consume without a per-call transpose
            flat = num_layers * self.num_blocks * self.block_size
            self.k_scale = jnp.zeros((num_kv_heads, flat), jnp.float32)
            self.v_scale = jnp.zeros((num_kv_heads, flat), jnp.float32)
        if sharding is not None:
            for name in self._parts():
                setattr(self, name, jax.device_put(getattr(self, name), sharding))
            if self.quantized:
                # scales shard with the kv-head dim (pool dim 2 → scale dim 0)
                from jax.sharding import NamedSharding, PartitionSpec as P

                if isinstance(sharding, NamedSharding) and len(sharding.spec) >= 3:
                    sc = NamedSharding(sharding.mesh, P(sharding.spec[2], None))
                    self.k_scale = jax.device_put(self.k_scale, sc)
                    self.v_scale = jax.device_put(self.v_scale, sc)

    def _parts(self):
        """Names of the value pools this cache has, in ``pools()`` order."""
        return ("k_pool", "v_pool")[:len(self.entry)]

    def _map_parts(self, fn) -> None:
        """Replace every value pool ``p`` by ``fn(p)``."""
        for name in self._parts():
            setattr(self, name, fn(getattr(self, name)))

    @property
    def free_blocks(self) -> int:
        return self._allocator.free_blocks

    # -- the state slots of a model with state layers ------------------------
    @property
    def has_state(self) -> bool:
        return self.state_pool is not None

    @property
    def state_slots(self) -> int:
        return self._state_allocator.total_blocks if self.has_state else 0

    @property
    def free_state_slots(self) -> int:
        return self._state_allocator.free_blocks if self.has_state else 0

    def reserve_state(self) -> int:
        """One slot for a sequence that enters. What the slot held stays in
        it: the forward starts a sequence's first token from zero."""
        return int(self._state_allocator.allocate(1)[0])

    def free_state(self, slot: int) -> None:
        self._state_allocator.free([int(slot)])

    def state_entry_bytes(self) -> int:
        """Bytes ONE sequence holds in ONE state layer (state and tail)."""
        if not self.has_state:
            return 0
        return sum(p[0, 0].size * p.dtype.itemsize for p in (self.state_pool, self.tail_pool) if p is not None)

    # -- the pooled keys of a model with a learned block selection -----------
    @property
    def has_index(self) -> bool:
        return self.index_pool is not None

    def index_entry_bytes(self) -> int:
        """Bytes ONE pooled key holds in ONE layer (every kv head's)."""
        return self.index_pool[0, 0].size * self.index_pool.dtype.itemsize if self.has_index else 0

    @property
    def total_blocks(self) -> int:
        return self._allocator.total_blocks

    def reserve(self, n_blocks: int) -> np.ndarray:
        """Allocate ``n_blocks`` at refcount 1 (reference ``kv_cache.py:147``)."""
        return self._allocator.allocate(n_blocks)

    def free(self, blocks) -> None:
        self._allocator.free(blocks)

    # -- refcount-aware sharing surface (prefix cache) ---------------------
    def incref(self, blocks) -> None:
        """One more holder per block: the block contents become IMMUTABLE
        until the count drops back to one (copy-on-write for mutation)."""
        self._allocator.incref(blocks)

    def release(self, blocks) -> None:
        """Drop one reference per block; physical free happens at zero."""
        self._allocator.release(blocks)

    def refcount(self, block) -> int:
        return self._allocator.refcount(block)

    def refcount_snapshot(self):
        """Copy of the whole refcount table (cache telemetry's pool
        decomposition)."""
        return self._allocator.refcount_snapshot()

    def set_telemetry(self, telemetry) -> None:
        """Arm (or with None, disarm) the allocator's lifecycle hooks —
        the facade's only sanctioned route to them."""
        self._allocator.telemetry = telemetry

    def set_meter(self, view) -> None:
        """Arm (or with None, disarm) the tenant-metering view on the same
        allocator lifecycle surface cache telemetry rides."""
        self._allocator.meter = view

    def copy_block(self, src: int, dst: int) -> None:
        """Device-side copy of one block's KV slots ``src`` → ``dst`` (the
        copy-on-write primitive: a sequence that must write into a SHARED
        block first duplicates it into a privately-held block). Eager jnp
        ops — COW is rare (one copy per partial-tail prefix hit), so the
        dispatch cost is noise next to the prefill it saves."""
        bs = self.block_size
        s, d = int(src) * bs, int(dst) * bs
        self._map_parts(lambda pool: pool.at[:, d:d + bs].set(pool[:, s:s + bs]))
        if self.quantized:
            # scale layout [nkv, L * NB * bs]: per-layer strided slots — copy
            # through a [nkv, L, NB*bs] view so each layer's span moves
            nkv = self.num_kv_heads
            span = self.num_blocks * bs
            for name in ("k_scale", "v_scale"):
                sc = getattr(self, name).reshape(nkv, self.num_layers, span)
                sc = sc.at[:, :, d:d + bs].set(sc[:, :, s:s + bs])
                setattr(self, name, sc.reshape(nkv, -1))

    # -- tier migration surface (ragged/tiered_store.py) -------------------
    def read_block(self, block: int):
        """Value-snapshot of one block's KV for D2H demotion:
        ``(k, v, k_scale, v_scale)`` device arrays (scales None on the
        non-quantized layout, ``v`` None for a latent entry, whose one part is
        ``k``), each a NEW functional slice of the pools.
        The snapshot is safe to materialize from another thread AFTER the
        physical block is freed and even after the pool buffers themselves
        are donated to a later forward — jax slicing captures the pool
        VALUE at call time, so the migration worker's ``np.asarray`` reads
        the snapshot, never the live (possibly reused) slots."""
        bs = self.block_size
        s = int(block) * bs
        k = self.k_pool[:, s:s + bs]
        v = None if self.v_pool is None else self.v_pool[:, s:s + bs]
        ks = vs = None
        if self.quantized:
            nkv, span = self.num_kv_heads, self.num_blocks * bs
            ks = self.k_scale.reshape(nkv, self.num_layers, span)[:, :, s:s + bs]
            vs = self.v_scale.reshape(nkv, self.num_layers, span)[:, :, s:s + bs]
        return k, v, ks, vs

    def write_block(self, block: int, k, v=None, k_scale=None, v_scale=None) -> None:
        """H2D promotion: install host-resident KV into one block's slots
        (the inverse of :meth:`read_block`, same shapes). MUST run on the
        driver thread between forwards — it replaces the pool arrays, and
        racing a forward's donation would read an invalidated buffer."""
        bs = self.block_size
        d = int(block) * bs
        for name, part in zip(self._parts(), (k, v)):
            pool = getattr(self, name)
            setattr(self, name, pool.at[:, d:d + bs].set(jnp.asarray(part, pool.dtype)))
        if self.quantized and k_scale is not None:
            nkv, span = self.num_kv_heads, self.num_blocks * bs
            for name, blk in (("k_scale", k_scale), ("v_scale", v_scale)):
                sc = getattr(self, name).reshape(nkv, self.num_layers, span)
                sc = sc.at[:, :, d:d + bs].set(jnp.asarray(blk, jnp.float32))
                setattr(self, name, sc.reshape(nkv, -1))

    def compact_slots(self, src_slots, dst_slots) -> None:
        """Device-side KV move of individual token slots ``src → dst``
        across every layer — the token-tree verification commit: an
        accepted branch's nodes were verified at their FLAT tree slots and
        must land at the sequence's canonical contiguous positions before
        decoding continues. All reads happen before any write (one gather,
        one scatter), and the tree layout guarantees dst < src with the two
        ranges disjoint, so the move is alias-safe. Eager jnp ops like
        :meth:`copy_block` — a handful of slots per verify round."""
        src = jnp.asarray(src_slots, jnp.int32).reshape(-1)
        dst = jnp.asarray(dst_slots, jnp.int32).reshape(-1)
        if src.size == 0:
            return
        self._map_parts(lambda pool: pool.at[:, dst].set(pool[:, src]))
        if self.quantized:
            nkv = self.num_kv_heads
            span = self.num_blocks * self.block_size
            for name in ("k_scale", "v_scale"):
                sc = getattr(self, name).reshape(nkv, self.num_layers, span)
                sc = sc.at[:, :, dst].set(sc[:, :, src])
                setattr(self, name, sc.reshape(nkv, -1))

    def _pool_names(self):
        names = tuple(self._parts())
        if self.quantized:
            names += ("k_scale", "v_scale")
        if self.has_index:
            names += ("index_pool", )
        if self.has_state:
            names += ("state_pool", ) if self.tail_pool is None else ("state_pool", "tail_pool")
        return names

    def pools(self):
        """The donated pool tuple the compiled forwards thread through:
        (k, v) full-precision, (k, v, k_scale, v_scale) quantized, (latent, )
        for a latent entry, (k, v, state, tails) for a model with state
        layers (the delta rule's; (k, v, state) for lightning layers), the
        pooled keys of a model with a block selection before the state's: (k,
        v, index, state)."""
        return tuple(getattr(self, name) for name in self._pool_names())

    def update(self, *pools) -> None:
        """Install the pools returned by the jitted forward (donated in/out),
        in ``pools()`` order."""
        for name, pool in zip(self._pool_names(), pools, strict=True):
            setattr(self, name, pool)

    def memory_bytes(self) -> int:
        n = sum(p.size * p.dtype.itemsize for p in self.pools()[:len(self.entry)])
        if self.quantized:
            n += 2 * self.k_scale.size * 4
        if self.has_index:  # a block's pooled keys are part of what the block holds
            n += self.index_pool.size * self.index_pool.dtype.itemsize
        return n

    def block_bytes(self) -> int:
        """Device bytes one block occupies across all layers (every part of
        the entry, scales included on the int8 layout) — the unit of the prefix cache's
        ``cow_bytes`` accounting and the MRC's capacity math."""
        return self.memory_bytes() // self.num_blocks
