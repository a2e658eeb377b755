"""Inference v2 configuration.

Analog of the reference ``inference/v2/config_v2.py`` (RaggedInferenceEngineConfig
with ``state_manager: DSStateManagerConfig`` and tensor-parallel settings).
"""

from dataclasses import dataclass, field
from typing import Optional, Tuple, Union

import jax.numpy as jnp


@dataclass
class DSStateManagerConfig:
    max_tracked_sequences: int = 128
    max_ragged_batch_size: int = 768
    max_ragged_sequence_count: int = 64
    max_context: int = 2048  # per-sequence context ceiling (blocks * block_size)
    memory_config: str = "auto"  # 'auto' sizes the KV pool from free HBM
    offload: bool = False  # reference kv_cache.py:169 offload hooks — not yet
    # the static shapes a step is padded up to, ascending, the last the limit above (None: 8, 16, 32, ... up
    # to it). Fewer buckets are fewer programs to compile and warm; a step pads further.
    token_buckets: Optional[Tuple[int, ...]] = None
    seq_buckets: Optional[Tuple[int, ...]] = None


@dataclass
class CacheTelemetryConfig:
    """``ragged.prefix_cache.telemetry`` block: the memory & KV-cache
    observability plane (``ragged/cache_telemetry.py``) — per-block
    lifecycle accounting (allocate/publish/hit/evict/free, refcount
    classes, block-age / reuse-interval / eviction-victim-age histograms,
    occupancy + fragmentation gauges) and the online SHARDS miss-ratio-curve
    estimator predicting the hit rate at {0.5x..8x} the current pool size.
    Off by default with the PR 5 zero-overhead contract: absent/disabled ⇒
    no telemetry objects anywhere, no threads, no per-block allocations —
    every hook site is one ``is not None`` check (test-enforced in
    ``tests/test_cache_telemetry.py``)."""
    enabled: bool = False
    # SHARDS key-sampling rate in (0, 1]: 1.0 tracks every chunk (exact
    # stack distances), lower rates bound memory/CPU on hot admission paths
    mrc_sample_rate: float = 0.25
    # hard cap on tracked sampled keys; past it the coldest is dropped (its
    # next access reads as a cold miss — an under-estimate, never a promise)
    mrc_max_tracked: int = 4096
    # capacity multipliers the MRC is evaluated at (x current pool blocks)
    mrc_capacity_mults: tuple = (0.5, 1.0, 2.0, 4.0, 8.0)


@dataclass
class HostTierConfig:
    """``ragged.prefix_cache.host_tier`` block: the capacity tier under the
    radix tree (``ragged/tiered_store.py``) — evicted tree-only blocks are
    DEMOTED to a pinned host block pool (async D2H through a bounded
    migration queue) instead of dropped, and a later hit on a demoted chain
    PROMOTES the blocks back to HBM ahead of prefill. Presence-enabled:
    when this block is absent (``PrefixCacheConfig.host_tier is None``) no
    host pool, no worker thread and no per-block residency state exist
    anywhere (the PR 5 zero-overhead contract, test-enforced in
    ``tests/test_tiered_store.py``). Size the pool from the MRC curve
    (``serving/mrc_hit_rate``): flat by 2x the HBM pool ⇒ leave the tier
    off; still climbing at 8x ⇒ give the host pool the capacity the curve
    says the workload wants."""
    enabled: bool = True
    # host pool capacity in blocks; 0 derives it from host_pool_bytes
    host_blocks: int = 0
    # alternative sizing: host bytes -> blocks via the HBM pool's block_bytes
    host_pool_bytes: int = 0
    # proactive-demotion watermarks on the HBM FREE fraction: when free
    # drops below `low_watermark`, cold tree-only leaves are demoted in the
    # background until free reaches `high_watermark` — demand eviction then
    # rarely has to demote inline on the admission path
    low_watermark: float = 0.10
    high_watermark: float = 0.25
    # bounded migration queue depth (the ResilientSaver discipline: a slow
    # tier back-pressures into plain drops, never into unbounded memory)
    queue_depth: int = 8
    # optional disk tier: directory for spilled host blocks (None = off).
    # Block files are checksummed and tracked in a manifest; corrupt or
    # missing files read as misses, never as wrong KV.
    disk_path: object = None
    # disk tier capacity in blocks (ignored when disk_path is None)
    disk_blocks: int = 256


@dataclass
class PrefixCacheConfig:
    """``ragged.prefix_cache`` block: block-granular KV reuse across requests
    (PagedAttention sharing + RadixAttention LRU tree). Off by default —
    when enabled, identical outputs are guaranteed (greedy parity asserted
    in ``tests/test_prefix_cache.py``) and shared-prefix workloads skip the
    cached portion of prefill."""
    enabled: bool = False
    # leaf-eviction policy when the block pool runs dry ('lru' only for now)
    eviction: str = "lru"
    # minimum hit size (in blocks, COW tail included) worth taking: tiny
    # hits fragment the pool for negligible prefill savings
    min_hit_blocks: int = 1
    # memory & cache observability plane (block lifecycle + MRC estimator);
    # rides the prefix cache because the radix tree is what gives block
    # reuse a lifecycle worth accounting
    telemetry: CacheTelemetryConfig = field(default_factory=CacheTelemetryConfig)
    # host-memory (+ optional disk) capacity tier under the radix tree:
    # presence-enabled — None means no tier objects exist anywhere
    host_tier: object = None  # Optional[HostTierConfig]


@dataclass
class SpeculativeConfig:
    """``ragged.speculative`` block: speculative decoding over the ragged
    plane (draft K tokens cheaply, verify them in ONE batched ragged
    forward, commit the longest prefix the target model's own argmax
    agrees with, roll the rejected tail back through
    ``DSStateManager.rollback_to``). Off by default — greedy parity is
    unconditional when enabled (asserted in ``tests/test_speculative.py``),
    so the only tradeoff is throughput: larger ``k`` amortizes more host
    round-trips per accepted run but wastes more verify compute when the
    acceptance rate is low."""

    mode: str = "off"  # 'off' | 'ngram' (self-speculative prompt lookup) | 'draft_model'
    k: int = 4         # draft tokens verified per speculative step (per branch)
    # token-tree verification: candidate branches verified per round (1 =
    # linear, the PR 9 behavior). Each extra branch costs k verify tokens
    # and any ONE matching lifts the round's acceptance — the lever for
    # workloads where a single n-gram guess is weak. Greedy only: sampled
    # requests fall back to one linear branch (rejection-sampling verify).
    tree_width: int = 1
    # spec-burst backoff: after this many CONSECUTIVE zero-accept verify
    # rounds a request stops drafting (its verify FLOPs were pure waste)
    # and rides the plain multi-step decode burst; 0 disables backoff
    backoff_after: int = 8
    # while backed off, re-probe (draft again) every this many rounds so a
    # stream that BECOMES repetitive gets speculation back
    reprobe_every: int = 32
    # ngram drafter: shortest suffix n-gram worth matching (higher = fewer,
    # better-grounded drafts) and the longest tried first
    min_match: int = 2
    max_ngram: int = 4
    # ngram drafter: search window over the sequence's own stream (0 = the
    # whole stream). Bounded by default: the scan runs per sequence per
    # verify round in the hottest serving loop, and an unbounded window
    # would make steady-state decode O(context) on long-context requests;
    # the recent window is also where the live repetition signal is.
    max_history: int = 256
    # draft_model mode: a small same-tokenizer InferenceEngineV2 (object
    # handle, not serialized config — built by the caller)
    draft_engine: object = None

    @property
    def enabled(self) -> bool:
        return self.mode != "off"


@dataclass
class DiffusionConfig:
    """``ragged.diffusion`` block: how a model with ``diffusion_block_size``
    unmasks a block (the block length itself is the model's: it is part of
    its attention mask). Greedy: a position's token is its argmax and its
    confidence the float32 softmax probability of that token.

    ``remasking`` ``low_confidence_static``: denoise forward ``i`` of a block
    unmasks the ``B // denoising_steps`` (one more in the first ``B %
    denoising_steps`` forwards) masked positions of highest confidence;
    ``low_confidence_dynamic``: every masked position whose confidence is
    over ``confidence_threshold``, and at least the most confident one. The
    last of the ``denoising_steps`` forwards unmasks whatever is left. The K/V
    of a block's final ids are written by the next block's first denoise
    forward, which is fed both blocks, or, for the last block of a ``decode``
    call, by one forward more that writes K/V alone: nothing here chooses."""

    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if self.remasking not in ("low_confidence_static", "low_confidence_dynamic"):
            raise ValueError(f"remasking {self.remasking!r}: 'low_confidence_static' or 'low_confidence_dynamic'")
        if self.denoising_steps < 1:
            raise ValueError(f"denoising_steps must be positive, got {self.denoising_steps}")


@dataclass
class ModulesConfig:
    """Per-op implementation selection (reference ``modules/heuristics.py``
    config surface). Each slot is ``"auto"`` (heuristic pick), a registered
    implementation name, or ``{"name": ..., "implementation_config": {...}}``
    — resolved through the interface registries in
    ``modules/heuristics.build_modules`` at engine construction."""
    attention: object = "auto"
    linear: object = "auto"
    embedding: object = "auto"
    unembed: object = "auto"
    norm: object = "auto"


@dataclass
class RaggedInferenceEngineConfig:
    tensor_parallel_degree: int = 1
    kv_block_size: int = 64
    # pool size in blocks; 0/'auto' sizes the pool from the device's free
    # HBM after params (memory_config fraction below), reference
    # DSStateManagerConfig.memory_config semantics
    num_kv_blocks: object = "auto"
    kv_dtype: object = jnp.bfloat16
    # fraction of post-params free HBM given to the KV pool in auto mode
    kv_memory_fraction: float = 0.8
    # a blocking step's tokens: False cuts them to the live rows on the device before the fetch, an eager slice
    # that XLA compiles once per (bucket, rows), so a replica warms bucket x rows of them; True fetches the padded
    # bucket (a token a row) and cuts it on the host, and no slice program exists. Logits are cut on the device
    # either way: a row of them is the vocabulary wide.
    cut_rows_on_host: bool = False
    state_manager: DSStateManagerConfig = field(default_factory=DSStateManagerConfig)
    # prefix-cache subsystem (refcounted COW block sharing + radix reuse)
    prefix_cache: PrefixCacheConfig = field(default_factory=PrefixCacheConfig)
    # speculative decoding (n-gram self-drafting or a draft model, batched
    # K-token verification with refcount-aware rollback)
    speculative: SpeculativeConfig = field(default_factory=SpeculativeConfig)
    # unmasking schedule of a block-diffusion model; read by no other model
    diffusion: DiffusionConfig = field(default_factory=DiffusionConfig)
    use_pallas_kernels: str = "auto"  # 'auto' | 'never' | 'always'
    # weight-only int8 (per-output-channel scales): halves the decode weight
    # stream, which is the bandwidth-bound term at serving batch sizes
    # weight-only quantization for the serving weight stream:
    # False | True (int8) | 8 | 4 (packed nibbles — quarter the bf16 bytes)
    quantize_weights: Union[bool, int] = False
    # pluggable module layer: which implementation serves each op slot
    modules: ModulesConfig = field(default_factory=ModulesConfig)
