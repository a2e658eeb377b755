"""InferenceEngineV2 — continuous-batching ragged inference engine.

Analog of the reference ``inference/v2/engine_v2.py:30`` (``put:107``,
``query:153``, ``can_schedule:179``, ``flush:228``, ``serialize:237``). The
serving loop is host-driven exactly like the reference's (MII calls put() with
whatever mix of prefill chunks and decode steps the scheduler admitted); the
device side is one jitted ragged forward per shape-bucket with the KV pools
donated through, so steady-state decode reuses a single compiled program and
the only host→device traffic is the packed batch descriptor arrays.
"""

import collections
import functools
import time
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...monitor.flight import get_flight_recorder
from ...monitor.goodput import get_goodput
from ...monitor.health import get_health
from ...monitor.memory import get_memory, tree_device_bytes
from ...monitor.metrics import get_metrics
from ...monitor import scopes
from ...monitor.trace import (NULL_SPAN, get_tracer, pop_compile_source,
                              push_compile_source)
from ...moe.grouped import merge_routing_stats
from ...ops.pallas.kda import KERNEL_NAMES as KDA_KERNEL_NAMES, TILE as KDA_TILE
from ...ops.pallas.lightning import KERNEL_NAMES as LIGHTNING_KERNEL_NAMES, TILE as LIGHTNING_TILE
from ...ops.pallas.mamba2 import (KERNEL_NAMES as MAMBA_KERNEL_NAMES, TILE as MAMBA_TILE, TILE_BLOCK as MAMBA_TILE_BLOCK,
                                  step_operand_bytes as mamba_step_operand_bytes)
from ...ops.pallas.paged_attention import decode_kv_counts, kernel_choice, tiled_kv_counts
from ...utils.logging import log_dist
from .config_v2 import RaggedInferenceEngineConfig
from .model_implementations.sparse_index import index_tile, keys_scored, scores_by_kernel
from .model_implementations.flat_model import (expanded_batch, expanded_plan, expanded_slots, expanded_workspace_bytes,
                                                ragged_forward)
from .ragged.ragged_manager import DSStateManager
from .ragged.ragged_wrapper import RaggedBatchWrapper, next_bucket, packed_len, unpack_state_slots
from .scheduling_utils import SchedulingError, SchedulingResult


def _serving_compile_scope(method):
    """Label this thread's XLA compiles as ``serving`` for the duration of
    a forward — the compile listener (monitor/trace.py) attributes each
    compile event to the thread-local source, so a serving engine compiling
    from a replica thread counts under ``serving/compile_events``, not
    ``train/`` (the pre-goodput drift). Pushed only when something is
    listening: one enabled check otherwise."""

    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        if not (self.goodput_ledger is not None or get_metrics().enabled
                or get_tracer().enabled):
            return method(self, *args, **kwargs)
        prev = push_compile_source("serving")
        try:
            return method(self, *args, **kwargs)
        finally:
            pop_compile_source(prev)

    return wrapped


def _observe(sp, args, held=None):
    """What a step function does only because its span ``sp`` is live, under a
    child span of its own, ``serving/engine_observe``; nothing, and no span,
    for ``NULL_SPAN``. ``args()`` gives span arguments. A step function calls
    this twice. First between ``engine_dispatch`` and ``engine_fetch``, with
    what needs no output of the device, while the device runs the program: the
    arguments come back to be ``held``. Then last in the step span, with what
    the fetch brought: ``held`` and these are set on ``sp`` in ONE ``set_args``."""
    if sp is NULL_SPAN:
        return None
    with get_tracer().span("serving/engine_observe", tid="serving"):
        if held is None:
            return args()
        sp.set_args(**held, **args())


def _fetch(sp, out, watched):
    """``(out, [])`` with ``out`` on the host; for a live step span ``sp``,
    ``(out, [array, ...])`` with the ``watched`` arrays of the same program
    beside it, brought in the same transfer: one wait for the device."""
    if sp is NULL_SPAN or not watched:
        return np.asarray(out), []
    out, *seen = jax.device_get((out, *watched))
    return out, seen


def _cut_and_fetch(sp, out, n: int, watched, block: bool, on_host: bool):
    """``(out[:n], seen)``: a step's result cut to its live rows and, with
    ``block``, brought to the host (``_fetch``). ``on_host`` fetches the
    padded bucket and cuts there, so that no (bucket, n) slice program exists;
    otherwise the cut is an eager slice on the device before the fetch."""
    if block and on_host:
        out, seen = _fetch(sp, out, watched)
        return out[:n], seen
    return _fetch(sp, out[:n], watched) if block else (out[:n], [])


class InferenceEngineV2:

    def __init__(self, model, config: Optional[RaggedInferenceEngineConfig] = None, params=None):
        """``model``: framework model object (e.g. ``models.llama2()``);
        ``params``: trained param pytree (initialized randomly if omitted)."""
        self.config = config or RaggedInferenceEngineConfig()
        self.module = model
        self.model_config = model.config
        mc, ic = self.model_config, self.config

        if ic.use_pallas_kernels == "auto":
            self._use_pallas = jax.default_backend() == "tpu"
        else:
            self._use_pallas = ic.use_pallas_kernels == "always"

        # pluggable module layer (reference FastGen's DSModule registry +
        # heuristics): config→implementation selection happens HERE, once;
        # every compiled bucket traces through the same module set
        from .modules.heuristics import build_modules

        self._modules = build_modules(mc, ic, use_pallas=self._use_pallas)
        self._moe = self._modules.get("moe")  # None: a dense model
        # whether a step's result carries an int32 of counts made in the program: the routing's, or what a
        # learned block selection's work lists fetched
        self._counts = self._moe is not None or int(getattr(mc, "sparse_topk", 0) or 0) > 0
        # what every serving program of this engine is jitted with. A model
        # with experts on the TPU compiles with XLA's scoped VMEM raised to
        # 64 MiB: with the default 16 MiB, a put program of 64 tokens x 8 rows
        # with two or more layers HUNG the chip (PR 27, TPU v5e, jax 0.9.0:
        # any paged-attention grid followed by moe_gmm followed by the next
        # layer's attention; 32- and 128-token programs ran, one layer ran,
        # either kernel beside the other's jnp form ran). XLA keeps the
        # kernels' small operands in VMEM beside their scoped region and at
        # that size the two collide; with the larger scoped region it has not
        # been seen. Dense models' programs are compiled as before.
        self._jit_options = {}
        if self._moe is not None and self._use_pallas and jax.default_backend() == "tpu":
            self._jit_options = {"compiler_options": {"xla_tpu_scoped_vmem_limit_kib": 65536}}

        if params is None:
            params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
        for m in self._modules.values():
            # one-time parameter-layout transforms (e.g. the int8 linear
            # implementation quantizes the weight stream)
            params = m.transform_params(params)
        self.params = params

        bs = ic.kv_block_size
        # a block-diffusion model: ``decode`` advances whole blocks of this many
        # tokens by masked diffusion (0: a causal model, one token a row a step)
        self._block = int(getattr(mc, "diffusion_block_size", 0) or 0)
        if self._block:
            if bs % self._block:
                raise ValueError(f"kv_block_size {bs} must hold whole diffusion blocks of {self._block}: the "
                                 "prefix cache hashes KV blocks, and one may not end inside a block")
            if getattr(ic.speculative, "enabled", False):
                raise NotImplementedError(
                    f"speculative decoding of a model with diffusion_block_size={self._block}: a draft is "
                    "verified one causal token at a time, and this model has no causal next token")
        # what one token caches in one layer: per-head K and V, or one latent entry
        self._kv_entry = tuple(getattr(mc, "kv_entry", ((mc.num_kv_heads, mc.head_dim), ) * 2))
        self._latent = bool(getattr(mc, "latent_attention", False))
        if self._latent and getattr(ic.speculative, "enabled", False):
            raise NotImplementedError(
                "speculative decoding of a model with latent attention: a verify step of k + 1 tokens a row and the "
                "token-tree mask have not been shown equal to the reference over a latent pool")
        # a model with state layers (linear attention): those layers cache no token and hold a fixed state a
        # sequence instead; the K/V pool, the block tables and the attention counts are the other layers' alone
        self._state_layers = tuple(getattr(mc, "state_layers", ()))
        self._kv_layers = tuple(getattr(mc, "kv_layers", range(mc.num_layers)))
        if self._state_layers:
            if getattr(ic.speculative, "enabled", False):
                raise NotImplementedError(
                    "speculative decoding of a model with a recurrent state layer: a rejected draft is rewound, and "
                    "the state has consumed the draft and keeps no snapshot to return to")
            if np.dtype(ic.kv_dtype).itemsize == 1:
                raise NotImplementedError("an int8 KV cache beside a recurrent state layer: the program threads "
                                          "either the scales or the state pools, and the state is float32 as stated")
        # a model with a learned block-sparse selection: its softmax layers cache pooled keys beside K and V
        # and both paged kernels read by what the queries select; the program counts what was fetched
        self._index_entry = tuple(getattr(mc, "index_entry", ()))
        self._sparse = bool(self._index_entry)
        self._lightning = int(getattr(mc, "lightning_num_heads", 0) or 0) > 0
        self._mamba = int(getattr(mc, "mamba_num_heads", 0) or 0) > 0
        if self._sparse:
            # whether the indexer's kernel runs, as ``ragged_forward`` decides it: on the chip, or its body interpreted
            self._index_kernels = (self._use_pallas and jax.default_backend() == "tpu", bool(getattr(
                self._modules["attention"], "implementation_config", {}).get("interpret", False)))
            if getattr(ic.speculative, "enabled", False):
                raise NotImplementedError(
                    "speculative decoding of a model with pooled keys (a learned block selection): a rejected "
                    "draft's keys are pooled into entries that the kept tokens share, and nothing rewinds them")
            if np.dtype(ic.kv_dtype).itemsize == 1:
                raise NotImplementedError("an int8 KV cache beside pooled keys (a learned block selection): the "
                                          "indexer scores pooled keys of the compute type, and the selected lists "
                                          "carry no scales")
            if bs != mc.sparse_block_size:
                raise ValueError(f"kv_block_size {bs} of a model that selects blocks of {mc.sparse_block_size} "
                                 "tokens: the KV block is the selection's block")
        max_context = ic.state_manager.max_context
        model_max = getattr(mc, "max_seq_len", None)
        if model_max is not None and max_context > model_max:
            # past max_seq_len a learned-position model would silently clamp
            # its position gather — refuse to track context beyond the model
            log_dist(f"clamping max_context {max_context} -> model max_seq_len {model_max}", ranks=[0])
            max_context = model_max
        self._max_context = max_context
        self._max_blocks_per_seq = -(-max_context // bs)
        # resolve 'auto' into a LOCAL count (the caller's config object is
        # not mutated: a reused config re-measures for the next engine)
        if ic.num_kv_blocks in ("auto", 0, None):
            self.num_kv_blocks = self._auto_kv_blocks(mc, ic, max_context)
        else:
            self.num_kv_blocks = int(ic.num_kv_blocks)
        self.state_manager = DSStateManager(
            len(self._kv_layers), mc.num_kv_heads, mc.head_dim,
            max_tracked_sequences=ic.state_manager.max_tracked_sequences,
            num_blocks=self.num_kv_blocks, block_size=bs, dtype=ic.kv_dtype,
            prefix_cache_config=ic.prefix_cache, kv_entry=self._kv_entry,
            state_entry=tuple(getattr(mc, "state_entry", ())), state_layers=len(self._state_layers),
            index_entry=self._index_entry)
        if self._block and self.state_manager.prefix_cache is not None:
            self.state_manager.prefix_cache.token_quantum = self._block
        self.batch = RaggedBatchWrapper(
            max_ragged_batch_size=ic.state_manager.max_ragged_batch_size,
            max_ragged_sequence_count=ic.state_manager.max_ragged_sequence_count,
            max_blocks_per_seq=self._max_blocks_per_seq, block_size=bs,
            token_buckets=ic.state_manager.token_buckets, seq_buckets=ic.state_manager.seq_buckets)

        self._compiled: Dict[Tuple[int, int, Optional[str]], object] = {}
        self._ahead: Dict[tuple, object] = {}  # program key -> the future of compile_ahead() that fills _compiled[key]
        self._kernel_labels: Dict[Tuple[int, int, bool], str] = {}  # (tokens, rows, horizon) -> span label, see _kernel_of
        # (window or None, layers that attend in it), for _kv_span_args and _tiled_kv_span_args
        self._kv_windows = list(collections.Counter(mc.layer_window(l) for l in self._kv_layers).items())
        # speculative-decoding lifetime totals (two int adds per verify
        # step; the gauge feeding off them only updates when metrics are on)
        self._spec_totals = {"drafted": 0, "accepted": 0}
        # HBM attribution (monitor/memory.py): this engine's params + KV
        # block pool enter the process-wide ledger. Weakly owned — a
        # discarded engine self-prunes from the registry. A draft engine
        # referenced by our speculative config re-files its bytes under
        # `spec_draft_engine` so the decomposition names the sidecar cost.
        self._memory_role = None
        get_memory().register(f"engine_v2-{id(self)}",
                              lambda eng: eng._memory_sections(), self)
        draft = getattr(ic.speculative, "draft_engine", None)
        if draft is not None and hasattr(draft, "set_memory_role"):
            draft.set_memory_role("spec_draft_engine")
        # goodput ledger + recompile sentinel (monitor/goodput.py): the
        # owning replica (or a direct caller) attaches a serving ledger
        # post-warmup via `goodput_ledger`; `_gp_warmed` is this engine's
        # own warmup boundary — compiled-cache misses after it are flagged
        # by the sentinel. All None/False by default: one attribute check
        # per forward when the plane is off.
        self.goodput_ledger = None
        self._gp_warmed = False
        self._gp_last_uids = None
        self.gp_rid_resolver = None
        # tenant metering (serving/metering.py): the owning replica attaches
        # the gateway's TenantMeter via `set_tenant_meter`, which wires one
        # per-engine EngineMeterView into the block-lifecycle hooks. None by
        # default — no stamp arrays exist and every hook site below the
        # state manager stays one attribute check.
        self._tenant_meter = None
        # live-health plane: serving heartbeats (`serving` watchdog source,
        # armed per forward) + a /healthz section. One boolean per call when
        # the plane is off.
        self._health = get_health()
        if self._health.enabled:
            import weakref

            # the plane is a process-global singleton and this engine has no
            # destroy(): a strong closure would pin the whole KV cache (and
            # keep /healthz reporting a dead engine) after the engine is
            # discarded — hold a weakref and self-unregister once collected
            ref = weakref.ref(self)

            def _serving_state():
                eng = ref()
                if eng is None:
                    get_health().set_state_provider("serving", None)
                    return {"engine": "collected"}
                return {"tracked_sequences": eng.state_manager.n_tracked_sequences,
                        "free_blocks": eng.free_blocks,
                        "available_blocks": eng.available_blocks}

            self._health.set_state_provider("serving", _serving_state)
            if self.state_manager.cache_telemetry is not None:
                # cache observability rides the same weakref discipline:
                # MRC + refcount-class + occupancy gauges on /metrics, a
                # full telemetry snapshot in every forensic dump. Names and
                # labels are per-engine — a multi-replica gateway must show
                # every replica's curve, not whichever registered last —
                # and a collected engine self-unregisters its providers.
                tag = f"cache_telemetry-{id(self):x}"
                labels = {"engine": f"{id(self):x}"}

                def _cache_rows():
                    eng = ref()
                    tel = eng.state_manager.cache_telemetry if eng is not None else None
                    if tel is None:
                        get_health().set_gauge_provider(tag, None)
                        return []
                    return tel.gauge_rows(labels=labels)

                def _cache_dump():
                    eng = ref()
                    tel = eng.state_manager.cache_telemetry if eng is not None else None
                    if tel is None:
                        get_health().set_dump_provider(tag, None)
                        return {"engine": "collected"}
                    return tel.snapshot()

                self._health.set_gauge_provider(tag, _cache_rows)
                self._health.set_dump_provider(tag, _cache_dump)
        log_dist(
            f"InferenceEngineV2 ready: blocks={self.num_kv_blocks}x{bs} "
            f"kv={self.state_manager.kv_cache.memory_bytes()/2**20:.0f}MiB "
            f"max_batch_tokens={ic.state_manager.max_ragged_batch_size} pallas={self._use_pallas}", ranks=[0])

    # ------------------------------------------------------------------
    def _auto_kv_blocks(self, mc, ic, max_context: int) -> int:
        """Size the KV pool from the device's free HBM after params
        (resolves the round-2 'auto sizing TODO against HBM stats'):
        blocks = kv_memory_fraction x free / bytes_per_block, clamped to at
        least one max-context sequence and to the tracked-sequence budget.
        Free is what the params AND the programs' own workspace leave
        (``flat_model.expanded_workspace_bytes``: a model with latent attention
        alone has one). Without memory stats (CPU) the demand is capped at a
        conservative host budget instead of allocating the full
        tracked-sequence demand."""
        import numpy as _np

        bs = ic.kv_block_size
        dt_bytes = _np.dtype(ic.kv_dtype).itemsize  # accepts "int8" and jnp dtypes alike
        kv_layers = len(getattr(mc, "kv_layers", range(mc.num_layers)))  # the layers that cache a token's entry
        per_block = kv_layers * sum(h * w for h, w in self._kv_entry) * bs * dt_bytes
        if getattr(mc, "index_entry", ()):  # a block's pooled keys: one of (heads, width) every stride tokens
            stride, heads, width = mc.index_entry
            per_block += kv_layers * heads * width * (bs // stride) * dt_bytes
        if dt_bytes == 1:  # int8 KV: absmax scales ride along, fp32 per (token, head)
            per_block += 2 * mc.num_layers * mc.num_kv_heads * bs * 4
        min_blocks = -(-max_context // bs) + 1
        want_blocks = ic.state_manager.max_tracked_sequences * -(-max_context // bs)
        free = None
        try:
            stats = jax.devices()[0].memory_stats()
            if stats and "bytes_limit" in stats:
                param_bytes = sum(int(_np.prod(x.shape)) * x.dtype.itemsize
                                  for x in jax.tree_util.tree_leaves(self.params))
                # ... and what the largest program keeps beside the pool while it runs: the per-head K and V of
                # latent attention's long rows (zero for every other model), which the pool must not be given
                used = max(stats.get("bytes_in_use", 0), param_bytes) + expanded_workspace_bytes(
                    mc, ic.state_manager.max_ragged_batch_size, -(-max_context // bs), bs, dt_bytes) \
                    + self._state_bytes_ahead(mc, ic, dt_bytes) + self._index_bytes_ahead(mc, ic, max_context)
                free = max(0, int(stats["bytes_limit"]) - used)
        except Exception:
            free = None
        if free is None:
            # stats unavailable (CPU backend): cap the pool at ~2GiB so an
            # unconfigured engine cannot demand hundreds of GB of host RAM
            cap = max(min_blocks, (2 * 2**30) // per_block)
            return max(min_blocks, min(want_blocks, cap))
        blocks = int(free * ic.kv_memory_fraction) // per_block
        blocks = max(min_blocks, min(blocks, want_blocks))
        log_dist(f"auto KV pool: {blocks} x {bs}-token blocks "
                 f"({blocks * per_block / 2**20:.0f}MiB of {free / 2**20:.0f}MiB free)", ranks=[0])
        return blocks

    def _state_bytes_ahead(self, mc, ic, dt_bytes: int) -> int:
        """What a model with state layers takes BEFORE the K/V pool gets its
        share of what the weights leave: the state pools (one slot a tracked
        sequence a state layer) and what the largest ``put`` program keeps of
        a linear layer's operands while it runs (``kda_chunks``), counted in
        float32 arrays of ``[tokens, heads, width]``: the tiles' ``o`` at the
        static bound of tiles (a token budget of tiles plus a partial tile a
        row); eight of the token budget (the five operands a tile takes, flat,
        ``o`` back in the flat order, and the blocks of tiles laid one at a
        time, which no longer exist all at once: PR 44); and for the rows fed
        one token, which take the recurrent step, 32 of the rows (a row's ``a,
        k, q`` columns at 16 lanes a head are 16 of them, the rest what XLA
        lays them from, ``v`` and ``b``, and the step's ``o``). 320 MB at 512
        tokens and 128 rows of 64 heads of 128, where the compiled program
        holds 68 (a v5e, PERF.md section 6, PR 44). 0 for every other model."""
        state_layers = len(getattr(mc, "state_layers", ()))
        if not state_layers:
            return 0
        sm = ic.state_manager
        if len(mc.state_entry) == 1:  # lightning layers: the state alone, no convolution tail
            # the slots, and the chunk scan's tiles of q, k, v and o in float32 (``lightning_chunks``: a token
            # budget of tiles and a partial tile a row) beside their flat forms
            (h, dk, dv), = mc.state_entry
            tokens = 2 * (sm.max_ragged_batch_size + LIGHTNING_TILE * sm.max_ragged_sequence_count)
            return sm.max_tracked_sequences * state_layers * h * dk * dv * 4 + tokens * h * (3 * dk + dv) * 4
        (h, dk, dv), (taps, channels) = mc.state_entry
        slot = state_layers * (h * dk * dv * 4 + taps * channels * dt_bytes)
        if self._mamba:  # state-space layers: (heads, head width, state width)
            # the slots; of the largest ``put`` the convolution's float32 output, what the tiles are laid from (dt x,
            # dt A, B and C a token) and ``y`` back in the flat order, a block of tiles beside them
            # (``mamba2_chunks``); of the rows fed one token what the recurrent step is laid a row: ONE tile of
            # ``dt x`` and one of ``y`` a grid step (a head a lane; one step a row where a row's state fits VMEM, as
            # at the published widths) and the step's rows of B, C and the decays
            tokens = 4 * sm.max_ragged_batch_size + 4 * MAMBA_TILE_BLOCK * MAMBA_TILE
            rows = sm.max_ragged_sequence_count * mamba_step_operand_bytes(h, mc.mamba_n_groups, dk, dv)
            return sm.max_tracked_sequences * slot + tokens * (channels + h * dk) * 4 + rows
        tile_tokens = sm.max_ragged_batch_size + KDA_TILE * sm.max_ragged_sequence_count
        tokens = tile_tokens + 8 * sm.max_ragged_batch_size + 32 * sm.max_ragged_sequence_count
        return sm.max_tracked_sequences * slot + tokens * h * max(dk, dv) * 4

    def _index_bytes_ahead(self, mc, ic, max_context: int) -> int:
        """What a model with a learned block selection keeps beside the pools
        while its largest ``put`` program runs: the XLA form's scores of one
        pass (``sparse_index._SCORE_BYTES``, twice: the softmax beside them;
        the kernel's program holds less, two planes of block scores a tile
        and the rows' pooled keys twice, and is given the same room), the
        selection a token a kv head a block with the mask the tiled kernel
        takes with every (tile, block) pair (bfloat16, 16 sublanes), and the
        pooled keys gathered a tile. 0 for every other model."""
        if not getattr(mc, "index_entry", ()):
            return 0
        from .model_implementations.sparse_index import _SCORE_BYTES

        sm = ic.state_manager
        T, S = sm.max_ragged_batch_size, sm.max_ragged_sequence_count
        blocks = -(-max_context // ic.kv_block_size)
        stride, heads, width = mc.index_entry
        tiles = -(-T // 128) + S + 1
        mask = tiles * blocks * 16 * 128 * 2
        pooled = tiles * blocks * (ic.kv_block_size // stride) * heads * width * 2
        return 2 * _SCORE_BYTES + 2 * mask + 3 * T * heads * blocks + pooled

    def can_schedule(self, uids: Iterable[int], lengths: Iterable[int]) -> SchedulingResult:
        """Admission control (reference ``engine_v2.py:179``): sequence,
        token and KV-block budgets for the proposed batch."""
        uids, lengths = list(uids), list(lengths)
        cur_len = len(uids)
        tokens = sum(lengths)
        sm = self.config.state_manager

        if len(set(uids)) != len(uids):
            # a uid twice in one batch would pack both chunks at the same
            # positions and corrupt the KV cache — reject at admission
            return SchedulingResult.BatchSequenceLimitExceeded
        if cur_len > sm.max_ragged_sequence_count:
            return SchedulingResult.BatchSequenceLimitExceeded
        n_new = sum(1 for u in uids if self.state_manager.get_sequence(u) is None)
        if self.state_manager.n_tracked_sequences + n_new > sm.max_tracked_sequences:
            return SchedulingResult.EngineSequenceLimitExceeded
        if tokens > sm.max_ragged_batch_size:
            return SchedulingResult.TokenLimitExceeded

        bs = self.config.kv_block_size
        blocks_needed = 0
        for u, n in zip(uids, lengths):
            seq = self.state_manager.get_sequence(u)
            total = n + (seq.seen_tokens if seq is not None else 0)
            if total > self._max_context:
                return SchedulingResult.KVCacheLimitExceeded
            # clamp per-sequence demand at zero: a sequence holding excess
            # blocks must not mask OTHER sequences' demand against the pool
            blocks_needed += max(0, -(-total // bs)
                                 - (seq.cur_allocated_blocks if seq is not None else 0))
        # budget against free + evictable: a warm prefix cache keeps the free
        # list near empty by design, and allocation evicts LRU tree-only
        # blocks on demand
        if blocks_needed > self.state_manager.available_blocks:
            return SchedulingResult.KVCacheLimitExceeded
        return SchedulingResult.Success

    # ------------------------------------------------------------------
    def put(self, batch_uids: List[int], batch_tokens: List[np.ndarray], do_checks: bool = True,
            sample: Optional[str] = None, block: bool = True, sampling=None) -> np.ndarray:
        """Run one ragged forward (reference ``put:107``). ``batch_tokens[i]``
        are the new tokens of sequence ``batch_uids[i]`` (whole prompt for
        prefill, one token for decode). Returns last-token logits
        [len(batch_uids), vocab] — or, with ``sample='greedy'``, the argmax
        token ids [len(batch_uids)] sampled ON DEVICE, so only a few bytes
        travel back to the host per step (the serving loop's steady-state
        transfer instead of the full vocab row per sequence).

        ``block=False`` returns the device array without a host fetch, so a
        scheduler that doesn't need the values (e.g. speculative admission)
        can pipeline several steps into the device queue.

        ``sampling``: per-sequence :class:`SamplingParams` list (None
        entries = greedy rows). With any temperature > 0 the returned
        tokens are drawn from the tempered/top-p distribution ON DEVICE
        (``sampling.sample_tokens``), keyed by (seed, token position) so a
        fixed seed replays the same stream; all-greedy lists keep the
        byte-identical argmax program.

        ``sample='probe'`` (a model with a learned block selection; a check's
        reading, not a serving mode): ``(logits, (positions, selection,
        attention output))`` of the live rows, the three as
        ``ragged_forward(probe=True)`` lays them."""
        hb = self._health
        # normalize ONCE, before any breadcrumb math: both arguments may be
        # single-pass iterables, and _put's re-asarray of the converted rows
        # is then a free no-op
        batch_uids = list(batch_uids)
        batch_tokens = [np.asarray(t, np.int32).reshape(-1) for t in batch_tokens]
        gl = self.goodput_ledger
        if gl is None and not hb.enabled:
            return self._put(batch_uids, batch_tokens, do_checks, sample, block, sampling)
        if gl is not None:
            self._gp_last_uids = batch_uids
            gp_cat = ("prefill_active" if any(t.size > 1 for t in batch_tokens)
                      else "decode_active")
            t_gp = time.perf_counter()
        if hb.enabled:
            # operation-style heartbeat: `serving` is watched exactly while a
            # forward is in flight, so a wedged device call trips the watchdog
            hb.begin("serving")
            get_flight_recorder().record("serving", "put", seqs=len(batch_uids),
                                         tokens=int(sum(t.size for t in batch_tokens)))
        try:
            return self._put(batch_uids, batch_tokens, do_checks, sample, block, sampling)
        finally:
            if hb.enabled:
                hb.end("serving")
            if gl is not None:
                gl.book(gp_cat, time.perf_counter() - t_gp)

    @_serving_compile_scope
    def _put(self, batch_uids, batch_tokens, do_checks, sample, block, sampling=None):
        tr = get_tracer()
        reg = get_metrics()
        t0 = time.perf_counter() if reg.enabled else 0.0
        batch_tokens = [np.asarray(t, np.int32).reshape(-1) for t in batch_tokens]
        if any(t.size == 0 for t in batch_tokens):
            # an empty chunk would alias the PREVIOUS row's last_idx in the
            # packed batch and silently return the wrong sequence's logits
            raise ValueError("put(): zero-length token chunk "
                             f"(uids {[u for u, t in zip(batch_uids, batch_tokens) if t.size == 0]})")
        # classify prefill vs decode from the PRE-trim sizes: a cache hit can
        # trim a repeat prompt down to one token, but it is still a prefill
        # step (and the hit is exactly what makes it worth recording)
        had_prefill = any(t.size > 1 for t in batch_tokens)
        if self._block > 1 and any(t.size % self._block for t in batch_tokens):
            raise ValueError(f"put(): chunks of {[int(t.size) for t in batch_tokens]} tokens; a model with "
                             f"diffusion_block_size={self._block} is fed whole blocks (decode's first_tokens "
                             "take a prompt's last partial block)")
        # span name as a two-literal conditional so check_goodput_taxonomy
        # can map both
        with tr.span("serving/prefill" if had_prefill else "serving/decode_step",
                     tid="serving") as sp:
            with tr.span("serving/engine_batch", tid="serving"):
                if do_checks:
                    result = self.can_schedule(batch_uids, [t.size for t in batch_tokens])
                    if result is not SchedulingResult.Success:
                        raise SchedulingError(result)

                self.batch.clear()
                descs = []
                for i, (uid, toks) in enumerate(zip(batch_uids, batch_tokens)):
                    seq = self.state_manager.get_sequence(uid)
                    if seq is None:
                        # cache-hit prefill path: a new sequence's first chunk is
                        # matched against the radix tree; the hit's blocks arrive
                        # shared (seen_tokens pre-seeded) and only the uncached
                        # suffix is actually fed/computed
                        seq, skip = self._create_with_prefix(uid, toks)
                        if skip:
                            toks = batch_tokens[i] = toks[skip:]
                    self.state_manager.note_tokens(seq, toks)
                    self.state_manager.allocate_blocks(seq, toks.size)
                    seq.pre_forward(toks.size)
                    self.batch.insert_sequence(seq, toks)
                    descs.append(seq)
                rb = self.batch.finalize()
            t_bucket, s_bucket = rb.token_ids.shape[0], rb.block_tables.shape[0]

            from .sampling import all_greedy, pack_sampling

            kv = self.state_manager.kv_cache
            with tr.span("serving/engine_dispatch", tid="serving") as sd:
                n_programs = len(self._compiled)
                if sampling is not None and not all_greedy(sampling):
                    if sample is None:
                        # sample=None means "give me logits" — silently returning
                        # sampled token ids instead would hand a logits consumer an
                        # int32 vector
                        raise ValueError("put(sample=None) returns logits; pass sample='greedy' "
                                         "with a sampling list to draw tokens on device")
                    # sampled rows draw on device (greedy rows argmax via temp 0);
                    # sample='greedy' callers without sampling keep the original
                    # compiled program byte-for-byte
                    mode = "sample"
                    fn = self._get_compiled(t_bucket, s_bucket, "sample")
                    samp_f, seeds = pack_sampling(sampling, batch_uids, s_bucket)
                    (out, *stats), pools = fn(self.params, jnp.asarray(rb.packed()),
                                              jnp.asarray(samp_f), jnp.asarray(seeds), kv.pools())
                else:
                    mode = sample
                    fn = self._get_compiled(t_bucket, s_bucket, sample)
                    # ONE descriptor upload per forward (reference single pinned-buffer
                    # upload) instead of one host-to-device transfer per array
                    (out, *stats), pools = fn(self.params, jnp.asarray(rb.packed()), kv.pools())
                kv.update(*pools)
                if sd is not NULL_SPAN:  # the program by its ``_compiled`` key: the device's operations until the fetch are its
                    sd.set_args(compiled=len(self._compiled) > n_programs,
                                program="put:%d:%d:%s" % (t_bucket, s_bucket, mode or "logits"))
            # counts at the boundary, and the uids so that a request-scoped
            # trace can attribute every engine forward to the requests
            # composing it (capped: span args are payload, not a table)
            held = _observe(sp, lambda: dict(
                rows=len(batch_uids), rows_decode=sum(1 for t in batch_tokens if t.size == 1),
                tokens=sum(int(t.size) for t in batch_tokens),
                bucket_tokens=int(t_bucket), bucket_rows=int(s_bucket), steps=1,
                kernel=self._kernel_of(t_bucket, s_bucket), uids=[int(u) for u in batch_uids[:16]],
                blocked=bool(block),
                **self._attn_span_args([seq.seen_tokens for seq in descs], [t.size for t in batch_tokens], t_bucket,
                                       s_bucket),
                **self._state_span_args(len(batch_uids), sum(int(t.size) for t in batch_tokens),
                                        0 if self._lightning else sum(1 for t in batch_tokens if t.size == 1)),
                **({} if had_prefill else
                   self._kv_span_args(t_bucket, s_bucket, [[seq.seen_tokens for seq in descs]])),
                **self._tiled_kv_span_args(t_bucket, s_bucket, rb)))
            with tr.span("serving/engine_commit", tid="serving"):
                for seq in descs:
                    seq.post_forward()
                    self.state_manager.publish_sequence(seq)  # completed full blocks → tree
            with tr.span("serving/engine_fetch", tid="serving"):
                # beside the rows, for a live span, the int32 the step counted of its experts. Logits are cut on
                # the device whatever the option says: a row of them is the vocabulary wide
                out, seen = _cut_and_fetch(sp, out, rb.n_seqs, stats[:1], block,
                                           self.config.cut_rows_on_host and mode is not None)
                if mode == "probe":  # (logits, (positions, selection, attention output)) of the live rows
                    out = (out, tuple(np.asarray(a)[:rb.n_seqs] for a in stats[1]))
            _observe(sp, lambda: self._moe_span_args([(held["tokens"], t_bucket, 1, 0)], seen[0]) if seen else {}, held)
        if reg.enabled and block:
            # a STEP's latency, not a time to first token (the operator's TTFT
            # is gateway/ttft_ms_<class>, from admission); block=False measures
            # only the async dispatch, so no latency sample
            dt_ms = (time.perf_counter() - t0) * 1e3
            if had_prefill:
                reg.histogram("serving/prefill_step_ms").observe(dt_ms)
            else:
                reg.histogram("serving/decode_step_ms").observe(dt_ms)
        return out

    def _moe_span_args(self, shapes, stats) -> dict:
        """What a step span says of the expert layers over a call whose
        forwards are ``shapes``: ``(tokens, t_bucket, forwards,
        kv_only_forwards)``, ``forwards`` forwards of ``tokens`` live tokens
        each in a program of ``t_bucket``, ``kv_only_forwards`` of which stop
        before the last layer's experts (what the host reckons is reckoned a
        forward shape and summed). ``moe_slots_routed``:
        live tokens x top-k x EXPERT layers (a leading dense layer routes
        nothing); ``moe_slots``: those of them that landed on experts held
        here and took a row (all, for a model that holds every expert; a
        count of the program's otherwise: how many land here is data);
        ``moe_rows``: the rows the grouped kernel's grid covers for the
        bucket, padding included (static); ``experts_hit`` of
        ``experts_total`` held experts with at least one slot, summed over
        expert layers and steps; ``expert_load_max``, the most slots one
        expert of one layer held; ``experts_held`` of ``experts_published``,
        the experts here of those the router scores. A model with a learned
        block selection says ``attn_blocks_read`` instead, the one count its
        program makes (:meth:`_sparse_span_args`), and with it
        ``attn_items_live``, the (tile, column) pairs the tiled kernel's work
        lists laid, and ``attn_grid_steps``, the grid steps it ran for them:
        their ratio is how full a step's block slots were (0 and 0 where the
        decode kernel served every call: a horizon, a ``put`` of one-token
        rows). ``stats`` is the program's ``[experts_hit, expert_load_max,
        slots]`` (``[blocks_read, items_live, grid_steps]`` of such a model)."""
        mc = self.model_config
        if self._sparse:
            return {"attn_blocks_read": int(stats[0]), "attn_items_live": int(stats[1]),
                    "attn_grid_steps": int(stats[2])}
        layer_forwards = [(tokens, t_bucket, mc.num_expert_layers * forwards - kv_only)
                          for tokens, t_bucket, forwards, kv_only in shapes]
        return {"moe_slots": int(stats[2]),
                "moe_slots_routed": sum(tokens * mc.moe_top_k * n for tokens, _, n in layer_forwards),
                "moe_rows": sum(self._moe.padded_rows(t_bucket) * n for _, t_bucket, n in layer_forwards),
                "experts_hit": int(stats[0]),
                "experts_total": mc.experts_held * sum(n for _, _, n in layer_forwards),
                "expert_load_max": int(stats[1]),
                "experts_held": mc.experts_held, "experts_published": mc.moe_num_experts}

    def _attn_span_args(self, seen, new, t_bucket: int = 0, s_bucket: int = 0) -> dict:
        """What a step span says of the attention work whatever kernel and
        form ran it, from the rows' lengths alone: ``attn_pairs``, the visible
        (query token, context token) pairs of a call that feeds row ``r`` the
        ``new[r]`` tokens after its ``seen[r]`` (a chunk, or the steps of a
        decode horizon), and ``attn_ctx_tokens``, the context tokens those
        queries see (what a step must read at least once a row), both summed
        over layers with each layer's window applied; ``kv_entry_bytes``, the
        bytes one token caches in one layer (``2 x nkv x d x itemsize``, or the
        latent entry's), so that a reader need not know the family. A model
        with latent attention says ``attn_expanded_pairs`` too: those of
        ``attn_pairs`` that the ``put`` program of ``t_bucket`` tokens attended
        in the expanded form (``flat_model.expanded_slots`` on the same
        lengths; 0 for a decode horizon, which has no such program).
        ``s_bucket``: the program's rows, which a model with a block selection
        sizes its indexer's tiles by (:meth:`_sparse_span_args`)."""
        seen, new = np.asarray(seen, np.int64), np.asarray(new, np.int64)
        if self._sparse:
            return self._sparse_span_args(seen, new, t_bucket, s_bucket)
        pairs = ctx = 0
        for window, layers in self._kv_windows:
            if window is None:
                row_pairs, row_ctx = new * seen + new * (new + 1) // 2, seen + new
            else:  # the first ``a`` tokens still see everything before them, the others ``window`` keys
                a = np.clip(window - seen, 0, new)
                row_pairs = a * seen + a * (a + 1) // 2 + (new - a) * window
                row_ctx = seen + new - np.maximum(seen + 1 - window, 0)
            pairs, ctx = pairs + layers * int(row_pairs.sum()), ctx + layers * int(row_ctx.sum())
        kv = self.state_manager.kv_cache
        args = {"attn_pairs": pairs, "attn_ctx_tokens": ctx, "kv_entry_bytes": kv.block_bytes() // (
            kv.block_size * kv.num_layers)}
        if self._latent:  # no window beside a latent cache: ``row_pairs`` is the one kind's
            expanded = expanded_slots(new, seen + new, *self._expanded_plan(t_bucket), kv.block_size, xp=np) >= 0
            args["attn_expanded_pairs"] = self.model_config.num_layers * int((row_pairs * expanded).sum())
        return args

    def _sparse_span_args(self, seen, new, t_bucket: int = 0, s_bucket: int = 0) -> dict:
        """:meth:`_attn_span_args` of a model with a learned block selection,
        which counts what was READ, from the rows' lengths alone (a token with
        more than ``dense_len`` tokens of context selects exactly ``topk``
        blocks, its own among them, and every other token every visible
        block). In blocks of the selection, summed over sparse layers, kv heads
        and query tokens: ``attn_blocks_visible``, those at or before the
        token's own, and ``attn_blocks_selected``, those its selection chose
        (``attn_blocks_read``, what the work lists fetched, a tile's union, is
        counted in the program and joins the span with the step's result: equal
        to both for a row under ``dense_len``). ``sparse_rows`` / ``dense_rows``:
        the rows with and without a token past ``dense_len``; ``index_keys``:
        the pooled keys such tokens scored (x kv heads x layers);
        ``index_keys_scored``: the (query token slot, kv head, pooled key)
        triples the indexer's program scored for them (x layers): what the
        kernel's work list covers (``ops/pallas/sparse_index.keys_scored``, the
        program's own rule on the host), or every pooled key of the table for
        every slot of every tile where the XLA form runs (``t_bucket`` 0: a
        decode horizon, a program of ``s_bucket`` one-token rows a step);
        ``index_entry_bytes``: one pooled key's bytes in one layer.
        ``attn_pairs``: the (query, selected context token) pairs a layer;
        ``attn_ctx_tokens``: the context tokens the rows' queries selected, at
        most (a chunk's tokens may share blocks)."""
        mc, kv = self.model_config, self.state_manager.kv_cache
        bs, layers, nkv = kv.block_size, len(self._kv_layers), mc.num_kv_heads
        p = np.concatenate([np.arange(s, s + n) for s, n in zip(seen, new)] or [np.zeros(0, np.int64)])
        dense = p + 1 <= mc.sparse_dense_len
        visible = p // bs + 1
        selected = np.where(dense, visible, mc.sparse_topk)
        pairs = np.where(dense, p + 1, (mc.sparse_topk - 1) * bs + p % bs + 1)
        keys = np.where(dense, 0, (p - (mc.sparse_kernel_size - 1)) // mc.sparse_kernel_stride + 1)
        past = seen + new > mc.sparse_dense_len
        T = t_bucket or s_bucket
        qt, rule = index_tile(T), (bs, mc.sparse_kernel_stride, mc.sparse_kernel_size, mc.sparse_dense_len)
        steps = [(seen, new)] if t_bucket else [(seen + i, np.minimum(new - i, 1)) for i in range(int(new.max(initial=0)))]
        if scores_by_kernel(T, *self._index_kernels):
            scored = sum(keys_scored(s, n, self._max_blocks_per_seq, qt, *rule) for s, n in steps)
        else:
            scored = len(steps) * (-(-T // qt) + s_bucket + 1) * qt * self._max_blocks_per_seq * (bs // rule[1])
        row_of = np.repeat(np.arange(len(new)), new)
        ctx = np.minimum(seen + new, np.bincount(row_of, weights=pairs, minlength=len(new)).astype(np.int64))
        return {"attn_pairs": layers * int(pairs.sum()), "attn_ctx_tokens": layers * int(ctx.sum()),
                "kv_entry_bytes": sum(h * w for h, w in self._kv_entry) * kv.k_pool.dtype.itemsize,
                "attn_blocks_visible": layers * nkv * int(visible.sum()),
                "attn_blocks_selected": layers * nkv * int(selected.sum()),
                "sparse_rows": int(past.sum()), "dense_rows": int((~past).sum()),
                "index_keys": layers * nkv * int(keys.sum()), "index_keys_scored": layers * nkv * scored,
                "index_entry_bytes": kv.index_entry_bytes()}

    def _state_span_args(self, rows: int, tokens: int, stepped: int) -> dict:
        """What a step span says of the state layers of a model that has them
        (nothing otherwise): ``state_rows``, the rows whose state the call
        read and wrote (x the steps of a horizon); ``state_rows_stepped``,
        those of them whose state went through the recurrent step (a ``put``'s
        rows fed ONE token, of the delta rule: a lightning ``put`` scans every
        row; every row x step of a horizon), the rest through the chunk scan; ``state_bytes``, the rows x state layers x
        ``state_entry_bytes`` x 2, the least a correct form moves;
        ``lin_tokens``, the tokens through linear layers, x layers;
        ``state_slots_live`` of ``state_slots_total`` slots taken. A model with
        state-space layers says besides what its roofline reads:
        ``mamba_row_calls``, the (row, layer, call) triples whose state was
        read and written (a horizon's step is a call), and ``mamba_tokens``,
        the (token, layer) pairs through the scan."""
        if not self._state_layers:
            return {}
        kv = self.state_manager.kv_cache
        entry, n = kv.state_entry_bytes(), len(self._state_layers)
        out = {"state_rows": rows, "state_rows_stepped": stepped, "state_bytes": rows * n * entry * 2,
               "state_entry_bytes": entry,
               "lin_tokens": tokens * n, "state_slots_live": kv.state_slots - kv.free_state_slots,
               "state_slots_total": kv.state_slots}
        if self._mamba:
            out.update(mamba_row_calls=rows * n, mamba_tokens=tokens * n)
        return out

    def _expanded_plan(self, t_bucket: int):
        """``flat_model.expanded_plan`` of this engine's ``put`` program of
        ``t_bucket`` tokens: ``(0, 0)`` for a model without latent attention."""
        if not self._latent:
            return 0, 0
        return expanded_plan(self.model_config, int(t_bucket), self._max_blocks_per_seq, self.config.kv_block_size,
                             np.dtype(self.config.kv_dtype).itemsize)

    def _kv_span_args(self, T: int, S: int, pos) -> dict:
        """What a decode span says of the attention kernel's grid: ``kv_live``
        live (row, KV block) pairs and ``kv_steps`` block slots the grid runs
        for them, both summed over layers and steps
        (``paged_attention.decode_kv_counts``; ``pos``: the fed tokens'
        positions, a list of rows a step). Nothing for a shape no program has
        traced yet or one the tiled grid took, which says the same of itself
        under names of its own (:meth:`_tiled_kv_span_args`)."""
        choice = kernel_choice(T, S, self._max_blocks_per_seq)
        if choice is None or choice["kernel"] == "paged_attn_q_tiled" or self._sparse:
            return {}  # (under a selection the grid follows the data: ``attn_blocks_read`` says what it ran)
        steps, live = decode_kv_counts(choice, pos, self._kv_windows, self.config.kv_block_size,
                                       self._max_blocks_per_seq, T)
        return {"kv_steps": steps, "kv_live": live}

    def _tiled_kv_span_args(self, T: int, S: int, rb, forwards=((0, 1, 0, False), )) -> dict:
        """What a step span says of ``paged_attn_q_tiled``'s grid, for the
        forwards of a shape that kernel took (nothing if it took none):
        ``tile_kv_live``, the live (tile, KV block) pairs, ``tile_kv_steps``,
        the grid steps it ran for them (the choice's ``blocks_per_step`` pairs
        of a tile a step), and ``tile_kv_bound``, the tiles x table columns the
        shapes allow, each summed over layers and forwards
        (``paged_attention.tiled_kv_counts`` on the batch's own arrays, as
        masked by: a block-diffusion model's ``pos | (B - 1)``). ``forwards``:
        ``(offset, n, kv_only, fused)``, ``n`` forwards at the batch's
        positions plus ``offset``, ``kv_only`` of which stop before the last
        layer's attention; ``fused``: forwards of ``2 T`` tokens, a row's block
        before (a diffusion block's commit) then the row's tokens, as
        ``diffusion.build_block_program`` lays them."""
        from .diffusion import rows_beside

        if self._sparse:
            return {}
        last = self.model_config.layer_window(self.model_config.num_layers - 1)
        bs, B = self.config.kv_block_size, self._block
        counts, tiled = np.zeros(3, np.int64), False
        for offset, n, kv_only, fused in forwards:
            T_f = 2 * T if fused else T
            choice = kernel_choice(T_f, S, self._max_blocks_per_seq)
            if choice is None or choice["kernel"] != "paged_attn_q_tiled":
                continue
            tiled = True
            # attention calls a window: its layers in every forward, less the last layer's in a commit
            calls = [(w, layers * n - (kv_only if w == last else 0)) for w, layers in self._kv_windows]
            seq_idx, pos = rb.token_seq_idx, rb.token_pos + offset
            rows, cols = self._expanded_plan(T)
            if fused:  # (never beside latent attention: the configuration refuses block diffusion there)
                seq_idx, pos = rows_beside(seq_idx, seq_idx, B, np), rows_beside(np.maximum(pos - B, 0), pos, B, np)
            pos = pos | max(B - 1, 0)
            if rows:
                # latent attention's two calls: the absorbed one without the long rows' tiles, and the
                # expanded one over the workspace's own table
                slots = expanded_slots(rb.seq_total_len - rb.seq_start_len, rb.seq_total_len, rows, cols, bs, xp=np)
                slot_of_tok = np.where(rb.token_valid, slots[rb.token_seq_idx], -1)
                x_seq, x_pos = expanded_batch(slot_of_tok, pos, rows, xp=np)
                x_choice = kernel_choice(T, 2 * rows + 1, cols)
                counts += tiled_kv_counts(x_choice["q_tile"], x_seq, x_pos, calls, bs, cols, 2 * rows + 1,
                                          x_choice["blocks_per_step"])
                pos = np.where(slot_of_tok >= 0, -1, pos)
            counts += tiled_kv_counts(choice["q_tile"], seq_idx, pos, calls, bs, self._max_blocks_per_seq, S,
                                      choice["blocks_per_step"])
        if not tiled:
            return {}
        return {"tile_kv_bound": int(counts[0]), "tile_kv_live": int(counts[1]), "tile_kv_steps": int(counts[2])}

    def _kernel_of(self, T: int, S: int, horizon: bool = False) -> str:
        """``<kernel>:<n>:<rule>`` (``n``: the tiled kernel's ``q_tile``, or the
        KV blocks a grid step of the decode kernel takes) of the paged-attention
        call inside the compiled program of ``T`` tokens and ``S`` rows,
        looked up once per shape in the table ``paged_attention`` fills while
        ``jit`` traces it (so only after the program's first call); a program
        with latent attention's expanded call names both calls, the absorbed
        one first, joined by ``+``. Empty
        while nothing recorded a choice for the shape (an attention module
        that is not the paged kernel's). A model with state layers names the
        delta rule's kernels behind them: the recurrent step of a decode
        ``horizon``; of a ``put`` the chunk scan and the recurrent step, which
        takes the rows fed one token (``kda_chunks``)."""
        label = self._kernel_labels.get((T, S, horizon))
        if label is None:
            choices = [kernel_choice(T, S, self._max_blocks_per_seq)]
            rows, cols = self._expanded_plan(T)
            if rows:  # latent attention's second call, over the workspace of per-head K and V
                choices.append(kernel_choice(T, 2 * rows + 1, cols))
            parts = [] if None in choices else ["%s:%d:%s" % (c["kernel"], max(c["q_tile"], c["blocks_per_step"]), c["rule"])
                                                for c in choices]
            if self._sparse:  # the indexer that made the selection the paged kernel read by
                parts.append("%s:%d:top%d" % (scopes.SPARSE_INDEX, index_tile(T), self.model_config.sparse_topk))
            if self._lightning:  # one form a program: the chunk scan of a put, the recurrent step of a horizon
                parts.append("%s:1:one-token-rows" % LIGHTNING_KERNEL_NAMES[0] if horizon else
                             "%s:%d:ragged" % (LIGHTNING_KERNEL_NAMES[1], LIGHTNING_TILE))
            elif self._state_layers:  # beside the paged kernel, the rule's two forms in this program
                names, tile = (MAMBA_KERNEL_NAMES, MAMBA_TILE) if self._mamba else (KDA_KERNEL_NAMES, KDA_TILE)
                parts += [] if horizon else ["%s:%d:ragged" % (names[1], tile)]
                parts.append("%s:1:one-token-rows" % names[0])
            label = "+".join(parts)
            if None in choices:
                return label
            self._kernel_labels[(T, S, horizon)] = label
        return label

    # ------------------------------------------------------------------
    def decode(self, batch_uids: List[int], first_tokens, n_steps: int, block: bool = True,
               eos_token_ids=None, sampling=None, max_new_tokens=None, probe=()) -> np.ndarray:
        """Run ``n_steps`` greedy decode steps ON DEVICE in one compiled
        program (a ``lax.scan`` feeding each step's argmax back as the next
        token), for sequences already tracked by the engine.

        This is the steady-state continuous-batching fast path: ``put`` pays
        one host round-trip per token; ``decode`` pays it once per ``n_steps``.
        KV blocks for the whole horizon are reserved up front (admission
        refuses if the pool can't cover it). Returns token ids
        [len(batch_uids), n_steps].

        ``eos_token_ids`` (blocking mode only): one eos id — a scalar, or a
        per-sequence list with ``None`` entries — lets the engine rewind the
        horizon OVERSHOOT of a sequence that hits eos mid-scan: the KV (and
        token history) materialized past the eos is rolled back through
        ``DSStateManager.rollback_to`` before publish, so the radix tree
        never receives post-eos garbage paths and the tail blocks return to
        the pool immediately instead of idling until flush.

        ``sampling``: per-sequence :class:`SamplingParams` (None = greedy
        rows). The sampled scan draws each fed-back token from the
        tempered/top-p distribution on device, keyed by (seed, position);
        all-greedy lists keep the original argmax scan program.

        A model with ``diffusion_block_size`` ``B`` has no causal next token:
        its rows advance by WHOLE BLOCKS of masked diffusion (``diffusion.py``),
        ``n_steps`` a multiple of ``B``. A row has no last token to feed:
        ``first_tokens`` is None, or one entry a row with the tokens already
        KNOWN of the row's next block (a prompt's last partial block, fewer
        than ``B`` of them, on the row's first call; empty or None after it),
        which stand fixed beside the masks and whose K/V this call writes.
        Returns ``[len(batch_uids), n_steps]``, the final ids of the blocks, a
        row's known tokens first. ``max_new_tokens`` (one entry a row) says
        how many NEW tokens each row keeps: a row that stops inside a block
        (there, or at its eos) is rewound to its last whole kept block before
        anything is published. ``probe`` (row indices): also return, for those
        rows, every denoise forward's ids and logits (``(tokens, probe)``).
        """
        batch_uids = list(batch_uids)
        if self._block:
            decode = functools.partial(self._decode_blocks, max_new_tokens=max_new_tokens,
                                       probe=tuple(int(r) for r in probe))
        elif max_new_tokens is not None or probe:
            raise ValueError("decode(max_new_tokens=, probe=) are a block-diffusion model's arguments")
        else:
            decode = self._decode
        hb = self._health
        gl = self.goodput_ledger
        if gl is None and not hb.enabled:
            return decode(batch_uids, first_tokens, n_steps, block, eos_token_ids, sampling)
        if gl is not None:
            self._gp_last_uids = batch_uids
            t_gp = time.perf_counter()
        if hb.enabled:
            hb.begin("serving")
            get_flight_recorder().record("serving", "decode", seqs=len(batch_uids),
                                         steps=int(n_steps))
        try:
            return decode(batch_uids, first_tokens, n_steps, block, eos_token_ids, sampling)
        finally:
            if hb.enabled:
                hb.end("serving")
            if gl is not None:
                gl.book("decode_active", time.perf_counter() - t_gp)

    @_serving_compile_scope
    def _decode(self, batch_uids, first_tokens, n_steps, block, eos_token_ids=None,
                sampling=None):
        tr = get_tracer()
        reg = get_metrics()
        t0 = time.perf_counter() if reg.enabled else 0.0
        uids = list(batch_uids)
        S = len(uids)
        with tr.span("serving/decode", tid="serving") as sp:
            with tr.span("serving/engine_batch", tid="serving"):
                if len(set(uids)) != len(uids):
                    # same corruption mode put()'s admission rejects: two rows of one
                    # uid would write divergent KV at the same positions
                    raise SchedulingError(SchedulingResult.BatchSequenceLimitExceeded)
                if S > self.batch.max_seqs:
                    # must reject BEFORE allocate/pre_forward: a mid-loop wrapper
                    # ValueError would strand in-flight state on every sequence
                    raise SchedulingError(SchedulingResult.BatchSequenceLimitExceeded)
                first = [np.asarray(t, np.int32).reshape(-1) for t in first_tokens]
                assert all(t.size == 1 for t in first), \
                    "decode() takes exactly one next token per sequence"
                seqs = []
                for uid in uids:
                    seq = self.state_manager.get_sequence(uid)
                    if seq is None:
                        raise SchedulingError(SchedulingResult.EngineSequenceLimitExceeded)
                    if seq.seen_tokens + n_steps > self._max_context:
                        raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                    seqs.append(seq)
                blocks_needed = sum(s.blocks_needed(n_steps) for s in seqs)
                if blocks_needed > self.state_manager.available_blocks:
                    raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                if not hasattr(self, "_decode_batch"):
                    # the scan packs exactly one token per sequence, so its wrapper
                    # uses the SAME bucket table for tokens and sequences
                    self._decode_batch = RaggedBatchWrapper(
                        max_ragged_batch_size=self.batch.max_seqs,
                        max_ragged_sequence_count=self.batch.max_seqs,
                        max_blocks_per_seq=self._max_blocks_per_seq,
                        block_size=self.config.kv_block_size,
                        token_buckets=self.batch.seq_buckets, seq_buckets=self.batch.seq_buckets)
                for seq, toks in zip(seqs, first):
                    self.state_manager.allocate_blocks(seq, n_steps)
                    seq.pre_forward(n_steps)

                self._decode_batch.clear()
                for seq, toks in zip(seqs, first):
                    # tables now cover the full horizon; positions advance in-scan
                    self._decode_batch.insert_sequence(seq, toks)
                rb = self._decode_batch.finalize()

            from .sampling import all_greedy, pack_sampling

            kv = self.state_manager.kv_cache
            s_bucket = rb.token_ids.shape[0]
            sampled = sampling is not None and not all_greedy(sampling)
            with tr.span("serving/engine_dispatch", tid="serving") as sd:
                n_programs = len(self._compiled)
                if sampled:
                    fn = self._get_compiled_decode(s_bucket, n_steps, sampled=True)
                    samp_f, seeds = pack_sampling(sampling, uids, s_bucket)
                    (toks, *stats), pools = fn(self.params, jnp.asarray(rb.packed()),
                                               jnp.asarray(samp_f), jnp.asarray(seeds), kv.pools())
                else:
                    fn = self._get_compiled_decode(s_bucket, n_steps)
                    # start positions already ride inside packed() (each decode row
                    # is one token at its position) — no separate seq_start_len upload
                    (toks, *stats), pools = fn(self.params, jnp.asarray(rb.packed()), kv.pools())
                kv.update(*pools)
                if sd is not NULL_SPAN:
                    sd.set_args(compiled=len(self._compiled) > n_programs,
                                program="decode:%d:%d%s" % (s_bucket, n_steps, ":sampled" if sampled else ""))
            # without the host fetch the span is dispatch only: the blocked
            # flag discloses it
            held = _observe(sp, lambda: dict(
                rows=S, tokens=S * int(n_steps), steps=int(n_steps), bucket_rows=int(s_bucket),
                bucket_tokens=int(s_bucket), kernel=self._kernel_of(s_bucket, s_bucket, horizon=True),
                uids=[int(u) for u in uids[:16]], blocked=bool(block),
                **self._attn_span_args([seq.seen_tokens for seq in seqs], [int(n_steps)] * S, s_bucket=s_bucket),
                **self._state_span_args(S * int(n_steps), S * int(n_steps), S * int(n_steps)),
                **self._kv_span_args(s_bucket, s_bucket, np.asarray([seq.seen_tokens for seq in seqs])[None, :]
                                     + np.arange(int(n_steps))[:, None])))
            with tr.span("serving/engine_fetch", tid="serving"):
                toks, seen = _cut_and_fetch(sp, toks, S, stats[:1], block, self.config.cut_rows_on_host)
            pc = self.state_manager.prefix_cache
            with tr.span("serving/engine_commit", tid="serving"):
                if block:
                    if eos_token_ids is None or isinstance(eos_token_ids, (int, np.integer)):
                        eos_list = [eos_token_ids] * S
                    else:
                        eos_list = list(eos_token_ids)
                        assert len(eos_list) == S, "eos_token_ids must match batch_uids"
                    for seq, f, row, eos in zip(seqs, first, toks, eos_list):
                        start = seq.seen_tokens
                        if pc is not None:
                            # tokens materialized this burst: the fed first token
                            # plus every in-scan feedback token except the last
                            # output (whose KV is not written until it is fed back)
                            self.state_manager.note_tokens(seq, np.concatenate([f, row[:-1]]))
                        seq.post_forward()
                        if eos is not None:
                            hit = np.nonzero(row == eos)[0]
                            if hit.size and int(hit[0]) + 1 < n_steps:
                                # horizon overshoot: the caller keeps row[:hit+1];
                                # KV/history past the eos is garbage — rewind it
                                # BEFORE publish so the tree never sees it
                                self.state_manager.rollback_to(seq, start + 1 + int(hit[0]))
                        self.state_manager.publish_sequence(seq)
                else:
                    if pc is not None:
                        for seq in seqs:
                            seq.history_valid = False  # generated ids never reached host
                    for seq in seqs:
                        seq.post_forward()
                        self.state_manager.publish_sequence(seq)
            _observe(sp, lambda: self._moe_span_args([(S, s_bucket, int(n_steps), 0)], seen[0]) if seen else {}, held)
        if reg.enabled and block:
            dt = time.perf_counter() - t0
            reg.histogram("serving/decode_ms").observe(dt * 1e3)
            reg.gauge("serving/decode_tokens_per_sec").set(S * n_steps / max(dt, 1e-9))
        return toks

    @_serving_compile_scope
    def _decode_blocks(self, batch_uids, first_tokens, n_steps, block, eos_token_ids=None, sampling=None,
                       max_new_tokens=None, probe=()):
        """``decode`` of a block-diffusion model: ``n_steps // B`` blocks a row
        in one compiled program (``diffusion.build_block_program``), in which
        a block's commit rides in the next block's first denoise forward and
        the call's last block alone is committed by a forward of its own. Under
        the span's name and arguments of the causal burst, ``steps`` counting
        the FORWARDS the call ran (``denoise_forwards`` and
        ``commit_forwards``, the ``kv_only`` one), ``fused_commits`` the blocks
        whose commit rode in a denoise forward and ``tokens_fed`` the live
        tokens fed (a forward that carries a commit feeds two blocks a row),
        with the counts a block at a time beside them (PERF.md section 3)."""
        from .sampling import all_greedy

        tr = get_tracer()
        reg = get_metrics()
        uids, S, B = list(batch_uids), len(batch_uids), self._block
        n_steps = int(n_steps)
        t_call = time.perf_counter()
        with tr.span("serving/decode", tid="serving") as sp:
            with tr.span("serving/engine_batch", tid="serving"):
                if n_steps <= 0 or n_steps % B:
                    raise ValueError(f"decode(n_steps={n_steps}): a model with diffusion_block_size={B} "
                                     "advances by whole blocks")
                if not all_greedy(sampling):
                    raise NotImplementedError(
                        f"temperature sampling of a model with diffusion_block_size={B}: a block's positions are "
                        "unmasked by the confidence of their argmax, and no draw is carried through that choice")
                if not block:
                    raise NotImplementedError("decode(block=False) of a block-diffusion model: a row's stop is "
                                              "settled from the fetched tokens before anything is published")
                if len(set(uids)) != len(uids) or S > self.batch.max_seqs:
                    raise SchedulingError(SchedulingResult.BatchSequenceLimitExceeded)
                first = [np.zeros(0, np.int32) if t is None else np.asarray(t, np.int32).reshape(-1)
                         for t in (first_tokens if first_tokens is not None else [None] * S)]
                if len(first) != S or any(t.size >= B for t in first):
                    raise ValueError(f"decode(first_tokens=): {[int(t.size) for t in first]} tokens for {S} rows; a "
                                     f"row's next block of {B} opens with fewer than {B} known tokens")
                opened = [int(t.size) for t in first]
                seqs = []
                for uid in uids:
                    seq = self.state_manager.get_sequence(uid)
                    if seq is None:
                        raise SchedulingError(SchedulingResult.EngineSequenceLimitExceeded)
                    if seq.seen_tokens % B:
                        raise ValueError(f"uid {uid}: committed length {seq.seen_tokens} ends inside a block of {B}; "
                                         "prompt chunks end on block boundaries (first_tokens take the rest)")
                    if seq.seen_tokens + n_steps > self._max_context:
                        raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                    seqs.append(seq)
                if sum(s.blocks_needed(n_steps) for s in seqs) > self.state_manager.available_blocks:
                    raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                if not hasattr(self, "_block_batch"):
                    # B tokens a row, so the token buckets are the row buckets' multiples
                    self._block_batch = RaggedBatchWrapper(
                        max_ragged_batch_size=self.batch.max_seqs * B,
                        max_ragged_sequence_count=self.batch.max_seqs,
                        max_blocks_per_seq=self._max_blocks_per_seq, block_size=self.config.kv_block_size,
                        token_buckets=[s * B for s in self.batch.seq_buckets], seq_buckets=self.batch.seq_buckets)
                mask_id = self.model_config.mask_token_id
                self._block_batch.clear()
                for seq, known in zip(seqs, first):
                    self.state_manager.allocate_blocks(seq, n_steps)
                    seq.pre_forward(n_steps)  # the slots written; committed when the call returns
                    self._block_batch.insert_sequence(
                        seq, np.concatenate([known, np.full(B - known.size, mask_id, np.int32)]))
                rb = self._block_batch.finalize()

            kv = self.state_manager.kv_cache
            s_bucket, n_blocks = rb.block_tables.shape[0], n_steps // B
            with tr.span("serving/engine_dispatch", tid="serving") as sd:
                n_programs = len(self._compiled)
                fn = self._get_compiled_blocks(s_bucket, n_blocks, probe)
                # the blocks to advance ride behind the descriptor: the program is built for up to its capacity
                (toks, *counts), pools = fn(self.params, jnp.asarray(np.append(rb.packed(), np.int32(n_blocks))), kv.pools())
                kv.update(*pools)
                if sd is not NULL_SPAN:
                    sd.set_args(compiled=len(self._compiled) > n_programs,
                                program="diffuse:%d:%d" % (s_bucket, self._block_program_cap(n_blocks, probe)))
            # the attention call of the forwards of S x B tokens, then of those that carry a commit (S x 2B)
            held = _observe(sp, lambda: dict(
                rows=S, tokens=S * n_steps, bucket_rows=int(s_bucket), bucket_tokens=int(s_bucket * B),
                kernel="+".join(filter(None, (self._kernel_of(t * s_bucket * B, s_bucket)
                                              for t in ((1, 2) if n_blocks > 1 else (1, ))))),
                uids=[int(u) for u in uids[:16]], blocked=True, blocks=n_blocks, block_size=B, commit_forwards=1,
                fused_commits=n_blocks - 1, open_tokens=sum(opened)))
            with tr.span("serving/engine_fetch", tid="serving"):
                # the whole bucket comes to the host (a few KB of int32) and is cut there: an eager
                # slice on the device would be one more tiny program a (bucket, rows) pair to warm.
                # Beside it, for a live span: forwards a block, masked slots fed, the experts' counts
                toks, seen = _fetch(sp, toks, counts[:len(counts) - 2 * bool(probe)])
            toks = toks[:S, :n_steps]
            if eos_token_ids is None or isinstance(eos_token_ids, (int, np.integer)):
                eos_token_ids = [eos_token_ids] * S
            assert len(eos_token_ids) == S, "eos_token_ids must match batch_uids"
            kept = []
            with tr.span("serving/engine_commit", tid="serving"):
                for seq, row, n_open, eos, want in zip(seqs, toks, opened, eos_token_ids,
                                                       max_new_tokens or [None] * S):
                    start = seq.seen_tokens
                    self.state_manager.note_tokens(seq, row)
                    seq.post_forward()
                    keep = n_steps - n_open if want is None else min(n_steps - n_open, int(want))
                    if eos is not None:
                        hit = np.nonzero(row[n_open:n_open + keep] == eos)[0]
                        if hit.size:
                            keep = int(hit[0]) + 1
                    kept.append(keep)
                    if n_open + keep < n_steps:
                        # a stop inside the call: the caller keeps row[n_open:n_open + keep]. A block's K/V
                        # saw the whole block, so only the whole kept blocks stay committed
                        self.state_manager.rollback_to(seq, start + (n_open + keep) // B * B)
                    self.state_manager.publish_sequence(seq)

            def counted():
                t_done = time.perf_counter()  # the call's time a block, before what observing it takes
                forwards, masked_fed, *stats = seen
                forwards = forwards[:n_blocks]
                n_denoise, n_fused = int(forwards.sum()), n_blocks - 1
                # a block's first forward is of 2 x (S x B) tokens wherever a commit rides in the call (block 0's
                # feeds the half before as padding), the others and the call's one commit of S x B
                first = n_blocks if n_fused else 0  # forwards of the 2 x (S x B) shape
                T, t_bucket = S * B, s_bucket * B
                moe_args = {}
                if stats:
                    moe_args = self._moe_span_args(
                        [(T, 2 * t_bucket, min(first, 1), 0), (2 * T, 2 * t_bucket, n_fused, 0),
                         (T, t_bucket, n_denoise - first + 1, 1)], stats[0])
                tiled = [(b * B, 1, 0, True) for b in range(first)] + \
                    [(b * B, int(n) - bool(first), 0, False) for b, n in enumerate(forwards)] + \
                    [(n_fused * B, 1, 1, False)]
                return dict(steps=n_denoise + 1, denoise_forwards=n_denoise, tokens_committed=sum(kept),
                            tokens_fed=T * (n_denoise + 1 + n_fused), masked_fed=int(masked_fed),
                            tokens_dropped=n_steps * S - held["open_tokens"] - sum(kept),
                            block_ms=round((t_done - t_call) * 1e3 / n_blocks, 3), **moe_args,
                            **self._tiled_kv_span_args(t_bucket, s_bucket, rb, tiled))

            _observe(sp, counted, held)
        if reg.enabled:
            dt = time.perf_counter() - t_call
            reg.histogram("serving/decode_ms").observe(dt * 1e3)
            reg.gauge("serving/decode_tokens_per_sec").set(sum(kept) / max(dt, 1e-9))
        if probe:
            # forwards [blocks]; ids [blocks, steps, rows, B] and logits [..., V], the program's last two results
            forwards, ids, logits = (np.asarray(a)[:n_blocks] for a in (counts[0], *counts[-2:]))
            return toks, {"rows": list(probe), "ids": ids, "forwards": forwards, "logits": logits}
        return toks

    def _block_program_cap(self, n_blocks: int, probe_rows: tuple = ()) -> int:
        """The blocks the program that serves a call of ``n_blocks`` is built
        for. The blocks a call advances are an argument of the program, so a
        row bucket needs TWO: one of one block, whose forwards are all of ``S
        x B`` tokens, and one for every longer call, whose blocks' first
        forwards carry the commit of the block before; that one is built for
        the scheduler's burst (``DECODE_HORIZON`` tokens) or the call itself
        if it asks for more. A program of three forwards of the whole model
        is 4 s of a warm start and 30 s of a cold one: one a (row bucket,
        blocks) pair was 16 of them in a replica's set-up where this is 8. A
        probe's results are sized by the blocks, so its program is built for
        the call's own."""
        from .scheduler import DynamicSplitFuseScheduler

        if probe_rows or n_blocks == 1:
            return n_blocks
        return max(n_blocks, DynamicSplitFuseScheduler.DECODE_HORIZON // self._block)

    def _get_compiled_blocks(self, s_bucket: int, n_blocks: int, probe_rows: tuple = ()):
        cap = self._block_program_cap(n_blocks, probe_rows)
        key = ("diffuse", s_bucket, cap, probe_rows)
        if key not in self._compiled:
            from .diffusion import build_block_program

            self._note_compile(f"diffuse/s{s_bucket}/b{cap}{'/probe' if probe_rows else ''}")
            mc, dc = self.model_config, self.config.diffusion
            fwd = build_block_program(
                self._ragged_step, block_size=self._block, mask_id=mc.mask_token_id,
                denoising_steps=dc.denoising_steps, remasking=dc.remasking, threshold=dc.confidence_threshold,
                s_bucket=s_bucket, cap=cap, moe=self._moe is not None, vocab=mc.vocab_size,
                probe_rows=probe_rows)
            self._compiled[key] = jax.jit(fwd, donate_argnums=(2, ), **self._jit_options)
            log_dist(f"compiled block-diffusion decode bucket seqs={s_bucket} blocks<={cap} "
                     f"probe_rows={probe_rows}", ranks=[0])
        return self._compiled[key]

    def _ragged_step(self, params, packed, pools, t_bucket, s_bucket, gather_k: int = 0,
                     tree_meta=None, moe_stats: bool = False, kv_only: bool = False, one_token_rows: bool = False,
                     probe: bool = False):
        """One ragged forward over the pool tuple (2 = bf16 pools, 4 = int8
        pools + scales, 1 = a latent pool). The SINGLE builder both compiled paths share —
        quant/non-quant variation lives in the tuple arity, not in four
        hand-copied closures.

        ``gather_k``: the speculative-verify variant — project logits for
        each sequence's ENTIRE ``gather_k + 1``-token chunk (the chunk is
        contiguous in the packed layout, so the positions are
        ``last_idx - gather_k .. last_idx``) instead of only the last
        token. Returns logits ``[S * (gather_k + 1), V]`` row-major per
        sequence.

        ``tree_meta``: token-tree verification — one int32 ``[3 * T]``
        operand carrying per-token [logical pos_ids | branch id | depth]
        rows for the flattened draft tree. Each tree node occupies its own
        KV SLOT (``pos`` = start + flat node index, so sibling branches
        never collide in the cache) but its LOGICAL position is
        start + depth; visibility is ancestors-only — committed context,
        the shared root (depth 0), and earlier nodes of the token's OWN
        branch. The mask/ctx-position arrays built here feed
        ``ragged_forward``'s tree kwargs; with ``tree_meta`` None this is
        byte-identical to the plain causal step.

        ``moe_stats`` (a model with experts): a third result, int32
        ``[experts_hit, expert_load_max, slots]`` of this forward.

        ``kv_only``: the commit of the last diffusion block of a call
        (``ragged_forward``): the K/V of every layer and nothing else, logits None.

        A model with state layers: the pool tuple is ``(k, v, state, tails)``
        and the rows' state slots ride behind the descriptors;
        ``one_token_rows`` says that token ``i`` is row ``i`` (the decode
        horizon's step), which picks the delta rule's recurrent form. A model
        with a learned block selection: the pooled keys' pool is the third of
        the tuple, ``moe_stats`` carries the blocks its work lists served, and
        ``probe`` adds a last result of its own, ``ragged_forward``'s: the
        positions, the selection and the attention output of a few tokens a
        row in every sparse layer (``put(sample="probe")``: a check's way to
        read back what the indexer chose and what the paged kernel made of it)."""
        from .ragged.ragged_wrapper import unpack_descriptors

        with jax.named_scope(scopes.EMBED):
            token_ids, seq_idx, pos, valid, tables, last_idx = unpack_descriptors(
                packed, t_bucket, s_bucket, self._max_blocks_per_seq)
        extra = {}
        if tree_meta is not None:
            assert gather_k, "tree_meta requires the gather_k verify layout"
            with jax.named_scope(scopes.MIXER):
                T = t_bucket
                k1 = gather_k + 1
                pos_ids = tree_meta[0:T]
                branch = tree_meta[T:2 * T]
                depth = tree_meta[2 * T:3 * T]
                C = self._max_blocks_per_seq * self.config.kv_block_size
                # chunk-local flat node index from the packed layout alone:
                # every verify chunk is exactly k1 tokens ending at last_idx
                node_idx = jnp.arange(T, dtype=jnp.int32) - (last_idx[seq_idx] - gather_k)
                start = pos - node_idx                    # committed length, per token
                ctx_p = jnp.arange(C, dtype=jnp.int32)[None, :]
                j = ctx_p - start[:, None]                # ctx slot's flat node index
                jj = jnp.clip(j, 0, gather_k)
                # per-sequence node tables scattered from this batch's own rows
                b_tbl = jnp.zeros((s_bucket, k1), jnp.int32).at[seq_idx, node_idx].set(
                    branch, mode="drop")
                d_tbl = jnp.zeros((s_bucket, k1), jnp.int32).at[seq_idx, node_idx].set(
                    depth, mode="drop")
                cb = jnp.take_along_axis(b_tbl[seq_idx], jj, axis=1)   # [T, C]
                cd = jnp.take_along_axis(d_tbl[seq_idx], jj, axis=1)
                in_tree = (j >= 0) & (j <= gather_k)
                # ancestor visibility: committed prefix | root (depth 0) | an
                # EARLIER node of my own branch — a sibling branch's KV sits at
                # an earlier slot but must stay invisible
                vis_tree = in_tree & (cd <= depth[:, None]) & ((cd == 0) | (cb == branch[:, None]))
                mask = (ctx_p < start[:, None]) | vis_tree
                if getattr(self.model_config, "per_layer_attention", False):
                    raise NotImplementedError("token-tree verification builds one visibility mask for all "
                                              "layers; this model's layers differ in window (layer_types)")
                window = getattr(self.model_config, "sliding_window", None)
                if window:
                    ctx_pid_t = jnp.where(in_tree, start[:, None] + cd, ctx_p)
                    mask = mask & (pos_ids[:, None] - ctx_pid_t < int(window))
                # ctx logical positions per sequence (alibi distances)
                start_s = pos[jnp.maximum(last_idx, 0)] - gather_k     # [S]
                js = ctx_p - start_s[:, None]
                jjs = jnp.clip(js, 0, gather_k)
                ds = jnp.take_along_axis(d_tbl, jjs, axis=1)
                ctx_pid = jnp.where((js >= 0) & (js <= gather_k), start_s[:, None] + ds,
                                    jnp.broadcast_to(ctx_p, (s_bucket, C)))
                extra = {"pos_ids": pos_ids, "attn_mask": mask, "ctx_pos_ids": ctx_pid}
        if gather_k:
            with jax.named_scope(scopes.LM_HEAD):
                idx = last_idx[:, None] - gather_k + jnp.arange(gather_k + 1, dtype=jnp.int32)
                # padding rows carry last_idx 0 — clamp their (negative) indices;
                # the caller slices the garbage rows off with [:n_seqs]
                last_idx = jnp.maximum(idx, 0).reshape(-1)
        if self._state_layers:  # (never beside int8: the last two pools are the state's)
            if gather_k:
                raise NotImplementedError("a speculative verify step (a tree of drafts among them) of a model with a "
                                          "recurrent state layer: the rejected tokens cannot be rewound out of the state")
            beside = {"state_pools": tuple(pools[3 if self._sparse else 2:]), "one_token_rows": one_token_rows,
                      "state_slots": unpack_state_slots(packed, t_bucket, s_bucket, self._max_blocks_per_seq)}
        else:
            beside = {"k_scale": pools[2], "v_scale": pools[3]} if len(pools) == 4 else {}
        if self._sparse:  # (never beside int8 either: the third pool is the pooled keys')
            if gather_k:
                raise NotImplementedError("a speculative verify step of a model with pooled keys (a learned block "
                                          "selection): the rejected tokens' keys cannot be taken out of the pooled ones")
            beside.update(index_pool=pools[2], probe=probe)
        out = ragged_forward(self.model_config, self.config.kv_block_size, params,
                             token_ids, seq_idx, pos, valid, tables, last_idx,
                             pools[0], pools[1] if len(pools) > 1 else None, use_pallas=self._use_pallas,
                             modules=self._modules, moe_stats=moe_stats, kv_only=kv_only, **beside, **extra)
        tail = ()
        if probe:
            out, tail = out[:-1], out[-1:]
        if moe_stats:
            return (out[0], tuple(out[1:-1]), out[-1]) + tail
        return (out[0], tuple(out[1:])) + tail  # logits, new pool tuple

    # ------------------------------------------------------------------
    def speculate_decode(self, batch_uids: List[int], first_tokens, draft_tokens,
                         k: Optional[int] = None, eos_token_ids=None,
                         sampling=None) -> List[np.ndarray]:
        """One speculative verify step over tracked, in-decode sequences:
        feed ``[next_token, d_1..d_K]`` as ONE ragged chunk per sequence
        (the packed-batch path already supports multi-token chunks), accept
        the longest prefix of drafts matching the model's OWN greedy argmax
        at each position, commit the accepted KV and roll the rejected tail
        back through ``DSStateManager.rollback_to``.

        ``first_tokens[i]`` — the sequence's pending next token (exactly as
        :meth:`decode` takes it); ``draft_tokens[i]`` — up to ``k`` proposed
        continuations (shorter drafts are padded; a pad is only ever
        accepted when it happens to EQUAL the greedy choice, so parity is
        unconditional). Returns one 1-D int32 array per sequence: the newly
        committed tokens — the accepted drafts plus one bonus token from
        the verify logits. Always at least 1, at most ``k + 1``; the LAST
        entry is the new pending token (its KV is not yet materialized),
        exactly like the final column of :meth:`decode`'s output.

        ``eos_token_ids`` (scalar or per-sequence list with ``None``
        entries): an eos landing INSIDE the accepted run truncates the
        commit there — the returned tokens end at the eos, and KV/history
        past it is rolled back before publish, so the radix tree never
        receives post-eos paths (the same contract as :meth:`decode`'s
        eos rewind).

        ``draft_tokens[i]`` may also be a LIST of candidate branches
        (token-tree verification): the branches flatten into one ragged
        chunk — root (the pending token) + every branch at its own KV
        slots, ancestors-only attention via the tree mask in
        ``_ragged_step`` — and the DEEPEST branch matching the target's own
        argmax at each step wins; the winner's KV compacts to the canonical
        contiguous positions and every rejected branch rolls back, so a
        rejected sibling can never reach the radix tree. Tree verification
        is greedy-only.

        ``sampling``: per-sequence :class:`SamplingParams` (None entries =
        greedy rows). With any temperature > 0 the verify step switches to
        speculative REJECTION sampling (``sampling.spec_verify_draws``):
        draft ``d_i`` survives with probability ``p_i(d_i)`` under the
        target's tempered/top-p distribution and a rejection resamples the
        normalized residual — the committed stream is distributed exactly
        as direct sampling, so speculation stays a pure throughput lever
        at any temperature. Linear drafts only.

        Compiled once per (token-bucket, seq-bucket, K, tree, sampled);
        rollback is free — accepted tokens just advance ``seen_tokens``,
        rejected drafts release block-table tail refs via the PR 3
        refcount machinery."""
        batch_uids = list(batch_uids)
        if self._block:
            raise NotImplementedError(
                f"speculate_decode (speculative decoding, token-tree verification) of a model with "
                f"diffusion_block_size={self._block}: drafts are verified against a causal next token, which a "
                "block generated by masked diffusion does not have")
        if self._latent:
            raise NotImplementedError(
                "speculate_decode (speculative decoding, token-tree verification) of a model with latent attention: "
                "the verify step and the tree's mask have not been shown equal to the reference over a latent pool")
        hb = self._health
        gl = self.goodput_ledger
        if gl is None and not hb.enabled:
            return self._speculate(batch_uids, first_tokens, draft_tokens, k, eos_token_ids,
                                   sampling)
        if gl is not None:
            self._gp_last_uids = batch_uids
            t_gp = time.perf_counter()
        if hb.enabled:
            hb.begin("serving")
            get_flight_recorder().record("serving", "speculate", seqs=len(batch_uids),
                                         k=int(k) if k is not None else -1)
        try:
            return self._speculate(batch_uids, first_tokens, draft_tokens, k, eos_token_ids,
                                   sampling)
        finally:
            if hb.enabled:
                hb.end("serving")
            if gl is not None:
                gl.book("spec_verify", time.perf_counter() - t_gp)

    @_serving_compile_scope
    def _speculate(self, batch_uids, first_tokens, draft_tokens, k, eos_token_ids=None,
                   sampling=None):
        from .sampling import all_greedy, pack_sampling

        tr = get_tracer()
        reg = get_metrics()
        t0 = time.perf_counter() if reg.enabled else 0.0
        uids = list(batch_uids)
        S = len(uids)
        if self._state_layers or self._sparse:
            raise NotImplementedError(
                "speculate_decode (a linear draft or a token tree) of a model with a recurrent state layer or with "
                "pooled keys (a learned block selection): a rejected draft is rewound, and the state has consumed "
                "the draft and keeps no snapshot to return to, as the pooled keys have pooled the draft's keys")
        with tr.span("serving/spec_verify", tid="serving") as sp:
            with tr.span("serving/engine_batch", tid="serving"):
                firsts = [np.asarray(t, np.int32).reshape(-1) for t in first_tokens]
                # normalize drafts to per-sequence branch LISTS (a bare array is one
                # linear branch — the PR 9 call surface unchanged)
                branches: List[List[np.ndarray]] = []
                for d in draft_tokens:
                    bl = [np.asarray(b, np.int32).reshape(-1) for b in d] \
                        if isinstance(d, (list, tuple)) else [np.asarray(d, np.int32).reshape(-1)]
                    branches.append([b for b in bl if b.size])
                tree = any(len(bl) > 1 for bl in branches)
                sampled = not all_greedy(sampling)
                if tree and sampled:
                    raise ValueError("token-tree verification is greedy-only; a sampled request "
                                     "verifies one linear draft via rejection sampling")
                if k is None:
                    k = max((b.size for bl in branches for b in bl), default=0)
                k = int(k)
                if k < 1:
                    raise ValueError("speculate_decode needs k >= 1 (use decode() for plain steps)")
                assert all(t.size == 1 for t in firsts), \
                    "speculate_decode takes exactly one pending next token per sequence"
                if any(b.size > k for bl in branches for b in bl):
                    raise ValueError(f"draft longer than k={k}")
                W = max((len(bl) for bl in branches), default=1) if tree else 1
                n_new = 1 + W * k  # fed chunk length: root + every (padded) branch
                if len(set(uids)) != len(uids) or S > self.batch.max_seqs:
                    raise SchedulingError(SchedulingResult.BatchSequenceLimitExceeded)
                if S * n_new > self.batch.max_tokens:
                    raise SchedulingError(SchedulingResult.TokenLimitExceeded)
                seqs = []
                for uid in uids:
                    seq = self.state_manager.get_sequence(uid)
                    if seq is None:
                        raise SchedulingError(SchedulingResult.EngineSequenceLimitExceeded)
                    if seq.seen_tokens + n_new > self._max_context:
                        raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)
                    seqs.append(seq)
                if sum(s.blocks_needed(n_new) for s in seqs) > self.state_manager.available_blocks:
                    raise SchedulingError(SchedulingResult.KVCacheLimitExceeded)

                # uniform chunks; short drafts/branch lists pad by repeating their
                # last token (branch 0 clones for missing branches): pads ride the
                # forward like any draft and only ever COMMIT when they equal the
                # target's own choice, so parity is unconditional
                chunks, padded = [], []
                for f, bl in zip(firsts, branches):
                    if tree:
                        bl = list(bl) or [np.full(k, int(f[0]), np.int32)]
                        while len(bl) < W:
                            bl.append(bl[0])
                        pb = [np.concatenate([b, np.full(k - b.size,
                                                         int(b[-1]) if b.size else int(f[0]),
                                                         np.int32)]) for b in bl]
                        padded.append(pb)
                        chunks.append(np.concatenate([f] + pb))
                    else:
                        d = bl[0] if bl else np.empty(0, np.int32)
                        pad = np.full(k - d.size, int(d[-1]) if d.size else int(f[0]), np.int32)
                        padded.append([np.concatenate([d, pad])])
                        chunks.append(np.concatenate([f, d, pad]))
                starts = [s.seen_tokens for s in seqs]
                self.batch.clear()
                for seq, c in zip(seqs, chunks):
                    # note BEFORE the forward, like _put: history mirrors the fed
                    # chunk; commit_speculative/rollback_to reconcile it afterwards
                    self.state_manager.note_tokens(seq, c)
                    self.state_manager.allocate_blocks(seq, n_new)
                    seq.pre_forward(n_new)
                    self.batch.insert_sequence(seq, c)
                rb = self.batch.finalize()
                t_bucket, s_bucket = rb.token_ids.shape[0], rb.block_tables.shape[0]

            kv = self.state_manager.kv_cache
            with tr.span("serving/engine_dispatch", tid="serving") as sd:
                n_programs = len(self._compiled)
                fn = self._get_compiled_verify(t_bucket, s_bucket, n_new - 1, tree=tree,
                                               sampled=sampled)
                if tree:
                    # per-token tree metadata rows [pos_ids | branch | depth]: node
                    # 0 is the shared root at depth 0; branch b's nodes carry depth
                    # 1..k and LOGICAL position start + depth (their KV slots stay
                    # flat — the mask in _ragged_step keeps siblings invisible)
                    meta = np.zeros((3, t_bucket), np.int32)
                    depth_row = np.concatenate([[0]] + [np.arange(1, k + 1)] * W).astype(np.int32)
                    branch_row = np.concatenate([[0]] + [np.full(k, b) for b in range(W)]).astype(np.int32)
                    cur = 0
                    for start in starts:
                        meta[0, cur:cur + n_new] = start + depth_row
                        meta[1, cur:cur + n_new] = branch_row
                        meta[2, cur:cur + n_new] = depth_row
                        cur += n_new
                    out, pools = fn(self.params, jnp.asarray(rb.packed()),
                                    jnp.asarray(meta.reshape(-1)), kv.pools())
                elif sampled:
                    samp_f, seeds = pack_sampling(sampling, uids, s_bucket)
                    out, pools = fn(self.params, jnp.asarray(rb.packed()),
                                    jnp.asarray(samp_f), jnp.asarray(seeds), kv.pools())
                else:
                    out, pools = fn(self.params, jnp.asarray(rb.packed()), kv.pools())
                kv.update(*pools)
                if sd is not NULL_SPAN:
                    sd.set_args(compiled=len(self._compiled) > n_programs,
                                program="verify:%d:%d:%d%s%s" % (t_bucket, s_bucket, n_new - 1, ":tree" if tree else "",
                                                                 ":sampled" if sampled else ""))

            held = _observe(sp, lambda: dict(
                rows=S, tokens=S * n_new, bucket_tokens=int(t_bucket), bucket_rows=int(s_bucket), steps=1, k=k,
                tree_width=W, sampled=bool(sampled), kernel=self._kernel_of(t_bucket, s_bucket),
                uids=[int(u) for u in uids[:16]]))
            if eos_token_ids is None or isinstance(eos_token_ids, (int, np.integer)):
                eos_list = [eos_token_ids] * S
            else:
                eos_list = list(eos_token_ids)
                assert len(eos_list) == S, "eos_token_ids must match batch_uids"
            results = []
            drafted = accepted = 0
            accepts = []
            with tr.span("serving/engine_fetch", tid="serving"):
                if sampled:
                    acc_m = np.asarray(out[0][:S]).astype(bool)  # [S, k] accept bits
                    nxt_m = np.asarray(out[1][:S])               # [S, k+1] resample/bonus
                else:
                    rows = np.asarray(out[:S])  # [S, n_new] greedy argmax per position
            with tr.span("serving/engine_commit", tid="serving"):
                for i, (seq, c, start, bl, eos) in enumerate(zip(seqs, chunks, starts, branches,
                                                                 eos_list)):
                    src_dst = None
                    if sampled:
                        d = padded[i][0]
                        rej = np.nonzero(~acc_m[i])[0]
                        a = int(rej[0]) if rej.size else k
                        committed = list(c[1:1 + a]) + [int(nxt_m[i, a])]
                        path = c[1:1 + a]
                        real = int(bl[0].size) if bl else 0
                    elif tree:
                        row = rows[i]
                        # deepest-argmax-path walk: branch b's node at depth t+1 is
                        # accepted iff its token equals the argmax at its PARENT
                        # node (root for t=0); ties keep the first branch, so a
                        # padded branch-0 clone can never displace the original
                        a, bwin = -1, 0
                        for b in range(W):
                            pb = padded[i][b]
                            parents = np.concatenate(
                                [[0], 1 + b * k + np.arange(k - 1)]).astype(np.int64)
                            neq = np.nonzero(pb != row[parents])[0]
                            a_b = int(neq[0]) if neq.size else k
                            if a_b > a:
                                a, bwin = a_b, b
                        path = padded[i][bwin][:a]
                        bonus = int(row[0] if a == 0 else row[1 + bwin * k + a - 1])
                        committed = list(path) + [bonus]
                        if bwin != 0 and a > 0:
                            # winner's KV sits at its flat tree slots — move it to
                            # the canonical contiguous positions before rollback
                            src_dst = [(start + 1 + bwin * k + t, start + 1 + t)
                                       for t in range(a)]
                        real = int(bl[bwin].size) if bwin < len(bl) else 0
                        drafted += sum(int(b.size) for b in bl)
                    else:
                        row = rows[i]
                        neq = np.nonzero(c[1:] != row[:k])[0]
                        a = int(neq[0]) if neq.size else k
                        committed = list(row[:a + 1])
                        path = row[:a]
                        real = int(bl[0].size) if bl else 0
                    if eos is not None:
                        # an eos among the ACCEPTED tokens ends the stream there:
                        # commit through the eos only, so the post-eos accepted
                        # tail (KV + history) is rolled back with the rejects and
                        # never published (the bonus-position eos needs nothing —
                        # its KV was never materialized)
                        hit = np.nonzero(np.asarray(path)[:a] == eos)[0]
                        if hit.size:
                            a = int(hit[0])
                            committed = committed[:a + 1]
                            if src_dst is not None:
                                src_dst = src_dst[:a]
                    seq.post_forward()                       # seen = start + n_new
                    if tree:
                        self.state_manager.commit_speculative(
                            seq, start + 1 + a,
                            [int(c[0])] + [int(t) for t in committed[:a]], src_dst)
                    else:
                        self.state_manager.rollback_to(seq, start + 1 + a)
                    self.state_manager.publish_sequence(seq)  # accepted full blocks → tree
                    results.append(np.asarray(committed, np.int32))
                    if not tree:
                        drafted += real
                    accepted += min(a, real)  # pads excluded from the honest rate
                    accepts.append(a)
            _observe(sp, lambda: dict(drafted=drafted, accepted=accepts[:16]), held)
        self._spec_totals["drafted"] += drafted
        self._spec_totals["accepted"] += accepted
        if reg.enabled:
            reg.counter("serving/spec_drafted_tokens").inc(drafted)
            reg.counter("serving/spec_accepted_tokens").inc(accepted)
            reg.counter("serving/spec_rejected_tokens").inc(drafted - accepted)
            reg.gauge("serving/spec_accept_rate").set(
                self._spec_totals["accepted"] / max(1, self._spec_totals["drafted"]))
            dt = time.perf_counter() - t0
            reg.histogram("serving/spec_verify_ms").observe(dt * 1e3)
            reg.gauge("serving/spec_tokens_per_sec").set(
                sum(len(r) for r in results) / max(dt, 1e-9))
        return results

    def _note_compile(self, bucket):
        """Recompile-sentinel feed: a compiled-cache miss IS the moment XLA
        compiles a new (bucket) program — report it with this engine's own
        warmup-boundary verdict and the in-flight uids (joined to request
        ids when the replica registered a resolver)."""
        gp = get_goodput()
        if not gp.enabled:
            return
        uids = list(self._gp_last_uids or [])[:8]
        rids = None
        res = self.gp_rid_resolver
        if res is not None:
            try:
                rids = [res(u) for u in uids]
            except Exception:  # noqa: BLE001 — telemetry never raises
                rids = None
        gp.sentinel.note_compile("serving", bucket=bucket, warmed=self._gp_warmed,
                                 uids=uids, rids=rids)

    def declare_gp_warmed(self):
        """Declare this engine's recompile-sentinel warmup boundary without
        running :meth:`warmup` — for callers (bench, tests) that warmed the
        compiled-program cache with real traffic instead of zero
        descriptors. Every later compiled-cache miss is flagged."""
        self._gp_warmed = True
        gp = get_goodput()
        if gp.enabled:
            gp.sentinel.declare_warmed("serving")
        return self

    def _get_compiled_verify(self, t_bucket: int, s_bucket: int, k: int,
                             tree: bool = False, sampled: bool = False):
        key = ("verify", t_bucket, s_bucket, k, bool(tree), bool(sampled))
        if key not in self._compiled:
            bucket = (f"verify/t{t_bucket}/s{s_bucket}/k{k}"
                      f"{'/tree' if tree else ''}{'/sampled' if sampled else ''}")
            self._note_compile(bucket)
            step_fn = self._ragged_step
            mb = self._max_blocks_per_seq

            if sampled:
                from .sampling import spec_verify_draws

                def fwd(params, packed, samp_f, seeds, pools):
                    logits, pools = step_fn(params, packed, pools, t_bucket, s_bucket,
                                            gather_k=k)
                    with jax.named_scope(scopes.SAMPLE):
                        lg = logits.reshape(s_bucket, k + 1, -1)
                        last = packed[4 * t_bucket + s_bucket * mb:
                                      4 * t_bucket + s_bucket * mb + s_bucket]
                        idx = jnp.maximum(
                            last[:, None] - k + jnp.arange(k + 1, dtype=jnp.int32), 0)
                        chunk = packed[0:t_bucket][idx]                 # fed token rows
                        starts = packed[2 * t_bucket:3 * t_bucket][jnp.maximum(last, 0)] - k
                        accept, nxt = spec_verify_draws(lg, chunk, samp_f[:, 0], samp_f[:, 1],
                                                        seeds, starts)
                        return (accept.astype(jnp.int32), nxt), pools

                self._compiled[key] = jax.jit(fwd, donate_argnums=(4, ), **self._jit_options)
            elif tree:
                def fwd(params, packed, tree_meta, pools):
                    logits, pools = step_fn(params, packed, pools, t_bucket, s_bucket,
                                            gather_k=k, tree_meta=tree_meta)
                    with jax.named_scope(scopes.SAMPLE):
                        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        return toks.reshape(s_bucket, k + 1), pools

                self._compiled[key] = jax.jit(fwd, donate_argnums=(3, ), **self._jit_options)
            else:
                def fwd(params, packed, pools):
                    logits, pools = step_fn(params, packed, pools, t_bucket, s_bucket,
                                            gather_k=k)
                    with jax.named_scope(scopes.SAMPLE):
                        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        return toks.reshape(s_bucket, k + 1), pools

                self._compiled[key] = jax.jit(fwd, donate_argnums=(2, ), **self._jit_options)
            log_dist(f"compiled speculative verify bucket tokens={t_bucket} "
                     f"seqs={s_bucket} k={k} tree={tree} sampled={sampled}", ranks=[0])
        return self._compiled[key]

    def _get_compiled_decode(self, s_bucket: int, n_steps: int, sampled: bool = False):
        key = ("decode", s_bucket, n_steps, bool(sampled))
        self._await_ahead(key)
        if key not in self._compiled:
            bucket = f"decode/s{s_bucket}/n{n_steps}{'/sampled' if sampled else ''}"
            self._note_compile(bucket)
            from .ragged.ragged_wrapper import unpack_descriptors

            max_blocks = self._max_blocks_per_seq
            step_fn = self._ragged_step
            moe = self._counts
            stats0 = (jnp.zeros(3, jnp.int32), ) if moe else ()

            def merge(stats, new):
                """The routing counts (none for a dense model) over the steps."""
                with jax.named_scope(scopes.MOE):
                    return tuple(merge_routing_stats(a, b) for a, b in zip(stats, new))

            if sampled:
                from .sampling import sample_tokens

                def fwd(params, packed, samp_f, seeds, pools):
                    with jax.named_scope(scopes.EMBED):
                        token_ids = unpack_descriptors(packed, s_bucket, s_bucket, max_blocks)[0]
                        pos_row = packed[2 * s_bucket:3 * s_bucket]

                    def step(carry, t):
                        toks, pl, stats = carry
                        with jax.named_scope(scopes.EMBED):
                            stepped = packed.at[0:s_bucket].set(toks) \
                                            .at[2 * s_bucket:3 * s_bucket].add(t)
                        logits, pl, *new = step_fn(params, stepped, pl, s_bucket, s_bucket,
                                                   moe_stats=moe, one_token_rows=True)
                        # draw keyed by the NEW token's absolute position —
                        # the same stream the sampled put path would produce
                        with jax.named_scope(scopes.SAMPLE):
                            nxt = sample_tokens(logits, samp_f[:, 0], samp_f[:, 1], seeds,
                                                pos_row + t + 1)
                        return (nxt, pl, merge(stats, new)), nxt

                    (_, pools, stats), out = jax.lax.scan(
                        step, (token_ids, pools, stats0), jnp.arange(n_steps, dtype=jnp.int32))
                    with jax.named_scope(scopes.SAMPLE):
                        return (out.T, *stats), pools  # [S, n_steps]

                self._compiled[key] = jax.jit(fwd, donate_argnums=(4, ), **self._jit_options)
            else:
                def fwd(params, packed, pools):
                    with jax.named_scope(scopes.EMBED):
                        token_ids = unpack_descriptors(packed, s_bucket, s_bucket, max_blocks)[0]

                    def step(carry, t):
                        toks, pl, stats = carry
                        # feed the greedy tokens back into the packed descriptor
                        # and advance positions in-scan from the packed starts
                        # (packed layout: [T ids][T seq_idx][T pos]...)
                        with jax.named_scope(scopes.EMBED):
                            stepped = packed.at[0:s_bucket].set(toks) \
                                            .at[2 * s_bucket:3 * s_bucket].add(t)
                        logits, pl, *new = step_fn(params, stepped, pl, s_bucket, s_bucket,
                                                   moe_stats=moe, one_token_rows=True)
                        with jax.named_scope(scopes.SAMPLE):
                            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                        return (nxt, pl, merge(stats, new)), nxt

                    (_, pools, stats), out = jax.lax.scan(
                        step, (token_ids, pools, stats0), jnp.arange(n_steps, dtype=jnp.int32))
                    with jax.named_scope(scopes.SAMPLE):
                        return (out.T, *stats), pools  # [S, n_steps]

                self._compiled[key] = jax.jit(fwd, donate_argnums=(2, ), **self._jit_options)
            log_dist(f"compiled multi-step decode bucket seqs={s_bucket} steps={n_steps} "
                     f"sampled={sampled}", ranks=[0])
        return self._compiled[key]

    @_serving_compile_scope
    def warmup(self, seq_buckets: Iterable[int], decode_steps,
               token_buckets: Iterable[int] = (), put_samples=("greedy", ),
               declare_warmed: bool = True) -> List[dict]:
        """Pre-compile the lazy shape buckets at startup so the first real
        request does not pay the XLA compile inside its TTFT.

        ``seq_buckets``: sequence counts, each rounded UP to the wrapper's
        static bucket (the same rounding ``decode`` applies); ``decode_steps``:
        one scan horizon or an iterable of them. ``token_buckets`` (optional):
        prefill token counts — each (token-bucket x seq-bucket x sample mode
        in ``put_samples``) ``put`` program is ALSO pre-compiled, closing the
        warmup gap the recompile sentinel otherwise names on the first real
        prefill. Each distinct program is traced, compiled, and executed once
        on an all-zero descriptor against the real (donated-through) KV
        pools, so the jit executable cache holds exactly the signature real
        traffic hits. The zero descriptor scribbles into pool block 0, which
        is harmless before any sequence exists but NOT after — warmup
        therefore refuses to run once sequences are tracked. Each compile is
        recorded as a ``jax_compile`` event on the trace bus (``args.source``
        = "warmup"). Completion declares this engine's recompile-sentinel
        warmup boundary: with the goodput plane armed, every LATER compile of
        a new bucket is flagged as an unexpected steady-state recompile.
        Returns ``[{"seqs", "steps", "seconds", "cached"}, ...]`` (prefill
        entries carry ``"tokens"``/``"sample"`` instead of ``"steps"``).
        """
        if self.state_manager.n_tracked_sequences:
            raise RuntimeError("warmup() must run before serving traffic: its zero descriptor "
                               "writes into KV block 0, which live sequences may own")
        pc = self.state_manager.prefix_cache
        if pc is not None and pc.n_cached_blocks:
            # flushed sequences leave their blocks in the radix tree — block 0
            # may be cache-held, and the zero descriptor would scribble on its
            # KV. Dropping the (re-computable) cache keeps warmup safe.
            pc.clear()
        # materialize: a one-shot iterable would be exhausted by the first
        # seq bucket, silently leaving later buckets un-warmed
        decode_steps = (decode_steps, ) if isinstance(decode_steps, int) else tuple(decode_steps)
        tracer = get_tracer()
        kv = self.state_manager.kv_cache
        max_blocks = self._max_blocks_per_seq
        results = []
        s_buckets = [next_bucket(int(w), self.batch.seq_buckets) for w in seq_buckets]
        for s_bucket in s_buckets:
            for n_steps in decode_steps:
                n_steps = int(n_steps)
                B = self._block or 1  # a block-diffusion model: n_steps // B blocks of B tokens a row
                key = ("diffuse", s_bucket, self._block_program_cap(n_steps // B), ()) if self._block \
                    else ("decode", s_bucket, n_steps, False)
                if key in self._compiled and key not in self._ahead:  # one compiled ahead that no call has run runs below
                    results.append({"seqs": s_bucket, "steps": n_steps, "seconds": 0.0, "cached": True})
                    continue
                fn = self._get_compiled_blocks(s_bucket, n_steps // B) if self._block \
                    else self._get_compiled_decode(s_bucket, n_steps)
                # packed layout [T ids][T idx][T pos][T valid][S*max_blocks][S last]
                # with T == S on the decode path (S * B for a block-diffusion model, whose
                # descriptor ends in the blocks to advance: none here)
                packed = jnp.zeros(packed_len(s_bucket * B, s_bucket, max_blocks, bool(self._state_layers))
                                   + bool(self._block), jnp.int32)
                t0 = time.perf_counter()
                toks, pools = fn(self.params, packed, kv.pools())
                jax.block_until_ready(toks)
                kv.update(*pools)
                dt = time.perf_counter() - t0
                tracer.complete("jax_compile", t0, dt, tid="compile",
                                args={"source": "warmup", "seqs": s_bucket, "steps": n_steps})
                log_dist(f"warmup compiled decode bucket seqs={s_bucket} steps={n_steps} "
                         f"in {dt:.2f}s", ranks=[0])
                results.append({"seqs": s_bucket, "steps": n_steps, "seconds": dt, "cached": False})
        for sample in put_samples:
            if sample not in (None, "greedy"):
                # the 'sample' variant takes extra per-request sampling
                # operands this zero-descriptor path does not build
                raise ValueError(f"warmup(put_samples=...) supports None/'greedy', got {sample!r}")
        for want_t in token_buckets or ():
            t_bucket = next_bucket(int(want_t), self.batch.token_buckets)
            for s_bucket in s_buckets:
                if s_bucket > t_bucket:
                    continue  # a prefill batch never has more rows than tokens
                for sample in put_samples:
                    key = (t_bucket, s_bucket, sample)
                    if key in self._compiled and key not in self._ahead:
                        results.append({"seqs": s_bucket, "tokens": t_bucket,
                                        "sample": sample, "seconds": 0.0, "cached": True})
                        continue
                    fn = self._get_compiled(t_bucket, s_bucket, sample)
                    # put-path packed layout: [T ids][T idx][T pos][T valid]
                    # [S*max_blocks][S last]
                    packed = jnp.zeros(packed_len(t_bucket, s_bucket, max_blocks, bool(self._state_layers)), jnp.int32)
                    t0 = time.perf_counter()
                    out, pools = fn(self.params, packed, kv.pools())
                    jax.block_until_ready(out)
                    kv.update(*pools)
                    dt = time.perf_counter() - t0
                    tracer.complete("jax_compile", t0, dt, tid="compile",
                                    args={"source": "warmup", "tokens": t_bucket,
                                          "seqs": s_bucket, "sample": sample})
                    log_dist(f"warmup compiled prefill bucket tokens={t_bucket} "
                             f"seqs={s_bucket} sample={sample} in {dt:.2f}s", ranks=[0])
                    results.append({"seqs": s_bucket, "tokens": t_bucket,
                                    "sample": sample, "seconds": dt, "cached": False})
        # warmup boundary declared at COMPLETION: later bucket compiles on
        # this engine are steady-state recompiles the sentinel flags. A
        # caller warming in several calls (the replica's per-entry loop)
        # passes declare_warmed=False and declares once after the last.
        if declare_warmed:
            self.declare_gp_warmed()
        return results

    # ------------------------------------------------------------------
    def compile_ahead(self, programs):
        """Start compiling ``programs`` on two threads of their own and return
        at once. Compiling keeps the HOST busy and the device idle (tracing,
        lowering, XLA, or reading an executable back from the persistent
        cache: 8 to 40 s a program of a 16-layer model), so a start-up that
        has work for the DEVICE meanwhile (loading, a self-check) need not
        take both in turn. Two threads, because tracing holds the
        interpreter's lock and XLA and the cache's reads do not: a third gains
        little. ``programs``, in the order wanted: ``("decode", seqs,
        steps)``, the greedy multi-step decode program, and ``("put", tokens,
        seqs, sample)`` with ``sample`` ``None``, ``"greedy"`` or ``"probe"``,
        each rounded up to its bucket as :meth:`warmup` rounds. Nothing is
        executed and no pool is touched: a program is lowered from shapes and
        compiled ahead of time, and the executable takes the jitted
        function's place in the engine's table. A call that needs a program
        still in the making waits for it (and raises what its compile
        raised). Returns the futures; ``warmup`` afterwards runs once, on its
        zero descriptor, each of them that no call has run yet (a program's
        first run is part of warming it), reports the others ``"cached"`` and
        declares the boundary."""
        from concurrent.futures import ThreadPoolExecutor

        if self._block:
            raise NotImplementedError("compile_ahead: a block-diffusion model's decode programs are warmup()'s")
        shapes = lambda tree: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=getattr(a, "sharding", None)), tree)
        params, pools = shapes(self.params), shapes(self.state_manager.kv_cache.pools())
        jobs = []
        for kind, *spec in programs:
            if kind not in ("decode", "put"):
                raise ValueError(f"compile_ahead: unknown program kind {kind!r}: 'decode' | 'put'")
            s_bucket = next_bucket(int(spec[-2 if kind == "put" else 0]), self.batch.seq_buckets)
            t_bucket = next_bucket(int(spec[0]), self.batch.token_buckets) if kind == "put" else s_bucket
            key = (t_bucket, s_bucket, spec[2]) if kind == "put" else ("decode", s_bucket, int(spec[1]), False)
            if key in self._compiled:
                continue
            # the jitted function is made HERE, so that a later call finds the key and waits for its future
            fn = self._get_compiled(*key) if kind == "put" else self._get_compiled_decode(s_bucket, int(spec[1]))
            packed = jax.ShapeDtypeStruct(
                (packed_len(t_bucket, s_bucket, self._max_blocks_per_seq, bool(self._state_layers)), ), jnp.int32)
            jobs.append((key, fn, packed))

        def compile_one(key, fn, packed):
            prev = push_compile_source("serving")
            try:
                self._compiled[key] = fn.lower(params, packed, pools).compile()
            finally:
                pop_compile_source(prev)

        pool = ThreadPoolExecutor(2, thread_name_prefix="compile-ahead")
        for job in jobs:
            self._ahead[job[0]] = pool.submit(compile_one, *job)
        pool.shutdown(wait=False)
        return [self._ahead[key] for key, _, _ in jobs]

    def compiled_horizon(self, rows: int, wanted: int, sampled: bool = False) -> int:
        """The multi-step decode horizon a scheduler that wants ``wanted``
        steps for ``rows`` rows should ask for. Before the warm-up boundary is
        declared (:meth:`warmup`, :meth:`declare_gp_warmed`) that is
        ``wanted``: the program compiles at its first call, as ever. After it
        a compile is a stall in steady state, so it is the longest horizon of
        at most ``wanted`` steps that the engine HAS for these rows, and
        ``wanted`` only where it has none: a replica may warm fewer horizons
        than the scheduler's six (each is a program of the whole model,
        seconds of its start-up) and is then served by those."""
        if not self._gp_warmed:
            return int(wanted)
        s_bucket = next_bucket(int(rows), self.batch.seq_buckets)
        have = [k[2] for k in list(self._compiled)  # a snapshot: another thread may add a program meanwhile
                if len(k) == 4 and k[0] == "decode" and k[1] == s_bucket and k[3] == bool(sampled) and k[2] <= wanted]
        return max(have, default=int(wanted))

    def _await_ahead(self, key):
        """Wait for ``key``'s program if :meth:`compile_ahead` is making it."""
        if self._ahead:
            future = self._ahead.pop(key, None)
            if future is not None:
                future.result()

    # ------------------------------------------------------------------
    def query(self, uid: Optional[int] = None):
        """Sequence / engine state introspection (reference ``query:153``)."""
        return self.state_manager.query(uid)

    def flush(self, uid: int) -> None:
        """Finish a sequence and release its KV blocks (reference ``flush:228``)."""
        self.state_manager.flush_sequence(uid)

    def serialize(self, save_path: str) -> None:
        """Persist the engine's (possibly transformed — int8, etc.) params +
        model/engine metadata (reference ``serialize:237`` saves the
        flattened params + metadata per TP rank; tensorstore writes each
        host's shards, so one call covers every rank here)."""
        import dataclasses
        import os
        import pickle

        from ...runtime.checkpoint_engine.orbax_checkpoint_engine import OrbaxCheckpointEngine

        eng = OrbaxCheckpointEngine()
        eng.save({"module": self.params}, save_path)
        from ..quantization import QuantizedWeight, QuantizedWeight4

        _q = (QuantizedWeight, QuantizedWeight4)
        mc = self.model_config
        quantized = any(isinstance(x, _q) for x in jax.tree_util.tree_leaves(
            self.params, is_leaf=lambda x: isinstance(x, _q)))
        meta = {"model_config": dataclasses.asdict(mc) if dataclasses.is_dataclass(mc)
                else dict(getattr(mc, "__dict__", {})),
                "quantized": quantized,  # from the params themselves, not an impl name
                "kv_block_size": self.config.kv_block_size}
        with open(os.path.join(os.path.abspath(save_path), "engine_meta.pkl"), "wb") as f:
            pickle.dump(meta, f)
        log_dist(f"InferenceEngineV2 serialized to {save_path}", ranks=[0])

    @property
    def max_context(self) -> int:
        """Per-sequence context ceiling in tokens (prompt + generation),
        after the model's own ``max_seq_len`` clamp. Public so the request
        plane (``deepspeed_tpu/serving/``) can validate without reaching
        into engine internals — the ``tools/check_gateway_api.py`` gate
        forbids it anything non-public."""
        return self._max_context

    @property
    def max_concurrent_sequences(self) -> int:
        """Sequences one ragged forward may carry (the scheduler/batch
        ceiling) — the request plane's default in-flight bound."""
        return self.config.state_manager.max_ragged_sequence_count

    @property
    def free_blocks(self) -> int:
        return self.state_manager.free_blocks

    @property
    def available_blocks(self) -> int:
        """Free-list blocks plus what prefix-cache eviction could reclaim."""
        return self.state_manager.available_blocks

    @property
    def prefix_cache(self):
        """The :class:`PrefixKVCache` radix tree (None when disabled)."""
        return self.state_manager.prefix_cache

    @property
    def cache_telemetry(self):
        """The :class:`CacheTelemetry` plane (None unless the
        ``ragged.prefix_cache.telemetry`` block is enabled)."""
        return self.state_manager.cache_telemetry

    @property
    def tiered_store(self):
        """The host/disk KV capacity tier (None unless the
        ``ragged.prefix_cache.host_tier`` block is present and enabled)."""
        return self.state_manager.tiered_store

    def shutdown(self) -> None:
        """Stop background workers this engine owns (currently the KV
        tier's migration thread). Idempotent; a no-op without a tier."""
        self.state_manager.shutdown()

    # -- HBM attribution (monitor/memory.py) ----------------------------
    def _memory_sections(self):
        # per-host shard bytes (the pools shard over the model axis under
        # TP — the global logical size would over-count on multi-host)
        kv_bytes = tree_device_bytes(self.state_manager.kv_cache.pools())
        if self._memory_role is not None:
            return {self._memory_role: tree_device_bytes(self.params) + kv_bytes}
        return {"params": tree_device_bytes(self.params),
                "kv_block_pool": kv_bytes}

    def set_memory_role(self, role: Optional[str]) -> None:
        """Re-file this engine's bytes under one named section (a
        speculative draft engine reports as ``spec_draft_engine`` instead
        of inflating the primary ``params``/``kv_block_pool`` rows)."""
        self._memory_role = role

    # -- tenant metering (serving/metering.py) ---------------------------
    def set_tenant_meter(self, meter) -> None:
        """Attach a gateway ``TenantMeter``: builds this engine's
        :class:`~deepspeed_tpu.serving.metering.EngineMeterView` (block ids
        are engine-local) and wires it into the block-lifecycle hooks —
        allocator allocate/free (the CacheTelemetry surface), owner
        stamping in the state manager, and the prefix cache's tenant-level
        publish/hit/evict forwards. The ONE public entry the request plane
        is allowed to use (``tools/check_gateway_api.py`` keeps serving/
        out of engine internals). Idempotent per meter; ``None`` detaches."""
        if meter is None:
            view = self.state_manager.tenant_meter
            if self._tenant_meter is not None and view is not None:
                # settle the view's in-flight residency charges and stop it
                # contributing to reports (a detached view can never see
                # on_free again — kept live it would accrue phantom
                # block-seconds forever)
                self._tenant_meter.drop_view(view)
            self._tenant_meter = None
            self.state_manager.set_tenant_meter(None)
            return
        if self._tenant_meter is meter:
            return  # replica restart: keep the live view (owner stamps survive)
        self._tenant_meter = meter
        view = meter.engine_view(self.state_manager.kv_cache.total_blocks)
        self.state_manager.set_tenant_meter(view)

    def probe_prefix(self, prompt_tokens):
        """PURE prefix lookup (no references taken, no LRU touch, no stats):
        ``(n_cached_tokens, n_shared_full_blocks, n_tree_only, match)`` the
        cache would serve for this prompt. Admission uses it for budget math
        BEFORE committing — a refused request must leave the tree untouched.
        ``n_tree_only`` counts the hit's shared blocks whose sole holder is
        currently the tree: acquisition pins them, so they must come OFF the
        evictable supply in any admission check that subtracts the hit from
        the demand side (counting them on both sides over-admits)."""
        pc = self.state_manager.prefix_cache
        if pc is None:
            return 0, 0, 0, None
        m = pc.match(np.asarray(prompt_tokens, np.int32).reshape(-1))
        tree_only = sum(1 for b in m.shared_blocks
                        if self.state_manager.kv_cache.refcount(b) == 1)
        return m.n_cached_tokens, len(m.shared_blocks), tree_only, m

    def acquire_prefix(self, uid: int, prompt_tokens, match=None,
                       tenant=None) -> Tuple[int, int]:
        """Create the sequence for ``uid`` pre-populated from the prefix
        cache (the scheduler's admission-side entry: it knows the FULL
        prompt, so the match is not limited to the first SplitFuse chunk).
        ``match`` — the object from :meth:`probe_prefix` — skips the
        re-match (valid as long as nothing mutated the tree in between).
        ``tenant`` — the requesting owner identity, stamped on the sequence
        (and its blocks / published tree nodes) when the metering plane is
        attached; None = untenanted.
        Returns ``(n_cached_tokens, n_shared_full_blocks)`` — the scheduler
        feeds ``prompt[n_cached:]`` and charges only the uncached tokens.
        Roll back an abandoned acquisition with ``flush(uid)``."""
        seq, skip = self._create_with_prefix(
            uid, np.asarray(prompt_tokens, np.int32).reshape(-1), match=match,
            tenant=tenant)
        return skip, seq.shared_blocks

    def export_sequence_kv(self, uid: int, tokens):
        """Functional D2H export of one live sequence's FULL KV blocks for a
        cross-replica handoff (``serving/handoff.py``): returns
        ``(token_chunks, payloads)`` — per-block token-id tuples and their
        ``read_block`` value snapshots materialized to numpy. Driver-thread
        only (``read_block`` is a device op); the snapshots are plain host
        arrays afterwards, so the broker can checksum/ship them from any
        thread. ``tokens`` is the prompt + generated-so-far stream; export
        is clamped to the KV the engine has actually materialized
        (``seen_tokens``) — the KV for the newest generated token does not
        exist yet, and partial blocks never travel (the tree only holds
        full blocks, same rule as ``publish``)."""
        sm = self.state_manager
        if self._state_layers or self._sparse:
            raise NotImplementedError(
                "export_sequence_kv of a model with a recurrent state layer or with pooled keys (a learned block "
                "selection): the handoff ships K/V blocks, and the receiving replica would resume with them and no "
                "state and no pooled keys; both would have to travel beside them")
        seq = sm.get_sequence(uid)
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        bs = self.config.kv_block_size
        n = min(int(seq.seen_tokens), tokens.size)
        n_full = min(n // bs, len(seq.kv_blocks))
        chunks, payloads = [], []
        for i in range(n_full):
            payloads.append(tuple(None if a is None else np.asarray(a)
                                  for a in sm.kv_cache.read_block(seq.kv_blocks[i])))
            chunks.append(tuple(int(t) for t in tokens[i * bs:(i + 1) * bs]))
        return chunks, payloads

    def install_prefix_kv(self, token_chunks, payloads, tenant=None) -> int:
        """Receiving half of the handoff: adopt exported block payloads into
        this engine's prefix cache as HOST-tier residents
        (:meth:`PrefixKVCache.install_host_chain`). Host-memory ops only —
        callable off this replica's driver thread. Returns blocks installed
        (0 when the prefix cache or host tier is absent: the resume then
        simply re-prefills, correct but uncached)."""
        pc = self.state_manager.prefix_cache
        if pc is None:
            return 0
        return pc.install_host_chain(token_chunks, payloads, tenant=tenant)

    def _create_with_prefix(self, uid: int, prompt_tokens, match=None, tenant=None):
        """Sequence creation + the monitor's view of the lookup: hit-rate
        gauge, cached-token counters, and a ``prefix_hit`` trace span. When
        the hit landed on a demoted chain, the synchronous H2D promotion
        wait the request just ate is booked as ``input_wait``-class goodput
        and emitted as a ``serving/promote_wait`` span — a tier that slows
        admission must show up in the ledger, never silently."""
        pc = self.state_manager.prefix_cache
        pw0 = pc.stats["promote_wait_s"] if pc is not None else 0.0
        t0 = time.perf_counter()
        seq, skip = self.state_manager.create_sequence_with_prefix(uid, prompt_tokens,
                                                                   match=match,
                                                                   tenant=tenant)
        if pc is not None:
            m = get_metrics()
            m.counter("serving/prefix_lookups").inc()
            m.gauge("serving/prefix_hit_rate").set(pc.hit_rate)
            if skip:
                m.counter("serving/prefix_hits").inc()
                m.counter("serving/prefix_cached_tokens").inc(skip)
                get_tracer().instant("prefix_hit", tid="serving", uid=int(uid),
                                     tokens=int(skip), blocks=len(seq.kv_blocks))
            promote_wait = pc.stats["promote_wait_s"] - pw0
            if promote_wait > 0.0:
                gl = self.goodput_ledger
                if gl is not None:
                    gl.book("input_wait", promote_wait)
                tr = get_tracer()
                if tr.enabled:
                    tr.complete("serving/promote_wait", t0, promote_wait,
                                tid="serving", args={"uid": int(uid)})
        return seq, skip

    # ------------------------------------------------------------------
    def _get_compiled(self, t_bucket: int, s_bucket: int, sample: Optional[str] = None):
        key = (t_bucket, s_bucket, sample)
        self._await_ahead(key)
        if key not in self._compiled:
            bucket = f"put/t{t_bucket}/s{s_bucket}/{sample or 'logits'}"
            self._note_compile(bucket)
            if sample not in (None, "greedy", "sample") and not (sample == "probe" and self._sparse):
                raise ValueError(f"unsupported sample mode {sample!r}: None | 'greedy' | 'sample' (and 'probe', logits "
                                 "and beside them what a few tokens a row selected and attended, of a model that "
                                 "selects blocks)")
            step_fn = self._ragged_step
            mb = self._max_blocks_per_seq
            moe = self._counts  # the step's result then carries the routing counts (or a selection's blocks read)

            if sample == "sample":
                from .sampling import sample_tokens

                def fwd(params, packed, samp_f, seeds, pools):
                    logits, pools, *stats = step_fn(params, packed, pools, t_bucket, s_bucket,
                                                    moe_stats=moe)
                    with jax.named_scope(scopes.SAMPLE):
                        last = packed[4 * t_bucket + s_bucket * mb:
                                      4 * t_bucket + s_bucket * mb + s_bucket]
                        # key each draw by the sampled token's OWN position:
                        # replay-deterministic for a fixed (seed, prompt) and
                        # independent of batch composition
                        ctr = packed[2 * t_bucket:3 * t_bucket][jnp.maximum(last, 0)] + 1
                        toks = sample_tokens(logits, samp_f[:, 0], samp_f[:, 1], seeds, ctr)
                        return (toks, *stats), pools

                self._compiled[key] = jax.jit(fwd, donate_argnums=(4, ), **self._jit_options)
            else:
                def fwd(params, packed, pools):
                    logits, pools, *stats = step_fn(params, packed, pools, t_bucket, s_bucket,
                                                    moe_stats=moe, probe=sample == "probe")
                    with jax.named_scope(scopes.SAMPLE):
                        out = jnp.argmax(logits, axis=-1).astype(jnp.int32) if sample == "greedy" else logits
                        return (out, *stats), pools

                self._compiled[key] = jax.jit(fwd, donate_argnums=(2, ), **self._jit_options)
            log_dist(f"compiled ragged forward bucket tokens={t_bucket} seqs={s_bucket} "
                     f"sample={sample}", ranks=[0])
        return self._compiled[key]
