"""Ragged (flat-token) transformer forward over a paged KV pool.

This is the TPU analog of the reference FastGen data plane
(``inference/v2/model_implementations/inference_transformer_base.py`` —
``DSTransformerModelBase.forward``: per layer qkv gemm →
``linear_blocked_kv_rotary`` (rotary + append to paged KV) → ``blocked_flash``
attention over the block table → mlp → ``logits_gather`` for the last token of
each sequence). Here the whole thing is ONE jitted function over bucket-padded
arrays:

  - tokens are a flat [T] buffer mixing prefill chunks and decode steps of
    many sequences (Dynamic SplitFuse composition);
  - KV append is a scatter into the flat pool at
    ``block_table[seq, pos // bs] * bs + pos % bs`` (invalid/padding tokens
    scatter out-of-bounds with mode='drop'); what is appended is the model's
    entry (``TransformerConfig.kv_entry``): per-head K and V into two pools
    ``[L, NB*bs, nkv, d]``, or ONE latent entry a token into one pool
    ``[L, NB*bs, 1, width]`` (latent attention);
  - attention gathers each sequence's context from the pool by block table
    and masks ``ctx_pos <= token_pos`` — numerics-reference path; the Pallas
    paged kernel (``ops/pallas/paged_attention.py``) replaces the gather on
    real TPU;
  - only each sequence's last token is projected to the vocabulary
    (``logits_gather`` semantics).

Works with the same stacked param pytree as ``models.transformer`` training,
so a trained checkpoint serves directly.
"""

from typing import Any, Dict

import math

import jax
import jax.numpy as jnp
import numpy as np

from ....models.transformer import TransformerConfig, apply_rope, mlp_activation, rope_inv_freq, rope_table
from ....monitor import scopes
from ....moe.grouped import merge_routing_stats
from ....ops.pallas.kda import kda_chunks, kda_step
from ....ops.pallas.lightning import lightning_chunks, lightning_step
from ....ops.pallas.mamba2 import mamba2_chunks, mamba2_step
from .sparse_index import select_blocks, update_pooled_keys

# tokens a row whose selection and attention output ``ragged_forward(probe=True)`` hands back
PROBES = 4

# Latent attention, the form a row a step (``ragged_forward``). Expanded, a
# (query, key) pair a head costs ``2 (d + dv)`` operations where absorbed costs
# ``2 (W + c)``, and making a context token's K and V costs ``2 c (nope + dv)``
# a head once a layer a step: by count a row gains when it is fed more than
# ``c (nope + dv) / (W + c - d - dv)`` tokens a step (358 at GLM-4.7-Flash's
# widths). The chip's own crossing point lies above that, at 670-710 tokens
# (a v5e, one layer call at 12,288 and at 30,000 tokens of history: absorbed
# 4.4 and 9.6 us a fed token; expanded 1.95 and 4.4 us a token after 1.74 and
# 3.49 ms of expansion: PERF.md section 6, PR 40), because the expansion's
# matmuls run at half the MXU's peak and the absorbed call at 72%; the
# constant is the next whole number of 128-token KV blocks above it.
_EXPAND_MIN_TOKENS = 768
# long rows a program holds: a step's token budget is the tail of one prompt
# and the head of the next, and whatever is past that stays absorbed
_EXPAND_ROWS = 2
# KV blocks one pass of the expansion's loop makes K and V of
_EXPAND_SEGMENT_BLOCKS = 16
# what the workspace of a program may take: an eighth of a v5e's memory. Two
# rows of 257 blocks of 128 tokens at 20 heads of 256 + 256 in bf16 are
# 1.35e9 bytes; a wider table is cut to what fits and a row whose context is
# past the cut stays absorbed
_EXPAND_WORKSPACE_BYTES = 2 << 30


def _workspace_column_bytes(cfg: TransformerConfig, block_size: int, itemsize: int) -> int:
    """Bytes of one KV block of every head's K and V in the workspace."""
    return 2 * cfg.num_heads * block_size * cfg.head_dim * itemsize


def expanded_plan(cfg: TransformerConfig, T: int, max_blocks: int, block_size: int, itemsize: int = 2):
    """``(rows, cols)`` of the workspace a program of ``T`` tokens over tables
    of ``max_blocks`` columns keeps for latent attention in the expanded form:
    how many of a step's rows it can hold and how many KV blocks of context
    each; ``(0, 0)`` for a program that attends absorbed alone (no latent
    cache, no room for one row of ``_EXPAND_MIN_TOKENS`` tokens in ``T``, or
    values wider than a head's scores, which the per-head kernel does not
    take). From static shapes alone."""
    if not cfg.latent_attention or cfg.v_head_dim > cfg.head_dim:
        return 0, 0
    rows = min(_EXPAND_ROWS, T // _EXPAND_MIN_TOKENS)
    if rows == 0:
        return 0, 0
    cols = min(max_blocks, _EXPAND_WORKSPACE_BYTES // (rows * _workspace_column_bytes(cfg, block_size, itemsize)))
    return (rows, cols) if cols > 0 else (0, 0)


def expanded_workspace_bytes(cfg: TransformerConfig, T: int, max_blocks: int, block_size: int, itemsize: int = 2) -> int:
    """Bytes of per-head K and V that the program of :func:`expanded_plan`
    keeps beside the pool (one layer's at a time)."""
    rows, cols = expanded_plan(cfg, T, max_blocks, block_size, itemsize)
    return rows * cols * _workspace_column_bytes(cfg, block_size, itemsize)


def expanded_slots(new, total, rows: int, cols: int, block_size: int, xp=jnp):
    """The workspace slot of every table row of a step, or -1 for a row that
    is attended absorbed: the first ``rows`` rows, in table order, that are fed
    at least ``_EXPAND_MIN_TOKENS`` tokens (``new``) and whose context after
    the step (``total``) fits ``cols`` KV blocks. ``xp`` is ``jnp`` inside the
    program and ``numpy`` for the engine's count of the same step."""
    long_row = (new >= _EXPAND_MIN_TOKENS) & (total <= cols * block_size)
    rank = xp.cumsum(long_row.astype(xp.int32)) - 1
    return xp.where(long_row & (rank < rows), rank, -1).astype(xp.int32)


def expanded_batch(slot_of_tok, pos, rows: int, xp=jnp):
    """``(seq_idx, pos)`` of a step's tokens as the expanded call attends
    them: a token of a workspace slot at that slot's table row and its own
    position, every other token at position -1, where it sees no key and its
    tile costs no grid step, on one of the ``rows + 1`` further table rows
    that stand for no context (its run's number among the runs of such
    tokens: between, before and after ``rows`` long rows there are at most
    ``rows + 1``, so no two runs share a row and the tiled grid's bound of a
    ragged tile a table row holds)."""
    live = slot_of_tok >= 0
    run = xp.cumsum(xp.concatenate([live[:1], live[1:] & ~live[:-1]]).astype(xp.int32))   # long rows begun so far
    return xp.where(live, slot_of_tok, rows + run).astype(xp.int32), xp.where(live, pos, -1).astype(xp.int32)


def _expand_latents(cfg: TransformerConfig, block_size: int, entries, row_blocks, n_blocks, w_k, w_v, ws, slot: int):
    """Per-head K and V of one row's cached context, into workspace slot
    ``slot``. ``entries``: the pool as blocks ``[blocks, block_size, W]``;
    ``row_blocks`` ``[cols]``: the row's table columns (this layer's block
    ids); ``n_blocks`` (traced): the blocks the row's context holds; ``w_k``
    ``[nq, c, nope]`` / ``w_v`` ``[nq, c, dv]``: ``W_kvb`` by head; ``ws``: the
    workspace ``(K, V)``, each ``[nq, rows * cols, block_size, d]``: pools by
    head (``paged_attention``), slot ``i``'s table column ``j`` the block ``i *
    cols + j`` of every head.
    ``K_h = [ckv W_K_h | kr]``, ``V_h = ckv W_V_h`` (zeros up to ``d``),
    float32 sums rounded to the pool's type once. Segments of
    ``_EXPAND_SEGMENT_BLOCKS`` whole blocks under a loop bounded by the row's
    LENGTH, not the table's width; the last segment of a table that is not a
    whole number of segments starts early and makes a few blocks twice."""
    c, rope, d = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.head_dim
    nq, cols = w_k.shape[0], row_blocks.shape[0]
    seg = min(_EXPAND_SEGMENT_BLOCKS, cols)
    n = seg * block_size
    dt = ws[0].dtype
    # a matmul a head, and the workspace head-outermost, so that what a pass writes is the product as it
    # comes, ``[tokens, d]``, to one place: any other order of the workspace the compiler re-lays to this one
    # for the loop and copies all of it both ways around it (compiled for a described v5e, PR 40). The key's shared
    # rope part rides the same matmul through an identity under a head's last ``rope`` columns (exact: one
    # bf16 times one, summed with zeros in float32); joined on afterwards it is a cut at no whole lane tile
    through = jnp.pad(jnp.eye(rope, dtype=w_k.dtype), ((0, 0), (d - rope, 0)))
    w_k = jnp.concatenate([jnp.pad(w_k, ((0, 0), (0, 0), (0, rope))), jnp.broadcast_to(through, (nq, rope, d))], axis=1)
    w_v = jnp.pad(w_v, ((0, 0), (0, 0), (0, d - w_v.shape[-1])))

    def segment(j, ws):
        start = jnp.minimum(j * seg, cols - seg)
        e = entries[jax.lax.dynamic_slice(row_blocks, (start, ), (seg, ))].reshape(n, -1)
        for h in range(nq):
            made = (jnp.dot(e[:, :c + rope], w_k[h], preferred_element_type=jnp.float32),
                    jnp.dot(e[:, :c], w_v[h], preferred_element_type=jnp.float32))
            ws = tuple(jax.lax.dynamic_update_slice(part, m.astype(dt).reshape(1, seg, block_size, d),
                                                    (h, slot * cols + start, 0, 0)) for part, m in zip(ws, made))
        return ws

    return jax.lax.fori_loop(0, -(-n_blocks // seg), segment, ws)


def ragged_forward(cfg: TransformerConfig, block_size: int, params: Dict[str, Any], token_ids, seq_idx, pos, valid,
                   block_tables, last_idx, k_pool, v_pool, use_pallas: bool = False,
                   unroll: bool = True, modules: Dict[str, Any] = None,
                   k_scale=None, v_scale=None, pos_ids=None, attn_mask=None,
                   ctx_pos_ids=None, moe_stats: bool = False, kv_only: bool = False,
                   state_pools=None, state_slots=None, one_token_rows: bool = False, index_pool=None,
                   probe: bool = False):
    """Returns (last-token logits [S_pad, V], k_pool, v_pool).

    token_ids/seq_idx/pos/valid: [T_pad]; block_tables: [S_pad, max_blocks];
    last_idx: [S_pad]; k_pool/v_pool: [L, NB*bs, nkv, d] (donated), the
    cache's pools as the model's ``kv_entry`` shapes them.

    Latent attention (``cfg.latent_attention``; ``v_pool`` None, and the
    return has the one pool): ``k_pool`` ``[L, NB*bs, 1, W]`` holds a token's
    entry ``[rmsnorm(ckv) | rope(kr) | 0]`` (``kv_lora_rank`` lanes, then
    ``qk_rope_head_dim``, zeros up to ``W``: whole 128-lane tiles), scattered
    at the token's slot exactly as K is. The FORM of the attention follows how
    many tokens a row is fed this step, so a step is two attention calls whose
    outputs are merged by row. ABSORBED (a decode row, a short chunk): with
    ``W_kvb = [W_K_h | W_V_h]`` a head's query is ``[q_nope_h W_K_h^T |
    rope(q_rope_h) | 0]`` against the entries, scaled by ``1 /
    sqrt(qk_nope_head_dim + qk_rope_head_dim)``; the attention call returns
    ``sum p ckv`` a head and ``W_V_h`` is applied to it, both batched matmuls
    over heads on the stored ``wkv_b_k`` / ``wkv_b_v``, and no per-head K or V
    exists. EXPANDED (up to ``_EXPAND_ROWS`` rows fed at least
    ``_EXPAND_MIN_TOKENS`` tokens: :func:`expanded_plan`,
    :func:`expanded_slots`): per-head ``K_h = [ckv W_K_h | kr]`` and ``V_h =
    ckv W_V_h`` of the row's whole context, the chunk's own tokens included,
    are made from the pool after the scatter into a workspace
    (:func:`_expand_latents`) that the same attention module reads as pools
    by head (:func:`expanded_batch`), at ``2 (d + dv)`` operations a pair a head
    instead of ``2 (W + c)``. Each call's work list drops the other's rows (a
    token at position -1 sees no key and its tile costs no grid step), so
    every pair is attended once, in one form; which rows are long is data, how
    many a program holds is static, and a program under ``_EXPAND_MIN_TOKENS``
    tokens is the absorbed call alone.

    ``pos_ids``/``attn_mask``/``ctx_pos_ids``: token-tree verification
    (``engine_v2.speculate_decode`` with branched drafts). ``pos`` stays the
    KV SLOT position (each tree node scatters into its own slot);
    ``pos_ids`` is the LOGICAL position (committed length + tree depth) that
    rotary/learned/alibi positions must see; ``attn_mask`` [T, C] is the
    ancestor-visibility mask replacing causal masking (a sibling branch at
    an earlier slot must stay invisible); ``ctx_pos_ids`` [S, C] gives every
    context slot its logical position for alibi distances. All three default
    to None = the plain causal forward, byte-identical to before.

    ``unroll``: trace the layer loop as straight-line code instead of
    ``lax.scan``. scan dynamic-slices each layer's weights out of the
    stacked pytree into a fresh buffer every iteration — measured ~3x the
    weight-streaming roofline at decode batch sizes; unrolled indexing is
    ~1.5x. Serving compiles each shape bucket once (and caches), so the
    extra trace/compile time only pays at warmup. Models deeper than 48
    layers fall back to scan to bound compile time.

    ``modules``: the pluggable module set (``modules/heuristics.build_modules``
    — attention / linear / embedding / unembed / norm slots, reference
    FastGen's DSModule layer). None builds the auto set from ``cfg`` and
    ``use_pallas``, preserving the pre-registry call surface.

    A model with experts (``cfg.moe_num_experts``) runs its MLP through the
    ``moe`` module (padding tokens route nowhere); ``moe_stats`` appends to
    the return int32 ``[experts_hit, expert_load_max, slots]``: experts with at
    least one slot summed over the layers, the most slots on one expert in a
    layer, and the slots that took a row (all routed ones, unless the model
    holds a share of its experts). A model with ``cfg.layer_types`` gives each
    layer its own window (the ``attention`` / ``attention_full`` modules) and
    its own rope table, or none (``cfg.rope_layer_types``). The leading
    ``cfg.moe_num_dense_layers`` layers of a model with experts run the dense
    MLP, the others the routed experts held here plus the shared expert
    (plain matmuls), whose arrays are stacked over the expert layers alone. A
    q/k norm, the attention gate and the norms after each branch are the
    configuration's (``qk_norm``, ``attention_gate``, ``post_norms``).

    A model with ``cfg.diffusion_block_size`` ``B`` attends under the
    block-causal mask, key ``j`` visible to query ``i`` iff ``j // B <= i //
    B``: every attention path (both paged kernels, the interpreter, the gather)
    takes a token's position only to mask, so each is given the LAST position
    of the token's block, ``pos | (B - 1)``, here and nowhere else, while rope
    and the KV slot keep ``pos``. ``B`` divides the KV block, so the block
    columns a row walks are those of ``pos``. ``kv_only``: the forward that
    commits the LAST block of a ``decode`` call (a block before it is
    committed inside the next block's first denoise forward, an ordinary
    ragged step of two blocks a row: ``diffusion.build_block_program``): K/V
    of every layer are written and nothing else is wanted, so the last layer
    stops at its scatter, there is no head, and the logits returned are None.

    A model with LINEAR-attention layers (``cfg.state_layers``, Kimi Delta
    Attention: ``models/solar.py`` has the equations) takes ``state_pools``,
    ``(state [Ls, slots, H, dk, dv] float32, tails [Ls, slots, taps - 1, 3 H
    dk])`` stacked over the state layers, and ``state_slots`` ``[S_pad]``,
    each row's slot; the pools ``k_pool``/``v_pool`` are stacked over the
    layers that cache K and V alone (``cfg.kv_layers``), and so are the
    attention weights. Such a layer caches no token: it runs q, k and v
    through the causal convolution from each row's stored tail (zeros for a
    row whose first token is at position 0, whatever the slot held), the delta
    rule over each row's tokens from that row's state (``ops/pallas/kda.py``:
    the chunkwise form over a ragged batch, or with ``one_token_rows``, the
    decode horizon's step, where token ``i`` IS row ``i``, the recurrent
    step), and writes state and tail back at the rows that were fed. A padded
    token or row touches neither. The return gains the two pools after the
    K/V pools. LIGHTNING layers (``cfg.lightning_num_heads``, scalar-decay
    linear attention: ``models/minicpm.py``) are state layers too, with the
    state pool alone (``state_pools`` of one): q and k are normed a head and
    ROPED, there is no convolution, and the recurrence's two forms are
    ``ops/pallas/lightning.py``'s: the chunkwise one for every ``put``, the
    recurrent step under ``one_token_rows``. STATE-SPACE layers
    (``cfg.mamba_num_heads``, Mamba-2's selective scan: ``models/nemotron.py``)
    are state layers with both pools, the state ``[Ls, slots, H, P, N]`` and the
    tail of the convolution over x, B and C; the scan's two forms are
    ``ops/pallas/mamba2.py``'s, the ``D`` skip, the convolution and the gated
    norm XLA's.

    A model of ``cfg.single_branch_layers`` runs in each layer the ONE branch
    the layer has under its one norm: the mixer ``cfg.layer_types`` names, or,
    in an ``"mlp_only"`` layer, the MLP. Such a layer caches nothing, takes no
    part in the K/V pools or the state pools, and its arrays are stacked over
    the layers of its kind wherever they lie (``index_of``).

    A model with a learned block-sparse SELECTION (``cfg.sparse_topk``) takes
    ``index_pool`` ``[La, NB * block / stride, nkv, d]``, the pooled keys its
    sparse layers cache beside K and V on the same block table, and returns it
    after the K/V pools. Such a layer, after its K/V scatter, writes the
    pooled keys this step's tokens complete, scores the blocks of every
    query's context with them (``sparse_index.py``) and hands the selection to
    the attention module, whose kernels lay grid steps for selected blocks
    alone. The KV block is the selection's block. ``moe_stats`` of such a model
    (it has no experts) appends int32 ``[blocks_read, items_live, grid_steps]``:
    the (token, kv head, block) triples the work lists served, as the lists
    themselves count them, then the (tile, column) pairs the tiled kernel's
    lists laid and the grid steps it ran for them, several pairs a step where
    a block is narrower than a lane tile (``paged_attention``'s second result
    under a selection), each summed over the layers; what was visible and what was selected follow from
    the rows' lengths and are the engine's to count. ``probe`` appends, last of
    all, what a check reads back of ``PROBES`` tokens a row (its run's first
    and last and two between: the middle of a chunk's tiles and their ends)
    in every sparse layer: ``(positions [S_pad, PROBES] int32, selection
    [S_pad, PROBES, La, nkv, max_blocks] bool, attention output [S_pad, PROBES,
    La, nq * d] float32)``, the selection the indexer made and what the paged
    kernel gave back under it, before the gate and ``W_o``.

    ``cfg.residual_scale`` multiplies each branch's output before it is added
    and ``cfg.logit_scale`` the logits (the head has no bias, so that is the
    final normed hidden state scaled).

    Every part of the step is traced under its ``jax.named_scope`` of
    ``monitor/scopes.py`` (``embed``, ``attn_proj``, ``mixer``, ``attn_out``,
    ``mlp`` / ``moe``, ``lm_head``), the norm before a branch and the residual
    add after it with the matmuls they are fused into: a device trace is read
    back by those names, so new code here goes under the part it belongs to.

    ``k_scale``/``v_scale``: int8-KV mode — [nkv, L*pool_len] fp32 absmax
    scales (lane-major over slots, the layout both the scatter and the
    Pallas kernel consume without a transpose). When given, the pools hold
    int8, each layer quantizes its fresh K/V per (token, head) before the
    scatter, and the return gains the updated scale pools:
    (logits, k_pool, v_pool, k_scale, v_scale).
    """
    if modules is None:
        from ..config_v2 import RaggedInferenceEngineConfig
        from ..modules.heuristics import build_modules

        ec = RaggedInferenceEngineConfig()
        ec.kv_block_size = block_size
        modules = build_modules(cfg, ec, use_pallas=use_pallas)
    attention, linear = modules["attention"], modules["linear"]
    embedding, unembed, pre_norm = modules["embedding"], modules["unembed"], modules["norm"]
    moe = modules.get("moe")
    if cfg.moe_num_experts > 0 and moe is None:
        raise ValueError("a model with experts needs the module set's 'moe' slot "
                         "(modules/heuristics.build_modules fills it)")
    sparse = cfg.sparse_topk > 0
    if moe_stats and moe is None and not sparse:
        raise ValueError("moe_stats asked of a model without experts or a block selection")
    if sparse != (index_pool is not None) or (sparse and (block_size != cfg.sparse_block_size or k_scale is not None
                                                          or attn_mask is not None or kv_only)):
        raise ValueError(f"a model with a block selection takes index_pool, KV blocks of its sparse_block_size "
                         f"({cfg.sparse_block_size}, got {block_size}), and neither int8 scales, a token-tree mask "
                         "nor kv_only; every other model takes no index_pool")
    if getattr(cfg, "sparse_attention", None) is not None:
        # same policy as forward_with_cache: dense paged decode would
        # silently mismatch a sparse-trained model's attention distribution
        raise NotImplementedError("sparse_attention serving is not implemented on the ragged "
                                  "plane; unset sparse_attention for inference")
    T = token_ids.shape[0]
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pool_len = k_pool.shape[1]
    latent = cfg.latent_attention
    if latent != (v_pool is None) or (latent and (k_scale is not None or attn_mask is not None)):
        raise ValueError("latent attention takes the one latent pool (v_pool None), without int8 scales or a "
                         "token-tree mask; every other model takes k_pool and v_pool")

    pid = pos if pos_ids is None else pos_ids
    with jax.named_scope(scopes.EMBED):
        x = embedding(params, token_ids, pid)  # [T, H]
    # what the mixers share, once a program: rope tables, each token's slot, the rows' runs, the workspaces
    with jax.named_scope(scopes.MIXER):
        # one rope table an attention kind (a model of one kind: the key None), in
        # the order the kinds first appear: a set's order changes with the process's
        # string hash seed, and with it the traced program and its compile-cache key
        ropes = {kind: rope_table(cfg, pid, kind) for kind in dict.fromkeys(cfg.layer_types or (None, ))
                 if cfg.rope_layer_types is None or kind in cfg.rope_layer_types} \
            if cfg.positions == "rotary" and not latent else {}
        if latent:  # the rotated part of a head alone: tables [T, qk_rope_head_dim / 2], halves rotated
            angles = pid.astype(jnp.float32)[:, None] * jnp.asarray(rope_inv_freq(cfg)[0])[None, :]
            latent_rope = jnp.sin(angles), jnp.cos(angles)

        # flat KV slot of each token; padding tokens dropped via OOB scatter.
        # The pools ride the layer scan as CARRY over a layers-flattened view
        # [(L*NB*bs), nkv, d]: scatter/gather address layer l via an l*pool_len
        # (resp. l*NB block-table) offset. Pools as scan xs/ys would instead
        # round-trip the whole cache through fresh stacked outputs every forward
        # — at serving scale that copy (~2x pool bytes of HBM traffic per decode
        # step) dominated the step budget.
        quant = k_scale is not None
        NB = pool_len // block_size
        L = cfg.num_layers
        flat_len = k_pool.shape[0] * pool_len
        kv_index = {l: i for i, l in enumerate(cfg.kv_layers)}  # a layer's place in the K/V pools and attention weights
        state_index = {l: i for i, l in enumerate(cfg.state_layers)}
        if bool(state_index) != (state_pools is not None) or (state_index and (
                state_slots is None or quant or attn_mask is not None or kv_only or latent)):
            raise ValueError("a model with linear-attention layers takes state_pools and state_slots, and neither int8 "
                             "scales, a token-tree mask nor kv_only; every other model takes none")
        lightning = cfg.lightning_num_heads > 0
        # the kernels of the state layers and of the selection's indexer: on the chip, or their bodies on the interpreter
        kda_pallas = use_pallas and jax.default_backend() == "tpu"
        kda_interpret = bool(getattr(attention, "implementation_config", {}).get("interpret", False))
        if state_index:
            # what the rows are fed, once a program: tokens a row (rows come in order from flat token 0), a
            # token's place in its row's run, and which rows start their sequence here
            S = block_tables.shape[0]
            n_slots, taps = state_pools[0].shape[1], (cfg.mamba_conv_size if cfg.mamba_num_heads > 0 else cfg.kda_conv_size)
            ok = valid.astype(jnp.int32)
            if one_token_rows:
                n_tok, in_row, row_start = ok, jnp.zeros(T, jnp.int32), jnp.arange(T, dtype=jnp.int32)
                first_pos = pos
            else:
                n_tok = jnp.zeros(S, jnp.int32).at[seq_idx].add(ok)
                row_start = jnp.cumsum(n_tok) - n_tok
                in_row = jnp.arange(T, dtype=jnp.int32) - row_start[seq_idx]
                first_pos = pos[jnp.minimum(row_start, T - 1)]
            fed = n_tok > 0
            fresh = fed & (first_pos == 0)
            n_live = jnp.sum(fed.astype(jnp.int32))
            st_flat = state_pools[0].reshape((-1, ) + state_pools[0].shape[2:])
            cv_flat = None if lightning else state_pools[1].reshape((-1, ) + state_pools[1].shape[2:])
        if sparse:
            idx_flat = index_pool.reshape((-1, ) + index_pool.shape[2:])
        slot = block_tables[seq_idx, pos // block_size] * block_size + pos % block_size

        # what the attention paths mask by (see the docstring)
        vis_pos = pos | (cfg.diffusion_block_size - 1) if cfg.diffusion_block_size > 1 else pos
        if cfg.diffusion_block_size > 1 and (attn_mask is not None or block_size % cfg.diffusion_block_size):
            raise NotImplementedError(f"blocks of {cfg.diffusion_block_size} under a block-causal mask: no token-tree "
                                      f"mask beside it, and a KV block ({block_size}) holds whole blocks")

        # latent attention's long rows (see the docstring): which they are, their
        # tokens as the expanded call takes them, and the workspace, once a program
        x_rows, x_cols = expanded_plan(cfg, T, block_tables.shape[1], block_size, k_pool.dtype.itemsize)
        if x_rows:
            S = block_tables.shape[0]
            fed = jnp.zeros(S, jnp.int32).at[seq_idx].add(valid.astype(jnp.int32))
            length = jnp.zeros(S, jnp.int32).at[seq_idx].max(jnp.where(valid, pos + 1, 0))
            slot_of_row = expanded_slots(fed, length, x_rows, x_cols, block_size)
            slot_of_tok = jnp.where(valid, slot_of_row[seq_idx], -1)
            x_seq, x_pos = expanded_batch(slot_of_tok, vis_pos, x_rows)
            # a slot's table row and the KV blocks its context holds (none: no row took the slot)
            x_row = [jnp.argmax(slot_of_row == i) for i in range(x_rows)]
            x_blocks = [jnp.where(jnp.any(slot_of_row == i), -(-length[r] // block_size), 0) for i, r in enumerate(x_row)]
            # the workspace's own table, a constant: column ``j`` of slot ``i`` lies where it was made (and the
            # rows of the tokens of no slot name block 0: nothing is read through them)
            x_tables = jnp.pad(jnp.arange(x_rows * x_cols, dtype=jnp.int32).reshape(x_rows, x_cols), ((0, x_rows + 1), (0, 0)))
            absorbed_pos = jnp.where(slot_of_tok >= 0, -1, vis_pos)   # the absorbed call drops the long rows' tiles
            workspace = (jnp.zeros((nq, x_rows * x_cols, block_size, d), k_pool.dtype), ) * 2
        else:
            absorbed_pos, workspace = vis_pos, None

    def tailed_conv(x3, w, slot_li, cv_flat):
        """The causal depthwise convolution of a state layer over this step's
        tokens ``x3`` ``[T, channels]`` with filters ``w`` ``[taps, channels]``
        (float32), each row going on from its stored tail. Returns ``(y [T,
        channels] float32, cv_flat)``, the tails of the rows that were fed
        written back."""
        f32 = jnp.float32
        tails = jnp.where(fresh[:, None, None], 0, cv_flat[slot_li])                                  # [S, taps - 1, ..]
        # the causal convolution: tap ``j`` back is the row's own token ``j`` earlier in this step, or,
        # before the run's first token, what the row's tail kept of its earlier steps
        y = w[taps - 1] * x3.astype(f32)
        for j in range(1, taps):
            earlier = jnp.concatenate([jnp.zeros_like(x3[:j]), x3[:T - j]], axis=0)
            kept = tails[seq_idx, jnp.clip(taps - 1 + in_row - j, 0, taps - 2)]
            y = y + w[taps - 1 - j] * jnp.where((in_row >= j)[:, None], earlier, kept).astype(f32)
        # the tail after this step: the last ``taps - 1`` inputs of [old tail | the run]
        at = n_tok[:, None] - (taps - 1) + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]            # [S, taps - 1]
        own = x3[jnp.clip(row_start[:, None] + at, 0, T - 1)]
        old = jnp.take_along_axis(tails, jnp.clip(n_tok[:, None] + jnp.arange(taps - 1)[None, :], 0, taps - 2)[..., None],
                                  axis=1)
        new_tails = jnp.where((at >= 0)[..., None], own, old)
        return y, cv_flat.at[jnp.where(fed, slot_li, cv_flat.shape[0])].set(new_tails.astype(cv_flat.dtype), mode="drop")

    def linear_mixer(h1, blk, li, st_flat, cv_flat):
        """A linear-attention layer's mixer on the normed input ``h1`` ``[T,
        H]``, ``li`` its place among the state layers. Returns ``(out [T, H],
        st_flat, cv_flat)``."""
        nh, dk = cfg.kda_num_heads, cfg.kda_head_dim
        f32 = jnp.float32
        slot_li = li * n_slots + state_slots
        with jax.named_scope(scopes.ATTN_PROJ):
            x3 = jnp.concatenate([linear(h1, blk[f"kda_w{n}"], None) for n in "qkv"], axis=-1)        # [T, 3 nh dk]
        w = jnp.concatenate([blk[f"kda_conv_{n}"] for n in "qkv"], axis=-1).astype(f32)               # [taps, 3 nh dk]
        y, cv_flat = tailed_conv(x3, w, slot_li, cv_flat)
        q, k, v = (jax.nn.silu(part).reshape(T, nh, dk) for part in jnp.split(y, 3, axis=-1))
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / math.sqrt(dk)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        with jax.named_scope(scopes.ATTN_PROJ):
            decay_in = linear(linear(h1, blk["kda_wf1"], None), blk["kda_wf2"], None)
        decay_in = decay_in.astype(f32) + blk["kda_dt_bias"].astype(f32)
        g = -jnp.exp(blk["kda_A_log"].astype(f32))[None, :, None] * jax.nn.softplus(decay_in).reshape(T, nh, dk)
        with jax.named_scope(scopes.ATTN_PROJ):
            beta_in = linear(h1, blk["kda_wb"], None)
        beta = jax.nn.sigmoid(beta_in.astype(f32)) * (2.0 if cfg.kda_neg_eigval else 1.0)
        if one_token_rows:
            o, st_flat = kda_step(q, k, v, g, beta, st_flat, slot_li, fresh, n_live, use_pallas=kda_pallas,
                                  interpret=kda_interpret)
        else:
            o, st_flat = kda_chunks(q, k, v, g, beta, st_flat, slot_li, fresh, n_tok, use_pallas=kda_pallas,
                                    interpret=kda_interpret)
        # a norm over each head's values (one gain vector for all heads), then the output gate
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps) * blk["kda_o_norm_scale"].astype(f32)
        with jax.named_scope(scopes.ATTN_PROJ):
            gate = linear(linear(h1, blk["kda_wg1"], None), blk["kda_wg2"], None)
        o = (o.reshape(T, nh * dk) * jax.nn.sigmoid(gate.astype(f32))).astype(h1.dtype)
        with jax.named_scope(scopes.ATTN_OUT):
            return linear(o, blk["kda_wo"], None), st_flat, cv_flat

    def lightning_mixer(h1, blk, li, st_flat, rope):
        """A lightning layer's mixer on the normed input ``h1`` ``[T, H]``,
        ``li`` its place among the state layers, ``rope`` its ``(sin, cos)``.
        Returns ``(out [T, H], st_flat)``."""
        nh, dk = cfg.lightning_num_heads, cfg.lightning_head_dim
        f32 = jnp.float32
        with jax.named_scope(scopes.ATTN_PROJ):
            q, k, v = [linear(h1, blk[f"la_w{n}"], None).reshape(T, nh, dk) for n in "qkv"]
        q, k = pre_norm(q, blk["la_q_norm_scale"]), pre_norm(k, blk["la_k_norm_scale"])
        q = apply_rope(q[None], *rope, cfg.rotary_dim)[0].astype(f32) / math.sqrt(dk)
        k = apply_rope(k[None], *rope, cfg.rotary_dim)[0].astype(f32)
        slot_li = li * n_slots + state_slots
        if one_token_rows:
            o, st_flat = lightning_step(q, k, v, blk["la_slope"], st_flat, slot_li, fresh, n_live, use_pallas=kda_pallas,
                                        interpret=kda_interpret)
        else:
            o, st_flat = lightning_chunks(q, k, v, blk["la_slope"], st_flat, slot_li, fresh, n_tok, use_pallas=kda_pallas,
                                          interpret=kda_interpret)
        # a norm over all heads' values with one gain vector, then the output gate
        o = o.reshape(T, nh * dk)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps) * blk["la_o_norm_scale"].astype(f32)
        with jax.named_scope(scopes.ATTN_PROJ):
            gate = linear(h1, blk["la_wg"], None)
        o = (o * jax.nn.sigmoid(gate.astype(f32))).astype(h1.dtype)
        with jax.named_scope(scopes.ATTN_OUT):
            return linear(o, blk["la_wo"], None), st_flat

    def state_space_mixer(h1, blk, li, st_flat, cv_flat):
        """A state-space (Mamba-2) layer's mixer on the normed input ``h1``
        ``[T, H]``, ``li`` its place among the state layers. Returns ``(out [T,
        H], st_flat, cv_flat)``."""
        nh, P, N, G = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_state_size, cfg.mamba_n_groups
        f32 = jnp.float32
        inner = nh * P
        slot_li = li * n_slots + state_slots
        with jax.named_scope(scopes.ATTN_PROJ):
            z, xbc, dt_in = jnp.split(linear(h1, blk["m2_w_in"], None), (inner, inner + cfg.mamba_conv_channels), axis=-1)
        y, cv_flat = tailed_conv(xbc, blk["m2_conv_w"].astype(f32), slot_li, cv_flat)
        xs, B, C = jnp.split(jax.nn.silu(y + blk["m2_conv_b"].astype(f32)), (inner, inner + G * N), axis=-1)
        xs, B, C = xs.reshape(T, nh, P), B.reshape(T, G, N), C.reshape(T, G, N)
        dt = jax.nn.softplus(dt_in.astype(f32) + blk["m2_dt_bias"].astype(f32))
        A = -jnp.exp(blk["m2_A_log"].astype(f32))
        if one_token_rows:
            y, st_flat = mamba2_step(xs, B, C, dt, A, st_flat, slot_li, fresh, n_live, use_pallas=kda_pallas,
                                     interpret=kda_interpret)
        else:
            y, st_flat = mamba2_chunks(xs, B, C, dt, A, st_flat, slot_li, fresh, n_tok, use_pallas=kda_pallas,
                                       interpret=kda_interpret)
        y = (y + blk["m2_D"].astype(f32)[None, :, None] * xs).reshape(T, inner) * jax.nn.silu(z.astype(f32))
        # the gated norm: RMS within each group's channels, one gain vector over all
        y = y.reshape(T, G, inner // G)
        y = (y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)).reshape(T, inner)
        y = (y * blk["m2_norm_scale"].astype(f32)).astype(h1.dtype)
        with jax.named_scope(scopes.ATTN_OUT):
            return linear(y, blk["m2_w_out"], None), st_flat, cv_flat

    def softmax_mixer(h1, blk, l, kind, k_flat, v_flat, ks_flat, vs_flat, stats, ws, kv_alone):
        """Softmax attention over the paged pool (per-head K and V, or the
        latent entry): what every layer kind that is no state layer's runs.
        Returns ``(out [T, H], k_flat, v_flat, ks_flat, vs_flat, stats, ws)``,
        ``out`` None where ``kv_alone`` stopped the layer at its scatter."""
        attend = modules["attention_full"] if kind == "full_attention" else attention
        bias = (lambda n: blk[n]) if cfg.use_bias else (lambda n: None)
        lk = l if cfg.layer_types is None else kv_index[l]  # (a traced ``l``: every layer caches K and V)
        slot_l = jnp.where(valid, lk * pool_len + slot, flat_len)  # this layer's slots in the flat pool
        tables_l = block_tables + lk * NB  # layer l's blocks in the flat pool
        if latent:
            c, nope, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
            W = k_flat.shape[-1]
            sin, cos = latent_rope
            with jax.named_scope(scopes.ATTN_PROJ):
                cq = pre_norm(linear(h1, blk["wq_a"], None), blk["q_a_norm_scale"])
                qh = linear(cq, blk["wq_b"], None).reshape(T, nq, d)
                kv = linear(h1, blk["wkv_a"], None)
            ckv = pre_norm(kv[:, :c], blk["kv_a_norm_scale"])
            kr = apply_rope(kv[None, :, None, c:], sin, cos)[0]          # [T, 1, rope]: ONE key part for all heads
            entry = jnp.concatenate([ckv[:, None, :], kr], axis=-1)
            entry = jnp.pad(entry, ((0, 0), (0, 0), (0, W - entry.shape[-1])))
            k_flat = k_flat.at[slot_l].set(entry.astype(k_flat.dtype), mode="drop")
            if kv_alone:
                return None, k_flat, v_flat, ks_flat, vs_flat, stats, ws
            q_rope = apply_rope(qh[None, ..., nope:], sin, cos)[0]
            # W_K_h^T folded into the query: [q_nope_h W_K_h^T | rope(q_rope_h) | 0] against the entries
            q_lat = jnp.einsum("thn,hcn->thc", qh[..., :nope], blk["wkv_b_k"],
                               preferred_element_type=jnp.float32).astype(qh.dtype)
            q_abs = jnp.concatenate([q_lat, q_rope], axis=-1)
            q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, W - q_abs.shape[-1])))
            lat = attend(q_abs, k_flat, None, tables_l, seq_idx, absorbed_pos, value_dim=c,
                         softmax_scale=1.0 / math.sqrt(d))              # [T, nq, c]: sum p ckv a head
            # ... and W_V_h into the output
            ctx = jnp.einsum("thc,hcv->thv", lat, blk["wkv_b_v"],
                             preferred_element_type=jnp.float32).astype(lat.dtype)
            if x_rows:
                # the long rows: K and V by head from the pool as it stands after the scatter, then the
                # per-head call over them, [q_nope_h | rope(q_rope_h)] a head
                entries = k_flat[:flat_len - flat_len % block_size].reshape(-1, block_size, W)
                for i in range(x_rows):
                    ws = _expand_latents(cfg, block_size, entries, tables_l[x_row[i], :x_cols], x_blocks[i],
                                         blk["wkv_b_k"], blk["wkv_b_v"], ws, i)
                by_head = attend(jnp.concatenate([qh[..., :nope], q_rope], axis=-1), ws[0], ws[1], x_tables, x_seq, x_pos,
                                 softmax_scale=1.0 / math.sqrt(d))
                ctx = jnp.where((slot_of_tok >= 0)[:, None, None], by_head[..., :dv], ctx)
            ctx = ctx.reshape(T, nq * dv)
        else:
            qkvb = (lambda n: blk[n]) if cfg.qkv_bias_enabled else (lambda n: None)
            with jax.named_scope(scopes.ATTN_PROJ):
                q = linear(h1, blk["wq"], qkvb("bq")).reshape(T, nq, d)
                k = linear(h1, blk["wk"], qkvb("bk")).reshape(T, nkv, d)
                v = linear(h1, blk["wv"], qkvb("bv")).reshape(T, nkv, d)
            if cfg.qk_norm:  # over each head's d, one gain vector for all heads
                q = pre_norm(q, blk["q_norm_scale"])
                k = pre_norm(k, blk["k_norm_scale"])
            if kind in ropes:  # a layer kind without rope carries no position at all
                sin, cos = ropes[kind]
                q = apply_rope(q[None], sin, cos, cfg.rotary_dim)[0]
                k = apply_rope(k[None], sin, cos, cfg.rotary_dim)[0]

            # append this batch's KV to the paged pool (linear_blocked_kv_rotary);
            # in-place scatter on the scan carry at layer l's offset
            if quant:
                # symmetric int8 per (token, kv-head): absmax/127 over head_dim
                ks = jnp.maximum(jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1) / 127.0, 1e-8)
                vs = jnp.maximum(jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-1) / 127.0, 1e-8)
                k = jnp.round(k.astype(jnp.float32) / ks[..., None])
                v = jnp.round(v.astype(jnp.float32) / vs[..., None])
                heads = jnp.arange(nkv, dtype=jnp.int32)[None, :]
                ks_flat = ks_flat.at[heads, slot_l[:, None]].set(ks, mode="drop")
                vs_flat = vs_flat.at[heads, slot_l[:, None]].set(vs, mode="drop")
            k_flat = k_flat.at[slot_l].set(k.astype(k_flat.dtype), mode="drop")
            v_flat = v_flat.at[slot_l].set(v.astype(v_flat.dtype), mode="drop")
            if kv_alone:
                return None, k_flat, v_flat, ks_flat, vs_flat, stats, ws

            # scales/tree kwargs only passed when active, so full-precision
            # causal third-party attention implementations keep the original
            # 6-arg call signature
            scales = {"k_scale": ks_flat, "v_scale": vs_flat} if quant else {}
            if attn_mask is not None:
                scales = dict(scales, pos_ids=pid, mask=attn_mask, ctx_pos_ids=ctx_pos_ids)
            if kind == "sparse_attention":
                # the pooled keys this step completes, then each query's blocks: the work lists follow the data
                p_flat = update_pooled_keys(cfg, block_size, k_flat, ws[-1], tables_l, seq_idx, pos, valid)
                picked = select_blocks(cfg, block_size, q, p_flat, tables_l, seq_idx, pos, valid, use_pallas=kda_pallas,
                                       interpret=kda_interpret)
                ws = tuple(ws[:-1]) + (p_flat, )
                ctx, read = attend(q, k_flat, v_flat, tables_l, seq_idx, vis_pos, selection=picked, **scales)
                if stats is not None:  # blocks read a kv head, then the tiled list's pairs and its grid steps
                    stats = stats + read * jnp.asarray([nkv, 1, 1], jnp.int32)
                if probe:
                    probes.append((picked[probe_tok], ctx.reshape(T, nq * d)[probe_tok].astype(jnp.float32)))
            else:
                ctx = attend(q, k_flat, v_flat, tables_l, seq_idx, vis_pos, **scales)
            ctx = ctx.reshape(T, nq * d)
        if cfg.attention_gate:
            with jax.named_scope(scopes.ATTN_PROJ):
                gate = linear(h1, blk["w_attn_gate"], None)
            ctx = (ctx.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(ctx.dtype)
        with jax.named_scope(scopes.ATTN_OUT):
            return linear(ctx, blk["wo"], bias("bo")), k_flat, v_flat, ks_flat, vs_flat, stats, ws

    def state_mixer(run):
        """A state layer's mixer as the table's entries are called: the K/V
        pools pass through untouched and ``ws`` holds the state pools."""

        def mixer(h1, blk, l, kind, k_flat, v_flat, ks_flat, vs_flat, stats, ws, kv_alone):
            out, *pools = run(h1, blk, state_index[l], *ws[:n_state_pools], *((ropes[kind], ) if kind in ropes else ()))
            return out, k_flat, v_flat, ks_flat, vs_flat, stats, tuple(pools) + tuple(ws[n_state_pools:])

        return mixer

    # the mixers by the layer's kind; every kind that is not named here (None, of a model of one kind, among
    # them) is softmax attention over the paged pool, and an "mlp_only" layer has none
    mixers = {"linear_attention": state_mixer(linear_mixer), "lightning_attention": state_mixer(lightning_mixer),
              "state_space": state_mixer(state_space_mixer)}
    n_state_pools = len(state_pools or ())

    def layer(x, blk, l, k_flat, v_flat, ks_flat, vs_flat, stats=None, ws=None, kind=None, kv_alone=False):
        """``kind``: the layer's kind, static (None in a model of one kind,
        where ``l`` may be traced); ``stats``: the running MoE counts; ``ws``:
        latent attention's workspace of per-head K and V, or a model with
        state layers' ``(state, tails)`` pools; ``kv_alone``: write this
        layer's K/V and stop. A layer runs the branches it HAS: a mixer and an
        MLP under a norm each, or, in a model of ``single_branch_layers``, the
        one of them its kind names under the layer's one norm."""
        # the MLP's part: the routed experts', or the dense MLP's (the norm before and the residual add with it)
        mlp_part = scopes.MLP if "gate_wg" not in blk else scopes.MOE
        with jax.named_scope(mlp_part if kind == "mlp_only" else scopes.ATTN_PROJ):
            h1 = pre_norm(x, blk["ln1_scale"], blk.get("ln1_bias"))
        bias = (lambda n: blk[n]) if cfg.use_bias else (lambda n: None)
        if kind != "mlp_only":
            # a mixer names its input projections and its output projection inside: the rest of it is ``mixer``
            with jax.named_scope(scopes.MIXER):
                attn_out, k_flat, v_flat, ks_flat, vs_flat, stats, ws = mixers.get(kind, softmax_mixer)(
                    h1, blk, l, kind, k_flat, v_flat, ks_flat, vs_flat, stats, ws, kv_alone)
            if attn_out is None:  # ``kv_alone``: the layer stopped at its scatter
                return x, k_flat, v_flat, ks_flat, vs_flat, stats, ws

        def dense_mlp(h, w_up, w_gate, w_down, b_up=None, b_down=None):
            up = linear(h, w_up, b_up)
            act = mlp_activation(cfg, up, linear(h, w_gate, None)) if cfg.mlp == "swiglu" \
                else mlp_activation(cfg, up)
            return linear(act, w_down, b_down)

        def mlp(h):
            nonlocal stats
            if "gate_wg" not in blk:  # a dense model, or a leading dense layer of a model with experts
                return dense_mlp(h, blk["w_up"], blk.get("w_gate"), blk["w_down"], bias("b_up"), bias("b_down"))
            # the experts stay in the stacked arrays and the kernel reads this
            # layer's out of them: ``blk`` holds no routed expert's weights
            out = moe(h, blk["gate_wg"], experts["moe_wi"], experts.get("moe_wg"),
                      experts["moe_wo"], valid=valid, with_stats=stats is not None,
                      layer=expert_index[l], gate_bias=blk.get("gate_bias"))
            if stats is not None:
                out, layer_stats = out
                stats = merge_routing_stats(stats, layer_stats)
            if "shared_wi" in blk:  # every token's, whole on every chip of the group
                with jax.named_scope(scopes.MLP):
                    out = out + dense_mlp(h, blk["shared_wi"], blk.get("shared_wg"), blk["shared_wo"])
            return out

        def post(y, name):  # the sandwich norm on a branch's output, and MiniCPM's factor on the branch
            y = pre_norm(y, blk[name]) if cfg.post_norms else y
            return y if cfg.residual_scale == 1.0 else (y.astype(jnp.float32) * cfg.residual_scale).astype(y.dtype)

        if cfg.single_branch_layers:  # ONE branch under the layer's one norm
            with jax.named_scope(mlp_part if kind == "mlp_only" else scopes.ATTN_OUT):
                return x + post(mlp(h1) if kind == "mlp_only" else attn_out, "ln1_post_scale"), k_flat, v_flat, \
                    ks_flat, vs_flat, stats, ws
        if cfg.parallel_residual:  # GPT-J / NeoX / Falcon
            with jax.named_scope(mlp_part):
                h2 = h1 if cfg.shared_ln else pre_norm(x, blk["ln2_scale"], blk.get("ln2_bias"))
                return x + attn_out + mlp(h2), k_flat, v_flat, ks_flat, vs_flat, stats, ws
        with jax.named_scope(scopes.ATTN_OUT):
            x = x + post(attn_out, "ln1_post_scale")
        with jax.named_scope(mlp_part):
            h2 = pre_norm(x, blk["ln2_scale"], blk.get("ln2_bias"))
            return x + post(mlp(h2), "ln2_post_scale"), k_flat, v_flat, ks_flat, vs_flat, stats, ws

    # (a latent pool: one entry a token, [flat_len, 1, W], and no second pool)
    k_flat = k_pool.reshape((flat_len, ) + k_pool.shape[2:])
    v_flat = None if latent else v_pool.reshape(flat_len, nkv, d)
    ks_flat, vs_flat = k_scale, v_scale  # already [nkv, flat_len] or None
    stats = jnp.zeros(3, jnp.int32) if moe_stats else None
    # what each stacked array is stacked over: the routed experts (read in
    # place by the kernel) and the rest of an expert layer over the expert
    # layers, the dense MLP over the dense layers, everything else over all
    expert_keys = ("moe_wi", "moe_wg", "moe_wo")
    expert_layer_keys = ("gate_wg", "gate_bias", "shared_wi", "shared_wg", "shared_wo")
    dense_layer_keys = ("w_up", "w_gate", "w_down", "b_up", "b_down")
    attention_keys = ("wq", "wk", "wv", "wo", "w_attn_gate", "q_norm_scale", "k_norm_scale")
    if state_index:  # the state pools ride the layers where latent attention's workspace does
        workspace = (st_flat, ) if lightning else (st_flat, cv_flat)
    if sparse:  # ... and the pooled keys behind them
        workspace = (workspace or ()) + (idx_flat, )
    probes = []
    if probe:
        if not sparse:
            raise ValueError("probe asked of a model without a block selection")
        # a row's run this step: its tokens are consecutive and end at last_idx
        count = jnp.zeros((last_idx.shape[0], ), jnp.int32).at[seq_idx].add(valid.astype(jnp.int32))
        along = (jnp.maximum(count - 1, 0)[:, None] * jnp.arange(PROBES, dtype=jnp.int32)[None, :]) // (PROBES - 1)
        probe_tok = jnp.maximum(last_idx - jnp.maximum(count - 1, 0), 0)[:, None] + along
    mixed_mlp = moe is not None and cfg.moe_num_dense_layers > 0
    expert_index = {l: i for i, l in enumerate(cfg.expert_layers)}  # a layer's place in the expert layers' arrays
    dense_index = {l: i for i, l in enumerate(cfg.dense_layers)}
    experts = {k: v for k, v in params["blocks"].items() if k in expert_keys}
    per_layer = {k: v for k, v in params["blocks"].items() if k not in expert_keys}

    def index_of(name, l):
        """Layer ``l``'s index into the stacked array ``name``, by the
        layer's kind: an array is stacked over the layers that have it,
        wherever they lie; None: layer ``l`` has none there."""
        if name.startswith(("kda_", "la_", "m2_")):
            return state_index.get(l)
        if name in attention_keys:
            return kv_index.get(l)
        if name in expert_layer_keys:
            return expert_index.get(l)
        if name in dense_layer_keys:
            return dense_index.get(l)
        return l

    def part_of(name):
        """The part of a step that reads the stacked array ``name``: where a
        layer's slice of it is traced (XLA copies some out of their stacks, a
        step, a layer: 4.5% of a dense decode step's device time, PR 53)."""
        if name in dense_layer_keys or name.startswith("ln2"):
            return scopes.MLP
        if name in expert_layer_keys:
            return scopes.MOE
        return scopes.ATTN_OUT if name.endswith("wo") else scopes.ATTN_PROJ

    def layer_slice(name, stacked, i):
        with jax.named_scope(part_of(name)):
            return jax.tree_util.tree_map(lambda a: a[i], stacked)

    if unroll and L <= 48:
        for l in range(L):
            blk_l = {name: layer_slice(name, stacked, i)
                     for name, stacked in sorted(per_layer.items()) if (i := index_of(name, l)) is not None}
            x, k_flat, v_flat, ks_flat, vs_flat, stats, workspace = layer(
                x, blk_l, l, k_flat, v_flat, ks_flat, vs_flat, stats, workspace, cfg.layer_kind(l),
                kv_alone=kv_only and l == L - 1)
    else:
        if kv_only:
            raise NotImplementedError("kv_only under lax.scan: one scan body cannot stop its last layer at the "
                                      "scatter; the ragged forward unrolls up to 48 layers")
        if cfg.per_layer_attention or mixed_mlp:
            raise NotImplementedError("layer_types (windows, ropes, linear-attention, lightning, state-space or "
                                      "mlp-only layers) or leading dense layers under lax.scan: one scan body has one "
                                      "mixer, one window, one rope and one MLP kind; the ragged forward unrolls up to "
                                      "48 layers")

        def scan_body(carry, inp):
            blk, l = inp
            return layer(carry[0], blk, l, *carry[1:]), None

        (x, k_flat, v_flat, ks_flat, vs_flat, stats, workspace), _ = jax.lax.scan(
            scan_body, (x, k_flat, v_flat, ks_flat, vs_flat, stats, workspace),
            (per_layer, jnp.arange(L, dtype=jnp.int32)))
    pools = (k_flat.reshape(k_pool.shape), ) if latent else (k_flat.reshape(k_pool.shape), v_flat.reshape(v_pool.shape))

    # logits_gather semantics: final norm + unembed only each sequence's
    # last token, through the pluggable unembed module
    with jax.named_scope(scopes.LM_HEAD):
        logits = None if kv_only else unembed(params, x, last_idx)
        if logits is not None and cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
    if sparse:
        pools += (workspace[-1].reshape(index_pool.shape), )
    if state_index:
        pools += tuple(flat.reshape(pool.shape) for flat, pool in zip(workspace, state_pools))
    out = (logits, ) + pools + ((ks_flat, vs_flat) if quant else ())
    out = out + (stats, ) if moe_stats else out
    if probe:
        out += ((pos[probe_tok], jnp.stack([p for p, _ in probes], axis=2), jnp.stack([c for _, c in probes], axis=2)), )
    return out
