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

from ....models.transformer import TransformerConfig, apply_rope, mlp_activation, rope_inv_freq, rope_table
from ....moe.grouped import merge_routing_stats


def ragged_forward(cfg: TransformerConfig, block_size: int, params: Dict[str, Any], token_ids, seq_idx, pos, valid,
                   block_tables, last_idx, k_pool, v_pool, use_pallas: bool = False,
                   unroll: bool = True, modules: Dict[str, Any] = None,
                   k_scale=None, v_scale=None, pos_ids=None, attn_mask=None,
                   ctx_pos_ids=None, moe_stats: bool = False, kv_only: bool = False):
    """Returns (last-token logits [S_pad, V], k_pool, v_pool).

    token_ids/seq_idx/pos/valid: [T_pad]; block_tables: [S_pad, max_blocks];
    last_idx: [S_pad]; k_pool/v_pool: [L, NB*bs, nkv, d] (donated), the
    cache's pools as the model's ``kv_entry`` shapes them.

    Latent attention (``cfg.latent_attention``; ``v_pool`` None, and the
    return has the one pool): ``k_pool`` ``[L, NB*bs, 1, W]`` holds a token's
    entry ``[rmsnorm(ckv) | rope(kr) | 0]`` (``kv_lora_rank`` lanes, then
    ``qk_rope_head_dim``, zeros up to ``W``: whole 128-lane tiles), scattered
    at the token's slot exactly as K is. Attention runs in the ABSORBED form:
    with ``W_kvb = [W_K_h | W_V_h]`` a head's query is ``[q_nope_h W_K_h^T |
    rope(q_rope_h) | 0]`` against the entries, scaled by ``1 /
    sqrt(qk_nope_head_dim + qk_rope_head_dim)``; the attention call returns
    ``sum p ckv`` a head and ``W_V_h`` is applied to it. Both are batched
    matmuls over heads around the attention call on the stored
    ``wkv_b_k`` / ``wkv_b_v``; no per-head K or V exists anywhere, for a
    chunk's own tokens and for its history alike.

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
    commits a block: K/V of every layer are written and nothing else is
    wanted, so the last layer stops at its scatter, there is no head, and the
    logits returned are None.

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
    if moe_stats and moe is None:
        raise ValueError("moe_stats asked of a model without experts")
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
    x = embedding(params, token_ids, pid)  # [T, H]
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
    NB = pool_len // block_size
    L = k_pool.shape[0]
    flat_len = L * pool_len
    slot = block_tables[seq_idx, pos // block_size] * block_size + pos % block_size

    quant = k_scale is not None
    # what the attention paths mask by (see the docstring)
    vis_pos = pos | (cfg.diffusion_block_size - 1) if cfg.diffusion_block_size > 1 else pos
    if cfg.diffusion_block_size > 1 and (attn_mask is not None or block_size % cfg.diffusion_block_size):
        raise NotImplementedError(f"blocks of {cfg.diffusion_block_size} under a block-causal mask: no token-tree "
                                  f"mask beside it, and a KV block ({block_size}) holds whole blocks")

    def layer(x, blk, l, k_flat, v_flat, ks_flat, vs_flat, stats=None, kind=None, kv_alone=False):
        """``kind``: the layer's attention kind, static (None in a model of
        one kind, where ``l`` may be traced); ``stats``: the running MoE
        counts; ``kv_alone``: write this layer's K/V and stop."""
        attend = modules["attention_full"] if kind == "full_attention" else attention
        h1 = pre_norm(x, blk["ln1_scale"], blk.get("ln1_bias"))
        bias = (lambda n: blk[n]) if cfg.use_bias else (lambda n: None)
        slot_l = jnp.where(valid, l * pool_len + slot, flat_len)  # this layer's slots in the flat pool
        tables_l = block_tables + l * NB  # layer l's blocks in the flat pool
        if latent:
            c, nope, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
            W = k_flat.shape[-1]
            sin, cos = latent_rope
            cq = pre_norm(linear(h1, blk["wq_a"], None), blk["q_a_norm_scale"])
            qh = linear(cq, blk["wq_b"], None).reshape(T, nq, d)
            kv = linear(h1, blk["wkv_a"], None)
            ckv = pre_norm(kv[:, :c], blk["kv_a_norm_scale"])
            kr = apply_rope(kv[None, :, None, c:], sin, cos)[0]          # [T, 1, rope]: ONE key part for all heads
            entry = jnp.concatenate([ckv[:, None, :], kr], axis=-1)
            entry = jnp.pad(entry, ((0, 0), (0, 0), (0, W - entry.shape[-1])))
            k_flat = k_flat.at[slot_l].set(entry.astype(k_flat.dtype), mode="drop")
            if kv_alone:
                return x, k_flat, v_flat, ks_flat, vs_flat, stats
            # W_K_h^T folded into the query: [q_nope_h W_K_h^T | rope(q_rope_h) | 0] against the entries
            q_lat = jnp.einsum("thn,hcn->thc", qh[..., :nope], blk["wkv_b_k"],
                               preferred_element_type=jnp.float32).astype(qh.dtype)
            q_abs = jnp.concatenate([q_lat, apply_rope(qh[None, ..., nope:], sin, cos)[0]], axis=-1)
            q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, W - q_abs.shape[-1])))
            lat = attend(q_abs, k_flat, None, tables_l, seq_idx, vis_pos, value_dim=c,
                         softmax_scale=1.0 / math.sqrt(d))              # [T, nq, c]: sum p ckv a head
            # ... and W_V_h into the output
            ctx = jnp.einsum("thc,hcv->thv", lat, blk["wkv_b_v"],
                             preferred_element_type=jnp.float32).astype(lat.dtype).reshape(T, nq * dv)
        else:
            qkvb = (lambda n: blk[n]) if cfg.qkv_bias_enabled else (lambda n: None)
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
                return x, k_flat, v_flat, ks_flat, vs_flat, stats

            # scales/tree kwargs only passed when active, so full-precision
            # causal third-party attention implementations keep the original
            # 6-arg call signature
            scales = {"k_scale": ks_flat, "v_scale": vs_flat} if quant else {}
            if attn_mask is not None:
                scales = dict(scales, pos_ids=pid, mask=attn_mask, ctx_pos_ids=ctx_pos_ids)
            ctx = attend(q, k_flat, v_flat, tables_l, seq_idx, vis_pos, **scales).reshape(T, nq * d)
        if cfg.attention_gate:
            gate = linear(h1, blk["w_attn_gate"], None)
            ctx = (ctx.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(ctx.dtype)
        attn_out = linear(ctx, blk["wo"], bias("bo"))

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
                      layer=l - first_expert_layer, gate_bias=blk.get("gate_bias"))
            if stats is not None:
                out, layer_stats = out
                stats = merge_routing_stats(stats, layer_stats)
            if "shared_wi" in blk:  # every token's, whole on every chip of the group
                out = out + dense_mlp(h, blk["shared_wi"], blk.get("shared_wg"), blk["shared_wo"])
            return out

        def post(y, name):  # the sandwich norm on a branch's output
            return pre_norm(y, blk[name]) if cfg.post_norms else y

        if cfg.parallel_residual:  # GPT-J / NeoX / Falcon
            h2 = h1 if cfg.shared_ln else pre_norm(x, blk["ln2_scale"], blk.get("ln2_bias"))
            return x + attn_out + mlp(h2), k_flat, v_flat, ks_flat, vs_flat, stats
        x = x + post(attn_out, "ln1_post_scale")
        h2 = pre_norm(x, blk["ln2_scale"], blk.get("ln2_bias"))
        return x + post(mlp(h2), "ln2_post_scale"), k_flat, v_flat, ks_flat, vs_flat, stats

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
    first_expert_layer = cfg.moe_num_dense_layers if moe is not None else 0
    mixed_mlp = first_expert_layer > 0
    experts = {k: v for k, v in params["blocks"].items() if k in expert_keys}
    per_layer = {k: v for k, v in params["blocks"].items() if k not in expert_keys}

    def index_of(name, l):
        """Layer ``l``'s index into the stacked array ``name``; None: it has none there."""
        if mixed_mlp and name in expert_layer_keys:
            return l - first_expert_layer if l >= first_expert_layer else None
        if mixed_mlp and name in dense_layer_keys:
            return l if l < first_expert_layer else None
        return l

    if unroll and L <= 48:
        for l in range(L):
            blk_l = {name: jax.tree_util.tree_map(lambda a: a[i], stacked)
                     for name, stacked in sorted(per_layer.items()) if (i := index_of(name, l)) is not None}
            x, k_flat, v_flat, ks_flat, vs_flat, stats = layer(
                x, blk_l, l, k_flat, v_flat, ks_flat, vs_flat, stats, cfg.layer_kind(l),
                kv_alone=kv_only and l == L - 1)
    else:
        if kv_only:
            raise NotImplementedError("kv_only under lax.scan: one scan body cannot stop its last layer at the "
                                      "scatter; the ragged forward unrolls up to 48 layers")
        if cfg.per_layer_attention or mixed_mlp:
            raise NotImplementedError("layer_types or leading dense layers under lax.scan: one scan body has "
                                      "one window, one rope and one MLP kind; the ragged forward unrolls up to 48 layers")

        def scan_body(carry, inp):
            blk, l = inp
            return layer(carry[0], blk, l, *carry[1:]), None

        (x, k_flat, v_flat, ks_flat, vs_flat, stats), _ = jax.lax.scan(
            scan_body, (x, k_flat, v_flat, ks_flat, vs_flat, stats),
            (per_layer, jnp.arange(L, dtype=jnp.int32)))
    pools = (k_flat.reshape(k_pool.shape), ) if latent else (k_flat.reshape(k_pool.shape), v_flat.reshape(v_pool.shape))

    # logits_gather semantics: final norm + unembed only each sequence's
    # last token, through the pluggable unembed module
    logits = None if kv_only else unembed(params, x, last_idx)
    out = (logits, ) + pools + ((ks_flat, vs_flat) if quant else ())
    return out + (stats, ) if moe_stats else out
