"""Grouped-GEMM MoE dispatch: expert-sorted tokens through the Pallas
ragged matmul (``ops/pallas/grouped_matmul.py``).

Reference counterpart: the CUTLASS moe_gemm path
(``inference/v2/kernels/cutlass_ops/``) — gather each expert's tokens, run E
grouped GEMMs, scatter back. The one-hot ``[S, E, C]`` dispatch/combine
einsum (``sharded_moe.py``) materializes capacity-padded buffers whose cost
scales as S*E*C; here the FFN work scales with the ACTUAL routed slots (plus
less than one row block per expert for alignment), and the row block follows
the slot count: 8 rows where a decode step routes 4 slots an expert, 128 in
a prefill chunk.

Routing comes in as ``top_idx``/``top_w`` [S, k]: :func:`route_topk` (the
dropless top-k of the softmax over all experts) on the serving path and under
``TransformerConfig.moe_dropless``. A capacity gate's kept assignments go in
the same way (``lax.top_k`` of ``combine.sum(capacity_axis)``; a dropped
token's weights are zero), and then equal the einsum path.

Pipeline (all static shapes, jit-friendly):
  1. (expert id, weight) per token slot [S*k].
  2. a slot's rank within its expert (a running count down the one-hot
     [slots, E]; no sort); per-expert counts → BLOCK-ALIGNED group offsets
     (each group padded to a multiple of the row block with zero rows) →
     scatter tokens into ``x_sorted [T_pad, M]``.
  3. ``block_expert[i]`` = expert owning row block i (the groups that end at
     or before it, counted) — the kernel's scalar-prefetch table — and the
     number of live row blocks.
  4. grouped_matmul chain (up [+ gate] → activation → down).
  5. gather back by slot destination, scale by gate weight, sum a token's
     k slots.
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def _round_up(x, m):
    return (x + m - 1) // m * m


def pick_block_rows(slots: int, num_experts: int) -> int:
    """Rows of one row block for ``slots`` routed slots over ``num_experts``
    experts: the power of two at or above twice the mean slots an expert,
    between 8 and 128. A block is one expert's, so a small one wastes few
    rows where experts hold few slots (256 slots over 64 experts: 8) and a
    large one fills the MXU where they hold many (4,096 over 64: 128)."""
    mean2 = max(1, -(-2 * slots // num_experts))
    return min(128, max(8, 1 << (mean2 - 1).bit_length()))


def route_topk(x, gate_w, top_k: int, renormalise: bool = True, score_func: str = "softmax",
               bias=None, scale: float = 1.0):
    """Dropless routing: every expert's score in float32 (the router's matmul
    too, at the highest precision: the top-k set is a discontinuous function
    of these logits), the ``top_k`` largest, renormalised to sum to one if
    asked. ``score_func`` 'softmax': over ALL experts; 'sigmoid': each
    expert's own. ``bias`` [E] (float32, trained by no gradient): the chosen
    set is the top-k of score + bias, and the weights are the SCORES at the
    chosen experts, without it. ``scale``: a factor on the kept weights.
    x: [S, H]; gate_w: [H, E]. Returns (idx [S, k] int32, weights [S, k]
    float32)."""
    logits = jnp.einsum("sh,he->se", x.astype(jnp.float32), gate_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if score_func == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if bias is None:
        top_p, top_idx = jax.lax.top_k(scores, top_k)
    else:
        _, top_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        top_p = jnp.take_along_axis(scores, top_idx, axis=-1)
    if renormalise:
        total = jnp.sum(top_p, axis=-1, keepdims=True)
        # sigmoid scores can all be tiny where softmax's largest k cannot
        top_p = top_p / (total + 1e-20 if score_func == "sigmoid" else total)
    if scale != 1.0:
        top_p = top_p * scale
    return top_idx.astype(jnp.int32), top_p


def hold_experts(top_idx, top_w, first: int, held: int):
    """Expert parallelism's share of a routing over all experts: the experts
    ``[first, first + held)`` live here. A chosen index becomes its local
    one; an assignment to an absent expert becomes index ``held`` (an expert
    that does not exist: :func:`block_align_dispatch` gives it no row and no
    block) with weight zero, so its term is left out of the token's sum (the
    chip that holds that expert computes it). Nothing stands in for the
    exchange that would carry it there."""
    local = top_idx - first
    here = (local >= 0) & (local < held)
    return jnp.where(here, local, held).astype(jnp.int32), jnp.where(here, top_w, jnp.zeros_like(top_w))


def merge_routing_stats(a, b):
    """``[experts_hit, expert_load_max, slots]`` of two calls together
    (layers of a forward, steps of a decode): the hits and the slots that took
    a row add, the largest load stays."""
    return jnp.stack([a[0] + b[0], jnp.maximum(a[1], b[1]), a[2] + b[2]])


def padded_rows(slots: int, num_experts: int, block_rows: int, cover_all_experts: bool) -> int:
    """Static bound on the rows of the expert-sorted buffer, whatever the
    routing: with every expert owning a block, each group is padded to a
    multiple of the block and at least one; without, there are at most
    ``slots`` blocks (each holds a slot) and less than one block of padding an
    expert."""
    if cover_all_experts:
        return _round_up(slots, block_rows) + num_experts * block_rows
    return block_rows * max(1, min(slots, (slots + num_experts * (block_rows - 1)) // block_rows))


def block_align_dispatch(top_idx, top_w, num_experts: int, block_rows: int, valid=None,
                         cover_all_experts: bool = True):
    """From the routing ``top_idx``/``top_w`` [S, k] over ``num_experts``
    experts: slot order, destinations and the block→expert table.

    ``valid`` [S] bool: tokens that are padding route nowhere (their slots
    take no row and weigh zero). ``cover_all_experts``: every expert owns at
    least one row block, which the weight-gradient kernel needs (it writes
    an expert's block when it visits it); a forward-only caller passes
    False, and an expert without a slot then takes no block and its weights
    are never read.

    Returns, per slot in token order (slot ``s`` is token ``s // k``),
    (flat_tok [S*k], flat_w [S*k], dest [S*k] (``T_pad`` = no row)), then
    block_expert [T_pad//block_rows], T_pad, n_live_blocks, sizes [E]."""
    (S, top_k), E = top_idx.shape, num_experts
    flat_e = top_idx.reshape(-1).astype(jnp.int32)  # index E: no such expert, so no row (``hold_experts``)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.arange(S * top_k, dtype=jnp.int32) // top_k
    if valid is not None:
        live_slot = jnp.repeat(valid, top_k)
        flat_e = jnp.where(live_slot, flat_e, E)  # an expert that does not exist
        flat_w = jnp.where(live_slot, flat_w, jnp.zeros_like(flat_w))
    # no sort: a slot's place in its expert's group is the count of earlier
    # slots of that expert, a running sum down the one-hot [slots, E]
    onehot = flat_e[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]
    before = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - onehot
    rank = jnp.sum(jnp.where(onehot, before, 0), axis=1)  # position within the group
    sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)  # [E]
    padded = _round_up(sizes, block_rows)
    if cover_all_experts:
        padded = jnp.maximum(block_rows, padded)  # zero rows contribute zero gradient
    ends = jnp.cumsum(padded)  # [E]
    starts = ends - padded
    T_pad = padded_rows(S * top_k, E, block_rows, cover_all_experts)  # static bound
    start_of = jnp.sum(jnp.where(onehot, starts[None, :], 0), axis=1)
    dest = jnp.where(flat_e < E, start_of + rank, T_pad).astype(jnp.int32)  # row in the padded buffer
    n_live = (ends[E - 1] // block_rows).astype(jnp.int32)
    # a block's expert is the number of groups that end at or before its
    # first row; a block past the live ones names the last live block's
    # expert: the kernel then has that expert's weights already and reads nothing
    block = jnp.minimum(jnp.arange(T_pad // block_rows, dtype=jnp.int32), jnp.maximum(n_live - 1, 0))
    block_expert = jnp.minimum(jnp.sum(ends[None, :] <= (block * block_rows)[:, None], axis=1), E - 1)
    return flat_tok, flat_w, dest, block_expert.astype(jnp.int32), T_pad, n_live, sizes


def grouped_moe_ffn(x, top_idx, top_w, wi, wo, wg=None,
                    activation: Optional[Callable] = None, interpret: Optional[bool] = None,
                    valid=None, differentiable: bool = True,
                    with_stats: bool = False, layer=None, expected_slots: Optional[int] = None):
    """x: [S, M] tokens; ``top_idx``/``top_w`` [S, k]: each token's experts
    and their weights (a zero weight = a dropped assignment); wi:
    [E, M, F]; wg: optional swiglu gate weights [E, M, F]; wo: [E, F, M].
    ``activation(up, gate)`` (gate is None when wg is None); default
    silu(gate)*up / gelu(up).

    ``layer``: the weights are the stacked ``[L, E, ...]`` arrays of a whole
    model and this call uses layer ``layer`` of them (an int or a traced
    scalar). The kernel then reads its expert's tile out of the stack; a
    sliced ``wi[layer]`` handed to a Pallas call would first be COPIED, a
    layer's experts written and read once more in every step.

    The row block is :func:`pick_block_rows` of the slot count, or of
    ``expected_slots`` where the experts here are a share of those routed over
    (:func:`hold_experts`: most slots then take no row, the buffer is still
    sized for all of them); ``interpret``
    defaults to whether the backend is not a TPU. ``valid`` [S]: padding
    tokens route nowhere. ``differentiable=False`` (serving): experts without
    a slot take no row block, so a step reads only the experts it hit.

    Returns y [S, M] = sum over kept assignments of w * FFN_e(x) — the same
    quantity the einsum combine computes; with ``with_stats`` also int32
    ``[experts_hit, expert_load_max, slots]`` of this call's routing: experts
    with a slot, the most slots on one, and the slots that took a row.
    """
    from ..ops.pallas.grouped_matmul import gmm, grouped_matmul

    S, M = x.shape
    E = wi.shape[-3]
    if layer is not None and wi.dtype != x.dtype:
        # stored in another type than they are multiplied in: cast this
        # layer's experts alone, not the stack
        wi, wo, wg = (w if w is None else w[layer] for w in (wi, wo, wg))
        layer = None
    block_rows = pick_block_rows(top_idx.size if expected_slots is None else expected_slots, E)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if activation is None:
        activation = (lambda up, gate: jax.nn.silu(gate) * up) if wg is not None \
            else (lambda up, gate: jax.nn.gelu(up))
    tok, w_slot, dest, block_expert, T_pad, n_live, sizes = block_align_dispatch(
        top_idx, top_w, E, block_rows, valid=valid, cover_all_experts=differentiable)
    if layer is not None:
        block_expert = block_expert + jnp.asarray(layer, jnp.int32) * E
        flat = (lambda w: w.reshape((-1, ) + w.shape[2:]))  # [L * E, ...]: no data moves
    else:
        flat = (lambda w: w)
    if differentiable:
        def matmul(lhs, rhs):
            return grouped_matmul(lhs, flat(rhs).astype(x.dtype), block_expert, block_t=block_rows,
                                  interpret=interpret)
    else:
        def matmul(lhs, rhs):
            return gmm(lhs, flat(rhs).astype(x.dtype), block_expert, block_t=block_rows,
                       interpret=interpret, num_live=n_live)
    x_sorted = jnp.zeros((T_pad, M), x.dtype).at[dest].set(x[tok], mode="drop")
    up = matmul(x_sorted, wi)
    gate = matmul(x_sorted, wg) if wg is not None else None
    y_sorted = matmul(activation(up, gate), wo)
    y_slots = y_sorted[jnp.minimum(dest, T_pad - 1)] * w_slot[:, None].astype(x.dtype)
    y = jnp.sum(y_slots.reshape(S, -1, M), axis=1)  # a token's k slots are adjacent
    if with_stats:
        return y, jnp.stack([jnp.sum(sizes > 0), jnp.max(sizes), jnp.sum(sizes)]).astype(jnp.int32)
    return y
