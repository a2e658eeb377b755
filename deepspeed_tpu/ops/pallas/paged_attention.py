"""Paged (blocked) attention over a flat KV pool — the FastGen data-plane
kernel.

Analog of the reference ``v2/kernels/ragged_ops/blocked_flash`` (CUDA flash
attention adapted to paged KV block tables, SURVEY.md §2.3). TPU design: two
Pallas kernels on ``PrefetchScalarGridSpec`` grids whose K/V BlockSpec index
maps read the *block table* (scalar-prefetched) — the DMA engine then
streams exactly the KV blocks a sequence owns, straight from HBM, while the
online softmax accumulates in VMEM scratch: ``paged_attn_q_tiled`` for
batches with multi-token chunks, ``paged_attn_kv_split`` for everything
else; :func:`choose_kernel` picks between them from the static shapes.

Token-level formulation: query token ``t`` belongs to ``seq_idx[t]`` at
absolute position ``pos[t]`` and attends all cached positions ``<= pos[t]``.
This covers prefill chunks and decode steps uniformly (Dynamic SplitFuse
mixes both in one batch).

``paged_attention_reference`` is the jnp gather implementation used for CPU
tests and as the numerics oracle (reference test strategy: kernel vs
reference, tests/unit/inference/v2/kernels).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _contiguity_ok(seq_idx, S: int, pos=None) -> bool:
    """True when the tiled grid's layout contract holds: same-sequence
    tokens contiguous, at most S runs plus the trailing pad run, and (where
    ``pos`` is given) a run's positions ascending by one (a chunk) or
    standing still (the pad run) — a position that falls starts a new run,
    and a tile's positions span at most ``q_tile``, which is what bounds its
    KV blocks under a sliding window. Traced arguments (the jitted ragged
    step) are covered by the SplitFuse batch layout invariant itself
    (``ragged_wrapper.finalize``)."""
    if seq_idx is None or isinstance(seq_idx, jax.core.Tracer):
        return True
    s = np.asarray(seq_idx)
    newrun = s[1:] != s[:-1]
    if pos is not None and not isinstance(pos, jax.core.Tracer):
        step = np.diff(np.asarray(pos))
        if np.any(~newrun & (step > 1)):
            return False
        newrun = newrun | (step < 0)
    return 1 + int(np.count_nonzero(newrun)) <= S + 1


# Which kernel each traced shape took, and the rule that decided:
# ``(T, S, max_blocks) -> choose_kernel's record``. ``paged_attention``
# chooses while ``jit`` traces the program, from static shapes, so filling
# this costs nothing at run time; the serving engine reads it once per
# compiled program and puts it on the step's span.
KERNEL_CHOICES = {}


def kernel_choice(T: int, S: int, max_blocks: int):
    """The recorded choice for a traced shape, or None if no program of that
    shape has been traced in this process."""
    return KERNEL_CHOICES.get((int(T), int(S), int(max_blocks)))


def _note_choice(T, S, max_blocks, choice):
    KERNEL_CHOICES[(int(T), int(S), int(max_blocks))] = choice


# The largest q-tile: the MXU then sees ``g * 128`` query rows a kv head, and
# a KV block is fetched and read out by kv head once per 128 query tokens (256
# measured 2-5% faster at twice the VMEM: PERF.md, PR 25).
_LONG_ROW_TILE = 128
# ... unless the pools lie BY HEAD with ONE query head a kv head: the MXU's left
# operand is then the tile's own rows and nothing else (128 rows where a group
# of 4 gives it 512, under weights it loads anew for every head), so a long-row
# step of such a call takes the largest tile whose working set
# (:func:`_q_tiled_vmem_bytes`, and half again for Mosaic's own temporaries) the
# kernel's scoped limit admits (PERF.md section 6, PR 40: 20 heads of 256 over
# 14k and 32k tokens; over token-major pools, whose heads go through a scratch
# by kv head, no tile beyond 128 has run on the chip, so they keep it)
_Q_TILED_VMEM_LIMIT = 100 << 20


def _tiled_blocks_per_step(block_size: int, token_major: bool) -> int:
    """KV blocks one grid step of ``paged_attn_q_tiled`` takes (1, 2 or 4):
    as many as fill a 128-lane tile when a block is narrower than that, so a
    kv head's scores, masks and softmax run in whole registers and what a step
    pays whatever its keys (the rescale of ``m``/``l``/``acc``) is paid a tile
    of lanes. ``token_major``: bf16 or float32 K and V pools ``[pool_len,
    nkv, d]``, whose heads go through the scratch by kv head, where the
    step's blocks lie side by side; a latent pool, pools by head (read where
    the block lies) and int8 pools (a scale block a KV block) keep one."""
    return max(1, min(4, _LANES // block_size)) if token_major else 1


def choose_kernel(T: int, S: int, max_blocks: int, nq: int, block_rows: int, d: int, itemsize: int,
                  seq_idx=None, pos=None, parts: int = 2, kv_by_head: int = 0, block_size: int = 128) -> dict:
    """Which kernel serves a batch of ``T`` tokens over ``S`` table rows of
    ``max_blocks`` columns, and with which tile: ``{"kernel", "q_tile",
    "blocks_per_step", "rule"}``. It follows from the program's static shapes
    (and the backend) alone; no argument, file or environment variable
    changes it. ``block_rows`` is a KV block's rows, ``block_size * nkv``,
    ``itemsize`` the pool's and ``parts`` the pools a grid step fetches a block
    of (2: K and V; 1: a latent entry, read once for score and value);
    ``kv_by_head`` the kv heads of pools BY HEAD (0: token-major pools);
    ``block_size`` a KV block's tokens; ``seq_idx``/``pos`` only let a caller
    with CONCRETE arrays have the tiled grid's layout contract checked. In
    order:

    - off the TPU (``off_tpu``), or heads the kernels do not tile (``nq < 8``,
      ``d % 128``: ``unsupported_shape``): the gather reference;
    - ``T >= 64`` with at least two tokens a row: ``paged_attn_q_tiled``.
      ``T / S`` is the mean tokens a row of the bucket, and a step's chunks
      are longer than its mean row because most rows beside them are
      one-token decode rows, so the tile is the power of two at or under
      ``2 T / S`` (and ``T``), between 8 and 128. A long-prompt step (2,048
      tokens over at most 8 rows) takes the large tile
      (``heuristic:long_rows``), and over pools by head with one query head a
      kv head (``kv_by_head == nq``) the largest power of two up to ``2 T /
      S`` whose working set ``_Q_TILED_VMEM_LIMIT`` admits
      (``heuristic:long_rows_one_head``: the expanded form of latent attention
      over its workspace of per-head keys and values, 512 at 20 heads of
      256). A step with many rows for its tokens (a
      512-token SplitFuse ``put`` over 32 rows, a linear speculative verify of
      k+1 tokens a row) takes a smaller one (``heuristic:short_rows``),
      because a row beyond the chunks' own is a tile of its own and each
      costs a q and an output tile of DMA and a grid step a live KV block
      (the grid runs the live (tile, block) pairs alone:
      :func:`_tiled_work_list`). ``blocks_per_step`` is
      :func:`_tiled_blocks_per_step`'s: one block of 128 tokens or more a
      grid step, two of 64, four of 32 or fewer, over token-major pools that
      are not int8 (``itemsize`` above 1);
    - such a batch whose concrete ``seq_idx`` breaks the layout contract
      (:func:`_contiguity_ok`: the tiled grid would overflow its static tile
      bound and scatter tokens into the wrong tiles): ``contiguity_demoted``
      to the decode kernel;
    - everything else: ``paged_attn_kv_split``, whose work list takes
      ``seq_idx`` and ``pos`` a TOKEN, at :func:`_decode_blocks_per_step` KV
      blocks a grid step. The rule says which kind of batch it was: a table
      under 8 columns (``heuristic:short_table``), more than two tokens a
      row in a batch under 64 tokens (``heuristic:multi_token``: a chunk's
      tail beside decode rows), else decode (``heuristic:long_table``)."""
    choice = {"kernel": "paged_attention_reference", "q_tile": 1, "blocks_per_step": 1}
    if jax.default_backend() != "tpu":
        return {**choice, "rule": "off_tpu"}
    if nq < 8 or d % 128 != 0:
        return {**choice, "rule": "unsupported_shape"}
    per_row2 = min(T, 2 * T // max(S, 1))
    if T >= 64 and per_row2 >= 4:
        if _contiguity_ok(seq_idx, S, pos):
            fits = 1 << (per_row2.bit_length() - 1)
            qt = max(8, min(_LONG_ROW_TILE, fits))
            rule = "heuristic:long_rows" if qt == _LONG_ROW_TILE else "heuristic:short_rows"
            if kv_by_head == nq and qt == _LONG_ROW_TILE:
                while 2 * qt <= fits and _q_tiled_vmem_bytes(nq * 2 * qt, 2 * qt, d, block_rows // nq, nq, itemsize,
                                                             itemsize) * 3 // 2 <= _Q_TILED_VMEM_LIMIT:
                    qt *= 2
                rule = "heuristic:long_rows_one_head"
            per_step = _tiled_blocks_per_step(block_size, parts == 2 and not kv_by_head and itemsize > 1)
            return {"kernel": "paged_attn_q_tiled", "q_tile": qt, "blocks_per_step": per_step, "rule": rule}
        rule = "contiguity_demoted"
    elif max_blocks < 8:
        rule = "heuristic:short_table"
    elif T > 2 * max(S, 1):
        rule = "heuristic:multi_token"
    else:
        rule = "heuristic:long_table"
    return {"kernel": "paged_attn_kv_split", "q_tile": 1, "rule": rule,
            "blocks_per_step": _decode_blocks_per_step(block_rows, d, itemsize, parts)}


def paged_attention(q, k_pool, v_pool, block_tables, seq_idx, pos, block_size: int, window=None,
                    alibi=None, k_scale=None, v_scale=None, value_dim=None, softmax_scale=None, selection=None):
    """q: [T, nq, d]; k_pool/v_pool: [pool_len, nkv, d] (one layer,
    pool_len = num_blocks*block_size, may include one trailing scratch slot);
    block_tables: [S, max_blocks]; seq_idx/pos: [T].
    ``window``: sliding-window attention (Mistral) — token at position p
    attends cached positions in (p - window, p].
    ``k_scale``/``v_scale``: int8-KV mode (the FastGen quantized-KV analog,
    reference ``csrc/quantization/``) — pools hold int8 values and the
    scales [nkv, pool_len] hold one fp32 absmax/127 factor per (kv-head,
    slot); dequant happens at the kernel's tile read, so only int8 bytes
    stream from HBM.
    ``v_pool`` None: a LATENT pool (latent attention in the absorbed form).
    ``k_pool`` ``[pool_len, 1, d]`` holds one entry a token that every query
    head attends (a group of ``nq``), the score is over all ``d`` lanes of it
    and the value is its first ``value_dim`` lanes: the block is fetched ONCE a
    grid step for both. ``softmax_scale`` replaces ``1 / sqrt(d)`` (a latent
    entry's width is not the head's).
    Pools of FOUR dimensions are pools BY HEAD, ``[nkv, blocks, block_size,
    d]``: a head's blocks together, each the ``[block_size, d]`` of its tokens
    (the workspace latent attention's expanded form makes per-head K and V in,
    ``flat_model.ragged_forward``: laid out for the tiled kernel, which fetches
    a table column's block of every head in a grid step and reads each where
    it lies). No int8 scales there, and no decode kernel: the caller's rows
    are long.
    ``selection`` ``[T, nkv, max_blocks]`` bool: a learned block-sparse
    selection, computed in the program from the queries themselves: token
    ``t``'s heads of kv head ``n`` attend, of the keys at or before its
    position, those of the table columns ``j`` with ``selection[t, n, j]``
    alone. Both kernels' work lists then lay grid steps for selected blocks
    alone: a tile of several tokens (and a block of several kv heads) reads
    the UNION of its tokens' and heads' blocks and masks per token and head
    inside (:func:`_tiled_work_list`, :func:`_decode_work_list`). No window,
    alibi, int8 scales, latent pool or pools by head beside it. None: every
    visible block, the lists and bodies as they were.
    The kernel and its tile are :func:`choose_kernel`'s, from the shapes.
    Returns [T, nq, d] (``[T, nq, value_dim]`` over a latent pool); under a
    ``selection`` ``(out, read)``, ``read`` int32 ``[3]``: the (query token,
    table column) pairs the work list that ran served (for every live column
    it laid, the tokens of the column's tile at or after it: a tile's union is
    what the kernel fetches; the gather counts a token's own columns), then
    the (tile, column) pairs the TILED list laid and the grid steps it ran for
    them, :func:`_tiled_blocks_per_step` columns a step (0 and 0 from the
    decode kernel and the gather, which lay no tiles)."""
    T, nq, d = q.shape
    nkv = k_pool.shape[0 if k_pool.ndim == 4 else 1]
    S, max_blocks = block_tables.shape
    if k_pool.ndim == 4 and (v_pool is None or k_scale is not None or k_pool.shape[2] != block_size):
        raise ValueError(f"pools by head are K and V [nkv, blocks, {block_size}, d] without int8 scales")
    if window is not None:
        window = int(window)
    latent = v_pool is None
    if latent and (nkv != 1 or k_scale is not None or alibi is not None or not value_dim):
        raise ValueError("a latent pool is [pool_len, 1, d] with value_dim lanes of value, no int8 scales, no alibi")
    _check_selection(selection, T, nkv, max_blocks, window, alibi, k_scale, latent or k_pool.ndim == 4)
    choice = choose_kernel(T, S, max_blocks, nq, block_size * nkv, d, k_pool.dtype.itemsize, seq_idx, pos,
                           parts=1 if latent else 2, kv_by_head=nkv if k_pool.ndim == 4 else 0, block_size=block_size)
    _note_choice(T, S, max_blocks, choice)
    if k_pool.ndim == 4 and choice["kernel"] == "paged_attn_kv_split":
        raise NotImplementedError(f"pools by head under the decode kernel ({T} tokens over {S} rows: {choice['rule']})")
    if choice["kernel"] == "paged_attention_reference":
        if choice["rule"] == "unsupported_shape":
            # off-TPU the oracle is the design; ON TPU a shape miss silently
            # costing a full context gather per layer per step must be loud
            from ...utils.logging import warning_once

            warning_once(f"pallas paged attention: unsupported shape (nq={nq}, d={d}; needs "
                         "nq>=8, d%128==0) — serving through the DENSE gather fallback")
        return paged_attention_reference(q, k_pool, v_pool, block_tables, seq_idx, pos, block_size,
                                         window=window, alibi=alibi, k_scale=k_scale, v_scale=v_scale,
                                         value_dim=value_dim, softmax_scale=softmax_scale, selection=selection)
    if k_scale is not None and block_size % 128 != 0:
        # the scale block (nkv, block_size) must be lane-aligned: the TPU
        # lowering rejects it otherwise, with a message that names neither
        # the option nor the remedy
        raise ValueError(f"int8 KV on TPU needs kv_block_size % 128 == 0, got {block_size}")
    alibi_t = tuple(np.asarray(alibi).tolist()) if alibi is not None else None
    # ONE grid, chosen above from the shape; a grid the chip refuses RAISES
    # (here at trace/lowering, or at the enclosing jit's compile) — it is
    # never downgraded to another grid or to the gather, which would turn a
    # broken kernel into a slow server instead of an error. The gather is the
    # tests' reference, the off-TPU path and the (warned) path for the shapes
    # the kernels do not support, nothing else.
    return _pallas_paged(q, k_pool, v_pool, block_tables, seq_idx.astype(jnp.int32),
                         pos.astype(jnp.int32), block_size=block_size, window=window,
                         alibi=alibi_t, k_scale=k_scale, v_scale=v_scale, q_tile=choice["q_tile"],
                         value_dim=value_dim, softmax_scale=softmax_scale, selection=selection)


# kv heads a selection's mask holds a (tile, KV block) pair at most (whole bf16 registers of 16 sublanes)
_SELECTION_HEADS = 32


def _mask_heads(nkv: int) -> int:
    """Sublanes of a (tile, KV block) pair's mask: the kv heads in whole bf16 registers."""
    return -(-nkv // 16) * 16


def _check_selection(selection, T: int, nkv: int, max_blocks: int, window, alibi, k_scale, other_pool: bool):
    if selection is None:
        return
    if tuple(selection.shape) != (T, nkv, max_blocks) or nkv > _SELECTION_HEADS:
        raise ValueError(f"a selection is [tokens, kv heads (at most {_SELECTION_HEADS}), table columns] = "
                         f"{(T, nkv, max_blocks)}, got {tuple(selection.shape)}")
    if window is not None or alibi is not None or k_scale is not None or other_pool:
        raise NotImplementedError("a block selection beside a sliding window, alibi, int8 KV, a latent pool or pools "
                                  "by head: the selected lists and masks are built for plain K and V pools")


def paged_attention_reference(q, k_pool, v_pool, block_tables, seq_idx, pos, block_size: int,
                              window=None, alibi=None, k_scale=None, v_scale=None,
                              pos_ids=None, mask=None, ctx_pos_ids=None, value_dim=None, softmax_scale=None,
                              selection=None):
    """Gather-based oracle: materializes each sequence's context. ``alibi``:
    per-head slopes [nq] (Bloom). ``k_scale``/``v_scale``: int8-KV
    dequantization factors [nkv, pool_len] (see ``paged_attention``).
    ``pos_ids``: logical positions for alibi distances when they differ
    from the KV slot positions (token-tree verification); ``mask``: explicit
    [T, C] visibility replacing the causal/window mask — the tree attention
    mask (the caller owns window semantics inside it); ``ctx_pos_ids``:
    [S, C] logical position of every context slot (tree nodes sit at flat
    slots but depth-based logical positions — alibi distances must use the
    logical ones). ``v_pool`` None, ``value_dim``, ``softmax_scale``: a latent
    pool, and pools of four dimensions pools by head, as in
    ``paged_attention``."""
    T, nq, d = q.shape
    if k_pool.ndim == 4:  # [nkv, blocks, block, d] as the token-major pool the gather reads
        k_pool, v_pool = (jnp.moveaxis(pool, 0, 2).reshape(-1, pool.shape[0], d) for pool in (k_pool, v_pool))
    nkv = k_pool.shape[1]
    g = nq // nkv
    S, max_blocks = block_tables.shape
    C = max_blocks * block_size
    ctx_slots = (block_tables[:, :, None] * block_size +
                 jnp.arange(block_size, dtype=jnp.int32)[None, None, :]).reshape(S, C)
    ctxk = k_pool[ctx_slots].astype(jnp.float32)  # [S, C, nkv, d]
    ctxv = ctxk[..., :value_dim] if v_pool is None else v_pool[ctx_slots].astype(jnp.float32)
    if k_scale is not None:
        ctxk = ctxk * jnp.transpose(k_scale)[ctx_slots][..., None]  # [S, C, nkv, 1]
        ctxv = ctxv * jnp.transpose(v_scale)[ctx_slots][..., None]
    qr = (q.astype(jnp.float32) * (softmax_scale or 1.0 / math.sqrt(d))).reshape(T, nkv, g, d)
    s = jnp.einsum("tngd,tcnd->tngc", qr, ctxk[seq_idx])
    pid = pos if pos_ids is None else pos_ids
    if alibi is not None:
        ctx_pid = (jnp.arange(C, dtype=jnp.int32)[None, :] if ctx_pos_ids is None
                   else ctx_pos_ids[seq_idx])
        rel = ctx_pid.astype(jnp.float32) - pid[:, None].astype(jnp.float32)
        s = s + jnp.asarray(alibi, jnp.float32).reshape(nkv, g)[None, :, :, None] * rel[:, None, None, :]
    if mask is not None:
        causal = mask
    else:
        causal = jnp.arange(C, dtype=jnp.int32)[None, :] <= pos[:, None]
        if window is not None:
            causal = causal & (pos[:, None] - jnp.arange(C, dtype=jnp.int32)[None, :] < window)
    causal = causal[:, None, None, :]
    if selection is not None:  # [T, nkv, max_blocks] over the blocks' tokens, for every head of a kv head's group
        causal = causal & jnp.repeat(selection, block_size, axis=2)[:, :, None, :]
    s = jnp.where(causal, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("tngc,tcnd->tngd", p, ctxv[seq_idx])
    out = out.reshape(T, nq, ctxv.shape[-1]).astype(q.dtype)
    if selection is None:
        return out
    own = jnp.arange(selection.shape[2], dtype=jnp.int32)[None, :] <= (pos // block_size)[:, None]
    return out, _read_counts(jnp.sum(jnp.any(selection, axis=1) & own & (pos >= 0)[:, None], dtype=jnp.int32))


def _read_counts(pairs, items=0, steps=0):
    """``paged_attention``'s second result under a selection, int32 ``[3]``."""
    return jnp.stack([jnp.asarray(c, jnp.int32) for c in (pairs, items, steps)])


def _slopes_rows(alibi, reps):
    """Per-head alibi slopes as kernel rows [len(alibi)*reps, 1], built from
    Python floats: each ``jnp.full`` embeds a SCALAR constant, which Pallas
    accepts — a closure-captured ``jnp.asarray(tuple)`` array is rejected at
    kernel trace ("captures constants ... pass them as inputs"), which
    silently broke an alibi path before this helper."""
    return jnp.concatenate([jnp.full((reps, 1), float(a), jnp.float32) for a in alibi], axis=0)


def _slopes_tok_major(alibi_g, rows):
    """Alibi slopes of one kv head's ``g`` query heads as a [rows, 1] column
    for token-major rows (``row = t * g + h``), from scalar constants (see
    :func:`_slopes_rows`)."""
    out = jnp.full((rows, 1), float(alibi_g[0]), jnp.float32)
    if len(alibi_g) > 1:
        h = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % len(alibi_g)
        for i, a in enumerate(alibi_g[1:], start=1):
            out = jnp.where(h == i, float(a), out)
    return out


@functools.partial(jax.jit, static_argnames=("block_size", "interpret", "window", "alibi", "q_tile", "value_dim",
                                             "softmax_scale", "blocks_per_step"))
def _pallas_paged(q, k_pool, v_pool, block_tables, seq_idx, pos, block_size: int, interpret: bool = False,
                  window=None, alibi=None, k_scale=None, v_scale=None, q_tile: int = 1, value_dim=None,
                  softmax_scale=None, selection=None, blocks_per_step=None):
    """The kernel the caller names, with no choice of its own: ``q_tile``
    above 1 runs ``paged_attn_q_tiled`` at that tile, 1 (one query token a
    grid row) the decode kernel ``paged_attn_kv_split``. ``paged_attention``
    passes :func:`choose_kernel`'s tile; the interpret-mode parity tests
    name each body themselves. ``v_pool`` None: a latent pool
    (``paged_attention``), given to either kernel as its K operand alone.
    ``blocks_per_step`` is the tests': the KV blocks a grid step of the tiled
    kernel takes over token-major pools in place of
    :func:`_tiled_blocks_per_step`'s (the chip's before and after from one
    tree); nothing of the program passes it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d = q.shape[2]
    _check_selection(selection, q.shape[0], k_pool.shape[0 if k_pool.ndim == 4 else 1], block_tables.shape[1], window,
                     alibi, k_scale, v_pool is None or k_pool.ndim == 4)
    if k_pool.ndim == 4:  # pools by head, which only the tiled kernel reads
        if q_tile <= 1 or k_scale is not None:
            raise NotImplementedError("pools by head [nkv, blocks, block, d] are the tiled kernel's, without int8 scales")
        return _paged_q_tiled(pl, pltpu, q, k_pool, v_pool, block_tables, seq_idx, pos, None, None,
                              block_size=block_size, q_tile=q_tile, window=window, alibi=alibi, interpret=interpret,
                              softmax_scale=softmax_scale)
    nkv = k_pool.shape[1]
    # view the pool as whole blocks; drop any trailing scratch remainder
    n_pool_blocks = k_pool.shape[0] // block_size
    n_live = n_pool_blocks * block_size
    quant = k_scale is not None
    # scales stay [nkv, cols]: sublane = nkv, lane = block_size — the layout
    # the scatter side maintains natively, no per-call transpose
    ks2, vs2 = (k_scale[:, :n_live], v_scale[:, :n_live]) if quant else (None, None)

    # a block as one [block * nkv, d] matrix, row t * nkv + n: the pool's own
    # bytes, the one operand both kernels take (no relayout between them)
    as_rows = lambda pool: None if pool is None else pool[:n_live].reshape(n_pool_blocks, block_size * nkv, d)
    if q_tile > 1:
        return _paged_q_tiled(pl, pltpu, q, as_rows(k_pool), as_rows(v_pool), block_tables, seq_idx, pos,
                              ks2, vs2, block_size=block_size, q_tile=q_tile, window=window,
                              alibi=alibi, interpret=interpret, value_dim=value_dim, softmax_scale=softmax_scale,
                              selection=selection, blocks_per_step=blocks_per_step)
    # the decode kernel takes the int8 scales [nkv, cols] laid out to match the rows
    by_col = lambda sc: jnp.transpose(sc).reshape(n_pool_blocks, 1, block_size * nkv)
    return _paged_kv_split(pl, pltpu, q, as_rows(k_pool), as_rows(v_pool), block_tables, seq_idx,
                           pos, by_col(ks2) if quant else None, by_col(vs2) if quant else None,
                           block_size=block_size, window=window, alibi=alibi, interpret=interpret,
                           value_dim=value_dim, softmax_scale=softmax_scale, selection=selection)


# tokens of a tile's rows that the short pass of ``_paged_q_tiled`` covers: a
# tile holding no more than this many valid tokens (a one-token decode row
# riding beside a prefill chunk, a short ragged tail) meets the MXU with these
# rows only, whatever ``q_tile`` is
_SHORT_TILE_TOKENS = 8
_LANES = 128


# rows of a latent tile that one pass of the head loop takes: every row of the
# tile attends the same entry, so the loop walks the tile's rows in chunks
_LATENT_ROW_CHUNK = 512


def _latent_row_chunk(G: int) -> int:
    """The largest divisor of a latent tile's ``G`` rows that is a multiple
    of 8 and at most ``_LATENT_ROW_CHUNK`` (``G`` itself when it is small)."""
    return max((c for c in range(8, min(G, _LATENT_ROW_CHUNK) + 1, 8) if G % c == 0), default=G)


def _q_tiled_vmem_bytes(R: int, G: int, d: int, block_size: int, nkv: int, q_itemsize: int,
                        kv_itemsize: int, per_step: int = 1) -> int:
    """VMEM working set of one ``paged_attn_q_tiled`` grid step of
    ``per_step`` KV blocks: the q and output tiles and each block's K and V
    ``[block_size * nkv, d]`` double-buffered by the pipeline (and the step's
    blocks once more, side by side by kv head, with the words of one load and
    their unpacked heads in float32), the float32 ``acc``, the lane-replicated
    ``m``/``l`` and positions, and one kv head's float32 scores and
    probabilities over the step's keys."""
    tiles = 2 * 2 * R * d * q_itemsize                                   # q in, o out
    kv = per_step * 2 * 2 * block_size * nkv * d * kv_itemsize           # K, V: the pool's rows, no padding
    kv += per_step * 2 * nkv * block_size * d * 4                        # by head, at most float32
    kv += 2 * (1 + 4 // kv_itemsize) * block_size * d * 4                # one load's words and its heads
    state = R * d * 4 + 2 * R * _LANES * 4                               # acc, m, l
    pos = 2 * G * _LANES * 4
    head = 4 * G * max(per_step * block_size, _LANES) * 4                # s, p and their temporaries
    return tiles + kv + state + pos + head


def _head_load_path(dtype, nkv: int) -> str:
    """How ``paged_attn_q_tiled`` reads one kv head out of a KV block
    ``[block * nkv, d]`` (row ``t * nkv + n`` is head ``n`` of token ``t``),
    from the pool's STATIC dtype and head count alone: ``whole`` (one head:
    the block is the head), ``rows`` (32-bit elements: every ``nkv``-th row
    is a strided load), ``words`` (a bf16 or int8 pool, ``p = 4 // itemsize``
    rows to a 32-bit sublane word, ``nkv % p == 0``: every ``nkv / p``-th
    word holds heads ``n .. n + p - 1`` of one token, one strided word load
    per ``p`` heads: every serving configuration), or ``cut`` (a word would
    mix two tokens, bf16 with an odd head count or int8 with two, or the
    halves of a word are not the dtype's values: the value-level cut)."""
    dtype = jnp.dtype(dtype)
    if nkv == 1:
        return "whole"
    if dtype.itemsize == 4:
        return "rows"
    packs = dtype in (jnp.bfloat16, jnp.int8) and nkv % (4 // dtype.itemsize) == 0
    return "words" if packs else "cut"


def _block_heads(pl, pltpu, ref, nkv: int, block_size: int):
    """Yields the ``nkv`` heads of the KV block ``ref`` ``[1, block_size *
    nkv, d]`` in order, each ``[block_size, d]`` with the pool's own values:
    in the pool's dtype, but int8 widened to int32 (the caller dequantises in
    float32 anyway). The ``words`` path is one strided load of the block
    bitcast to ``uint32`` per ``4 // itemsize`` heads and a shift or a mask a
    head: a bf16 is the high half of its float32, an int8 the arithmetic shift
    of its byte moved to the top. A strided load of 32-bit rows is an ordinary
    load; the per-head ``kb[:, n, :]`` it replaces gathered sublanes out of
    every packed tile of the block, 2.0 of a live step's 2.755 us at 4 kv
    heads (PERF.md section 6, PR 34 and PR 37)."""
    dt = ref.dtype
    path = _head_load_path(dt, nkv)
    if path == "whole":
        yield ref[0]
    elif path == "rows":
        for n in range(nkv):
            yield ref[0, pl.ds(n, block_size, stride=nkv), :]
    elif path == "cut":
        block = ref[0].reshape(block_size, nkv, ref.shape[2])
        for n in range(nkv):
            yield block[:, n, :]
    else:
        p = 4 // dt.itemsize
        words = ref.bitcast(jnp.uint32)
        for n in range(0, nkv, p):
            w = words[0, pl.ds(n // p, block_size, stride=nkv // p), :]
            if dt == jnp.bfloat16:
                yield pltpu.bitcast(w << 16, jnp.float32).astype(dt)
                yield pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32).astype(dt)
            else:
                wi = pltpu.bitcast(w, jnp.int32)
                for i in range(p):
                    yield (wi << (24 - 8 * i)) >> 24


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` column as ``[rows, n]``: itself when
    the widths agree (no broadcast is emitted), else its first lane spread."""
    return x if x.shape[1] == n else jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _lane_copies(x, n: int):
    """:func:`_lanes` for the tiled kernel's accumulator, where ``n`` is a
    value's width: copies of the column side by side when ``n`` is several
    whole ones (registers used again: values of 256 or 512 lanes, where
    spreading the first lane over them was a third of a live step's time over
    a latent pool: PERF.md section 6, PR 40)."""
    reps, rest = divmod(n, x.shape[1])
    return _lanes(x, n) if rest or reps == 1 else jnp.concatenate([x] * reps, axis=1)


# what the scalar-prefetched work list of ``paged_attn_q_tiled`` may take of a
# core's scalar memory. A v5e has 1 MiB and its compiler refuses the program
# that passes it with whatever else it keeps there (compiled for a described
# v5e, PR 34: a list of 1.00 MB passes, one of 1.21 MB is "Used 1.16M of 1.00M
# smem"); half is the list's, eight times what the widest serving shape takes
_TILED_SMEM_BYTES = 512 << 10


def _tiled_smem_bytes(n_tiles: int, cols: int, S: int, max_blocks: int, per_step: int = 1) -> int:
    """Scalar memory ``paged_attn_q_tiled`` prefetches for a shape: the two
    arrays of the work list (an item's tile, and the column of each of its
    ``per_step`` slots: ``n_tiles x ceil(cols / per_step)`` items at most), a
    tile's table row and token count, and the block table, whose rows are
    padded to whole 128-lane words. Several blocks a step take no more than
    one does once a tile can have three columns."""
    items = n_tiles * -(-cols // per_step)
    return 4 * ((1 + per_step) * items + 2 + 2 * n_tiles + S * -(-max_blocks // _LANES) * _LANES)


def _tile_runs(seq_idx, pos, q_tile: int, xp=jnp):
    """The tile and the slot inside it of every token of a ragged batch: a
    run is one sequence's chunk (the sequence changes, or the position falls:
    the pad run, seq 0 at position 0, behind a chunk of row 0), and a run is
    cut into tiles of ``q_tile`` tokens from its first token on, so no tile
    spans two runs. ``xp`` is ``jnp`` for the traced program and ``numpy``
    for the host's count of the same grid (:func:`tiled_kv_counts`)."""
    T = pos.shape[0]
    tok = xp.arange(T, dtype=xp.int32)
    newrun = xp.concatenate([xp.ones((1, ), bool),
                             xp.logical_or(seq_idx[1:] != seq_idx[:-1], pos[1:] < pos[:-1])])
    marks = xp.where(newrun, tok, 0)
    run_start = jax.lax.associative_scan(jnp.maximum, marks) if xp is jnp else np.maximum.accumulate(marks)
    within = tok - run_start                      # offset inside this token's run
    tile_id = xp.cumsum((within % q_tile == 0).astype(xp.int32)) - 1
    return tile_id, within % q_tile


def _tile_columns(tile_min, tile_max, tile_cnt, block_size: int, max_blocks: int, window, q_tile: int, xp=jnp):
    """``(lo, n, cols)``: the first table column a tile's rows can see, how
    many LIVE columns it has from there (0 for an empty tile, and for a tile
    whose tokens all sit at a NEGATIVE position: they see no key, which is how
    a caller takes a row out of a call without reshaping the batch), and the
    most the shapes allow a tile. The last live column is that of the tile's
    largest position; under a sliding window the first is that of its
    smallest position's window edge, and since a run's positions ascend by
    at most one (the layout contract) a tile spans at most ``(window +
    q_tile - 2) // block_size + 2`` columns wherever in the context it lies
    (the clip keeps a traced batch that breaks the contract inside the
    arrays the bound sizes)."""
    hi = xp.clip(tile_max // block_size, 0, max_blocks - 1)
    if window is None:
        lo, cols = xp.zeros_like(hi), max_blocks
    else:
        cols = min(max_blocks, (window + q_tile - 2) // block_size + 2)
        lo = xp.clip(xp.maximum(tile_min - (window - 1), 0) // block_size, hi - cols + 1, hi)
    return lo, xp.where((tile_cnt > 0) & (tile_max >= 0), hi - lo + 1, 0), cols


# a dead slot's column inside the tiled kernel: past every position (times a block's tokens it stays an int32)
_NO_COLUMN = 1 << 20


def _tiled_work_list(block_tables, seq_idx, pos, block_size: int, window, q_tile: int, selection=None,
                     per_step: int = 1):
    """The tiles of a ragged batch and the LIVE (tile, KV block) pairs of
    their grid, ``per_step`` of a tile's pairs an item, as the int32 arrays
    ``paged_attn_q_tiled`` prefetches.

    Tiles (:func:`_tile_runs`): ``n_tiles`` is the static bound ``ceil(T /
    q_tile) + S + 1`` (interior splits, one ragged tail tile a sequence run,
    the trailing pad run); a tile the batch does not fill is EMPTY. Returns
    ``(tile_id, slot, tile_tok, valid, tile_seq, tile_cnt, w_tile, w_col,
    total)``: token ``t`` sits in slot ``slot[t]`` of tile ``tile_id[t]``;
    ``tile_tok``/``valid`` ``[n_tiles, q_tile]`` are each slot's token and
    whether it holds one, ``tile_seq`` a tile's table row and ``tile_cnt``
    its valid slots (a prefix).

    The work list (:func:`_tile_columns`), one block an item: tile ``i`` with
    tokens sees table columns ``lo_i .. hi_i`` and has ``hi_i - lo_i + 1 >= 1``
    items, an empty tile none. Item ``k < total`` is tile ``w_tile[k]`` against
    table column ``w_col[k]``, whose pool block is
    ``block_tables[tile_seq[w_tile[k]], w_col[k]]``; items go tile after tile
    and ascend by column, so a tile's items are consecutive. No item lies past
    a tile's last position or wholly under its window. The arrays' length is
    one more than the most items the SHAPES allow, ``n_tiles x
    min(max_blocks, columns a window can span)`` (prefix-shared blocks count
    once per tile that reads them, so the pool's size bounds nothing);
    ``w_tile`` reads ``n_tiles`` from ``total`` on, so ``w_tile[k + 1] !=
    w_tile[k]`` marks the last item of every tile.

    Both arrays are what an item inherits from its tile, spread over the
    tile's items by a scatter of ``n_tiles`` steps and a running sum: a
    gather an item (the first form of this list: four of 6,306 elements)
    cost a call of 64 rows 0.24 ms of XLA time beside a kernel of 1.6 ms (my
    chip run, PR 34).

    Under a ``selection`` ``[T, nkv, max_blocks]`` (``paged_attention``) a
    tile's pairs are the columns, among those above, that ANY of its tokens
    selects for ANY kv head, still consecutive and ascending, found by one
    compaction of the ``[n_tiles, max_blocks]`` table of such pairs (so a tile
    reads the union of its tokens' blocks, and a row whose tokens select every
    visible block has the items it had). A tenth result is then the mask the
    kernel takes with each pair, ``[n_tiles, max_blocks, _mask_heads(nkv),
    q_tile]`` bfloat16: 1 where slot ``t`` of the tile selects the column for
    kv head ``n``; an eleventh the (token, column) pairs the list serves, each
    live pair counted for its tile's tokens at or after the column (blocks,
    whatever the items hold), and a twelfth the live pairs themselves.

    ``per_step`` ``B > 1`` (:func:`_tiled_blocks_per_step`): an item is a tile
    against ``B`` of its live columns, taken in ascending order from the
    tile's first on: consecutive columns without a selection, the selected
    ones (which need not be neighbours) under one, so a tile of ``n`` pairs
    has ``ceil(n / B)`` items, ``total`` their sum and the arrays' bound
    ``n_tiles x ceil(columns / B)``. ``w_col`` is then ``[B * bound]``, slot
    ``b`` of item ``k`` at ``b * bound + k``; only a tile's last item can have
    dead slots, and a dead slot reads ``~c`` (negative), ``c`` the column that
    slot held in the item before: the pipeline finds the block it has and
    fetches nothing, and the kernel gives the slot :data:`_NO_COLUMN`, which
    no position reaches. (A tile's ONLY item looks its ``c`` up in its own
    table row: some block of its own sequence, fetched and masked.) The pairs
    and their order are those of one block an item, so an all-true selection
    lays what no selection lays."""
    T = pos.shape[0]
    S, max_blocks = block_tables.shape
    qt, B = int(q_tile), int(per_step)
    n_tiles = -(-T // qt) + S + 1
    tile_id, slot = _tile_runs(seq_idx, pos, qt)
    tile_tok = jnp.zeros((n_tiles, qt), jnp.int32).at[tile_id, slot].set(jnp.arange(T, dtype=jnp.int32))
    valid = jnp.zeros((n_tiles, qt), bool).at[tile_id, slot].set(True)
    tile_pos = pos[tile_tok]
    tile_seq = jnp.where(valid[:, 0], seq_idx[tile_tok[:, 0]], 0)
    tile_cnt = jnp.sum(valid, axis=1, dtype=jnp.int32)
    lo, n, cols = _tile_columns(jnp.min(jnp.where(valid, tile_pos, jnp.int32(2**30)), axis=1),
                                jnp.max(jnp.where(valid, tile_pos, -1), axis=1), tile_cnt,
                                block_size, max_blocks, window, qt)
    tiles = (tile_id, slot, tile_tok, valid, tile_seq, tile_cnt)
    bound = n_tiles * -(-cols // B)
    col = jnp.arange(max_blocks, dtype=jnp.int32)[None, :]
    selected = ()
    if selection is not None or B > 1:  # the table of live (tile, column) pairs
        live = (col >= lo[:, None]) & (col < (lo + n)[:, None])
    if selection is not None:
        nkv = selection.shape[1]
        picked = selection[tile_tok] & valid[:, :, None, None]                       # [n_tiles, qt, nkv, max_blocks]
        live = live & jnp.any(picked, axis=(1, 2))
        mask = jnp.pad(jnp.transpose(picked, (0, 3, 2, 1)).astype(jnp.bfloat16),
                       ((0, 0), (0, 0), (0, _mask_heads(nkv) - nkv), (0, 0)))
        served = jnp.sum(valid[:, :, None] & ((tile_pos // block_size)[:, :, None] >= col[None]), axis=1, dtype=jnp.int32)
        selected = (mask, jnp.sum(jnp.where(live, served, 0), dtype=jnp.int32), jnp.sum(live, dtype=jnp.int32))
    if B > 1:
        # a live pair's place: slot ``rank % B`` of its tile's item ``rank // B``, as ``_decode_work_list`` lays a row's
        rank = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
        n = (rank[:, -1] + B) // B
        start = jnp.cumsum(n) - n
        total = start[-1] + n[-1]
        place = jnp.where(live, (rank % B) * bound + start[:, None] + rank // B, B * bound)
        w_col = jnp.full((B * bound, ), -1, jnp.int32).at[place.reshape(-1)].set(
            jnp.broadcast_to(col, live.shape).reshape(-1), mode="drop")
        k = jnp.arange(B * bound, dtype=jnp.int32)
        dead = w_col < 0
        w_col = jnp.where(dead, ~w_col[jax.lax.cummax(jnp.where(dead, 0, k))], w_col)
        # an item's tile steps at each tile's first item (an empty tile's step falls on the next tile's)
        steps = jnp.diff(jnp.arange(n_tiles, dtype=jnp.int32), prepend=0)
        w_tile = jnp.cumsum(jnp.zeros((bound + 1, ), jnp.int32).at[start].add(steps))
        return (*tiles, jnp.where(k[:bound + 1] < total, w_tile, n_tiles), w_col, total.astype(jnp.int32), *selected)
    if selection is not None:  # one pair an item: the compaction of the table is the list, its pairs the items
        item = jnp.nonzero(live.reshape(-1), size=bound + 1, fill_value=n_tiles * max_blocks)[0].astype(jnp.int32)
        return (*tiles, item // max_blocks, jnp.minimum(item % max_blocks, max_blocks - 1), selected[2], *selected)
    start = jnp.cumsum(n) - n                     # the items before a tile's own
    total = start[-1] + n[-1]
    # a tile's number, and its first column less its first item: each steps at the tile's first item
    # (an empty tile's step falls on the next tile's, and the sum passes over it)
    of_tile = jnp.stack([jnp.arange(n_tiles, dtype=jnp.int32), lo - start])
    steps = jnp.diff(of_tile, axis=1, prepend=0)
    spread = jnp.cumsum(jnp.zeros((2, bound + 1), jnp.int32).at[:, start].add(steps), axis=1)
    k = jnp.arange(bound + 1, dtype=jnp.int32)
    return (*tiles, jnp.where(k < total, spread[0], n_tiles), jnp.clip(k + spread[1], 0, max_blocks - 1),
            total.astype(jnp.int32))


def _paged_q_tiled(pl, pltpu, q, k3, v3, block_tables, seq_idx, pos, ks2, vs2,
                   block_size: int, q_tile: int, window, alibi, interpret: bool, value_dim=None,
                   softmax_scale=None, selection=None, blocks_per_step=None):
    """Q-tiled kernel: grid steps for the LIVE (tile, KV block) pairs only,
    as many pairs a step as fill a 128-lane tile.

    Each tile packs up to ``q_tile`` CONTIGUOUS same-sequence tokens, so
    every KV block streams from HBM (and is read out by kv head) once per
    *tile* instead of once per token — a 2,048-token prefill chunk at
    q_tile=128 reads each of its KV blocks 16x instead of 2,048x, and each kv
    head's dot feeds the MXU ``g * q_tile`` query rows.

    Tile assembly happens in jnp-land (traced, static shapes): a segmented
    tiling over the ragged batch — tiles never span a sequence boundary, so
    one block-table row serves a whole tile — and beside it the work list of
    :func:`_tiled_work_list`, scalar-prefetched. The grid is not ``tiles x
    table columns`` but that list: step ``i`` is tile ``w_tile[i]`` against
    table column ``w_col[i]``, the grid's length is the number of items (a
    DYNAMIC bound: no step runs for a column past a tile's last position,
    under its window, or of a tile no token fills; the static bound is only
    the arrays' length), and the pipeline fetches the next item's block while
    this one is computed, across tiles as within them. A tile's items are
    consecutive and ascend by column, so each tile is ONE online-softmax
    chain, begun where ``w_tile`` changes and normalised at its last item. A
    batch of many short rows under a table as wide as the longest context
    the engine admits (64 rows of 4 tokens at contexts of 2-17 blocks under
    65 columns) costs its own blocks and nothing else. Ragged tile tails ride
    the per-row position masking (invalid slots get pos = -1, masking every
    context position). An EMPTY tile has no item, so its output tile is never
    written: nothing reads it, because the scatter back to token order takes
    only slots that tokens fill (the pad run's included: it is a real tile
    with one live column).

    The work list is two int32 arrays of ``n_tiles x columns + 1`` entries in
    scalar memory beside the block table, which the K/V index maps read as the
    rectangle's did (97 tiles x 65 columns and 64 table rows: 50 + 34 KB);
    a shape whose list would pass ``_TILED_SMEM_BYTES`` raises here, at trace
    time.

    Blocks narrower than a 128-lane tile (:func:`_tiled_blocks_per_step`: two
    of 64 tokens, four of 32 or fewer, over token-major pools that are not
    int8): an item is a tile against ``B`` of its live columns (the work
    list's ``per_step``), the pools are given ``B`` times with a K and a V
    in_spec a slot, and each slot's heads are read out into rows ``b *
    block_size ..`` of the scratch by kv head, so a kv head's keys are ONE
    ``[B * block_size, d]`` matrix: one ``q k^T`` of ``B * block_size``
    columns, one position mask whose key positions take each slot's own
    column (a dead slot's is past every position), one online-softmax update
    (one max, one ``exp``, one sum, one rescale of ``m``/``l``/``acc``) and
    one ``p v``. At 64-token blocks every register of the scores is whole; one
    block a step read half of each and paid the rescale every 64 keys
    (PERF.md section 6, PR 48). At ``B == 1`` the list, the in_specs and the
    body are what they were.

    The pool arrives as the decode kernel takes it, ``k3``/``v3`` ``[blocks,
    block_size * nkv, d]``, the pool's own bytes (row ``t * nkv + n`` is head
    ``n`` of token ``t``). Inside a grid step the block's heads are read out
    of it once (:func:`_block_heads`: strided 32-bit word loads that deliver
    two bf16 or four int8 heads each, by the pool's static dtype and head
    count, :func:`_head_load_path`) into a small scratch by kv head, and the
    heads are walked one at a time, so what lives at once is one head's
    ``[g * q_tile, block]`` scores beside the resident q / output tile and
    the ``acc``/``m``/``l`` scratch (sized by :func:`_q_tiled_vmem_bytes`).
    Rows of a kv head are token-major (``row = t * g + h``): a tile's valid
    rows are a prefix, and a tile with at most ``_SHORT_TILE_TOKENS`` valid
    tokens runs only that prefix through the MXU. Both dots take the operands
    in the precision they arrive in (bf16 q and pool: bf16 operands, float32
    accumulation; the scores, the masking and the softmax state are float32);
    int8 KV dequantises at the tile in float32, head by head, with the head's
    row of scales. ``m``, ``l`` and the positions are kept replicated across
    the 128 lanes, so that with 128-token blocks no step broadcasts a column.
    Alibi and the sliding window mask as in the decode kernel.

    A LATENT pool (``v3`` None; ``paged_attention``): one entry a token,
    ``k3`` ``[blocks, block_size, d]``, which all ``nq`` query heads attend, so
    a tile is ONE group of ``nq * q_tile`` token-major rows. The block is
    fetched once a grid step and read in place: the scores take all ``d``
    lanes of it, the value product its first ``value_dim``, and the output is
    ``value_dim`` wide. Nothing is copied by head; the head loop walks the
    tile's rows in chunks of :func:`_latent_row_chunk` instead (each row has
    its own position, so a chunk need not hold whole tokens).

    Pools BY HEAD (``paged_attention``), ``k3``/``v3`` ``[nkv, blocks,
    block_size, d]``: a grid step fetches the table column's block of every
    head, ``[nkv, 1, block_size, d]``, and the head loop reads a head's where
    it lies: there is no scratch by kv head and nothing is copied.

    Under a ``selection`` the work list holds the union of the tile's selected
    columns and each of an item's slots brings its mask ``[_mask_heads(nkv),
    q_tile]`` (a kv head a sublane, a slot of the tile a lane: 4 KB beside the
    block's K and V). A row's bit is wanted down the sublanes and across its
    slot's lanes: a product with the identity turns each slot's mask
    ``[q_tile, heads]``, a head's column of each slot is spread over that
    slot's lanes, 16 registers ``[q_tile, keys]`` a kv head, and one product
    with a constant 0/1 matrix ``[G, q_tile]`` (row ``t * g + h`` picks slot
    ``t``) repeats them down each token's ``g`` rows to join the position
    mask: the MXU has the room, and a lane broadcast a row and a slot (each
    slot's bits as columns ``[rows, heads]``, this body's first form) took 22
    of a 35 ms call where this takes 3 of 16 (PERF.md section 6, PR 48). The
    second result is then
    :func:`_read_counts`: the list's served pairs, its live pairs and the
    grid's steps.
    """
    T, nq, d = q.shape
    by_head = k3.ndim == 4
    nkv = k3.shape[0] if by_head else k3.shape[1] // block_size
    g = nq // nkv
    qt = int(q_tile)
    quant = ks2 is not None
    latent = v3 is None
    token_major = not (latent or by_head or quant)
    B = int(blocks_per_step) if blocks_per_step and token_major else _tiled_blocks_per_step(block_size, token_major)
    W = B * block_size             # keys of a grid step
    dv = int(value_dim) if latent else d   # width of a value, of acc and of the output
    scale = softmax_scale or 1.0 / math.sqrt(d)
    G = g * qt                     # rows of one kv head in a tile
    R = nkv * G                    # == nq * qt
    short = min(G, g * _SHORT_TILE_TOKENS)
    # operands of the two dots: what q and the pool hold, unless the pool is
    # quantised (its dequantised values are float32)
    cdt = jnp.float32 if quant else jnp.promote_types(q.dtype, k3.dtype)

    # --- segmented tiles and their live KV blocks (contiguity contract: see paged_attention) ---
    selected = selection is not None
    tile_id, slot, tile_tok, valid, tile_seq, tile_cnt, w_tile, w_col, total, *sel_mask = _tiled_work_list(
        block_tables, seq_idx, pos, block_size, window, qt, selection, B)
    n_tiles = tile_cnt.shape[0]
    bound = w_tile.shape[0] - 1    # the most items the shapes allow
    cols = bound // n_tiles * B
    smem = _tiled_smem_bytes(n_tiles, cols, *block_tables.shape, B)
    if smem > _TILED_SMEM_BYTES:
        raise ValueError(f"paged_attn_q_tiled: the work list of {n_tiles} tiles x {cols} table columns takes {smem} "
                         f"bytes of scalar memory, over {_TILED_SMEM_BYTES}")
    pos_t = jnp.where(valid, pos[tile_tok], -1)                      # [n_tiles, qt]

    # kv-head-major, then token-major row layout [n_tiles, R, d], row
    # r = n*G + t*g + h: each kv head's G query rows are contiguous and its
    # valid rows come first. The rows (and one kv head's positions, spread
    # over the lanes) are laid out HERE, by XLA, so every block's last two
    # dims are (8, 128)-aligned or whole and the kernel never reshapes
    # across the sublane/lane boundary.
    q_t = q[tile_tok.reshape(-1)].reshape(n_tiles, qt, nkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(n_tiles, R, d)
    pos_rows = jnp.broadcast_to(jnp.repeat(pos_t, g, axis=1)[:, :, None], (n_tiles, G, _LANES))

    def q_map(i, tile_ref, col_ref, seq_ref, cnt_ref, bt_ref):
        return (tile_ref[i], 0, 0)

    def column(b, i, col_ref):
        """The table column of slot ``b`` of item ``i``, and whether the slot is dead (which names, as ``~c``,
        the column whose block the pipeline already holds for it)."""
        if B == 1:
            return col_ref[i], False
        c = col_ref[b * bound + i]
        return jnp.where(c < 0, ~c, c), c < 0

    def kv_map(b):
        return lambda i, tile_ref, col_ref, seq_ref, cnt_ref, bt_ref: (
            bt_ref[seq_ref[tile_ref[i]], column(b, i, col_ref)[0]], 0, 0)

    def scale_map(i, *refs):
        return (0, kv_map(0)(i, *refs)[0])

    nt_dims = (((1, ), (1, )), ((), ()))  # [rows, d] x [block, d] -> [rows, block]

    def kernel(tile_ref, col_ref, seq_ref, cnt_ref, bt_ref, q_ref, *rest):
        k_refs, rest = rest[:B], rest[B:]
        if not latent:
            v_refs, rest = rest[:B], rest[B:]
        pos_ref, *rest = rest
        if quant:
            ks_ref, vs_ref, *rest = rest
        if selected:
            sel_refs, spread_ref, rest = rest[:B], rest[B], rest[B + 1:]
        o_ref, acc_ref, m_ref, l_ref, *by_kv_head = rest
        k_ref = k_refs[0]
        i = pl.program_id(0)
        tile = tile_ref[i]
        jb = col_ref[i]  # the table column this step covers (of several, its first slot's)

        @pl.when(jnp.logical_or(i == 0, tile_ref[jnp.maximum(i - 1, 0)] != tile))
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, -1e30)
            l_ref[:] = jnp.zeros_like(l_ref)

        def key_positions(rows):
            """The positions of this step's keys: ``[rows, block]`` of the one column's, or one row ``[1, W]``,
            each slot's lanes from its own column on."""
            if B == 1:
                return jb * block_size + jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 1)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
            kpos = None
            for b in reversed(range(B)):
                c, dead = column(b, i, col_ref)
                first = (jnp.where(dead, _NO_COLUMN, c) - b) * block_size
                kpos = lane + first if kpos is None else jnp.where(lane < (b + 1) * block_size, lane + first, kpos)
            return kpos

        def _update(r, s, values):
            """Rows ``r`` of the tile's online softmax take the masked scores
            ``s`` of this step's keys and their ``values`` ``[keys, dv]``."""
            m_prev = m_ref[r, :]               # [rows, 128], lanes equal
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, W))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r, :] = l_ref[r, :] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[r, :] = acc_ref[r, :] * _lane_copies(alpha, dv) + jax.lax.dot(
                p.astype(cdt), values, preferred_element_type=jnp.float32)
            m_ref[r, :] = m_new

        def _latent(rows):
            """The block's entries against the tile's first ``rows`` rows, a
            chunk of rows at a time; the block is read where it lies."""
            step = _latent_row_chunk(rows)

            def chunk(n):
                r0 = n * step
                if not isinstance(r0, int):
                    r0 = pl.multiple_of(r0, 8)
                r = pl.ds(r0, step)
                my_pos = _lanes(pos_ref[0, r, :], block_size)   # -1 on invalid slots
                kpos = key_positions(step)
                vis = kpos <= my_pos
                if window is not None:
                    vis = jnp.logical_and(vis, my_pos - kpos < window)
                s = jax.lax.dot_general(q_ref[0, r, :].astype(cdt), k_ref[0].astype(cdt), nt_dims,
                                        preferred_element_type=jnp.float32) * scale
                _update(r, jnp.where(vis, s, -1e30), k_ref[0, :, :dv].astype(cdt))

            if rows == step:
                chunk(0)
            else:
                jax.lax.fori_loop(0, rows // step, lambda n, c: (chunk(n), c)[1], 0)

        def _compute(rows):
            """This step's KV blocks against the first ``rows`` rows of every kv head."""
            if latent:
                return _latent(rows)
            my_pos = _lanes(pos_ref[0, :rows, :], W)   # -1 on invalid slots
            kpos = key_positions(rows)
            vis = kpos <= my_pos
            if window is not None:
                vis = jnp.logical_and(vis, my_pos - kpos < window)
            if alibi is not None:
                rel = (kpos - my_pos).astype(jnp.float32)
            if selected:  # each slot's mask with a token a sublane, [q_tile, heads]: a transpose through the MXU
                eye = (jax.lax.broadcasted_iota(jnp.int32, (qt, qt), 0)
                       == jax.lax.broadcasted_iota(jnp.int32, (qt, qt), 1)).astype(jnp.bfloat16)
                by_token = [jax.lax.dot_general(eye, ref[0, 0], nt_dims, preferred_element_type=jnp.float32)
                            for ref in sel_refs]
                lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
            if quant:  # dequant at the VMEM tile — HBM only streamed int8
                ks_t, vs_t = ks_ref[...].T, vs_ref[...].T   # [bs, nkv]
            if by_head:  # the fetched block is by head already
                k_of, v_of = (lambda n: k_ref[n, 0].astype(cdt)), (lambda n: v_refs[0][n, 0].astype(cdt))
            else:
                kh_ref, vh_ref = by_kv_head
                for b in range(B):  # the step's blocks side by side, each block's heads read out once
                    at = slice(None) if B == 1 else pl.ds(b * block_size, block_size)
                    heads = zip(*(_block_heads(pl, pltpu, ref, nkv, block_size) for ref in (k_refs[b], v_refs[b])))
                    for n, (kh, vh) in enumerate(heads):  # for the head loop to index
                        if quant:
                            kh = kh.astype(jnp.float32) * ks_t[:, n:n + 1]
                            vh = vh.astype(jnp.float32) * vs_t[:, n:n + 1]
                        kh_ref[n, at] = kh.astype(cdt)
                        vh_ref[n, at] = vh.astype(cdt)
                k_of, v_of = (lambda n: kh_ref[n]), (lambda n: vh_ref[n])

            def chosen(n):
                """Whether each row's token selected, for kv head ``n``, the column of each key's slot: the
                bits by token ``[q_tile, keys]`` (a slot's column of ``by_token`` over the slot's lanes), which
                one product with the 0/1 matrix ``[rows, q_tile]`` repeats down each token's rows."""
                bits = jnp.broadcast_to(by_token[-1][:, n:n + 1], (qt, W))
                for b in reversed(range(B - 1)):
                    bits = jnp.where(lane < (b + 1) * block_size, by_token[b][:, n:n + 1], bits)
                return jax.lax.dot(spread_ref[:rows, :], bits.astype(jnp.bfloat16),
                                   preferred_element_type=jnp.float32) > 0.5

            def head(n):
                """One kv head's G rows: its working set is all that lives."""
                r0 = n * G
                if not isinstance(n, int) and G % 8 == 0:
                    r0 = pl.multiple_of(r0, 8)
                r = pl.ds(r0, rows)
                s = jax.lax.dot_general(q_ref[0, r, :].astype(cdt), k_of(n), nt_dims,
                                        preferred_element_type=jnp.float32) * scale
                if alibi is not None:
                    s = s + _slopes_tok_major(alibi[n * g:(n + 1) * g], rows) * rel
                seen = jnp.logical_and(vis, chosen(n)) if selected else vis
                _update(r, jnp.where(seen, s, -1e30), v_of(n))

            if alibi is None and not selected:
                # traced once and unrolled by the lowering: the same straight
                # line of eight heads as a Python loop gives Mosaic, at an
                # eighth of the tracing (a rolled loop ran 1.6x slower; eight
                # traced copies cost every program 0.3-1.0 s of set-up)
                jax.lax.fori_loop(0, nkv, lambda n, c: (head(n), c)[1], 0, unroll=True)
            else:  # the slopes, and a selection's column, are constants of the head
                for n in range(nkv):
                    head(n)

        if short < G:
            is_short = cnt_ref[tile] <= _SHORT_TILE_TOKENS
            pl.when(is_short)(lambda: _compute(short))
            pl.when(jnp.logical_not(is_short))(lambda: _compute(G))
        else:
            _compute(G)

        @pl.when(tile_ref[i + 1] != tile)
        def _finalize():
            o_ref[0] = (acc_ref[:] / _lane_copies(jnp.maximum(l_ref[:], 1e-30), dv)).astype(o_ref.dtype)

    if by_head:
        kv_specs = [pl.BlockSpec((nkv, 1, block_size, d), lambda i, *refs: (0, kv_map(0)(i, *refs)[0], 0, 0))]
    else:
        kv_specs = [pl.BlockSpec((1, block_size * nkv, d), kv_map(b)) for b in range(B)]
    in_specs = [pl.BlockSpec((1, R, d), q_map)] + kv_specs * (1 if latent else 2) + [
        pl.BlockSpec((1, G, _LANES), q_map)]
    operands = [q_t] + [k3] * B + ([] if latent else [v3] * B) + [pos_rows]
    if quant:
        in_specs += [pl.BlockSpec((nkv, block_size), scale_map),
                     pl.BlockSpec((nkv, block_size), scale_map)]
        operands += [ks2, vs2]
    if selected:
        spread = (np.arange(G)[:, None] // g == np.arange(qt)[None, :])
        mask_map = lambda b: lambda i, tile_ref, col_ref, *refs: (tile_ref[i], column(b, i, col_ref)[0], 0, 0)
        in_specs += [pl.BlockSpec((1, 1, _mask_heads(nkv), qt), mask_map(b)) for b in range(B)] + [
            pl.BlockSpec((G, qt), lambda i, *refs: (0, 0))]
        operands += [sel_mask[0]] * B + [jnp.asarray(spread, jnp.bfloat16)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(total, ),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, R, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((R, dv), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
        ] + ([] if latent or by_head else [
            pltpu.VMEM((nkv, W, d), cdt),   # the step's K, V by kv head
            pltpu.VMEM((nkv, W, d), cdt),
        ]),
    )
    kwargs = {}
    if not interpret:
        # the default scoped limit (16 MiB on a v5e) is under the working set
        # of a 128-token tile of 32 heads; ask for what the step needs plus
        # half again for Mosaic's own temporaries
        need = _q_tiled_vmem_bytes(R, G, d, block_size, nkv, q.dtype.itemsize, k3.dtype.itemsize, B)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, min(need * 3 // 2, _Q_TILED_VMEM_LIMIT)))
    out_t = pl.pallas_call(kernel, grid_spec=grid_spec,
                           out_shape=jax.ShapeDtypeStruct((n_tiles, R, dv), q.dtype),
                           interpret=interpret, name="paged_attn_q_tiled", **kwargs)(
                               w_tile, w_col, tile_seq, tile_cnt, block_tables, *operands)
    # scatter tiles back to token order: only slots that tokens fill are read
    flat = out_t.reshape(n_tiles, nkv, qt, g, dv).transpose(0, 2, 1, 3, 4).reshape(n_tiles * qt, nq, dv)
    out = flat[tile_id * qt + slot]
    return (out, _read_counts(sel_mask[1], sel_mask[2], total)) if selected else out


# measured on a v5e (PERF.md section 6, PR 28): 512 KiB blocks (8 kv heads of
# 128, bf16) 0.84 us a block at one a step, 0.70 at two or four; 256 KiB
# blocks (4 kv heads) 0.60, 0.42 and 0.35 at one, two and four
_DECODE_STEP_BYTES = 1 << 20


def _decode_blocks_per_step(rows: int, d: int, itemsize: int, parts: int = 2) -> int:
    """KV blocks one grid step of the decode kernel takes (1, 2 or 4), from
    the bytes of one block's K and V (``rows = block_size * nkv`` rows of
    ``d``; ``parts`` 1: a latent block, fetched once): a grid step costs about
    a third of a microsecond whatever it fetches, so a step should stream at
    least ``_DECODE_STEP_BYTES``."""
    return max(1, min(4, _DECODE_STEP_BYTES // (parts * rows * d * itemsize)))


def decode_kv_counts(choice, pos, windows, block_size: int, max_blocks: int, bucket_rows: int):
    """``(kv_steps, kv_live)`` of a decode bucket's attention calls, on the
    host, from the rows' positions: ``kv_live`` the live (row, block) pairs
    (:func:`_decode_work_list`'s count, real rows only) and ``kv_steps`` the
    block slots the chosen kernel's grid runs for them, pad rows included:
    the decode kernel's items times the blocks an item takes, every table
    column of every bucket row for the gather (a shape the tiled grid took
    is :func:`tiled_kv_counts`'s, under names of its own).
    ``choice`` is the shape's :func:`kernel_choice`; ``pos`` the positions of
    the fed tokens, ``[..., rows]`` (one leading entry a step); ``windows``
    pairs of (window or None, layers that attend in it)."""
    pos = np.asarray(pos, np.int64)
    calls = pos.size // max(pos.shape[-1], 1)
    hi = np.minimum(pos // block_size, max_blocks - 1)
    per = choice["blocks_per_step"] if choice["kernel"] == "paged_attn_kv_split" else 0
    steps = live = 0
    for window, layers in windows:
        lo = 0 if window is None else np.minimum(np.maximum(pos - (window - 1), 0) // block_size, hi)
        n = hi - lo + 1
        live += layers * int(n.sum())
        if per:
            steps += layers * per * (int((-(-n // per)).sum()) + calls * (bucket_rows - pos.shape[-1]))
        else:
            steps += layers * calls * bucket_rows * max_blocks
    return steps, live


def tiled_kv_counts(q_tile: int, seq_idx, pos, windows, block_size: int, max_blocks: int, bucket_rows: int,
                    per_step: int = 1):
    """``(tile_kv_bound, tile_kv_live, tile_kv_steps)`` of one forward's
    ``paged_attn_q_tiled`` calls without a selection, on the host:
    ``tile_kv_live`` the live (tile, KV block) pairs of
    :func:`_tiled_work_list` (the same run, tile and column rules, applied in
    numpy), ``tile_kv_steps`` its items, the grid steps the kernel ran for
    them at ``per_step`` of a tile's pairs a step (the choice's
    ``blocks_per_step``), and ``tile_kv_bound`` the rectangle the shapes
    allow, ``n_tiles x min(max_blocks, columns a window can span)``, which
    the grid walked until PR 34. ``seq_idx``/``pos``: the batch's tokens as
    the kernel is given them, the pad run included (``pos`` what it MASKS by:
    a block-diffusion model's ``pos | (B - 1)``); ``windows`` as for
    :func:`decode_kv_counts`, over whose layers the counts are summed."""
    seq_idx, pos = np.asarray(seq_idx, np.int32), np.asarray(pos, np.int32)
    qt = int(q_tile)
    n_tiles = -(-pos.size // qt) + bucket_rows + 1
    tile_id, _ = _tile_runs(seq_idx, pos, qt, xp=np)
    tile_min = np.full(n_tiles, 2**30, np.int32)
    tile_max = np.full(n_tiles, -1, np.int32)
    np.minimum.at(tile_min, tile_id, pos)
    np.maximum.at(tile_max, tile_id, pos)
    tile_cnt = np.bincount(tile_id, minlength=n_tiles)
    bound = live = steps = 0
    for window, layers in windows:
        _, n, cols = _tile_columns(tile_min, tile_max, tile_cnt, block_size, max_blocks, window, qt, xp=np)
        live += layers * int(n.sum())
        steps += layers * int((-(-n // per_step)).sum())
        bound += layers * n_tiles * cols
    return bound, live, steps


def _decode_work_list(block_tables, seq_idx, pos, block_size: int, window, per_step: int = 1, selection=None):
    """The live (row, block) pairs of a decode batch, row after row and
    ``per_step`` consecutive table columns an item, as the int32 arrays the
    decode kernel prefetches, and how many items there are.

    Row ``t`` at position ``p`` sees table columns ``lo..hi`` with ``hi = p //
    block_size`` and ``lo`` the column of ``p - window + 1`` (0 without a
    window), so it has ``hi - lo + 1 >= 1`` live pairs, in
    ``ceil((hi - lo + 1) / per_step)`` items: no item lies past a row's
    position or wholly under its window. Returns ``(w_row, w_col, w_blk,
    total)``: item ``i < total`` is row ``w_row[i]`` against table columns
    ``w_col[i] + b``, pool blocks ``w_blk[b * bound + i]``, for ``b <
    per_step``; a row's items are consecutive and ascend by column. A slot
    past the row's last column (only a row's last item has any) names the
    block that slot held in the item before, so nothing is fetched for it,
    and the kernel's position mask hides it. ``bound``, the arrays' length,
    is the most items the SHAPES allow, ``T x ceil(min(max_blocks, blocks a
    window can span) / per_step)``: prefix-shared blocks count once per row
    that reads them, so the pool's size bounds nothing. ``w_row`` has one
    more entry and reads ``T`` from ``total`` on, so ``w_row[i + 1] !=
    w_row[i]`` marks the last item of every row.

    Under a ``selection`` ``[T, nkv, max_blocks]`` (``paged_attention``) a
    row's pairs are the columns up to ``hi`` that ANY kv head selects, in
    ascending order, ``per_step`` of them an item, which need not be
    neighbours: ``w_col`` is then ``[per_step * bound]``, slot ``b`` of item
    ``i`` at ``b * bound + i`` as in ``w_blk`` (a dead slot names a column no
    position reaches, so the position mask hides it), a fifth result
    ``w_heads`` ``[per_step * bound]`` holds, bit ``n``, whether kv head ``n``
    selected the slot's column, and a sixth counts the live pairs."""
    T = pos.shape[0]
    max_blocks = block_tables.shape[1]
    hi = jnp.clip(pos // block_size, 0, max_blocks - 1)
    if selection is not None:
        nkv = selection.shape[1]
        col = jnp.arange(max_blocks, dtype=jnp.int32)[None, :]
        live = jnp.any(selection, axis=1) & (col <= hi[:, None]) & (pos >= 0)[:, None]      # [T, max_blocks]
        rank = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
        n = (rank[:, -1] + per_step) // per_step
        bound = T * (-(-max_blocks // per_step))
        total = jnp.sum(n)
        start = jnp.cumsum(n) - n
        i = jnp.arange(bound + 1, dtype=jnp.int32)
        w_row = jnp.repeat(jnp.arange(T, dtype=jnp.int32), n, total_repeat_length=bound + 1)
        # a live pair's place: slot ``rank % per_step`` of item ``start + rank // per_step``
        place = jnp.where(live, (rank % per_step) * bound + start[:, None] + rank // per_step, per_step * bound)
        heads = jnp.sum(selection.astype(jnp.int32) << jnp.arange(nkv, dtype=jnp.int32)[None, :, None], axis=1)
        no_col = 2**20  # a dead slot's column: past every position
        w_col = jnp.full((per_step * bound, ), no_col, jnp.int32).at[place.reshape(-1)].set(
            jnp.broadcast_to(col, live.shape).reshape(-1), mode="drop")
        w_heads = jnp.zeros((per_step * bound, ), jnp.int32).at[place.reshape(-1)].set(heads.reshape(-1), mode="drop")
        item_row = jnp.tile(w_row[:bound], per_step)
        dead = w_col == no_col
        blk = block_tables[seq_idx[item_row], jnp.where(dead, 0, w_col)]
        # a dead slot repeats the block the slot last held, so nothing is fetched for it
        k = jnp.arange(per_step * bound, dtype=jnp.int32)
        blk = blk[jax.lax.cummax(jnp.where(dead, 0, k))]
        return jnp.where(i < total, w_row, T), w_col, blk, total.astype(jnp.int32), w_heads, jnp.sum(live, dtype=jnp.int32)
    if window is None:
        lo = jnp.zeros_like(hi)
        cols = max_blocks
    else:
        lo = jnp.minimum(jnp.maximum(pos - (window - 1), 0) // block_size, hi)
        cols = min(max_blocks, (window + block_size - 2) // block_size + 1)
    n = (hi - lo + per_step) // per_step
    bound = T * (-(-cols // per_step))
    total = jnp.sum(n)
    start = jnp.cumsum(n) - n
    i = jnp.arange(bound + 1, dtype=jnp.int32)
    w_row = jnp.repeat(jnp.arange(T, dtype=jnp.int32), n, total_repeat_length=bound + 1)
    w_col = lo[w_row] + (i - start[w_row]) * per_step
    blks = []
    for b in range(per_step):
        live = w_col + b <= hi[w_row]
        blk = block_tables[seq_idx[w_row], jnp.minimum(w_col + b, hi[w_row])]
        if b:  # a dead slot repeats the block this slot last held
            blk = blk[jax.lax.cummax(jnp.where(live, i, 0))]
        blks.append(blk[:bound])
    return (jnp.where(i < total, w_row, T), w_col[:bound], jnp.concatenate(blks),
            total.astype(jnp.int32))


def _paged_kv_split(pl, pltpu, q, k2, v2, block_tables, seq_idx, pos, ks2, vs2,
                    block_size: int, window, alibi, interpret: bool, value_dim=None, softmax_scale=None,
                    selection=None):
    """The decode kernel: grid steps for the LIVE (row, KV block) pairs only.

    A decode batch is one query token a row against contexts of very
    different length under one block table as wide as the longest context
    the engine admits. The grid is therefore not ``rows x table columns`` but
    the work list of :func:`_decode_work_list`, scalar-prefetched: step ``i``
    is row ``w_row[i]`` against ``B`` consecutive table columns from
    ``w_col[i]`` (:func:`_decode_blocks_per_step`: as many blocks as make a
    step's stream worth its fixed cost), the grid's length is the number of
    items (a DYNAMIC bound: no step runs for a pair that is not live, and the
    static bound is only the arrays' length), and the pipeline fetches the
    next item's blocks while this one is computed, across rows as within
    them. A batch whose contexts fit two columns runs a step a row, a row
    past its sliding window starts at the window's first block, and one long
    row among short ones costs its own blocks and nothing else. Each row is
    ONE online-softmax chain, normalised at its last step: nothing is merged
    afterwards (the kernel keeps the name its time is read by).

    The pool ``[pool_len, nkv, d]`` is read as ``[blocks, block * nkv, d]``:
    the same bytes on the chip (a token's ``nkv`` heads are the rows of one
    tile either way, so XLA makes no copy), and a block is then ONE matrix
    whose row ``t * nkv + n`` is token ``t``'s key for kv head ``n``. A step
    multiplies all ``nq`` query rows with all of it, masks the columns of the
    other kv heads like keys out of sight (one compare against a constant
    table of each column's token, or of no token for another head's), and
    multiplies the probabilities ``[nq, block * nkv]`` with the value block
    the same way: the zeros of the other heads' columns drop out of the sum,
    so the output needs no cut by head either. Nothing is gathered by
    sublane, sliced under a tile or transposed; the MXU sees two dots a block
    whatever ``nkv`` and ``g`` are.
    Operands go to the MXU as they are stored (bf16 q and pool: bf16
    operands, float32 accumulation); scores, mask, ``m``, ``l`` and ``acc``
    are float32, ``m``/``l`` replicated across the lanes. int8 KV is cast
    exactly and its per-token scales (``ks2``/``vs2``: ``[blocks, 1, block *
    nkv]``, laid out by the caller) multiply the scores and the probabilities
    in float32.

    A LATENT pool (``v2`` None; ``paged_attention``): a block is the one
    matrix ``[block_size, d]`` of its tokens' entries, fetched once a step; the
    scores take all of it, the value product its first ``value_dim`` lanes,
    and ``acc`` and the output are ``value_dim`` wide. The query heads are
    padded to whole 16-row tiles of the MXU's left operand.

    Under a ``selection`` an item's slots are the row's selected columns
    (each slot its own column, :func:`_decode_work_list`), fetched for every kv
    head that any head chose them for; a sixth prefetched array says which kv
    heads did, and a query head sees a slot's keys only if its kv head's bit is
    set."""
    T, nq, d = q.shape
    latent = v2 is None
    if latent and nq % 16:
        out = _paged_kv_split(pl, pltpu, jnp.pad(q, ((0, 0), (0, -nq % 16), (0, 0))), k2, v2, block_tables,
                              seq_idx, pos, ks2, vs2, block_size, window, alibi, interpret, value_dim,
                              softmax_scale)
        return out[:, :nq]
    selected = selection is not None
    M = k2.shape[1]                # rows of a block: block_size * nkv
    nkv = M // block_size
    g = nq // nkv
    quant = ks2 is not None
    dv = int(value_dim) if latent else d   # width of a value, of acc and of the output
    scale = softmax_scale or 1.0 / math.sqrt(d)
    # operands of the two dots: what q and the pool hold, unless the pool is
    # quantised (int8 is exact in float32, and the scales are float32)
    cdt = jnp.float32 if quant else jnp.promote_types(q.dtype, k2.dtype)
    B = _decode_blocks_per_step(M, d, k2.dtype.itemsize, 1 if latent else 2)
    w_row, w_col, w_blk, total, *w_heads = _decode_work_list(block_tables, seq_idx, pos, block_size, window, B, selection)
    read = w_heads.pop() if selected else None
    bound = w_blk.shape[0] // B

    def q_map(i, row_ref, *refs):
        return (row_ref[i], 0, 0)

    def kv_map(b):
        return lambda i, row_ref, col_ref, blk_ref, *refs: (blk_ref[b * bound + i], 0, 0)

    nt_dims = (((1, ), (1, )), ((), ()))  # [nq, d] x [M, d] -> [nq, M]

    def kernel(row_ref, col_ref, blk_ref, pos_ref, *rest):
        if selected:
            heads_ref, *rest = rest
        q_ref, tok_ref, *rest = rest
        if latent:  # the value is the entry's first lanes: the same block, read where it lies
            k_refs, rest = rest[:B], rest[B:]
            value = lambda b: k_refs[b][0, :, :dv]
        else:
            k_refs, v_refs, rest = rest[:B], rest[B:2 * B], rest[2 * B:]
            value = lambda b: v_refs[b][0]
        if quant:
            ks_refs, vs_refs, rest = rest[:B], rest[B:2 * B], rest[2 * B:]
        o_ref, acc_ref, m_ref, l_ref = rest
        i = pl.program_id(0)
        row = row_ref[i]
        my_pos = pos_ref[row]

        @pl.when(jnp.logical_or(i == 0, row_ref[jnp.maximum(i - 1, 0)] != row))
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, -1e30)
            l_ref[:] = jnp.zeros_like(l_ref)

        qb = q_ref[0].astype(cdt)
        # tok_ref[h, c]: the token of column c inside its block where c is a
        # key of query head h's kv head, else a number no position reaches. A
        # key is seen at or before the row's position, inside the window; a
        # slot past the row's last column lies wholly after the position
        tok = tok_ref[...]
        scs = []
        for b in range(B):
            sc = jax.lax.dot_general(qb, k_refs[b][0].astype(cdt), nt_dims,
                                     preferred_element_type=jnp.float32)     # [nq, M]
            if quant:
                sc = sc * ks_refs[b][0]
            sc = sc * scale
            rel = my_pos - (col_ref[b * bound + i] if selected else col_ref[i] + b) * block_size
            vis = tok <= rel
            if selected:  # the slot's column, for the kv heads that chose it alone
                kv_head = jax.lax.broadcasted_iota(jnp.int32, (nq, M), 0) // g
                vis = jnp.logical_and(vis, ((heads_ref[b * bound + i] >> kv_head) & 1) == 1)
            if window is not None:
                vis = jnp.logical_and(vis, tok > rel - window)
            if alibi is not None:
                sc = sc + _slopes_rows(alibi, 1) * (tok - rel).astype(jnp.float32)
            scs.append(jnp.where(vis, sc, -1e30))
        m_prev = m_ref[:]                                                    # [nq, 128], lanes equal
        m_new = m_prev
        for sc in scs:
            m_new = jnp.maximum(m_new, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:] * alpha
        acc = acc_ref[:] * _lanes(alpha, dv)
        for b, sc in enumerate(scs):
            p = jnp.exp(sc - _lanes(m_new, M))
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * vs_refs[b][0]
            acc = acc + jax.lax.dot(p.astype(cdt), value(b).astype(cdt),
                                    preferred_element_type=jnp.float32)
        l_ref[:] = l_new
        m_ref[:] = m_new
        acc_ref[:] = acc

        @pl.when(row_ref[i + 1] != row)
        def _finalize():
            o_ref[0] = (acc_ref[:] / _lanes(jnp.maximum(l_ref[:], 1e-30), dv)).astype(o_ref.dtype)

    # the one table of the masks, from the shapes alone (a constant of the
    # program, fetched once: its block index never changes)
    col = np.arange(M)
    tok_of = np.where(col[None, :] % nkv == np.arange(nq)[:, None] // g, col[None, :] // nkv, 2**30)
    in_specs = [pl.BlockSpec((1, nq, d), q_map), pl.BlockSpec((nq, M), lambda i, *refs: (0, 0))]
    in_specs += [pl.BlockSpec((1, M, d), kv_map(b)) for b in range(B)] * (1 if latent else 2)
    operands = [q, jnp.asarray(tok_of, jnp.int32)] + [k2] * B + ([] if latent else [v2] * B)
    if quant:
        in_specs += [pl.BlockSpec((1, 1, M), kv_map(b)) for b in range(B)] * 2
        operands += [ks2] * B + [vs2] * B

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if selected else 4,
        grid=(total, ),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nq, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((nq, dv), jnp.float32),
            pltpu.VMEM((nq, _LANES), jnp.float32),
            pltpu.VMEM((nq, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(kernel, grid_spec=grid_spec,
                         out_shape=jax.ShapeDtypeStruct((T, nq, dv), q.dtype),
                         interpret=interpret, name="paged_attn_kv_split")(
                             w_row, w_col, w_blk, pos, *w_heads, *operands)
    return (out, _read_counts(read)) if selected else out
