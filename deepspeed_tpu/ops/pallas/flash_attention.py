"""Flash attention for TPU — forward AND backward Pallas kernels.

Replaces the reference's fused attention kernels (training:
``csrc/transformer/softmax_kernels.cu`` / ``general_kernels.cu``; inference
context: ``csrc/transformer/inference/csrc/softmax.cu``) with a Pallas blocked
flash-attention. The public entry ``flash_attention(q, k, v, causal=...)``
takes [B, S, n_heads, head_dim] (GQA allowed: n_kv may divide n_q) and is
numerically validated against ``models.transformer.reference_attention``
(mirroring the reference's tests/unit/ops kernel-vs-torch strategy) — in both
forward and ``jax.grad``.

Backward follows the flash-attention recurrences: the forward saves
``q, k, v``, its output and the per-row log-sum-exp ``lse = m + log(l)``
([B, nq, S] float32, not the kernel's 128-lane broadcast); the backward
recomputes ``p = exp(s - lse)`` blockwise, with the two-pass split:

  * dk/dv pass — grid over k-blocks, inner loop over q-blocks:
      dv += p^T dO;   ds = p * (dO v^T - delta);   dk += ds^T q * scale
  * dq pass — grid over q-blocks, inner loop over k-blocks:
      dq += ds k * scale
  where ``delta = rowsum(dO * O)``.

Under ``jax.checkpoint`` the forward rule gives ``out`` and ``lse`` the name
``attn_out``: a policy that saves that name (``save_only_these_names(attn_out)``,
what the training configurations state) keeps the pair across the remat
boundary, so the backward runs ``flash_bwd_dkdv`` and ``flash_bwd_dq`` from
them and recomputes only ``q, k, v`` (norm, projections, rope) — ``flash_fwd``
runs once a layer. Under any other policy the name is inert and the
rematerialised forward runs the kernel a second time; with no checkpoint at
all the residuals are simply kept.

Fallback policy: on non-TPU backends, or for shapes the kernel does not
support (S not a multiple of 128), we use the jnp reference implementation —
XLA fuses it reasonably. On TPU with supported shapes a kernel failure
RAISES (at trace/lowering here, or at the enclosing jit's compile), so
training can never silently drop to O(S^2) unfused attention.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_NEG_INF = -1e30


def _use_pallas():
    return jax.default_backend() == "tpu"


def _fit_block(S: int, want: int) -> int:
    """Largest lane-aligned (multiple-of-128) divisor of S that is <= want.

    Always returns a true divisor: ``_shapes_supported`` guarantees
    S % 128 == 0, so 128 qualifies as the floor — the kernel's
    ``S % block == 0`` precondition can never trip on the auto-fit path."""
    b = max(128, min(want, S) // 128 * 128)
    while b > 128 and S % b:
        b -= 128
    return b


# Generations where the 1024 tiling is validated (forward and backward, on a
# v5e: tests_tpu::test_flash_bwd_large_tiles_on_chip). Older / unknown
# generations keep the 512 default: a VMEM exhaustion surfaces at the
# enclosing jit's compile, as an error.
_LARGE_TILE_KINDS = ("v5 lite", "v5e", "v5p", "v6")


def _default_tile():
    kind = jax.devices()[0].device_kind.lower()
    return 1024 if any(t in kind for t in _LARGE_TILE_KINDS) else 512


def _resolve_tiles(block_q=None, block_k=None):
    """The caller's explicit tile, else the generation's ``_default_tile``.
    The VMEM fit and divisor snap downstream apply to both."""
    dflt = _default_tile()
    return int(dflt if block_q is None else block_q), int(dflt if block_k is None else block_k)


def _shapes_supported(q):
    B, S, nq, d = q.shape
    return S % 128 == 0 and d >= 32


_VMEM_BUDGET = 14 * 2**20  # conservative slice of the ~16MiB/core VMEM


def _fit_tiles_vmem(S: int, d: int, bq: int, bk: int):
    """Shrink (block_q, block_k) until the kernel's VMEM working set fits.

    All four kernels (fwd + the three bwd passes) stream K/V one tile per
    grid step, so residency is independent of S: the working set is the
    [bq, bk] score/prob temporaries, the [bq|bk, d] tiles and accumulators.
    A VMEM overflow inside an enclosing jit (or under jax.grad) is
    uncatchable at runtime, so the fit happens at trace time. Returns
    (bq, bk) — or None if even 128-tiles cannot fit (head_dim would have to
    be pathological for that).
    """
    while True:
        # approximate LARGEST working set across fwd and the bwd passes
        # (bwd holds p/dp/ds temporaries plus more d-sized tiles/accums —
        # the binding term for large head_dim). Calibrated against on-chip
        # evidence: (1024, 1024, d=128) passes (validated by
        # tests_tpu::test_flash_bwd_large_tiles); (1024, 1024, d=256) is
        # rejected to 512 tiles rather than risk an uncatchable grad-compile
        # OOM.
        tmp = 2 * bq * bk * 4 + (bq + bk) * d * 16 + bq * 128 * 4
        if tmp <= _VMEM_BUDGET:
            return bq, bk
        if bq <= 128 and bk <= 128:
            return None
        bq2 = _fit_block(S, max(128, bq // 2)) if bq >= bk else bq
        bk2 = _fit_block(S, max(128, bk // 2)) if bk >= bq else bk
        if (bq2, bk2) == (bq, bk):  # both already at their floor for this S
            return None
        bq, bk = bq2, bk2


def name_attn_out(out):
    """Give an attention output [B, S, n, d] the remat name ``attn_out``. A
    value is saved in the shape it was named in, and the scan stacks it in
    (8, 128) tiles: heads that fill the 128 lanes are named as they are (XLA
    then keeps the stack in the kernels' layout and the backward reads it in
    place), narrower ones in the [B, S, n * d] shape, or half of every tile
    is padding (heads of 64 at 410M: 402 MB where [.., 1024] takes 201)."""
    B, S, n, d = out.shape
    if d % 128 == 0:
        return checkpoint_name(out, "attn_out")
    return checkpoint_name(out.reshape(B, S, n * d), "attn_out").reshape(B, S, n, d)


def _reference_fallback(q, k, v, causal, window, alibi, reason=None):
    """The single O(S^2) jnp fallback path; ``reason`` warns once."""
    from ...models.transformer import alibi_slopes, reference_attention

    if reason is not None:
        from ...utils.logging import warning_once

        warning_once(f"flash attention: {reason} — using O(S^2) reference attention")
    out = reference_attention(q, k, v, causal=causal, window=window,
                              alibi=alibi_slopes(q.shape[2]) if alibi else None)
    return name_attn_out(out)  # as the kernel names its own output


def flash_attention(q, k, v, causal: bool = True, block_q: int = None, block_k: int = None,
                    window=None, alibi: bool = False):
    """q: [B, S, nq, d]; k/v: [B, S, nkv, d] with nq % nkv == 0.

    Differentiable: both forward and backward run as Pallas kernels on TPU.
    ``window``: sliding-window attention (Mistral reference
    ``inference/v2/model_implementations/mistral/``) — query i attends keys
    in (i - window, i]; requires ``causal=True``. ``alibi``: Bloom-style
    per-head linear bias ``slope_h * (k_pos - q_pos)`` with the standard
    power-of-two slopes (non-power-of-2 head counts use the reference path).
    """
    if window is not None:
        assert causal, "sliding window requires causal attention"
        window = int(window)
    if alibi and (q.shape[2] & (q.shape[2] - 1)) != 0:
        # the in-kernel closed-form slope only matches pow-2 head counts;
        # others use the interleaved table — LOUD jnp path
        return _reference_fallback(q, k, v, causal, window, alibi,
                                   f"alibi with non-power-of-2 head count {q.shape[2]}")
    block_q, block_k = _resolve_tiles(block_q, block_k)
    if _use_pallas() and not _shapes_supported(q):
        return _reference_fallback(q, k, v, causal, window, alibi,
                                   f"unsupported shape {q.shape} (S must be a multiple of 128, "
                                   "head_dim >= 32)")
    if _use_pallas():
        # block sizes snap to the largest lane-aligned divisor of S, so
        # non-multiple-of-1024 lengths (1536, 2560, ...) keep the kernel
        S, d = q.shape[1], q.shape[3]
        bq, bk = _fit_block(S, block_q), _fit_block(S, block_k)
        fitted = _fit_tiles_vmem(S, d, bq, bk)
        if fitted is None:
            return _reference_fallback(q, k, v, causal, window, alibi,
                                       f"no tiling fits VMEM for S={S}, d={d}")
        bq, bk = fitted
        return _pallas_flash(q, k, v, causal=causal, block_q=bq, block_k=bk,
                             window=window, alibi=alibi)
    return _reference_fallback(q, k, v, causal, window, alibi)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret", "window",
                                             "alibi"))
def _pallas_flash(q, k, v, causal=True, block_q=1024, block_k=1024, interpret=False, window=None,
                  alibi=False):
    return _flash_core(causal, min(block_q, q.shape[1]), min(block_k, q.shape[1]),
                       interpret, window, alibi, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _flash_core(causal, block_q, block_k, interpret, window, alibi, q, k, v):
    out, _ = _flash_fwd_impl(causal, block_q, block_k, interpret, window, alibi, q, k, v)
    return out


def _flash_core_fwd(causal, block_q, block_k, interpret, window, alibi, q, k, v):
    out, lse = _flash_fwd_impl(causal, block_q, block_k, interpret, window, alibi, q, k, v)
    # the kernel's two outputs ARE the attention output: under
    # save_only_these_names(attn_out) they cross the remat boundary and the
    # backward starts from them; under any other policy the name is inert
    out = name_attn_out(out)
    lse = checkpoint_name(lse, "attn_out")
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, block_q, block_k, interpret, window, alibi, res, dout):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd_impl(causal, block_q, block_k, interpret, window, alibi, q, k, v, out, lse,
                                 dout)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _alibi_slope(h, n_heads):
    """Closed-form power-of-2 ALiBi slope for head ``h`` (traced int32):
    2^(-8(h+1)/n) — matches models.transformer.alibi_slopes for pow-2 n."""
    return jnp.exp2(-8.0 * (h.astype(jnp.float32) + 1.0) / n_heads)


def _flash_fwd_impl(causal, block_q, block_k, interpret, window, alibi, q, k, v):
    """Returns (out [B,S,nq,d], lse [B,nq,S] float32).

    Streaming revisit-accumulate grid ``(B, nq, q_blocks, k_blocks)`` — the
    same Mosaic idiom as the backward passes below: K/V arrive one
    ``[block_k, d]`` tile per grid step, the online-softmax state lives in
    VMEM scratch across the innermost dimension, and the output flushes on
    the last k step. VMEM residency is therefore independent of S (the
    previous full-S K/V slabs capped S near 8k on a 16MiB core — the
    long-context path OOM'd inside the training jit where no retry can
    fire). Causal/window skipping: ``pl.when`` guards the compute and the
    K/V index map clamps out-of-range k blocks to the last visible one, so
    the pipeline re-uses the resident tile instead of streaming blocks the
    softmax never reads.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, nq, d = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    assert S % block_q == 0 and S % block_k == 0
    scale = 1.0 / math.sqrt(d)
    n_qblocks = S // block_q
    n_kblocks = S // block_k

    # layout: [B, n, S, d] for contiguous per-head slabs
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    # TPU requires the last two block dims to be (8k, 128k)-aligned; stats get
    # a broadcast 128-lane trailing dim (same layout as jax's own TPU flash
    # kernel), sliced back to [B, nq, S] for the saved residual.
    LANES = 128

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
        qi = pl.program_id(2)
        kj = pl.program_id(3)
        head = pl.program_id(1)

        @pl.when(kj == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

        def compute():
            qb = q_ref[0, 0].astype(jnp.float32) * scale  # [bq, d] (resident across kj)
            kb = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
            vb = v_ref[0, 0].astype(jnp.float32)
            s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32)  # [bq, bk]
            if causal or alibi:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
                k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                if alibi:
                    s = s + _alibi_slope(head, nq) * (k_pos - q_pos).astype(jnp.float32)
                if causal:
                    visible = q_pos >= k_pos
                    if window is not None:
                        visible = jnp.logical_and(visible, q_pos - k_pos < window)
                    s = jnp.where(visible, s, _NEG_INF)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jnp.dot(p, vb, preferred_element_type=jnp.float32)
            m_ref[:] = m_new

        if causal:
            in_range = (qi + 1) * block_q > kj * block_k
            if window is not None:
                in_range = jnp.logical_and(
                    in_range, qi * block_q - ((kj + 1) * block_k - 1) < window)
            pl.when(in_range)(compute)
        else:
            compute()

        @pl.when(kj == n_kblocks - 1)
        def _flush():
            l_safe = jnp.maximum(l_ref[:], 1e-30)
            o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
            lse_ref[0, 0] = jnp.broadcast_to(m_ref[:] + jnp.log(l_safe), (block_q, LANES))

    def q_index(b, h, i, j):
        return (b, h, i, 0)

    def kv_index(b, h, i, j):
        if not causal:
            return (b, h // group, j, 0)
        # clamp into the visible range: index maps issue their DMA even for
        # pl.when-skipped steps, so out-of-range columns re-use the resident
        # block (repeated index -> no refetch) instead of streaming dead data
        hi = ((i + 1) * block_q - 1) // block_k
        jj = jnp.minimum(j, hi)
        if window is not None:
            lo = jnp.maximum(i * block_q - (window - 1), 0) // block_k
            jj = jnp.maximum(jj, jnp.minimum(lo, hi))
        return (b, h // group, jj, 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, nq, n_qblocks, n_kblocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, block_q, LANES), q_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nq, S, d), q.dtype),
            jax.ShapeDtypeStruct((B, nq, S, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


def _flash_bwd_impl(causal, block_q, block_k, interpret, window, alibi, q, k, v, out, lse, dout):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, nq, d = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    scale = 1.0 / math.sqrt(d)
    n_qblocks = S // block_q
    n_kblocks = S // block_k

    LANES = 128

    qt = q.transpose(0, 2, 1, 3)          # [B, nq, S, d]
    kt = k.transpose(0, 2, 1, 3)          # [B, nkv, S, d]
    vt = v.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)        # [B, nq, S, d]
    dot = dout.transpose(0, 2, 1, 3)      # [B, nq, S, d]
    # lane-broadcast the saved [B, nq, S] stats back to the TPU-aligned layout
    lse_b = jnp.broadcast_to(lse[..., None], (B, nq, S, LANES))

    # Both passes use the canonical Mosaic revisit-accumulate idiom: the block
    # loop is the innermost *grid* dimension (TPU grids execute sequentially),
    # the output block spec ignores it, and a VMEM scratch accumulates across
    # revisits — initialized on the first visit, flushed on the last. Causal
    # skipping is done with pl.when on statically-shaped programs (dynamic
    # fori_loop trip counts inside the kernel miscompile on some Mosaic
    # versions — observed as NaNs in the final grid programs in bf16).

    def _shared_block_math(qb, ob, dob, lseb, kb, vb, qi, kj, head):
        """Recompute p and ds for one (q-block, k-block) tile."""
        deltab = jnp.sum(dob * ob, axis=-1, keepdims=True)               # [bq, 1]
        s = scale * jnp.dot(qb, kb.T, preferred_element_type=jnp.float32)  # [bq, bk]
        if causal or alibi:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            if alibi:
                s = s + _alibi_slope(head, nq) * (k_pos - q_pos).astype(jnp.float32)
            if causal:
                vis = q_pos >= k_pos
                if window is not None:
                    vis = jnp.logical_and(vis, q_pos - k_pos < window)
                s = jnp.where(vis, s, _NEG_INF)
        p = jnp.exp(s - lseb)                                            # [bq, bk]
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)      # [bq, bk]
        ds = p * (dp - deltab)
        return p, ds

    # ---- pass 1: dk/dv (per q-head; grouped-sum outside for GQA) ----
    # grid: q-blocks innermost; dk/dv blocks revisited across qi.
    def dkdv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc):
        kj = pl.program_id(2)
        qi = pl.program_id(3)
        head = pl.program_id(1)

        @pl.when(qi == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        # causal: q blocks strictly before this k block contribute nothing;
        # sliding window: q blocks entirely beyond kj's window contribute
        # nothing either
        visible = (qi + 1) * block_q > kj * block_k if causal else True
        if causal and window is not None:
            visible = jnp.logical_and(
                visible, qi * block_q - ((kj + 1) * block_k - 1) < window)

        @pl.when(visible)
        def _compute():
            kb = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
            vb = v_ref[0, 0].astype(jnp.float32)
            qb = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
            ob = o_ref[0, 0].astype(jnp.float32)
            dob = do_ref[0, 0].astype(jnp.float32)
            lseb = lse_ref[0, 0, :, :1]           # [bq, 1]
            p, ds = _shared_block_math(qb, ob, dob, lseb, kb, vb, qi, kj, head)
            dv_acc[:] += jnp.dot(p.T, dob, preferred_element_type=jnp.float32)
            dk_acc[:] += scale * jnp.dot(ds.T, qb, preferred_element_type=jnp.float32)

        @pl.when(qi == n_qblocks - 1)
        def _flush():
            dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)

    def kv_index4(b, h, j, i):
        return (b, h // group, j, 0)

    def q_index4(b, h, j, i):
        return (b, h, i, 0)

    dk_g, dv_g = pl.pallas_call(
        dkdv_kernel,
        grid=(B, nq, n_kblocks, n_qblocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index4),       # q
            pl.BlockSpec((1, 1, block_k, d), kv_index4),      # k
            pl.BlockSpec((1, 1, block_k, d), kv_index4),      # v
            pl.BlockSpec((1, 1, block_q, d), q_index4),       # out
            pl.BlockSpec((1, 1, block_q, d), q_index4),       # dout
            pl.BlockSpec((1, 1, block_q, LANES), q_index4),   # lse
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nq, S, d), jnp.float32),
            jax.ShapeDtypeStruct((B, nq, S, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(qt, kt, vt, ot, dot, lse_b)

    # ---- pass 2: dq — k-blocks innermost; dq block revisited across kj ----
    def dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dq_acc):
        qi = pl.program_id(2)
        kj = pl.program_id(3)
        head = pl.program_id(1)

        @pl.when(kj == 0)
        def _init():
            dq_acc[:] = jnp.zeros_like(dq_acc)

        visible = (qi + 1) * block_q > kj * block_k if causal else True
        if causal and window is not None:
            visible = jnp.logical_and(
                visible, qi * block_q - ((kj + 1) * block_k - 1) < window)

        @pl.when(visible)
        def _compute():
            qb = q_ref[0, 0].astype(jnp.float32)     # [bq, d]
            ob = o_ref[0, 0].astype(jnp.float32)
            dob = do_ref[0, 0].astype(jnp.float32)
            lseb = lse_ref[0, 0, :, :1]              # [bq, 1]
            kb = k_ref[0, 0].astype(jnp.float32)     # [bk, d]
            vb = v_ref[0, 0].astype(jnp.float32)
            _, ds = _shared_block_math(qb, ob, dob, lseb, kb, vb, qi, kj, head)
            dq_acc[:] += scale * jnp.dot(ds, kb, preferred_element_type=jnp.float32)

        @pl.when(kj == n_kblocks - 1)
        def _flush():
            dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)

    def q_index_dq(b, h, i, j):
        return (b, h, i, 0)

    def kv_index_dq(b, h, i, j):
        return (b, h // group, j, 0)

    dq_t = pl.pallas_call(
        dq_kernel,
        grid=(B, nq, n_qblocks, n_kblocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index_dq),
            pl.BlockSpec((1, 1, block_k, d), kv_index_dq),
            pl.BlockSpec((1, 1, block_k, d), kv_index_dq),
            pl.BlockSpec((1, 1, block_q, d), q_index_dq),
            pl.BlockSpec((1, 1, block_q, d), q_index_dq),
            pl.BlockSpec((1, 1, block_q, LANES), q_index_dq),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_index_dq),
        out_shape=jax.ShapeDtypeStruct((B, nq, S, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, ot, dot, lse_b)

    dq = dq_t.transpose(0, 2, 1, 3).astype(q.dtype)
    if group > 1:
        dk_g = dk_g.reshape(B, nkv, group, S, d).sum(axis=2)
        dv_g = dv_g.reshape(B, nkv, group, S, d).sum(axis=2)
    dk = dk_g.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv_g.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv
