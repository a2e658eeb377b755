"""Ragged grouped matmul (megablocks-style) for MoE expert FFNs.

Reference counterpart: the CUTLASS grouped expert GEMM
(``inference/v2/kernels/cutlass_ops/`` moe_gemm) — E variable-size GEMMs,
one per expert, over that expert's gathered tokens. SURVEY §2.3 plans the
TPU version as a Pallas ragged matmul; VERDICT r4 missing #5 flagged the
one-hot ``[S, E, C]`` dispatch einsum as the scaling bottleneck at large E.

TPU-first formulation: dynamic per-expert row counts are shape-hostile, so
the DISPATCHER block-aligns every expert's token group (each group padded to
a multiple of the row-block size, zero rows) and hands the kernel a
scalar-prefetched ``block_expert[i]`` table — the expert owning row block
``i``. Every row block then multiplies exactly one expert's weight block, so
the kernel is a plain tiled matmul whose RHS block index is data-dependent
through the prefetch table (the same mechanism the block-sparse attention
kernel uses for its column LUT). Work scales with actual tokens
(+ at most one padding block per expert), not with S*E*C.

Two kernels:
  - :func:`gmm`  — ``[T, K] x [E, K, N] -> [T, N]``: row block i uses
    ``rhs[block_expert[i]]`` (forward, and dx with rhs transposed).
  - :func:`tgmm` — ``[T, K] x [T, N] -> [E, K, N]``: per-expert
    ``x_e^T @ dy_e`` accumulated across that expert's row blocks (dw).
    Requires every expert to own >=1 row block (the dispatcher's padding
    guarantees it) so every output block is written.

:func:`grouped_matmul` wraps gmm with a custom VJP so the training MoE layer
can differentiate through it.
"""

import functools

import jax
import jax.numpy as jnp


# One expert's weight tile is what a row block streams from HBM, and a row
# block of a decode step is 8 rows: the tile has to be megabytes for the
# stream to run near the memory's rate (a 256 x 128 tile is 64 KiB, a tenth
# of a microsecond of HBM against a third of one per grid step).
WEIGHT_TILE_BYTES = 6 * 2**20
VMEM_LIMIT_BYTES = 64 * 2**20


def _fit_block(dim: int, preferred: int) -> int:
    """Largest block <= preferred that divides dim: a multiple of 128 where
    dim is one (2304 -> 2304, 1152, 768, ...; 896 -> 896, 128), else a power
    of two (1 worst case)."""
    if dim % 128 == 0:
        n = dim // 128
        return 128 * max(m for m in range(1, n + 1) if n % m == 0 and 128 * m <= max(preferred, 128))
    b = 1 << (max(preferred, 1).bit_length() - 1)
    while b > 1 and dim % b != 0:
        b //= 2
    return max(b, 1)


def _resolve_gmm_tiles(K: int, N: int, block_k=None, block_n=None, itemsize: int = 2):
    """K/N tiles: the caller's explicit value, else the whole of K and N, K
    halved until the weight tile fits ``WEIGHT_TILE_BYTES``. ``block_t`` is
    NOT chosen here — it is a dispatcher contract (block_expert's shape)."""
    bn = block_n
    if bn is None:
        bn = _fit_block(N, max(WEIGHT_TILE_BYTES // (itemsize * 128), 128))
    bk = block_k
    if bk is None:
        bk = _fit_block(K, max(WEIGHT_TILE_BYTES // (itemsize * int(bn)), 128))
    return int(bk), int(bn)


def gmm_reference(lhs, rhs, block_expert, block_t=128):
    """jnp gather oracle for :func:`gmm` — the numerics reference the kernel
    is tested against."""
    expert_per_row = jnp.repeat(block_expert, block_t)
    out = jnp.einsum("tk,tkn->tn", lhs.astype(jnp.float32),
                     rhs[expert_per_row].astype(jnp.float32))
    return out.astype(lhs.dtype)


def gmm(lhs, rhs, block_expert, block_t=128, block_k=None, block_n=None, interpret=False,
        num_live=None):
    """Grouped matmul ``out[i*bt:(i+1)*bt] = lhs[i*bt:(i+1)*bt] @
    rhs[block_expert[i]]``.

    lhs: [T, K] block-aligned expert-sorted rows; rhs: [E, K, N] stacked
    expert weights; block_expert: [T//block_t] int32 (non-decreasing).
    Returns [T, N] in lhs.dtype; fp32 accumulation.

    ``num_live``: int32 scalar, the row blocks that hold routed rows (the
    dispatcher's static bound ``T`` is for the worst routing). Blocks from
    there on compute nothing and come back zero; the dispatcher names the
    last live block's expert for them and the weight tile's K index stays at
    the last one that block fetched, so they read no weights either, however
    many K tiles a matrix is cut into (a 3072 x 3072 matrix is three 6 MiB
    tiles: walking them again for every dead block streamed a whole expert
    a block, three times the live bytes where an eighth of the slots land
    here: PERF.md section 6, PR 31).

    Registry tiles resolve HERE, outside the jit: resolving inside would key
    the compiled-executable cache on ``block_k=None`` and freeze the
    first-seen tiles — a later kernel-config install would be silently
    ignored for already-traced shapes.
    """
    block_k, block_n = _resolve_gmm_tiles(lhs.shape[1], rhs.shape[2], block_k, block_n,
                                          jnp.dtype(rhs.dtype).itemsize)
    if num_live is None:
        num_live = block_expert.shape[0]
    live = jnp.asarray(num_live, jnp.int32).reshape(1)
    return _gmm(lhs, rhs, block_expert, live, block_t, block_k, block_n, interpret)


@functools.partial(jax.jit, static_argnames=("block_t", "block_k", "block_n", "interpret"))
def _gmm(lhs, rhs, block_expert, live, block_t, block_k, block_n, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2, f"contraction mismatch {K} vs {K2}"
    # block_t is a CONTRACT with the dispatcher (block_expert's shape is tied
    # to it) — never refit it; K/N tiles are free to shrink to fit
    bt = block_t
    assert T % bt == 0, f"T={T} must be a multiple of block_t={bt} (block-aligned dispatch)"
    bk = _fit_block(K, block_k)
    bn = _fit_block(N, block_n)
    nt, nk, nn = T // bt, K // bk, N // bn
    assert block_expert.shape == (nt, ), \
        f"block_expert must be [{nt}] for T={T}, block_t={bt}, got {block_expert.shape}"

    def kernel(be_ref, live_ref, x_ref, w_ref, o_ref, acc_ref):
        i, k = pl.program_id(0), pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # operands in the type they are stored in (a bf16 weight tile is not
        # widened in VMEM: the MXU multiplies bf16 and accumulates float32)
        @pl.when(i < live_ref[0])
        def _dot():
            acc_ref[:] += jax.lax.dot(x_ref[...], w_ref[0], preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _store():
            o_ref[:] = acc_ref[:].astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, nn, nk),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j, k, be, lv: (i, k)),
            # a dead row block keeps the tile the last live one fetched last: no DMA
            pl.BlockSpec((1, bk, bn), lambda i, j, k, be, lv: (
                be[i], jnp.where(i < lv[0], k, nk - 1), jnp.where(i < lv[0], j, nn - 1))),
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda i, j, k, be, lv: (i, j)),
        scratch_shapes=[pltpu.VMEM((bt, bn), jnp.float32)],
    )
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct((T, N), lhs.dtype),
                          compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
                          name="moe_gmm", interpret=interpret)(block_expert, live, lhs, rhs)


def tgmm(lhs, dy, block_expert, num_experts, block_t=128, block_k=None, block_n=None,
         interpret=False):
    """Per-expert weight gradient ``out[e] = sum_{i: be[i]=e}
    lhs_block_i^T @ dy_block_i`` → [E, K, N] (fp32).

    ``block_expert`` must be non-decreasing AND cover every expert in
    [0, num_experts) at least once (block-aligned dispatch guarantees both);
    otherwise an absent expert's output block would never be written.
    Registry tiles resolve outside the jit (see :func:`gmm`).
    """
    block_k, block_n = _resolve_gmm_tiles(lhs.shape[1], dy.shape[1], block_k, block_n, 4)
    return _tgmm(lhs, dy, block_expert, num_experts, block_t, block_k, block_n, interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_experts", "block_t", "block_k", "block_n", "interpret"))
def _tgmm(lhs, dy, block_expert, num_experts, block_t, block_k, block_n, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, K = lhs.shape
    T2, N = dy.shape
    assert T == T2, f"row mismatch {T} vs {T2}"
    bt = block_t  # dispatcher contract, same as gmm
    assert T % bt == 0, f"T={T} must be a multiple of block_t={bt} (block-aligned dispatch)"
    bk = _fit_block(K, block_k)
    bn = _fit_block(N, block_n)
    nt, nk, nn = T // bt, K // bk, N // bn
    assert block_expert.shape == (nt, ), \
        f"block_expert must be [{nt}] for T={T}, block_t={bt}, got {block_expert.shape}"

    def kernel(be_ref, x_ref, dy_ref, o_ref, acc_ref):
        t = pl.program_id(2)
        e = be_ref[t]
        # group boundaries: zero the accumulator on the first block of each
        # expert's run, write back on the last (out block changes there)
        first = jnp.logical_or(t == 0, be_ref[jnp.maximum(t - 1, 0)] != e)
        last = jnp.logical_or(t == nt - 1, be_ref[jnp.minimum(t + 1, nt - 1)] != e)

        @pl.when(first)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), dy_ref[...].astype(jnp.float32),
            dimension_numbers=(((0, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            o_ref[0] = acc_ref[:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nk, nn, nt),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda i, j, t, be: (t, i)),
            pl.BlockSpec((bt, bn), lambda i, j, t, be: (t, j)),
        ],
        out_specs=pl.BlockSpec((1, bk, bn), lambda i, j, t, be: (be[t], i, j)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct((num_experts, K, N), jnp.float32),
                          compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
                          name="moe_tgmm", interpret=interpret)(block_expert, lhs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, ))
def _gm(lhs, rhs, block_expert, opts):
    bt, bk, bn, interpret = opts
    return gmm(lhs, rhs, block_expert, bt, bk, bn, interpret)


def _gm_fwd(lhs, rhs, block_expert, opts):
    return _gm(lhs, rhs, block_expert, opts), (lhs, rhs, block_expert)


def _gm_bwd(opts, res, dy):
    import numpy as np

    lhs, rhs, block_expert = res
    bt, bk, bn, interpret = opts
    dy = dy.astype(lhs.dtype)
    dx = gmm(dy, rhs.transpose(0, 2, 1), block_expert, bt, bk, bn, interpret)
    dw = tgmm(lhs, dy, block_expert, rhs.shape[0], bt, bk, bn, interpret).astype(rhs.dtype)
    # block_expert is integer routing metadata: float0 cotangent
    return dx, dw, np.zeros(block_expert.shape, dtype=jax.dtypes.float0)


_gm.defvjp(_gm_fwd, _gm_bwd)


def grouped_matmul(lhs, rhs, block_expert, block_t=128, block_k=None, block_n=None,
                   interpret=False):
    """Differentiable grouped matmul: gmm forward; backward dx via gmm
    against the transposed expert weights, dw via tgmm. ``block_expert`` is
    an explicit primal (not a closure capture) so the VJP stays valid inside
    scans/jits where the table is itself a traced value."""
    return _gm(lhs, rhs, block_expert, (block_t, block_k, block_n, interpret))
