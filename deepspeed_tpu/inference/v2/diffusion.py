"""Generation by masked diffusion over blocks: the device program of
``InferenceEngineV2.decode`` for a model with ``diffusion_block_size``.

Such a model has no causal next token. A row advances a BLOCK of ``B``
positions at a time: the block starts as the tokens already known of it (the
prompt's last partial block) beside ``mask_token_id`` slots, each denoise
forward feeds the block's current ids at its positions (writing the block's
K/V over what the last forward wrote), reads for every position a token and
its confidence (``sampling.diffusion_candidates``: the logits at position
``i`` predict the token AT ``i``) and unmasks the positions the rule picks
(``sampling.diffusion_unmask``), until no mask is left. The cache must then
hold the K/V of the block's FINAL ids, which no denoise forward was fed: the
commit. Inside a call a block's commit is no forward of its own: the final
ids of block ``b`` ride in block ``b + 1``'s first denoise forward, a ragged
step of ``S x 2B`` tokens, row ``i`` holding ``[block b's final ids | block
b + 1's ids]`` at consecutive positions. The ragged step scatters a forward's
K/V before it attends and the model's mask lets a block see every block
before it, so block ``b + 1`` reads what a commit of its own would have
written. Only the call's LAST block is committed by a forward that writes K/V
and nothing else (``kv_only``). ONE compiled program loops over the blocks
and, inside a block, runs the first forward and loops over the others, with
the choice of what to unmask on the device: a call is one host round trip, as
the causal multi-step scan is. How many blocks a call advances is an ARGUMENT
of the program (the descriptor's last word), up to the capacity it was built
for: a row bucket's calls of two, four and eight blocks are one program. Each
forward gathers the logits of every row's last ``B`` tokens (``gather_k = B -
1``, the speculative verify's layout); a forward of ``S x B`` tokens has its
rows at ``[i * B, (i + 1) * B)`` of the token axis, one of ``S x 2B`` at ``[i
* 2B, (i + 1) * 2B)``.
"""

import jax
import jax.numpy as jnp

from ...moe.grouped import merge_routing_stats
from ...monitor import scopes
from .sampling import diffusion_candidates, diffusion_quota, diffusion_unmask


def rows_beside(before, block, B: int, xp=jnp):
    """The token axis of a forward that carries a commit: ``before`` and
    ``block``, ``[S x B]`` arrays a token of a row's block before and of its
    block, as one ``[S x 2B]`` array, row ``i`` at ``[i * 2B, (i + 1) * 2B)``
    (``xp``: ``numpy`` for the host's count of the same batch)."""
    return xp.concatenate([before.reshape(-1, B), block.reshape(-1, B)], axis=1).reshape(-1)


def build_block_program(step_fn, *, block_size: int, mask_id: int, denoising_steps: int, remasking: str,
                        threshold: float, s_bucket: int, cap: int, moe: bool, vocab: int, probe_rows: tuple = ()):
    """``fwd(params, packed, pools) -> ((tokens [S, cap * B], forwards [cap],
    masked_fed, *moe_stats[, probe]), pools)``.

    ``packed``: the ragged descriptor of the FIRST block (``S x B`` tokens: a
    row's known tokens then masks, at the row's committed length on; no state
    slots behind it: such a model has no state layers), then ONE word more,
    the blocks this call advances, ``n <= cap`` (a warm-up's zero descriptor
    advances none); ``tokens`` and ``forwards`` are filled for the first ``n``
    blocks: the denoise forwards each took; ``masked_fed``: the mask ids among
    the live tokens fed over the call. ``probe_rows`` (row indices) adds, for
    those rows, every denoise forward's ids ``[cap, steps, rows, B]`` and
    float32 logits ``[cap, steps, rows, B, V]`` (zeros where a forward did not
    run): the same program with one more result, for the checks.

    The forwards of a program of ``cap > 1``, three traces of ``step_fn``:
    every block's FIRST forward is of ``2T = S x 2B`` tokens, the final ids of
    the block before (``pending``) then the block's own; block 0 has nothing
    pending and feeds that half as padding (validity 0: nothing of it is
    written, routed or read), so that one trace serves every block. It runs
    whatever the block holds: a block that enters without a mask (no call of
    the engine's makes one: a row opens with fewer than ``B`` known tokens)
    still writes what is pending there and unmasks nothing. The block's other
    forwards, while a mask is left, are of ``T`` tokens, and the last block's
    final ids are committed by the ``kv_only`` forward behind the loop. With
    ``cap == 1`` nothing rides anywhere: the block's forwards are all of ``T``
    tokens, then the commit."""
    B, S, steps, cap = int(block_size), int(s_bucket), int(denoising_steps), int(cap)
    T = S * B
    quota = jnp.asarray(diffusion_quota(B, steps), jnp.int32)
    stats0 = (jnp.zeros(3, jnp.int32), ) if moe else ()
    rows = jnp.asarray(probe_rows, jnp.int32)
    fused = cap > 1

    def merge(stats, new):
        with jax.named_scope(scopes.MOE):
            return tuple(merge_routing_stats(a, b) for a, b in zip(stats, new))

    def fwd(params, packed, pools):
        with jax.named_scope(scopes.EMBED):  # the descriptors, and below every change made to them
            packed, n_blocks = packed[:-1], packed[-1]
            ids0, seq_idx, pos0 = packed[0:T], packed[T:2 * T], packed[2 * T:3 * T]
            valid = packed[3 * T:4 * T] > 0
            tables, last_idx = packed[4 * T:-S], packed[-S:]
        beside = lambda before, block: rows_beside(before, block, B)

        def unmask(i, b, ids, logits, masked_fed, probe):
            """What denoise forward ``i`` of block ``b`` makes of the ids it was fed."""
            with jax.named_scope(scopes.SAMPLE):
                tok, conf = diffusion_candidates(logits)
                masked = valid & (ids == mask_id)
                choose = diffusion_unmask(conf.reshape(S, B), masked.reshape(S, B), remasking, quota[i],
                                          threshold, i == steps - 1)
                if probe:
                    probe = (probe[0].at[b, i].set(ids.reshape(S, B)[rows]),
                             probe[1].at[b, i].set(logits.reshape(S, B, vocab)[rows].astype(jnp.float32)))
                return (jnp.where(choose.reshape(T), tok, ids), masked_fed + jnp.sum(masked, dtype=jnp.int32), probe)

        def block(b, carry):
            pl, stats, masked_fed, probe, pending, out, forwards = carry
            with jax.named_scope(scopes.EMBED):
                at_block = packed.at[2 * T:3 * T].add(b * B)  # every position a block further

            def feed(ids):
                with jax.named_scope(scopes.EMBED):
                    return at_block.at[0:T].set(ids)

            def masks_left(c):
                i, ids = c[0], c[1]
                with jax.named_scope(scopes.SAMPLE):
                    return (i < steps) & jnp.any(valid & (ids == mask_id))

            def denoise(c):
                i, ids, pl, stats, masked_fed, probe = c
                logits, pl, *new = step_fn(params, feed(ids), pl, T, S, gather_k=B - 1, moe_stats=moe)
                ids, masked_fed, probe = unmask(i, b, ids, logits, masked_fed, probe)
                return i + 1, ids, pl, merge(stats, new), masked_fed, probe

            with jax.named_scope(scopes.SAMPLE):
                i, ids = jnp.int32(0), jnp.where(b == 0, ids0, jnp.int32(mask_id))
            if fused:
                # the block before's commit and this block's first denoise forward in one: a row's 2B tokens
                # end at 2 * (its last index) + 1, and the logits gathered are its last B tokens', the block's
                with jax.named_scope(scopes.EMBED):
                    pos = pos0 + b * B
                    both = jnp.concatenate([beside(pending, ids), beside(seq_idx, seq_idx),
                                            beside(jnp.maximum(pos - B, 0), pos), beside(valid & (b > 0), valid),
                                            tables, 2 * last_idx + 1]).astype(jnp.int32)
                logits, pl, *new = step_fn(params, both, pl, 2 * T, S, gather_k=B - 1, moe_stats=moe)
                ids, masked_fed, probe = unmask(i, b, ids, logits, masked_fed, probe)
                i, stats = i + 1, merge(stats, new)
            n, ids, pl, stats, masked_fed, probe = jax.lax.while_loop(
                masks_left, denoise, (i, ids, pl, stats, masked_fed, probe))
            with jax.named_scope(scopes.SAMPLE):
                return pl, stats, masked_fed, probe, ids, out.at[b].set(ids.reshape(S, B)), forwards.at[b].set(n)

        probe0 = ()
        if probe_rows:
            probe0 = (jnp.zeros((cap, steps, len(probe_rows), B), jnp.int32),
                      jnp.zeros((cap, steps, len(probe_rows), B, vocab), jnp.float32))
        pools, stats, masked_fed, probe, last, out, forwards = jax.lax.fori_loop(
            0, n_blocks, block, (pools, stats0, jnp.int32(0), probe0, ids0, jnp.zeros((cap, S, B), jnp.int32),
                                 jnp.zeros(cap, jnp.int32)))
        # the commit of the call's last block: the cache must hold the K/V of its FINAL ids
        with jax.named_scope(scopes.EMBED):
            final = packed.at[2 * T:3 * T].add(jnp.maximum(n_blocks - 1, 0) * B).at[0:T].set(last)
        _, pools, *new = step_fn(params, final, pools, T, S, moe_stats=moe, kv_only=True)
        with jax.named_scope(scopes.SAMPLE):
            tokens = out.transpose(1, 0, 2).reshape(S, cap * B)
        return (tokens, forwards, masked_fed, *merge(stats, new), *probe), pools

    return fwd
