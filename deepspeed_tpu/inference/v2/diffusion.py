"""Generation by masked diffusion over blocks: the device program of
``InferenceEngineV2.decode`` for a model with ``diffusion_block_size``.

Such a model has no causal next token. A row advances a BLOCK of ``B``
positions at a time: the block starts as the tokens already known of it (the
prompt's last partial block) beside ``mask_token_id`` slots, each denoise
forward feeds the block's current ids at its positions (writing the block's
K/V over what the last forward wrote), reads for every position a token and
its confidence (``sampling.diffusion_candidates``: the logits at position
``i`` predict the token AT ``i``) and unmasks the positions the rule picks
(``sampling.diffusion_unmask``), until no mask is left; one more forward of
the final ids, for its K/V alone, commits the block. ONE compiled program a
(row bucket, blocks) pair scans the blocks and, inside a block, loops over
the denoise forwards and runs the commit, with the choice of what to unmask
on the device: a call is one host round trip, as the causal multi-step scan
is. Each forward is a ragged step of ``S x B`` tokens that gathers the logits
of every row's whole chunk (``gather_k = B - 1``, the speculative verify's
layout); the rows lie at ``[i * B, (i + 1) * B)`` of the token axis.
"""

import jax
import jax.numpy as jnp

from ...moe.grouped import merge_routing_stats
from .sampling import diffusion_candidates, diffusion_quota, diffusion_unmask


def build_block_program(step_fn, *, block_size: int, mask_id: int, denoising_steps: int, remasking: str,
                        threshold: float, s_bucket: int, n_blocks: int, moe: bool, vocab: int, probe_rows: tuple = ()):
    """``fwd(params, packed, pools) -> ((tokens [S, n_blocks * B], forwards
    [n_blocks], masked_fed, *moe_stats[, probe]), pools)``.

    ``packed``: the ragged descriptor of the FIRST block (``S x B`` tokens: a
    row's known tokens then masks, at the row's committed length on);
    ``forwards``: the denoise forwards each block took (a block whose rows
    hold no mask takes none); ``masked_fed``: the mask ids among the live
    tokens fed over the call. ``probe_rows`` (row indices) adds, for those
    rows, every denoise forward's ids ``[n_blocks, steps, rows, B]`` and
    float32 logits ``[n_blocks, steps, rows, B, V]`` (zeros where a forward
    did not run): the same program with one more result, for the checks."""
    B, S, steps = int(block_size), int(s_bucket), int(denoising_steps)
    T = S * B
    quota = jnp.asarray(diffusion_quota(B, steps), jnp.int32)
    stats0 = (jnp.zeros(3, jnp.int32), ) if moe else ()
    rows = jnp.asarray(probe_rows, jnp.int32)

    def merge(stats, new):
        return tuple(merge_routing_stats(a, b) for a, b in zip(stats, new))

    def fwd(params, packed, pools):
        ids0 = packed[0:T]
        valid = packed[3 * T:4 * T] > 0

        def block(carry, b):
            pl, stats, masked_fed, probe = carry
            at_block = packed.at[2 * T:3 * T].add(b * B)  # every position a block further
            feed = lambda ids: at_block.at[0:T].set(ids)

            def masks_left(c):
                i, ids = c[0], c[1]
                return (i < steps) & jnp.any(valid & (ids == mask_id))

            def denoise(c):
                i, ids, pl, stats, masked_fed, probe = c
                logits, pl, *new = step_fn(params, feed(ids), pl, T, S, gather_k=B - 1, moe_stats=moe)
                tok, conf = diffusion_candidates(logits)
                masked = valid & (ids == mask_id)
                choose = diffusion_unmask(conf.reshape(S, B), masked.reshape(S, B), remasking, quota[i],
                                          threshold, i == steps - 1)
                if probe:
                    probe = (probe[0].at[b, i].set(ids.reshape(S, B)[rows]),
                             probe[1].at[b, i].set(logits.reshape(S, B, vocab)[rows].astype(jnp.float32)))
                return (i + 1, jnp.where(choose.reshape(T), tok, ids), pl, merge(stats, new),
                        masked_fed + jnp.sum(masked, dtype=jnp.int32), probe)

            ids = jnp.where(b == 0, ids0, jnp.int32(mask_id))
            n, ids, pl, stats, masked_fed, probe = jax.lax.while_loop(
                masks_left, denoise, (jnp.int32(0), ids, pl, stats, masked_fed, probe))
            # the commit: the cache must hold the K/V of the FINAL block
            _, pl, *new = step_fn(params, feed(ids), pl, T, S, moe_stats=moe, kv_only=True)
            return (pl, merge(stats, new), masked_fed, probe), (ids.reshape(S, B), n)

        probe0 = ()
        if probe_rows:
            probe0 = (jnp.zeros((n_blocks, steps, len(probe_rows), B), jnp.int32),
                      jnp.zeros((n_blocks, steps, len(probe_rows), B, vocab), jnp.float32))
        (pools, stats, masked_fed, probe), (out, forwards) = jax.lax.scan(
            block, (pools, stats0, jnp.int32(0), probe0), jnp.arange(n_blocks, dtype=jnp.int32))
        tokens = out.transpose(1, 0, 2).reshape(S, n_blocks * B)
        return (tokens, forwards, masked_fed, *stats, *probe), pools

    return fwd
