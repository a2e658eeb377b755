"""Operations and bytes that attention over a LATENT cache needs at least,
computed from counts alone (the program's spans report them). Kept with the
benchmark so that no change to the program can move a roofline share.

Conventions as in ``opcount.py``: a multiply-add is 2 operations, only matrix
products count, bytes are what the algorithm must move once between HBM and
the chip.

The count is the LEAST work of any correct form. Latent attention can be
computed expanded (per-head keys and values made from the latent: a (query,
key) pair a head costs ``2 * (qk_dim + v_dim)`` operations) or absorbed
(``W_kvb`` folded into the query and the output: ``2 * (entry + latent)``, more
than twice as many at the published sizes). The expanded pair cost is the
smaller, so it is what is counted whichever form the program runs: the share
then reads the same work before and after a change of form, and cannot pass
100% by one. The expansion's own matmuls (``W_kvb`` applied to the context) are
NOT counted: the absorbed form does without them. Bytes: every context token's
cached entry read once a row a layer at its UNPADDED size (what a layout pads
it to is the program's choice), each query token's heads read and its outputs
written once.
"""

from typing import Tuple


def latent_attention_cost(pairs: int, ctx_tokens: int, query_tokens: int, n_q: int, qk_dim: int, v_dim: int,
                          entry_bytes: int, itemsize: int = 2) -> Tuple[int, int]:
    """``pairs``: visible (query token, context token) pairs, ``ctx_tokens``:
    context tokens the rows see, ``query_tokens``: tokens fed, all three summed
    over layers (and steps) as the caller counts them; ``n_q`` heads with
    scores ``qk_dim`` wide and values ``v_dim`` wide; ``entry_bytes`` what one
    token caches in one layer."""
    flops = pairs * n_q * 2 * (qk_dim + v_dim)
    nbytes = ctx_tokens * entry_bytes + query_tokens * n_q * (qk_dim + v_dim) * itemsize
    return flops, nbytes


def absorbed_share_of_expanded(qk_dim: int, v_dim: int, latent: int, rope: int) -> float:
    """The most a program in the ABSORBED form can read where FLOP/s bound:
    the expanded pair cost over its own, ``(qk_dim + v_dim) / (latent + rope +
    latent)``. 1,024 / 2,176 = 47.1% at 192 + 64, 256, 512, 64."""
    return (qk_dim + v_dim) / (2 * latent + rope)
