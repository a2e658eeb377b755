"""Operations and bytes that the selective scan of a state-space layer
(Mamba-2) needs at least, computed from counts alone (the program's spans
report them). Kept with the benchmark so that no change to the program can
move a roofline share.

Conventions as in ``opcount.py``. The count is the LEAST work of any correct
form, which is the recurrent one: a token a head costs the rank-one update
``(dt x) B^T`` and the read ``S C`` of its ``P x N`` state, ``4 P N`` operations
(the decay and the ``D`` skip are no matrix products); the chunkwise form
spends more (``C B^T`` and the products with the chunk's own tokens) to spend
it on the matrix unit. So the share reads the same work whichever form the
program runs, and cannot pass 100% by a change of form. Bytes: a row's state
read and written ONCE a call a layer however many tokens the call feeds it (a
decode horizon of n steps is n calls), float32; each token's x in and y out a
head and its B and C a group at the compute type's size, and its dt a head in
float32. The convolution's tail, the gated norm and the two projections are
not the scan's.
"""

from typing import Tuple


def selective_scan_cost(row_calls: int, tokens: int, heads: int, head_dim: int, state: int, groups: int,
                        itemsize: int = 2) -> Tuple[int, int]:
    """``row_calls``: (row, layer, call) triples whose state was read and
    written; ``tokens``: (token, layer) pairs through the scan."""
    flops = tokens * heads * 4 * head_dim * state
    nbytes = row_calls * 2 * heads * head_dim * state * 4 \
        + tokens * ((2 * heads * head_dim + 2 * groups * state) * itemsize + heads * 4)
    return flops, nbytes
