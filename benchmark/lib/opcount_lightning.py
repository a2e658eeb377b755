"""Operations and bytes that the recurrence of a lightning-attention layer
(linear attention with a scalar decay a head) needs at least, computed from
counts alone (the program's spans report them). Kept with the benchmark so
that no change to the program can move a roofline share.

Conventions as in ``opcount.py``. The count is the LEAST work of any correct
form, which is the recurrent one: a token a head costs the rank-one update ``k
v^T`` and the read ``S^T q``, ``4 dk dv`` operations (the decay is no matrix
product); the chunkwise form spends more (the products with the chunk's own
keys) to spend it on the matrix unit. So the share reads the same work
whichever form the program runs, and cannot pass 100% by a change of form.
Bytes: a row's state read and written ONCE a call a layer however many tokens
the call feeds it (a decode horizon of n steps is n calls), float32; each
token's q, k, v in and its output out at the compute type's size.
"""

from typing import Tuple


def recurrence_cost(row_calls: int, tokens: int, heads: int, dk: int, dv: int, itemsize: int = 2) -> Tuple[int, int]:
    """``row_calls``: (row, layer, call) triples whose state was read and
    written; ``tokens``: (token, layer) pairs through the recurrence."""
    flops = tokens * heads * 4 * dk * dv
    nbytes = row_calls * 2 * heads * dk * dv * 4 + tokens * heads * (2 * dk + 2 * dv) * itemsize
    return flops, nbytes
