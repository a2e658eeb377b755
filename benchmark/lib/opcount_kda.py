"""Operations and bytes that the delta rule of a linear-attention layer (Kimi
Delta Attention) needs at least, computed from counts alone (the program's
spans report them). Kept with the benchmark so that no change to the program
can move a roofline share.

Conventions as in ``opcount.py``: a multiply-add is 2 operations, only matrix
products count, bytes are what the algorithm must move once between HBM and
the chip.

The count is the LEAST work of any correct form, which is the recurrent one: a
token a head costs three products with the ``dk x dv`` state (``S'^T k``, the
rank-one update ``k u^T``, ``S^T q``), ``6 dk dv`` operations; the chunkwise
form spends more (its triangular solves and the products with the chunk's own
keys) to spend it on the matrix unit. So the share reads the same work
whichever form the program runs, and cannot pass 100% by a change of form.
Bytes: a row's state read and written ONCE a call a layer however many tokens
the call feeds it (a decode horizon of n steps is n calls), float32; each
token's q, k, v in and its output out at the compute type's size, its decay
(a float32 a key channel) and its step size in.
"""

from typing import Tuple


def delta_rule_cost(row_calls: int, tokens: int, heads: int, dk: int, dv: int, itemsize: int = 2) -> Tuple[int, int]:
    """``row_calls``: (row, layer, call) triples whose state was read and
    written; ``tokens``: (token, layer) pairs through the rule; both summed as
    the caller counts them."""
    flops = tokens * heads * 6 * dk * dv
    nbytes = row_calls * 2 * heads * dk * dv * 4 + tokens * heads * ((2 * dk + 2 * dv) * itemsize + dk * 4 + 4)
    return flops, nbytes
