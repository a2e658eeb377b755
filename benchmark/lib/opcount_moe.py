"""Operations and bytes that the expert layers' grouped matmuls need,
computed from counts alone (the program's spans report them). Kept with the
benchmark so that no change to the program can move a roofline share.

Conventions as in ``opcount.py``: a multiply-add is 2 operations, only matrix
products count, bytes are what the algorithm must move once between HBM and
the chip.
"""

from typing import Tuple


def expert_ffn_cost(slots: int, experts_hit: int, hidden: int, expert_width: int,
                    itemsize: int = 2, gated: bool = True) -> Tuple[int, int]:
    """The expert MLPs of ``slots`` routed slots (one token at one of its
    top-k experts; summed over layers and steps as the caller counts them)
    that together touch ``experts_hit`` (layer, expert) weight sets. Each slot
    is multiplied by its expert's up, (gate) and down matrices: 3 (2)
    products of ``2 * hidden * expert_width`` operations. Bytes: every hit
    expert's matrices read once, each slot's input row read and output row
    written once; the ``expert_width``-wide intermediate need not leave the
    chip."""
    matrices = 3 if gated else 2
    flops = slots * matrices * 2 * hidden * expert_width
    nbytes = experts_hit * matrices * hidden * expert_width * itemsize + slots * 2 * hidden * itemsize
    return flops, nbytes
