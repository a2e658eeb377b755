"""Operations and bytes that softmax attention under a learned block selection
needs at least, computed from counts alone (the program's spans report them).
Kept with the benchmark so that no change to the program can move a roofline
share.

Conventions as in ``opcount.py``: a multiply-add is 2 operations, only matrix
products count, bytes are what the algorithm must move once between HBM and
the chip.

The work counted is the MODEL's: a query head against the tokens of the blocks
its selection chose (``pairs``: (query token, selected context token) pairs a
layer, as the span's ``attn_pairs`` sums them), two products a pair a head. A
tile of query tokens reads the union of its tokens' blocks, which is the
kernel's cost and not the model's work, so it is not counted. Bytes: the
context tokens a row's queries selected, read ONCE a row a call a layer
(``ctx_tokens``, the span's ``attn_ctx_tokens``: at most the row's whole
context however many of its tokens chose a block, so a chunk whose tokens
share blocks is not charged a block a token), each ``entry_bytes`` of K and V,
plus each query token's q in and o out.
"""

from typing import Tuple


def selected_attention_cost(pairs: int, ctx_tokens: int, query_tokens: int, n_q: int, d: int, entry_bytes: int,
                            itemsize: int = 2) -> Tuple[int, int]:
    """``pairs`` and ``ctx_tokens`` summed over layers as the spans sum them;
    ``query_tokens``: (token, layer) pairs through a sparse layer."""
    flops = 4 * pairs * n_q * d
    nbytes = ctx_tokens * entry_bytes + 2 * query_tokens * n_q * d * itemsize
    return flops, nbytes


def index_cost(index_keys: int, group: int, d: int, key_bytes: int) -> Tuple[int, int]:
    """The indexer's scores: ``index_keys`` (query token, kv head, pooled key)
    triples, each scored by the ``group`` heads of the kv head (one product of
    ``d``); every pooled key read once a triple at most (``key_bytes`` a kv head)."""
    return 2 * index_keys * group * d, index_keys * key_bytes
