"""Operations and bytes that the attention kernels and a training step need,
computed from shapes alone. Kept with the benchmark so that no change to the
program can move a roofline share or an MFU.

Conventions: a multiply-add is 2 operations; only matrix products count (the
softmax's exponentials do not); bytes are what the algorithm must move once
between HBM and the chip for the call, not what an implementation re-reads.
"""

from typing import Iterable, Optional, Tuple


def attended_pairs(q_start: int, q_len: int, window: Optional[int] = None) -> int:
    """Query-key pairs of causal attention for queries at positions
    ``q_start .. q_start+q_len-1`` over a context that starts at 0: query
    ``p`` sees ``min(p + 1, window)`` keys."""
    if q_len <= 0:
        return 0
    first, last = q_start + 1, q_start + q_len  # keys seen by the first and last query
    if window is None or last <= window:
        return (first + last) * q_len // 2
    if first >= window:
        return window * q_len
    n_grow = window - first  # queries that still see fewer than `window` keys
    return (first + window - 1) * n_grow // 2 + window * (q_len - n_grow)


def flash_fwd_cost(batch: int, seq: int, n_q: int, n_kv: int, d: int, window: Optional[int] = None,
                   itemsize: int = 2) -> Tuple[int, int]:
    """``flash_fwd``: S = QK^T and O = PV, 2 products of 2*d operations per
    attended pair and query head. Bytes: Q, K, V read and O written once,
    plus the float32 log-sum-exp row per query head."""
    pairs = attended_pairs(0, seq, window)
    flops = batch * n_q * pairs * 4 * d
    nbytes = batch * seq * d * (2 * n_q + 2 * n_kv) * itemsize + batch * n_q * seq * 4
    return flops, nbytes


def flash_bwd_dkdv_cost(batch: int, seq: int, n_q: int, n_kv: int, d: int, window: Optional[int] = None,
                        itemsize: int = 2, out_itemsize: int = 4) -> Tuple[int, int]:
    """``flash_bwd_dkdv``: recomputes S, then dV = P^T dO, dP = dO V^T and
    dK = dS^T Q: 4 products. Reads Q, K, V, dO and the two float32 rows
    (log-sum-exp, delta); writes dK, dV."""
    pairs = attended_pairs(0, seq, window)
    flops = batch * n_q * pairs * 8 * d
    nbytes = (batch * seq * d * (2 * n_q + 2 * n_kv) * itemsize + 2 * batch * n_q * seq * 4
              + batch * seq * d * 2 * n_kv * out_itemsize)
    return flops, nbytes


def flash_bwd_dq_cost(batch: int, seq: int, n_q: int, n_kv: int, d: int, window: Optional[int] = None,
                      itemsize: int = 2, out_itemsize: int = 4) -> Tuple[int, int]:
    """``flash_bwd_dq``: recomputes S, then dP = dO V^T and dQ = dS K: 3
    products. Reads Q, K, V, dO and the two float32 rows; writes dQ."""
    pairs = attended_pairs(0, seq, window)
    flops = batch * n_q * pairs * 6 * d
    nbytes = (batch * seq * d * (2 * n_q + 2 * n_kv) * itemsize + 2 * batch * n_q * seq * 4
              + batch * seq * d * n_q * out_itemsize)
    return flops, nbytes


FLASH_COSTS = {"flash_fwd": flash_fwd_cost, "flash_bwd_dkdv": flash_bwd_dkdv_cost,
               "flash_bwd_dq": flash_bwd_dq_cost}


def paged_attention_cost(rows: Iterable[Tuple[int, int]], n_q: int, n_kv: int, d: int,
                         window: Optional[int] = None, kv_itemsize: int = 2,
                         q_itemsize: int = 2) -> Tuple[int, int]:
    """One ``paged_attn_*`` call (one layer) over ragged rows
    ``(context_before, new_tokens)``: each new token attends to everything
    before it in its sequence. 2 products of 2*d per pair and query head.
    Bytes: each row's visible K and V read once from the pool, Q read and
    O written once. The same function serves prefill rows (many new tokens)
    and decode rows (one)."""
    flops = nbytes = 0
    for before, new in rows:
        flops += n_q * attended_pairs(before, new, window) * 4 * d
        visible = before + new if window is None else min(before + new, window + new - 1)
        nbytes += visible * n_kv * d * 2 * kv_itemsize + new * n_q * d * 2 * q_itemsize
    return flops, nbytes


def min_seconds(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_f, t_b = flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def matmul_params(hidden: int, layers: int, n_q: int, n_kv: int, d: int, ffn: int, vocab: int,
                  gated_mlp: bool) -> int:
    """Parameters that take part in a matrix product per token: the layers'
    projections and MLP, and the output head. The embedding is a lookup."""
    attn = hidden * d * (n_q + 2 * n_kv) + n_q * d * hidden
    mlp = hidden * ffn * (3 if gated_mlp else 2)
    return layers * (attn + mlp) + hidden * vocab


def train_flops_per_token(hidden: int, layers: int, n_q: int, n_kv: int, d: int, ffn: int, vocab: int,
                          gated_mlp: bool, seq: int, window: Optional[int] = None) -> float:
    """Model operations per trained token: 6 per matmul parameter (forward 2,
    backward 4) plus causal attention, forward 4*d per pair and query head
    and backward twice that. Recomputed operations are not counted."""
    pairs_per_token = attended_pairs(0, seq, window) / seq
    return 6.0 * matmul_params(hidden, layers, n_q, n_kv, d, ffn, vocab, gated_mlp) \
        + 3.0 * layers * n_q * pairs_per_token * 4 * d


def mfu(tokens_per_s_per_chip: float, flops_per_token: float, peaks: dict) -> float:
    """Model FLOP/s utilisation of one chip, as a fraction."""
    return tokens_per_s_per_chip * flops_per_token / peaks["flops_bf16"]
