"""Ragged batch packing.

Analog of the reference ``inference/v2/ragged/ragged_wrapper.py``
(``RaggedBatchWrapper``: packs token ids + per-sequence metadata into pinned
host buffers, ``finalize()`` uploads once per forward). TPU version: the
arrays are padded to *bucketed* static shapes so the jitted ragged forward
compiles once per (token-bucket, seq-bucket, block-bucket) triple, then the
whole descriptor set ships to the device as one transfer.
"""

from dataclasses import dataclass
from typing import Dict, List

import numpy as np


def next_bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds the largest bucket {buckets[-1]}")


@dataclass
class RaggedBatch:
    """Finalized, padded batch — everything the device forward needs."""

    token_ids: np.ndarray  # [T_pad] int32
    token_seq_idx: np.ndarray  # [T_pad] int32 — batch row of each token
    token_pos: np.ndarray  # [T_pad] int32 — absolute position in its sequence
    token_valid: np.ndarray  # [T_pad] bool
    block_tables: np.ndarray  # [S_pad, max_blocks] int32
    seq_start_len: np.ndarray  # [S_pad] int32 — tokens already in cache
    seq_total_len: np.ndarray  # [S_pad] int32 — start + new tokens this batch
    last_token_idx: np.ndarray  # [S_pad] int32 — flat index of each seq's last token
    n_tokens: int
    n_seqs: int
    state_slots: np.ndarray = None  # [S_pad] int32, a model with state layers alone: each row's state slot

    @property
    def max_context_bucket(self) -> int:
        return self.block_tables.shape[1]

    def packed(self) -> np.ndarray:
        """All descriptor arrays as ONE int32 vector — a single host→device
        transfer per forward (the analog of the reference's single pinned-
        buffer upload, ``ragged_wrapper.py finalize()``).
        Layout: [T ids][T seq_idx][T pos][T valid][S*max_blocks tables][S last_idx],
        then [S state slots] for a model with state layers.
        """
        return np.concatenate([
            self.token_ids, self.token_seq_idx, self.token_pos,
            self.token_valid.astype(np.int32), self.block_tables.reshape(-1),
            self.last_token_idx,
        ] + ([] if self.state_slots is None else [self.state_slots])).astype(np.int32)


def unpack_descriptors(packed, t_bucket: int, s_bucket: int, max_blocks: int):
    """In-jit inverse of ``RaggedBatch.packed()`` (shapes are static per
    bucket). Returns (token_ids, seq_idx, pos, valid, block_tables, last_idx)."""
    T, S = t_bucket, s_bucket
    token_ids = packed[0:T]
    seq_idx = packed[T:2 * T]
    pos = packed[2 * T:3 * T]
    valid = packed[3 * T:4 * T].astype(bool)
    tables = packed[4 * T:4 * T + S * max_blocks].reshape(S, max_blocks)
    last_idx = packed[4 * T + S * max_blocks:4 * T + S * max_blocks + S]
    return token_ids, seq_idx, pos, valid, tables, last_idx


def packed_len(t_bucket: int, s_bucket: int, max_blocks: int, state: bool = False) -> int:
    """Length of ``RaggedBatch.packed()`` for a bucket (``state``: with the
    rows' state slots behind it)."""
    return 4 * t_bucket + s_bucket * (max_blocks + 1 + bool(state))


def unpack_state_slots(packed, t_bucket: int, s_bucket: int, max_blocks: int):
    """The rows' state slots, which a model with state layers packs last."""
    at = packed_len(t_bucket, s_bucket, max_blocks)
    return packed[at:at + s_bucket]


class RaggedBatchWrapper:

    def __init__(self, max_ragged_batch_size: int = 768, max_ragged_sequence_count: int = 128,
                 max_blocks_per_seq: int = 32, block_size: int = 64,
                 token_buckets=None, seq_buckets=None):
        self.max_tokens = max_ragged_batch_size
        self.max_seqs = max_ragged_sequence_count
        self.max_blocks_per_seq = max_blocks_per_seq
        self.block_size = block_size
        self.token_buckets = _buckets(token_buckets, max_ragged_batch_size)
        self.seq_buckets = _buckets(seq_buckets, max_ragged_sequence_count)
        self.clear()

    def clear(self):
        self._tokens: List[np.ndarray] = []
        self._descs = []

    def insert_sequence(self, desc, tokens: np.ndarray) -> None:
        """Queue ``tokens`` (1-D int array) of sequence ``desc`` for this
        forward (reference ``ragged_wrapper.py`` insert_sequence)."""
        tokens = np.asarray(tokens, dtype=np.int32).reshape(-1)
        if len(self._descs) >= self.max_seqs:
            raise ValueError(f"batch already holds {self.max_seqs} sequences")
        if self.current_tokens + tokens.size > self.max_tokens:
            raise ValueError(f"token budget exceeded: {self.current_tokens}+{tokens.size} > {self.max_tokens}")
        self._tokens.append(tokens)
        self._descs.append(desc)

    @property
    def current_tokens(self) -> int:
        return int(sum(t.size for t in self._tokens))

    @property
    def current_sequences(self) -> int:
        return len(self._descs)

    def finalize(self) -> RaggedBatch:
        """Pack into bucket-padded arrays (reference ``finalize()`` — its
        single pinned-host upload is here the bucketed transfer of this
        struct's arrays when they are passed into the jitted forward)."""
        n_seqs = len(self._descs)
        n_tokens = self.current_tokens
        assert n_seqs > 0, "empty ragged batch"
        T = next_bucket(n_tokens, self.token_buckets)
        S = next_bucket(n_seqs, self.seq_buckets)

        token_ids = np.zeros(T, np.int32)
        seq_idx = np.zeros(T, np.int32)
        pos = np.zeros(T, np.int32)
        valid = np.zeros(T, bool)
        tables = np.zeros((S, self.max_blocks_per_seq), np.int32)
        start_len = np.zeros(S, np.int32)
        total_len = np.zeros(S, np.int32)
        last_idx = np.zeros(S, np.int32)
        with_state = any(desc.state_slot >= 0 for desc in self._descs)
        state_slots = np.zeros(S, np.int32) if with_state else None

        cur = 0
        for i, (desc, toks) in enumerate(zip(self._descs, self._tokens)):
            n = toks.size
            token_ids[cur:cur + n] = toks
            seq_idx[cur:cur + n] = i
            pos[cur:cur + n] = desc.seen_tokens + np.arange(n)
            valid[cur:cur + n] = True
            tables[i] = desc.block_table(self.max_blocks_per_seq)
            start_len[i] = desc.seen_tokens
            total_len[i] = desc.seen_tokens + n
            last_idx[i] = cur + n - 1
            if with_state:
                state_slots[i] = desc.state_slot
            cur += n

        return RaggedBatch(token_ids=token_ids, token_seq_idx=seq_idx, token_pos=pos, token_valid=valid,
                           block_tables=tables, seq_start_len=start_len, seq_total_len=total_len,
                           last_token_idx=last_idx, n_tokens=n_tokens, n_seqs=n_seqs, state_slots=state_slots)


def _buckets(given, max_n: int):
    """``given`` ascending, or the powers of two from 8 up to ``max_n``; the
    largest bucket is the limit itself, so that a full batch has one."""
    if not given:
        return _pow2_buckets(max_n)
    out = sorted(int(b) for b in given)
    if out[-1] != max_n:
        raise ValueError(f"buckets {out} must end at the limit {max_n}")
    return out


def _pow2_buckets(max_n: int):
    out, b = [], 8
    while b < max_n:
        out.append(b)
        b *= 2
    out.append(max_n)
    return out
