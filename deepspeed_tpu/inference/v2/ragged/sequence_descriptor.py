"""Per-sequence tracking state.

Analog of the reference ``inference/v2/ragged/sequence_descriptor.py``
(``DSSequenceDescriptor``: seen tokens, KV block ids, in-flight count). The
reference mirrors this metadata into pinned host tensors; on TPU the metadata
lives as plain numpy and is shipped to the device once per forward inside the
``RaggedBatchWrapper`` arrays.
"""

from dataclasses import dataclass, field
from typing import List

import numpy as np


@dataclass
class DSSequenceDescriptor:
    uid: int
    block_size: int
    seen_tokens: int = 0  # tokens whose KV is already materialized
    in_flight_tokens: int = 0  # tokens scheduled in the current forward
    kv_blocks: List[int] = field(default_factory=list)
    # prefix-cache bookkeeping: the token ids behind the materialized KV (so
    # completed full blocks can be published into the radix tree), how many
    # leading blocks arrived SHARED from the tree (immutable for this
    # sequence), and how many prompt tokens the cache let prefill skip.
    # ``history_valid`` drops to False when generated tokens were never
    # fetched to host (decode(block=False)) — publishing then stops at the
    # last known-token boundary forever, never guesses.
    token_history: List[int] = field(default_factory=list)
    history_valid: bool = True
    shared_blocks: int = 0
    prefix_cached_tokens: int = 0
    published_blocks: int = 0  # publish() walk cursor: full blocks already walked
    # owner identity (serving/metering.py): stamped at creation when the
    # request plane knows a tenant; rides into published radix-tree nodes
    # so hits and eviction pressure are attributable. None = untenanted.
    tenant: str = None
    # a model with state layers: the sequence's slot in the state pools, taken
    # at admission and freed at flush; -1 for every other model
    state_slot: int = -1
    # a block-diffusion model (``diffusion_block_size`` B): ``seen_tokens`` is
    # the COMMITTED length, a multiple of B, whose K/V is final. The slots of
    # the block after it are written by every denoise forward of a ``decode``
    # call and count as ``in_flight_tokens`` until that call's commit, so no
    # rollback, flush or prefix hash ever reads a slot that is not final.

    @property
    def cur_allocated_blocks(self) -> int:
        return len(self.kv_blocks)

    @property
    def max_context(self) -> int:
        return len(self.kv_blocks) * self.block_size

    def blocks_needed(self, new_tokens: int) -> int:
        """Additional blocks required to hold ``new_tokens`` more KV entries."""
        total = self.seen_tokens + new_tokens
        need = -(-total // self.block_size)  # ceil
        return max(0, need - len(self.kv_blocks))

    def extend_blocks(self, blocks) -> None:
        self.kv_blocks.extend(int(b) for b in np.atleast_1d(blocks))

    def pre_forward(self, num_tokens: int) -> None:
        self.in_flight_tokens = num_tokens

    def post_forward(self) -> None:
        self.seen_tokens += self.in_flight_tokens
        self.in_flight_tokens = 0

    def block_table(self, max_blocks: int) -> np.ndarray:
        out = np.zeros(max_blocks, dtype=np.int32)
        n = min(len(self.kv_blocks), max_blocks)
        out[:n] = self.kv_blocks[:n]
        return out
