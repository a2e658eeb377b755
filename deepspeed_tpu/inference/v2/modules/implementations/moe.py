"""MoE implementation (reference
``implementations/moe/cutlass_multi_gemm_moe.py``).

The reference's CUTLASS multi-gemm gathers each expert's tokens and runs E
variable-size gemms. Here the routed slots are sorted by expert into
block-aligned groups and go through the Pallas grouped matmul
(``ops/pallas/grouped_matmul.py``, kernels named ``moe_gmm``): the work
follows the routed slots, an expert without a slot is not read, and the row
block follows the slot count of the bucket, so that a decode step of 32 rows
(4 slots an expert at 64 experts top-8) runs 8-row blocks and streams each
hit expert's weights once. It is the one serving MoE path: a dense dispatch
(every token through every expert) reads every expert at any batch and
multiplies E/k times as much.
"""

import jax

from .....moe.grouped import grouped_moe_ffn, padded_rows, pick_block_rows, route_topk
from ..configs import DSMoEConfig
from ..interfaces import DSMoEBase, DSMoERegistry


@DSMoERegistry.register_module
class GroupedGemmMoE(DSMoEBase):

    @staticmethod
    def name() -> str:
        return "grouped_gemm_moe"

    @staticmethod
    def supports_config(config: DSMoEConfig) -> bool:
        return 1 <= config.top_k <= config.n_experts

    def padded_rows(self, tokens: int) -> int:
        """Rows the grouped kernel computes for a bucket of ``tokens``
        tokens, padding included (static: the bound over every routing)."""
        cfg = self.config
        slots = tokens * cfg.top_k
        return padded_rows(slots, cfg.n_experts, pick_block_rows(slots, cfg.n_experts), False)

    def __call__(self, x, gate_w, expert_up, expert_gate, expert_down, valid=None,
                 with_stats: bool = False, layer=None):
        """x: [T, H]; gate_w: [H, E]; expert_up/expert_gate: [E, H, F]
        (expert_gate may be None for non-glu); expert_down: [E, F, H] — or,
        with ``layer`` given, the model's stacked ``[L, E, ...]`` arrays, of
        which the kernel reads that layer's experts in place (a slice handed
        to it would be copied first). ``valid`` [T]: padding tokens of the
        bucket route nowhere. Routing:
        the top-k of the float32 softmax over all experts, renormalised if
        the configuration says so; no token is dropped. ``with_stats`` adds
        int32 ``[experts_hit, expert_load_max]``."""
        cfg = self.config
        top_idx, top_w = route_topk(x, gate_w, cfg.top_k, cfg.norm_topk_prob)

        def act(up, gate):
            if cfg.activation == "swiglu":
                return jax.nn.silu(gate) * up
            return jax.nn.relu(up) if cfg.activation == "relu" else jax.nn.gelu(up)

        return grouped_moe_ffn(x.astype(cfg.dtype), top_idx, top_w.astype(cfg.dtype), expert_up, expert_down,
                               wg=expert_gate if cfg.activation == "swiglu" else None, activation=act,
                               valid=valid, differentiable=False, with_stats=with_stats, layer=layer)
