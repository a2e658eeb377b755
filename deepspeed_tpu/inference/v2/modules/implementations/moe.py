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

A model served as one chip of an expert-parallel group holds a share of the
experts (``DSMoEConfig.n_held`` from ``first_expert`` on): the router still
scores all ``n_experts``, an assignment to an absent expert takes no row here
and its term is left out (``hold_experts``), the row buffer is sized for every
slot of the bucket landing here and the row block for the slots expected here.
"""

import jax

from .....moe.grouped import grouped_moe_ffn, hold_experts, padded_rows, pick_block_rows, route_topk
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

    def _slots_here(self, tokens: int):
        """(every slot of a bucket of ``tokens`` tokens, the slots expected on
        the experts held here under uniform routing)."""
        cfg = self.config
        slots = tokens * cfg.top_k
        return slots, max(1, slots * cfg.held // cfg.n_experts)

    def padded_rows(self, tokens: int) -> int:
        """Rows the grouped kernel's grid covers for a bucket of ``tokens``
        tokens, padding included (static: the bound over every routing; with a
        share of the experts held, over every slot landing here)."""
        slots, expected = self._slots_here(tokens)
        return padded_rows(slots, self.config.held, pick_block_rows(expected, self.config.held), False)

    def __call__(self, x, gate_w, expert_up, expert_gate, expert_down, valid=None,
                 with_stats: bool = False, layer=None, gate_bias=None):
        """x: [T, H]; gate_w: [H, E]; expert_up/expert_gate: [E_held, H, F]
        (expert_gate may be None for non-glu); expert_down: [E_held, F, H] — or,
        with ``layer`` given, the model's stacked ``[L, E_held, ...]`` arrays, of
        which the kernel reads that layer's experts in place (a slice handed
        to it would be copied first). ``valid`` [T]: padding tokens of the
        bucket route nowhere. Routing: the top-k of the float32 scores over
        all E experts by the configuration's rule (softmax, or sigmoid with
        the selection bias ``gate_bias`` [E]), renormalised and scaled as it
        says; no token is dropped, and an assignment to an expert that is not
        held here takes no row. ``with_stats`` adds int32
        ``[experts_hit, expert_load_max, slots]`` over the experts held."""
        cfg = self.config
        top_idx, top_w = route_topk(x, gate_w, cfg.top_k, cfg.norm_topk_prob, cfg.score_func,
                                    gate_bias, cfg.route_scale)
        expected = None
        if cfg.held != cfg.n_experts:
            top_idx, top_w = hold_experts(top_idx, top_w, cfg.first_expert, cfg.held)
            expected = self._slots_here(x.shape[0])[1]

        def act(up, gate):
            if cfg.activation == "swiglu":
                return jax.nn.silu(gate) * up
            if cfg.activation == "relu2":  # relu squared, no gate matrix
                return jax.numpy.square(jax.nn.relu(up))
            return jax.nn.relu(up) if cfg.activation == "relu" else jax.nn.gelu(up)

        return grouped_moe_ffn(x.astype(cfg.dtype), top_idx, top_w.astype(cfg.dtype), expert_up, expert_down,
                               wg=expert_gate if cfg.activation == "swiglu" else None, activation=act,
                               valid=valid, differentiable=False, with_stats=with_stats, layer=layer,
                               expected_slots=expected)
