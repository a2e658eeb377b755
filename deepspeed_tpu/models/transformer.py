"""TPU-first decoder-only transformer.

This is the framework's flagship training model family, covering the model
space of the reference's containers (``deepspeed/module_inject/containers/``
gpt2…llama2, ``model_implementations/``): configurable norm (LayerNorm /
RMSNorm), positions (learned / rotary), MLP (gelu / SwiGLU), GQA, tied or
untied LM head. Design choices are TPU-native, not a port:

  * **Scan-stacked layers**: all L blocks live in single stacked arrays
    ([L, ...]) consumed by ``lax.scan`` — one block compiled once, and when
    ZeRO-3 shards the stacked arrays over the data axis, XLA's scan lowering
    all-gathers exactly one layer's params per iteration: the same per-submodule
    allgather/release lifecycle the reference drives with module hooks
    (``partitioned_param_coordinator.py:256 fetch_sub_module``), but from the
    compiler.
  * **Mixed precision by policy**: params fp32 (master weights, reference
    ``bf16_optimizer.py``), compute in bf16 on the MXU.
  * **Remat**: ``jax.checkpoint`` with a named policy replaces the reference's
    activation-checkpointing machinery (``activation_checkpointing/checkpointing.py``).
  * **Parallelism by sharding**: TP via PartitionRules over the ``model`` axis,
    sequence parallel via Ulysses sharding constraints, batch over ``data``.
"""

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..monitor import scopes
from ..ops.pallas.flash_attention import name_attn_out
from ..parallel.mesh import BATCH_AXES, DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS
from ..runtime.zero.partition import PartitionRules


# what ``layer_types`` may call a layer; those of ``STATE_KINDS`` cache no token and hold a state a sequence;
# an "mlp_only" layer has no mixer at all and caches nothing (a model of ``single_branch_layers``)
LAYER_KINDS = ("sliding_attention", "full_attention", "sparse_attention", "linear_attention", "lightning_attention",
               "state_space", "mlp_only")
STATE_KINDS = ("linear_attention", "lightning_attention", "state_space")


@dataclass
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: Optional[int] = None  # default 4x (gelu) or 8/3x (swiglu)
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # GQA; None = MHA
    max_seq_len: int = 2048
    norm: str = "rmsnorm"  # 'rmsnorm' | 'layernorm'
    positions: str = "rotary"  # 'rotary' | 'learned' | 'alibi'
    mlp: str = "swiglu"  # 'swiglu' | 'gelu' | 'relu' | 'relu2' (relu squared, no gate matrix)
    use_bias: bool = False
    # per-site override for the qkv projections only (Qwen2: biased qkv,
    # bias-free o/mlp). None = follow use_bias.
    qkv_bias: Optional[bool] = None
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    # GPT-J / GPT-NeoX / Falcon style: attention and MLP read the SAME
    # residual input and their outputs add jointly (x + attn + mlp)
    parallel_residual: bool = False
    # parallel_residual models with a single pre-norm (GPT-J, Falcon-7B);
    # False = separate ln2 for the MLP branch (GPT-NeoX)
    shared_ln: bool = False
    # partial rotary (GPT-J rotary_dim, NeoX rotary_pct): rope applies to the
    # first rotary_dim dims of each head; None = full head_dim
    rotary_dim: Optional[int] = None
    # Bloom: LayerNorm right after the token embedding
    embed_layernorm: bool = False
    dtype: Any = jnp.bfloat16  # compute dtype; params are fp32 masters
    # sequence-chunked cross entropy: compute/remat the vocabulary logits
    # one [B, loss_chunk, V] slice at a time instead of materializing the
    # full [B, S, V] — at seq 32k x vocab 32k the full fp32 logits alone are
    # 4GiB/sample, the long-context HBM binding term. None = full logits.
    loss_chunk: Optional[int] = None
    remat: bool = False
    remat_policy: str = "nothing_saveable"
    attention_impl: str = "auto"  # 'auto' | 'reference' | 'flash'
    # sliding-window attention (Mistral): query i sees keys in (i-window, i];
    # None = full causal context. Applies to training (flash/reference),
    # the v1 KV-cache path, and the v2 paged path.
    sliding_window: Optional[int] = None
    # per-layer attention kind ("sliding_attention" | "full_attention", one
    # entry a layer): ``sliding_window`` then applies only where the kind
    # says so. None = every layer is of the one kind ``sliding_window`` gives.
    layer_types: Optional[Tuple[str, ...]] = None
    # a rope per attention kind, as published (``rope_type`` "default" or
    # "yarn" with its factor, original length, betas and attention factor);
    # a kind it does not name, and None, is plain rope at ``rope_theta``
    rope_parameters: Optional[Dict[str, dict]] = None
    # width of a head where it is not hidden_size // num_heads
    head_size: Optional[int] = None
    sequence_parallel: bool = False  # Ulysses/ring sharding over the seq axis
    sequence_parallel_impl: str = "ulysses"  # 'ulysses' (a2a) | 'ring' (ppermute)
    dropout: float = 0.0
    # block-sparse attention: the ds_config 'sparse_attention' dict (mode +
    # per-mode keys, reference config.py:289). None = dense attention.
    sparse_attention: Optional[dict] = None
    # MoE (reference deepspeed/moe): 0 = dense; experts shard over the data
    # axes (expert parallelism); XLA inserts the dispatch/combine all-to-alls
    # at the sharding-constraint boundaries.
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    moe_noisy_gate_policy: Optional[str] = None
    # width of one expert; None = intermediate_size
    moe_intermediate_size: Optional[int] = None
    # False: the capacity gates (top-1 / top-2, tokens over capacity are
    # dropped) through the [S, E, C] one-hot einsum, which shards over the
    # expert-parallel axis. True: softmax over all experts in float32, the
    # top ``moe_top_k`` of it, no token ever dropped, through the grouped
    # ragged matmul (moe/grouped.py), whose work follows the routed slots.
    moe_dropless: bool = False
    # dropless routing: renormalise the top-k probabilities to sum to one
    moe_norm_topk_prob: bool = True
    # The fields below state what a family adds to the dropless expert layer
    # and to the block; their defaults leave every other family as it is.
    # The first ``moe_num_dense_layers`` layers of a model with experts carry
    # the dense MLP of width ``intermediate_size``, the rest the experts
    moe_num_dense_layers: int = 0
    # shared experts of width ``expert_size`` each, run on every token beside
    # the routed ones (one MLP of their summed width, or of
    # ``moe_shared_expert_size`` where the family states a width of its own)
    moe_num_shared_experts: int = 0
    moe_shared_expert_size: Optional[int] = None
    # the router's score over all ``moe_num_experts``: 'softmax', or 'sigmoid'
    # (each expert's own; with ``moe_route_bias`` the top-k is taken of score +
    # a per-expert float32 bias no gradient trains, and the weights are the
    # scores alone); the kept weights are multiplied by ``moe_route_scale``
    moe_score_func: str = "softmax"
    moe_route_bias: bool = False
    moe_route_scale: float = 1.0
    # expert parallelism's share: of the ``moe_num_experts`` routed experts the
    # router scores, ``moe_experts_held`` live here, from ``moe_first_expert``
    # on; an assignment to another expert is computed by the chip that holds
    # it and takes no row here. None = all of them
    moe_experts_held: Optional[int] = None
    moe_first_expert: int = 0
    # RMSNorm over each head's ``head_dim`` on q and on k (one gain vector
    # shared by the heads), before rope
    qk_norm: bool = False
    # attention's output times sigmoid(h W_gate), elementwise, before W_o
    attention_gate: bool = False
    # sandwich norm: a second norm on each branch's output before it is added
    post_norms: bool = False
    # the attention kinds whose layers rotate q and k; None = every layer
    rope_layer_types: Optional[Tuple[str, ...]] = None
    # factor on the token embedding (muP: sqrt(hidden_size))
    embed_scale: float = 1.0
    # generation by masked diffusion over blocks: query ``i`` sees key ``j``
    # iff ``j // B <= i // B`` on absolute positions (causal between blocks of
    # ``B`` tokens, full inside one), and a block is generated by unmasking
    # ``mask_token_id`` slots over several forwards (InferenceEngineV2's
    # ``decode``). 0 = a causal model, every other family as it was
    diffusion_block_size: int = 0
    mask_token_id: Optional[int] = None
    # latent attention (MLA): q and k/v each go through a low-rank projection
    # with an RMSNorm inside, a head's score has ``qk_nope_head_dim`` dims
    # without position and ``qk_rope_head_dim`` rotated ones (``head_size`` is
    # their sum, ``rotary_dim`` the second), the rotated KEY part is one vector
    # all heads share, and what a token caches in a layer is the normed latent
    # and that key part, ``kv_lora_rank + qk_rope_head_dim`` values, from which
    # ``W_kvb`` gives every head's keys and its ``v_head_dim`` values. Served by
    # the ragged path in the absorbed form. 0 = per-head K and V, every other
    # family as it was
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # linear attention (Kimi Delta Attention, the gated delta rule with a decay
    # per key channel): the layers ``layer_types`` calls "linear_attention"
    # cache NOTHING per token and carry, per sequence, a float32 state of
    # ``kda_num_heads`` x ``kda_head_dim`` x ``kda_head_dim`` and the last
    # ``kda_conv_size - 1`` inputs of the short causal convolution over q, k
    # and v (``state_entry``). The decay and the output gate come through two
    # matrices of rank ``kda_gate_rank`` each; ``kda_neg_eigval`` doubles the
    # step size (the transition's eigenvalue along k lies in (-1, 1)). Served
    # by the ragged path alone (``ops/pallas/kda.py``). 0 heads = none, every
    # other family as it was
    kda_num_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kda_gate_rank: int = 128
    kda_neg_eigval: bool = True
    # Lightning attention (scalar-decay linear attention, MiniCPM-SALA): the
    # layers ``layer_types`` calls "lightning_attention" cache nothing per
    # token either and carry a float32 state of ``lightning_num_heads`` x
    # ``lightning_head_dim`` x ``lightning_head_dim`` a sequence and nothing
    # else: ``S_t = lambda_h S_{t-1} + k_t v_t^T``, ``o_t = S_t^T q_t``, with
    # ``lambda_h = exp(-s_h)`` a constant of the head and the layer (``s_h =
    # 2^(-8 (h + 1) / heads) (1 - l / (L - 1) + 1e-5)`` at the PUBLISHED layer
    # index ``l = lightning_layer_offset + layer`` of ``lightning_layers_published``
    # layers), q and k normed a head and roped, no convolution, no delta rule.
    # Served by the ragged path alone (``ops/pallas/lightning.py``). 0 heads = none
    lightning_num_heads: int = 0
    lightning_head_dim: int = 128
    lightning_layer_offset: int = 0
    lightning_layers_published: Optional[int] = None
    # learned block-sparse selection (InfLLM v2): the layers ``layer_types``
    # calls "sparse_attention" cache, beside K and V, one mean-pooled key every
    # ``sparse_kernel_stride`` tokens over ``sparse_kernel_size`` tokens a KV
    # head (``index_entry``), score the blocks of ``sparse_block_size`` tokens
    # with them and attend, past ``sparse_dense_len`` tokens of context, the
    # ``sparse_topk`` highest-scoring blocks alone, the first
    # ``sparse_init_blocks`` and those of the last ``sparse_window_size``
    # tokens always among them (``models/minicpm.py`` has the equations).
    # 0 = every softmax layer attends every visible token
    sparse_topk: int = 0
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # state-space layers (Mamba-2's selective scan, Nemotron-H): the layers
    # ``layer_types`` calls "state_space" cache nothing per token and carry, per
    # sequence, a float32 state of ``mamba_num_heads`` x ``mamba_head_dim`` x
    # ``mamba_state_size`` (the state width last: 128 on the lanes) and the last
    # ``mamba_conv_size - 1`` inputs of the causal convolution over x, B and C
    # (``state_entry``). A head's decay is made from the input, ``exp(dt_t
    # A_h)``; B and C are shared by the heads of one of ``mamba_n_groups``
    # groups (``models/nemotron.py`` has the equations). Served by the ragged
    # path alone (``ops/pallas/mamba2.py``). 0 heads = none
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    mamba_state_size: int = 128
    mamba_n_groups: int = 8
    mamba_conv_size: int = 4
    # a layer is ONE pre-norm branch, ``x + branch(norm(x))``: a mixer where
    # ``layer_types`` names one, the MLP where it says "mlp_only". False: every
    # layer is a mixer, then an MLP, under two norms
    single_branch_layers: bool = False
    # MiniCPM's scaled residual path: each branch's output times
    # ``residual_scale`` before it is added, the final normed hidden state times
    # ``logit_scale`` before the head (``embed_scale`` is the third of them)
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # ZeRO++ qwZ (reference partition_parameters.py:1139 quantized all-gather
    # handles): when set (by the engine, from zero_quantized_weights), the
    # per-layer stage-3 weight gathers inside the scan body travel as int8
    # payload + per-block scales instead of fp32 — 4x less ICI traffic —
    # with a straight-through gradient to the fp32 masters.
    quantized_weights: bool = False
    # Explicit ZeRO-3 gather/compute overlap (set by the engine from
    # zero_optimization.overlap_comm at stage 3): the scan double-buffers the
    # NEXT layer's gathered params in the carry — layer l+1's all-gather is
    # issued at the top of iteration l, so the collective overlaps layer l's
    # compute explicitly instead of relying on XLA's latency-hiding
    # scheduler. Bit-identical loss vs the implicit path (test-enforced).
    overlap_gather: bool = False

    def __post_init__(self):
        if self.moe_num_experts > 0 and self.moe_top_k > 2 and not self.moe_dropless:
            raise ValueError(f"moe_top_k={self.moe_top_k}: the capacity gates are top-1 and top-2 only; "
                             "set moe_dropless=True for top-k routing without dropped tokens")
        if self.moe_score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score_func {self.moe_score_func!r}: 'softmax' or 'sigmoid'")
        if self.moe_num_experts > 0:
            first, held = self.moe_first_expert, self.experts_held
            if not (1 <= held and 0 <= first and first + held <= self.moe_num_experts):
                raise ValueError(f"experts [{first}, {first + held}) held of {self.moe_num_experts}")
            if not 0 <= self.moe_num_dense_layers < len(self.mlp_layers):
                raise ValueError(f"moe_num_dense_layers={self.moe_num_dense_layers} of {len(self.mlp_layers)} layers "
                                 "with an MLP")
        if self.diffusion_block_size:
            B = self.diffusion_block_size
            if B < 1 or B & (B - 1):
                raise ValueError(f"diffusion_block_size={B}: a power of two (a KV block holds whole blocks)")
            if self.mask_token_id is None or not 0 <= self.mask_token_id < self.vocab_size:
                raise ValueError(f"diffusion_block_size={B} needs a mask_token_id inside the vocabulary, "
                                 f"got {self.mask_token_id}")
            if self.sliding_window is not None or self.positions == "alibi":
                raise NotImplementedError("a block-causal mask with a sliding window or alibi: the paged "
                                          "kernels take ONE position a token, which here is its block's last")
        if self.rope_layer_types is not None:
            self.rope_layer_types = tuple(self.rope_layer_types)
        if self.latent_attention:
            sizes = (self.q_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)
            if min(sizes) <= 0 or self.qk_rope_head_dim % 2:
                raise ValueError(f"kv_lora_rank={self.kv_lora_rank} needs q_lora_rank, qk_nope_head_dim, an even "
                                 f"qk_rope_head_dim and v_head_dim, got {sizes}")
            if self.head_size is None:
                self.head_size = self.qk_nope_head_dim + self.qk_rope_head_dim
            if self.rotary_dim is None:
                self.rotary_dim = self.qk_rope_head_dim
            if (self.head_size, self.rotary_dim) != (self.qk_nope_head_dim + self.qk_rope_head_dim,
                                                     self.qk_rope_head_dim):
                raise ValueError("latent attention: head_size is qk_nope_head_dim + qk_rope_head_dim and "
                                 "rotary_dim is qk_rope_head_dim")
            if self.sliding_window is not None or self.layer_types is not None or self.positions != "rotary" \
                    or self.qk_norm or self.attention_gate or self.use_bias or self.diffusion_block_size:
                raise NotImplementedError("latent attention beside a window, layer_types, alibi or learned "
                                          "positions, a q/k norm, a gate, biases or block diffusion")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            unknown = set(self.layer_types) - set(LAYER_KINDS)
            if unknown or len(self.layer_types) != self.num_layers:
                raise ValueError(f"layer_types needs {self.num_layers} entries of {', '.join(LAYER_KINDS)}, got "
                                 f"{len(self.layer_types)} with {sorted(unknown)}")
        # ``layer_types`` says which kind a layer is; the widths of a kind are stated if and only if it occurs
        for kind, heads, name in (("linear_attention", self.kda_num_heads, "kda_num_heads"),
                                  ("lightning_attention", self.lightning_num_heads, "lightning_num_heads"),
                                  ("state_space", self.mamba_num_heads, "mamba_num_heads"),
                                  ("sparse_attention", self.sparse_topk, "sparse_topk")):
            n = (self.layer_types or ()).count(kind)
            if bool(n) != (heads > 0):
                raise ValueError(f"{name}={heads} with {n} {kind!r} layers in layer_types: the one names the other")
        if sum(n > 0 for n in (self.kda_num_heads, self.lightning_num_heads, self.mamba_num_heads)) > 1:
            raise NotImplementedError("'linear_attention' (the delta rule), 'lightning_attention' (scalar decay) and "
                                      "'state_space' (the selective scan) layers in one model: a sequence's state "
                                      "slot holds one kind")
        if self.mamba_num_heads > 0 and (self.mamba_num_heads % self.mamba_n_groups or self.mamba_conv_size < 2):
            raise ValueError(f"mamba_num_heads={self.mamba_num_heads} in {self.mamba_n_groups} groups of whole heads, "
                             f"a convolution of at least 2 taps (got {self.mamba_conv_size})")
        if ("mlp_only" in (self.layer_types or ())) and not self.single_branch_layers:
            raise ValueError("an 'mlp_only' layer belongs to a model of single_branch_layers: every other model's "
                             "layer is a mixer, then an MLP")
        if self.single_branch_layers:
            if self.layer_types is None or "mlp_only" not in self.layer_types:
                raise ValueError("single_branch_layers needs layer_types with at least one 'mlp_only' layer")
            if self.parallel_residual or self.post_norms or self.moe_num_dense_layers or self.norm == "layernorm" \
                    or self.use_bias or self.latent_attention or self.diffusion_block_size:
                raise NotImplementedError("single_branch_layers beside a parallel residual, norms after a branch, leading "
                                          "dense layers, layernorm, biases, latent attention or block diffusion")
        if self.lightning_num_heads > 0 and (self.lightning_head_dim != self.head_dim or self.lightning_head_dim % 2):
            raise NotImplementedError(f"lightning_head_dim={self.lightning_head_dim} beside heads of {self.head_dim}: "
                                      "the lightning layers rotate q and k with the softmax layers' one rope table")
        if self.sparse_topk > 0:
            kinds = {self.layer_types[l] for l in self.kv_layers}
            sc = (self.sparse_kernel_size, self.sparse_kernel_stride, self.sparse_block_size)
            if kinds != {"sparse_attention"} or self.sliding_window is not None or self.latent_attention \
                    or self.diffusion_block_size or self.positions == "alibi":
                raise NotImplementedError("a learned selection beside softmax layers without one, a sliding window, "
                                          "latent attention, block diffusion or alibi: one pool of pooled keys is "
                                          "stacked over every layer that caches K and V")
            if sc[0] != 2 * sc[1] or sc[2] % sc[1] or self.sparse_window_size < sc[0] + sc[2] \
                    or self.sparse_topk < self.sparse_init_blocks + self.sparse_window_size // sc[2] + 2 \
                    or self.sparse_dense_len < self.sparse_topk * sc[2]:
                raise ValueError(f"sparse selection: kernel {sc[0]} = 2 x stride {sc[1]}, block {sc[2]} a multiple of "
                                 f"the stride, window {self.sparse_window_size} >= kernel + block, topk "
                                 f"{self.sparse_topk} over the forced blocks, dense_len {self.sparse_dense_len} >= topk blocks")
        if self.state_layers:
            if not self.kv_layers:
                raise NotImplementedError("no layer that caches K and V (every layer 'linear_attention', or of another "
                                          "kind that caches no token): the paged cache and its block tables are built "
                                          "for at least one")
            if self.use_bias or self.qkv_bias_enabled or self.diffusion_block_size or self.parallel_residual \
                    or self.positions in ("alibi", "learned") or (self.kda_num_heads > 0 and self.kda_conv_size < 2):
                raise NotImplementedError("a recurrent state layer beside biases, block diffusion, a parallel residual, "
                                          "alibi or learned positions, or without its short convolution")
        if self.intermediate_size is None:
            if self.mlp == "swiglu":
                self.intermediate_size = int(8 * self.hidden_size / 3 / 128 + 1) * 128
            else:
                self.intermediate_size = 4 * self.hidden_size
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.sparse_attention is not None:
            if self.sliding_window is not None or self.positions == "alibi":
                raise NotImplementedError("sparse_attention does not compose with sliding_window "
                                          "or alibi (express the window via the layout instead)")
            if self.num_kv_heads != self.num_heads:
                raise NotImplementedError(
                    "sparse_attention requires num_kv_heads == num_heads (MHA) — reject at "
                    "config time rather than deep inside the first jitted forward")
        assert self.head_size is not None or self.hidden_size % self.num_heads == 0
        assert self.num_heads % self.num_kv_heads == 0

    @property
    def qkv_bias_enabled(self) -> bool:
        return self.use_bias if self.qkv_bias is None else self.qkv_bias

    @property
    def head_dim(self):
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def expert_size(self):
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def expert_rows(self) -> int:
        """The width a routed expert's matrices are STORED at: ``expert_size``,
        or, where that is wider than a lane tile and no whole number of them
        (1,856 = 14.5 x 128), the next multiple of 128, the padding zeros (an
        expert's hidden units that read nothing and feed nothing: exact through
        relu, relu squared and SwiGLU, and a fixed point of their gradients).
        The chip's tiled memory holds a row of 1,856 as 1,920 whatever its
        shape says, and given the choice lays such an array with its OTHER
        matrix dimension last: the grouped kernel, which reads an expert's tile
        in place, then got a transposed copy of every expert a program (3.08 GB
        at 5 x 64 experts: the compiler's own report, PR 51)."""
        f = self.expert_size
        return f if f <= 128 or f % 128 == 0 else -(-f // 128) * 128

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def kv_entry(self) -> Tuple[Tuple[int, int], ...]:
        """What ONE token caches in ONE layer, as ``(heads, width)`` of each
        pool of the paged cache: per-head K and V, or the one latent entry
        (normed latent, then the shared rotated key part), padded to whole
        128-lane tiles: the chip's tiled memory pads a row of 576 to 640 values
        whatever its shape says, and the kernels read rows of whole tiles. The
        value is the entry's first ``kv_lora_rank`` lanes, stored once."""
        if self.latent_attention:
            return ((1, -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128), )
        return ((self.num_kv_heads, self.head_dim), ) * 2

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """The layers that carry a recurrent state per sequence and cache
        nothing per token (``layer_types`` says 'linear_attention', the delta
        rule, 'lightning_attention', scalar decay, or 'state_space', the
        selective scan)."""
        return tuple(l for l, kind in enumerate(self.layer_types or ()) if kind in STATE_KINDS)

    @property
    def kv_layers(self) -> Tuple[int, ...]:
        """The layers that cache ``kv_entry`` per token: all but the state
        layers and the layers that are an MLP alone. The paged pool is stacked
        over these alone."""
        return tuple(l for l in range(self.num_layers)
                     if self.layer_types is None or self.layer_types[l] not in STATE_KINDS + ("mlp_only", ))

    @property
    def mlp_layers(self) -> Tuple[int, ...]:
        """The layers that have an MLP: every layer, or, in a model of
        ``single_branch_layers``, those ``layer_types`` calls 'mlp_only'."""
        if not self.single_branch_layers:
            return tuple(range(self.num_layers))
        return tuple(l for l, kind in enumerate(self.layer_types) if kind == "mlp_only")

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        """The layers whose MLP is the routed experts: the MLP layers after
        the leading ``moe_num_dense_layers`` of them, wherever they lie."""
        return self.mlp_layers[self.moe_num_dense_layers:] if self.moe_num_experts > 0 else ()

    @property
    def dense_layers(self) -> Tuple[int, ...]:
        """The layers whose MLP is the dense one."""
        return self.mlp_layers[:self.moe_num_dense_layers] if self.moe_num_experts > 0 else self.mlp_layers

    @property
    def mamba_conv_channels(self) -> int:
        """Channels the state-space layers' convolution runs over: x, B and C."""
        return self.mamba_num_heads * self.mamba_head_dim + 2 * self.mamba_n_groups * self.mamba_state_size

    @property
    def state_entry(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """What ONE sequence holds in ONE state layer, whatever its length, one
        of three shapes: the delta rule's float32 state ``(heads, key width,
        value width)`` and its convolution's tail ``(taps - 1, channels of q, k
        and v)`` in the compute type; a lightning layer's state of the same
        form alone; a state-space layer's float32 state ``(heads, head width,
        state width)`` (the state width, 128, on the lanes: a head's 64 values
        last would be padded to twice their bytes) and its convolution's tail
        ``(taps - 1, channels of x, B and C)``. ``()`` for a model without
        state layers."""
        if not self.state_layers:
            return ()
        if self.lightning_num_heads > 0:
            return ((self.lightning_num_heads, self.lightning_head_dim, self.lightning_head_dim), )
        if self.mamba_num_heads > 0:
            return ((self.mamba_num_heads, self.mamba_head_dim, self.mamba_state_size),
                    (self.mamba_conv_size - 1, self.mamba_conv_channels))
        h, d = self.kda_num_heads, self.kda_head_dim
        return ((h, d, d), (self.kda_conv_size - 1, 3 * h * d))

    @property
    def index_entry(self) -> Tuple[int, ...]:
        """What a sparse layer caches beside K and V: ``(stride, heads,
        width)``, one pooled key of ``(heads, width)`` every ``stride`` tokens,
        on the K/V blocks' own table. ``()`` for a model without selection."""
        if self.sparse_topk <= 0:
            return ()
        return (self.sparse_kernel_stride, self.num_kv_heads, self.head_dim)

    def layer_kind(self, l: int) -> Optional[str]:
        """Attention kind of layer ``l``; None where the model has one kind."""
        return None if self.layer_types is None else self.layer_types[l]

    def layer_window(self, l: int) -> Optional[int]:
        """The window layer ``l`` attends in; None = all earlier keys."""
        return None if self.layer_kind(l) == "full_attention" else self.sliding_window

    @property
    def per_layer_attention(self) -> bool:
        """Window and rope are a layer's own, so no one scan body serves all."""
        return self.layer_types is not None

    @property
    def num_expert_layers(self) -> int:
        return len(self.expert_layers)

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights live here (all, unless a share is stated)."""
        return self.moe_num_experts if self.moe_experts_held is None else self.moe_experts_held

    @property
    def unscannable(self) -> Tuple[str, ...]:
        """What keeps ONE scanned block from serving every layer, by name."""
        why = []
        if self.layer_types is not None:
            why.append(f"layer_types gives each layer its own window and rope ({sorted(set(self.layer_types))})")
        if self.mamba_num_heads > 0:
            why.append(f"{len(self.state_layers)} state-space layer(s) (a selective-scan state and a convolution's tail "
                       "per sequence, which no whole-sequence forward builds)")
        elif self.state_layers:
            rule = "scalar-decay (lightning)" if self.lightning_num_heads > 0 else "delta-rule"
            why.append(f"{len(self.state_layers)} linear-attention layer(s) (a {rule} state per sequence, which "
                       "no whole-sequence forward builds)")
        if self.single_branch_layers:
            why.append(f"layers of ONE branch each ({len(self.mlp_layers)} of {self.num_layers} an MLP alone, the others "
                       "a mixer alone): the scanned block is a mixer, then an MLP")
        if self.sparse_topk > 0:
            why.append(f"a learned block-sparse selection (top {self.sparse_topk} blocks of {self.sparse_block_size} "
                       "over pooled keys cached beside K and V, which no whole-sequence forward builds)")
        if self.moe_num_experts > 0 and self.moe_num_dense_layers > 0:
            why.append(f"{self.moe_num_dense_layers} leading dense layer(s) before the expert layers: two MLP kinds")
        if self.experts_held != self.moe_num_experts:
            why.append(f"a share of the experts ({self.experts_held} of {self.moe_num_experts}) without its exchange")
        for flag, what in ((self.moe_num_shared_experts > 0, "a shared expert"),
                           (self.moe_score_func != "softmax" or self.moe_route_bias or self.moe_route_scale != 1.0,
                            "sigmoid / biased / scaled routing"),
                           (self.moe_num_experts > 0 and self.mlp == "relu2", "relu-squared experts without a gate matrix"),
                           (self.qk_norm, "a q/k norm"), (self.attention_gate, "gated attention"),
                           (self.post_norms, "norms after attention and MLP"),
                           (self.rope_layer_types is not None, "rope in some layer kinds only"),
                           (self.embed_scale != 1.0, "a scaled embedding"),
                           (self.residual_scale != 1.0 or self.logit_scale != 1.0, "scaled residual branches and head input"),
                           (self.latent_attention,
                            f"latent attention (a cached latent of {self.kv_lora_rank} + {self.qk_rope_head_dim}, "
                            "attended in the absorbed form)"),
                           (self.diffusion_block_size > 0,
                            f"a block-causal mask (blocks of {self.diffusion_block_size}, generated by masked "
                            "diffusion)")):
            if flag:
                why.append(what)
        return tuple(why)


def lightning_slopes(cfg: TransformerConfig) -> np.ndarray:
    """``s_h`` of every lightning layer's heads, ``[layers, heads]`` float32:
    ``2^(-8 (h + 1) / heads) (1 - l / (L - 1) + 1e-5)`` at the published layer
    index ``l`` of ``L`` published layers (Lightning Attention-2,
    arXiv:2401.04658); a head's decay a token is ``exp(-s_h)``."""
    nh = cfg.lightning_num_heads
    L = cfg.lightning_layers_published or cfg.num_layers
    head = 2.0 ** (-8.0 * (np.arange(nh, dtype=np.float64) + 1.0) / nh)
    layer = np.asarray([1.0 - (cfg.lightning_layer_offset + l) / max(L - 1, 1) + 1e-5
                        for l in cfg.state_layers], np.float64)
    return (layer[:, None] * head[None, :]).astype(np.float32)


# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------

def init_params(cfg: TransformerConfig, rng: jax.Array) -> Dict[str, Any]:
    """fp32 master params; stacked [L, ...] block arrays for lax.scan."""
    L, H, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k = jax.random.split(rng, 12)
    extra = partial(jax.random.fold_in, k[11])  # keys of what PR 31 added: the first eleven draw what they drew
    Ld = len(cfg.dense_layers)  # layers with the dense MLP
    Le = len(cfg.expert_layers)  # layers with experts: their arrays are stacked over these alone, wherever they lie

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in))

    def gain(key, shape):
        """A norm's gain: one, unless the family's extra norms are on, whose
        gains are drawn about one so that leaving a norm out shows."""
        if not (cfg.post_norms or cfg.qk_norm or cfg.latent_attention or cfg.lightning_num_heads or cfg.mamba_num_heads):
            return jnp.ones(shape, jnp.float32)
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    La = len(cfg.kv_layers)  # layers with softmax attention: its arrays are stacked over these alone
    blocks = {
        "ln1_scale": gain(extra(12), (L, H)),
        "wq": dense_init(k[0], (La, H, nq * d), H),
        "wk": dense_init(k[1], (La, H, nkv * d), H),
        "wv": dense_init(k[2], (La, H, nkv * d), H),
        "wo": dense_init(k[3], (La, nq * d, H), nq * d) / math.sqrt(2 * L),
        "ln2_scale": gain(extra(13), (L, H)),
    }
    if cfg.kda_num_heads > 0:
        # linear attention (KDA), stacked over the state layers alone: q, k, v
        # with their depthwise convolutions ``[taps, channels]``, the decay's
        # and the output gate's two low-rank matrices, ``A_log`` a head and
        # ``dt_bias`` a channel (float32 whatever the compute type), the step
        # size, the per-head output norm's one gain vector, and ``W_o``
        Ll, nh, dk, r, taps = len(cfg.state_layers), cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_gate_rank, \
            cfg.kda_conv_size
        C = nh * dk
        for i, name in enumerate(("q", "k", "v")):
            blocks[f"kda_w{name}"] = dense_init(extra(40 + i), (Ll, H, C), H)
            blocks[f"kda_conv_{name}"] = dense_init(extra(43 + i), (Ll, taps, C), taps)
        blocks.update(
            kda_wf1=dense_init(extra(46), (Ll, H, r), H), kda_wf2=dense_init(extra(47), (Ll, r, C), r),
            kda_wg1=dense_init(extra(48), (Ll, H, r), H), kda_wg2=dense_init(extra(49), (Ll, r, C), r),
            kda_wb=dense_init(extra(50), (Ll, H, nh), H),
            # decays spread over (0, 1): exp(A_log) in [1, 16) and softplus(dt_bias) about 0.01 to 0.3
            kda_A_log=jnp.log(jax.random.uniform(extra(51), (Ll, nh), jnp.float32, 1.0, 16.0)),
            kda_dt_bias=jax.random.uniform(extra(52), (Ll, C), jnp.float32, -4.5, -1.0),
            kda_o_norm_scale=1.0 + 0.1 * jax.random.normal(extra(53), (Ll, dk), jnp.float32),
            kda_wo=dense_init(extra(54), (Ll, C, H), C) / math.sqrt(2 * L))
    if cfg.lightning_num_heads > 0:
        # lightning attention, stacked over its layers alone: q, k, v, the output gate and ``W_o``, the two
        # per-head norms' gain vectors, the output norm's one gain vector over all heads' values, and the
        # decay's exponent a head (float32, a constant of the head and the PUBLISHED layer, not a weight)
        Ll, nh, dk = len(cfg.state_layers), cfg.lightning_num_heads, cfg.lightning_head_dim
        C = nh * dk
        for i, name in enumerate(("q", "k", "v", "g")):
            blocks[f"la_w{name}"] = dense_init(extra(60 + i), (Ll, H, C), H)
        blocks.update(
            la_wo=dense_init(extra(64), (Ll, C, H), C) / math.sqrt(2 * L),
            la_q_norm_scale=1.0 + 0.1 * jax.random.normal(extra(65), (Ll, dk), jnp.float32),
            la_k_norm_scale=1.0 + 0.1 * jax.random.normal(extra(66), (Ll, dk), jnp.float32),
            la_o_norm_scale=1.0 + 0.1 * jax.random.normal(extra(67), (Ll, C), jnp.float32),
            la_slope=jnp.asarray(lightning_slopes(cfg), jnp.float32))
    if cfg.mamba_num_heads > 0:
        # state-space layers (Mamba-2), stacked over their layers alone: ``W_in`` to [z | x B C | dt], the depthwise
        # convolution over x, B and C with its bias, ``A_log``, ``D`` and ``dt_bias`` a head (float32 whatever the
        # compute type), the gated norm's gain and ``W_out``. ``dt = softplus(dt_in + dt_bias)`` log-uniform on
        # [1e-3, 1e-1] at a zero input and ``exp(A_log)`` in [1, 16): a token's decays spread over (0.2, 1)
        Ll, nh, P = len(cfg.state_layers), cfg.mamba_num_heads, cfg.mamba_head_dim
        C, conv, taps = nh * P, cfg.mamba_conv_channels, cfg.mamba_conv_size
        dt0 = jnp.exp(jax.random.uniform(extra(75), (Ll, nh), jnp.float32, math.log(1e-3), math.log(1e-1)))
        blocks.update(
            m2_w_in=dense_init(extra(70), (Ll, H, C + conv + nh), H),
            m2_conv_w=dense_init(extra(71), (Ll, taps, conv), taps),
            m2_conv_b=0.1 * jax.random.normal(extra(72), (Ll, conv), jnp.float32),
            m2_A_log=jnp.log(jax.random.uniform(extra(73), (Ll, nh), jnp.float32, 1.0, 16.0)),
            m2_D=1.0 + 0.1 * jax.random.normal(extra(74), (Ll, nh), jnp.float32),
            m2_dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1
            m2_norm_scale=1.0 + 0.1 * jax.random.normal(extra(76), (Ll, C), jnp.float32),
            m2_w_out=dense_init(extra(77), (Ll, C, H), C) / math.sqrt(2 * L))
    if cfg.latent_attention:
        # the two low-rank projections with their norms, and ``W_kvb`` as the
        # two parts the absorbed form multiplies by, a head at a time: keys
        # ``[nq, latent, nope]`` and values ``[nq, latent, v]`` (the published
        # ``[latent, nq * (nope + v)]`` cut by head; no third copy is kept)
        qr, c, nope, rope, dv = (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                 cfg.v_head_dim)
        for name in ("wq", "wk", "wv"):
            del blocks[name]
        blocks.update(
            wq_a=dense_init(extra(26), (L, H, qr), H), q_a_norm_scale=gain(extra(27), (L, qr)),
            wq_b=dense_init(extra(28), (L, qr, nq * d), qr),
            wkv_a=dense_init(extra(29), (L, H, c + rope), H), kv_a_norm_scale=gain(extra(30), (L, c)),
            wkv_b_k=dense_init(extra(31), (L, nq, c, nope), c), wkv_b_v=dense_init(extra(32), (L, nq, c, dv), c),
            wo=dense_init(k[3], (L, nq * dv, H), nq * dv) / math.sqrt(2 * L))
    if cfg.attention_gate:
        blocks["w_attn_gate"] = dense_init(extra(11), (La, H, nq * d), H)
    if cfg.qk_norm:
        blocks["q_norm_scale"] = gain(extra(14), (La, d))
        blocks["k_norm_scale"] = gain(extra(15), (La, d))
    if cfg.post_norms:
        blocks["ln1_post_scale"] = gain(extra(16), (L, H))
        blocks["ln2_post_scale"] = gain(extra(17), (L, H))
    if Le > 0:
        # the router scores all E experts; the weights of those held here
        # alone are allocated, and none for a dense layer
        E, Eh, Fe, Fr = cfg.moe_num_experts, cfg.experts_held, cfg.expert_size, cfg.expert_rows
        blocks["gate_wg"] = dense_init(k[4], (Le, H, E), H)
        blocks["moe_wi"] = dense_init(k[5], (Le, Eh, H, Fr), H)
        blocks["moe_wo"] = dense_init(k[6], (Le, Eh, Fr, H), Fe) / math.sqrt(2 * L)
        if cfg.mlp == "swiglu":
            blocks["moe_wg"] = dense_init(k[10], (Le, Eh, H, Fr), H)
        if Fr != Fe:  # the width is stored padded (``expert_rows``): the hidden units past it are zeros
            live = (jnp.arange(Fr) < Fe).astype(jnp.float32)
            blocks.update({name: blocks[name] * (live[:, None] if name == "moe_wo" else live)
                           for name in ("moe_wi", "moe_wo", "moe_wg") if name in blocks})
        if cfg.moe_route_bias:
            # of the order of the gap between the k-th and the next score
            blocks["gate_bias"] = 0.01 * jax.random.normal(extra(18), (Le, E), jnp.float32)
        if cfg.moe_num_shared_experts > 0:
            Fs = cfg.moe_shared_expert_size or cfg.moe_num_shared_experts * Fe
            blocks["shared_wi"] = dense_init(extra(19), (Le, H, Fs), H)
            blocks["shared_wo"] = dense_init(extra(20), (Le, Fs, H), Fs) / math.sqrt(2 * L)
            if cfg.mlp == "swiglu":
                blocks["shared_wg"] = dense_init(extra(21), (Le, H, Fs), H)
    if Ld > 0:
        blocks["w_up"] = dense_init(k[4] if Le == 0 else extra(23), (Ld, H, F), H)
        blocks["w_down"] = dense_init(k[5] if Le == 0 else extra(24), (Ld, F, H), F) / math.sqrt(2 * L)
        if cfg.mlp == "swiglu":
            blocks["w_gate"] = dense_init(k[6] if Le == 0 else extra(25), (Ld, H, F), H)
    if (cfg.parallel_residual and cfg.shared_ln) or cfg.single_branch_layers:
        del blocks["ln2_scale"]  # single pre-norm feeds both branches, or a layer has one branch and its one norm
    if cfg.norm == "layernorm":
        blocks["ln1_bias"] = jnp.zeros((L, H), jnp.float32)
        if not (cfg.parallel_residual and cfg.shared_ln):
            blocks["ln2_bias"] = jnp.zeros((L, H), jnp.float32)
    if cfg.qkv_bias_enabled:
        blocks["bq"] = jnp.zeros((L, nq * d), jnp.float32)
        blocks["bk"] = jnp.zeros((L, nkv * d), jnp.float32)
        blocks["bv"] = jnp.zeros((L, nkv * d), jnp.float32)
    if cfg.use_bias:
        blocks["bo"] = jnp.zeros((L, H), jnp.float32)
        blocks["b_up"] = jnp.zeros((L, F), jnp.float32)
        blocks["b_down"] = jnp.zeros((L, H), jnp.float32)

    params = {
        "embed": {"embedding": jax.random.normal(k[7], (cfg.vocab_size, H), jnp.float32) * 0.02},
        "blocks": blocks,
        "final_norm": {"scale": gain(extra(22), (H, ))},
    }
    if cfg.norm == "layernorm":
        params["final_norm"]["bias"] = jnp.zeros((H, ), jnp.float32)
    if cfg.embed_layernorm:  # Bloom word_embeddings_layernorm
        params["embed_norm"] = {"scale": jnp.ones((H, ), jnp.float32)}
        if cfg.norm == "layernorm":
            params["embed_norm"]["bias"] = jnp.zeros((H, ), jnp.float32)
    if cfg.positions == "learned":
        params["pos_embed"] = {"embedding": jax.random.normal(k[8], (cfg.max_seq_len, H), jnp.float32) * 0.02}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense_init(k[9], (H, cfg.vocab_size), H)}
    return params


# ---------------------------------------------------------------------------
# TP partition rules (composed with ZeRO by ZeroShardingPolicy)
# ---------------------------------------------------------------------------

def partition_rules(cfg: Optional[TransformerConfig] = None) -> PartitionRules:
    """Megatron-style TP sharding over the ``model`` mesh axis: qkv/up
    column-parallel, out/down row-parallel, vocab-sharded embeddings — the
    layout the reference's AutoTP infers (``module_inject/auto_tp.py:187``)."""
    return PartitionRules([
        (r"embed/embedding", P(MODEL_AXIS, None)),
        (r"pos_embed/embedding", P(None, None)),
        # blocks dim 0 is the stacked layer dim: sharding it over 'pipe' IS
        # pipeline stage assignment (uniform partitioning, reference
        # PipelineModule._partition_layers); dropped automatically at pipe=1
        (r"blocks/w[qkv]$", P(PIPE_AXIS, None, MODEL_AXIS)),
        (r"blocks/b[qkv]$", P(PIPE_AXIS, MODEL_AXIS)),
        (r"blocks/wo$", P(PIPE_AXIS, MODEL_AXIS, None)),
        (r"blocks/(w_up|w_gate)$", P(PIPE_AXIS, None, MODEL_AXIS)),
        (r"blocks/b_up$", P(PIPE_AXIS, MODEL_AXIS)),
        (r"blocks/w_down$", P(PIPE_AXIS, MODEL_AXIS, None)),
        (r"blocks/(ln1_scale|ln2_scale|ln1_bias|ln2_bias|b_down|bo)$", P(PIPE_AXIS, None)),
        # MoE: experts shard over the data axes (= expert parallelism; this IS
        # their ZeRO sharding), FFN dims over model (TP inside each expert)
        (r"blocks/gate_wg$", P(PIPE_AXIS, None, None)),
        (r"blocks/(moe_wi|moe_wg)$", P(PIPE_AXIS, DATA_AXIS, None, MODEL_AXIS)),
        (r"blocks/moe_wo$", P(PIPE_AXIS, DATA_AXIS, MODEL_AXIS, None)),
        (r"lm_head/kernel", P(None, MODEL_AXIS)),
    ])


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _norm(x, scale, bias, kind, eps):
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        out = x32 * scale
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu)**2, axis=-1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + (bias if bias is not None else 0.0)
    return out.astype(x.dtype)


def rope_inv_freq(cfg: TransformerConfig, kind: Optional[str] = None) -> Tuple[np.ndarray, float]:
    """Inverse frequencies [d/2] and the factor on sin and cos for the rope of
    attention kind ``kind``. Plain rope unless ``cfg.rope_parameters[kind]``
    says ``yarn`` (Peng et al. 2023, as Hugging Face's
    ``_compute_yarn_parameters`` computes it): dimensions that turn more than
    ``beta_fast`` times in the original context keep their frequency, those
    that turn less than ``beta_slow`` times are interpolated by ``factor``,
    a linear ramp between."""
    d = cfg.rotary_dim or cfg.head_dim
    rp = (cfg.rope_parameters or {}).get(kind) or {}
    theta = float(rp.get("rope_theta", cfg.rope_theta))
    extrap = 1.0 / theta**(np.arange(0, d, 2, dtype=np.float64) / d)
    kind_of = rp.get("rope_type", "default")
    if kind_of == "default":
        return extrap.astype(np.float32), 1.0
    if kind_of != "yarn":
        raise NotImplementedError(f"rope_type {kind_of!r}: 'default' and 'yarn' are implemented")
    factor, original = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rp.get("beta_slow", 1))), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = extrap / factor * ramp + extrap * (1.0 - ramp)
    scale = rp.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(scale)


def rope_table(cfg: TransformerConfig, positions: jax.Array,
               kind: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """The two tables ``apply_rope`` takes, at ``positions`` for attention kind
    ``kind``. With ``r = cfg.rotary_dim`` and ``d = cfg.head_dim``:

    - ``r == d``: sin and cos, ``[S, d/2]`` each.
    - ``r < d`` (partial rotary): both at the FULL width of a head, ``[S, d]``,
      so that nothing narrower than a head exists where they are applied or
      where they are made: the signed sine ``[-sin, sin, 0 ... 0]`` and the
      cosine ``[cos, cos, 1 ... 1]`` (``r/2`` lanes, ``r/2`` lanes, ``d - r``
      lanes), from the frequencies laid out twice with zeros behind them
      (sin 0 = 0, cos 0 = 1). Made once a step and outside the layer scan."""
    inv_freq, scale = rope_inv_freq(cfg, kind)
    half, rest = len(inv_freq), cfg.head_dim - 2 * len(inv_freq)
    if rest == 0:
        freqs = jnp.einsum("s,f->sf", positions.astype(jnp.float32), jnp.asarray(inv_freq))
        if scale != 1.0:
            return jnp.sin(freqs) * scale, jnp.cos(freqs) * scale
        return jnp.sin(freqs), jnp.cos(freqs)
    lanes = np.concatenate([inv_freq, inv_freq, np.zeros(rest, np.float32)])
    sign = np.concatenate([np.full(half, -scale), np.full(half, scale), np.zeros(rest)]).astype(np.float32)
    gain = np.concatenate([np.full(2 * half, scale), np.ones(rest)]).astype(np.float32)
    freqs = jnp.einsum("s,f->sf", positions.astype(jnp.float32), jnp.asarray(lanes))
    return jnp.sin(freqs) * sign, jnp.cos(freqs) * gain


def _rope_partner(x, r):
    """``x`` [.., d] with the two halves of its first ``r`` lanes swapped and
    zeros beyond, in float32: ``x @ P`` for the 0/1 matrix of that permutation.
    A product with 0 or 1 summed in float32 is exact in any dtype (``HIGHEST``:
    a float32 ``x`` is not cut to bfloat16 first; a bfloat16 one is one pass
    either way), and it runs on the MXU, so no lane of ``x`` is moved by a
    slice, a concatenation or a gather of the head dim."""
    d, h = x.shape[-1], r // 2
    perm = np.zeros((d, d), np.float32)
    perm[np.arange(h) + h, np.arange(h)] = 1.0
    perm[np.arange(h), np.arange(h) + h] = 1.0
    return jnp.einsum("...d,de->...e", x, jnp.asarray(perm, x.dtype), precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rotate_full_width(x, sin, cos, r):
    y = x.astype(jnp.float32) * cos[None, :, None, :] + _rope_partner(x, r) * sin[None, :, None, :]
    # lanes at and beyond r are x itself: x * 1 + 0 * 0 would do for finite
    # values and turn an infinity into a NaN
    lane = lax.broadcasted_iota(jnp.int32, (x.shape[-1], ), 0)
    return jnp.where(lane < r, y.astype(x.dtype), x)


@partial(jax.custom_vjp, nondiff_argnums=(3, ))
def _rope_full_width(x, sin, cos, r):
    return _rotate_full_width(x, sin, cos, r)


def _rope_full_width_fwd(x, sin, cos, r):
    return _rotate_full_width(x, sin, cos, r), (sin, cos)


def _rope_full_width_bwd(r, tables, g):
    # the transpose of a rotation is the rotation back: the same expression on
    # the cotangent with the sine negated, so the backward too permutes what
    # arrives in x's dtype and never a float32 product. The tables are
    # functions of the positions alone: no cotangent (None is a zero).
    sin, cos = tables
    return _rotate_full_width(g, -sin, cos, r), None, None


_rope_full_width.defvjp(_rope_full_width_fwd, _rope_full_width_bwd)


def apply_rope(x, sin, cos, rotary_dim=None):
    """x: [B, S, n, d]; sin/cos: ``rope_table``'s pair for ``r = rotary_dim``
    (None: ``d``). The first r dims rotate in half style (GPT-J ``rotary_dim``
    / NeoX ``rotary_pct``), the rest pass through. ``r < d`` alone decides the
    form:

    - ``r == d``: tables ``[S, d/2]``, the two halves of a head rotated and
      concatenated.
    - ``r < d``: tables ``[S, d]`` (signed sine, cosine padded with ones) and
      ``y = x * cos + partner(x) * sin`` at the full width of the head, the
      pass-through lanes selected from ``x`` by a lane iota. No array narrower
      than ``d`` exists in the forward or in the backward (which is the same
      rotation turned back). Float32 arithmetic, cast to ``x.dtype`` at the
      end, as the other form. An infinity anywhere in a head makes that head's
      ROTATED lanes NaN (the partner is a matrix product); a pass-through lane
      keeps its value whatever it is."""
    d = x.shape[-1]
    r = d if rotary_dim is None else rotary_dim
    if r < d:
        assert sin.shape[-1] == d, f"partial rotary ({r} of {d}) takes rope_table's full-width tables, got {sin.shape}"
        return _rope_full_width(x, sin, cos, r)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    sinb = sin[None, :, None, :]
    cosb = cos[None, :, None, :]
    return jnp.concatenate([x1 * cosb - x2 * sinb, x2 * cosb + x1 * sinb], axis=-1).astype(x.dtype)


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (train-short-test-long paper / Bloom
    ``build_alibi_tensor``): pure powers of two for power-of-2 head counts,
    the standard interleave otherwise."""

    def pow2_slopes(n):
        start = 2.0**(-(2.0**-(math.log2(n) - 3)))
        return [start * (start**i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        return np.asarray(pow2_slopes(n_heads), np.float32)
    closest = 2**int(math.floor(math.log2(n_heads)))
    out = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][:n_heads - closest]
    return np.asarray(out + extra, np.float32)


def reference_attention(q, k, v, causal=True, segment_ids=None, window=None, alibi=None):
    """jnp einsum attention — the numerics baseline every Pallas kernel is
    tested against (mirrors reference tests/unit/ops strategy). ``window``:
    sliding-window attention (Mistral) — query at position i sees keys in
    (i - window, i]. ``alibi``: per-head slopes [nq]; adds
    ``slope * (k_pos - q_pos)`` to the scores (Bloom)."""
    B, S, nq, d = q.shape
    nkv = k.shape[2]
    group = nq // nkv
    qf = q.astype(jnp.float32) / math.sqrt(d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, S, nkv, group, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qf, kf)
    if alibi is not None:
        rel = (jnp.arange(S, dtype=jnp.float32)[None, :] - jnp.arange(S, dtype=jnp.float32)[:, None])
        scores = scores + jnp.asarray(alibi, jnp.float32).reshape(nkv, group)[:, :, None, None] * rel[None, None]
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        if window is not None:
            mask = jnp.logical_and(mask, ~jnp.tril(jnp.ones((S, S), bool), k=-int(window)))
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, :, None] == segment_ids[:, None, :]
        scores = jnp.where(seg_mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgst,btkd->bskgd", probs, vf)
    return ctx.reshape(B, S, nq, d).astype(q.dtype)


def mlp_activation(cfg: TransformerConfig, up, gate=None):
    """Shared MLP nonlinearity (swiglu/gelu/relu — relu for OPT-era models;
    relu2, relu squared, for Nemotron-H's)."""
    if cfg.mlp == "swiglu":
        return jax.nn.silu(gate) * up
    if cfg.mlp == "relu":
        return jax.nn.relu(up)
    if cfg.mlp == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.gelu(up)


_SPARSE_LAYOUT_CACHE = {}


def _sparse_attention(cfg: TransformerConfig, q, k, v):
    """Block-sparse training attention, configured by the ds_config
    ``sparse_attention`` block (reference ``SparseSelfAttention`` training
    path). The layout/LUT is a host-side trace-time constant cached per
    (config, heads, S); causality follows the layout's ``attention`` type
    (unidirectional layouts get the token-level causal mask in-kernel)."""
    B, S, nq, d = q.shape
    assert k.shape[2] == nq, "MHA enforced at config time (TransformerConfig.__post_init__)"
    key = (repr(sorted(cfg.sparse_attention.items())), nq, S)
    if key not in _SPARSE_LAYOUT_CACHE:
        from ..ops.sparse_attention import build_sparsity_config, make_layout_lut

        sc = build_sparsity_config(cfg.sparse_attention, nq)
        layout = sc.make_layout(S)
        causal = getattr(sc, "attention", "bidirectional") == "unidirectional"
        if not causal:
            from ..utils.logging import warning_once

            warning_once("sparse_attention layout is BIDIRECTIONAL: next-token training would "
                         "see future tokens. Set attention='unidirectional' in the sparsity "
                         "config unless this is an encoder-style objective.")
        _SPARSE_LAYOUT_CACHE[key] = (sc.block, causal, layout) + make_layout_lut(layout)
    block, causal, layout, lut, nvalid = _SPARSE_LAYOUT_CACHE[key]
    from ..ops.pallas.block_sparse_attention import block_sparse_attention

    ctx = block_sparse_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3), layout, block, causal=causal,
                                 lut=lut, nvalid=nvalid)
    return ctx.transpose(0, 2, 1, 3)


def _multi_device_tpu_mesh():
    """The registered mesh when Pallas kernels will run on more than one TPU
    device, else None (off-TPU the kernels are jnp references GSPMD
    partitions itself; on one device there is nothing to partition)."""
    if jax.default_backend() != "tpu":
        return None
    from ..parallel import groups

    if not groups.is_initialized():
        return None
    mesh = groups.get_mesh()
    return mesh if mesh.size > 1 else None


def _attention(cfg: TransformerConfig, q, k, v):
    """Local attention, [B, S, n, d] in and out. Every branch returns its
    output under the remat name ``attn_out``, given ONCE and in the shape
    ``name_attn_out`` picks for the scan's stack: the flash kernel names its
    own pair (``out`` and the log-sum-exp its backward starts from) inside
    its forward rule, the other branches are named here. A second name on the
    caller's reshape of the kernel's output would make the scan stack it twice."""
    if cfg.sparse_attention is not None:
        return name_attn_out(_sparse_attention(cfg, q, k, v))
    impl = cfg.attention_impl
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    alibi = cfg.positions == "alibi"
    if impl == "flash":
        from ..ops.pallas.flash_attention import flash_attention

        attn = partial(flash_attention, causal=True, window=cfg.sliding_window, alibi=alibi)
        mesh = _multi_device_tpu_mesh()
        if mesh is None:
            return attn(q, k, v)
        # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
        # shard_map"): on a multi-device TPU mesh the kernel runs per shard,
        # batch over the data axes and heads over the tensor-parallel axis
        # (plus the seq axis inside Ulysses, whose all-to-all put it there)
        heads = (MODEL_AXIS, SEQ_AXIS) if cfg.sequence_parallel else (MODEL_AXIS, )
        if alibi and math.prod(mesh.shape[a] for a in heads) > 1:
            raise NotImplementedError("alibi flash attention with sharded heads: the kernel "
                                      "derives slopes from the LOCAL head index")
        spec = P(BATCH_AXES, None, heads, None)
        return jax.shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                             check_vma=False)(q, k, v)
    return name_attn_out(reference_attention(q, k, v, causal=True, window=cfg.sliding_window,
                                             alibi=alibi_slopes(cfg.num_heads) if alibi else None))


def _qwz_target_specs(cfg: TransformerConfig, layer):
    """ZeRO++ qwZ: the per-layer compute layout each big weight is gathered
    into (``layer`` holds per-layer slices — the stacked dim is already
    gone). 1-D vectors and expert-parallel weights are skipped; the spec
    derivation itself is shared with overlap_comm (``_layer_gather_spec``)."""
    rules = partition_rules(cfg)
    out = {}
    for k, v in layer.items():
        if np.ndim(v) < 2:
            continue
        spec = _layer_gather_spec(rules, k, np.ndim(v))
        if spec is not None:
            out[k] = spec
    return out


def _layer_gather_spec(rules: PartitionRules, key: str, per_layer_ndim: int):
    """Gathered compute layout for ONE stacked-blocks leaf: its TP spec with
    the stacked-L/pipe dim dropped — replicated over the ZeRO data axes,
    still sharded over 'model'. Returns None when the spec's data axes are
    expert parallelism (MoE expert weights), not a ZeRO shard to gather.
    Shared by the qwZ and overlap_comm planes so their layouts cannot
    drift from ``partition_rules`` or from each other."""
    full = rules.spec_for(f"blocks/{key}", per_layer_ndim + 1)
    entries = list(full)[1:]  # drop the stacked-L/pipe dim
    flat = [a for e in entries if e is not None
            for a in (e if isinstance(e, (tuple, list)) else (e, ))]
    return None if DATA_AXIS in flat else P(*entries)


def _zero3_gather_specs(cfg: TransformerConfig, blocks):
    """Per-leaf gathered layouts for the explicit overlap_comm schedule
    (stacked [L, ...] input; None entries are left unconstrained)."""
    rules = partition_rules(cfg)
    return {k: _layer_gather_spec(rules, k, np.ndim(v) - 1) for k, v in blocks.items()}


def _qwz_layer_view(cfg: TransformerConfig, layer):
    """Route the stage-3 per-layer weight gathers through int8
    (ops/pallas/quant.quantized_gather_ste)."""
    from ..parallel import groups
    from ..ops.pallas.quant import quantized_gather_ste
    from ..utils.logging import logger

    if not groups.is_initialized():
        return layer
    mesh = groups.get_mesh()
    out = dict(layer)
    for k, spec in _qwz_target_specs(cfg, layer).items():
        try:
            out[k] = quantized_gather_ste(out[k], spec, mesh)
        except (ValueError, jax.errors.JaxRuntimeError, RuntimeError) as e:
            # e.g. manual mesh axes inside shard_map: keep the plain view,
            # but say so — a silent fp32 fallback would defeat the flag
            logger.warning(f"qwZ: falling back to unquantized gather for blocks/{k}: {e}")
    return out


def _attn_branch(cfg: TransformerConfig, layer, h, sin, cos):
    """Attention sub-block on pre-normed input ``h`` [B, S, H]."""
    dt = cfg.dtype
    B, S, H = h.shape
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope(scopes.ATTN_PROJ):
        q = jnp.einsum("bsh,hd->bsd", h, layer["wq"].astype(dt))
        k = jnp.einsum("bsh,hd->bsd", h, layer["wk"].astype(dt))
        v = jnp.einsum("bsh,hd->bsd", h, layer["wv"].astype(dt))
        if cfg.qkv_bias_enabled:
            q = q + layer["bq"].astype(dt)
            k = k + layer["bk"].astype(dt)
            v = v + layer["bv"].astype(dt)
    q = q.reshape(B, S, nq, d)
    k = k.reshape(B, S, nkv, d)
    v = v.reshape(B, S, nkv, d)
    if cfg.positions == "rotary":
        q = apply_rope(q, sin, cos, cfg.rotary_dim)
        k = apply_rope(k, sin, cos, cfg.rotary_dim)

    if cfg.sequence_parallel:
        if cfg.sequence_parallel_impl == "ring":
            if cfg.sliding_window is not None:
                raise NotImplementedError(
                    "sliding_window + ring attention is not supported yet; use "
                    "sequence_parallel_impl='ulysses' (its local attention honors the window)")
            if cfg.positions == "alibi":
                raise NotImplementedError("alibi + ring attention is not supported yet; use ulysses")
            if cfg.sparse_attention is not None:
                raise NotImplementedError("sparse_attention + ring attention is not supported; "
                                          "use sequence_parallel_impl='ulysses' (its local "
                                          "attention routes through the sparse kernel)")
            from ..parallel import groups
            from ..parallel.mesh import mesh_axis_size
            from ..sequence.ring import ring_attention_gspmd

            # degrade to plain attention when no mesh registry is live (same
            # graceful behavior as ulysses' sharding constraints outside a mesh)
            if groups.is_initialized() and mesh_axis_size(groups.get_mesh(), SEQ_AXIS) > 1:
                ctx = name_attn_out(ring_attention_gspmd(q, k, v, groups.get_mesh(), causal=True))
            else:
                ctx = _attention(cfg, q, k, v)
        else:
            if cfg.positions == "alibi":
                # ulysses shards the SEQUENCE dim around local attention: the
                # local attention sees global positions only via rope tables;
                # alibi's relative bias would use local indices — wrong
                raise NotImplementedError("alibi + ulysses sequence parallel is not supported yet")
            from ..sequence.layer import ulysses_attention_gspmd

            ctx = ulysses_attention_gspmd(partial(_attention, cfg), q, k, v)
    else:
        ctx = _attention(cfg, q, k, v)
    # ctx arrives named ``attn_out`` by the path that computed it (see
    # _attention). Under remat_policy="save_only_these_names(attn_out)" the
    # block saves its input, that output and, for the flash kernel, its
    # [B, nq, S] float32 log-sum-exp, so no attention forward runs in the
    # backward; the norm, the q/k/v projections, rope (and Ulysses'
    # all-to-alls), this reshape and the whole MLP branch are recomputed.
    ctx = ctx.reshape(B, S, nq * d)
    with jax.named_scope(scopes.ATTN_OUT):
        attn_out = jnp.einsum("bsd,dh->bsh", ctx, layer["wo"].astype(dt))
        if cfg.use_bias:
            attn_out = attn_out + layer["bo"].astype(dt)
    return attn_out


def _mlp_branch(cfg: TransformerConfig, layer, h, rng=None, constrain=True):
    """MLP (dense or MoE) sub-block on pre-normed input ``h``. Returns
    (out, moe_aux_loss)."""
    dt = cfg.dtype
    if cfg.moe_num_experts > 0:
        return _moe_mlp(cfg, layer, h, rng, constrain=constrain)
    up = jnp.einsum("bsh,hf->bsf", h, layer["w_up"].astype(dt))
    if cfg.use_bias:
        up = up + layer["b_up"].astype(dt)
    if cfg.mlp == "swiglu":
        gate = jnp.einsum("bsh,hf->bsf", h, layer["w_gate"].astype(dt))
        act = mlp_activation(cfg, up, gate)
    else:
        act = mlp_activation(cfg, up)
    down = jnp.einsum("bsf,fh->bsh", act, layer["w_down"].astype(dt))
    if cfg.use_bias:
        down = down + layer["b_down"].astype(dt)
    return down, jnp.zeros([], jnp.float32)


def _block(cfg: TransformerConfig, x, layer, sin, cos, rng=None, constrain=True):
    """One transformer block; ``layer`` holds this layer's slice of the
    stacked arrays. Returns (x, moe_aux_loss). ``constrain=False`` disables
    GSPMD sharding constraints (for use inside shard_map pipeline stages).
    ``parallel_residual`` (GPT-J/NeoX/Falcon): attention and MLP both read
    the block input and add jointly; ``shared_ln`` reuses ln1 for the MLP."""
    if cfg.quantized_weights and constrain:
        layer = _qwz_layer_view(cfg, layer)
    # the parts of a step as a device trace is read by (monitor/scopes.py): each norm and residual add lies
    # with the matmuls it is fused into, and the attention branch names its projections inside ``mixer``
    mlp_part = scopes.MOE if cfg.moe_num_experts > 0 else scopes.MLP
    with jax.named_scope(scopes.ATTN_PROJ):
        h1 = _norm(x, layer["ln1_scale"], layer.get("ln1_bias"), cfg.norm, cfg.norm_eps)
    with jax.named_scope(scopes.MIXER):
        attn_out = _attn_branch(cfg, layer, h1, sin, cos)
    if cfg.parallel_residual:
        with jax.named_scope(mlp_part):
            h2 = h1 if cfg.shared_ln else _norm(x, layer["ln2_scale"], layer.get("ln2_bias"),
                                                cfg.norm, cfg.norm_eps)
            mlp_out, l_aux = _mlp_branch(cfg, layer, h2, rng, constrain=constrain)
            x = x + attn_out + mlp_out
        return _activation_constraint(cfg, x, enabled=constrain), l_aux
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + attn_out
    with jax.named_scope(mlp_part):
        h2 = _norm(x, layer["ln2_scale"], layer.get("ln2_bias"), cfg.norm, cfg.norm_eps)
        mlp_out, l_aux = _mlp_branch(cfg, layer, h2, rng, constrain=constrain)
        x = x + mlp_out
    return _activation_constraint(cfg, x, enabled=constrain), l_aux


def _moe_mlp(cfg: TransformerConfig, layer, h, rng=None, constrain=True):
    """MoE FFN. Dropless top-k (``cfg.moe_dropless``) runs the grouped
    ragged matmul; otherwise GSPMD form: per-row top-1/top-2 capacity gating
    (moe/sharded_moe.py math), dispatch to [B, E, C, M] slots, flip the sharding from batch-over-data to
    experts-over-data (XLA lowers the constraint boundary to the dispatch
    all-to-all of the reference's ``_AllToAll``), expert FFN, flip back,
    combine."""
    from ..moe.sharded_moe import top1gating, top2gating, multiplicative_jitter

    dt = cfg.dtype
    B, S, H = h.shape
    E = cfg.moe_num_experts
    if cfg.moe_dropless:
        # top-k of the softmax over all experts, nothing dropped: the kept
        # set has no [E, C] shape, so the slots go sorted by expert through
        # the grouped matmul; no auxiliary loss is defined for it here
        from ..moe.grouped import grouped_moe_ffn, route_topk

        x = h.reshape(B * S, H)
        top_idx, top_w = route_topk(x, layer["gate_wg"], cfg.moe_top_k, cfg.moe_norm_topk_prob)
        y = grouped_moe_ffn(x, top_idx, top_w.astype(dt), layer["moe_wi"], layer["moe_wo"],
                            wg=layer.get("moe_wg") if cfg.mlp == "swiglu" else None,
                            activation=lambda up, gate: mlp_activation(cfg, up, gate))
        return y.reshape(B, S, H), jnp.zeros([], jnp.float32)
    gate_in = h.astype(jnp.float32)
    if cfg.moe_noisy_gate_policy == "Jitter" and rng is not None:
        rng, jit_key = jax.random.split(rng)
        gate_in = multiplicative_jitter(gate_in, jit_key)
    logits = jnp.einsum("bsh,he->bse", gate_in, layer["gate_wg"].astype(jnp.float32))

    def gate_row(lg, key):
        if cfg.moe_top_k == 1:
            return top1gating(lg, cfg.moe_capacity_factor, cfg.moe_min_capacity,
                              noisy_gate_policy=cfg.moe_noisy_gate_policy, rng=key,
                              use_rts=key is not None)[:3]
        return top2gating(lg, cfg.moe_capacity_factor, cfg.moe_min_capacity, rng=key)[:3]

    if rng is not None:
        keys = jax.random.split(rng, B)
        l_aux, combine, dispatch = jax.vmap(gate_row)(logits, keys)
    else:
        l_aux, combine, dispatch = jax.vmap(lambda lg: gate_row(lg, None))(logits)

    dispatched = jnp.einsum("bsec,bsm->becm", dispatch.astype(dt), h)
    if constrain:
        try:
            dispatched = lax.with_sharding_constraint(dispatched, P(None, DATA_AXIS, None, None))
        except (ValueError, jax.errors.JaxRuntimeError, RuntimeError, NameError):
            pass
    up = jnp.einsum("becm,emf->becf", dispatched, layer["moe_wi"].astype(dt))
    gate = jnp.einsum("becm,emf->becf", dispatched, layer["moe_wg"].astype(dt)) if cfg.mlp == "swiglu" else None
    hmid = mlp_activation(cfg, up, gate)
    expert_out = jnp.einsum("becf,efm->becm", hmid, layer["moe_wo"].astype(dt))
    if constrain:
        try:
            expert_out = lax.with_sharding_constraint(expert_out, P(BATCH_AXES, None, None, None))
        except (ValueError, jax.errors.JaxRuntimeError, RuntimeError, NameError):
            pass
    out = jnp.einsum("bsec,becm->bsm", combine.astype(dt), expert_out)
    return out, jnp.mean(l_aux)


def _activation_constraint(cfg: TransformerConfig, x, enabled=True):
    """Pin activation layout [B, S, H]: batch over data, sequence over seq."""
    if not enabled:
        return x
    try:
        return lax.with_sharding_constraint(x, P(BATCH_AXES, SEQ_AXIS if cfg.sequence_parallel else None, None))
    except (ValueError, jax.errors.JaxRuntimeError, RuntimeError, NameError):
        return x


def _remat_policy(name: str):
    """Resolve a remat policy name. Supports every ``jax.checkpoint_policies``
    attribute plus ``"save_only_these_names(a,b,...)"`` for checkpoint_name-
    tagged values (e.g. ``attn_out``)."""
    if name.startswith("save_only_these_names(") and name.endswith(")"):
        names = [n.strip() for n in name[len("save_only_these_names("):-1].split(",") if n.strip()]
        return jax.checkpoint_policies.save_only_these_names(*names)
    policy = getattr(jax.checkpoint_policies, name, None)
    if policy is None:
        raise ValueError(f"unknown remat_policy {name!r}: expected an attribute of "
                         f"jax.checkpoint_policies or 'save_only_these_names(a,b,...)'")
    return policy


def _refuse_mixed_layers(cfg: TransformerConfig, what: str):
    """The whole-sequence paths scan ONE block over the stacked layers, with
    one window, one rope table and one MLP kind, and know the block the
    training families share; a model whose layers differ, or whose block has
    what ``cfg.unscannable`` names, is served by the ragged path
    (inference/v2), which unrolls them."""
    if cfg.unscannable:
        raise NotImplementedError(
            f"{what}: the scanned block has one window, one rope and one MLP kind, and this model has "
            f"{'; '.join(cfg.unscannable)}. Serve this model through InferenceEngineV2 (ragged_forward)")


def forward_hidden(cfg: TransformerConfig, params: Dict[str, Any], input_ids: jax.Array, rng=None,
                   pld_theta=None):
    """Token ids [B, S] → (final-norm hidden [B, S, H], moe_aux_loss).
    Split from :func:`forward_with_aux` so the chunked-CE long-context path
    can unembed sequence chunks without materializing [B, S, V] logits.

    ``pld_theta``: progressive layer dropping (reference
    ``runtime/progressive_layer_drop.py``) — traced keep-rate scalar;
    requires ``rng``. Each layer is wrapped in ``lax.cond`` so dropped
    layers are genuinely skipped at runtime (the training-time saving)."""
    _refuse_mixed_layers(cfg, "forward_hidden")
    dt = cfg.dtype
    B, S = input_ids.shape
    with jax.named_scope(scopes.EMBED):
        x = params["embed"]["embedding"].astype(dt)[input_ids]
        if cfg.positions == "learned":
            x = x + params["pos_embed"]["embedding"].astype(dt)[:S][None]
        if cfg.embed_layernorm:
            en = params["embed_norm"]
            x = _norm(x, en["scale"], en.get("bias"), cfg.norm, cfg.norm_eps)
        x = _activation_constraint(cfg, x)

    positions = jnp.arange(S)
    with jax.named_scope(scopes.MIXER):
        sin, cos = rope_table(cfg, positions) if cfg.positions == "rotary" else (None, None)

    block_fn = partial(_block, cfg)
    if cfg.remat:
        block_fn = jax.checkpoint(block_fn, policy=_remat_policy(cfg.remat_policy),
                                  static_argnums=())

    pld_keep = None
    if pld_theta is not None:
        assert rng is not None, "progressive layer drop needs an rng"
        from ..runtime.progressive_layer_drop import layer_keep_probs

        rng, pld_rng = jax.random.split(rng)
        pld_keep = jax.random.bernoulli(pld_rng, layer_keep_probs(cfg.num_layers, pld_theta))

    use_layer_keys = cfg.moe_num_experts > 0 and rng is not None
    layer_keys = jax.random.split(rng, cfg.num_layers) if use_layer_keys else None

    # Explicit overlap_comm schedule (ZeRO-3): double-buffer the gathered
    # next-layer params in the scan carry. Layer l+1's all-gather (a
    # resharding constraint, routed through comm.zero3_params_allgather so
    # the trace bus / in-flight table see it) is issued BEFORE layer l's
    # compute in program order — the explicit analog of the reference's
    # overlap_comm side stream. Values are untouched (same slices, same
    # math), so the loss is bit-identical to the implicit path. PLD drops
    # layers at runtime (prefetching a dropped layer's params would waste
    # the gather) and qwZ owns its own quantized gather — both keep the
    # plain scan.
    if cfg.overlap_gather and pld_keep is None and not cfg.quantized_weights:
        from ..parallel import groups as _groups

        mesh = _groups.get_mesh() if _groups.is_initialized() else None
        specs = _zero3_gather_specs(cfg, params["blocks"]) if mesh is not None else None
        from ..comm.comm import zero3_params_allgather

        blocks = params["blocks"]
        L = cfg.num_layers

        def fetch(i):
            layer = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), blocks)
            return zero3_params_allgather(layer, specs=specs, mesh=mesh)

        def overlap_body(carry, xs):
            x, cur = carry
            if use_layer_keys:
                i, key = xs
            else:
                i, key = xs, None
            # last iteration: no next layer — reuse cur instead of issuing a
            # redundant gather whose result the scan would discard
            nxt = lax.cond(i + 1 < L, lambda: fetch(jnp.minimum(i + 1, L - 1)), lambda: cur)
            y, aux = block_fn(x, cur, sin, cos, key)
            return (y, nxt), jnp.asarray(aux, jnp.float32)

        idx = jnp.arange(L, dtype=jnp.int32)
        xs = (idx, layer_keys) if use_layer_keys else idx
        (x, _), l_auxs = lax.scan(overlap_body, (x, fetch(jnp.int32(0))), xs)
        with jax.named_scope(scopes.LM_HEAD):
            x = _norm(x, params["final_norm"]["scale"], params["final_norm"].get("bias"),
                      cfg.norm, cfg.norm_eps)
        return x, jnp.sum(l_auxs)

    xs_list = [params["blocks"]]
    if use_layer_keys:
        xs_list.append(layer_keys)
    if pld_keep is not None:
        xs_list.append(pld_keep)

    def scan_body(carry, xs):
        items = list(xs) if isinstance(xs, tuple) else [xs]
        layer = items.pop(0)
        key = items.pop(0) if use_layer_keys else None
        if pld_keep is None:
            return block_fn(carry, layer, sin, cos, key)
        keep = items.pop(0)

        def run(x):
            y, aux = block_fn(x, layer, sin, cos, key)
            return y, jnp.asarray(aux, jnp.float32)

        def skip(x):
            return x, jnp.zeros((), jnp.float32)

        return lax.cond(keep, run, skip, carry)

    x, l_auxs = lax.scan(scan_body, x, tuple(xs_list) if len(xs_list) > 1 else xs_list[0])
    with jax.named_scope(scopes.LM_HEAD):
        x = _norm(x, params["final_norm"]["scale"], params["final_norm"].get("bias"), cfg.norm, cfg.norm_eps)
    return x, jnp.sum(l_auxs)


def _unembed(cfg: TransformerConfig, params, x):
    """Final hidden [..., H] → vocabulary logits [..., V] in fp32."""
    dt = cfg.dtype
    with jax.named_scope(scopes.LM_HEAD):
        if cfg.tie_embeddings:
            logits = jnp.einsum("...h,vh->...v", x, params["embed"]["embedding"].astype(dt))
        else:
            logits = jnp.einsum("...h,hv->...v", x, params["lm_head"]["kernel"].astype(dt))
            if "bias" in params["lm_head"]:  # GPT-J style biased unembedding
                logits = logits + params["lm_head"]["bias"].astype(logits.dtype)
        return logits.astype(jnp.float32)


def forward_with_aux(cfg: TransformerConfig, params: Dict[str, Any], input_ids: jax.Array, rng=None,
                     pld_theta=None):
    """Token ids [B, S] → (logits [B, S, V], moe_aux_loss)."""
    x, moe_aux = forward_hidden(cfg, params, input_ids, rng, pld_theta=pld_theta)
    return _unembed(cfg, params, x), moe_aux


def forward(cfg: TransformerConfig, params: Dict[str, Any], input_ids: jax.Array) -> jax.Array:
    """Token ids [B, S] → logits [B, S, V]."""
    return forward_with_aux(cfg, params, input_ids)[0]


# ---------------------------------------------------------------------------
# KV-cache inference path (v1 inference engine; reference
# ``ops/transformer/inference`` fused qkv+rotary+kv-append+softmax_context)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: TransformerConfig, batch_size: int, max_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype), "length": jnp.zeros([], jnp.int32)}


def _cached_attention(cfg, q, ck, cv, q_pos0, cache_len_total):
    """q: [B, T, nq, d] at absolute positions q_pos0..q_pos0+T-1; ck/cv:
    [B, Smax, nkv, d] (positions < cache_len_total are valid).

    On TPU with kernel-friendly shapes the dense cache is viewed as a paged
    pool with an identity block table and handed to the fused paged-attention
    decode kernel (the v1 analog of the reference's fused softmax_context,
    ``csrc/transformer/inference/csrc/softmax.cu``) — one kernel per step
    instead of the materialized [B, nq, T, Smax] score tensor."""
    B, T, nq, d = q.shape
    Smax = ck.shape[1]
    nkv = ck.shape[2]
    group = nq // nkv
    if _use_fused_decode(cfg, nq, d, Smax):
        from ..ops.pallas.paged_attention import paged_attention

        bs = 128
        nb = Smax // bs
        kp = ck.reshape(B * Smax, nkv, d)
        vp = cv.reshape(B * Smax, nkv, d)
        tables = (jnp.arange(B, dtype=jnp.int32)[:, None] * nb
                  + jnp.arange(nb, dtype=jnp.int32)[None, :])
        seq_idx = jnp.repeat(jnp.arange(B, dtype=jnp.int32), T)
        pos = jnp.tile(q_pos0 + jnp.arange(T, dtype=jnp.int32), B)
        slopes = alibi_slopes(nq) if cfg.positions == "alibi" else None
        ctx = paged_attention(q.reshape(B * T, nq, d), kp, vp, tables, seq_idx, pos, bs,
                              window=cfg.sliding_window, alibi=slopes)
        return ctx.reshape(B, T, nq * d).astype(q.dtype)
    qf = q.astype(jnp.float32).reshape(B, T, nkv, group, d) / math.sqrt(d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qf, ck.astype(jnp.float32))
    k_pos = jnp.arange(Smax)[None, None, None, None, :]
    q_pos = (q_pos0 + jnp.arange(T))[None, None, None, :, None]
    if cfg.positions == "alibi":
        slopes = jnp.asarray(alibi_slopes(nq), jnp.float32).reshape(nkv, group)
        scores = scores + slopes[None, :, :, None, None] * (k_pos - q_pos).astype(jnp.float32)
    mask = (k_pos <= q_pos) & (k_pos < cache_len_total)
    if cfg.sliding_window is not None:
        mask = mask & (q_pos - k_pos < cfg.sliding_window)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgts,bskd->btkgd", probs, cv.astype(jnp.float32))
    return ctx.reshape(B, T, nq * d).astype(q.dtype)


def _use_fused_decode(cfg, nq, d, Smax) -> bool:
    """Engage the paged decode kernel for the dense v1 cache: TPU backend,
    MXU-friendly shapes, and no tensor parallelism (a pallas call on
    model-sharded pools would make XLA replicate them)."""
    if cfg.attention_impl == "reference":
        return False
    try:
        import jax as _jax

        if _jax.default_backend() != "tpu":
            return False
        from ..parallel import groups
        from ..parallel.mesh import MODEL_AXIS, mesh_axis_size

        if groups.is_initialized() and mesh_axis_size(groups.get_mesh(), MODEL_AXIS) > 1:
            return False
    except Exception:
        return False
    return nq >= 8 and d % 128 == 0 and Smax % 128 == 0


def forward_with_cache(cfg: TransformerConfig, params, input_ids, cache):
    """Prefill/decode step: consumes tokens at positions [len, len+T), appends
    their k/v into the cache and returns (logits [B, T, V], new_cache)."""
    if cfg.sparse_attention is not None:
        # serving a sparse-trained model with dense cached attention would
        # silently use a distribution the model never saw — reject loudly
        # (same policy as the other unsupported combinations)
        raise NotImplementedError("sparse_attention serving is not implemented: the KV-cache "
                                  "decode applies dense attention; unset sparse_attention "
                                  "for inference")
    _refuse_mixed_layers(cfg, "forward_with_cache")
    dt = cfg.dtype
    B, T = input_ids.shape
    start = cache["length"]
    x = params["embed"]["embedding"].astype(dt)[input_ids]
    if cfg.positions == "learned":
        pos_table = params["pos_embed"]["embedding"].astype(dt)
        x = x + jax.lax.dynamic_slice_in_dim(pos_table, start, T, axis=0)[None]
    if cfg.embed_layernorm:
        en = params["embed_norm"]
        x = _norm(x, en["scale"], en.get("bias"), cfg.norm, cfg.norm_eps)
    positions = start + jnp.arange(T)
    sin, cos = rope_table(cfg, positions) if cfg.positions == "rotary" else (None, None)
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def scan_body(carry, layer_and_cache):
        x = carry
        layer, ck, cv = layer_and_cache
        h1 = _norm(x, layer["ln1_scale"], layer.get("ln1_bias"), cfg.norm, cfg.norm_eps)
        q = jnp.einsum("bsh,hd->bsd", h1, layer["wq"].astype(dt))
        k = jnp.einsum("bsh,hd->bsd", h1, layer["wk"].astype(dt))
        v = jnp.einsum("bsh,hd->bsd", h1, layer["wv"].astype(dt))
        if cfg.qkv_bias_enabled:
            q, k, v = q + layer["bq"].astype(dt), k + layer["bk"].astype(dt), v + layer["bv"].astype(dt)
        q = q.reshape(B, T, nq, d)
        k = k.reshape(B, T, nkv, d)
        v = v.reshape(B, T, nkv, d)
        if cfg.positions == "rotary":
            q = apply_rope(q, sin, cos, cfg.rotary_dim)
            k = apply_rope(k, sin, cos, cfg.rotary_dim)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), start, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), start, axis=1)
        ctx = _cached_attention(cfg, q, ck, cv, start, start + T)
        attn_out = jnp.einsum("bsd,dh->bsh", ctx, layer["wo"].astype(dt)) + \
            (layer["bo"].astype(dt) if cfg.use_bias else 0.0)

        def mlp(h):
            # deterministic gating at inference (rng=None)
            return _mlp_branch(cfg, layer, h, rng=None)[0]

        if cfg.parallel_residual:
            h2 = h1 if cfg.shared_ln else _norm(x, layer["ln2_scale"], layer.get("ln2_bias"),
                                                cfg.norm, cfg.norm_eps)
            return x + attn_out + mlp(h2), (ck, cv)
        x = x + attn_out
        h2 = _norm(x, layer["ln2_scale"], layer.get("ln2_bias"), cfg.norm, cfg.norm_eps)
        return x + mlp(h2), (ck, cv)

    x, (new_k, new_v) = lax.scan(scan_body, x, (params["blocks"], cache["k"], cache["v"]))
    x = _norm(x, params["final_norm"]["scale"], params["final_norm"].get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsh,vh->bsv", x, params["embed"]["embedding"].astype(dt))
    else:
        logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"]["kernel"].astype(dt))
        if "bias" in params["lm_head"]:
            logits = logits + params["lm_head"]["bias"].astype(logits.dtype)
    new_cache = {"k": new_k, "v": new_v, "length": start + T}
    return logits.astype(jnp.float32), new_cache


def _ce_aux(batch, input_ids):
    """Normalize a batch into the CE aux dict consumed by ``_ce_loss``."""
    aux = {}
    if isinstance(batch, dict) and "labels" in batch:
        aux["labels"] = batch["labels"]
    else:
        aux["shift_ids"] = input_ids
    if isinstance(batch, dict) and "loss_mask" in batch:
        aux["loss_mask"] = batch["loss_mask"]
    return aux


def _ce_loss(logits, aux, use_onehot=False):
    """Next-token cross entropy. ``aux``: {'labels'} or {'shift_ids'} plus
    optional 'loss_mask'. ``use_onehot`` contracts against a one-hot instead
    of gathering: the gather op makes XLA's SPMD partitioner CHECK-fail when
    the vocab dim is sharded over an auto axis inside a manual-subset
    shard_map (the 1F1B pipeline); the einsum partitions cleanly (the vocab
    sum lowers to a psum over 'model')."""
    if "labels" in aux:
        shift_logits, labels = logits, aux["labels"]
    else:
        shift_logits, labels = logits[..., :-1, :], aux["shift_ids"][..., 1:]
    logp = jax.nn.log_softmax(shift_logits, axis=-1)
    if use_onehot:
        onehot = (labels[..., None] == jnp.arange(logp.shape[-1])).astype(logp.dtype)
        token_ll = jnp.einsum("...v,...v->...", logp, onehot)
    else:
        token_ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if "loss_mask" in aux:
        mask = aux["loss_mask"][..., :token_ll.shape[-1]].astype(jnp.float32)
        return -(token_ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return -token_ll.mean()


def _stage_scan_fn(cfg: TransformerConfig, with_aux: bool = False):
    """One pipeline stage: scan this stage's contiguous layer slice (shared
    by the GPipe and 1F1B executors so the schedules cannot diverge).
    ``with_aux`` (MoE models): also return the stage's summed load-balancing
    loss so the executors can thread it into the total loss."""
    if cfg.moe_num_experts > 0 and cfg.moe_noisy_gate_policy:
        # the stage fn runs gating with rng=None, which would silently turn
        # Jitter/RSample off — pipeline and serial runs would optimize
        # different objectives (same stance as PLD+pipeline, engine.py)
        raise NotImplementedError(
            f"moe_noisy_gate_policy={cfg.moe_noisy_gate_policy!r} does not compose with "
            "pipeline parallelism yet (stage executors run gating without an rng); "
            "disable the noisy gate or run without the pipe axis")

    _refuse_mixed_layers(cfg, "pipeline stages")

    def stage_fn(blocks_local, xb, sin, cos):
        def body(carry, layer):
            y, aux = _block(cfg, carry, layer, sin, cos, None, constrain=False)
            return y, jnp.asarray(aux, jnp.float32)

        y, auxs = lax.scan(body, xb, blocks_local)
        if with_aux:
            return y, jnp.sum(auxs)
        return y

    return stage_fn


def _chunked_ce_loss(cfg: TransformerConfig, params, h, aux, chunk: int):
    """Sequence-chunked next-token CE over final hidden ``h`` [B, S, H].

    Each chunk's logits are computed inside ``jax.checkpoint``, so neither
    forward nor backward ever holds more than one [B, chunk, V] logits
    slice — the memory that caps long-context training. Numerically
    identical to ``_ce_loss`` (same masked-mean semantics)."""
    if "labels" in aux:
        h_eff, labels = h, aux["labels"]
    else:
        h_eff, labels = h[:, :-1], aux["shift_ids"][..., 1:]
    B, Sp, H = h_eff.shape
    mask = aux.get("loss_mask")
    mask = jnp.ones((B, Sp), jnp.float32) if mask is None else \
        mask[..., :Sp].astype(jnp.float32)
    pad = (-Sp) % chunk
    if pad:
        h_eff = jnp.pad(h_eff, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (Sp + pad) // chunk
    hc = h_eff.reshape(B, n, chunk, H).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_fn(h_c, l_c, m_c):
        logp = jax.nn.log_softmax(_unembed(cfg, params, h_c), axis=-1)
        ll = jnp.take_along_axis(logp, l_c[..., None], axis=-1)[..., 0]
        return (ll * m_c).sum()

    def scan_body(tot, xs):
        return tot + chunk_fn(*xs), None

    total_ll, _ = lax.scan(scan_body, jnp.float32(0.0), (hc, lc, mc))
    return -total_ll / jnp.maximum(mask.sum(), 1.0)


def loss_fn(cfg: TransformerConfig, params, batch, rng=None):
    """Next-token cross entropy (+ MoE aux loss). ``batch``: dict with
    'input_ids' [B, S] and optional 'labels' (defaults to shifted input) and
    'loss_mask'. ``cfg.loss_chunk`` routes through the sequence-chunked CE
    (logits never fully materialized)."""
    input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
    with jax.named_scope(scopes.LOSS):
        aux_d = _ce_aux(batch, input_ids)
    pld_theta = batch.get("pld_theta") if isinstance(batch, dict) else None
    if cfg.loss_chunk and input_ids.shape[1] > cfg.loss_chunk:
        h, moe_aux = forward_hidden(cfg, params, input_ids, rng, pld_theta=pld_theta)
        with jax.named_scope(scopes.LOSS):  # (its chunks' unembedding stays ``lm_head``, inside)
            ce = _chunked_ce_loss(cfg, params, h, aux_d, int(cfg.loss_chunk))
    else:
        logits, moe_aux = forward_with_aux(cfg, params, input_ids, rng, pld_theta=pld_theta)
        with jax.named_scope(scopes.LOSS):
            ce = _ce_loss(logits, aux_d)
    with jax.named_scope(scopes.LOSS):
        aux = cfg.moe_aux_loss_coef * moe_aux if cfg.moe_num_experts > 0 else 0.0
        return ce + aux


def pipeline_loss_fn(cfg: TransformerConfig, params, batches, rng=None, *, mesh, num_stages: int):
    """Pipelined loss over microbatches [M, b, S] (runtime/pipe/spmd.py).

    Embedding and head run replicated over the pipe axis; the L blocks are
    split into ``num_stages`` contiguous slices (blocks dim 0 is sharded over
    'pipe' — see partition_rules) and executed in a compiled fill/drain loop
    with ppermute handoffs. jax.grad through this function generates the
    backward pipeline automatically.
    """
    from ..runtime.pipe.spmd import pipeline_apply

    ids = batches["input_ids"] if isinstance(batches, dict) else batches
    M, B, S = ids.shape
    dt = cfg.dtype
    assert cfg.num_layers % num_stages == 0, (
        f"num_layers {cfg.num_layers} must divide evenly into {num_stages} pipeline stages")
    moe = cfg.moe_num_experts > 0

    x = params["embed"]["embedding"].astype(dt)[ids]  # [M, B, S, H]
    if cfg.positions == "learned":
        x = x + params["pos_embed"]["embedding"].astype(dt)[:S][None, None]
    sin, cos = rope_table(cfg, jnp.arange(S)) if cfg.positions == "rotary" else (
        jnp.zeros((S, 1)), jnp.zeros((S, 1)))

    outs = pipeline_apply(_stage_scan_fn(cfg, with_aux=moe), params["blocks"], x, sin, cos,
                          mesh=mesh, num_stages=num_stages,
                          remat=True, with_aux=moe)  # [M, B, S, H]
    moe_aux = jnp.zeros([], jnp.float32)
    if moe:
        outs, aux_total = outs
        moe_aux = cfg.moe_aux_loss_coef * aux_total / M  # mean over microbatches
    h = _norm(outs, params["final_norm"]["scale"], params["final_norm"].get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("mbsh,vh->mbsv", h, params["embed"]["embedding"].astype(dt))
    else:
        logits = jnp.einsum("mbsh,hv->mbsv", h, params["lm_head"]["kernel"].astype(dt))
    logits = logits.astype(jnp.float32)
    if isinstance(batches, dict) and "labels" in batches:
        shift_logits, labels = logits, batches["labels"]
    else:
        shift_logits, labels = logits[:, :, :-1], ids[:, :, 1:]
    logp = jax.nn.log_softmax(shift_logits, axis=-1)
    token_ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if isinstance(batches, dict) and "loss_mask" in batches:
        # per-microbatch masked mean, then mean over microbatches — identical
        # weighting to the non-pipeline path (loss_fn averaged over gas), so
        # enabling pipe does not change the training objective
        mask = batches["loss_mask"][:, :, :token_ll.shape[2]].astype(jnp.float32)
        per_mb = -(token_ll * mask).sum(axis=(1, 2)) / jnp.maximum(mask.sum(axis=(1, 2)), 1.0)
        return per_mb.mean() + moe_aux
    return -token_ll.mean() + moe_aux


def pipeline_loss_fn_1f1b(cfg: TransformerConfig, params, batches, rng=None, *, mesh, num_stages: int):
    """1F1B pipelined loss over microbatches [M, b, S] (runtime/pipe/spmd.py
    ``pipeline_1f1b`` — the reference ``TrainSchedule`` schedule.py:189
    compiled into one program).

    Because 1F1B interleaves backward work into the forward loop, gradients
    are produced by the pipeline itself; a ``custom_vjp`` hands them to
    ``jax.grad`` so the engine's ``jax.grad(scaled_loss)`` contract is
    unchanged. The embedding runs outside the pipeline (GSPMD) and its VJP is
    chained through the pipeline's d(injected activations); the loss head
    (final norm + LM head + CE) runs inside at the last stage, per tick.
    """
    from ..runtime.pipe.spmd import pipeline_1f1b

    ids = batches["input_ids"] if isinstance(batches, dict) else batches
    M, B, S = ids.shape
    dt = cfg.dtype
    assert cfg.num_layers % num_stages == 0, (
        f"num_layers {cfg.num_layers} must divide evenly into {num_stages} pipeline stages")
    moe = cfg.moe_num_experts > 0

    sin, cos = rope_table(cfg, jnp.arange(S)) if cfg.positions == "rotary" else (
        jnp.zeros((S, 1)), jnp.zeros((S, 1)))

    head_keys = ["final_norm"] + (["embed"] if cfg.tie_embeddings else ["lm_head"])
    aux = _ce_aux(batches, ids)

    def head_fn(hp, y, aux_mb):
        h = _norm(y, hp["final_norm"]["scale"], hp["final_norm"].get("bias"), cfg.norm, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsh,vh->bsv", h, hp["embed"]["embedding"].astype(dt))
        else:
            logits = jnp.einsum("bsh,hv->bsv", h, hp["lm_head"]["kernel"].astype(dt))
        return _ce_loss(logits.astype(jnp.float32), aux_mb, use_onehot=True)

    def embed_fn(p):
        x = p["embed"]["embedding"].astype(dt)[ids]
        if cfg.positions == "learned":
            x = x + p["pos_embed"]["embedding"].astype(dt)[:S][None, None]
        return x

    def _loss_and_grads(params):
        xs, embed_vjp = jax.vjp(embed_fn, params)
        head_params = {k: params[k] for k in head_keys}
        loss, g_blocks, g_head, d_xs = pipeline_1f1b(
            _stage_scan_fn(cfg, with_aux=moe), head_fn, params["blocks"], head_params, xs, aux,
            sin, cos, mesh=mesh, num_stages=num_stages,
            with_aux=moe, aux_weight=cfg.moe_aux_loss_coef)
        (grads, ) = embed_vjp(d_xs)  # full-tree cotangent (embedding only)
        grads = dict(grads)
        grads["blocks"] = g_blocks
        for k in head_keys:  # tied embeddings: head grads add to embed grads
            grads[k] = jax.tree_util.tree_map(jnp.add, grads[k], g_head[k])
        return loss, grads

    @jax.custom_vjp
    def run(params):
        return _loss_and_grads(params)[0]

    def run_fwd(params):
        loss, grads = _loss_and_grads(params)
        return loss, grads

    def run_bwd(grads, g):
        return (jax.tree_util.tree_map(lambda x: x * g, grads), )

    run.defvjp(run_fwd, run_bwd)
    return run(params)


class TransformerLM:
    """Model object consumed by ``deepspeed_tpu.initialize``: bundles config,
    init, loss and TP partition rules (the engine's model protocol)."""

    def __init__(self, config: TransformerConfig):
        self.config = config

    def init(self, rng, example_batch=None):
        return init_params(self.config, rng)

    def apply(self, params, input_ids):
        return forward(self.config, params, input_ids)

    def loss(self, params, batch, rng=None):
        return loss_fn(self.config, params, batch, rng)

    def pipeline_loss(self, params, batches, rng=None, *, mesh, num_stages, schedule="1f1b"):
        if schedule == "1f1b":
            return pipeline_loss_fn_1f1b(self.config, params, batches, rng, mesh=mesh, num_stages=num_stages)
        return pipeline_loss_fn(self.config, params, batches, rng, mesh=mesh, num_stages=num_stages)

    def partition_rules(self):
        return partition_rules(self.config)

    def num_params(self, params=None):
        if params is None:
            params = jax.eval_shape(lambda r: init_params(self.config, r), jax.random.PRNGKey(0))
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
