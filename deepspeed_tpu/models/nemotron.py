"""Nemotron-H model family configs (nvidia NVIDIA-Nemotron-3-Nano-30B-A3B,
``model_type`` ``nemotron_h``, 31.6B-A3.2B, context 262,144).

A pre-norm decoder without biases, untied head, RMSNorm, in which a layer is
ONE branch, ``x <- x + branch(rmsnorm(x))`` with one gain vector; there is no
layer with a mixer and an MLP both. ``hybrid_override_pattern`` gives each
layer a letter:

* ``M`` (here ``"state_space"``): Mamba-2. ``[z | xBC | dt] = h W_in`` (``d_inner
  = heads x head width`` | ``d_inner + 2 groups x state width`` | heads);
  ``xBC_t <- silu(sum_j w[j, c] xBC_{t - 3 + j} + b_c)``, depthwise, causal, zeros
  before a sequence's first token; ``x`` (heads of ``P``), ``B`` and ``C``
  (groups of ``N``) are its parts. ``dt_t,h = softplus(dt_t,h + dt_bias_h)``,
  ``A_h = -exp(A_log_h)``, and with ``g`` the group of head ``h``:
  ``S_t,h = exp(dt_t,h A_h) S_{t-1,h} + dt_t,h x_t,h (x) B_t,g`` (``S`` is ``P x
  N``, float32, zero at a sequence's start), ``y_t,h = S_t,h C_t,g + D_h
  x_t,h``. Then the gated norm, ``y * silu(z)`` RMS-normalised within each
  group's channels, times a gain, and ``W_out``. Such a layer caches nothing
  per token: a sequence holds the state and the convolution's last three
  inputs, whatever its length (``TransformerConfig.state_entry``).
* ``E`` (``"mlp_only"``): ``n_routed_experts`` experts ``relu(h W_up)^2 W_down``
  without a gate matrix, a token's ``num_experts_per_tok`` chosen by
  ``sigmoid`` score plus a per-expert bias that no gradient trains, weighted
  by the scores alone over their sum, times ``routed_scaling_factor``; beside
  them ONE shared expert of the same form at a width of its own
  (``moe_shared_expert_intermediate_size``).
* ``*`` (``"full_attention"``): softmax attention, 32 query / 2 KV heads of
  128, causal, NO positional encoding (the ``nemotron_h`` modelling code
  builds no rotary table: the Mamba layers carry position; Nemotron-H,
  arXiv:2504.03624), no window, no q/k norm, no gate.

Served through ``InferenceEngineV2`` alone (``ragged_forward`` unrolls the
layers, ``ops/pallas/mamba2.py`` holds the selective scan's two forms); a chip
is told which experts it holds (``moe_experts_held``), as Trinity's and
Solar's are. The whole-sequence forwards refuse this family
(``TransformerConfig.unscannable``). What a sequence's state forbids until it
can be snapshot (the prefix cache, the host tier, a rewind, the handoff,
speculative decoding, int8 KV) refuses by name in the engine and the state
manager, as it does for every model with a state layer.
"""

from .transformer import TransformerConfig, TransformerLM

_KINDS = {"M": "state_space", "E": "mlp_only", "*": "full_attention"}
_PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_config(size: str = "3-nano-30b-a3b", **overrides) -> TransformerConfig:
    presets = {
        # the published first stage's letters at toy widths: a group of 3, 2 heads a Mamba group, a head
        # narrower than the state
        "tiny": dict(vocab_size=512, hidden_size=64, num_layers=13, num_heads=6, num_kv_heads=2, head_size=16,
                     intermediate_size=48, moe_intermediate_size=48, moe_shared_expert_size=96, moe_num_experts=16,
                     moe_top_k=2, max_seq_len=2048, mamba_num_heads=4, mamba_head_dim=8, mamba_state_size=16,
                     mamba_n_groups=2),
        "3-nano-30b-a3b": dict(vocab_size=131072, hidden_size=2688, num_layers=52, num_heads=32, num_kv_heads=2,
                               head_size=128, intermediate_size=1856, moe_intermediate_size=1856,
                               moe_shared_expert_size=3712, moe_num_experts=128, moe_top_k=6, max_seq_len=262144,
                               mamba_num_heads=64, mamba_head_dim=64, mamba_state_size=128, mamba_n_groups=8),
    }
    base = dict(presets[size], norm="rmsnorm", positions="rotary", mlp="relu2", use_bias=False,
                tie_embeddings=False, norm_eps=1e-5, moe_dropless=True, moe_norm_topk_prob=True,
                moe_num_shared_experts=1, moe_score_func="sigmoid", moe_route_bias=True, moe_route_scale=2.5,
                mamba_conv_size=4, rope_layer_types=(), single_branch_layers=True)
    base.update(overrides)
    # the published key that is no field of the program's own: a depth cut keeps the published pattern and
    # reads its first ``num_layers`` letters
    pattern = base.pop("hybrid_override_pattern", None) or _PUBLISHED
    if "layer_types" not in base:
        base["layer_types"] = tuple(_KINDS[c] for c in pattern[:base["num_layers"]])
    return TransformerConfig(**base)


def nemotron(size: str = "3-nano-30b-a3b", **overrides) -> TransformerLM:
    return TransformerLM(nemotron_config(size, **overrides))
