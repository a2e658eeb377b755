"""Declarative parameter-mapping layer for v2 model families.

Reference mechanism: ``inference/v2/model_implementations/parameter_base.py``
+ ``layer_container_base.py`` (declarative parameter specs with automatic
mapping/transformation per family) — VERDICT r4 missing #4 flagged the repo's
bespoke converter-per-family pattern (11 hand-written dict builders growing
linearly) as the evidence an abstraction was overdue.

TPU-first shape of the same idea: a model family is a LIST of
:class:`ParamSpec` rows — (HF source name(s), target pytree path(s),
transform, predicate) — and ONE generic :func:`convert_with_spec` walks the
table, stacking per-layer tensors into the ``[L, ...]`` arrays the scan-based
``models.transformer`` forward consumes. Adding a family means writing a
table, not a converter; transforms are shared, named, and unit-testable.

Layout conventions encoded by the transforms:
  - torch ``nn.Linear`` stores ``[out, in]`` → our einsum layout is
    ``[in, out]`` (transform ``"t"``); GPT-2 ``Conv1D`` is already
    ``[in, out]`` (transform ``"copy"``).
  - fused query_key_value weights split per family layout: Bloom/NeoX
    per-head interleave ``(nh, 3, hd)``; Falcon GQA grouped rows
    ``[q heads..., k, v]``.
  - GPT-J's interleaved (rotate-every-two) rotary becomes our half-style
    rope via a score-preserving column permutation of q/k.
"""

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# transforms: (cfg, *source_arrays) -> tuple of target arrays
# ---------------------------------------------------------------------------
def _t(cfg, w):
    return (w.T, )


def _copy(cfg, a):
    return (a, )


def _rows_from_2(cfg, a):
    # OPT's learned positions carry a +2 offset (rows 0-1 unused)
    return (a[2:], )


def _split3_last(cfg, a):
    # GPT-2 fused c_attn: qkv concatenated on the LAST axis ([in, 3H] weight,
    # [3H] bias) — three equal slices
    return tuple(np.split(a, 3, axis=-1))


def _qkv_interleaved(cfg, w):
    """Bloom/NeoX fused qkv weight [(nh*3*hd), H] (torch [out, in]) with
    per-head interleave → ([H, nh*hd],)*3 in our [in, out] layout."""
    nh, hd = cfg.num_heads, cfg.head_dim
    H = w.shape[1]
    w3 = w.reshape(nh, 3, hd, H)
    return tuple(w3[:, j].reshape(nh * hd, H).T for j in range(3))


def _qkv_bias_interleaved(cfg, b):
    nh, hd = cfg.num_heads, cfg.head_dim
    b3 = b.reshape(nh, 3, hd)
    return tuple(b3[:, j].reshape(-1) for j in range(3))


def _qkv_gqa_rows(cfg, w):
    """Falcon MQA/GQA fused layout: per kv group [q heads..., k, v] on the
    out dim → q [H, nh*hd], k/v [H, nkv*hd]."""
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    H = w.shape[1]
    w3 = w.reshape(nkv, nh // nkv + 2, hd, H)
    q = w3[:, :-2].reshape(nh * hd, H).T
    k = w3[:, -2].reshape(nkv * hd, H).T
    v = w3[:, -1].reshape(nkv * hd, H).T
    return q, k, v


def _interleaved_to_half_perm(w_cols, nh, hd, rotary_dim):
    """Permute q/k OUTPUT columns so HF's interleaved (rotate_every_two)
    rotary becomes our half-style rope. Score-preserving: the same orthogonal
    permutation hits q and k."""
    perm_r = list(range(0, rotary_dim, 2)) + list(range(1, rotary_dim, 2))
    idx = []
    for h in range(nh):
        off = h * hd
        idx.extend(off + np.asarray(perm_r))
        idx.extend(range(off + rotary_dim, off + hd))
    return w_cols[..., np.asarray(idx)]


def _t_rotary_half(cfg, w):
    return (_interleaved_to_half_perm(w.T, cfg.num_heads, cfg.head_dim, cfg.rotary_dim), )


def _tail_to_half(cols, rope):
    """The LAST ``rope`` entries of ``cols`` from interleaved pairs (2i, 2i+1)
    to halves (i, i + rope/2)."""
    tail = cols[-rope:]
    return np.concatenate([cols[:-rope], tail[0::2], tail[1::2]])


def _t_rope_tail_half(cfg, w):
    """Latent attention's rotated columns, which close each query head
    (``q_b_proj`` ``[nq * (nope + rope), q_rank]``) and the latent projection
    (``kv_a_proj_with_mqa`` ``[latent + rope, H]``): transposed, and where the
    checkpoint pairs interleaved lanes, permuted to our half-style rope. The
    same permutation on both is score-preserving."""
    rope, wt = cfg.qk_rope_head_dim, w.T
    group = cfg.head_dim if wt.shape[1] == cfg.num_heads * cfg.head_dim else wt.shape[1]
    idx = np.concatenate([_tail_to_half(np.arange(g, g + group), rope) for g in range(0, wt.shape[1], group)])
    return (wt[:, idx], )


def _kvb_by_head(cfg, w):
    """``kv_b_proj`` ``[nq * (nope + v), latent]`` as the two parts the
    absorbed form multiplies by, a head at a time: keys ``[nq, latent, nope]``
    and values ``[nq, latent, v]`` (``W_kvb`` is kept no third time)."""
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    by_head = w.reshape(cfg.num_heads, nope + dv, cfg.kv_lora_rank).transpose(0, 2, 1)
    return by_head[..., :nope], by_head[..., nope:]


def _zeros_qkv(cfg):
    return (np.zeros(cfg.num_heads * cfg.head_dim, np.float32), )


def _zeros_hidden(cfg):
    return (np.zeros(cfg.hidden_size, np.float32), )


TRANSFORMS: Dict[str, Callable] = {
    "copy": _copy,
    "t": _t,
    "rows_from_2": _rows_from_2,
    "split3_last": _split3_last,
    "qkv_interleaved": _qkv_interleaved,
    "qkv_bias_interleaved": _qkv_bias_interleaved,
    "qkv_gqa_rows": _qkv_gqa_rows,
    "t_rotary_half": _t_rotary_half,
    "t_rope_tail_half": _t_rope_tail_half,
    "kvb_by_head": _kvb_by_head,
    "zeros_qkv": _zeros_qkv,
    "zeros_hidden": _zeros_hidden,
}

# predicates: (cfg, sd) -> bool, gating conditional rows
PREDICATES: Dict[str, Callable] = {
    "untied": lambda cfg, sd: not cfg.tie_embeddings,
    # qkv_bias_enabled is what the FORWARD consults (qkv_bias with a use_bias
    # fallback, transformer.py:129) — the converter must agree with it or the
    # forward KeyErrors on layer['bq']. Direct attribute access on purpose: a
    # cfg missing the property should raise, not silently skip bias rows.
    "qkv_bias": lambda cfg, sd: bool(cfg.qkv_bias_enabled),
    # falcon's 40b/180b decoder names its two parallel norms ln_attn/ln_mlp;
    # detected from the checkpoint itself, as the HF loaders do
    "falcon_new_arch": lambda cfg, sd: "transformer.h.0.ln_attn.weight" in sd,
    "falcon_old_arch": lambda cfg, sd: "transformer.h.0.ln_attn.weight" not in sd,
}


@dataclass(frozen=True)
class ParamSpec:
    """One row of a family's mapping table: ``srcs`` (HF names, ``{i}`` = layer
    index when ``per_layer``) feed ``transform``, whose outputs land at
    ``targets`` (dotted paths into the param pytree)."""

    targets: Tuple[str, ...]
    srcs: Tuple[str, ...] = ()
    transform: str = "copy"
    per_layer: bool = False
    when: Optional[str] = None

    def __post_init__(self):
        if isinstance(self.targets, str):
            object.__setattr__(self, "targets", (self.targets, ))
        if isinstance(self.srcs, str):
            object.__setattr__(self, "srcs", (self.srcs, ))
        if self.transform not in TRANSFORMS:
            raise ValueError(f"unknown transform {self.transform!r} for {self.targets}")
        if self.when is not None and self.when not in PREDICATES:
            raise ValueError(f"unknown predicate {self.when!r} for {self.targets}")


S = ParamSpec  # table-writing shorthand


def _set_path(tree: dict, dotted: str, val) -> None:
    parts = dotted.split(".")
    d = tree
    for p in parts[:-1]:
        d = d.setdefault(p, {})
    d[parts[-1]] = val


def convert_with_spec(sd: Dict[str, np.ndarray], cfg, entries) -> dict:
    """Run a family's mapping table over an HF state dict → stacked fp32
    param pytree. Missing source tensors raise with the offending row named
    (a silent skip would materialize a prayer, not a model)."""
    out: dict = {}
    for e in entries:
        if e.when is not None and not PREDICATES[e.when](cfg, sd):
            continue
        tf = TRANSFORMS[e.transform]

        def fetch(name):
            if name not in sd:
                raise KeyError(
                    f"HF checkpoint is missing {name!r} (needed for {e.targets} via "
                    f"transform {e.transform!r})")
            return np.asarray(sd[name], np.float32)

        if e.per_layer:
            cols = [[] for _ in e.targets]
            for i in range(cfg.num_layers):
                outs = tf(cfg, *(fetch(s.format(i=i)) for s in e.srcs))
                for c, o in zip(cols, outs):
                    c.append(o)
            vals = [np.stack(c) for c in cols]
        else:
            vals = tf(cfg, *(fetch(s) for s in e.srcs))
        if len(vals) != len(e.targets):
            raise ValueError(f"transform {e.transform!r} produced {len(vals)} outputs "
                             f"for {len(e.targets)} targets {e.targets}")
        for t, v in zip(e.targets, vals):
            _set_path(out, t, v)
    return out


# ---------------------------------------------------------------------------
# family tables (reference model_implementations/<family>/: one container
# spec per family; here one table per family)
# ---------------------------------------------------------------------------
def _llama_family() -> tuple:
    """llama / mistral / qwen2 (qwen2 adds biased qkv via the predicate)."""
    b = "model.layers.{i}."
    return (
        S("embed.embedding", "model.embed_tokens.weight"),
        S("blocks.ln1_scale", b + "input_layernorm.weight", per_layer=True),
        S("blocks.wq", b + "self_attn.q_proj.weight", "t", per_layer=True),
        S("blocks.wk", b + "self_attn.k_proj.weight", "t", per_layer=True),
        S("blocks.wv", b + "self_attn.v_proj.weight", "t", per_layer=True),
        S("blocks.wo", b + "self_attn.o_proj.weight", "t", per_layer=True),
        S("blocks.bq", b + "self_attn.q_proj.bias", per_layer=True, when="qkv_bias"),
        S("blocks.bk", b + "self_attn.k_proj.bias", per_layer=True, when="qkv_bias"),
        S("blocks.bv", b + "self_attn.v_proj.bias", per_layer=True, when="qkv_bias"),
        S("blocks.ln2_scale", b + "post_attention_layernorm.weight", per_layer=True),
        S("blocks.w_gate", b + "mlp.gate_proj.weight", "t", per_layer=True),
        S("blocks.w_up", b + "mlp.up_proj.weight", "t", per_layer=True),
        S("blocks.w_down", b + "mlp.down_proj.weight", "t", per_layer=True),
        S("final_norm.scale", "model.norm.weight"),
        S("lm_head.kernel", "lm_head.weight", "t", when="untied"),
    )


def _phi() -> tuple:
    b = "model.layers.{i}."
    return (
        S("embed.embedding", "model.embed_tokens.weight"),
        S("blocks.ln1_scale", b + "input_layernorm.weight", per_layer=True),
        S("blocks.ln1_bias", b + "input_layernorm.bias", per_layer=True),
        S("blocks.wq", b + "self_attn.q_proj.weight", "t", per_layer=True),
        S("blocks.bq", b + "self_attn.q_proj.bias", per_layer=True),
        S("blocks.wk", b + "self_attn.k_proj.weight", "t", per_layer=True),
        S("blocks.bk", b + "self_attn.k_proj.bias", per_layer=True),
        S("blocks.wv", b + "self_attn.v_proj.weight", "t", per_layer=True),
        S("blocks.bv", b + "self_attn.v_proj.bias", per_layer=True),
        S("blocks.wo", b + "self_attn.dense.weight", "t", per_layer=True),
        S("blocks.bo", b + "self_attn.dense.bias", per_layer=True),
        S("blocks.w_up", b + "mlp.fc1.weight", "t", per_layer=True),
        S("blocks.b_up", b + "mlp.fc1.bias", per_layer=True),
        S("blocks.w_down", b + "mlp.fc2.weight", "t", per_layer=True),
        S("blocks.b_down", b + "mlp.fc2.bias", per_layer=True),
        S("final_norm.scale", "model.final_layernorm.weight"),
        S("final_norm.bias", "model.final_layernorm.bias"),
        S("lm_head.kernel", "lm_head.weight", "t"),
        S("lm_head.bias", "lm_head.bias"),
    )


def _gpt2() -> tuple:
    b = "transformer.h.{i}."
    return (
        S("embed.embedding", "transformer.wte.weight"),
        S("pos_embed.embedding", "transformer.wpe.weight"),
        S("blocks.ln1_scale", b + "ln_1.weight", per_layer=True),
        S("blocks.ln1_bias", b + "ln_1.bias", per_layer=True),
        # Conv1D stores [in, out] — no transpose; c_attn fuses qkv on out dim
        S(("blocks.wq", "blocks.wk", "blocks.wv"), b + "attn.c_attn.weight",
          "split3_last", per_layer=True),
        S(("blocks.bq", "blocks.bk", "blocks.bv"), b + "attn.c_attn.bias",
          "split3_last", per_layer=True),
        S("blocks.wo", b + "attn.c_proj.weight", per_layer=True),
        S("blocks.bo", b + "attn.c_proj.bias", per_layer=True),
        S("blocks.ln2_scale", b + "ln_2.weight", per_layer=True),
        S("blocks.ln2_bias", b + "ln_2.bias", per_layer=True),
        S("blocks.w_up", b + "mlp.c_fc.weight", per_layer=True),
        S("blocks.b_up", b + "mlp.c_fc.bias", per_layer=True),
        S("blocks.w_down", b + "mlp.c_proj.weight", per_layer=True),
        S("blocks.b_down", b + "mlp.c_proj.bias", per_layer=True),
        S("final_norm.scale", "transformer.ln_f.weight"),
        S("final_norm.bias", "transformer.ln_f.bias"),
    )


def _opt() -> tuple:
    b = "model.decoder.layers.{i}."
    return (
        S("embed.embedding", "model.decoder.embed_tokens.weight"),
        S("pos_embed.embedding", "model.decoder.embed_positions.weight", "rows_from_2"),
        S("blocks.ln1_scale", b + "self_attn_layer_norm.weight", per_layer=True),
        S("blocks.ln1_bias", b + "self_attn_layer_norm.bias", per_layer=True),
        S("blocks.wq", b + "self_attn.q_proj.weight", "t", per_layer=True),
        S("blocks.wk", b + "self_attn.k_proj.weight", "t", per_layer=True),
        S("blocks.wv", b + "self_attn.v_proj.weight", "t", per_layer=True),
        S("blocks.bq", b + "self_attn.q_proj.bias", per_layer=True),
        S("blocks.bk", b + "self_attn.k_proj.bias", per_layer=True),
        S("blocks.bv", b + "self_attn.v_proj.bias", per_layer=True),
        S("blocks.wo", b + "self_attn.out_proj.weight", "t", per_layer=True),
        S("blocks.bo", b + "self_attn.out_proj.bias", per_layer=True),
        S("blocks.ln2_scale", b + "final_layer_norm.weight", per_layer=True),
        S("blocks.ln2_bias", b + "final_layer_norm.bias", per_layer=True),
        S("blocks.w_up", b + "fc1.weight", "t", per_layer=True),
        S("blocks.b_up", b + "fc1.bias", per_layer=True),
        S("blocks.w_down", b + "fc2.weight", "t", per_layer=True),
        S("blocks.b_down", b + "fc2.bias", per_layer=True),
        S("final_norm.scale", "model.decoder.final_layer_norm.weight"),
        S("final_norm.bias", "model.decoder.final_layer_norm.bias"),
    )


def _bloom() -> tuple:
    b = "transformer.h.{i}."
    return (
        S("embed.embedding", "transformer.word_embeddings.weight"),
        S("embed_norm.scale", "transformer.word_embeddings_layernorm.weight"),
        S("embed_norm.bias", "transformer.word_embeddings_layernorm.bias"),
        S("blocks.ln1_scale", b + "input_layernorm.weight", per_layer=True),
        S("blocks.ln1_bias", b + "input_layernorm.bias", per_layer=True),
        S(("blocks.wq", "blocks.wk", "blocks.wv"),
          b + "self_attention.query_key_value.weight", "qkv_interleaved", per_layer=True),
        S(("blocks.bq", "blocks.bk", "blocks.bv"),
          b + "self_attention.query_key_value.bias", "qkv_bias_interleaved", per_layer=True),
        S("blocks.wo", b + "self_attention.dense.weight", "t", per_layer=True),
        S("blocks.bo", b + "self_attention.dense.bias", per_layer=True),
        S("blocks.ln2_scale", b + "post_attention_layernorm.weight", per_layer=True),
        S("blocks.ln2_bias", b + "post_attention_layernorm.bias", per_layer=True),
        S("blocks.w_up", b + "mlp.dense_h_to_4h.weight", "t", per_layer=True),
        S("blocks.b_up", b + "mlp.dense_h_to_4h.bias", per_layer=True),
        S("blocks.w_down", b + "mlp.dense_4h_to_h.weight", "t", per_layer=True),
        S("blocks.b_down", b + "mlp.dense_4h_to_h.bias", per_layer=True),
        S("final_norm.scale", "transformer.ln_f.weight"),
        S("final_norm.bias", "transformer.ln_f.bias"),
    )


def _gptj() -> tuple:
    b = "transformer.h.{i}."
    return (
        S("embed.embedding", "transformer.wte.weight"),
        S("blocks.ln1_scale", b + "ln_1.weight", per_layer=True),
        S("blocks.ln1_bias", b + "ln_1.bias", per_layer=True),
        # interleaved->half rotary handled by a column permutation of q/k
        S("blocks.wq", b + "attn.q_proj.weight", "t_rotary_half", per_layer=True),
        S("blocks.wk", b + "attn.k_proj.weight", "t_rotary_half", per_layer=True),
        S("blocks.wv", b + "attn.v_proj.weight", "t", per_layer=True),
        # GPT-J attention has no biases; the block layout expects them
        S("blocks.bq", transform="zeros_qkv", per_layer=True),
        S("blocks.bk", transform="zeros_qkv", per_layer=True),
        S("blocks.bv", transform="zeros_qkv", per_layer=True),
        S("blocks.wo", b + "attn.out_proj.weight", "t", per_layer=True),
        S("blocks.bo", transform="zeros_hidden", per_layer=True),
        S("blocks.w_up", b + "mlp.fc_in.weight", "t", per_layer=True),
        S("blocks.b_up", b + "mlp.fc_in.bias", per_layer=True),
        S("blocks.w_down", b + "mlp.fc_out.weight", "t", per_layer=True),
        S("blocks.b_down", b + "mlp.fc_out.bias", per_layer=True),
        S("final_norm.scale", "transformer.ln_f.weight"),
        S("final_norm.bias", "transformer.ln_f.bias"),
        S("lm_head.kernel", "lm_head.weight", "t"),
        S("lm_head.bias", "lm_head.bias"),
    )


def _gpt_neox() -> tuple:
    b = "gpt_neox.layers.{i}."
    return (
        S("embed.embedding", "gpt_neox.embed_in.weight"),
        S("blocks.ln1_scale", b + "input_layernorm.weight", per_layer=True),
        S("blocks.ln1_bias", b + "input_layernorm.bias", per_layer=True),
        S(("blocks.wq", "blocks.wk", "blocks.wv"),
          b + "attention.query_key_value.weight", "qkv_interleaved", per_layer=True),
        S(("blocks.bq", "blocks.bk", "blocks.bv"),
          b + "attention.query_key_value.bias", "qkv_bias_interleaved", per_layer=True),
        S("blocks.wo", b + "attention.dense.weight", "t", per_layer=True),
        S("blocks.bo", b + "attention.dense.bias", per_layer=True),
        S("blocks.ln2_scale", b + "post_attention_layernorm.weight", per_layer=True),
        S("blocks.ln2_bias", b + "post_attention_layernorm.bias", per_layer=True),
        S("blocks.w_up", b + "mlp.dense_h_to_4h.weight", "t", per_layer=True),
        S("blocks.b_up", b + "mlp.dense_h_to_4h.bias", per_layer=True),
        S("blocks.w_down", b + "mlp.dense_4h_to_h.weight", "t", per_layer=True),
        S("blocks.b_down", b + "mlp.dense_4h_to_h.bias", per_layer=True),
        S("final_norm.scale", "gpt_neox.final_layer_norm.weight"),
        S("final_norm.bias", "gpt_neox.final_layer_norm.bias"),
        S("lm_head.kernel", "embed_out.weight", "t", when="untied"),
    )


def _falcon() -> tuple:
    b = "transformer.h.{i}."
    return (
        S("embed.embedding", "transformer.word_embeddings.weight"),
        # 7b family: single shared input_layernorm; 40b/180b: ln_attn + ln_mlp
        S("blocks.ln1_scale", b + "input_layernorm.weight", per_layer=True,
          when="falcon_old_arch"),
        S("blocks.ln1_bias", b + "input_layernorm.bias", per_layer=True,
          when="falcon_old_arch"),
        S("blocks.ln1_scale", b + "ln_attn.weight", per_layer=True, when="falcon_new_arch"),
        S("blocks.ln1_bias", b + "ln_attn.bias", per_layer=True, when="falcon_new_arch"),
        S("blocks.ln2_scale", b + "ln_mlp.weight", per_layer=True, when="falcon_new_arch"),
        S("blocks.ln2_bias", b + "ln_mlp.bias", per_layer=True, when="falcon_new_arch"),
        S(("blocks.wq", "blocks.wk", "blocks.wv"),
          b + "self_attention.query_key_value.weight", "qkv_gqa_rows", per_layer=True),
        S("blocks.wo", b + "self_attention.dense.weight", "t", per_layer=True),
        S("blocks.w_up", b + "mlp.dense_h_to_4h.weight", "t", per_layer=True),
        S("blocks.w_down", b + "mlp.dense_4h_to_h.weight", "t", per_layer=True),
        S("final_norm.scale", "transformer.ln_f.weight"),
        S("final_norm.bias", "transformer.ln_f.bias"),
        S("lm_head.kernel", "lm_head.weight", "t", when="untied"),
    )


def latent_attention_rows(prefix: str = "model.layers.{i}.self_attn.") -> tuple:
    """The rows of a latent-attention block (DeepSeek-V3 / ``glm4_moe_lite``
    names) for a family's table: the two low-rank projections with their
    norms, ``W_kvb`` cut by head, the output projection. The rotated columns
    are taken as INTERLEAVED in the checkpoint (``t_rope_tail_half``); a
    checkpoint that pairs halves takes ``t`` on those two rows. No family
    with experts has a table yet (the expert arrays are stacked over two
    axes, which ``convert_with_spec`` does not do), so these rows stand
    alone."""
    a = prefix
    return (
        S("blocks.wq_a", a + "q_a_proj.weight", "t", per_layer=True),
        S("blocks.q_a_norm_scale", a + "q_a_layernorm.weight", per_layer=True),
        S("blocks.wq_b", a + "q_b_proj.weight", "t_rope_tail_half", per_layer=True),
        S("blocks.wkv_a", a + "kv_a_proj_with_mqa.weight", "t_rope_tail_half", per_layer=True),
        S("blocks.kv_a_norm_scale", a + "kv_a_layernorm.weight", per_layer=True),
        S(("blocks.wkv_b_k", "blocks.wkv_b_v"), a + "kv_b_proj.weight", "kvb_by_head", per_layer=True),
        S("blocks.wo", a + "o_proj.weight", "t", per_layer=True),
    )


_LLAMA_FAMILY = _llama_family()

FAMILY_SPECS: Dict[str, tuple] = {
    "llama": _LLAMA_FAMILY,
    "mistral": _LLAMA_FAMILY,
    "qwen2": _LLAMA_FAMILY,
    "phi": _phi(),
    "gpt2": _gpt2(),
    "opt": _opt(),
    "bloom": _bloom(),
    "gptj": _gptj(),
    "gpt_neox": _gpt_neox(),
    "falcon": _falcon(),
}
