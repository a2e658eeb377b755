"""The plain reference of the Mellum 2 decoder (JetBrains
Mellum2-12B-A2.5B ``config.json``): forward pass in straightforward float32
``jax.numpy`` at ``default_matmul_precision("highest")``. No kernels, no
cache, no batching tricks, no sorting of tokens by expert: every expert is
applied to every position and weighted by the router's (mostly zero)
weight. It shares no code with ``deepspeed_tpu``; it only reads the
system's parameter tree (stacked ``[L, ...]`` block arrays, experts stacked
``[L, E, ...]``) and casts one layer, and inside it one expert, at a time to
float32, so that it fits beside the system.

The layer, from the published keys (pre-norm, no biases, untied head):

    a = x + Wo Attn_l(rope_l(Wq n1(x)), rope_l(Wk n1(x)), Wv n1(x))
    y = a + sum_{e in top-k(p)} p_e / sum_{top-k} p . W2_e(silu(Wg_e h) * W1_e h)
    h = n2(a),  p = softmax(Wr h) over all experts

``Attn_l`` is causal softmax attention with grouped KV heads at scale
``1/sqrt(head_dim)``, over keys in ``(i - sliding_window, i]`` where
``layer_types[l]`` is ``sliding_attention`` and over all keys where it is
``full_attention``; ``rope_l`` is the rotate-half rotary embedding with the
section of ``rope_parameters`` of that layer's type: plain, or YaRN.

Departures from the published description, each noted where it is made:
none in the mathematics. Not in ``config.json`` and so in neither the system
nor here: a per-head norm on q and k, a multi-token-prediction head.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


def hyper_from_published(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file's
    published keys (Hugging Face names of the ``mellum`` model type)."""
    return {
        "n_q": cfg["num_attention_heads"], "n_kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "eps": cfg["rms_norm_eps"], "window": cfg["sliding_window"],
        "layer_types": tuple(cfg["layer_types"]),
        "top_k": cfg["num_experts_per_tok"], "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "rope": tuple(sorted((kind, tuple(sorted(section.items())))
                             for kind, section in cfg["rope_parameters"].items())),
    }


def rope_inverse_frequencies(section: dict, d: int):
    """Inverse frequencies ``[d/2]`` (float64) and the factor on sin and cos
    for one section of ``rope_parameters``. ``default``: ``theta^(-2i/d)``.
    ``yarn`` (Peng et al. 2023, "YaRN", as ``transformers`` computes it): a
    dimension that turns more than ``beta_fast`` times within the original
    context keeps that frequency, one that turns less than ``beta_slow``
    times has it divided by ``factor``, and between the two dimensions
    ``floor``/``ceil`` of ``d ln(original / (beta 2 pi)) / (2 ln theta)`` the
    two are mixed linearly; sin and cos are multiplied by ``attention_factor``."""
    theta = float(section["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if section["rope_type"] == "default":
        return plain, 1.0
    if section["rope_type"] != "yarn":
        raise ValueError(f"the reference has no rope_type {section['rope_type']!r}")
    original = float(section["original_max_position_embeddings"])

    def dimension_turning(times):
        return d * math.log(original / (times * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dimension_turning(section["beta_fast"])), 0)
    high = min(math.ceil(dimension_turning(section["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    mixed = plain / float(section["factor"]) * ramp + plain * (1.0 - ramp)
    return mixed, float(section["attention_factor"])


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, section: dict):
    """Rotate-half rotary embedding on ``x`` ``[B, S, n, d]`` at positions
    ``0..S-1``."""
    d = x.shape[-1]
    inv, factor = rope_inverse_frequencies(section, d)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    sin, cos = (jnp.sin(ang) * factor)[None, :, None, :], (jnp.cos(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, n_kv: int, window):
    """Causal softmax attention with grouped KV heads; ``window`` None: every
    earlier key, else query ``i`` sees keys in ``(i - window, i]``."""
    B, S, n_q, d = q.shape
    k = jnp.repeat(k, n_q // n_kv, axis=2)
    v = jnp.repeat(v, n_q // n_kv, axis=2)
    scores = jnp.einsum("bsnd,btnd->bnst", q, k) / math.sqrt(d)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bnst,btnd->bsnd", jax.nn.softmax(scores, axis=-1), v)


def router_weights(h, gate, top_k: int, norm_topk_prob: bool):
    """``[..., E]`` weights of every expert: the softmax over all experts,
    kept for the ``top_k`` largest (divided by their sum if
    ``norm_topk_prob``), zero for the others."""
    p = jax.nn.softmax(h @ gate, axis=-1)
    top_p, top_e = lax.top_k(p, top_k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_e, p.shape[-1], dtype=F32) * top_p[..., None], axis=-2)


def _experts(h, weights, blk):
    """``sum_e weights[..., e] . W2_e(silu(Wg_e h) * W1_e h)``, one expert at
    a time, each cast to float32 as it is used."""
    def one(acc, xs):
        w, w1, wg, w2 = xs
        w1, wg, w2 = w1.astype(F32), wg.astype(F32), w2.astype(F32)
        return acc + w[..., None] * ((jax.nn.silu(h @ wg) * (h @ w1)) @ w2), None

    per_expert = jnp.moveaxis(weights, -1, 0)  # [E, B, S]
    out, _ = lax.scan(one, jnp.zeros_like(h), (per_expert, blk["moe_wi"], blk["moe_wg"], blk["moe_wo"]))
    return out


def layer(x, blk, hp: dict, kind: str):
    """One decoder layer of attention kind ``kind`` on ``x`` ``[B, S, H]``;
    ``blk`` holds this layer's parameters (the experts still in the type they
    are stored in, everything else float32)."""
    B, S, _ = x.shape
    h1 = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    q = (h1 @ blk["wq"]).reshape(B, S, hp["n_q"], hp["d"])
    k = (h1 @ blk["wk"]).reshape(B, S, hp["n_kv"], hp["d"])
    v = (h1 @ blk["wv"]).reshape(B, S, hp["n_kv"], hp["d"])
    section = dict(dict(hp["rope"])[kind])
    window = hp["window"] if kind == "sliding_attention" else None
    ctx = _attention(_rope(q, section), _rope(k, section), v, hp["n_kv"], window).reshape(B, S, -1)
    a = x + ctx @ blk["wo"]
    h2 = _rms_norm(a, blk["ln2_scale"], hp["eps"])
    weights = router_weights(h2, blk["gate_wg"], hp["top_k"], hp["norm_topk_prob"])
    return a + _experts(h2, weights, blk)


_EXPERT_KEYS = ("moe_wi", "moe_wg", "moe_wo")


@partial(jax.jit, static_argnums=(3, 4))
def _layer_fwd(x, blocks, l, hp_items, kind):
    blk = {name: lax.dynamic_index_in_dim(a, l, 0, keepdims=False) for name, a in blocks.items()}
    blk = {name: a if name in _EXPERT_KEYS else a.astype(F32) for name, a in blk.items()}
    return layer(x, blk, dict(hp_items), kind)


@partial(jax.jit, static_argnums=(3, ))
def _head(x, scale, head_kernel, eps):
    return _rms_norm(x, scale.astype(F32), eps) @ head_kernel.astype(F32)


def forward_logits(hp: dict, params, ids, positions):
    """Logits ``[B, len(positions), V]`` of the full forward pass over
    ``ids`` ``[B, S]`` at the given positions."""
    hp_items = tuple(sorted(hp.items()))
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids].astype(F32)
        for l, kind in enumerate(hp["layer_types"][:params["blocks"]["wq"].shape[0]]):
            x = _layer_fwd(x, params["blocks"], l, hp_items, kind)
        return _head(x[:, jnp.asarray(positions)], params["final_norm"]["scale"],
                     params["lm_head"]["kernel"], hp["eps"])
