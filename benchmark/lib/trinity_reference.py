"""The plain reference of the Trinity decoder (arcee-ai Trinity-Large-Preview,
``model_type`` ``afmoe``): forward pass in straightforward float32
``jax.numpy`` at ``default_matmul_precision("highest")``. No kernels, no cache,
no batching, no sorting of tokens by expert: every expert held here is
applied to every position and weighted by the router's (mostly zero) weight.
It shares no code with ``deepspeed_tpu``; it only reads the system's parameter
tree and casts one layer's small matrices, and inside an expert layer one
EXPERT at a time (3 x 3072 x 3072: 113 MB in float32; a layer's 32 would be
3.6 GB), to float32, so that it fits beside the system. Attention is computed
a block of queries at a time against all keys (the same numbers as in one
piece, a bounded score matrix).

The layer, ``x`` ``[S, H]``, every projection without bias,
``n(x; g) = x / sqrt(mean(x^2) + eps) * g``:

    x0 = E[ids] * sqrt(H)
    h  = n(x; g_in);  q, k, v, gate = h Wq, h Wk, h Wv, h Wgate
    q  = n_d(q; g_q), k = n_d(k; g_k)            per head, over its d dimensions
    window layer: q, k = rope(q), rope(k); key j seen by query i iff i - window < j <= i
    full layer:   no positional encoding at all; key j seen iff j <= i
    a  = softmax(q k^T / sqrt(d)) v;  a = a * sigmoid(gate);  x = x + n(a Wo; g_post_attn)
    h  = n(x; g_pre_mlp)
    l < num_dense_layers:  m = (silu(h Wg) * (h Wu)) Wd
    otherwise:             s = sigmoid(h Wr) over ALL published experts;
                           chosen = top-k of s + b;  w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale
                           m = Shared(h) + sum over chosen experts HELD HERE of w_e Expert_e(h)
    x  = x + n(m; g_post_mlp)
    logits = n(x; g_f) W_lm

The share. The configuration states which experts live on this chip
(``num_experts`` held from ``first_expert`` on, of ``num_experts_published``)
and the program's expert arrays hold those alone; the router has its published
width. A chosen expert that is not held adds nothing here (the chip that holds
it computes that term), in the program and here alike, and the partial ``m``
goes on to the next layer.

Departures from the published description: none in the mathematics of what is
held. What ``config.json`` has no key for (the embedding factor, the gate, the
q/k norm, no rope in the full layers, the selection bias, the post-norms) is
the afmoe model's published modelling code, listed under ``assumed`` in the
configuration file. Each of those is a switch of ``hyper_from_published``'s
result, on as published; a control run turns one off on this side to show
that the comparison sees it.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 256


def hyper_from_published(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file's
    published keys (Hugging Face names of the ``afmoe`` model type) and the
    share the file states."""
    held = cfg["num_experts"]
    return {
        "n_q": cfg["num_attention_heads"], "n_kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "eps": cfg["rms_norm_eps"], "window": cfg["sliding_window"], "rope_theta": float(cfg["rope_theta"]),
        "layer_types": tuple(cfg["layer_types"]), "n_dense": cfg["num_dense_layers"],
        "top_k": cfg["num_experts_per_tok"], "route_norm": bool(cfg["route_norm"]),
        "route_scale": float(cfg["route_scale"]), "score_func": cfg["score_func"],
        "n_experts": cfg.get("num_experts_published", held), "first_expert": cfg.get("first_expert", 0),
        "n_held": held,
        # muP: the embedding times sqrt(hidden_size)
        "embed_scale": math.sqrt(cfg["hidden_size"]) if cfg.get("mup_enabled") else 1.0,
        # the parts config.json has no key for, on as the modelling code has them
        "gate": True, "qk_norm": True, "rope_in_full_layers": False, "selection_bias": True,
        "post_norms": True,
    }


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """Rotate-half rotary embedding on ``x`` ``[S, n, d]`` at positions
    ``0..S-1``, all ``d`` dimensions, no scaling."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, n_kv: int, window):
    """Causal softmax attention with grouped KV heads, ``[S, n, d]`` in and
    out; ``window`` None: every earlier key, else query ``i`` sees keys in
    ``(i - window, i]``. ``QUERY_BLOCK`` queries at a time against all keys."""
    S, n_q, d = q.shape
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    blocks = -(-S // QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - S), (0, 0), (0, 0))).reshape(blocks, QUERY_BLOCK, n_q, d)
    j = jnp.arange(S)[None, :]

    def one(args):
        qb, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        scores = jnp.einsum("snd,tnd->nst", qb, k) / math.sqrt(d)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v)

    out = lax.map(one, (q, jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, n_q, d)[:S]


def router_scores(h, gate_w, hp: dict):
    """Every published expert's score ``[..., E]``: the sigmoid of its logit,
    or the softmax over all."""
    logits = h @ gate_w
    return jax.nn.sigmoid(logits) if hp["score_func"] == "sigmoid" else jax.nn.softmax(logits, axis=-1)


def router_weights(h, gate_w, bias, hp: dict):
    """``[..., E]`` weights over ALL published experts: each expert's score
    (sigmoid of its logit, or the softmax over all), kept for the ``top_k``
    largest of score + ``bias`` (the bias chooses, it does not weigh), divided
    by their sum plus 1e-20 if ``route_norm``, times ``route_scale``; zero for
    the others."""
    s = router_scores(h, gate_w, hp)
    choose_by = s + bias if (bias is not None and hp["selection_bias"]) else s
    _, chosen = lax.top_k(choose_by, hp["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if hp["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * hp["route_scale"]
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * w[..., None], axis=-2)


def _swiglu(h, w_up, w_gate, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def held_experts(h, weights_held, wi, wg, wo, le):
    """``sum_e weights_held[..., e] . Expert_e(h)`` over the experts held
    here, one at a time, each read out of expert layer ``le`` of the stacked
    ``[Le, E_held, ...]`` arrays and cast to float32 as it is used."""
    H, F = wi.shape[-2:]

    def one(acc, xs):
        w, e = xs
        w_up = lax.dynamic_slice(wi, (le, e, 0, 0), (1, 1, H, F))[0, 0].astype(F32)
        w_gate = lax.dynamic_slice(wg, (le, e, 0, 0), (1, 1, H, F))[0, 0].astype(F32)
        w_down = lax.dynamic_slice(wo, (le, e, 0, 0), (1, 1, F, H))[0, 0].astype(F32)
        return acc + w[..., None] * _swiglu(h, w_up, w_gate, w_down), None

    per_expert = jnp.moveaxis(weights_held, -1, 0)  # [E_held, S]
    out, _ = lax.scan(one, jnp.zeros_like(h), (per_expert, jnp.arange(wi.shape[1])))
    return out


def expert_mlp(h, blk, experts, le, hp: dict):
    """The MLP of an expert layer on normed ``h`` ``[S, H]``: the shared
    expert plus this chip's part of the routed sum."""
    weights = router_weights(h, blk["gate_wg"], blk.get("gate_bias"), hp)
    first = hp["first_expert"]
    routed = held_experts(h, weights[..., first:first + hp["n_held"]],
                          experts["moe_wi"], experts["moe_wg"], experts["moe_wo"], le)
    return _swiglu(h, blk["shared_wi"], blk["shared_wg"], blk["shared_wo"]) + routed


def attention_block(x, blk, hp: dict, kind: str):
    """The attention half of a layer of kind ``kind`` on ``x`` ``[S, H]``,
    residual added."""
    S = x.shape[0]
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    q = (h @ blk["wq"]).reshape(S, hp["n_q"], hp["d"])
    k = (h @ blk["wk"]).reshape(S, hp["n_kv"], hp["d"])
    v = (h @ blk["wv"]).reshape(S, hp["n_kv"], hp["d"])
    if hp["qk_norm"]:
        q, k = _rms_norm(q, blk["q_norm_scale"], hp["eps"]), _rms_norm(k, blk["k_norm_scale"], hp["eps"])
    if kind == "sliding_attention" or hp["rope_in_full_layers"]:
        q, k = _rope(q, hp["rope_theta"]), _rope(k, hp["rope_theta"])
    window = hp["window"] if kind == "sliding_attention" else None
    a = _attention(q, k, v, hp["n_kv"], window).reshape(S, -1)
    if hp["gate"]:
        a = a * jax.nn.sigmoid(h @ blk["w_attn_gate"])
    o = a @ blk["wo"]
    return x + (_rms_norm(o, blk["ln1_post_scale"], hp["eps"]) if hp["post_norms"] else o)


def mlp_block(x, blk, experts, le, hp: dict):
    """The MLP half of a layer, residual added: dense where ``blk`` has
    ``w_up``, else the shared expert and this chip's routed experts."""
    h = _rms_norm(x, blk["ln2_scale"], hp["eps"])
    m = _swiglu(h, blk["w_up"], blk["w_gate"], blk["w_down"]) if "w_up" in blk else expert_mlp(h, blk, experts, le, hp)
    return x + (_rms_norm(m, blk["ln2_post_scale"], hp["eps"]) if hp["post_norms"] else m)


def layer(x, blk, experts, le, hp: dict, kind: str):
    """One decoder layer of attention kind ``kind`` on ``x`` ``[S, H]``.
    ``blk``: this layer's parameters but the routed experts, float32;
    ``experts``: the stacked expert arrays as stored, of which this layer is
    index ``le`` (unused in a dense layer, which ``blk`` tells by ``w_up``)."""
    return mlp_block(attention_block(x, blk, hp, kind), blk, experts, le, hp)


_EXPERT_KEYS = ("moe_wi", "moe_wg", "moe_wo")
_EXPERT_LAYER_KEYS = ("gate_wg", "gate_bias", "shared_wi", "shared_wg", "shared_wo")
_DENSE_LAYER_KEYS = ("w_up", "w_gate", "w_down")


@partial(jax.jit, static_argnums=(4, 5))
def _layer_fwd(x, blk, experts, le, hp_items, kind):
    return layer(x, {name: a.astype(F32) for name, a in blk.items()}, experts, le, dict(hp_items), kind)


@partial(jax.jit, static_argnums=(3, ))
def _head(x, scale, head_kernel, eps):
    return _rms_norm(x, scale.astype(F32), eps) @ head_kernel.astype(F32)


def layer_params(blocks, l: int, n_dense: int) -> dict:
    """Layer ``l``'s parameters but the routed experts, out of the system's
    stacked arrays: the dense MLP is stacked over the dense layers, the rest
    of an expert layer over the expert layers, everything else over all."""
    out = {}
    for name, a in blocks.items():
        if name in _EXPERT_KEYS:
            continue
        if name in _EXPERT_LAYER_KEYS:
            if l >= n_dense:
                out[name] = a[l - n_dense]
        elif name in _DENSE_LAYER_KEYS:
            if l < n_dense:
                out[name] = a[l]
        else:
            out[name] = a[l]
    return out


def forward_logits(hp: dict, params, ids, positions):
    """Logits ``[B, len(positions), V]`` of the full forward pass over
    ``ids`` ``[B, S]`` at the given positions, one sequence at a time."""
    hp_items = tuple(sorted(hp.items()))
    blocks = params["blocks"]
    experts = {name: blocks[name] for name in _EXPERT_KEYS if name in blocks}
    n_layers = blocks["wq"].shape[0]
    out = []
    with jax.default_matmul_precision("highest"):
        for row in ids:
            x = params["embed"]["embedding"][row].astype(F32) * hp["embed_scale"]
            for l, kind in enumerate(hp["layer_types"][:n_layers]):
                x = _layer_fwd(x, layer_params(blocks, l, hp["n_dense"]), experts,
                               max(l - hp["n_dense"], 0), hp_items, kind)
            out.append(_head(x[jnp.asarray(positions)], params["final_norm"]["scale"],
                             params["lm_head"]["kernel"], hp["eps"]))
    return jnp.stack(out)
