"""The plain reference of the GLM-4.7-Flash decoder (zai-org, ``model_type``
``glm4_moe_lite``): forward pass in straightforward float32 ``jax.numpy`` at
``default_matmul_precision("highest")``, in the EXPANDED form of latent
attention: every head's keys and values are made from the latent and attended
as ordinary multi-head attention. No kernels, no cache, no batching, no
absorption of ``W_kvb`` into the query or the output, no sorting of tokens by
expert: every expert is applied to every position and weighted by the
router's (mostly zero) weight. It shares no code with ``deepspeed_tpu``; it
only reads the system's parameter tree and casts one layer's small matrices,
and inside an expert layer one EXPERT at a time (3 x 2048 x 1536: 38 MB in
float32; a layer's 64 would be 2.4 GB), to float32, so that it fits beside
the system. Attention is computed a block of queries at a time against all
keys (the same numbers as in one piece, a bounded score matrix), so a check
of 17k tokens fits.

The layer, ``x`` ``[S, H]``, every projection without bias,
``n(x; g) = x / sqrt(mean(x^2) + eps) * g``:

    h   = n(x; g_in)
    cq  = n(h W_qa; g_q)                                   [S, q_lora_rank]
    [q_nope_h | q_rope_h] = cq W_qb                        per head, nope | rope
    [ckv | kr] = h W_kva;  ckv = n(ckv; g_kv)              [S, kv_lora_rank], [S, rope]
    kr = rope(kr);  q_rope_h = rope(q_rope_h)              ONE kr for all heads; all rope dims, halves rotated
    [k_nope_h | v_h] = ckv W_kvb_h                         per head, nope | v_head_dim
    score_h = (q_nope_h . k_nope_h + q_rope_h . kr) / sqrt(nope + rope), causal, softmax in float32
    a   = concat_h(softmax(score_h) v_h) W_o;  x = x + a
    h   = n(x; g_pre_mlp)
    l < first_k_dense_replace:  m = (silu(h Wg) * (h Wu)) Wd
    otherwise:                  s = sigmoid(h Wr);  chosen = top-k of s + b
                                w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
                                m = Shared(h) + sum over chosen experts of w_e Expert_e(h)
    x   = x + m
    logits = n(x; g_f) W_lm

The system stores ``W_kvb`` as the two parts its absorbed form multiplies by,
``wkv_b_k`` ``[heads, latent, nope]`` and ``wkv_b_v`` ``[heads, latent, v]``;
here they are the columns of each head's ``W_kvb_h``, used as published.

NOT built, here or in the program: the multi-token-prediction layer
(``num_nextn_predict_layers``): it adds nothing to these logits.

What ``config.json`` has no key for (the pairing of the rotated lanes, the
router's float32, where the bias enters) is the ``glm4_moe_lite`` /
DeepSeek-V3 modelling code, listed under ``assumed`` in the configuration
file. The switches of ``hyper_from_published``'s result are on as published;
a control run turns one off on this side to show that the comparison sees it.
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
    published keys (Hugging Face names of the ``glm4_moe_lite`` model type)."""
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {
        "n_q": cfg["num_attention_heads"], "latent": cfg["kv_lora_rank"], "nope": nope, "rope": rope,
        "v": cfg["v_head_dim"], "eps": cfg["rms_norm_eps"], "rope_theta": float(cfg["rope_theta"]),
        "n_dense": cfg["first_k_dense_replace"], "top_k": cfg["num_experts_per_tok"],
        "route_norm": bool(cfg["norm_topk_prob"]), "route_scale": float(cfg["routed_scaling_factor"]),
        # the score's divisor is the width of a head's query, nope + rope
        "score_dim": nope + rope,
        # on as published: the shared rotated key part, the norm on the latent, the selection bias
        "rope_key": True, "kv_norm": True, "selection_bias": True,
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


def _attention(q, k, v, score_dim: int):
    """Causal softmax attention, one key and value head a query head:
    ``q``/``k`` ``[S, n, dk]``, ``v`` ``[S, n, dv]`` -> ``[S, n, dv]``;
    ``QUERY_BLOCK`` queries at a time against all keys."""
    S, n, dk = q.shape
    blocks = -(-S // QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - S), (0, 0), (0, 0))).reshape(blocks, QUERY_BLOCK, n, dk)
    j = jnp.arange(S)[None, :]

    def one(args):
        qb, i0 = args
        mask = j <= i0 + jnp.arange(QUERY_BLOCK)[:, None]
        scores = jnp.einsum("snd,tnd->nst", qb, k) / math.sqrt(score_dim)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v)

    out = lax.map(one, (q, jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, n, v.shape[-1])[:S]


def router_weights(h, gate_w, bias, hp: dict):
    """``[..., E]`` weights over all experts: each expert's sigmoid score, kept
    for the ``top_k`` largest of score + ``bias`` (the bias chooses, it does
    not weigh), divided by their sum plus 1e-20 if ``route_norm``, times
    ``route_scale``; zero for the others."""
    s = jax.nn.sigmoid(h @ gate_w)
    choose_by = s + bias if hp["selection_bias"] else s
    _, chosen = lax.top_k(choose_by, hp["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if hp["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * hp["route_scale"]
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * w[..., None], axis=-2)


def _swiglu(h, w_up, w_gate, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routed_experts(h, weights, wi, wg, wo, le):
    """``sum_e weights[..., e] . Expert_e(h)``, one expert at a time, each read
    out of expert layer ``le`` of the stacked ``[Le, E, ...]`` arrays and cast
    to float32 as it is used."""
    H, F = wi.shape[-2:]

    def one(acc, xs):
        w, e = xs
        w_up = lax.dynamic_slice(wi, (le, e, 0, 0), (1, 1, H, F))[0, 0].astype(F32)
        w_gate = lax.dynamic_slice(wg, (le, e, 0, 0), (1, 1, H, F))[0, 0].astype(F32)
        w_down = lax.dynamic_slice(wo, (le, e, 0, 0), (1, 1, F, H))[0, 0].astype(F32)
        return acc + w[..., None] * _swiglu(h, w_up, w_gate, w_down), None

    out, _ = lax.scan(one, jnp.zeros_like(h), (jnp.moveaxis(weights, -1, 0), jnp.arange(wi.shape[1])))
    return out


def attention_block(x, blk, hp: dict):
    """The attention half of a layer on ``x`` ``[S, H]``, residual added:
    the expanded form, per-head keys and values made from the latent."""
    S, n, c, nope = x.shape[0], hp["n_q"], hp["latent"], hp["nope"]
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    cq = _rms_norm(h @ blk["wq_a"], blk["q_a_norm_scale"], hp["eps"])
    q = (cq @ blk["wq_b"]).reshape(S, n, nope + hp["rope"])
    kv = h @ blk["wkv_a"]
    ckv = _rms_norm(kv[:, :c], blk["kv_a_norm_scale"], hp["eps"]) if hp["kv_norm"] else kv[:, :c]
    kr = _rope(kv[:, None, c:], hp["rope_theta"])                           # [S, 1, rope]: one for all heads
    if not hp["rope_key"]:
        kr = jnp.zeros_like(kr)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], hp["rope_theta"])], axis=-1)
    k_nope = jnp.einsum("sc,hcn->shn", ckv, blk["wkv_b_k"])                 # each head's W_kvb_h, key columns
    v = jnp.einsum("sc,hcv->shv", ckv, blk["wkv_b_v"])                      # ... and value columns
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr, (S, n, hp["rope"]))], axis=-1)
    return x + _attention(q, k, v, hp["score_dim"]).reshape(S, -1) @ blk["wo"]


def mlp_block(x, blk, experts, le, hp: dict):
    """The MLP half of a layer, residual added: dense where ``blk`` has
    ``w_up``, else the shared expert and the routed ones."""
    h = _rms_norm(x, blk["ln2_scale"], hp["eps"])
    if "w_up" in blk:
        return x + _swiglu(h, blk["w_up"], blk["w_gate"], blk["w_down"])
    weights = router_weights(h, blk["gate_wg"], blk["gate_bias"], hp)
    routed = routed_experts(h, weights, experts["moe_wi"], experts["moe_wg"], experts["moe_wo"], le)
    return x + _swiglu(h, blk["shared_wi"], blk["shared_wg"], blk["shared_wo"]) + routed


_EXPERT_KEYS = ("moe_wi", "moe_wg", "moe_wo")
_EXPERT_LAYER_KEYS = ("gate_wg", "gate_bias", "shared_wi", "shared_wg", "shared_wo")
_DENSE_LAYER_KEYS = ("w_up", "w_gate", "w_down")


@partial(jax.jit, static_argnums=(4, ))
def _layer_fwd(x, blk, experts, le, hp_items):
    hp = dict(hp_items)
    blk = {name: a.astype(F32) for name, a in blk.items()}
    return mlp_block(attention_block(x, blk, hp), blk, experts, le, hp)


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


def first_layer_cache_entries(hp: dict, params, ids):
    """What each token of ``ids`` ``[S]`` caches in layer 0, as published:
    ``[rmsnorm(ckv) | rope(kr)]`` ``[S, latent + rope]`` in float32, AFTER the
    norm and the rope. Layer 0 alone, because its input is the embedding: no
    routed expert lies before it, so the program's cached entries can be held
    to these at the rounding of their type, which the logits (every position
    reads thousands of cached tokens behind flipped experts) cannot be."""
    blk = {name: a.astype(F32) for name, a in layer_params(params["blocks"], 0, hp["n_dense"]).items()
           if name in ("ln1_scale", "wkv_a", "kv_a_norm_scale")}
    c = hp["latent"]
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(params["embed"]["embedding"][jnp.asarray(ids)].astype(F32), blk["ln1_scale"], hp["eps"])
        kv = h @ blk["wkv_a"]
        ckv = _rms_norm(kv[:, :c], blk["kv_a_norm_scale"], hp["eps"])
        return jnp.concatenate([ckv, _rope(kv[:, None, c:], hp["rope_theta"])[:, 0]], axis=-1)


def forward_logits(hp: dict, params, ids, positions):
    """Logits ``[B, len(positions), V]`` of the full forward pass over
    ``ids`` ``[B, S]`` at the given positions, one sequence at a time."""
    hp_items = tuple(sorted(hp.items()))
    blocks = params["blocks"]
    experts = {name: blocks[name] for name in _EXPERT_KEYS}
    out = []
    with jax.default_matmul_precision("highest"):
        for row in ids:
            x = params["embed"]["embedding"][row].astype(F32)
            for l in range(blocks["wo"].shape[0]):
                x = _layer_fwd(x, layer_params(blocks, l, hp["n_dense"]), experts, max(l - hp["n_dense"], 0),
                               hp_items)
            out.append(_head(x[jnp.asarray(positions)], params["final_norm"]["scale"],
                             params["lm_head"]["kernel"], hp["eps"]))
    return jnp.stack(out)
