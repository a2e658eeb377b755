"""The plain reference of the SDAR decoder (JetLM SDAR-30B-A3B-Chat
``config.json``, ``model_type`` ``sdar_moe``) and of its generation by masked
diffusion over blocks: straightforward float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. No kernels, no cache, no batching,
no sorting of tokens by expert: every expert is applied to every position
and weighted by the router's (mostly zero) weight, and every forward of
``generate`` recomputes the whole sequence. It shares no code with
``deepspeed_tpu``; it only reads the system's parameter tree (stacked ``[L,
...]`` block arrays, experts stacked ``[L, E, ...]``) and casts one layer,
and inside it one expert, at a time to float32, so that it fits beside the
system.

The layer, from the published keys (pre-norm, no biases, untied head), for
one sequence of tokens at absolute positions ``p``:

    q, k, v = Wq n1(x), Wk n1(x), Wv n1(x)          32 / 4 / 4 heads of 128
    q, k = rope_p(g_q . rms_128(q)), rope_p(g_k . rms_128(k))
    a = x + Wo softmax(q k^T / sqrt(128) + mask) v
    y = a + sum_{e in top-8(P)} P_e / sum_{top-8} P . W2_e(silu(Wg_e h) * W1_e h)
    h = n2(a),  P = softmax(Wr h) over all 128 experts, in float32

``rms_128`` is an RMSNorm over each head's own 128 dimensions with ONE gain
vector of 128 for all heads; ``rope_p`` rotates halves over the whole head at
theta 1,000,000 with no scaling. The ``mask`` is block-causal on absolute
positions: query at ``p_i`` sees key at ``p_j`` iff ``p_j // B <= p_i // B``
(causal between blocks of ``B``, full inside one), the prompt included; or
whatever boolean ``visible`` ``[T, T]`` the caller gives outright.

Generation (``generate``): the logits at position ``i`` predict the token AT
``i``. The prompt's whole blocks are context; its last partial block opens
the first generated block, fixed, beside MASK ids. A block is denoised by up
to ``denoising_steps`` forwards over (committed tokens + the block's ids),
each unmasking by the strategy among the positions still masked, a position's
token being its argmax and its confidence the softmax probability of that
token: ``low_confidence_static`` the ``B // steps`` (one more in the first ``B
% steps`` forwards) of highest confidence, ``low_confidence_dynamic`` every
one whose confidence is over ``confidence_threshold`` and at least the most
confident; the last forward takes what is left; equal confidences go by
position. There is no commit forward here: nothing is cached.

Departures from the published description: none in the mathematics. Not in
``config.json`` and taken from the model's published modelling and generation
code (the configuration file lists each under ``assumed``): the block length,
the mask token, the unmasking rules, the shape of the q/k norm.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


def hyper_from_published(cfg: dict) -> dict:
    """The reference's hyper-parameters from the configuration file: the
    published keys (Hugging Face names of the ``sdar_moe`` model type), and
    what the file assumes of the generation (``engine.generation``) and of
    the mask token (``overrides.mask_token_id``)."""
    gen = cfg["engine"]["generation"]
    return {
        "n_q": cfg["num_attention_heads"], "n_kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"], "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "block": int(gen["block_length"]), "mask_id": int(cfg["overrides"]["mask_token_id"]),
        "denoising_steps": int(gen["denoising_steps"]),
        "confidence_threshold": float(gen.get("confidence_threshold", 0.9)),
    }


def block_causal(positions, block: int):
    """``visible[i, j]``: the query at ``positions[i]`` sees the key at
    ``positions[j]``."""
    p = jnp.asarray(positions)
    return (p[None, :] // block) <= (p[:, None] // block)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta: float):
    """Rotate-half rotary embedding on ``x`` ``[T, n, d]`` at ``positions`` ``[T]``."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, n_kv: int, visible):
    """Softmax attention with grouped KV heads under ``visible`` ``[T, T]``."""
    T, n_q, d = q.shape
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("snd,tnd->nst", q, k) / math.sqrt(d)
    scores = jnp.where(visible[None], scores, -jnp.inf)
    return jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v)


def router_weights(h, gate, top_k: int, norm_topk_prob: bool):
    """``[T, E]`` weights of every expert: the softmax over all experts, kept
    for the ``top_k`` largest (divided by their sum if ``norm_topk_prob``),
    zero for the others."""
    p = jax.nn.softmax(h @ gate, axis=-1)
    top_p, top_e = lax.top_k(p, top_k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(top_e, p.shape[-1], dtype=F32) * top_p[..., None], axis=-2)


def _experts(h, weights, blk):
    """``sum_e weights[:, e] . W2_e(silu(Wg_e h) * W1_e h)``, one expert at a
    time, each cast to float32 as it is used."""
    def one(acc, xs):
        w, w1, wg, w2 = xs
        w1, wg, w2 = w1.astype(F32), wg.astype(F32), w2.astype(F32)
        return acc + w[:, None] * ((jax.nn.silu(h @ wg) * (h @ w1)) @ w2), None

    out, _ = lax.scan(one, jnp.zeros_like(h), (weights.T, blk["moe_wi"], blk["moe_wg"], blk["moe_wo"]))
    return out


def layer(x, blk, hp: dict, positions, visible):
    """One decoder layer on ``x`` ``[T, H]``; ``blk`` holds this layer's
    parameters (the experts still in the type they are stored in, everything
    else float32). ``hp["qk_norm"]`` False leaves the q/k norm out: a control
    of the check, not the model."""
    T = x.shape[0]
    h1 = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    q = (h1 @ blk["wq"]).reshape(T, hp["n_q"], hp["d"])
    k = (h1 @ blk["wk"]).reshape(T, hp["n_kv"], hp["d"])
    v = (h1 @ blk["wv"]).reshape(T, hp["n_kv"], hp["d"])
    if hp.get("qk_norm", True):
        q = _rms_norm(q, blk["q_norm_scale"], hp["eps"])
        k = _rms_norm(k, blk["k_norm_scale"], hp["eps"])
    q, k = _rope(q, positions, hp["theta"]), _rope(k, positions, hp["theta"])
    a = x + _attention(q, k, v, hp["n_kv"], visible).reshape(T, -1) @ blk["wo"]
    h2 = _rms_norm(a, blk["ln2_scale"], hp["eps"])
    weights = router_weights(h2, blk["gate_wg"], hp["top_k"], hp["norm_topk_prob"])
    return a + _experts(h2, weights, blk)


_EXPERT_KEYS = ("moe_wi", "moe_wg", "moe_wo")


@partial(jax.jit, static_argnums=(5, ))
def _layer_fwd(x, blocks, l, positions, visible, hp_items):
    blk = {name: lax.dynamic_index_in_dim(a, l, 0, keepdims=False) for name, a in blocks.items()}
    blk = {name: a if name in _EXPERT_KEYS else a.astype(F32) for name, a in blk.items()}
    return layer(x, blk, dict(hp_items), positions, visible)


@partial(jax.jit, static_argnums=(3, ))
def _head(x, scale, head_kernel, eps):
    return _rms_norm(x, scale.astype(F32), eps) @ head_kernel.astype(F32)


def forward_logits(hp: dict, params, ids, positions, visible=None, at=None):
    """Logits ``[T, V]`` (``[len(at), V]`` where ``at`` lists the tokens
    wanted) of ONE sequence of tokens ``ids`` ``[T]`` standing at the absolute
    ``positions`` ``[T]``. ``visible`` None: the block-causal rule from the
    positions; a boolean ``[T, T]``: that mask outright (so that one pass can
    hold a clean sequence and, behind it, noisy copies of its blocks that see
    the clean blocks before their own, and themselves)."""
    hp_items = tuple(sorted(hp.items()))
    ids, positions = jnp.asarray(ids, jnp.int32), jnp.asarray(positions, jnp.int32)
    visible = block_causal(positions, hp["block"]) if visible is None else jnp.asarray(visible, bool)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][ids].astype(F32)
        for l in range(params["blocks"]["wq"].shape[0]):
            x = _layer_fwd(x, params["blocks"], l, positions, visible, hp_items)
        if at is not None:
            x = x[jnp.asarray(at)]
        return _head(x, params["final_norm"]["scale"], params["lm_head"]["kernel"], hp["eps"])


def unmask(conf, masked, strategy: str, quota: int, threshold: float, last: bool):
    """Which of a block's masked positions one denoise forward unmasks, in
    plain numpy. ``conf`` ``[B]`` float, ``masked`` ``[B]`` bool."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    order = sorted(np.nonzero(masked)[0], key=lambda i: (-conf[i], i))  # most confident first, then by position
    if last:
        chosen = order
    elif strategy == "low_confidence_static":
        chosen = order[:quota]
    elif strategy == "low_confidence_dynamic":
        chosen = [i for i in order if conf[i] > threshold] or order[:1]
    else:
        raise ValueError(f"the reference has no remasking strategy {strategy!r}")
    out = np.zeros(masked.shape, bool)
    out[list(chosen)] = True
    return out


def quotas(block: int, steps: int):
    return [block // steps + (i < block % steps) for i in range(steps)]


def generate(hp: dict, params, prompt, n_blocks: int, strategy: str):
    """``n_blocks`` blocks after the prompt's whole blocks, by the procedure
    in the module's docstring. Returns ``(tokens, forwards)``: the final ids
    of the generated blocks (the prompt's open tokens first) and, for every
    denoise forward, ``{"block", "step", "ids", "logits"}`` (the block's ids
    as fed and its float32 logits ``[B, V]``)."""
    B, mask_id, steps = hp["block"], hp["mask_id"], hp["denoising_steps"]
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    whole = prompt.size // B * B
    committed, open_tokens = list(prompt[:whole]), list(prompt[whole:])
    forwards = []
    for b in range(n_blocks):
        ids = np.asarray((open_tokens if b == 0 else []) + [mask_id] * B, np.int32)[:B]
        for i in range(steps):
            masked = ids == mask_id
            if not masked.any():
                break
            seq = np.concatenate([np.asarray(committed, np.int32), ids])
            logits = np.asarray(forward_logits(hp, params, seq, np.arange(seq.size), at=np.arange(seq.size - B, seq.size)))
            forwards.append({"block": b, "step": i, "ids": ids.copy(), "logits": logits})
            top = logits.max(axis=-1, keepdims=True)
            conf = 1.0 / np.exp(logits - top).sum(axis=-1)
            chosen = unmask(conf, masked, strategy, quotas(B, steps)[i], hp["confidence_threshold"], i == steps - 1)
            ids = np.where(chosen, logits.argmax(axis=-1).astype(np.int32), ids)
        committed += list(ids)
    return np.asarray(committed[whole:], np.int32), forwards
