"""The plain reference: the decoder these configurations publish, forward pass
and next-token loss with its gradient norm, in straightforward float32
``jax.numpy`` at ``default_matmul_precision("highest")``. No kernels, no
cache, no batching tricks. It shares no code with ``deepspeed_tpu``; it only
reads the system's parameter tree (stacked ``[L, ...]`` block arrays), and
casts one layer at a time to float32 so that it fits beside the system.

Departures from the published models, each noted where it is made:
none in the mathematics. GPT-NeoX's ``gelu`` is the exact (erf) form here, as
published; the system computes the tanh approximation, a difference of at
most 5e-4 per activation, far inside bfloat16's own rounding.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def hyper_from_published(cfg: dict) -> dict:
    """The reference's hyper-parameters from a configuration file's published
    keys (Hugging Face names of the Mistral and GPT-NeoX families). A family
    with other names states them under ``"reference_hyper"`` in its file."""
    n_q = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // n_q
    act = cfg.get("hidden_act", "silu")
    hp = {
        "n_q": n_q, "n_kv": cfg.get("num_key_value_heads", n_q), "d": d,
        "norm": "rmsnorm" if "rms_norm_eps" in cfg else "layernorm",
        "eps": cfg.get("rms_norm_eps", cfg.get("layer_norm_eps", 1e-5)),
        "theta": cfg.get("rope_theta", cfg.get("rotary_emb_base", 10000.0)),
        "rotary_dim": int(round(d * cfg.get("rotary_pct", 1.0))),
        "window": cfg.get("sliding_window"),
        "mlp": "swiglu" if act == "silu" else act,
        "parallel_residual": bool(cfg.get("use_parallel_residual", False)),
    }
    hp.update(cfg.get("reference_hyper", {}))
    return hp


def _norm(x, scale, bias, hp):
    if hp["norm"] == "rmsnorm":
        return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + hp["eps"]) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + hp["eps"]) * scale + bias


def _rope(x, hp):
    """Rotary embedding, rotate-half form, on the first ``rotary_dim`` dims of
    each head; ``x`` is ``[B, S, n, d]`` at positions ``0..S-1``."""
    r = hp["rotary_dim"]
    inv = 1.0 / (hp["theta"] ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(q, k, v, hp):
    """Causal softmax attention with grouped KV heads and an optional sliding
    window: query ``i`` sees keys in ``(i - window, i]``."""
    B, S, n_q, d = q.shape
    group = n_q // hp["n_kv"]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bsnd,btnd->bnst", q, k) / math.sqrt(d)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = j <= i
    if hp["window"] is not None:
        mask = mask & (i - j < hp["window"])
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bnst,btnd->bsnd", jax.nn.softmax(scores, axis=-1), v)


def _mlp(h, blk, hp):
    up = h @ blk["w_up"] + blk.get("b_up", 0.0)
    if hp["mlp"] == "swiglu":
        act = jax.nn.silu(h @ blk["w_gate"]) * up
    elif hp["mlp"] == "gelu":
        act = jax.nn.gelu(up, approximate=False)
    else:
        raise ValueError(f"the reference has no activation {hp['mlp']!r}")
    return act @ blk["w_down"] + blk.get("b_down", 0.0)


def layer(x, blk, hp):
    """One decoder layer on ``x`` ``[B, S, H]``; ``blk`` holds this layer's
    float32 parameters."""
    B, S, _ = x.shape
    h1 = _norm(x, blk["ln1_scale"], blk.get("ln1_bias"), hp)
    q = (h1 @ blk["wq"] + blk.get("bq", 0.0)).reshape(B, S, hp["n_q"], hp["d"])
    k = (h1 @ blk["wk"] + blk.get("bk", 0.0)).reshape(B, S, hp["n_kv"], hp["d"])
    v = (h1 @ blk["wv"] + blk.get("bv", 0.0)).reshape(B, S, hp["n_kv"], hp["d"])
    ctx = _attention(_rope(q, hp), _rope(k, hp), v, hp).reshape(B, S, -1)
    attn = ctx @ blk["wo"] + blk.get("bo", 0.0)
    if hp["parallel_residual"]:  # GPT-NeoX: both branches read the layer's input
        h2 = _norm(x, blk["ln2_scale"], blk.get("ln2_bias"), hp)
        return x + attn + _mlp(h2, blk, hp)
    x = x + attn
    return x + _mlp(_norm(x, blk["ln2_scale"], blk.get("ln2_bias"), hp), blk, hp)


def _layer_params(blocks, l):
    """Layer ``l`` of the stacked block arrays, cast to float32."""
    return jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False).astype(F32), blocks)


def _head(x, final_norm, head_kernel, hp):
    h = _norm(x, final_norm["scale"].astype(F32), final_norm.get("bias", jnp.zeros((), F32)).astype(F32), hp)
    return h @ head_kernel.astype(F32)


def _frozen(hp):
    return tuple(sorted(hp.items()))


@partial(jax.jit, static_argnums=(3, ))
def _layer_fwd(x, blocks, l, hp_items):
    return layer(x, _layer_params(blocks, l), dict(hp_items))


@partial(jax.jit, static_argnums=(4, ))
def _layer_bwd(x, blocks, l, dy, hp_items):
    blk = _layer_params(blocks, l)
    _, vjp = jax.vjp(lambda x_, b_: layer(x_, b_, dict(hp_items)), x, blk)
    dx, dblk = vjp(dy)
    return dx, sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(dblk))


def _hidden_states(hp, params, ids):
    hp_items = _frozen(hp)
    xs = [params["embed"]["embedding"].astype(F32)[ids]]
    for l in range(params["blocks"]["wq"].shape[0]):
        xs.append(_layer_fwd(xs[-1], params["blocks"], l, hp_items))
    return xs


def forward_logits(hp: dict, params, ids, positions):
    """Logits ``[B, len(positions), V]`` of the full forward pass over
    ``ids`` ``[B, S]`` at the given positions."""
    with jax.default_matmul_precision("highest"):
        x = _hidden_states(hp, params, ids)[-1][:, jnp.asarray(positions)]
        return jax.jit(partial(_head, hp=hp))(x, params["final_norm"], params["lm_head"]["kernel"])


def loss_and_grad_norm(hp: dict, params, ids):
    """Mean next-token cross entropy over ``ids`` ``[B, S]`` and the global L2
    norm of its gradient over every parameter, taken one layer at a time so
    that no more than one layer's float32 gradient is alive."""
    hp_items = _frozen(hp)

    def head_loss(x, final_norm, head_kernel):
        logp = jax.nn.log_softmax(_head(x, final_norm, head_kernel, hp)[:, :-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))

    @jax.jit
    def head_bwd(x, final_norm, head_kernel):
        loss, vjp = jax.vjp(head_loss, x, final_norm, head_kernel)
        dx, d_norm, d_head = vjp(jnp.ones((), F32))
        sq = jnp.sum(d_head.astype(F32) ** 2) + sum(jnp.sum(g.astype(F32) ** 2)
                                                    for g in jax.tree_util.tree_leaves(d_norm))
        return loss, dx, sq

    @jax.jit
    def embed_sq(dx, embedding):
        g = jnp.zeros(embedding.shape, F32).at[ids].add(dx)
        return jnp.sum(g * g)

    with jax.default_matmul_precision("highest"):
        xs = _hidden_states(hp, params, ids)
        loss, dx, sq = head_bwd(xs[-1], params["final_norm"], params["lm_head"]["kernel"])
        for l in reversed(range(len(xs) - 1)):
            dx, layer_sq = _layer_bwd(xs[l], params["blocks"], l, dx, hp_items)
            sq = sq + layer_sq
        sq = sq + embed_sq(dx, params["embed"]["embedding"])
    return float(loss), float(jnp.sqrt(sq))
