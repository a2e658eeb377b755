"""The plain reference of the Solar Open 2 decoder (upstage Solar-Open2-250B,
``model_type`` ``solar_open2``): forward pass in straightforward float32
``jax.numpy`` at ``default_matmul_precision("highest")``. No kernels, no cache,
no batching, no chunks: the delta rule is computed TOKEN BY TOKEN under
``lax.scan``, exactly as written below, and every expert held here is applied
to every position and weighted by the router's (mostly zero) weight. It shares
no code with ``deepspeed_tpu``; it only reads the system's parameter tree and
casts one layer's matrices (and inside an expert layer one EXPERT at a time,
and the head a block of the vocabulary at a time) to float32, so that it fits
beside the system.

The layer, ``x`` ``[S, H]``, every projection without bias, ``n(x; g) = x /
sqrt(mean(x^2) + eps) * g``, residual ``x + mixer(n(x))`` then ``x +
mlp(n(x))``, no norm after a branch:

  softmax layer (0, 4, 8, ...: ``gqa_interval`` 3 linear layers between two):
    q, k, v, gate = h Wq, h Wk, h Wv, h Wgate     64 / 8 heads of 128
    NO positional encoding; key j seen by query i iff j <= i
    a = softmax(q k^T / sqrt(128)) v;  y = (a * sigmoid(gate)) Wo
  linear layer (Kimi Delta Attention), per head h of 64, widths 128:
    q~, k~, v~ = h Wq, h Wk, h Wv                each 4096 -> 8192
    c(z)_t = sum_{j=0..3} w_j z_{t-3+j}          causal, depthwise, no bias; z before the sequence is 0
    q, k, v = silu(c(q~)), silu(c(k~)), silu(c(v~))
    q_h <- q_h / sqrt(|q_h|^2 + 1e-6) / sqrt(128);  k_h <- k_h / sqrt(|k_h|^2 + 1e-6)
    g_t = -exp(A_log_h) * softplus(W_f2 (W_f1 h_t) + dt_bias)     per key channel;  a_t = exp(g_t)
    b_t = 2 sigmoid(h_t W_b)                     (the 2: ``kda_allow_neg_eigval``)
    S_0 = 0;  S' = diag(a_t) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
    y_t = W_o [ n_head(o_t; g_o) * sigmoid(W_g2 (W_g1 h_t)) ]
  MLP, every layer:
    s = sigmoid(h W_r) over ALL 320 published experts, float32
    chosen = top-8 of s + b;  w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
    m = Shared(h) + sum over chosen experts HELD HERE of w_e Expert_e(h)      (SwiGLU, 1,280 wide)
  logits = n(x; g_f) W_lm

The share is Trinity's: the configuration states which experts live on this
chip, the router has its published width, and a chosen expert that is not held
adds nothing here, in the program and here alike.

What ``config.json`` has no key for is listed under ``assumed`` in the
configuration file. Each is a switch of ``hyper_from_published``'s result, on
as stated; a control run turns one off on this side to show that the
comparison sees it: ``decay`` (a_t = 1), ``beta_scale`` (2 -> 1),
``l2_norm``, ``selection_bias``, ``gate``.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 256
VOCAB_BLOCK = 32768


def hyper_from_published(cfg: dict) -> dict:
    lin = cfg["linear_attn_config"]
    n_layers = cfg["num_hidden_layers"]
    gqa = set(l for l in cfg["gqa_layers"] if l < n_layers)
    return {
        "n_q": cfg["num_attention_heads"], "n_kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "eps": cfg["rms_norm_eps"],
        "layer_kinds": tuple("gqa" if l in gqa else "kda" for l in range(n_layers)),
        "lin_heads": lin["num_heads"], "lin_dim": lin["head_dim"], "taps": lin["short_conv_kernel_size"],
        "beta_scale": 2.0 if cfg["kda_allow_neg_eigval"] else 1.0,
        "top_k": cfg["num_experts_per_tok"], "route_norm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "n_experts": cfg.get("n_routed_experts_published", cfg["n_routed_experts"]),
        "first_expert": cfg.get("first_expert", 0), "n_held": cfg["n_routed_experts"],
        # what config.json has no key for, on as the configuration file's ``assumed`` states it
        "gate": True, "decay": True, "l2_norm": True, "selection_bias": True,
    }


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _attention(q, k, v, n_kv: int):
    """Causal softmax attention with grouped KV heads and no positions,
    ``[S, n, d]`` in and out, ``QUERY_BLOCK`` queries at a time."""
    S, n_q, d = q.shape
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    blocks = -(-S // QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - S), (0, 0), (0, 0))).reshape(blocks, QUERY_BLOCK, n_q, d)
    j = jnp.arange(S)[None, :]

    def one(args):
        qb, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
        scores = jnp.einsum("snd,tnd->nst", qb, k) / math.sqrt(d)
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
        return jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, axis=-1), v)

    out = lax.map(one, (q, jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, n_q, d)[:S]


def gqa_mixer(h, blk, hp: dict):
    S = h.shape[0]
    q = (h @ blk["wq"]).reshape(S, hp["n_q"], hp["d"])
    k = (h @ blk["wk"]).reshape(S, hp["n_kv"], hp["d"])
    v = (h @ blk["wv"]).reshape(S, hp["n_kv"], hp["d"])
    a = _attention(q, k, v, hp["n_kv"]).reshape(S, -1)
    if hp["gate"]:
        a = a * jax.nn.sigmoid(h @ blk["w_attn_gate"])
    return a @ blk["wo"]


def _causal_conv(z, w):
    """``c(z)_t = sum_j w_j z_{t - (taps - 1) + j}`` over time (axis 0), a
    filter a channel, zeros before the sequence."""
    taps = w.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[j:j + z.shape[0]] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The rule as written, one token at a time: ``q, k, g`` ``[S, n, dk]``,
    ``v`` ``[S, n, dv]``, ``beta`` ``[S, n]``; from a zero state. Returns
    ``(o [S, n, dv], the state after the last token [n, dk, dv])``."""

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[:, :, None] * S
        S = S + kt[:, :, None] * (bt[:, None] * (vt - jnp.einsum("nkv,nk->nv", S, kt)))[:, None, :]
        return S, jnp.einsum("nkv,nk->nv", S, qt)

    state, o = lax.scan(step, jnp.zeros(q.shape[1:] + v.shape[-1:], F32), (q, k, v, g, beta))
    return o, state


def kda_mixer(h, blk, hp: dict):
    """Returns ``(y [S, H], the layer's state after the last token, what the
    rule was fed: (q, k, v, g, beta))``."""
    S, n, d = h.shape[0], hp["lin_heads"], hp["lin_dim"]
    q, k, v = (jax.nn.silu(_causal_conv(h @ blk[f"kda_w{x}"], blk[f"kda_conv_{x}"])).reshape(S, n, d) for x in "qkv")
    if hp["l2_norm"]:
        q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
        k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q = q / math.sqrt(d)
    g = -jnp.exp(blk["kda_A_log"])[None, :, None] * jax.nn.softplus(
        (h @ blk["kda_wf1"]) @ blk["kda_wf2"] + blk["kda_dt_bias"]).reshape(S, n, d)
    if not hp["decay"]:
        g = jnp.zeros_like(g)
    beta = hp["beta_scale"] * jax.nn.sigmoid(h @ blk["kda_wb"])
    o, state = delta_rule(q, k, v, g, beta)
    o = _rms_norm(o, blk["kda_o_norm_scale"], hp["eps"]).reshape(S, n * d)
    return (o * jax.nn.sigmoid((h @ blk["kda_wg1"]) @ blk["kda_wg2"])) @ blk["kda_wo"], state, (q, k, v, g, beta)


def router_weights(h, gate_w, bias, hp: dict):
    """``[S, E]`` weights over ALL published experts: the sigmoid of each
    expert's logit, kept for the ``top_k`` largest of score + ``bias`` (the
    bias chooses, it does not weigh), over their sum plus 1e-20, times
    ``route_scale``; zero for the others."""
    s = jax.nn.sigmoid(h @ gate_w)
    _, chosen = lax.top_k(s + bias if hp["selection_bias"] else s, hp["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if hp["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * hp["route_scale"]
    return jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * w[..., None], axis=-2)


def _swiglu(h, w_up, w_gate, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def held_experts(h, weights_held, wi, wg, wo, l):
    """``sum_e weights_held[:, e] Expert_e(h)`` over the experts held here,
    one at a time, each read out of layer ``l`` of the stacked ``[L, E_held,
    ...]`` arrays and cast to float32 as it is used."""
    H, F = wi.shape[-2:]

    def one(acc, xs):
        w, e = xs
        w_up = lax.dynamic_slice(wi, (l, e, 0, 0), (1, 1, H, F))[0, 0].astype(F32)
        w_gate = lax.dynamic_slice(wg, (l, e, 0, 0), (1, 1, H, F))[0, 0].astype(F32)
        w_down = lax.dynamic_slice(wo, (l, e, 0, 0), (1, 1, F, H))[0, 0].astype(F32)
        return acc + w[:, None] * _swiglu(h, w_up, w_gate, w_down), None

    out, _ = lax.scan(one, jnp.zeros_like(h), (weights_held.T, jnp.arange(wi.shape[1])))
    return out


def mlp(h, blk, experts, l, hp: dict):
    weights = router_weights(h, blk["gate_wg"], blk["gate_bias"], hp)
    first = hp["first_expert"]
    routed = held_experts(h, weights[:, first:first + hp["n_held"]], experts["moe_wi"], experts["moe_wg"],
                          experts["moe_wo"], l)
    return _swiglu(h, blk["shared_wi"], blk["shared_wg"], blk["shared_wo"]) + routed


def layer(x, blk, experts, l, hp: dict, kind: str):
    """One decoder layer on ``x`` ``[S, H]``; ``blk``: this layer's parameters
    but the routed experts, float32. Returns ``(x, state, fed)``: the state
    and what the delta rule was fed, both None for a softmax layer."""
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    y, state, fed = kda_mixer(h, blk, hp) if kind == "kda" else (gqa_mixer(h, blk, hp), None, None)
    x = x + y
    return x + mlp(_rms_norm(x, blk["ln2_scale"], hp["eps"]), blk, experts, l, hp), state, fed


_EXPERT_KEYS = ("moe_wi", "moe_wg", "moe_wo")
_GQA_KEYS = ("wq", "wk", "wv", "wo", "w_attn_gate")


@partial(jax.jit, static_argnums=(4, 5))
def _layer_fwd(x, blk, experts, l, hp_items, kind):
    return layer(x, {name: a.astype(F32) for name, a in blk.items()}, experts, l, dict(hp_items), kind)


@partial(jax.jit, static_argnums=(3, ))
def _head_block(x, scale, head_kernel, eps):
    return _rms_norm(x, scale.astype(F32), eps) @ head_kernel.astype(F32)


def layer_params(blocks, l: int, kinds) -> dict:
    """Layer ``l``'s parameters but the routed experts, out of the system's
    stacked arrays: the softmax layers' are stacked over those layers alone,
    the linear layers' (``kda_*``) over theirs, everything else over all."""
    mine = sum(1 for kind in kinds[:l] if kind == kinds[l])  # this layer's place among its kind
    out = {}
    for name, a in blocks.items():
        if name in _EXPERT_KEYS:
            continue
        if name.startswith("kda_"):
            if kinds[l] == "kda":
                out[name] = a[mine]
        elif name in _GQA_KEYS:
            if kinds[l] == "gqa":
                out[name] = a[mine]
        else:
            out[name] = a[l]
    return out


def forward(hp: dict, params, row, positions, with_fed: bool = False):
    """One sequence ``row`` ``[S]``: ``(logits [len(positions), V], the linear
    layers' states after the last token [n_linear, heads, dk, dv])``; with
    ``with_fed`` a third: what the FIRST linear layer's delta rule was fed,
    ``(q, k, v, g [S, heads, dk], beta [S, heads])``."""
    hp_items = tuple(sorted(hp.items()))
    blocks = params["blocks"]
    experts = {name: blocks[name] for name in _EXPERT_KEYS}
    kinds = hp["layer_kinds"]
    states, first_fed = [], None
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][row].astype(F32)
        for l, kind in enumerate(kinds):
            x, state, fed = _layer_fwd(x, layer_params(blocks, l, kinds), experts, l, hp_items, kind)
            if state is not None:
                states.append(state)
                first_fed = fed if first_fed is None else first_fed
            del fed
        x = x[jnp.asarray(positions)]
        kernel = params["lm_head"]["kernel"]
        logits = jnp.concatenate([_head_block(x, params["final_norm"]["scale"], kernel[:, v0:v0 + VOCAB_BLOCK], hp["eps"])
                                  for v0 in range(0, kernel.shape[1], VOCAB_BLOCK)], axis=-1)
    return (logits, jnp.stack(states), first_fed) if with_fed else (logits, jnp.stack(states))


def forward_logits(hp: dict, params, ids, positions):
    """Logits ``[B, len(positions), V]`` of the full forward pass over ``ids``
    ``[B, S]`` at the given positions, one sequence at a time."""
    return jnp.stack([forward(hp, params, row, positions)[0] for row in ids])
