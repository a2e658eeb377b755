"""The plain reference of the Nemotron-H decoder (nvidia
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type`` ``nemotron_h``): forward
pass in straightforward float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. No kernels, no cache, no batching, no
chunks: the selective scan is computed TOKEN BY TOKEN under ``lax.scan``,
exactly as written below, and every expert held here is applied to every
position and weighted by the router's (mostly zero) weight. It shares no code
with ``deepspeed_tpu``; it only reads the system's parameter tree and casts one
layer's matrices (and inside an expert layer one EXPERT at a time, and the head
a block of the vocabulary at a time) to float32, so that it fits beside the
system.

A layer is ONE branch, ``x <- x + branch(n(x; g))`` with ``n(x; g) = x /
sqrt(mean(x^2) + eps) * g``, every projection without bias; the branch by the
layer's letter in ``hybrid_override_pattern``:

  M, Mamba-2 (64 heads of P = 64, state N = 128, 8 groups, 4 taps):
    [z | xBC | dt] = h W_in                       4,096 | 6,144 | 64
    xBC_t <- silu(sum_{j=0..3} w[j, c] xBC_{t-3+j} + b_c)     causal, depthwise; zeros before the sequence
    x, B, C = xBC                                 64 heads of 64 | 8 groups of 128 | 8 groups of 128
    dt_t,h = softplus(dt_t,h + dt_bias_h);  A_h = -exp(A_log_h);  g = h // 8
    S_0 = 0;  S_t,h = exp(dt_t,h A_h) S_{t-1,h} + dt_t,h x_t,h (x) B_t,g      (S: 64 x 128)
    y_t,h = S_t,h C_t,g + D_h x_t,h
    y <- y * silu(z);  y <- y / sqrt(mean over each group's 512 channels of y^2 + eps) * g_n;  out = y W_out
  E, experts:
    s = sigmoid(h W_r) over ALL 128 published experts, float32
    chosen = top-6 of s + b;  w = s[chosen] / (sum s[chosen] + 1e-20) * 2.5
    m = Shared(h) + sum over chosen experts HELD HERE of w_e Expert_e(h)
    Expert(h) = relu(h W_up)^2 W_down             1,856 wide, no gate matrix; Shared the same at 3,712
  *, attention:
    q, k, v = h Wq, h Wk, h Wv                    32 / 2 heads of 128
    NO positional encoding; key j seen by query i iff j <= i
    out = softmax(q k^T / sqrt(128)) v Wo
  logits = n(x; g_f) W_lm

The share is Trinity's and Solar's: the configuration states which experts
live on this chip, the router has its published width, and a chosen expert
that is not held adds nothing here, in the program and here alike.

What ``config.json`` has no key for is listed under ``assumed`` in the
configuration file. Each is a switch of ``hyper_from_published``'s result, on
as stated; a control run changes one on this side to show that the comparison
sees it: ``dt_bias`` (off: ``dt = softplus(dt_in)``), ``D_skip`` (off: no ``D
x``), ``norm_groups`` (1: the gated norm over all 4,096 channels at once),
``group_of_head`` (``"mod"``: head ``h`` reads B and C of group ``h % 8``),
``activation`` (``"relu"`` for relu squared), ``route_scale`` (1 for 2.5),
``selection_bias``.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 256
VOCAB_BLOCK = 32768
_KINDS = {"M": "mamba", "E": "experts", "*": "gqa"}


def hyper_from_published(cfg: dict) -> dict:
    n_layers = cfg["num_hidden_layers"]
    return {
        "n_q": cfg["num_attention_heads"], "n_kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "eps": cfg["layer_norm_epsilon"],
        "layer_kinds": tuple(_KINDS[c] for c in cfg["hybrid_override_pattern"][:n_layers]),
        "m_heads": cfg["mamba_num_heads"], "m_dim": cfg["mamba_head_dim"], "m_state": cfg["ssm_state_size"],
        "m_groups": cfg["n_groups"], "taps": cfg["conv_kernel"],
        "top_k": cfg["num_experts_per_tok"], "route_norm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "n_experts": cfg.get("n_routed_experts_published", cfg["n_routed_experts"]),
        "first_expert": cfg.get("first_expert", 0), "n_held": cfg["n_routed_experts"],
        "expert_width": cfg["moe_intermediate_size"],
        # what config.json has no key for, on as the configuration file's ``assumed`` states it
        "dt_bias": True, "D_skip": True, "norm_groups": cfg["n_groups"], "group_of_head": "div",
        "activation": "relu2", "selection_bias": True,
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
    return _attention(q, k, v, hp["n_kv"]).reshape(S, -1) @ blk["wo"]


def _causal_conv(z, w, b):
    """``c(z)_t = sum_j w_j z_{t - (taps - 1) + j} + b`` over time (axis 0), a
    filter and a bias a channel, zeros before the sequence."""
    taps = w.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[j:j + z.shape[0]] for j in range(taps)) + b


def selective_scan(x, B, C, dt, A):
    """The rule as written, one token at a time: ``x`` ``[S, n, P]``, ``B, C``
    ``[S, n, N]`` (each head's own group's), ``dt`` ``[S, n]``, ``A`` ``[n]``;
    from a zero state. Returns ``(y [S, n, P] without the D skip, the state
    after the last token [n, P, N])``."""

    def step(S, xs):
        xt, Bt, Ct, dtt = xs
        S = jnp.exp(dtt * A)[:, None, None] * S + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :]
        return S, jnp.einsum("npk,nk->np", S, Ct)

    state, y = lax.scan(step, jnp.zeros(x.shape[1:] + B.shape[-1:], F32), (x, B, C, dt))
    return y, state


def _of_head(a, n: int, how: str):
    """``a`` ``[S, G, N]`` by head ``[S, n, N]``: head ``h`` reads group ``h //
    (n / G)`` (``"div"``, as published) or ``h % G`` (``"mod"``, a control)."""
    G = a.shape[1]
    index = jnp.arange(n) // (n // G) if how == "div" else jnp.arange(n) % G
    return a[:, index]


def mamba_mixer(h, blk, hp: dict):
    """Returns ``(y [S, H], the layer's state after the last token, what the
    scan was fed: (x, B, C [by group], dt, A), the convolution's last ``taps -
    1`` inputs)``."""
    S, n, P, N, G = h.shape[0], hp["m_heads"], hp["m_dim"], hp["m_state"], hp["m_groups"]
    inner = n * P
    z, xbc, dt = jnp.split(h @ blk["m2_w_in"], (inner, inner + inner + 2 * G * N), axis=-1)
    tail = jnp.pad(xbc, ((hp["taps"] - 1, 0), (0, 0)))[-(hp["taps"] - 1):]
    xbc = jax.nn.silu(_causal_conv(xbc, blk["m2_conv_w"], blk["m2_conv_b"]))
    x, B, C = jnp.split(xbc, (inner, inner + G * N), axis=-1)
    x, B, C = x.reshape(S, n, P), B.reshape(S, G, N), C.reshape(S, G, N)
    dt = jax.nn.softplus(dt + blk["m2_dt_bias"] if hp["dt_bias"] else dt)
    A = -jnp.exp(blk["m2_A_log"])
    y, state = selective_scan(x, _of_head(B, n, hp["group_of_head"]), _of_head(C, n, hp["group_of_head"]), dt, A)
    if hp["D_skip"]:
        y = y + blk["m2_D"][None, :, None] * x
    y = y.reshape(S, inner) * jax.nn.silu(z)
    groups = hp["norm_groups"]
    y = y.reshape(S, groups, inner // groups)
    y = (y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + hp["eps"])).reshape(S, inner) * blk["m2_norm_scale"]
    return y @ blk["m2_w_out"], state, (x, B, C, dt, A), tail


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


def _expert(h, w_up, w_down, activation: str):
    up = jax.nn.relu(h @ w_up)
    return (up * up if activation == "relu2" else up) @ w_down


def held_experts(h, weights_held, wi, wo, l, activation: str, F: int):
    """``sum_e weights_held[:, e] Expert_e(h)`` over the experts held here,
    one at a time, each read out of expert layer ``l`` of the stacked ``[L,
    E_held, ...]`` arrays and cast to float32 as it is used. ``F``: the
    PUBLISHED width of an expert: its first ``F`` hidden units are read,
    whatever width the system stores its matrices at."""
    H = wi.shape[-2]

    def one(acc, xs):
        w, e = xs
        w_up = lax.dynamic_slice(wi, (l, e, 0, 0), (1, 1, H, F))[0, 0].astype(F32)
        w_down = lax.dynamic_slice(wo, (l, e, 0, 0), (1, 1, F, H))[0, 0].astype(F32)
        return acc + w[:, None] * _expert(h, w_up, w_down, activation), None

    out, _ = lax.scan(one, jnp.zeros_like(h), (weights_held.T, jnp.arange(wi.shape[1])))
    return out


def expert_layer(h, blk, experts, l, hp: dict, shared: bool = True):
    """The expert layer's branch on the normed ``h``; ``l``: the layer's place
    among the expert layers; ``shared`` False leaves the shared expert out (a
    second chip's share of the same layer: the shared expert is counted once)."""
    weights = router_weights(h, blk["gate_wg"], blk["gate_bias"], hp)
    first = hp["first_expert"]
    routed = held_experts(h, weights[:, first:first + hp["n_held"]], experts["moe_wi"], experts["moe_wo"], l, hp["activation"],
                          hp["expert_width"])
    return routed + (_expert(h, blk["shared_wi"], blk["shared_wo"], hp["activation"]) if shared else 0.0)


def layer(x, blk, experts, l, hp: dict, kind: str):
    """One decoder layer on ``x`` ``[S, H]``; ``blk``: this layer's parameters
    but the routed experts, float32; ``l``: the layer's place among its kind.
    Returns ``(x, state, fed, tail)``: the state, what the scan was fed and
    the convolution's tail, all None for a layer that is no Mamba layer."""
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    if kind == "mamba":
        y, state, fed, tail = mamba_mixer(h, blk, hp)
        return x + y, state, fed, tail
    y = expert_layer(h, blk, experts, l, hp) if kind == "experts" else gqa_mixer(h, blk, hp)
    return x + y, None, None, None


_EXPERT_KEYS = ("moe_wi", "moe_wo")
_EXPERT_LAYER_KEYS = ("gate_wg", "gate_bias", "shared_wi", "shared_wo")
_GQA_KEYS = ("wq", "wk", "wv", "wo")


@partial(jax.jit, static_argnums=(4, 5))
def _layer_fwd(x, blk, experts, l, hp_items, kind):
    return layer(x, {name: a.astype(F32) for name, a in blk.items()}, experts, l, dict(hp_items), kind)


@partial(jax.jit, static_argnums=(3, ))
def _head_block(x, scale, head_kernel, eps):
    return _rms_norm(x, scale.astype(F32), eps) @ head_kernel.astype(F32)


def layer_params(blocks, l: int, kinds) -> dict:
    """Layer ``l``'s parameters but the routed experts, out of the system's
    stacked arrays: each kind's are stacked over the layers of that kind
    alone (``m2_*`` over the Mamba layers, the attention matrices over the
    attention layers, the router and the shared expert over the expert
    layers), the layer's norm over all."""
    mine = sum(1 for kind in kinds[:l] if kind == kinds[l])  # this layer's place among its kind
    of_kind = {"mamba": lambda n: n.startswith("m2_"), "gqa": lambda n: n in _GQA_KEYS,
               "experts": lambda n: n in _EXPERT_LAYER_KEYS}[kinds[l]]
    out = {name: a[mine] for name, a in blocks.items() if name not in _EXPERT_KEYS and of_kind(name)}
    out["ln1_scale"] = blocks["ln1_scale"][l]
    return out


def forward(hp: dict, params, row, positions, with_fed: bool = False, with_tails: bool = False):
    """One sequence ``row`` ``[S]``: ``(logits [len(positions), V], the Mamba
    layers' states after the last token [n_mamba, heads, P, N])``; with
    ``with_fed`` a third: what the FIRST Mamba layer's scan was fed, ``(x [S,
    heads, P], B, C [S, groups, N], dt [S, heads], A [heads])``; with
    ``with_tails`` one more: the Mamba layers' convolution tails after the
    last token ``[n_mamba, taps - 1, channels]``."""
    hp_items = tuple(sorted(hp.items()))
    blocks = params["blocks"]
    experts = {name: blocks[name] for name in _EXPERT_KEYS}
    kinds = hp["layer_kinds"]
    states, tails, first_fed = [], [], None
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][row].astype(F32)
        for l, kind in enumerate(kinds):
            mine = sum(1 for k in kinds[:l] if k == kind)
            x, state, fed, tail = _layer_fwd(x, layer_params(blocks, l, kinds), experts, mine, hp_items, kind)
            if state is not None:
                states.append(state)
                tails.append(tail)
                first_fed = fed if first_fed is None else first_fed
            del fed
        x = x[jnp.asarray(positions)]
        kernel = params["lm_head"]["kernel"]
        logits = jnp.concatenate([_head_block(x, params["final_norm"]["scale"], kernel[:, v0:v0 + VOCAB_BLOCK], hp["eps"])
                                  for v0 in range(0, kernel.shape[1], VOCAB_BLOCK)], axis=-1)
    out = (logits, jnp.stack(states)) + ((first_fed, ) if with_fed else ()) + ((jnp.stack(tails), ) if with_tails else ())
    return out


def forward_logits(hp: dict, params, ids, positions):
    """Logits ``[B, len(positions), V]`` of the full forward pass over ``ids``
    ``[B, S]`` at the given positions, one sequence at a time."""
    return jnp.stack([forward(hp, params, row, positions)[0] for row in ids])
