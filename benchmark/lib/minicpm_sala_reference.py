"""The plain reference of the MiniCPM-SALA decoder (openbmb MiniCPM-SALA,
``model_type`` ``minicpm_sala``): forward pass in straightforward float32
``jax.numpy`` at ``default_matmul_precision("highest")``. No kernels, no cache,
no batching, no chunks, no work lists: the linear recurrence is computed TOKEN
BY TOKEN under ``lax.scan``, the selection exactly as written below with a
dense masked softmax over the selected tokens. It shares no code with
``deepspeed_tpu``; it only reads the system's parameter tree and casts one
layer's matrices (and the head a block of the vocabulary at a time) to
float32, and walks the sequence in blocks of tokens, so that 63k tokens at
the published widths fit beside the system's weights.

``n(x; g) = x / sqrt(mean(x^2) + eps) * g``; ``h_0 = scale_emb * embed(ids)``;
``x <- x + c mixer(n(x))``, ``x <- x + c mlp(n(x))`` with ``c = scale_depth /
sqrt(num_hidden_layers)`` at the PUBLISHED depth; logits ``= W_head (n(h_L) /
(hidden_size / dim_model_base))``; the MLP ``W_down (silu(W_gate n) * W_up
n)``. ``mixer_types`` names the mixer of each published layer; a depth cut
runs layers ``first_layer .. first_layer + num_hidden_layers - 1``.

  lightning layer, per head h of ``lightning_nkv``, width d:
    q, k, v = n W_q, n W_k, n W_v;  q_h, k_h <- n_head(q_h; g_q), n_head(k_h; g_k)
    rope over the whole head (theta 10,000, the two halves of a head rotated) on q and k at the absolute position
    q_h <- q_h / sqrt(d)
    S_0 = 0;  S_t = lam_h S_{t-1} + k_t v_t^T;  o_t = S_t^T q_t            float32
    lam_h = exp(-s_h),  s_h = 2^(-8 (h + 1) / heads) * (1 - l / (L - 1) + 1e-5),  l the PUBLISHED layer index of L
    y_t = W_o [ n(o_t over all heads; g_o) * sigmoid(n_t W_g) ]
  sparse layer, ``num_attention_heads`` query / ``num_key_value_heads`` KV heads of d, NO positional encoding:
    q_h, k_g <- n_head(.);  gate = sigmoid(n W_gate)
    kbar_{g,m} = mean(k_{g, stride m .. stride m + kernel - 1})              for every m whose tokens exist
    the query at position t, when t + 1 > dense_len:
      a_{h,m} = softmax_m(q_h . kbar_{g(h),m} / sqrt(d))   over the m with stride m + kernel - 1 <= t
      A_{g,m} = sum_{h in g} a_{h,m};   R_{g,j} = max A_{g,m} over the m whose tokens touch block j
      block j < init_blocks and the blocks that hold positions t - window + 1 .. t score +inf
      sel_g(t) = the topk highest-scoring blocks j <= t // block (ties: the lower j)
      o_h = causal softmax at 1/sqrt(d) of q_h over the tokens <= t of the blocks in sel_{g(h)}(t)
    when t + 1 <= dense_len: over all tokens <= t
    y_t = W_o [ o_t * gate_t ]

``forward`` takes a ``selection`` to use IN PLACE of its own at given
positions (the program's, read back: a top-k is a step function and a block
within rounding of the k-th flips, so the logits are compared on the
program's own choice and the choice against this file's scores), and returns
what the comparison holds beside the logits: every lightning layer's state
after the last token, every sparse layer's pooled keys, and at ``probes`` the
block scores ``R`` with the chosen sets and the attention's output ``o``
(before the gate and ``W_o``) over the selection in force there.

What ``config.json`` has no key for is listed under ``assumed`` in the
configuration file. Each is a switch of ``hyper_from_published``'s result, on
as stated; a control run changes one on this side to show that the comparison
sees it: ``topk``, ``force_window``, ``group_sum``, ``lightning_rope``,
``branch_depth``.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
TOKEN_BLOCK = 2048
QUERY_BLOCK = 32
VOCAB_BLOCK = 16384
_KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def hyper_from_published(cfg: dict) -> dict:
    sc = cfg["sparse_config"]
    first, n = int(cfg.get("first_layer", 0)), cfg["num_hidden_layers"]
    published = int(cfg.get("num_hidden_layers_published", n))
    return {
        "layer_kinds": tuple(_KINDS[m] for m in cfg["mixer_types"][first:first + n]),
        "first_layer": first, "layers_published": published,
        "n_heads": cfg["num_attention_heads"], "n_kv": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "lightning_heads": cfg["lightning_nkv"], "lightning_head_dim": cfg["lightning_head_dim"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg.get("rope_theta", 10000.0)),
        "scale_emb": float(cfg["scale_emb"]), "scale_depth": float(cfg["scale_depth"]),
        "logit_div": cfg["hidden_size"] / cfg["dim_model_base"],
        "kernel_size": sc["kernel_size"], "kernel_stride": sc["kernel_stride"], "block_size": sc["block_size"],
        "topk": sc["topk"], "init_blocks": sc["init_blocks"], "window_size": sc["window_size"],
        "dense_len": sc["dense_len"],
        # the switches of the controls
        "force_window": True, "group_sum": True, "lightning_rope": True, "branch_depth": published,
    }


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta: float):
    """``x`` ``[n, heads, d]``: the two halves of a head rotated, ``(x1, x2) ->
    (x1 cos - x2 sin, x2 cos + x1 sin)`` at ``positions x theta^(-2i/d)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def slopes(hp: dict, layer: int):
    """``s_h`` of the heads of the lightning layer at place ``layer`` of the cut."""
    nh = hp["lightning_heads"]
    published = hp["first_layer"] + layer
    head = 2.0 ** (-8.0 * (np.arange(nh, dtype=np.float64) + 1.0) / nh)
    return jnp.asarray(head * (1.0 - published / max(hp["layers_published"] - 1, 1) + 1e-5), F32)


def recurrence(q, k, v, s, state, live):
    """``S_t = exp(-s) S_{t-1} + k_t v_t^T; o_t = S_t^T q_t`` token by token:
    ``q, k, v`` ``[n, heads, d]``, ``s`` ``[heads]``, ``state`` ``[heads, d,
    d]``, ``live`` ``[n]`` (a padded token leaves the state as it is)."""
    lam = jnp.exp(-s)[:, None, None]

    def step(S, xs):
        qt, kt, vt, on = xs
        S = jnp.where(on, lam * S + kt[:, :, None] * vt[:, None, :], S)
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    state, o = lax.scan(step, state, (q, k, v, live))
    return o, state


def _swiglu(h, blk):
    return (jax.nn.silu(h @ blk["w_gate"]) * (h @ blk["w_up"])) @ blk["w_down"]


def _branch(hp: dict) -> float:
    return hp["scale_depth"] / math.sqrt(hp["branch_depth"])


def lightning_block(x, positions, live, state, blk, s, hp: dict):
    """One block of tokens through a lightning layer and its MLP, from
    ``state``. Returns ``(x, state)``."""
    nh, d, c = hp["lightning_heads"], hp["lightning_head_dim"], _branch(hp)
    n = x.shape[0]
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    q, k, v = ((h @ blk[f"la_w{name}"]).reshape(n, nh, d) for name in "qkv")
    q, k = _rms_norm(q, blk["la_q_norm_scale"], hp["eps"]), _rms_norm(k, blk["la_k_norm_scale"], hp["eps"])
    if hp["lightning_rope"]:
        q, k = _rope(q, positions, hp["theta"]), _rope(k, positions, hp["theta"])
    o, state = recurrence(q / math.sqrt(d), k, v, s, state, live)
    o = _rms_norm(o.reshape(n, nh * d), blk["la_o_norm_scale"], hp["eps"]) * jax.nn.sigmoid(h @ blk["la_wg"])
    x = x + c * (o @ blk["la_wo"])
    return x + c * _swiglu(_rms_norm(x, blk["ln2_scale"], hp["eps"]), blk), state


def keys_values(x, blk, hp: dict):
    """A sparse layer's normed keys and its values of a block of tokens, ``[n, n_kv, d]`` each."""
    n, nkv, d = x.shape[0], hp["n_kv"], hp["head_dim"]
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    return _rms_norm((h @ blk["wk"]).reshape(n, nkv, d), blk["k_norm_scale"], hp["eps"]), (h @ blk["wv"]).reshape(n, nkv, d)


def pooled_keys(K, hp: dict):
    """``kbar_m = mean(K[stride m .. stride m + kernel - 1])`` for every ``m``
    whose tokens exist: ``[M, n_kv, d]`` (``M`` 0 for a sequence shorter than a kernel)."""
    ksize, stride = hp["kernel_size"], hp["kernel_stride"]
    M = max((K.shape[0] - ksize) // stride + 1, 0)
    at = stride * jnp.arange(M, dtype=jnp.int32)[:, None] + jnp.arange(ksize, dtype=jnp.int32)[None, :]
    return jnp.mean(K[at], axis=1)


def block_scores(q, pooled, positions, n_blocks: int, hp: dict):
    """``R`` ``[n, n_kv, n_blocks]`` of queries ``q`` ``[n, heads, d]`` at
    ``positions``: the block scores as written, before the forced blocks."""
    ksize, stride, bs = hp["kernel_size"], hp["kernel_stride"], hp["block_size"]
    n, nq, d = q.shape
    nkv, M = hp["n_kv"], pooled.shape[0]
    g = nq // nkv
    s = jnp.einsum("qngd,mnd->qngm", q.reshape(n, nkv, g, d), pooled) / math.sqrt(d)
    whole = (stride * jnp.arange(M, dtype=jnp.int32) + ksize - 1)[None, :] <= positions[:, None]
    a = jax.nn.softmax(jnp.where(whole[:, None, None, :], s, -jnp.inf), axis=-1)
    a = jnp.where(whole[:, None, None, :], a, 0.0)
    A = jnp.sum(a, axis=2) if hp["group_sum"] else a[:, :, 0]
    # the pooled keys whose tokens touch block j (stride m <= bs j + bs - 1 and stride m + kernel - 1 >= bs j) lie
    # among m = (bs j - kernel + 1) // stride .. (bs j + bs - 1) // stride: those, tested by the two conditions
    j = jnp.arange(n_blocks, dtype=jnp.int32)[:, None]
    m = (bs * j - ksize + 1) // stride + jnp.arange((bs + ksize - 2) // stride + 2, dtype=jnp.int32)[None, :]
    touch = (m >= 0) & (m < M) & (stride * m <= bs * j + bs - 1) & (stride * m + ksize - 1 >= bs * j)
    return jnp.max(jnp.where(touch[None, None], A[:, :, jnp.clip(m, 0, max(M - 1, 0))], 0.0), axis=-1)


def select(R, positions, hp: dict):
    """``sel`` ``[n, n_kv, n_blocks]`` bool: the ``topk`` highest-scoring
    visible blocks with the forced ones at +inf; every visible block at
    ``dense_len`` tokens of context or under."""
    bs = hp["block_size"]
    n_blocks = R.shape[-1]
    j = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    own = (positions // bs)[:, None]
    visible = j <= own
    forced = j < hp["init_blocks"]
    if hp["force_window"]:
        forced = forced | (j >= (jnp.maximum(positions - (hp["window_size"] - 1), 0) // bs)[:, None])
    forced = forced | (j == own)   # a query's own block is always read (it is the window's last)
    r = jnp.where((forced & visible)[:, None, :], jnp.inf, jnp.where(visible[:, None, :], R, -jnp.inf))
    _, idx = lax.top_k(r, min(hp["topk"], n_blocks))
    chosen = jnp.any(idx[..., None] == jnp.arange(n_blocks, dtype=jnp.int32), axis=-2)
    dense = (positions + 1 <= hp["dense_len"])[:, None, None]
    return jnp.where(dense, True, chosen) & visible[:, None, :]


def sparse_block(x, positions, K, V, pooled, given, use_given, blk, hp: dict):
    """One block of query tokens through a sparse layer and its MLP over the
    whole sequence's ``K``, ``V`` and pooled keys. ``given`` ``[n, n_kv,
    n_blocks]`` is used in place of this file's selection where ``use_given``
    ``[n]``. Returns ``(x, R, own, o)``: the block scores, this file's OWN
    selection, whatever was used, and the attention's output ``[n, heads *
    d]`` over what was used."""
    nq, nkv, d, bs, c = hp["n_heads"], hp["n_kv"], hp["head_dim"], hp["block_size"], _branch(hp)
    n, N = x.shape[0], K.shape[0]
    n_blocks = given.shape[-1]
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    q = _rms_norm((h @ blk["wq"]).reshape(n, nq, d), blk["q_norm_scale"], hp["eps"])
    R = block_scores(q, pooled, positions, n_blocks, hp)
    own = select(R, positions, hp)
    sel = jnp.where(use_given[:, None, None], given, own)
    key = jnp.arange(N, dtype=jnp.int32)
    seen = (key[None, :] <= positions[:, None])[:, None, :] & sel[:, :, jnp.minimum(key // bs, n_blocks - 1)]   # [n, n_kv, N]
    s = jnp.einsum("qngd,knd->qngk", q.reshape(n, nkv, nq // nkv, d), K) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen[:, :, None, :], s, -jnp.inf), axis=-1)
    o = jnp.einsum("qngk,knd->qngd", p, V).reshape(n, nq * d)
    x = x + c * ((o * jax.nn.sigmoid(h @ blk["w_attn_gate"])) @ blk["wo"])
    return x + c * _swiglu(_rms_norm(x, blk["ln2_scale"], hp["eps"]), blk), R, own, o


_SPARSE_KEYS = ("wq", "wk", "wv", "wo", "w_attn_gate", "q_norm_scale", "k_norm_scale")


def layer_params(blocks, l: int, kinds) -> dict:
    """Layer ``l``'s parameters out of the system's stacked arrays: the sparse
    layers' attention is stacked over those layers alone, the lightning
    layers' (``la_*``) over theirs, everything else over all."""
    mine = sum(1 for kind in kinds[:l] if kind == kinds[l])
    out = {}
    for name, a in blocks.items():
        if name.startswith("la_"):
            if kinds[l] == "lightning":
                out[name] = a[mine]
        elif name in _SPARSE_KEYS:
            if kinds[l] == "sparse":
                out[name] = a[mine]
        else:
            out[name] = a[l]
    return out


def _cast(blk):
    return {name: a.astype(F32) for name, a in blk.items()}


@partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0, ))
def _lightning_layer(x, n_live, blk, hp_items, layer):
    """``x`` ``[blocks, TOKEN_BLOCK, H]``, the first ``n_live`` tokens real."""
    hp, blk = dict(hp_items), _cast(blk)
    s = slopes(hp, layer)
    nh, d = hp["lightning_heads"], hp["lightning_head_dim"]
    tb = x.shape[1]

    def one(state, xs):
        xb, b = xs
        positions = b * tb + jnp.arange(tb, dtype=jnp.int32)
        xb, state = lightning_block(xb, positions, positions < n_live, state, blk, s, hp)
        return state, xb

    state, x = lax.scan(one, jnp.zeros((nh, d, d), F32), (x, jnp.arange(x.shape[0], dtype=jnp.int32)))
    return x, state


@partial(jax.jit, static_argnums=(2, ))
def _sparse_keys(x, blk, hp_items):
    hp, blk = dict(hp_items), _cast(blk)
    K, V = lax.map(lambda xb: keys_values(xb, blk, hp), x)
    return K.reshape((-1, ) + K.shape[2:]), V.reshape((-1, ) + V.shape[2:])


@partial(jax.jit, static_argnums=(7, ), donate_argnums=(0, ))
def _sparse_queries(x, K, V, pooled, given, use_given, blk, hp_items):
    """``x`` ``[blocks, QUERY_BLOCK, H]``; ``given`` ``[blocks, QUERY_BLOCK, n_kv, n_blocks]``."""
    hp, blk = dict(hp_items), _cast(blk)
    qb = x.shape[1]

    def one(xs):
        xb, gb, ub, b = xs
        return sparse_block(xb, b * qb + jnp.arange(qb, dtype=jnp.int32), K, V, pooled, gb, ub, blk, hp)[0]

    return lax.map(one, (x, given, use_given, jnp.arange(x.shape[0], dtype=jnp.int32)))


@partial(jax.jit, static_argnums=(8, ))
def _sparse_probes(x, positions, K, V, pooled, given, use_given, blk, hp_items):
    """The block scores, this file's own choice and the attention's output of
    the queries ``x`` ``[n, H]`` (a sparse layer's inputs) at ``positions``."""
    return sparse_block(x, positions, K, V, pooled, given, use_given, _cast(blk), dict(hp_items))[1:]


@partial(jax.jit, static_argnums=(3, 4))
def _head_block(x, scale, head_kernel, eps, div):
    return (_rms_norm(x, scale.astype(F32), eps) / div) @ head_kernel.astype(F32)


def _blocked(x, size: int):
    n = x.shape[0]
    pad = -n % size
    return jnp.pad(x, ((0, pad), ) + ((0, 0), ) * (x.ndim - 1)).reshape((-1, size) + x.shape[1:])


def forward(hp: dict, params, row, positions, selection=None, probes=()):
    """One sequence ``row`` ``[N]``. ``selection``: ``{position: [sparse
    layers, n_kv, n_blocks] bool}``, used in place of this file's own choice at
    those positions. Returns a dict: ``logits`` ``[len(positions), V]``;
    ``states`` ``[lightning layers, heads, d, d]`` after the last token;
    ``pooled`` ``[sparse layers, M, n_kv, d]``; ``scores`` and ``chosen``
    ``[len(probes), sparse layers, n_kv, n_blocks]``: at each probe position
    the block scores ``R`` and this file's OWN choice from them; ``attn``
    ``[len(probes), sparse layers, heads * d]``: the attention's output there,
    over ``selection`` where one is given. The hidden
    states live in ONE buffer of whole token blocks (the padding behind the
    last token never reaches a real one: every layer is causal), handed from
    layer to layer."""
    hp_items = tuple(sorted(hp.items()))
    blocks = params["blocks"]
    kinds = hp["layer_kinds"]
    N = int(row.shape[0])
    n_blocks = -(-N // hp["block_size"])
    selection = selection or {}
    padded = -(-N // TOKEN_BLOCK) * TOKEN_BLOCK
    given = np.zeros((padded, sum(k == "sparse" for k in kinds), hp["n_kv"], n_blocks), bool)
    use_given = np.zeros(padded, bool)
    for p, sel in selection.items():
        given[p, :, :, :min(n_blocks, sel.shape[-1])] = np.asarray(sel)[..., :n_blocks]
        use_given[p] = True
    use_given = jnp.asarray(use_given).reshape(-1, QUERY_BLOCK)
    states, pooled_all, scores, chosen, attn = [], [], [], [], []
    probes = np.asarray(list(probes), np.int32)
    with jax.default_matmul_precision("highest"):
        x = _blocked(params["embed"]["embedding"][row].astype(F32) * hp["scale_emb"], TOKEN_BLOCK)
        H = x.shape[-1]
        for l, kind in enumerate(kinds):
            blk = layer_params(blocks, l, kinds)
            place = sum(1 for k in kinds[:l] if k == kind)
            if kind == "lightning":
                x, state = _lightning_layer(x, N, blk, hp_items, l)
                states.append(state)
            else:
                K, V = _sparse_keys(x, blk, hp_items)
                K, V = K[:N], V[:N]
                pooled = pooled_keys(K, hp)
                if probes.shape[0]:
                    R, own, o = _sparse_probes(x.reshape(-1, H)[probes], jnp.asarray(probes), K, V, pooled,
                                               jnp.asarray(given[probes, place]), use_given.reshape(-1)[probes], blk, hp_items)
                    scores.append(R)
                    chosen.append(own)
                    attn.append(o)
                x = _sparse_queries(x.reshape(-1, QUERY_BLOCK, H), K, V, pooled,
                                    jnp.asarray(given[:, place]).reshape(-1, QUERY_BLOCK, hp["n_kv"], n_blocks),
                                    use_given, blk, hp_items).reshape(-1, TOKEN_BLOCK, H)
                pooled_all.append(pooled)
                del K, V
        x = x.reshape(-1, H)[jnp.asarray(list(positions), jnp.int32)]
        kernel = params["lm_head"]["kernel"]
        logits = jnp.concatenate([_head_block(x, params["final_norm"]["scale"], kernel[:, v0:v0 + VOCAB_BLOCK], hp["eps"],
                                              hp["logit_div"]) for v0 in range(0, kernel.shape[1], VOCAB_BLOCK)], axis=-1)
    return {"logits": logits, "states": jnp.stack(states) if states else None,
            "pooled": pooled_all, "scores": jnp.stack(scores, axis=1) if scores else None,
            "chosen": jnp.stack(chosen, axis=1) if chosen else None, "attn": jnp.stack(attn, axis=1) if attn else None}


def forward_logits(hp: dict, params, ids, positions):
    """Logits ``[B, len(positions), V]`` of the full forward pass over ``ids``
    ``[B, S]`` at the given positions, one sequence at a time."""
    return jnp.stack([forward(hp, params, row, positions)["logits"] for row in ids])


@partial(jax.jit, static_argnums=(2, ))
def _lightning_inputs(x, blk, hp_items):
    hp, blk = dict(hp_items), _cast(blk)
    nh, d = hp["lightning_heads"], hp["lightning_head_dim"]
    n = x.shape[0]
    positions = jnp.arange(n, dtype=jnp.int32)
    h = _rms_norm(x, blk["ln1_scale"], hp["eps"])
    q, k, v = ((h @ blk[f"la_w{name}"]).reshape(n, nh, d) for name in "qkv")
    q, k = _rms_norm(q, blk["la_q_norm_scale"], hp["eps"]), _rms_norm(k, blk["la_k_norm_scale"], hp["eps"])
    if hp["lightning_rope"]:
        q, k = _rope(q, positions, hp["theta"]), _rope(k, positions, hp["theta"])
    return q / math.sqrt(d), k, v


def first_lightning_inputs(hp: dict, params, row):
    """``(q, k, v)`` ``[N, heads, d]`` float32 as the FIRST lightning layer's
    recurrence takes them, made by that layer's own projections, norms and rope
    of the embedded tokens ``row`` (the layers before it left out: the inputs
    only have to be of the kind the recurrence sees)."""
    kinds = hp["layer_kinds"]
    l = kinds.index("lightning")
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][row].astype(F32) * hp["scale_emb"]
        return _lightning_inputs(x, layer_params(params["blocks"], l, kinds), tuple(sorted(hp.items())))
