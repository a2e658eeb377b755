"""The serving system under test for a model whose softmax layers attend a
LEARNED BLOCK SELECTION over pooled keys cached beside K and V, between
linear-attention layers that hold a recurrent state a sequence
(MiniCPM-SALA): what ``builders/serve.py`` builds, with ONE model for every
seed (``serve_share.WEIGHTS_WORD`` and its reason: a deployment serves one
model) and a check of its own.

* **The weights.** ``serve.make_params`` with every norm gain drawn about one
  (``1 + 0.1 N(0, 1)``: a norm left out shows) and the lightning layers' decay
  exponents as the program's own ``init`` states them (constants of the head
  and the published layer; the reference computes them from the file).
* **The check** feeds one sequence the way the timed traffic is fed, in a FULL
  HOUSE (``max_tracked_sequences - 2`` other sequences decode beside it from
  its first chunk on, its row in the middle of them, in the state slot and on
  the KV blocks that a sequence which ran and was flushed left dirty): a
  ``prompt_tokens`` prompt (the mix's longest) in chunks of what the token
  budget leaves beside a token of every other row (no multiple of the pooling
  stride, the block or the lightning tile, so pooling kernels, blocks and
  tiles straddle ``put`` steps, and the prompt crosses ``dense_len`` in its
  fifth chunk); ``ride_positions`` decoded as one-token rows beside the
  chunks of a last sequence's prompt (the tiled kernel under a selection, a
  short tile beside a long run); ``decode_tokens`` through ``engine.decode``'s
  horizon, every row in it (the decode kernel under a selection, the
  recurrent step); ``tail_positions`` more one-token ``put`` steps. Logits,
  not tokens; the horizon's tokens are the program's own, so the reference
  runs afterwards over the final sequence, the engine's pools dropped
  meanwhile. A top-k is a step function: a block whose score is within
  rounding of the k-th flips, and logits then stand on a floor of flipped
  blocks. With random weights the logits hardly feel which blocks a layer
  attends at all (an average of thousands of random values is a small part of
  the residual stream), so what the paged kernels made of the selection is
  read back itself. The check holds SIX things, at the PROBED positions: a
  few tokens of the check's row in every call that is read (``put(sample=
  "probe")``: the call's last token, which has logits, and three inside a
  chunk: the middle of the tiled kernel's tiles and their ends):
  (a) **the selection**: at every probed position past ``dense_len`` the
  program's own choice against the
  reference's scores: the forced blocks present, exactly ``topk`` a KV head,
  every block the program chose and the reference did not within
  ``select_margin`` (relative) of the reference's k-th score, and the share
  of equal choices at least ``select_share`` of the blocks no rule forces and
  ``select_share_all`` of the whole set;
  (b) **the logits** against the reference run ON the program's selection at
  those positions (``rel_l2_tol`` on each, ``quantile_tol`` on the lower
  quartile);
  (f) **the attention's output** of every sparse layer at those positions,
  as the paged kernel that served the step gave it back (before the gate and
  ``W_o``), against the reference's masked softmax over the program's
  selection (``attn_tol`` on the worst position and layer): a kernel that
  ignored the mask, or fetched another block than the list names, parts here
  and nowhere else;
  (c) **the lightning state** of the sequence read back out of its slot
  after its last token, a layer at a time (``state_tol``);
  (d) **the pooled keys** read back out of the pool through the sequence's
  block table against the mean of the reference's keys, every sparse layer
  (``pooled_tol`` on the worst pooled key's relative L2: a kernel that
  straddles a chunk boundary is one key among thousands);
  (e) **the recurrence alone** (``rule_tol``): the program's two forms on the
  engine's own state pool, fed the REFERENCE's q, k, v of the first lightning
  layer, against the reference's recurrence token by token: inputs common to
  both sides leave the state's arithmetic alone to be seen, which bf16
  activations hide in (c).
* **Controls** (``python3 benchmark/builders/serve_sparse.py --workload
  <cell> --seeds a,b [--rehearsal]``): the same check, one thing changed, each
  NOT correct. On the reference's side: ``top_half`` (top-k halved),
  ``window_not_forced``, ``one_head`` (the group's sum replaced by its first
  head), ``no_lightning_rope``, ``branch_cut_depth`` (``scale_depth /
  sqrt(the cut's layers)``). Behind the program's back, no switch in it:
  ``mask_ignored`` (the attention module handed an all-true selection in
  place of the indexer's: dense attention under a selection's name),
  ``block_shifted`` (handed the indexer's with ONE block a token a KV head
  moved one column down: the lowest it reads past block 0), ``stale_pooled`` (``sparse_index.update_pooled_keys`` patched to skip a
  kernel that begins before the step's first token of its row: the boundary
  kernel missing), ``state_bf16`` (the state pool rounded to bfloat16 after
  every call, the horizon in calls of one step), ``padding_touches``
  (``lightning.tile_plan`` patched so that a row's last tile counts its
  padding as tokens).
"""

import functools
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

REFERENCE_CONTROLS = {"top_half": "topk", "window_not_forced": {"force_window": False}, "one_head": {"group_sum": False},
                      "no_lightning_rope": {"lightning_rope": False}, "branch_cut_depth": "branch_depth"}
_PATCHED = ("mask_ignored", "block_shifted", "stale_pooled", "padding_touches")   # a function of the program replaced
PROGRAM_CONTROLS = _PATCHED + ("state_bf16", )


def make_params(cell, serve):
    from benchmark.lib import loader

    word = loader.load_module("builders", "serve_share", cell["root"]).WEIGHTS_WORD

    def draw(model, seed_word, dtype):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from deepspeed_tpu.models.transformer import lightning_slopes

        params = serve.make_params(model, np.uint32(word), dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(1), word)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        leaves = [(1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)).astype(dtype)
                  if str(getattr(path[-1], "key", path[-1])).endswith("scale") else leaf
                  for i, (path, leaf) in enumerate(leaves)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        params["blocks"]["la_slope"] = jnp.asarray(lightning_slopes(model.config), jnp.float32)
        return params

    return draw


def make_engine(cell, model, params, dtype, rehearsal: bool):
    from deepspeed_tpu.inference.v2 import DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig

    ec = cell["config_file"]["engine"]
    sm = DSStateManagerConfig(max_tracked_sequences=ec["max_tracked_sequences"],
                              max_ragged_batch_size=ec["max_ragged_batch_size"],
                              max_ragged_sequence_count=ec["max_ragged_sequence_count"], max_context=ec["max_context"],
                              token_buckets=tuple(ec["token_buckets"]), seq_buckets=tuple(ec["seq_buckets"]))
    icfg = RaggedInferenceEngineConfig(kv_block_size=ec["kv_block_size"], num_kv_blocks=ec["num_kv_blocks"],
                                       kv_memory_fraction=ec.get("kv_memory_fraction", 0.8), kv_dtype=dtype,
                                       state_manager=sm, cut_rows_on_host=bool(ec["cut_rows_on_host"]))
    if rehearsal:  # the same kernel programs through the Pallas interpreter
        icfg.modules.attention = {"name": "paged_pallas_attention", "implementation_config": {"interpret": True}}
    return InferenceEngineV2(model, icfg, params=params)


def without_pools(engine, work, keep=()):
    """``work()`` while the engine's pools (but those in ``keep``) are off the
    device (no sequence is live), then pools of zeros as the engine began with."""
    import jax.numpy as jnp

    kv = engine.state_manager.kv_cache
    names = [n for n in ("k_pool", "v_pool", "index_pool", "state_pool") if n not in keep and getattr(kv, n, None) is not None]
    like = {n: (getattr(kv, n).shape, getattr(kv, n).dtype) for n in names}
    for n in names:
        getattr(kv, n).delete()
        setattr(kv, n, None)
    try:
        return work()
    finally:
        gc.collect()
        for n, (shape, dt) in like.items():
            setattr(kv, n, jnp.zeros(shape, dt))


def drive_check(cell, engine, ids, after_call=None, horizon=None, uid: int = 2**30):
    """Feed the check's sequence beside a full house (see the module's
    docstring). Returns a namespace: ``sequence``, the final tokens;
    ``positions`` and ``logits``, the positions whose logits were fetched and
    those logits; ``probed``, ``chosen`` and ``attended``, the probed positions
    with the program's selection ``[sparse layers, nkv, blocks]`` and its
    attention's output ``[sparse layers, heads * d]`` at each; ``states``
    ``[layers, heads, d, d]`` and ``pooled`` ``[sparse layers, M, nkv, d]``,
    the sequence's lightning states and pooled keys after its last token;
    ``span``, the horizon's positions; ``where``, the row, slot and rows."""
    import numpy as np

    from benchmark.lib import loader

    horizon_calls = loader.load_module("builders", "serve_state", cell["root"]).horizon_calls
    cf, tf = cell["config_file"], cell["traffic_file"]
    ck, sc = cf["check"], cf["sparse_config"]
    budget = int(tf.get("gateway", {}).get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    rows = int(cf["engine"]["max_tracked_sequences"])
    n_prompt, ride, n_decode, tail = (int(ck[k]) for k in ("prompt_tokens", "ride_positions", "decode_tokens",
                                                            "tail_positions"))
    horizon = int(horizon or ck["horizon"])
    after_call = after_call or (lambda eng: None)
    vocab = int(cf["vocab_size"])
    rng = np.random.default_rng([int(ids[0]), 11])
    draw = lambda n: rng.integers(0, vocab, size=n, dtype=np.int32)
    seq = [int(t) for t in ids[:n_prompt + ride]]
    positions, logits, probed, chosen, attended = [], [], [], [], []
    feeder, others, dummy = uid + 1, [uid + 2 + i for i in range(rows - 2)], uid + rows
    at_row = at = len(others) // 2  # the check's row in every call
    house = lambda mid, last=(): others[:at] + mid + others[at:] + list(last)
    kv = engine.state_manager.kv_cache

    def note(pos, out):
        """The check's row alone: its logits, and what its probed tokens selected and attended."""
        rows, (at, picked, ctx) = out
        assert int(at[at_row, -1]) == pos, (at[at_row], pos)
        positions.append(pos)
        logits.append(np.asarray(rows[at_row], np.float32))
        for j, p in enumerate(at[at_row]):
            if int(p) not in probed:
                probed.append(int(p))
                chosen.append(np.asarray(picked[at_row, j]))
                attended.append(np.asarray(ctx[at_row, j], np.float32))

    def call(mid_tokens, last_tokens=(), want=False, pos=None):
        uids = house([uid], [feeder] if len(last_tokens) else [])
        tokens = [draw(1) for _ in others[:at]] + [np.asarray(mid_tokens, np.int32)] + [draw(1) for _ in others[at:]]
        if len(last_tokens):
            tokens.append(np.asarray(last_tokens, np.int32))
        out = engine.put(uids, tokens, sample="probe" if want else "greedy", block=True)
        after_call(engine)
        if want:
            note(pos, out)

    # a sequence that runs and goes before the check's comes: the slot and the blocks it frees hold what it left
    opening = budget // (rows - 1)
    engine.put(house([dummy]), [draw(opening) for _ in range(rows - 1)], sample="greedy")
    after_call(engine)
    engine.flush(dummy)
    chunk = budget - len(others)
    for c0 in range(0, n_prompt, chunk):
        call(seq[c0:min(c0 + chunk, n_prompt)], want=c0 + chunk >= n_prompt, pos=n_prompt - 1)
    fed = budget - len(others) - 1
    feeder_prompt = draw(ride * fed)
    for i in range(ride):  # a one-token row among the others' beside a chunk
        call(seq[n_prompt + i:n_prompt + i + 1], feeder_prompt[i * fed:(i + 1) * fed], want=True, pos=n_prompt + i)
    uids = house([uid], [feeder] if ride else [])
    nxt = draw(len(uids))
    nxt[at] = int(logits[-1].argmax())
    for h in horizon_calls(n_decode, horizon):
        toks = np.asarray(engine.decode(uids, [np.asarray([t], np.int32) for t in nxt], h))
        after_call(engine)
        seq += [int(nxt[at])] + [int(t) for t in toks[at, :-1]]
        nxt = toks[:, -1].astype(np.int32)
    first_tail = len(seq)
    seq += [int(t) for t in ids[n_prompt + ride:n_prompt + ride + tail]]
    for j in range(first_tail, len(seq)):
        tokens = [draw(1) for _ in uids]
        tokens[at] = np.asarray(seq[j:j + 1], np.int32)
        out = engine.put(uids, tokens, sample="probe", block=True)
        after_call(engine)
        note(j, out)
    desc = engine.state_manager.get_sequence(uid)
    slot = int(desc.state_slot)
    states = np.asarray(kv.state_pool[:, slot], np.float32)
    per = int(cf["engine"]["kv_block_size"]) // int(sc["kernel_stride"])
    n_pooled = max((len(seq) - int(sc["kernel_size"])) // int(sc["kernel_stride"]) + 1, 0)
    m = np.arange(n_pooled)
    at_pool = np.asarray(desc.kv_blocks, np.int64)[m // per] * per + m % per
    pooled = np.asarray(kv.index_pool[:, at_pool], np.float32)
    for u in uids:
        engine.flush(u)
    return SimpleNamespace(sequence=np.asarray(seq, np.int32), positions=positions, logits=logits, probed=probed, chosen=chosen,
                           attended=attended, states=states, pooled=pooled, span=(n_prompt + ride, first_tail),
                           where={"row": at, "slot": slot, "rows": len(uids)})


@functools.lru_cache(maxsize=1)
def _rule_programs(use_pallas: bool, interpret: bool):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import lightning

    from benchmark.lib import minicpm_sala_reference as reference

    options = {"use_pallas": use_pallas, "interpret": interpret}

    def chunks(pool, arrs, slope, src, slot, fresh, n_tok):
        flat = pool.reshape((-1, ) + pool.shape[2:])
        _, flat = lightning.lightning_chunks(*(a[src] for a in arrs), slope, flat, slot, fresh, n_tok, **options)
        return flat.reshape(pool.shape)

    def step(pool, arrs, slope, src, slot, fresh, n_live):
        flat = pool.reshape((-1, ) + pool.shape[2:])
        _, flat = lightning.lightning_step(*(a[src] for a in arrs), slope, flat, slot, fresh, n_live, **options)
        return flat.reshape(pool.shape)

    def plain(arrs, slope, idx):
        q, k, v = (a[idx] for a in arrs)
        return reference.recurrence(q, k, v, slope, jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32),
                                    jnp.ones(idx.shape[0], bool))[1]

    return SimpleNamespace(chunks=jax.jit(chunks, donate_argnums=0), step=jax.jit(step, donate_argnums=0), plain=jax.jit(plain),
                           ones=jax.jit(jnp.ones_like, donate_argnums=0), zeros=jax.jit(jnp.zeros_like, donate_argnums=0))


def rule_check(cell, engine, fed, slope, rehearsal: bool, after_call=None):
    """The program's recurrence ALONE on what the reference fed its own first
    lightning layer (``fed``: ``(q, k, v)`` of ``N`` tokens, float32): the two
    forms as ``ragged_forward`` calls them, on the engine's own state pool
    filled with ones, a full house of rows in the window's shapes (the check's
    row the tokens in chunks beside one token of every other row, then one
    token a row a step), against the reference's recurrence token by token on
    the same inputs. Returns the relative L2 of some rows' states after the
    last chunk (``"chunks"``) and after the last step (``"steps"``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cf, tf = cell["config_file"], cell["traffic_file"]
    kv = engine.state_manager.kv_cache
    after_call = after_call or (lambda eng: None)
    budget = int(tf.get("gateway", {}).get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    R = int(cf["engine"]["max_tracked_sequences"])
    N = int(fed[0].shape[0])
    steps = min(int(cf["check"]["rule_steps"]), N // 4)
    n_chunked, c, li = N - steps, (R - 2) // 2, kv.state_pool.shape[0] // 2
    chunk = budget - (R - 1)
    slots = np.random.default_rng([R, 5]).permutation(R).astype(np.int32)
    slot_li = jnp.asarray(li * R + slots)
    on_tpu = jax.default_backend() == "tpu"
    run = _rule_programs(on_tpu, bool(rehearsal and not on_tpu))
    streams = [[] for _ in range(R)]

    def take(r, n):
        idx = [(len(streams[r]) + j + 17 * (r - c)) % N for j in range(n)]
        streams[r] += idx
        return idx

    def read():
        out = {}
        with jax.default_matmul_precision("highest"):
            for r in sorted({0, c - 1, c, c + 1, R - 1}):
                want = np.asarray(run.plain(fed, slope, jnp.asarray(np.asarray(streams[r], np.int32))))
                got = np.asarray(kv.state_pool[li, int(slots[r])])
                out[r] = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        return out

    kv.state_pool = run.ones(kv.state_pool)
    for c0 in range(0, n_chunked, chunk):
        n_tok = np.ones(R, np.int32)
        n_tok[c] = min(chunk, n_chunked - c0)
        src = np.zeros(budget, np.int32)
        flat = [i for r in range(R) for i in take(r, int(n_tok[r]))]
        src[:len(flat)] = flat
        kv.state_pool = run.chunks(kv.state_pool, fed, slope, jnp.asarray(src), slot_li, jnp.asarray(np.full(R, c0 == 0)),
                                   jnp.asarray(n_tok))
        after_call(engine)
    out = {"chunks": read()}
    for _ in range(steps):
        src = np.asarray([take(r, 1)[0] for r in range(R)], np.int32)
        kv.state_pool = run.step(kv.state_pool, fed, slope, jnp.asarray(src), slot_li, jnp.zeros(R, bool), jnp.asarray(R, jnp.int32))
        after_call(engine)
    out["steps"] = read()
    kv.state_pool = run.zeros(kv.state_pool)
    return out


def selection_check(cell, positions, chosen, ref_scores, ref_own) -> dict:
    """(a): the program's choice against the reference's scores and own choice
    at every probed position with more than ``dense_len`` tokens of context. The forced blocks are the FILE's statement, whatever switch the
    reference ran under; the share of equal choices is taken twice: over the
    blocks the reference chose that no rule forces (``select_share``: the
    forced ones agree by construction and are half of the set), and over the
    reference's whole set (``select_share_all``: a reference that forces other
    blocks, or admits fewer, parts from the program there)."""
    import numpy as np

    ck, sc = cell["config_file"]["check"], cell["config_file"]["sparse_config"]
    bs, topk = int(sc["block_size"]), int(sc["topk"])
    worst_gap, shares, whole, forced_ok, count_ok = 0.0, [], [], True, True
    for p, mine, scores, theirs in zip(positions, chosen, ref_scores, ref_own):
        n_blocks = scores.shape[-1]
        own = p // bs
        mine = mine[..., :n_blocks]
        j = np.arange(n_blocks)
        visible = j <= own
        if p + 1 <= sc["dense_len"]:
            count_ok &= bool((mine == visible).all())
            continue
        forced = visible & ((j < sc["init_blocks"]) | (j >= max(p - (sc["window_size"] - 1), 0) // bs))
        forced_ok &= bool(mine[..., forced].all())
        count_ok &= bool((mine.sum(-1) == topk).all() and not mine[..., ~visible].any())
        learned = theirs & ~forced
        kth = np.min(np.where(learned, scores, np.inf), axis=-1, keepdims=True)   # the reference's last admitted score
        extra = mine & ~theirs & ~forced
        gap = np.where(extra & np.isfinite(kth), (kth - scores) / np.maximum(np.where(np.isfinite(kth), kth, 1.0), 1e-30), 0.0)
        worst_gap = max(worst_gap, float(gap.max()))
        shares.append(float((mine & learned).sum() / max((topk - forced.sum()) * mine.shape[0] * mine.shape[1], 1)))
        whole.append(float((mine & theirs).sum() / (topk * mine.shape[0] * mine.shape[1])))
    share, share_all = (float(min(x)) if x else 1.0 for x in (shares, whole))
    return {"select_gap_max": worst_gap, "select_margin": float(ck["select_margin"]), "select_share_min": share,
            "select_share_mean": float(np.mean(shares)) if shares else 1.0, "select_share": float(ck["select_share"]),
            "select_share_all_min": share_all, "select_share_all": float(ck["select_share_all"]),
            "select_forced_ok": forced_ok, "select_count_ok": count_ok, "select_positions": len(shares),
            "select_ok": bool(forced_ok and count_ok and worst_gap <= ck["select_margin"] and share >= ck["select_share"]
                              and share_all >= ck["select_share_all"])}


def compare(cell, run, ref, rule) -> dict:
    """The decision (see the module's docstring) on what :func:`drive_check` read."""
    import numpy as np

    from benchmark.lib import loader

    routed = loader.load_module("builders", "serve_routed", cell["root"])
    ck = cell["config_file"]["check"]
    positions, logits, states, pooled = run.positions, run.logits, run.states, run.pooled
    rel = [float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(logits, ref["logits"])]
    check = {"positions": positions, "rel_l2": rel, "rel_l2_tol": ck["rel_l2_tol"],
             "finite": bool(all(np.isfinite(g).all() for g in logits) and np.isfinite(states).all()),
             "argmax_equal": [int(g.argmax()) == int(r.argmax()) for g, r in zip(logits, ref["logits"])]}
    check["ok"] = bool(check["finite"] and max(rel) <= ck["rel_l2_tol"])
    out = routed.decide(check, float(ck["quantile"]), float(ck["quantile_tol"]))
    state_rel = [float(np.linalg.norm(s - r) / np.linalg.norm(r)) for s, r in zip(states, ref["states"])]
    state_tol = [float(t) for t in ck["state_tol"]]
    pooled_rel = []
    for mine, theirs in zip(pooled, ref["pooled"]):   # a layer: the worst pooled key
        n = min(len(mine), len(theirs))
        err = np.linalg.norm((mine[:n] - theirs[:n]).reshape(n, -1), axis=1) / np.linalg.norm(theirs[:n].reshape(n, -1), axis=1)
        pooled_rel.append(float(err.max()) if n else 0.0)
    # (f) a probed position a sparse layer: what the paged kernel gave back against the masked softmax on the same blocks
    attn_rel = [[float(np.linalg.norm(m - t) / np.linalg.norm(t)) for m, t in zip(mine, theirs)]
                for mine, theirs in zip(run.attended, ref["attn"])]
    h0, h1 = run.span
    out.update(selection_check(cell, run.probed, run.chosen, ref["scores"], ref["own"]))
    out.update(state_rel_l2=state_rel, state_tol=state_tol, pooled_rel_l2_max=pooled_rel, pooled_tol=float(ck["pooled_tol"]),
               rule_rel_l2=rule, rule_rel_l2_max=max(max(part.values()) for part in rule.values()),
               rule_tol=float(ck["rule_tol"]), attn_rel_l2_max=max(max(a) for a in attn_rel),
               attn_rel_l2_median=float(np.median(attn_rel)), attn_tol=float(ck["attn_tol"]), attn_positions=len(attn_rel),
               horizon_positions=int(h1 - h0), logits_ok=out["ok"], **run.where)
    out["ok"] = bool(out["ok"] and out["select_ok"] and len(state_rel) == len(state_tol)
                     and all(s <= t for s, t in zip(state_rel, state_tol)) and max(pooled_rel) <= out["pooled_tol"]
                     and out["rule_rel_l2_max"] <= out["rule_tol"] and np.isfinite(out["attn_rel_l2_max"])
                     and out["attn_rel_l2_max"] <= out["attn_tol"])
    return out


def hyper_of(cell, switches=None):
    from benchmark.lib import loader

    reference = loader.load_reference(cell)
    hp = reference.hyper_from_published(cell["config_file"])
    for key, value in (switches or {}).items():
        hp[key] = value
    return reference, hp


def reference_of(cell, params, run, switches=None, with_fed: int = 0):
    """The reference over the final sequence, run ON the program's selection
    at the probed positions: its logits where the program has them; its
    scores, own choice and attention output at the probed positions; the
    lightning states, the pooled keys; with ``with_fed`` what its first
    lightning layer's recurrence was fed over the first so many tokens."""
    import jax.numpy as jnp
    import numpy as np

    reference, hp = hyper_of(cell, switches)
    out = reference.forward(hp, params, jnp.asarray(run.sequence), list(run.positions),
                            selection=dict(zip(run.probed, run.chosen)), probes=list(run.probed))
    ref = {"logits": np.asarray(out["logits"], np.float32), "states": np.asarray(out["states"], np.float32),
           "pooled": [np.asarray(p, np.float32) for p in out["pooled"]], "scores": np.asarray(out["scores"], np.float32),
           "own": np.asarray(out["chosen"]), "attn": np.asarray(out["attn"], np.float32)}
    fed = reference.first_lightning_inputs(hp, params, jnp.asarray(run.sequence[:with_fed])) if with_fed else None
    return ref, fed


def _switches(cell, control):
    """A reference control's switches: a name stands for a value made from the file."""
    spec = REFERENCE_CONTROLS[control]
    if spec == "topk":
        return {"topk": int(cell["config_file"]["sparse_config"]["topk"]) // 2}
    if spec == "branch_depth":
        return {"branch_depth": int(cell["config_file"]["num_hidden_layers"])}
    return spec


def run_check(cell, engine, params, seed: int, rehearsal: bool):
    import jax.numpy as jnp
    import numpy as np

    ck = cell["config_file"]["check"]
    n = int(ck["prompt_tokens"]) + int(ck["ride_positions"]) + int(ck["tail_positions"])
    ids = np.random.default_rng([int(seed), 7]).integers(0, int(cell["config_file"]["vocab_size"]), size=n, dtype=np.int32)
    t0 = time.perf_counter()
    run = drive_check(cell, engine, ids)
    t1 = time.perf_counter()
    ref, fed = without_pools(engine, lambda: reference_of(cell, params, run, with_fed=int(ck["rule_tokens"])))
    t2 = time.perf_counter()
    slope = jnp.asarray(params["blocks"]["la_slope"][0], jnp.float32)
    rule = without_pools(engine, lambda: rule_check(cell, engine, fed, slope, rehearsal), keep=("state_pool", ))
    # where the check's time went (a run has to end inside the driver's limit): the program, the reference, the rule
    seconds = [round(t, 1) for t in (t1 - t0, t2 - t1, time.perf_counter() - t2)]
    return dict(compare(cell, run, ref, rule), check_seconds=seconds)


def build(cell: dict, seed: int, devices, rehearsal: bool, phases):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.serving import GatewayConfig, ServingGateway

    from benchmark.lib import common, loader
    from benchmark.lib.model import model_config, seed_word

    mark = phases.mark
    serve = loader.load_module("builders", "serve", cell["root"])
    cf, tf = cell["config_file"], cell["traffic_file"]
    dtype = jnp.float32 if rehearsal else jnp.bfloat16
    cfg = model_config(cf, dtype)
    model = TransformerLM(cfg)
    with common.span("weights"):
        params = jax.block_until_ready(make_params(cell, serve)(model, seed_word(seed), dtype))
    mark("weights")
    engine = make_engine(cell, model, params, dtype, rehearsal)
    mark("engine")

    gwc = tf.get("gateway", {})
    inflight = int(gwc.get("max_inflight_per_replica") or cf["engine"]["max_ragged_sequence_count"])
    budget = int(gwc.get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    seq_buckets = serve._buckets_up_to(engine.batch.seq_buckets, inflight)
    token_buckets = serve._buckets_up_to(engine.batch.token_buckets, budget)
    ck, rows = cf["check"], int(cf["engine"]["max_tracked_sequences"])
    horizon_calls = loader.load_module("builders", "serve_state", cell["root"]).horizon_calls
    in_check = horizon_calls(int(ck["decode_tokens"]), int(ck["horizon"]))
    # three decode horizons of the scheduler's six: under this mix a prefill is always pending (no decode-only step in
    # the traced windows; some in the ramp and the drain), each horizon is one more program of 16 layers to compile
    # or read back (8 s warm, 16-19 s cold), and the scheduler takes the longest the engine has
    # (`InferenceEngineV2.compiled_horizon`): 1 serves any count, the others are the check's own (32 and 4)
    horizons = sorted({1, *in_check})
    # the host compiles (8 to 40 s a program) while the device runs the check (a minute): the programs in the order
    # the check calls them, then the window's others; the check waits only for one that is not there yet
    checked = [("put", budget, rows, "greedy"), ("put", budget, rows, "probe")] \
        + [("decode", rows, h) for h in in_check] + [("put", rows, rows, "probe")]
    timed = [("decode", s, h) for s in seq_buckets for h in horizons] \
        + [("put", t, s, "greedy") for t in token_buckets for s in seq_buckets if s <= t]
    ahead = engine.compile_ahead(checked + timed)
    with common.span("check"):
        check = run_check(cell, engine, params, seed, rehearsal)
    mark("check")
    with common.span("warmup"):
        for program in ahead:
            program.result()
        warmed = engine.warmup(seq_buckets, horizons, token_buckets=token_buckets)
    mark("warmup_programs")

    steps: list = []
    serve.instrument(engine, steps)
    gateway = ServingGateway([engine], GatewayConfig(
        enabled=True, port=0, token_budget=int(gwc.get("token_budget", 0)),
        max_inflight_per_replica=int(gwc.get("max_inflight_per_replica", 0)))).start()
    return SimpleNamespace(engine=engine, gateway=gateway, cfg=cfg, steps=steps, check=check,
                           programs_warmed=len(warmed), kv_blocks=engine.num_kv_blocks,
                           kv_itemsize=jnp.dtype(dtype).itemsize)


def _control_hand(control):
    """What a control does behind the program's back after every engine call."""
    import jax
    import jax.numpy as jnp

    if control != "state_bf16":
        return None

    def to_bf16_and_back(pool):
        # on the integer bits, round to nearest even: the TPU's compiler drops a float32 -> bfloat16 -> float32
        # round trip as excess precision it is allowed to keep (PERF.md section 6, PR 41)
        bits = jax.lax.bitcast_convert_type(pool, jnp.uint32)
        bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    rounded = jax.jit(to_bf16_and_back, donate_argnums=0)

    def hand(engine):
        kv = engine.state_manager.kv_cache
        kv.state_pool = rounded(kv.state_pool)

    return hand


def _patch(control, on: bool):
    """``padding_touches``, ``stale_pooled``, ``mask_ignored`` and
    ``block_shifted``: a function of the program replaced behind its back (and
    put back)."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_implementations import flat_model, sparse_index
    from deepspeed_tpu.inference.v2.modules.implementations import attention
    from deepspeed_tpu.ops.pallas import lightning

    _rule_programs.cache_clear()
    if not hasattr(_patch, "plain"):
        _patch.plain = {"tile_plan": lightning.tile_plan, "update": sparse_index.update_pooled_keys,
                        "paged_attention": attention.paged_attention, "_pallas_paged": attention._pallas_paged}
    lightning.tile_plan = _patch.plain["tile_plan"]
    flat_model.update_pooled_keys = _patch.plain["update"]
    attention.paged_attention, attention._pallas_paged = _patch.plain["paged_attention"], _patch.plain["_pallas_paged"]
    if not on:
        return
    if control in ("mask_ignored", "block_shifted"):
        def shifted(sel):  # the lowest column a token reads past column 0, for each kv head: one column down
            first = jnp.argmax(sel[..., 1:], axis=-1)[..., None] + 1
            col = jnp.arange(sel.shape[-1], dtype=jnp.int32)
            moved = (sel & (col != first)) | (col == first - 1)
            return jnp.where(jnp.any(sel[..., 1:], axis=-1, keepdims=True), moved, sel)

        change = jnp.ones_like if control == "mask_ignored" else shifted

        def handed(fn):  # the kernels read by another selection than the indexer's, which the probe still reports
            return lambda *a, selection=None, **k: fn(*a, selection=None if selection is None else change(selection), **k)

        attention.paged_attention = handed(_patch.plain["paged_attention"])
        attention._pallas_paged = handed(_patch.plain["_pallas_paged"])
    if control == "padding_touches":
        def touched(n_tok, T, tile=lightning.TILE, xp=jnp):
            row, tok0, cnt, first, n_tiles = _patch.plain["tile_plan"](n_tok, T, tile, xp)
            return row, tok0, jnp.where(cnt > 0, tile, 0).astype(cnt.dtype), first, n_tiles

        lightning.tile_plan = touched
    if control == "stale_pooled":
        def stale(cfg, block_size, k_flat, p_flat, tables_l, seq_idx, pos, valid):
            # a kernel that begins before its row's first token of this step is not made: the boundary kernel missing
            S = tables_l.shape[0]
            first = jnp.full((S, ), 2**30, jnp.int32).at[seq_idx].min(jnp.where(valid, pos, 2**30))
            inside = pos - (cfg.sparse_kernel_size - 1) >= first[seq_idx]
            return _patch.plain["update"](cfg, block_size, k_flat, p_flat, tables_l, seq_idx, pos, valid & inside)

        flat_model.update_pooled_keys = stale


def main(argv=None) -> int:
    """The check over seeds, sound and under each control; one JSON line a reading."""
    import argparse

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated: each draws the check's ids")
    ap.add_argument("--controls", default=",".join(PROGRAM_CONTROLS + tuple(REFERENCE_CONTROLS)))
    ap.add_argument("--control-seeds", type=int, default=2, help="a control runs on the first so many seeds")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    from benchmark.lib import loader
    from benchmark.lib.model import model_config, seed_word

    enable_compile_cache()
    cell = loader.resolve_cell(args.workload, root, rehearsal=args.rehearsal)
    serve = loader.load_module("builders", "serve", root)
    cf = cell["config_file"]
    ck = cf["check"]
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    model = TransformerLM(model_config(cf, dtype))
    params = jax.block_until_ready(make_params(cell, serve)(model, seed_word(0), dtype))
    engine = make_engine(cell, model, params, dtype, args.rehearsal)
    slope = jnp.asarray(params["blocks"]["la_slope"][0], jnp.float32)
    n = int(ck["prompt_tokens"]) + int(ck["ride_positions"]) + int(ck["tail_positions"])
    keys = ("ok", "logits_ok", "select_ok", "rel_l2_low", "rel_l2_max", "quantile_tol", "rel_l2_tol", "argmax_equal_share",
            "attn_rel_l2_max", "attn_rel_l2_median", "attn_tol", "attn_positions",
            "select_gap_max", "select_margin", "select_share_min", "select_share_mean", "select_share", "select_share_all_min", "select_share_all", "select_forced_ok",
            "select_count_ok", "select_positions", "state_rel_l2", "state_tol", "pooled_rel_l2_max", "pooled_tol",
            "rule_rel_l2", "rule_rel_l2_max", "rule_tol", "horizon_positions", "row", "slot", "rows")
    wanted = [c for c in args.controls.split(",") if c]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ids = np.random.default_rng([seed, 7]).integers(0, int(cf["vocab_size"]), size=n, dtype=np.int32)
        sound = None
        for control in [None] + [c for c in wanted if i < args.control_seeds]:
            t0 = time.perf_counter()
            gc.collect()
            jax.clear_caches()
            if control is None or control in PROGRAM_CONTROLS:
                hand = _control_hand(control)
                if control in _PATCHED:
                    _patch(control, True)
                    engine._compiled.clear()
                try:
                    run = drive_check(cell, engine, ids, after_call=hand, horizon=1 if control == "state_bf16" else None)
                    ref, fed = without_pools(engine, lambda: reference_of(cell, params, run, with_fed=int(ck["rule_tokens"])))
                    rule = without_pools(engine, lambda: rule_check(cell, engine, fed, slope, args.rehearsal, after_call=hand),
                                         keep=("state_pool", ))
                finally:
                    if control in _PATCHED:
                        _patch(control, False)
                        engine._compiled.clear()
                if control is None:
                    sound = (run, rule)
            else:  # the sound run's outputs against the reference with one switch thrown
                run, rule = sound
                ref, _ = without_pools(engine, lambda: reference_of(cell, params, run, _switches(cell, control)))
            check = compare(cell, run, ref, rule)
            quantiles = {f"q{int(q * 100):02d}": round(float(np.quantile(check["rel_l2"], q)), 6) for q in (0.25, 0.5, 0.75)}
            print(json.dumps({"seed": seed, "control": control or "sound", "seconds": round(time.perf_counter() - t0, 1),
                              **{k: check[k] for k in keys}, **quantiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
