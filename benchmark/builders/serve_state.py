"""The serving system under test for a model whose sequences hold a RECURRENT
STATE beside the paged K/V cache (linear-attention layers between softmax
layers), of which this chip holds a share of the experts: what
``builders/serve.py`` builds, with ``builders/serve_share.py``'s one model for
every seed, and a check of its own.

* **The weights are ONE model for every seed** (``serve_share.WEIGHTS_WORD``
  and its reason: a chip's share of the routed slots follows the draw);
  ``--seed`` draws the token ids. Norm gains are drawn about one, the
  selection bias as ``serve_share`` draws it, and what only a linear layer
  has is drawn so that it is felt: ``A_log = log U(1, 16)`` and ``dt_bias ~
  U(-4.5, -1)`` in float32, so that a token's decays spread over (0, 1).
* **The check** feeds one sequence the way the timed traffic is fed, in a
  FULL HOUSE: ``max_tracked_sequences - 2`` other sequences decode beside it
  from its first chunk on (random tokens; every state slot but one live, every
  ``put`` and ``decode`` in the largest row bucket, the programs the window
  runs), the check's row in the middle of them, in the slot that a sequence
  which ran and was flushed left dirty. Its ``prompt_tokens`` prompt goes in
  chunks of what the token budget leaves beside a token of every other row (so
  the state and the convolution's tail cross ``put`` steps at boundaries that
  are no multiple of the kernel's tile); ``ride_positions`` positions are
  decoded as one-token rows among the others' beside the chunks of a last
  sequence's prompt; ``decode_tokens`` positions go through
  ``engine.decode``'s horizon in calls of at most ``horizon`` steps, every row
  in it (the recurrent step kernel); ``tail_positions`` more one-token ``put``
  steps give logits that stand on the state the horizon left.
  ``engine.decode`` feeds its own greedy tokens back and returns no logits, so
  the sequence is the program's own choice and the reference cannot run
  first: it runs afterwards over the final sequence, the engine's pools
  dropped meanwhile and made anew. Five limits:
  (1, 2) the logits at the prompt's last position, the riding positions and
  the tail positions, decided as ``serve_routed.decide`` decides (lower
  quartile tight, every position loose); the share of the horizon's tokens
  that are the reference's argmax is reported, not held (with random weights
  the largest logit changes on rounding);
  (3) **the state itself**: every linear layer's state of the sequence read
  back out of the pool after its last token, against the reference's,
  relative L2, each layer under a limit of its own (``state_tol``: a later
  layer stands behind more routed-expert layers);
  (4) **the rule alone** (``rule_check``, ``rule_tol``): bf16 activations put
  9e-3 into q, k, v and the decay before the rule sees them, and under that a
  state held in bfloat16, or a kernel whose products dropped to one bfloat16
  pass, moves (3) by a few percent. So the program's two forms of the rule
  are run once more on the engine's own pool, a full house in the window's
  shapes, fed the REFERENCE's q, k, v, g and beta of the first linear layer
  (float32, as the program's own are when the kernels take them), against the
  reference's rule token by token on the same inputs. Inputs common to both
  sides leave the state's arithmetic alone to be seen: 6e-6 sound, 2-3e-3
  under either fault (PERF.md section 6, PR 41).
* **Controls** (``python3 benchmark/builders/serve_state.py --workload <cell>
  --seeds a,b [--rehearsal]``): the same check, one thing changed, each NOT
  correct. On the reference's side (``solar_reference.hyper_from_published``'s
  switches): ``no_decay``, ``beta_not_doubled``, ``no_l2_norm``,
  ``no_selection_bias``. Behind the program's back, no switch in it:
  ``state_bf16`` (the state pool rounded to bfloat16 after every call, the
  horizon in calls of ONE step so that every step rounds; the rule alone
  likewise), ``no_tail`` (the convolution's tail zeroed after every call: not
  carried across a chunk boundary), ``padding_touches``
  (``ops.pallas.kda.tile_plan`` patched so that a row's last tile counts its
  padding as tokens), ``products_default`` (the kernels' products with the
  state at the default precision; the rule alone is run again, and on the CPU,
  whose default is float32, it reads sound).
"""

import functools
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

REFERENCE_CONTROLS = {"no_decay": {"decay": False}, "beta_not_doubled": {"beta_scale": 1.0},
                      "no_l2_norm": {"l2_norm": False}, "no_selection_bias": {"selection_bias": False}}
PROGRAM_CONTROLS = ("state_bf16", "no_tail", "padding_touches")
RULE_CONTROLS = ("products_default", )


def make_params(cell, serve):
    """``serve_share.make_params`` (one model, gains about one, the bias's
    mid-quantile points a chip's run) with the linear layers' decay
    parameters drawn in float32 over it."""
    from benchmark.lib import loader

    share = loader.load_module("builders", "serve_share", cell["root"])
    plain = share.make_params(serve, float(cell["config_file"]["check"]["bias_std"]))

    def draw(model, seed_word, dtype):
        import jax
        import jax.numpy as jnp

        params = plain(model, seed_word, dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(3), share.WEIGHTS_WORD)
        blocks = params["blocks"]
        blocks["kda_A_log"] = jnp.log(jax.random.uniform(jax.random.fold_in(key, 0), blocks["kda_A_log"].shape,
                                                         jnp.float32, 1.0, 16.0))
        blocks["kda_dt_bias"] = jax.random.uniform(jax.random.fold_in(key, 1), blocks["kda_dt_bias"].shape,
                                                   jnp.float32, -4.5, -1.0)
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
    """``work()`` while the engine's pools, the state's among them (but
    those in ``keep``), are off the device (no sequence is live: nothing in
    them is wanted), then pools of zeros as the engine began with."""
    import jax.numpy as jnp

    kv = engine.state_manager.kv_cache
    names = [n for n in ("k_pool", "v_pool", "state_pool", "tail_pool")
             if n not in keep and getattr(kv, n, None) is not None]
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


def horizon_calls(n: int, horizon: int):
    """``n`` steps as calls of the warmed horizons: the largest power of two
    that is at most ``horizon`` and at most what is left."""
    out = []
    while n:
        h = 1
        while 2 * h <= min(horizon, n):
            h *= 2
        out.append(h)
        n -= h
    return out


def drive_check(cell, engine, ids, after_call=None, horizon=None, uid: int = 2**30):
    """Feed the check's sequence beside a full house of other rows (see the
    module's docstring). ``ids``: the seed's tokens, prompt + riding + tail
    positions (the horizon's are the program's own). Returns ``(sequence,
    positions, logits, states, horizon_span, where)``: the final token
    sequence, the positions whose float32 logits were fetched and those
    logits, the sequence's state in every linear layer ``[layers, heads, dk,
    dv]`` after its last token, and its row and slot. ``after_call(engine)``
    runs after every engine call (a control's hand in the pools)."""
    import numpy as np

    cf, tf = cell["config_file"], cell["traffic_file"]
    ck = cf["check"]
    budget = int(tf.get("gateway", {}).get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    rows = int(cf["engine"]["max_tracked_sequences"])
    n_prompt, ride, n_decode, tail = (int(ck[k]) for k in ("prompt_tokens", "ride_positions", "decode_tokens",
                                                            "tail_positions"))
    horizon = int(horizon or ck["horizon"])
    after_call = after_call or (lambda eng: None)
    rng = np.random.default_rng([int(ids[0]), 11])
    draw = lambda n: rng.integers(0, int(cf["vocab_size"]), size=n, dtype=np.int32)
    seq = [int(t) for t in ids[:n_prompt + ride]]
    positions, logits = [], []
    # the house: rows - 2 sequences that decode from the start, the check's in the middle of them, and from
    # the riding steps on a last one whose prompt's chunks the check's tokens ride beside
    feeder, others, dummy = uid + 1, [uid + 2 + i for i in range(rows - 2)], uid + rows
    at = len(others) // 2  # the check's row in every call
    house = lambda mid, last=(): others[:at] + mid + others[at:] + list(last)

    def call(mid_tokens, last_tokens=(), want_logits=False):
        """One ``put``: every other row a token, the check's ``mid_tokens``
        in the middle, the feeder's chunk last."""
        uids = house([uid], [feeder] if len(last_tokens) else [])
        tokens = [draw(1) for _ in others[:at]] + [np.asarray(mid_tokens, np.int32)] + [draw(1) for _ in others[at:]]
        if len(last_tokens):
            tokens.append(np.asarray(last_tokens, np.int32))
        # logits of the whole house stay on the device: the check's row alone is fetched
        out = engine.put(uids, tokens, sample=None if want_logits else "greedy", block=not want_logits)
        after_call(engine)
        return np.asarray(out[at], np.float32) if want_logits else None

    # a sequence that runs and goes before the check's comes: the slot it frees holds what it left
    opening = budget // (rows - 1)
    engine.put(house([dummy]), [draw(opening) for _ in range(rows - 1)], sample="greedy")
    after_call(engine)
    engine.flush(dummy)
    chunk = budget - len(others)
    for c0 in range(0, n_prompt, chunk):
        got = call(seq[c0:min(c0 + chunk, n_prompt)], want_logits=c0 + chunk >= n_prompt)
    positions.append(n_prompt - 1)
    logits.append(got)
    fed = budget - len(others) - 1
    feeder_prompt = draw(ride * fed)
    for i in range(ride):  # a one-token row among the others' beside a chunk
        logits.append(call(seq[n_prompt + i:n_prompt + i + 1], feeder_prompt[i * fed:(i + 1) * fed], want_logits=True))
        positions.append(n_prompt + i)
    # the horizon, every row in it: the program's own greedy tokens, the check's first being the argmax at its
    # last riding position
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
        out = engine.put(uids, tokens, sample=None, block=False)
        after_call(engine)
        logits.append(np.asarray(out[at], np.float32))
        positions.append(j)
    kv = engine.state_manager.kv_cache
    slot = int(engine.state_manager.get_sequence(uid).state_slot)
    states = np.asarray(kv.state_pool[:, slot], np.float32)
    for u in uids:
        engine.flush(u)
    return (np.asarray(seq, np.int32), positions, logits, states, (n_prompt + ride, first_tail),
            {"row": at, "slot": slot, "rows": len(uids)})


@functools.lru_cache(maxsize=1)
def _rule_programs(use_pallas: bool, interpret: bool):
    """The rule check's programs, compiled once a process (``cache_clear()``
    after a control has changed what they trace): the two forms on the pool
    ``[layers, slots, ...]``, the reference's rule over a row's tokens, and
    the pool filled with ones and with zeros."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import kda

    from benchmark.lib import solar_reference

    options = {"use_pallas": use_pallas, "interpret": interpret}

    def chunks(pool, arrs, src, slot, fresh, n_tok):
        flat = pool.reshape((-1, ) + pool.shape[2:])
        _, flat = kda.kda_chunks(*(a[src] for a in arrs), flat, slot, fresh, n_tok, **options)
        return flat.reshape(pool.shape)

    def step(pool, arrs, src, slot, fresh, n_live):
        flat = pool.reshape((-1, ) + pool.shape[2:])
        _, flat = kda.kda_step(*(a[src] for a in arrs), flat, slot, fresh, n_live, **options)
        return flat.reshape(pool.shape)

    return SimpleNamespace(chunks=jax.jit(chunks, donate_argnums=0), step=jax.jit(step, donate_argnums=0),
                           plain=jax.jit(lambda arrs, idx: solar_reference.delta_rule(*(a[idx] for a in arrs))[1]),
                           ones=jax.jit(jnp.ones_like, donate_argnums=0), zeros=jax.jit(jnp.zeros_like, donate_argnums=0))


def rule_check(cell, engine, fed, rehearsal: bool, after_call=None):
    """The program's delta rule ALONE, on what the reference fed its own: the
    two forms as ``ragged_forward`` calls them (``ops.pallas.kda.kda_chunks``
    and ``kda_step``), on the engine's own state pool, over a full house of
    rows in the shapes the window runs, against the reference's rule token by
    token on the same float32 inputs. The inputs are common to both sides, so
    what is left is the arithmetic of the state: the kernels' products, what
    they accumulate in, the pool's type (a state held in bfloat16 reads 3e-3
    here; through the whole model it hides under the 9e-3 that bf16
    activations put into q, k, v and the decay before the rule sees them).

    ``fed``: ``(q, k, v, g, beta)`` of the check's sequence, ``N`` tokens. Row
    ``r`` is fed that sequence from token ``17 (r - c)`` on, wrapping; the
    check's row ``c`` the sequence itself: its prompt in chunks beside one
    token of every other row, then one token a row a step. The last rows come
    late and start fresh in a pool filled with ones. Returns the relative L2
    of some rows' states (first, the check's and its neighbours, the last
    early one, the late ones' first and last), read twice: after the prompt's
    last chunk (``"chunks"``: what the chunkwise form left; a thousand steps
    later the decay has worn it away) and after the last step (``"steps"``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cf, tf = cell["config_file"], cell["traffic_file"]
    ck = cf["check"]
    kv = engine.state_manager.kv_cache
    after_call = after_call or (lambda eng: None)
    budget = int(tf.get("gateway", {}).get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    R = int(cf["engine"]["max_tracked_sequences"])
    N, n_prompt = int(fed[0].shape[0]), int(ck["prompt_tokens"])
    late, li = max(1, R // 24), kv.state_pool.shape[0] // 2  # rows that come late; the state layer whose slots are used
    early, c = R - late, (R - 2) // 2
    chunk, steps = budget - (early - 1), N - n_prompt
    join = min(32, steps // 2)
    slots = np.random.default_rng([R, 5]).permutation(R).astype(np.int32)  # a row's slot is not its row
    slot_li = jnp.asarray(li * R + slots)
    on_tpu = jax.default_backend() == "tpu"
    run = _rule_programs(on_tpu, bool(rehearsal and not on_tpu))
    streams = [[] for _ in range(R)]  # the tokens each row was fed, as places in ``fed``

    def take(r, n):
        idx = [(len(streams[r]) + j + 17 * (r - c)) % N for j in range(n)]
        streams[r] += idx
        return idx

    def read():
        out = {}
        with jax.default_matmul_precision("highest"):
            for r in sorted({0, c - 1, c, c + 1, early - 1, early, R - 1}):
                if streams[r]:
                    want = np.asarray(run.plain(fed, jnp.asarray(np.asarray(streams[r], np.int32))))
                    got = np.asarray(kv.state_pool[li, int(slots[r])])
                    out[r] = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        return out

    kv.state_pool = run.ones(kv.state_pool)  # what freed slots might hold
    for c0 in range(0, n_prompt, chunk):
        n_tok = np.where(np.arange(R) < early, 1, 0).astype(np.int32)
        n_tok[c] = min(chunk, n_prompt - c0)
        src = np.zeros(budget, np.int32)
        flat = [i for r in range(early) for i in take(r, int(n_tok[r]))]
        src[:len(flat)] = flat
        fresh = (np.arange(R) < early) & (c0 == 0)
        kv.state_pool = run.chunks(kv.state_pool, fed, jnp.asarray(src), slot_li, jnp.asarray(fresh), jnp.asarray(n_tok))
        after_call(engine)
    out = {"chunks": read()}
    for t in range(steps):
        live = R if t >= join else early
        src = np.zeros(R, np.int32)
        src[:live] = [take(r, 1)[0] for r in range(live)]
        fresh = (np.arange(R) >= early) & (t == join)
        kv.state_pool = run.step(kv.state_pool, fed, jnp.asarray(src), slot_li, jnp.asarray(fresh), jnp.asarray(live, jnp.int32))
        after_call(engine)
    out["steps"] = read()
    kv.state_pool = run.zeros(kv.state_pool)
    return out


def compare(cell, sequence, positions, logits, states, ref_logits, ref_states, horizon_span, rule, where) -> dict:
    """The decision (see the module's docstring)."""
    import numpy as np

    from benchmark.lib import loader

    routed = loader.load_module("builders", "serve_routed", cell["root"])
    ck = cell["config_file"]["check"]
    rel = [float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(logits, ref_logits["at"])]
    check = {"positions": positions, "rel_l2": rel, "rel_l2_tol": ck["rel_l2_tol"],
             "finite": bool(all(np.isfinite(g).all() for g in logits) and np.isfinite(states).all()),
             "argmax_equal": [int(g.argmax()) == int(r.argmax()) for g, r in zip(logits, ref_logits["at"])]}
    check["ok"] = bool(check["finite"] and max(rel) <= ck["rel_l2_tol"])
    out = routed.decide(check, float(ck["quantile"]), float(ck["quantile_tol"]))
    state_rel = [float(np.linalg.norm(s - r) / np.linalg.norm(r)) for s, r in zip(states, ref_states)]
    state_tol = [float(t) for t in ck["state_tol"]]
    h0, h1 = horizon_span  # the horizon's tokens: sequence[h0 + 1 .. h1 - 1] were argmaxes at positions h0 .. h1 - 2
    chosen = ref_logits["horizon_argmax"]
    out.update(state_rel_l2=state_rel, state_tol=state_tol, rule_rel_l2=rule, rule_rel_l2_max=max(max(part.values()) for part in rule.values()),
               rule_tol=float(ck["rule_tol"]), horizon_positions=int(h1 - h0), logits_ok=out["ok"], **where,
               horizon_argmax_share=float(np.mean(chosen == sequence[h0 + 1:h0 + 1 + len(chosen)])) if len(chosen) else 1.0)
    out["ok"] = bool(out["ok"] and len(state_rel) == len(state_tol) and all(s <= t for s, t in zip(state_rel, state_tol))
                     and out["rule_rel_l2_max"] <= out["rule_tol"])
    return out


def reference_of(cell, params, sequence, positions, horizon_span, switches=None):
    """The reference over the final sequence: logits at ``positions``, its
    argmax over the horizon's positions, the linear layers' states, and what
    its first linear layer's delta rule was fed."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import loader

    reference = loader.load_reference(cell)
    hp = {**reference.hyper_from_published(cell["config_file"]), **(switches or {})}
    h0, h1 = horizon_span
    over = list(range(h0, h1 - 1))
    logits, states, fed = reference.forward(hp, params, jnp.asarray(sequence), list(positions) + over, with_fed=True)
    logits = np.asarray(logits, np.float32)
    return ({"at": logits[:len(positions)], "horizon_argmax": logits[len(positions):].argmax(axis=-1)},
            np.asarray(states, np.float32), fed)


def run_check(cell, engine, params, seed: int, rehearsal: bool):
    import numpy as np

    ck = cell["config_file"]["check"]
    n = int(ck["prompt_tokens"]) + int(ck["ride_positions"]) + int(ck["tail_positions"])
    ids = np.random.default_rng([int(seed), 7]).integers(0, int(cell["config_file"]["vocab_size"]), size=n, dtype=np.int32)
    sequence, positions, logits, states, span, where = drive_check(cell, engine, ids)
    ref_logits, ref_states, fed = without_pools(engine, lambda: reference_of(cell, params, sequence, positions, span))
    rule = without_pools(engine, lambda: rule_check(cell, engine, fed, rehearsal), keep=("state_pool", ))
    return compare(cell, sequence, positions, logits, states, ref_logits, ref_states, span, rule, where)


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

    # warm the programs this traffic can reach (as serve.build does), BEFORE the check: the check runs through
    # the warmed programs, and the warm-up's zero descriptor needs an engine that tracks no sequence. The
    # engine's buckets are the configuration's (two of rows, two of tokens: 16 programs where the powers of
    # two are 55), and it cuts a step's tokens to the live rows on the host, so no slice program exists
    gwc = tf.get("gateway", {})
    inflight = int(gwc.get("max_inflight_per_replica") or cf["engine"]["max_ragged_sequence_count"])
    budget = int(gwc.get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    seq_buckets = serve._buckets_up_to(engine.batch.seq_buckets, inflight)
    token_buckets = serve._buckets_up_to(engine.batch.token_buckets, budget)
    longest = int(tf["output_tokens"].get("hi", tf["output_tokens"].get("value", 1)))
    horizons = [h for h in (1, 2, 4, 8, 16, 32) if h < longest]
    with common.span("warmup"):
        warmed = engine.warmup(seq_buckets, horizons, token_buckets=token_buckets)
    mark("warmup_programs")
    with common.span("check"):
        check = run_check(cell, engine, params, seed, rehearsal)
    mark("check")

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

    if control == "state_bf16":
        def to_bf16_and_back(pool):
            # on the integer bits, round to nearest even: the TPU's compiler drops a float32 -> bfloat16 ->
            # float32 round trip as excess precision it is allowed to keep (my chip run, PR 41: the control read
            # the sound program's numbers to the last digit)
            bits = jax.lax.bitcast_convert_type(pool, jnp.uint32)
            bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
            return jax.lax.bitcast_convert_type(bits, jnp.float32)

        rounded = jax.jit(to_bf16_and_back, donate_argnums=0)

        def hand(engine):
            kv = engine.state_manager.kv_cache
            kv.state_pool = rounded(kv.state_pool)

        return hand
    if control == "no_tail":
        def hand(engine):
            kv = engine.state_manager.kv_cache
            if kv.tail_pool is not None:  # (the rule alone runs with the tails off the device: it has no convolution)
                kv.tail_pool = jnp.zeros_like(kv.tail_pool)

        return hand
    return None


def _patch_padding(on: bool):
    """``padding_touches``: a row's last tile counts its padding as tokens,
    so that whatever follows the row in the flat batch (the next row's
    tokens, the bucket's padding) goes through the rule into its state."""
    from deepspeed_tpu.ops.pallas import kda

    if not hasattr(_patch_padding, "plain"):
        _patch_padding.plain = kda.tile_plan
    _rule_programs.cache_clear()
    if not on:
        kda.tile_plan = _patch_padding.plain
        return

    def touched(n_tok, T, xp=None):
        import jax.numpy as jnp

        row, tok0, cnt, first, n_tiles = _patch_padding.plain(n_tok, T, **({} if xp is None else {"xp": xp}))
        return row, tok0, jnp.where(cnt > 0, kda.TILE, 0).astype(cnt.dtype), first, n_tiles

    kda.tile_plan = touched


def _patch_products(on: bool):
    """``products_default``: the kernels' two products with the state (``S0``
    against ``W`` and ``Q``, float32 at ``HIGHEST``) at the default precision
    instead, one bfloat16 pass on the chip."""
    from jax import lax

    from deepspeed_tpu.ops.pallas import kda

    kda._HI = lax.Precision.DEFAULT if on else lax.Precision.HIGHEST
    _rule_programs.cache_clear()


def main(argv=None) -> int:
    """The check over seeds, sound and under each control; one JSON line a reading."""
    import argparse

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated: each draws the check's ids")
    ap.add_argument("--controls", default=",".join(PROGRAM_CONTROLS + RULE_CONTROLS + tuple(REFERENCE_CONTROLS)))
    ap.add_argument("--control-seeds", type=int, default=2,
                    help="a control runs on the first so many seeds (name:k for a number of its own)")
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
    n = int(ck["prompt_tokens"]) + int(ck["ride_positions"]) + int(ck["tail_positions"])
    keys = ("ok", "logits_ok", "within_loose", "rel_l2_low", "rel_l2_max", "quantile_tol", "rel_l2_tol", "argmax_equal_share",
            "state_rel_l2", "state_tol", "rule_rel_l2", "rule_rel_l2_max", "rule_tol", "horizon_argmax_share",
            "horizon_positions", "row", "slot", "rows")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ids = np.random.default_rng([seed, 7]).integers(0, int(cf["vocab_size"]), size=n, dtype=np.int32)
        sound = sound_ref = rule = fed = None
        wanted = [(c.split(":")[0], int(c.split(":")[1]) if ":" in c else args.control_seeds)
                  for c in args.controls.split(",") if c]
        for control in [None] + [c for c, k in wanted if i < k]:
            t0 = time.perf_counter()
            hand = _control_hand(control)
            driven = now_fed = ref_logits = ref_states = None
            gc.collect()
            jax.clear_caches()  # programs no reading needs again hold the device's memory (the engine's load again)
            if control in PROGRAM_CONTROLS or control is None:
                if control == "padding_touches":
                    _patch_padding(True)
                    engine._compiled.clear()
                try:
                    driven = drive_check(cell, engine, ids, after_call=hand, horizon=1 if control == "state_bf16" else None)
                    sequence, positions, logits, states, span, where = driven
                    ref_logits, ref_states, now_fed = without_pools(engine, lambda: reference_of(
                        cell, params, sequence, positions, span))
                    now_rule = without_pools(engine, lambda: rule_check(cell, engine, now_fed, args.rehearsal, after_call=hand),
                                             keep=("state_pool", ))
                finally:
                    if control == "padding_touches":
                        _patch_padding(False)
                        engine._compiled.clear()
                if control is None:
                    sound, sound_ref, rule, fed = driven, (ref_logits, ref_states), now_rule, now_fed
            elif control in RULE_CONTROLS:  # the sound run's outputs, the rule alone run again with one thing changed
                (sequence, positions, logits, states, span, where), (ref_logits, ref_states) = sound, sound_ref
                _patch_products(True)
                try:
                    now_rule = without_pools(engine, lambda: rule_check(cell, engine, fed, args.rehearsal),
                                             keep=("state_pool", ))
                finally:
                    _patch_products(False)
            else:  # the sound run's outputs against the reference with one switch thrown
                sequence, positions, logits, states, span, where = sound
                now_rule = rule
                ref_logits, ref_states, _ = without_pools(engine, lambda: reference_of(
                    cell, params, sequence, positions, span, REFERENCE_CONTROLS[control]))
            check = compare(cell, sequence, positions, logits, states, ref_logits, ref_states, span, now_rule, where)
            quantiles = {f"q{int(q * 100):02d}": round(float(np.quantile(check["rel_l2"], q)), 6) for q in (0.25, 0.5, 0.75)}
            print(json.dumps({"seed": seed, "control": control or "sound", "seconds": round(time.perf_counter() - t0, 1),
                              **{k: check[k] for k in keys}, **quantiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
