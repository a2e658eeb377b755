"""The serving system under test for a model generated by masked diffusion
over blocks (``diffusion_block_size``): what ``builders/serve.py`` builds (one
``InferenceEngineV2`` replica behind ``ServingGateway``, weights from the
seed, warm-up, a step log), with what such a model changes.

* **The step log counts tokens where they were emitted.** A ``decode`` call
  advances its rows by whole blocks and a row keeps fewer tokens than the
  call's ``n_steps`` when its prompt's tail opened the first block or its
  answer ends inside the last: the log's ``sizes`` are the new tokens each
  row KEPT, so ``lib/rates.py`` counts output tokens where they were emitted
  (and ``decode_step_p50_ms.tput``, a call's host time over ``sizes[0]``,
  reads milliseconds a token of a row). The prompt tokens a row's first call
  is handed as ``first_tokens`` (its last partial block) are logged as a
  ``put`` of that many tokens just before the call, so that every prompt
  token is counted once. A prefill emits nothing, but
  ``classify_serving_steps`` credits one token to the ``put`` that completes
  a prompt: the rate reads one token a request high, 1 in about 1,320, on
  parent and change alike (PERF.md section 7).
* **``correct`` is of the serving engine's own programs at the timed sizes.**
  The check fills every row the traffic keeps in flight (row ``r`` with a
  prompt of ``check.prompt_tokens[r mod 4]`` tokens, one length for each ``P
  mod B``, within the traffic's range), fed in the gateway's chunks, and
  advances them ``check.blocks`` blocks in calls of
  ``check.blocks_a_call``. Each call runs twice from the same committed
  length: once with the engine's probe (the same program with one more
  result: every denoise forward's ids and float32 logits of the probed
  rows), then, the rows rolled back, as the program the window runs. So the
  cache every later call reads is what the TIMED program wrote, and the two
  programs' tokens are compared over all rows (``timed_tokens_equal_share``).
  The ids a forward is fed are the program's own choices, so the reference
  cannot run first; it computes all forwards of a row in ONE pass over the
  row's final sequence followed by each forward's noisy block, under an
  explicit mask (clean sees clean by the block rule; a noisy block sees the
  clean blocks before its own and itself), at the forwards' true positions
  (``reference_logits``). That pass needs over a gigabyte, which the serving
  pool does not leave: the rows are flushed, the pool's arrays dropped for
  the reference's four passes and made anew (zeros, as they began).
* **What is compared** (``compare``). The relative L2 a position of every
  probed forward, decided as ``serve_routed.py`` decides: the lower quartile
  tight (a flipped expert cannot fool it), every position loose. With random
  weights 95% of a position's logits is one vector common to all positions,
  so a third to two fifths of the positions, those where no expert flipped,
  stand at one floor, the error of that vector (1.3-1.9e-2 over seeds; an
  int8 KV cache lifts the floor and leaves fewer positions on it: 2.5-3.5e-2;
  my chip runs, PR 33). Taking the mean logit vector over the run's checked
  positions from both sides first (``rel_l2_centred_low``, printed beside it)
  was tried and parts the two no better: 1.42-2.05 against 2.55-4.21. The
  unmasking is held apart, exactly: what each forward wrote into its block
  against the rule applied (``reference.unmask``) to the program's OWN
  float32 logits (``unmask_own_share``; with random weights a token may BE
  the mask id, 1 in 151,936, and the position then stays masked on both
  sides), and whether the reference's confidences choose the same positions
  (``unmask_ref_share``: three choices in four, because the confidences of a
  block's positions lie close together; a program with a wrong mask, no
  commit or no q/k norm agrees in about half).
* **Weights and prompts are drawn from ``--seed``**, as in the other cells.
  **Norm gains are drawn about one** (``1 + 0.1 N(0, 1)``, as
  ``serve_share.py`` draws them), so that a program that leaves the q/k norm
  out reads as not correct.
* **Controls** (``python3 benchmark/builders/serve_diffusion.py --workload
  <cell> --seeds a,b,... [--rehearsal] [--out DIR]``): the same check with
  the program's int8 KV cache, a causal mask in place of the block rule, the
  commit left out, and the q/k norm left out; each must come out NOT correct.
"""

import dataclasses
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

CONTROLS = ("int8_kv", "causal_mask", "no_commit", "no_qk_norm")


def make_params(serve):
    """``serve.make_params`` with every norm gain (a leaf named ``*scale``)
    drawn about one."""
    plain = serve.make_params

    def draw(model, seed_word, dtype):
        import jax
        import jax.numpy as jnp

        params = plain(model, seed_word, dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(2), seed_word)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        # the large leaves stay as they are: no second copy of the weights is ever alive
        leaves = [(1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)).astype(dtype)
                  if str(getattr(path[-1], "key", path[-1])).endswith("scale") else leaf
                  for i, (path, leaf) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return draw


def instrument(serve, engine, log: list):
    """``serve.instrument`` for the ``put``; a ``decode`` is logged with the
    new tokens each row kept: the call's ``n_steps`` less the row's
    ``first_tokens``, at most what the row still wants (no EOS in the
    traffic), and those first tokens as a ``put`` where they are handed
    over."""
    from benchmark.lib.common import span

    decode = engine.decode
    serve.instrument(engine, log)  # its decode wrapper is replaced below

    def decode_logged(batch_uids, first_tokens, n_steps, *args, max_new_tokens=None, **kwargs):
        uids = list(batch_uids)
        opened = [0 if t is None else len(t) for t in (first_tokens or [None] * len(uids))]
        t0 = time.perf_counter()
        if any(opened):
            log.append({"kind": "put", "uids": [u for u, o in zip(uids, opened) if o],
                        "sizes": [o for o in opened if o], "t0": t0, "t1": t0})
        with span("decode"):
            out = decode(uids, first_tokens, n_steps, *args, max_new_tokens=max_new_tokens, **kwargs)
        want = max_new_tokens or [n_steps] * len(uids)
        log.append({"kind": "decode", "uids": uids, "t0": t0, "t1": time.perf_counter(), "n_steps": int(n_steps),
                    "sizes": [min(int(n_steps) - o, int(w)) for o, w in zip(opened, want)]})
        return out

    engine.decode = decode_logged


def probe_of_row(probes, j: int):
    """Row ``j`` of the probes of consecutive ``decode`` calls as one (blocks
    in order): ``ids`` ``[blocks, steps, B]``, ``logits`` ``[..., V]``,
    ``forwards`` ``[blocks]``."""
    import numpy as np

    return {"ids": np.concatenate([p["ids"][:, :, j] for p in probes]),
            "logits": np.concatenate([p["logits"][:, :, j] for p in probes]),
            "forwards": np.concatenate([p["forwards"] for p in probes])}


def reference_logits(reference, hp, params, prompt, tokens, probe, block: int):
    """The reference's logits ``[B, V]`` for EVERY denoise forward of
    ``probe`` (one row's), in one pass: the final sequence (the prompt's whole
    blocks and ``tokens``, the blocks' final ids), then each forward's noisy
    ids at the forward's own positions. A clean token sees clean tokens by the
    block rule; a noisy one sees the clean blocks BEFORE its own and its own
    forward's tokens."""
    import numpy as np

    whole = prompt.size // block * block
    clean = np.concatenate([prompt[:whole], tokens]).astype(np.int32)
    ids, pos, group = [clean], [np.arange(clean.size)], [np.full(clean.size, -1)]
    for b in range(probe["ids"].shape[0]):
        for i in range(int(probe["forwards"][b])):
            ids.append(probe["ids"][b, i])
            pos.append(whole + b * block + np.arange(block))
            group.append(np.full(block, len(ids) - 2))
    ids, pos, group = np.concatenate(ids), np.concatenate(pos), np.concatenate(group)
    blk = pos // block
    before = (group[None, :] < 0) & (blk[None, :] < blk[:, None])
    own = np.where(group[:, None] < 0, (group[None, :] < 0) & (blk[None, :] == blk[:, None]),
                   group[None, :] == group[:, None])
    noisy = np.nonzero(group >= 0)[0]
    out = np.asarray(reference.forward_logits(hp, params, ids, pos, visible=before | own, at=noisy))
    return list(out.reshape(-1, block, out.shape[-1]))


def apply_control(engine, control):
    """Turn the engine into one of the controls: the program with one thing
    wrong. (``int8_kv`` is an option of the engine's own and is set where the
    engine is made.)"""
    import jax.numpy as jnp

    if control == "causal_mask":  # the block length stays the engine's; the mask alone is causal
        engine.model_config = dataclasses.replace(engine.model_config, diffusion_block_size=1)
    elif control == "no_qk_norm":
        engine.model_config = dataclasses.replace(engine.model_config, qk_norm=False)
    elif control == "no_commit":
        step = engine._ragged_step

        def without_commit(params, packed, pools, T, S, **kw):
            if kw.get("kv_only"):
                return None, tuple(pools), jnp.zeros(3, jnp.int32)
            return step(params, packed, pools, T, S, **kw)

        engine._ragged_step = without_commit
    elif control not in (None, "int8_kv"):
        raise ValueError(f"no control {control!r}: {CONTROLS}")


def make_engine(cell, model, params, dtype, rehearsal: bool, control=None):
    from deepspeed_tpu.inference.v2 import (DiffusionConfig, DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    ec = cell["config_file"]["engine"]
    gen = ec["generation"]
    if gen["block_length"] != model.config.diffusion_block_size or gen.get("temperature", 0.0) != 0.0:
        raise ValueError(f"generation {gen}: the block length is the model's mask "
                         f"({model.config.diffusion_block_size}), and the path is greedy")
    sm = DSStateManagerConfig(max_tracked_sequences=ec["max_tracked_sequences"],
                              max_ragged_batch_size=ec["max_ragged_batch_size"],
                              max_ragged_sequence_count=ec["max_ragged_sequence_count"],
                              max_context=ec["max_context"])
    icfg = RaggedInferenceEngineConfig(
        kv_block_size=ec["kv_block_size"], num_kv_blocks=ec["num_kv_blocks"],
        kv_memory_fraction=ec.get("kv_memory_fraction", 0.8), kv_dtype="int8" if control == "int8_kv" else dtype,
        state_manager=sm, diffusion=DiffusionConfig(
            denoising_steps=gen["denoising_steps"], remasking=gen["remasking"],
            confidence_threshold=gen.get("confidence_threshold", 0.9)))
    if rehearsal:  # the same kernel program through the Pallas interpreter
        icfg.modules.attention = {"name": "paged_pallas_attention", "implementation_config": {"interpret": True}}
    engine = InferenceEngineV2(model, icfg, params=params)
    apply_control(engine, control)
    return engine


def without_pools(engine, work):
    """``work()`` while the engine's KV pool is off the device (no sequence is
    live: nothing in it is wanted), then a pool of zeros as the engine began
    with."""
    import jax.numpy as jnp

    kv = engine.state_manager.kv_cache
    names = [n for n in ("k_pool", "v_pool", "k_scale", "v_scale") if getattr(kv, n) is not None]
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


def check_prompts(cell, model, seed_word, n_rows: int):
    """One prompt a row from the seed: row ``r`` is ``check.prompt_tokens[r mod
    4]`` tokens long (one length for each ``P mod B``, so two shapes of prefill
    chunk), its ids the seed's."""
    import numpy as np

    lengths = cell["config_file"]["check"]["prompt_tokens"]
    rng = np.random.default_rng([int(seed_word), 7])
    prompts = [rng.integers(0, model.config.vocab_size, size=int(lengths[r % len(lengths)]), dtype=np.int32)
               for r in range(n_rows)]
    for p in prompts:
        p[p == model.config.mask_token_id] = 0
    return prompts


def drive_check(cell, engine, prompts):
    """Fill the rows, advance them ``check.blocks`` blocks (each call probed,
    rolled back and run again as the timed program), flush. Returns the timed
    program's tokens ``[rows, blocks * B]``, the probed program's, and the
    probes of the calls."""
    import numpy as np

    cf, tf = cell["config_file"], cell["traffic_file"]
    ck, block, sm = cf["check"], int(cf["engine"]["generation"]["block_length"]), engine.state_manager
    chunk = int(ck.get("chunk_tokens") or tf.get("gateway", {}).get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    uids = [2**30 + r for r in range(len(prompts))]
    for uid, prompt in zip(uids, prompts):
        whole = prompt.size // block * block
        for c0 in range(0, whole, chunk):
            engine.put([uid], [prompt[c0:min(c0 + chunk, whole)]], sample="greedy")
    known = [p[p.size // block * block:] for p in prompts]
    rows, n = tuple(int(r) for r in ck["probe_rows"]), int(ck["blocks_a_call"]) * block
    timed, probed, probes = [], [], []
    for _ in range(int(ck["blocks"]) // int(ck["blocks_a_call"])):
        seqs = [sm.get_sequence(u) for u in uids]
        start = [s.seen_tokens for s in seqs]
        toks, probe = engine.decode(uids, known, n, probe=rows)
        for seq, at in zip(seqs, start):
            sm.rollback_to(seq, at)
        timed.append(np.asarray(engine.decode(uids, known, n)))  # the program the window runs
        probed.append(toks)
        probes.append(probe)
        known = None
    for uid in uids:
        engine.flush(uid)
    return np.concatenate(timed, axis=1), np.concatenate(probed, axis=1), probes


def compare(reference, hp, got, want, ids, nxt, step, strategy: str) -> dict:
    """Per checked position (``got``/``want`` ``[N, B, V]``, forwards in
    order): the relative L2, plain and centred, and the argmax flags; per
    forward that held a mask (forward ``step`` of its block): whether what it
    wrote (``ids`` -> ``nxt``) is the rule applied to its own logits; per
    forward that had a choice (not the last, more masks than it may take):
    whether the reference's confidences choose the same positions."""
    import numpy as np

    B, V = got.shape[1:]
    g, w = got.reshape(-1, V), want.reshape(-1, V)
    norm = lambda x: np.sqrt(np.einsum("nv,nv->n", x, x, dtype=np.float64))
    plain = norm(g - w) / norm(w)
    tok = g.argmax(-1)
    equal = tok == w.argmax(-1)
    conf = [(1.0 / np.exp(x - x.max(-1, keepdims=True)).sum(-1, dtype=np.float64)).reshape(-1, B) for x in (g, w)]
    wc = w - w.mean(0)
    centred = norm(g - g.mean(0) - wc) / norm(wc)
    quota, steps, tok = reference.quotas(B, hp["denoising_steps"]), hp["denoising_steps"], tok.reshape(-1, B)
    own, ref = [], []
    for f in range(ids.shape[0]):
        masked, i = ids[f] == hp["mask_id"], int(step[f])
        if not masked.any():
            continue
        rule = lambda c: reference.unmask(c, masked, strategy, quota[i], hp["confidence_threshold"], i == steps - 1)
        # exactly the rule on the program's own logits (a token may BE the mask id: the position stays masked)
        own.append(bool((nxt[f] == np.where(rule(conf[0][f]), tok[f], ids[f])).all()))
        if i < steps - 1 and masked.sum() > quota[i]:  # a choice: does the reference make the same one
            ref.append(bool((rule(conf[0][f]) == rule(conf[1][f])).all()))
    return {"rel_l2": plain, "rel_l2_centred": centred, "argmax_equal": equal, "unmask_choices": len(ref),
            "unmask_own_share": float(np.mean(own)) if own else 1.0,
            "unmask_ref_share": float(np.mean(ref)) if ref else 1.0}


def run_check(cell, model, params, engine, seed_word) -> dict:
    """The check on the serving engine: ``drive_check``, the reference a
    probed row, ``compare`` and the decision."""
    import numpy as np

    from benchmark.lib import loader

    cf = cell["config_file"]
    ck, block, mask_id = cf["check"], model.config.diffusion_block_size, model.config.mask_token_id
    routed = loader.load_module("builders", "serve_routed", cell["root"])
    reference = loader.load_reference(cell)
    hp = reference.hyper_from_published(cf)
    n_rows = int(cell["traffic_file"].get("gateway", {}).get("max_inflight_per_replica")
                 or cf["engine"]["max_ragged_sequence_count"])
    prompts = check_prompts(cell, model, seed_word, n_rows)
    timed, probed, probes = drive_check(cell, engine, prompts)

    def reference_rows():
        return [reference_logits(reference, hp, params, prompts[row], timed[row], probe_of_row(probes, j), block)
                for j, row in enumerate(ck["probe_rows"])]

    wants = without_pools(engine, reference_rows)
    got, want, ids, nxt, step = [], [], [], [], []
    for j, (row, ref_row) in enumerate(zip(ck["probe_rows"], wants)):
        probe, k = probe_of_row(probes, j), 0
        for b in range(probe["ids"].shape[0]):
            n = int(probe["forwards"][b])
            for i in range(n):
                got.append(probe["logits"][b, i])
                want.append(ref_row[k])
                ids.append(probe["ids"][b, i])
                nxt.append(probe["ids"][b, i + 1] if i + 1 < n else probed[row, b * block:(b + 1) * block])
                step.append(i)
                k += 1
    cmp = compare(reference, hp, np.stack(got), np.stack(want), np.stack(ids), np.stack(nxt), step,
                  cf["engine"]["generation"]["remasking"])
    check = {"rel_l2": list(cmp["rel_l2"]), "rel_l2_tol": ck["rel_l2_tol"], "forwards": len(got),
             "rows": n_rows, "probe_rows": list(ck["probe_rows"]),
             "finite": bool(all(np.isfinite(g).all() for g in got)),
             "mask_tokens": int((timed == mask_id).sum()), "argmax_equal": list(cmp["argmax_equal"]),
             "rel_l2_centred_low": float(np.quantile(cmp["rel_l2_centred"], float(ck["quantile"]))),
             "timed_tokens_equal_share": float((timed == probed).mean()), "timed_equal_min": ck["timed_equal_min"],
             "unmask_own_share": cmp["unmask_own_share"], "unmask_own_min": ck["unmask_own_min"],
             "unmask_choices": cmp["unmask_choices"], "unmask_ref_share": cmp["unmask_ref_share"],
             "unmask_ref_min": ck["unmask_ref_min"]}
    # (a MASK id among the final tokens is the model's own argmax there: ``unmask_own_share`` holds that)
    check["ok"] = bool(check["finite"] and max(check["rel_l2"]) <= ck["rel_l2_tol"]
                       and check["timed_tokens_equal_share"] >= ck["timed_equal_min"]
                       and check["unmask_own_share"] >= ck["unmask_own_min"]
                       and check["unmask_ref_share"] >= ck["unmask_ref_min"])
    out = routed.decide(check, float(ck["quantile"]), float(ck["quantile_tol"]))
    out["detail"] = {"rel_l2": cmp["rel_l2"], "rel_l2_centred": cmp["rel_l2_centred"], "timed": timed, "probed": probed}
    return out


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
        params = jax.block_until_ready(make_params(serve)(model, seed_word(seed), dtype))
    mark("weights")
    engine = make_engine(cell, model, params, dtype, rehearsal)
    mark("engine")
    with common.span("check"):
        check = run_check(cell, model, params, engine, seed_word(seed))
        check.pop("detail")
    mark("check")

    # warm the programs this traffic can reach. Prefill: a put holds chunks alone (a diffusion row rides
    # in no put), at most one row continued from the step before and as many new prompts as fit the budget:
    # the smallest row bucket that holds budget / shortest prompt + 2 rows. Bursts: every row bucket up to
    # the in-flight limit, a power of two of blocks up to the scheduler's horizon
    gwc, block = tf.get("gateway", {}), cfg.diffusion_block_size
    inflight = int(gwc.get("max_inflight_per_replica") or cf["engine"]["max_ragged_sequence_count"])
    budget = int(gwc.get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    put_rows = min(inflight, budget // max(block, int(tf["prompt_tokens"].get("lo", 1)) // block * block) + 2)
    token_buckets = serve._buckets_up_to(engine.batch.token_buckets, budget)
    seq_buckets = serve._buckets_up_to(engine.batch.seq_buckets, inflight)
    longest = int(tf["output_tokens"].get("hi", tf["output_tokens"].get("value", 1)))
    horizons = [h * block for h in (1, 2, 4, 8) if h * block <= 32 and (h - 1) * block < longest]
    put_buckets = serve._buckets_up_to(engine.batch.seq_buckets, put_rows)
    with common.span("warmup"):
        warmed = engine.warmup(put_buckets, (), token_buckets=token_buckets, declare_warmed=False)
        warmed += engine.warmup(seq_buckets, horizons)
        mark("warmup_programs")
        # a put's result is cut to its live rows ON the device, an eager slice that XLA compiles once per
        # (bucket, n): run each once here, so that none compiles in the window (a burst's tokens are cut
        # on the host)
        zeros = jax.jit(lambda shape: jnp.zeros(shape, jnp.int32), static_argnums=0)
        for bucket in put_buckets:
            padded = zeros((bucket, ))
            for n in range(1, bucket + 1):
                padded[:n]
        n_slices = sum(put_buckets)
    mark("warmup_slices")
    steps: list = []
    instrument(serve, engine, steps)
    gateway = ServingGateway([engine], GatewayConfig(
        enabled=True, port=0, token_budget=int(gwc.get("token_budget", 0)),
        max_inflight_per_replica=int(gwc.get("max_inflight_per_replica", 0)))).start()
    return SimpleNamespace(engine=engine, gateway=gateway, cfg=cfg, steps=steps, check=check,
                           programs_warmed=len(warmed), slices_warmed=n_slices, kv_blocks=engine.num_kv_blocks,
                           kv_itemsize=jnp.dtype(dtype).itemsize)


def main(argv=None) -> int:
    """The check over seeds, sound and under each control; one JSON line a reading."""
    import argparse

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated: each draws a model and the check's prompts")
    ap.add_argument("--controls", default=",".join(CONTROLS), help="run on every seed")
    ap.add_argument("--more-controls", default="", help="run on the first --more-on seeds only")
    ap.add_argument("--more-on", type=int, default=0)
    ap.add_argument("--out", default=None, help="directory for the per-position readings (.npz a reading)")
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
    keep = ("ok", "within_loose", "rel_l2_low", "rel_l2_median", "rel_l2_max", "quantile_tol", "rel_l2_tol",
            "rel_l2_centred_low", "forwards", "mask_tokens", "argmax_equal_share",
            "timed_tokens_equal_share", "unmask_choices", "unmask_own_share", "unmask_ref_share")
    cell = loader.resolve_cell(args.workload, root, rehearsal=args.rehearsal)
    serve = loader.load_module("builders", "serve", root)
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    model = TransformerLM(model_config(cell["config_file"], dtype))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = jax.block_until_ready(make_params(serve)(model, seed_word(seed), dtype))
        controls = [c for c in (args.controls + ("," + args.more_controls if n < args.more_on else "")).split(",") if c]
        for control in [None] + controls:
            t0 = time.perf_counter()
            engine = make_engine(cell, model, params, dtype, args.rehearsal, control)
            check = run_check(cell, model, params, engine, seed_word(seed))
            del engine
            gc.collect()  # its pool goes before the next engine's is sized (the programs' closures hold it in a cycle)
            detail = check.pop("detail")
            if args.out:
                np.savez_compressed(os.path.join(args.out, f"{seed}.{control or 'sound'}.npz"), **detail)
            quantiles = {f"q{int(q * 100):02d}": round(float(np.quantile(check["rel_l2"], q)), 6)
                         for q in (0.05, 0.1, 0.25, 0.5, 0.75, 0.9)}
            print(json.dumps({"seed": seed, "control": control or "sound", "seconds": round(time.perf_counter() - t0, 1),
                              **{k: check[k] for k in keep}, **quantiles}), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
