#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

Drives the two main paths once, in ONE process, through the entry points a
user calls, at the full width of the one model with chip history (748M
Llama-arch: h 2048, 12 layers, 16 heads x d 128, ffn 5632, vocab 32000, bf16,
random weights from a seed):

  server   ServingGateway over InferenceEngineV2: engine.warmup, then HTTP
           /healthz and POST /v1/generate (512-token prompts, 64 new tokens,
           SSE and blocking, three in flight at once), a repeated prompt, and
           last-position logits against models.transformer.forward with
           attention_impl="reference" on the same parameters.
  trainer  deepspeed_tpu.initialize + engine.train_batch: ZeRO-3 bf16,
           micro 2, seq 2048, global batch 8 over data=<all devices>, four
           steps on one repeated batch.

Both phases also prove the Pallas kernels are IN the compiled programs (flash
forward + both backward passes in the train step, each ONCE: the remat policy
saves the kernel's output; the paged kernel in the prefill and decode steps).
The server runs first and is freed, so both fit one 16 GB chip. Any failed
check or exception is a non-zero exit; nothing is downgraded to a warning.

    python chip_smoke.py                                   # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal     # control flow only

With no TPU visible the script exits non-zero and prints no result. The
rehearsal (tiny sizes, paged kernel through the Pallas interpreter) exists
to debug the script itself off the chip: every line says "rehearsal" and it
prints no time, rate or utilisation. The library's log goes to stderr; the
last line of stdout is one JSON object.
"""

import argparse
import dataclasses
import functools
import gc
import http.client
import json
import math
import os
import re
import sys
import threading
import time

FULL = dict(vocab=32000, hidden=2048, layers=12, heads=16, ffn=5632,
            seq=2048, micro=2, global_batch=8, steps=4,
            prompt=512, new_tokens=64, kv_block=128, kv_blocks=224, max_seqs=32,
            batch_tokens=512, max_context=768)
# the rehearsal keeps what shapes the control flow (global batch, micro, gas,
# a prompt that fills the token budget, several decode horizons); the rest is cut
TINY = dict(vocab=256, hidden=64, layers=2, heads=4, ffn=128,
            seq=64, micro=2, global_batch=8, steps=4,
            prompt=32, new_tokens=8, kv_block=16, kv_blocks=40, max_seqs=8,
            batch_tokens=32, max_context=64)

FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
CUSTOM_CALL = "tpu_custom_call"


class SmokeFailure(AssertionError):
    """A smoke check that did not hold."""


class Smoke:
    """Run state shared by the phases: sizes, the output prefix, the checks."""

    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.size = TINY if rehearsal else FULL
        self.record = {}

    def say(self, msg: str):
        print(("rehearsal: " if self.rehearsal else "") + msg, flush=True)

    def say_time(self, what: str, seconds: float):
        """A time is a device metric: printed on the chip, never in rehearsal."""
        if not self.rehearsal:
            self.say(f"{what}: {seconds:.2f} s")

    def check(self, ok: bool, what: str):
        if not ok:
            raise SmokeFailure(what)
        self.say(f"ok: {what}")

    def model_config(self, **extra):
        import jax.numpy as jnp
        from deepspeed_tpu.models import TransformerConfig

        s = self.size
        return TransformerConfig(
            vocab_size=s["vocab"], hidden_size=s["hidden"], num_layers=s["layers"],
            num_heads=s["heads"], num_kv_heads=s["heads"], intermediate_size=s["ffn"],
            max_seq_len=s["seq"], norm="rmsnorm", positions="rotary", mlp="swiglu",
            dtype=jnp.float32 if self.rehearsal else jnp.bfloat16,
            attention_impl="flash", **extra)

    def check_kernels(self, what: str, lowered, names, narrow=()):
        """The named Pallas kernels are in the program XLA compiled, and no
        array in it has one of the minor dimensions ``narrow``. Off the chip
        the program is only lowered: a CPU program holds no Mosaic call to
        find, so compiling it again would prove nothing."""
        text = lowered.as_text()
        if self.rehearsal:
            self.say(f"{what}: lowered; kernel presence is a chip-only check")
            return None
        compiled_text = lowered.compile().as_text()
        calls = {n: text.count(f'"{n}"') for n in names}
        self.check(all(calls.values()) and CUSTOM_CALL in compiled_text,
                   f"{what}: Pallas calls lowered {calls}, {compiled_text.count(CUSTOM_CALL)} x "
                   f"{CUSTOM_CALL} in the compiled program")
        if narrow:
            widths = "|".join(str(n) for n in narrow)
            found = sorted(set(re.findall(rf"\b(?:f32|bf16)\[(?:\d+,)+(?:{widths})\]", compiled_text)))
            self.check(not found, f"{what}: no array with a minor dimension of {' or '.join(map(str, narrow))} "
                                  f"in the compiled program: {found}")
        return calls


def memory_lines(smoke: Smoke, label: str):
    """Per-device memory as the runtime reports it; returns bytes_in_use."""
    import jax

    in_use = []
    for d in jax.devices():
        st = d.memory_stats()
        if not st:
            smoke.say(f"memory {label}: device {d.id} reports no memory_stats")
            continue
        in_use.append(int(st["bytes_in_use"]))
        smoke.say(f"memory {label}: device {d.id} bytes_in_use={st['bytes_in_use']} "
                  f"peak_bytes_in_use={st['peak_bytes_in_use']} bytes_limit={st['bytes_limit']}")
    return in_use


def post_generate(port: int, prompt, new_tokens: int, stream: bool):
    """One POST /v1/generate; returns (status, tokens, t_sent, t_done)."""
    from deepspeed_tpu.serving.gateway import parse_sse

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        body = json.dumps({"prompt": [int(t) for t in prompt],
                           "max_new_tokens": new_tokens, "stream": stream})
        t_sent = time.perf_counter()
        conn.request("POST", "/v1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        t_done = time.perf_counter()
    finally:
        conn.close()
    if resp.status != 200:
        return resp.status, raw.decode("utf-8", "replace"), t_sent, t_done
    if stream:
        frames = parse_sse(raw)
        final = frames[-1]
        if not final.get("done") or final.get("error"):
            return 500, json.dumps(final), t_sent, t_done
        tokens = [f["token"] for f in frames if "token" in f]
    else:
        tokens = json.loads(raw)["tokens"]
    return resp.status, tokens, t_sent, t_done


def check_generated(smoke: Smoke, label: str, status: int, tokens, want: int):
    """One answered request: 200 with the asked number of tokens (on another
    status ``tokens`` holds the response body, which the message shows)."""
    got = len(tokens) if status == 200 else tokens
    smoke.check(status == 200 and got == want, f"{label}: 200 with {want} tokens (got {status}, {got})")


def server_phase(smoke: Smoke):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.transformer import forward
    from deepspeed_tpu.serving import GatewayConfig, ServingGateway

    s = smoke.size
    cfg = smoke.model_config()
    sm = DSStateManagerConfig(max_tracked_sequences=s["max_seqs"],
                              max_ragged_batch_size=s["batch_tokens"],
                              max_ragged_sequence_count=s["max_seqs"],
                              max_context=s["max_context"])
    icfg = RaggedInferenceEngineConfig(kv_block_size=s["kv_block"], num_kv_blocks=s["kv_blocks"],
                                       kv_dtype=cfg.dtype, state_manager=sm)
    if smoke.rehearsal:
        # the same kernel program through the Pallas interpreter
        icfg.modules.attention = {"name": "paged_pallas_attention",
                                  "implementation_config": {"interpret": True}}
    smoke.say(f"server: one replica on device {jax.devices()[0].id} of {jax.device_count()} "
              "(InferenceEngineV2 takes no device argument; one replica per chip is ROADMAP R5b)")
    engine = InferenceEngineV2(TransformerLM(cfg), icfg)
    smoke.check(smoke.rehearsal or engine._use_pallas, "server: engine selected the Pallas modules")

    # every bucket the traffic below can reach: prompts fill one token bucket,
    # mixed decode+tail steps the smallest, decode bursts quantize to 2^k <= 32
    seq_bucket = engine.batch.seq_buckets[0]
    tok_small = engine.batch.token_buckets[0]
    horizons = [h for h in (32, 16, 8, 4, 2, 1) if h < s["new_tokens"]]
    t0 = time.perf_counter()
    warmed = engine.warmup([seq_bucket], horizons, token_buckets=[s["prompt"], tok_small])
    smoke.say_time(f"server: warmup of {len(warmed)} programs (compile included)",
                   time.perf_counter() - t0)
    smoke.record["server_programs_warmed"] = len(warmed)

    mb = engine._max_blocks_per_seq
    n_put = 4 * s["prompt"] + seq_bucket * (mb + 1)  # packed descriptor sizes, as in warmup()
    n_decode = seq_bucket * (5 + mb)

    def check_step(what, key, n_packed, kernel):
        smoke.check(key in engine._compiled, f"{what}: bucket {key} is compiled")
        lowered = engine._compiled[key].lower(engine.params, jnp.zeros(n_packed, jnp.int32),
                                              engine.state_manager.kv_cache.pools())
        smoke.check_kernels(what, lowered, (kernel, ))

    check_step("server prefill step", (s["prompt"], seq_bucket, "greedy"), n_put,
               "paged_attn_q_tiled")
    check_step("server decode step", ("decode", seq_bucket, horizons[0], False), n_decode,
               "paged_attn_kv_split")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, s["vocab"], size=s["prompt"], dtype=np.int32) for _ in range(4)]
    gw = ServingGateway([engine], GatewayConfig(enabled=True, port=0)).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        health = json.loads(resp.read())
        conn.close()
        smoke.check(resp.status == 200 and health.get("live") is True and health.get("ready") is True,
                    "GET /healthz is 200, live and ready")

        # the same prompt twice, alone both times (so both runs take the same
        # programs): once blocking, once streamed
        st_a, toks_a, _, _ = post_generate(gw.port, prompts[0], s["new_tokens"], stream=False)
        st_b, toks_b, _, _ = post_generate(gw.port, prompts[0], s["new_tokens"], stream=True)
        check_generated(smoke, "blocking request", st_a, toks_a, s["new_tokens"])
        check_generated(smoke, "SSE request", st_b, toks_b, s["new_tokens"])
        smoke.check(toks_a == toks_b, "the same prompt twice returns identical tokens")

        # three more in flight at once, SSE and blocking mixed
        results = [None] * 3
        barrier = threading.Barrier(3)

        def client(i):
            barrier.wait(timeout=60)
            results[i] = post_generate(gw.port, prompts[1 + i], s["new_tokens"], stream=i != 1)

        threads = [threading.Thread(target=client, args=(i, ), daemon=True) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        smoke.check(all(not t.is_alive() for t in threads) and all(r is not None for r in results),
                    "three concurrent requests returned")
        for i, (status, toks, _, _) in enumerate(results):
            check_generated(smoke, f"concurrent request {i}", status, toks, s["new_tokens"])
        overlap = max(r[2] for r in results) < min(r[3] for r in results)
        smoke.check(overlap, "the three requests were in flight at once (every one was sent "
                             "before any had finished)")
        smoke.record["requests_served"] = 5
    finally:
        gw.stop()

    # last-position logits of one prompt against the plain-jnp forward on the
    # SAME parameters, both in the model dtype. The tolerance is the repo's
    # own for bf16 logits on the chip (tests_tpu: rtol 5e-2): bf16 keeps 8
    # mantissa bits through 12 layers in another summation order, while a
    # wrong mask, position or block table is an error of order one.
    uid = 10**6
    logits = np.asarray(engine.put([uid], [prompts[0]], sample=None), np.float32)[0]
    engine.flush(uid)
    check_step("server logits step", (s["prompt"], seq_bucket, None), n_put, "paged_attn_q_tiled")
    ref_cfg = dataclasses.replace(cfg, attention_impl="reference")
    ref = np.asarray(jax.jit(lambda p, ids: forward(ref_cfg, p, ids))(
        engine.params, jnp.asarray(prompts[0][None, :])), np.float32)[0, -1]
    smoke.check(logits.shape == (s["vocab"], ) and np.isfinite(logits).all(),
                f"engine logits are finite with shape ({s['vocab']},)")
    rel_l2 = float(np.linalg.norm(logits - ref) / np.linalg.norm(ref))
    max_abs = float(np.abs(logits - ref).max())
    scale = float(np.abs(ref).max())
    tol_l2, tol_abs = (1e-4, 1e-4) if smoke.rehearsal else (5e-2, 5e-2)
    smoke.check(rel_l2 <= tol_l2 and max_abs <= tol_abs * max(scale, 1.0),
                f"engine.put logits agree with forward(attention_impl='reference'): "
                f"rel_l2={rel_l2:.2e} (<= {tol_l2}), max_abs={max_abs:.3e} "
                f"(<= {tol_abs} x max|ref|={scale:.3f}), argmax equal: "
                f"{int(logits.argmax()) == int(ref.argmax())}")

    # free the replica so the trainer has the chip's memory to itself
    del gw, engine, check_step, logits, ref
    gc.collect()
    memory_lines(smoke, "after the server was freed")


def diffusion_phase(smoke: Smoke):
    """A model generated by masked diffusion over blocks (SDAR's widths, the
    layers and the rows of ``sdar-30b-a3b-chat.block-diffusion-64``): ONE
    ``decode`` call of four blocks, in which a block's commit rides in the next
    block's first denoise forward, against the same call written out forward by
    forward through the engine's own ``_ragged_step`` (four denoise forwards
    and a ``kv_only`` commit a block, of ``S x B`` tokens each) from the same
    pools: tokens equal. Then the three shapes of forward a call is made of, a
    time each: a denoise forward, one that carries a commit (``S x 2B``
    tokens) and the commit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.diffusion import rows_beside
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatchWrapper
    from deepspeed_tpu.inference.v2.sampling import diffusion_candidates, diffusion_quota, diffusion_unmask
    from deepspeed_tpu.models import TransformerLM, sdar_config
    from deepspeed_tpu.monitor.trace import get_tracer

    if smoke.rehearsal:
        cfg = sdar_config("tiny", dtype=jnp.float32)
        S, prompt, put_rows, kv_block, kv_blocks, max_context = 8, 16, 4, 16, 40, 64
    else:
        cfg = sdar_config("30b-a3b", num_layers=8, dtype=jnp.bfloat16)
        S, prompt, put_rows, kv_block, kv_blocks, max_context = 64, 1024, 2, 128, 592, 8320
    B, mask_id, n_blocks = cfg.diffusion_block_size, cfg.mask_token_id, 4
    T = S * B
    model = TransformerLM(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k, None), jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    def draw(key):  # in the served type from the start: no float32 copy of 11 GB of experts is ever alive
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(jax.random.fold_in(key, i), a.shape, cfg.dtype) / math.sqrt(a.shape[-2]) if a.ndim >= 2
            else jnp.ones(a.shape, cfg.dtype) for i, a in enumerate(leaves)])

    params = jax.block_until_ready(jax.jit(draw)(jax.random.PRNGKey(50)))
    sm = DSStateManagerConfig(max_tracked_sequences=S, max_ragged_batch_size=put_rows * prompt,
                              max_ragged_sequence_count=S, max_context=max_context)
    icfg = RaggedInferenceEngineConfig(kv_block_size=kv_block, num_kv_blocks=kv_blocks, kv_dtype=cfg.dtype,
                                       state_manager=sm)
    if smoke.rehearsal:
        icfg.modules.attention = {"name": "paged_pallas_attention", "implementation_config": {"interpret": True}}
    engine = InferenceEngineV2(model, icfg, params=params)
    rng = np.random.default_rng(50)
    uids = list(range(S))
    # every row a prompt of whole blocks and r = row mod 4 tokens more, which open its first block
    prompts = [rng.integers(0, cfg.vocab_size - 1, size=prompt + r % B, dtype=np.int32) for r in uids]
    for at in range(0, S, put_rows):
        engine.put(uids[at:at + put_rows], [p[:prompt] for p in prompts[at:at + put_rows]], sample="greedy")
    known = [p[prompt:] for p in prompts]
    kv = engine.state_manager.kv_cache
    before = tuple(jnp.copy(p) for p in kv.pools())
    batches, finalize = [], RaggedBatchWrapper.finalize
    RaggedBatchWrapper.finalize = lambda self: batches.append(finalize(self)) or batches[-1]
    tracer = get_tracer().configure(enabled=True)
    try:
        start = [engine.state_manager.get_sequence(u).seen_tokens for u in uids]
        toks = np.asarray(engine.decode(uids, known, n_blocks * B))
        (span, ) = [e["args"] for e in tracer.drain() if e["ph"] == "X" and e["name"] == "serving/decode"]
        call_s = []
        for _ in range(0 if smoke.rehearsal else 3):  # the same call again from the same committed lengths
            for u, at in zip(uids, start):
                engine.state_manager.rollback_to(engine.state_manager.get_sequence(u), at)
            t0 = time.perf_counter()
            engine.decode(uids, known, n_blocks * B)
            call_s.append(time.perf_counter() - t0)
    finally:
        RaggedBatchWrapper.finalize = finalize
        get_tracer().reset()
    smoke.check((span["steps"], span["denoise_forwards"], span["commit_forwards"], span["fused_commits"])
                == (4 * n_blocks + 1, 4 * n_blocks, 1, n_blocks - 1),
                f"diffusion: a call of {n_blocks} blocks ran {span['steps']} forwards, {span['fused_commits']} commits "
                f"rode in a denoise forward and {span['commit_forwards']} ran alone ({span['kernel']})")

    rb = batches[0]
    packed, step = rb.packed(), engine._ragged_step
    jit = lambda **kw: functools.partial(jax.jit(  # (the weights an ARGUMENT: closed over they would be constants)
        lambda params, packed, pools, n: step(params, packed, pools, n * T, S, moe_stats=True, **kw)[:2],
        static_argnums=3, donate_argnums=2, **engine._jit_options), params)
    denoise, commit = jit(gather_k=B - 1), jit(kv_only=True)
    valid, quota = packed[3 * T:4 * T] > 0, diffusion_quota(B, 4)
    pools, want = before, []
    for b in range(n_blocks):
        at = packed.copy()
        at[2 * T:3 * T] += b * B
        ids = packed[0:T].copy() if b == 0 else np.full(T, mask_id, np.int32)
        for i in range(4):
            at[0:T] = ids
            logits, pools = denoise(jnp.asarray(at), pools, 1)
            tok, conf = diffusion_candidates(logits)
            masked = valid & (ids == mask_id)
            choose = np.asarray(diffusion_unmask(conf.reshape(S, B), jnp.asarray(masked.reshape(S, B)),
                                                 "low_confidence_static", quota[i], 0.9, i == 3)).reshape(T)
            ids = np.where(choose, np.asarray(tok), ids)
        at[0:T] = ids
        _, pools = commit(jnp.asarray(at), pools, 1)
        want.append(ids.reshape(S, B))
    want = np.concatenate(want, axis=1)
    equal = [float((toks[:, b * B:(b + 1) * B] == want[:, b * B:(b + 1) * B]).mean()) for b in range(n_blocks)]
    # bf16 on the chip, random weights: a block's K/V come out of a forward of another shape, the confidences of
    # a block's positions lie close together, and a row that chose another token reads another context from
    # there on (0.949 of a call's tokens equal: my chip run, PR 50); a wrong position, mask or table leaves none
    smoke.check(min(equal) == 1.0 if smoke.rehearsal else equal[0] >= 0.95 and min(equal) >= 0.8,
                "diffusion: the call's tokens are the written-out sequence's (equal a block: "
                + ", ".join(f"{e:.4f}" for e in equal) + ")")
    smoke.check(not (toks == mask_id).any() and all(toks[r, :r % B].tolist() == known[r].tolist() for r in uids),
                "diffusion: no mask is left and a row's open tokens lead its first block")
    if not smoke.rehearsal:
        # a forward that carries a commit, as diffusion.build_block_program lays it: a row's block before, then its block
        beside = lambda before, block: rows_beside(before, block, B, np)
        at = packed.copy()
        at[2 * T:3 * T] += B
        pos = at[2 * T:3 * T]
        both = np.concatenate([beside(want[:, :B].reshape(T), at[0:T]), beside(at[T:2 * T], at[T:2 * T]),
                               beside(pos - B, pos), beside(at[3 * T:4 * T], at[3 * T:4 * T]), at[4 * T:-S],
                               2 * at[-S:] + 1]).astype(np.int32)
        ms = {}
        for name, fn, desc, n in (("denoise", denoise, at, 1), ("fused", denoise, both, 2), ("commit", commit, at, 1)):
            desc = jnp.asarray(desc)
            _, pools = fn(desc, pools, n)
            jax.block_until_ready(pools)
            t0 = time.perf_counter()
            for _ in range(20):
                _, pools = fn(desc, pools, n)
            jax.block_until_ready(pools)
            ms[name] = (time.perf_counter() - t0) / 20 * 1e3
        ms["call_of_4_blocks"] = float(np.median(call_s)) * 1e3
        ms["reckoned_call"] = 12 * ms["denoise"] + 4 * ms["fused"] + ms["commit"]
        ms["five_forwards_a_block"] = 16 * ms["denoise"] + 4 * ms["commit"]
        smoke.record["diffusion_ms"] = {k: round(v, 3) for k, v in ms.items()}
        smoke.say("diffusion: ms a forward of %d rows x %d tokens: denoise %.2f, with a commit riding (2 x %d tokens a "
                  "row) %.2f (%.2f x), kv_only commit %.2f; a call of 4 blocks %.1f ms (12 + 4 + 1 forwards reckon "
                  "%.1f; 16 + 4 as they were %.1f)" % (S, B, ms["denoise"], B, ms["fused"], ms["fused"] / ms["denoise"],
                                                    ms["commit"], ms["call_of_4_blocks"], ms["reckoned_call"],
                                                    ms["five_forwards_a_block"]))
    for u in uids:
        engine.flush(u)
    del engine, params, pools, before, denoise, commit, step
    gc.collect()
    memory_lines(smoke, "after the diffusion replica was freed")


def trainer_phase(smoke: Smoke):
    import jax
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerLM

    s = smoke.size
    n = jax.device_count()
    gas = s["global_batch"] // (s["micro"] * n)
    smoke.check(gas >= 1 and gas * s["micro"] * n == s["global_batch"],
                f"global batch {s['global_batch']} = micro {s['micro']} x gas {gas} x {n} devices")
    # a quarter of each head rotates, as in the Pythias the benchmark trains
    cfg = smoke.model_config(remat=True, remat_policy="save_only_these_names(attn_out)")
    cfg = dataclasses.replace(cfg, rotary_dim=cfg.head_dim // 4)
    config = {
        "train_batch_size": s["global_batch"],
        "train_micro_batch_size_per_gpu": s["micro"],
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.0}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": not smoke.rehearsal},
        "steps_per_print": 10**9,
        "tpu": {"mesh": {"data": n}},
    }
    model = TransformerLM(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    smoke.check(dict(engine.mesh.shape)["data"] == n, f"mesh has data={n}")
    smoke.say(f"trainer: {model.num_params() / 1e6:.1f}M parameters, ZeRO-3, seq {s['seq']}, "
              f"micro {s['micro']} x gas {gas} x data {n}")
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, s["vocab"], size=(s["global_batch"], s["seq"]),
                                       dtype=np.int32)}
    losses = []
    for step in range(s["steps"]):
        t0 = time.perf_counter()
        loss = float(np.asarray(engine.train_batch(batch)))  # host fetch ends the step
        dt = time.perf_counter() - t0
        losses.append(loss)
        smoke.say(f"trainer: step {step} loss {loss:.4f}")
        smoke.say_time(f"trainer: step {step}" + (" (compile included)" if step == 0 else ""), dt)
    smoke.check(all(np.isfinite(l) for l in losses), "every loss is finite")
    smoke.check(losses[-1] < losses[0],
                f"loss fell on the repeated batch: {losses[0]:.4f} -> {losses[-1]:.4f}")
    smoke.record["train_steps"] = len(losses)
    smoke.record["first_loss"] = round(losses[0], 4)
    smoke.record["last_loss"] = round(losses[-1], 4)

    in_use = memory_lines(smoke, "with the training state live")
    if len(in_use) > 1:
        limit = int(jax.devices()[0].memory_stats()["bytes_limit"])
        smoke.check(min(in_use) >= 0.5 * max(in_use) and max(in_use) < 0.95 * limit,
                    f"training state is spread over all {len(in_use)} devices "
                    f"(min {min(in_use)}, max {max(in_use)} bytes in use, limit {limit})")

    # partial rotary at the full width of the head: neither a half of the
    # rotated lanes nor the pass-through lanes is an array of its own
    calls = smoke.check_kernels("train step", engine.aot_lower_train_step(s["seq"]), FLASH_KERNELS,
                                narrow=(cfg.rotary_dim // 2, cfg.head_dim - cfg.rotary_dim))
    if calls is not None:
        # the policy saves attn_out, which the kernel gives its output and its
        # log-sum-exp: a second flash_fwd means the remat recomputes it again
        smoke.check(len(set(calls.values())) == 1,
                    f"train step: one flash_fwd for each backward pair under {cfg.remat_policy}: {calls}")
    engine.destroy()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at a tiny size; needs JAX_PLATFORMS=cpu")
    args = ap.parse_args(argv)
    if args.rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("chip_smoke: --rehearsal runs only with JAX_PLATFORMS=cpu in the environment",
              file=sys.stderr)
        return 2

    import jax

    from deepspeed_tpu.monitor.metrics import peak_flops_per_chip, peak_hbm_bw_per_chip
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logger

    for h in logger.handlers:  # stdout carries the result, stderr the library's log
        h.setStream(sys.stderr)
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearsal and dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX {jax.__version__} found {len(devices)} x "
              f"{dev.platform} ({dev.device_kind}); JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}. Run on the chip, or rehearse with "
              "JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal", file=sys.stderr)
        return 2

    smoke = Smoke(args.rehearsal)
    t_start = time.perf_counter()
    smoke.say(f"jax {jax.__version__} platform={dev.platform} device_kind={dev.device_kind!r} "
              f"devices={len(devices)}")
    placed_by = "JAX_COMPILATION_CACHE_DIR" if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        else "checkout default"
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    smoke.say(f"compile cache: {cache_dir} ({placed_by}, {entries} entries at start)")
    peak_flops, peak_bw = peak_flops_per_chip(dev.device_kind), peak_hbm_bw_per_chip(dev.device_kind)
    if smoke.rehearsal:
        smoke.say(f"peaks for {dev.device_kind!r}: {peak_flops}, {peak_bw} (none off the chip)")
    else:
        smoke.check(peak_flops is not None and peak_bw is not None,
                    f"peaks resolve for {dev.device_kind!r}: {peak_flops} FLOP/s bf16, "
                    f"{peak_bw} B/s HBM")
    memory_lines(smoke, "at start")

    server_phase(smoke)
    diffusion_phase(smoke)
    trainer_phase(smoke)

    smoke.say_time("total", time.perf_counter() - t_start)
    smoke.say("record: " + json.dumps(smoke.record))
    result = json.dumps({"ok": True, "device": {"platform": dev.platform,
                                                "kind": dev.device_kind,
                                                "count": len(devices)}})
    smoke.say(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
