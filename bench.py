"""Benchmark: training tokens/sec/chip + FastGen-style serving on the
flagship model family.

Prints TWO JSON lines; the LAST is the headline training metric (tracked
round-over-round by the driver), the first is the serving plane:

  {"metric": "fastgen_decode_tokens_per_sec_per_chip", ..., "ttft_p50_ms": ...}
  {"metric": "train_tokens_per_sec_per_chip", ..., "serving": {...}}

Training metric: decoder-LM training throughput (tokens/sec/chip) in bf16
with the fused train step on a Llama-2-architecture model (rmsnorm/rotary/
swiglu — the BASELINE.md target workload) at the largest configuration that
fits one v5e chip's HBM with ZeRO-3 + Adam. ``vs_baseline`` reports achieved
MFU relative to the reference's published 54%-of-peak Ulysses number
(`blogs/deepspeed-ulysses/README.md:81-83` — the only hardware-normalized
efficiency figure the reference publishes), i.e. vs_baseline = MFU / 0.54.

Serving metric (reference methodology `blogs/deepspeed-fastgen/README.md:139-144`:
p50 TTFT + steady-state generation throughput under continuous batching):
InferenceEngineV2.put drives prefill (whole prompt) then batched decode (one
token per tracked sequence per step) through the paged-KV ragged plane.
``vs_baseline`` for serving is achieved decode throughput over the single-chip
HBM roofline (decode is bandwidth-bound: every step re-reads the bf16 params
and each sequence's KV) — a hardware-normalized efficiency comparable across
rounds, with the absolute A100 bar unavailable on one v5e chip.

Attention runs the Pallas flash kernel (fwd+bwd); the remat policy saves the
attention context (`save_only_these_names(attn_out)`) so the backward never
recomputes the flash kernel; gradient accumulation amortizes the
HBM-bandwidth-bound Adam step over 16 microbatches.

Process layout: ONE process. ``python bench.py`` runs the bench in the
process that imports JAX, because a chip belongs to one process at a time: a
parent that touched JAX holds the chip, and a child that needs it then fails
or hangs. With no TPU visible the script exits non-zero unless the caller
asked for the CPU smoke explicitly with ``JAX_PLATFORMS=cpu`` (tiny shapes,
``"on_tpu": false`` disclosed, device metrics null). The on-chip kernel suite
is its own command in its own process: ``python -m pytest tests_tpu -q``.
"""

import gc
import json
import os
import sys
import time


def backend_stamp(on_tpu: bool) -> dict:
    """``{'backend': 'tpu'|'cpu', 'chip': <device_kind>}`` — stamped into
    every final JSON line so round-over-round tooling can tell a CPU-fallback
    number from an on-chip one WITHOUT reading prose caveats (the
    BENCH_r04/r05 lesson: r04/r05 ran CPU-only and their headline values are
    not comparable to the r01-r02 on-chip rounds)."""
    chip = "cpu"
    if on_tpu:
        try:
            import jax

            chip = str(jax.devices()[0].device_kind)
        except Exception:
            chip = "tpu-unknown"
    return {"backend": "tpu" if on_tpu else "cpu", "chip": chip}


def backend_of(line: dict):
    """Backend stamp of a bench JSON line: explicit ``backend`` wins, the
    pre-r06 ``on_tpu`` field is the fallback, neither -> None."""
    b = line.get("backend")
    if b is None and "on_tpu" in line:
        b = "tpu" if line.get("on_tpu") else "cpu"
    return b


def comparability_refusal(base: dict, cur: dict):
    """Why a base-vs-cur ratio would be MEANINGLESS (None = comparable):
    missing backend stamps, cross-backend, or cross-chip. The shared
    refusal core of :func:`compare_to_baseline` and
    ``tools/perf_sentinel.py``'s round-trajectory verdicts — the r04/r05
    lesson (CPU-fallback rounds silently ratioed against on-chip rounds)
    machine-checked in one place."""
    b_backend = backend_of(base)
    c_backend = backend_of(cur)
    if b_backend is None:
        return "baseline carries no backend stamp (pre-r06 format without on_tpu)"
    if b_backend != c_backend:
        return f"cross-backend comparison: baseline={b_backend} current={c_backend}"
    if base.get("chip") and cur.get("chip") and base["chip"] != cur["chip"]:
        return f"cross-chip comparison: baseline={base['chip']} current={cur['chip']}"
    return None


def compare_to_baseline(line: dict, baseline_path: str) -> dict:
    """Headline-vs-previous-round comparison that REFUSES cross-backend
    ratios. Accepts a raw bench JSON line or the driver's ``BENCH_rXX.json``
    wrapper (``{"parsed": {...}}``). A baseline without a backend stamp is
    judged by its ``on_tpu`` field; one with neither is refused — an
    unknown-backend ratio is exactly the trap this exists to close."""
    try:
        with open(baseline_path) as f:
            base = json.load(f)
    except (OSError, ValueError) as e:
        return {"refused": f"unreadable baseline: {type(e).__name__}"}
    if isinstance(base, dict) and isinstance(base.get("parsed"), dict):
        base = base["parsed"]
    if not isinstance(base, dict):
        return {"refused": "baseline is not a bench JSON object"}
    refusal = comparability_refusal(base, line)
    if refusal is not None:
        return {"refused": refusal}
    b_backend = backend_of(base)
    if (base.get("metric") and line.get("metric") and base["metric"] != line["metric"]):
        # bench prints TWO stamped lines (serving + train headline) — a
        # ratio across metrics is as meaningless as one across backends
        return {"refused": f"cross-metric comparison: baseline={base['metric']} "
                           f"current={line['metric']}"}
    if not base.get("value"):
        return {"refused": "baseline has no headline value"}
    try:
        return {"ratio": round(float(line["value"]) / float(base["value"]), 4),
                "baseline_value": base["value"], "baseline_backend": b_backend}
    except (TypeError, ValueError, ZeroDivisionError) as e:
        # a malformed baseline must cost this field, never the headline line
        return {"refused": f"non-numeric baseline value: {type(e).__name__}"}


def _free_engine(engine, *attrs):
    """Drop an engine's device buffers (params/state/KV pools) so the next
    benchmark configuration has the chip's HBM to itself."""
    for a in attrs:
        setattr(engine, a, None)
    engine._compiled = {}
    gc.collect()


def bench_serving(on_tpu: bool):
    """FastGen-equivalent serving bench: p50 TTFT (prefill latency) and
    steady-state decode tokens/s/chip under continuous batching."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig

    if on_tpu:
        cfg = TransformerConfig(vocab_size=32000, hidden_size=2048, num_layers=12,
                                num_heads=16, num_kv_heads=16, intermediate_size=5632,
                                max_seq_len=2048, norm="rmsnorm", positions="rotary",
                                mlp="swiglu", dtype=jnp.bfloat16, attention_impl="flash")
        # int8 KV halves the pool: 64 tracked sequences fit where bf16 fit 32,
        # and the bigger decode batch amortizes the 1.5 GB/step weight stream —
        # the dominant serving-roofline term. DS_TPU_BENCH_NSEQS pins it; the
        # ladder below falls back 64 -> 32 on OOM so a tight chip still
        # produces a number instead of forfeiting the serving line.
        n_seqs = int(os.environ.get("DS_TPU_BENCH_NSEQS", "64"))
        prompt_len, decode_steps, block_size = 512, 192, 128
    else:  # CPU smoke
        cfg = TransformerConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                                intermediate_size=256, max_seq_len=512, dtype=jnp.float32,
                                attention_impl="reference")
        n_seqs, prompt_len, decode_steps, block_size = 4, 64, 4, 64

    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    warm_prompt = rng.integers(0, cfg.vocab_size, size=prompt_len, dtype=np.int32)

    def build(ns, k8):
        icfg = RaggedInferenceEngineConfig()
        icfg.kv_block_size = block_size
        icfg.num_kv_blocks = ns * (-(-(prompt_len + decode_steps + block_size) // block_size)) + 8
        icfg.kv_dtype = "int8" if k8 else cfg.dtype
        icfg.state_manager.max_tracked_sequences = ns
        icfg.state_manager.max_ragged_sequence_count = ns
        icfg.state_manager.max_ragged_batch_size = max(prompt_len, ns)
        icfg.state_manager.max_context = prompt_len + decode_steps + block_size
        return InferenceEngineV2(model, icfg)

    # int8 KV (FastGen quantized-KV analog) halves the decode KV stream —
    # the serving default on TPU (the on-chip kernel suite validates the int8
    # paged kernel before this bench runs; DS_TPU_BENCH_KV=bf16 reverts).
    # Fallback ladder: batch 64 -> 32, int8 -> bf16 — an OOM or a kernel
    # failure costs one rung, never the serving number (r3 lesson). 64+bf16
    # is omitted: by the sizing model above it cannot fit where 64+int8
    # didn't. Each rung warms the FULL memory-heavy program set (all-seqs
    # prefill + the widest decode scan) so a late OOM can't escape the
    # ladder, and failed rungs drop their tracebacks + collect before the
    # next build so dead buffers don't cascade-OOM the rungs that would fit.
    horizon = 64 if on_tpu else 2
    kv_int8 = on_tpu and os.environ.get("DS_TPU_BENCH_KV", "int8") == "int8"
    ladder = [(n_seqs, kv_int8)]
    if on_tpu and n_seqs > 32:
        ladder.append((32, kv_int8))
    if kv_int8:
        ladder += [(ns, False) for ns, _ in ladder if ns <= 32] or [(32, False)]

    def warm_rung(ns, k8):
        eng = build(ns, k8)
        first = eng.put([0], [warm_prompt], sample="greedy")  # compile prefill bucket
        for uid in range(1, ns):  # full-batch KV residency
            eng.put([uid], [warm_prompt], sample="greedy")
        tok = [np.asarray([int(first[0])], np.int32)] * ns
        # the timed phase's batched 1-token put (all seqs) and the widest
        # decode scan — the recompile sentinel flags any bucket this rung
        # misses as a steady-state recompile below
        eng.put(list(range(ns)), tok, sample="greedy")
        eng.decode(list(range(ns)), tok, horizon)  # compile the widest decode scan
        for uid in range(ns):
            eng.flush(uid)
        return eng

    engine, last_err = None, None
    for ns, k8 in ladder:
        try:
            engine = warm_rung(ns, k8)
            n_seqs, kv_int8 = ns, k8
            # the rung warmed every bucket the timed phases hit with REAL
            # traffic — declare the sentinel boundary and attach the serving
            # ledger so the TTFT/decode phases are wall-clock attributed and
            # any steady-state recompile below is flagged, not silent
            from deepspeed_tpu.monitor.goodput import get_goodput as _gp

            if _gp().enabled:
                engine.goodput_ledger = _gp().serving_ledger("bench")
                engine.declare_gp_warmed()
            break
        except Exception as e:
            print(f"# WARNING: serving config n_seqs={ns} kv={'int8' if k8 else 'bf16'} failed "
                  f"({type(e).__name__}: {str(e)[:200]}); trying next rung", flush=True)
            last_err = e.with_traceback(None)  # frames pin device buffers
            if engine is not None:
                _free_engine(engine, "state_manager", "params")
                engine = None
            gc.collect()
    if engine is None:
        raise last_err

    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len, dtype=np.int32) for _ in range(n_seqs)]
    # --- prefill / TTFT: one prompt per put (the FastGen TTFT definition:
    # time from request admission to its first generated token on host;
    # on-device greedy sampling so the transfer is the token, not the logits) ---
    ttfts = []
    first_tok = None
    for uid in range(n_seqs):
        t0 = time.time()
        first_tok = engine.put([uid], [prompts[uid]], sample="greedy")
        ttfts.append((time.time() - t0) * 1000.0)
    ttft_p50 = float(np.percentile(ttfts, 50))

    # --- steady-state continuous-batching decode: the multi-step on-device
    # scan (engine.decode) with greedy feedback — one host round-trip per
    # horizon instead of per token, the serving loop's steady-state shape ---
    uids = list(range(n_seqs))
    step_tok = [np.asarray([int(first_tok[0])], np.int32) for _ in uids]
    # horizon 64 (set at the rung ladder, where the scan was pre-compiled):
    # each decode() call pays one host round-trip regardless of length
    n_rounds = max(1, (decode_steps - horizon) // horizon)
    last = [np.asarray([int(t)], np.int32) for t in np.asarray(engine.put(
        uids, step_tok, sample="greedy"))]
    t0 = time.time()
    for _ in range(n_rounds):
        out = engine.decode(uids, last, horizon)
        last = [np.asarray([int(t)], np.int32) for t in out[:, -1]]
    dt = time.time() - t0
    decode_tps = n_seqs * n_rounds * horizon / dt

    # --- prefix-cache phase: hit-vs-miss TTFT on a shared-prefix stream.
    # A separate small engine (params SHARED with the main one — no second
    # HBM copy) with ragged.prefix_cache enabled: per shared system prompt,
    # the first request pays full prefill (miss), repeats prefill only their
    # unique suffix (radix hit) — the TTFT gap is the serving win ---
    prefix_line = None
    try:
        from deepspeed_tpu.inference.v2 import PrefixCacheConfig

        if on_tpu:
            n_prefixes, repeats, shared_len, suffix_len = 4, 3, 384, 128
        else:
            n_prefixes, repeats, shared_len, suffix_len = 2, 2, 48, 16
        per_seq = -(-(shared_len + suffix_len + 1) // block_size) + 1
        picfg = RaggedInferenceEngineConfig()
        picfg.kv_block_size = block_size
        picfg.num_kv_blocks = (n_prefixes + 2) * per_seq + 8
        picfg.kv_dtype = "int8" if kv_int8 else cfg.dtype
        picfg.state_manager.max_tracked_sequences = 4
        picfg.state_manager.max_ragged_sequence_count = 4
        picfg.state_manager.max_ragged_batch_size = max(prompt_len, 4)
        picfg.state_manager.max_context = shared_len + suffix_len + block_size
        picfg.use_pallas_kernels = "never" if not on_tpu else "auto"
        picfg.prefix_cache = PrefixCacheConfig(enabled=True)
        peng = InferenceEngineV2(model, picfg, params=engine.params)
        # compile the miss- and hit-shaped buckets before timing
        wp = rng.integers(0, cfg.vocab_size, size=shared_len + suffix_len, dtype=np.int32)
        peng.put([90_000], [wp], sample="greedy")
        peng.put([90_001], [wp[-suffix_len:]], sample="greedy")
        for u in (90_000, 90_001):
            peng.flush(u)
        peng.prefix_cache.clear()
        peng.prefix_cache.stats.update({k: 0 for k in peng.prefix_cache.stats})
        ttft_miss, ttft_hit = [], []
        uid = 91_000
        for p in range(n_prefixes):
            shared = rng.integers(0, cfg.vocab_size, size=shared_len, dtype=np.int32)
            for r in range(repeats + 1):
                suffix = rng.integers(0, cfg.vocab_size, size=suffix_len, dtype=np.int32)
                t0 = time.time()
                peng.put([uid], [np.concatenate([shared, suffix])], sample="greedy")
                (ttft_miss if r == 0 else ttft_hit).append((time.time() - t0) * 1000.0)
                peng.flush(uid)
                uid += 1
        pc = peng.prefix_cache
        prefix_line = {
            "hit_rate": round(pc.hit_rate, 3),
            "cached_tokens": int(pc.stats["cached_tokens"]),
            "ttft_hit_p50_ms": round(float(np.percentile(ttft_hit, 50)), 1),
            "ttft_miss_p50_ms": round(float(np.percentile(ttft_miss, 50)), 1),
        }
        _free_engine(peng, "state_manager")
    except Exception as e:
        # the headline serving numbers never forfeit to the prefix phase
        print(f"# WARNING: prefix-cache bench phase failed "
              f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    # --- HBM roofline for vs_baseline (decode is bandwidth-bound). The KV
    # term uses the bytes ACTUALLY streamed (int8 + fp32 scales in quantized
    # mode) so the ratio stays an honest fraction of the achievable bound ---
    n_params = model.num_params()
    param_bytes = n_params * np.dtype(np.float32 if cfg.dtype == jnp.float32 else np.float16).itemsize
    ctx = prompt_len + decode_steps // 2
    kv_token_bytes = (cfg.head_dim * 1 + 4) if kv_int8 else cfg.head_dim * 2
    kv_bytes_per_seq = 2 * cfg.num_layers * cfg.num_kv_heads * ctx * kv_token_bytes
    hbm_bw = 819e9 if on_tpu else 50e9  # v5e HBM bandwidth
    step_time_roofline = (param_bytes + n_seqs * kv_bytes_per_seq) / hbm_bw
    roofline_tps = n_seqs / step_time_roofline

    out = {
        "metric": "fastgen_decode_tokens_per_sec_per_chip",
        "value": round(decode_tps, 1),
        "unit": "tokens/s/chip",
        "ttft_p50_ms": round(ttft_p50, 1),
        "batch_sequences": n_seqs,
        "prompt_len": prompt_len,
        "kv_cache": "int8" if kv_int8 else "bf16",
        # vs_baseline is a fraction of the TPU HBM roofline; on the CPU
        # fallback it is meaningless (a naive reader would see a 95%
        # "regression" — VERDICT r4), so it is null unless measured on-chip
        "vs_baseline": round(decode_tps / roofline_tps, 4) if on_tpu else None,
    }
    if prefix_line is not None:
        out["prefix_cache"] = prefix_line
    if engine.goodput_ledger is not None:
        # freeze the wall clock: the ledger's report covers the serving
        # phases, not the unrelated bench minutes that follow
        engine.goodput_ledger.stop()
    _free_engine(engine, "state_manager", "params")
    return out


def trace_demo(seq=128, micro=2):
    """Drive the eager 3-call engine API and one eager collective under the
    live tracer: the fwd/bwd/step phase spans only exist as separate host
    calls on this path (the fused train_batch is ONE compiled program and is
    traced as its own span), and the eager all_reduce exercises @timed_op's
    wall-timed regime with real payload bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu import dist
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.parallel import groups

    groups.reset()
    cfg = TransformerConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                            intermediate_size=256, max_seq_len=seq, dtype=jnp.float32,
                            attention_impl="reference")
    model = TransformerLM(cfg)
    n_chips = len(jax.devices())
    config = {
        "train_batch_size": micro * n_chips,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 10**9,
        "tpu": {"mesh": {"data": n_chips}},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, size=(micro * n_chips, seq),
                                       dtype=np.int32)}
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    x = np.ones((256, 1024), np.float32)  # 1 MiB payload
    # the first call compiles the eager executable; timed_op tags that span
    # `compiled` and keeps it out of the comms bandwidth stats automatically
    for _ in range(4):
        dist.all_reduce(x)
    _free_engine(engine, "state")


def run_bench():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # --trace OUT.jsonl: enable the unified observability bus (monitor/trace.py)
    # BEFORE any compile so jax_compile events land in the artifact; the same
    # switch turns on the metrics registry and real comms byte accounting
    trace_path = os.environ.get("DS_TPU_BENCH_TRACE")
    if trace_path:
        from deepspeed_tpu.monitor.trace import configure_tracer
        from deepspeed_tpu.monitor.metrics import configure_metrics
        from deepspeed_tpu.comm import comm as _dist

        try:  # fresh artifact per run
            os.remove(trace_path)
        except OSError:
            pass
        configure_tracer(enabled=True, path=trace_path)
        configure_metrics(enabled=True)
        _dist.configure(enabled=True, prof_all=True)

    # goodput ledger + recompile sentinel (monitor/goodput.py): armed for
    # every bench run — the final JSON's `goodput` block attributes the
    # bench's own wall clock (compile vs compute vs input wait) and proves
    # the steady-state phases recompiled nothing
    from deepspeed_tpu.monitor.goodput import configure_goodput

    configure_goodput(enabled=True)

    # roofline plane (monitor/roofline.py): cost-vs-wall verdict for every
    # post-warmup compiled bucket; the final JSON's `roofline` block is what
    # perf_sentinel trends MFU/MBU over. DS_TPU_BENCH_ROOFLINE=0 skips.
    if os.environ.get("DS_TPU_BENCH_ROOFLINE", "1") != "0":
        from deepspeed_tpu.monitor.roofline import configure_roofline

        configure_roofline(enabled=True)

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        # a missing chip is an error, not a reason to time the CPU: only an
        # explicit JAX_PLATFORMS=cpu selects the disclosed CPU smoke
        sys.exit(f"bench.py: no TPU (JAX found {jax.devices()[0].platform}); "
                 "set JAX_PLATFORMS=cpu for the CPU smoke")
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    serving = bench_serving(on_tpu)
    # gateway plane (PR 6): latency-under-load curves through the HTTP/SSE
    # request plane + the prefix-router vs random-placement A/B. Small-engine
    # config by design (two production replicas do not share one chip), so it
    # rides every bench run; DS_TPU_BENCH_GATEWAY=0 skips, and a failure
    # costs this block only — never the headline serving numbers.
    if os.environ.get("DS_TPU_BENCH_GATEWAY", "1") != "0":
        try:
            from tools.serving_load import gateway_bench

            serving["gateway"] = gateway_bench(on_tpu)
            # request-scoped tracing (PR 8): surface the p99-TTFT attribution
            # and the measured trace-on-vs-off throughput tax as one readable
            # line — the full table rides the serving JSON below
            tr = serving["gateway"].get("tracing", {})
            attr = tr.get("attribution", {})
            if attr.get("stages_p99_ms"):
                stages = " ".join(f"{k.removesuffix('_ms')}={v}ms"
                                  for k, v in attr["stages_p99_ms"].items())
                print(f"# p99 TTFT attribution: ttft_p99={attr.get('ttft_p99_ms')}ms "
                      f"[{stages}] breakdown_ok={attr.get('breakdown_ok_frac')} "
                      f"trace_overhead={tr.get('overhead_pct')}%", flush=True)
        except Exception as e:
            print(f"# WARNING: gateway bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)
    # speculative decoding (PR 9/13): spec-on/off A/B on the shared-prefix
    # workload — acceptance rate + decode tok/s both arms + greedy token
    # parity — plus the K × tree-width sweep grid with per-drafter-mode
    # accept rates. DS_TPU_BENCH_SPEC=0 skips; a failure costs this block
    # only, never the headline serving numbers.
    if os.environ.get("DS_TPU_BENCH_SPEC", "1") != "0":
        try:
            from tools.serving_load import speculative_ab

            sp = speculative_ab(on_tpu)
            serving["speculative"] = {k: sp[k] for k in
                                      ("accept_rate", "decode_tok_s_on", "decode_tok_s_off",
                                       "speedup", "k", "min_match", "tree_width",
                                       "spec_rounds", "drafted_tokens", "token_parity")
                                      if k in sp}
            print(f"# speculative: accept_rate={sp.get('accept_rate')} decode_tok_s "
                  f"on/off={sp.get('decode_tok_s_on')}/{sp.get('decode_tok_s_off')} "
                  f"(k={sp.get('k')}, width={sp.get('tree_width')}, "
                  f"parity={sp.get('token_parity')})", flush=True)
        except Exception as e:
            print(f"# WARNING: speculative bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)
        # the sweep is its own failure domain: the headline A/B above must
        # survive a sweep-only regression (and vice versa)
        try:
            from tools.serving_load import speculative_sweep

            sw = speculative_sweep(on_tpu)
            serving.setdefault("speculative", {})["sweep"] = {
                "grid": sw["grid"], "decode_tok_s_off": sw["decode_tok_s_off"],
                "best_accept_rate_by_mode": sw["best_accept_rate_by_mode"],
                "all_parity": sw["all_parity"]}
            best = max(sw["grid"], key=lambda c: c["decode_tok_s"], default=None)
            if best:
                print(f"# speculative sweep: best cell mode={best['mode']} k={best['k']} "
                      f"width={best['tree_width']} accept={best['accept_rate']} "
                      f"tok/s={best['decode_tok_s']} (parity={sw['all_parity']})", flush=True)
        except Exception as e:
            print(f"# WARNING: speculative sweep phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)
    serving.update(backend_stamp(on_tpu))
    print(json.dumps(serving))

    def train_tps(cfg, micro, gas, seq, steps, warmup, data="batch"):
        """One training-throughput measurement. ``data`` selects the input
        path: "batch" re-feeds one host batch (zero assembly cost — the
        headline metric, unchanged round-over-round); "iter" assembles a
        fresh batch per microbatch on the host, synchronously; "prefetch"
        runs the same assembly through ``engine.prefetching_loader`` (the
        async input pipeline). Returns (tokens/s/chip, model,
        input_wait_ms p50 over the timed steps)."""
        from deepspeed_tpu.parallel import groups
        from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics

        groups.reset()
        configure_metrics(enabled=True)  # train/input_wait_ms rides the registry
        model = TransformerLM(cfg)
        n_chips = len(jax.devices())
        config = {
            "train_batch_size": micro * gas * n_chips,
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.0}},
            "zero_optimization": {"stage": 3 if on_tpu else 0},
            "bf16": {"enabled": bool(on_tpu)},
            "steps_per_print": 10**9,
            "tpu": {"mesh": {"data": n_chips}},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
        rng = np.random.default_rng(0)
        prefetcher = None
        if data == "batch":
            batch = {"input_ids": rng.integers(0, cfg.vocab_size, size=(config["train_batch_size"], seq),
                                               dtype=np.int32)}
            feed = lambda: engine.train_batch(batch)
        else:
            rows = config["train_batch_size"] // gas  # per-microbatch rows (single process)

            def mb_gen():
                # per-sample sequence packing + collate — the standard LM
                # input-pipeline shape (draw short documents, concatenate,
                # truncate, stack), identical for the sync and prefetch arms
                while True:
                    samples = []
                    for _ in range(rows):
                        lens = rng.integers(16, 64, size=-(-seq // 16))
                        toks = rng.integers(0, cfg.vocab_size, size=int(lens.sum()), dtype=np.int32)
                        # document-boundary resets, then truncate to one row
                        samples.append(np.concatenate(np.split(toks, np.cumsum(lens)[:-1]))[:seq])
                    yield {"input_ids": np.stack(samples)}

            it = mb_gen()
            if data == "prefetch":
                it = prefetcher = engine.prefetching_loader(it, depth=2)
            # per-step host sync: the A/B arms model a device-bound training
            # loop (the loop waits on the step each iteration), which is what
            # the prefetch worker overlaps — async dispatch would let the
            # consumer outrun assembly and measure worker throughput instead
            feed = lambda: float(np.asarray(engine.train_batch(data_iter=it)))
        for _ in range(warmup):
            feed()
        float(np.asarray(engine.state["step"]))  # host fetch = real barrier
        get_metrics().reset()  # timed-window stats only (warmup pays the compiles)
        t0 = time.time()
        for _ in range(steps):
            feed()
        float(np.asarray(engine.state["step"]))
        tps = steps * config["train_batch_size"] * seq / (time.time() - t0) / n_chips
        input_wait_p50 = get_metrics().histogram("train/input_wait_ms").percentile(50)
        if prefetcher is not None:
            prefetcher.close()
        _free_engine(engine, "state")
        return tps, model, input_wait_p50

    if on_tpu:
        # 748M-param Llama-arch model: h=2048 x 12 layers, seq 2048 — the
        # largest clean shape that fits v5e HBM (16G) with fp32 Adam states
        # and an f32 grad accumulator.
        cfg = TransformerConfig(vocab_size=32000, hidden_size=2048, num_layers=12,
                                num_heads=16, num_kv_heads=16, intermediate_size=5632,
                                max_seq_len=2048, norm="rmsnorm", positions="rotary",
                                mlp="swiglu", dtype=jnp.bfloat16, attention_impl="flash",
                                remat=True, remat_policy="save_only_these_names(attn_out)")
        micro, gas, seq, steps, warmup = 2, 16, 2048, 6, 2
    else:  # CI / CPU smoke mode
        cfg = TransformerConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                                intermediate_size=256, max_seq_len=256, dtype=jnp.float32,
                                attention_impl="reference")
        micro, gas, seq, steps, warmup = 2, 1, 256, 3, 1

    tok_per_sec_per_chip, model, input_wait_p50 = train_tps(cfg, micro, gas, seq, steps, warmup)
    # low-accumulation point (the optimizer step un-amortized): the update
    # chain must stay near the HBM roofline, not hide behind gas=16
    gas4_tps, _, _ = train_tps(cfg, micro, 4 if on_tpu else 1, seq, 3 * steps if on_tpu else 2, 2)

    # --prefetch: same workload, same per-microbatch host assembly, with and
    # without the async device-prefetching pipeline — the sync arm's input
    # wait should collapse to ~0 under prefetch while throughput holds (the
    # headline `value` above stays the zero-assembly batch= measurement, so
    # round-over-round tracking is not perturbed by this comparison)
    prefetch_line = None
    if os.environ.get("DS_TPU_BENCH_PREFETCH") == "1":
        # the A/B arms run with gradient accumulation (the real training
        # shape — the sync path stalls once per microbatch pull): headline
        # gas on TPU; the CPU smoke raises its gas=1 to 4 so the sync arm's
        # stall is actually representative
        ab_gas = gas if on_tpu else 4
        ab_steps = steps if on_tpu else 12  # p50 over 3 CPU-smoke steps is noise
        sync_tps, _, sync_wait = train_tps(cfg, micro, ab_gas, seq, ab_steps, warmup, data="iter")
        pf_tps, _, pf_wait = train_tps(cfg, micro, ab_gas, seq, ab_steps, warmup, data="prefetch")
        prefetch_line = {
            "gas": ab_gas,
            "input_wait_ms_p50": round(pf_wait, 3),
            "sync_input_wait_ms_p50": round(sync_wait, 3),
            "tokens_per_sec_per_chip": round(pf_tps, 1),
            "sync_tokens_per_sec_per_chip": round(sync_tps, 1),
            "depth": 2,
        }
        if not on_tpu:
            # the "device" compute runs on the same host cores as the worker,
            # so the CPU fallback understates the throughput side of overlap
            prefetch_line["note"] = "CPU fallback: device compute shares host cores"

    # --ckpt: checkpoint-plane A/B — per-save step-loop blocked time, sync
    # full-write vs async (host-snapshot + background writer). The async
    # number should collapse toward the snapshot cost while the durable
    # write overlaps the next training steps (runtime/resilience/).
    ckpt_line = None
    if os.environ.get("DS_TPU_BENCH_CKPT") == "1":
        import shutil
        import tempfile
        from deepspeed_tpu.parallel import groups
        from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics

        n_saves = 4
        ckpt_line = {"n_saves": n_saves}
        for mode in ("sync", "async"):
            groups.reset()
            configure_metrics(enabled=True)
            get_metrics().reset()
            n_chips = len(jax.devices())
            ck_config = {
                "train_batch_size": micro * n_chips,
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.0}},
                "zero_optimization": {"stage": 3 if on_tpu else 0},
                "bf16": {"enabled": bool(on_tpu)},
                "steps_per_print": 10**9,
                "tpu": {"mesh": {"data": n_chips}},
                "checkpoint": {"async_save": mode == "async"},
            }
            ck_engine, _, _, _ = deepspeed_tpu.initialize(model=TransformerLM(cfg),
                                                          config=ck_config)
            ck_rng = np.random.default_rng(0)
            ck_batch = {"input_ids": ck_rng.integers(0, cfg.vocab_size,
                                                     size=(ck_config["train_batch_size"], seq),
                                                     dtype=np.int32)}
            ck_engine.train_batch(ck_batch)  # compile outside the timed window
            ck_dir = tempfile.mkdtemp(prefix=f"ds_bench_ckpt_{mode}_")
            try:
                for i in range(n_saves):
                    ck_engine.save_checkpoint(ck_dir, tag=f"bench_save{i}")
                    # the async writer persists while these steps run — the
                    # overlap the sync arm cannot have
                    ck_engine.train_batch(ck_batch)
                    ck_engine.train_batch(ck_batch)
                ck_engine.flush_checkpoints()
                reg = get_metrics()
                ckpt_line[f"ckpt_blocked_ms_p50_{mode}"] = round(
                    reg.histogram("train/ckpt_blocked_ms").percentile(50), 3)
                ckpt_line[f"write_ms_p50_{mode}"] = round(
                    reg.histogram("checkpoint/write_ms").percentile(50), 3)
            finally:
                shutil.rmtree(ck_dir, ignore_errors=True)
                ck_engine.destroy()
        if ckpt_line.get("ckpt_blocked_ms_p50_sync"):
            ckpt_line["blocked_ratio_async_vs_sync"] = round(
                ckpt_line["ckpt_blocked_ms_p50_async"] / ckpt_line["ckpt_blocked_ms_p50_sync"], 4)

    # --health: live-health-plane micro-bench — a short health-armed run
    # (flight recorder + watchdog + in-process exporter) on a deliberately
    # tiny model: proves the watchdog stays silent on a healthy loop and
    # prices a /metrics scrape. Runs OUTSIDE the headline timed window (the
    # headline arms no health plane at all, per the zero-overhead contract).
    health_line = None
    if os.environ.get("DS_TPU_BENCH_HEALTH", "1") != "0":
        import urllib.request
        from deepspeed_tpu.parallel import groups
        from deepspeed_tpu.monitor.health import get_health
        from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics

        groups.reset()
        configure_metrics(enabled=True)
        get_metrics().reset()
        n_chips = len(jax.devices())
        h_cfg = TransformerConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
                                  intermediate_size=256, max_seq_len=256, dtype=jnp.float32,
                                  attention_impl="reference")
        h_config = {
            "train_batch_size": 2 * n_chips,
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.0}},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 10**9,
            "tpu": {"mesh": {"data": n_chips}},
            "health": {"export_port": 0, "deadline_train_step_s": 300.0,
                       "dump_on_destroy": False},
        }
        h_engine, _, _, _ = deepspeed_tpu.initialize(model=TransformerLM(h_cfg),
                                                     config=h_config)
        h = get_health()
        h_rng = np.random.default_rng(0)
        h_batch = {"input_ids": h_rng.integers(0, h_cfg.vocab_size,
                                               size=(h_config["train_batch_size"], 64),
                                               dtype=np.int32)}
        for _ in range(4):
            h_engine.train_batch(h_batch)
        scrape_ms, body = [], b""
        url = h.server.url + "/metrics"
        for _ in range(20):
            t_s = time.perf_counter()
            body = urllib.request.urlopen(url, timeout=10).read()
            scrape_ms.append((time.perf_counter() - t_s) * 1e3)
        skew_hist = get_metrics().histogram("train/straggler_skew_ms_hist")
        health_line = {
            # a healthy loop must produce ZERO watchdog trips
            "stalls": h.stall_count,
            # cross-rank skew rides the multi-host resilience vote; a
            # single-host run has no samples, disclosed as null
            "straggler_skew_ms_p50": (round(skew_hist.percentile(50), 3)
                                      if skew_hist.count else None),
            "export_scrape_ms_p50": round(sorted(scrape_ms)[len(scrape_ms) // 2], 3),
            "scrape_bytes": len(body),
        }
        if skew_hist.count == 0:
            health_line["note"] = "single-host run: no cross-rank skew samples"
        h_engine.destroy()
        h.shutdown()
        _free_engine(h_engine, "state")

    # --cache: memory & KV-cache observability plane (ISSUE 11) — the
    # cache_pressure workload runs a Zipf corpus ~4x an undersized block
    # pool and reports the measured hit rate against the MRC estimator's 1x
    # prediction (its live accuracy check), block-lifecycle percentiles and
    # fragmentation, plus the process-wide HBM attribution captured while
    # the engine is live. Outside the headline timed window;
    # DS_TPU_BENCH_CACHE=0 skips, failure never costs the headline.
    cache_line = memory_line = None
    if os.environ.get("DS_TPU_BENCH_CACHE", "1") != "0":
        try:
            from tools.serving_load import cache_pressure_bench

            cp = cache_pressure_bench(on_tpu)
            snap = cp["telemetry"]
            cache_line = {
                "mrc": cp["mrc"],
                "mrc_predicted_1x": cp["mrc_predicted_1x"],
                "measured_hit_rate": cp["measured_hit_rate"],
                "mrc_abs_err_1x": cp["mrc_abs_err_1x"],
                "block_age_p50_s": snap["block_age_s"]["p50"],
                "evicted_block_age_p50_s": snap["evicted_block_age_s"]["p50"],
                "reuse_interval_p50_s": snap["reuse_interval_s"]["p50"],
                "fragmentation": snap["fragmentation"],
                "evictions": cp["evictions"],
                "evicted_tokens": cp["evicted_tokens"],
                "cow_bytes": cp["cow_bytes"],
            }
            memory_line = cp["memory"]
            mrc_line = " ".join(f"{k}={v}" for k, v in cp["mrc"].items())
            print(f"# cache: measured_hit={cp['measured_hit_rate']} "
                  f"mrc[{mrc_line}] err_1x={cp['mrc_abs_err_1x']} "
                  f"evicted_age_p50={cache_line['evicted_block_age_p50_s']}s", flush=True)
            sect = memory_line.get("sections", {})
            print("# memory: " + " ".join(f"{k}={v / 2**20:.1f}MiB"
                                          for k, v in sorted(sect.items()))
                  + (f" unattributed={memory_line['unattributed_bytes'] / 2**20:.1f}MiB"
                     if memory_line.get("unattributed_bytes") is not None else ""),
                  flush=True)
        except Exception as e:
            print(f"# WARNING: cache bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    # --cache.host_tier: tiered KV-cache A/B (ISSUE 17) — the Zipf corpus
    # resized to ~10x the HBM pool, run HBM-only vs with the pinned host
    # tier armed. The leaves perf_sentinel trends: hierarchy_hit_rate vs
    # hbm_hit_rate (higher-better), promote_p50/p99_ms and the TTFT split
    # (lower-better). Outside the headline window; DS_TPU_BENCH_HOST_TIER=0
    # skips, failure never costs the headline.
    if cache_line is not None and os.environ.get("DS_TPU_BENCH_HOST_TIER", "1") != "0":
        try:
            from tools.serving_load import host_tier_ab

            ht = host_tier_ab(on_tpu)
            on, off = ht["host_tier"], ht["hbm_only"]
            cache_line["host_tier"] = {
                "hierarchy_hit_rate": on["hierarchy_hit_rate"],
                "hbm_hit_rate": off["hbm_hit_rate"],
                "hit_rate_gain": ht["hit_rate_gain"],
                "token_parity": ht["token_parity"],
                "promote_p50_ms": on.get("promote_p50_ms"),
                "promote_p99_ms": on.get("promote_p99_ms"),
                "ttft_promoted_hit_p50_ms": (on["ttft_promoted_hit_ms"] or {}).get("p50_ms"),
                "ttft_miss_p50_ms": (on["ttft_miss_ms"] or {}).get("p50_ms"),
                "demotions": on["demotions"],
                "promotions": on["promotions"],
            }
            print(f"# host_tier: hierarchy_hit={on['hierarchy_hit_rate']} "
                  f"hbm_hit={off['hbm_hit_rate']} gain={ht['hit_rate_gain']} "
                  f"parity={ht['token_parity']} promote_p99={on.get('promote_p99_ms')}ms",
                  flush=True)
        except Exception as e:
            print(f"# WARNING: host_tier bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    # --disagg: disaggregated prefill/decode A/B (ISSUE 18) — a decode-heavy
    # foreground stream measured under a pure-prefill background storm,
    # co-located mixed fleet vs ("prefill","decode") pools with the
    # host-tier KV handoff. The leaves perf_sentinel trends: foreground
    # TPOT/TTFT percentiles (lower-better), handoff_p50_ms and
    # handoff_fallback_rate (explicitly lower-better in its direction
    # table). Outside the headline window; DS_TPU_BENCH_DISAGG=0 skips,
    # failure never costs the headline.
    disagg_line = None
    if os.environ.get("DS_TPU_BENCH_DISAGG", "1") != "0":
        try:
            from tools.serving_load import disagg_ab

            da = disagg_ab(on_tpu)
            co, dg = da["colocated"], da["disagg"]
            disagg_line = {
                "fg_tpot_p99_colocated_ms": co["fg_tpot"].get("p99_ms"),
                "fg_tpot_p99_disagg_ms": dg["fg_tpot"].get("p99_ms"),
                "fg_ttft_p99_colocated_ms": co["fg_ttft"].get("p99_ms"),
                "fg_ttft_p99_disagg_ms": dg["fg_ttft"].get("p99_ms"),
                "tpot_p99_improved": da["tpot_p99_improved"],
                "token_parity": da["token_parity"],
                "migrated": dg["migrated"],
                "fallbacks": dg["fallbacks"],
                "blocks_moved": dg["blocks_moved"],
                "handoff_p50_ms": dg["handoff_p50_ms"],
                "handoff_fallback_rate": dg["handoff_fallback_rate"],
            }
            print(f"# disagg: fg_tpot_p99 {co['fg_tpot'].get('p99_ms')}ms -> "
                  f"{dg['fg_tpot'].get('p99_ms')}ms parity={da['token_parity']} "
                  f"migrated={dg['migrated']} fallbacks={dg['fallbacks']} "
                  f"handoff_p50={dg['handoff_p50_ms']}ms", flush=True)
        except Exception as e:
            print(f"# WARNING: disagg bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    # --chaos: resilience drills (ISSUE 12) — the seeded training storm
    # (kill/stall/straggle/preempt/collective-delay with warm-remesh
    # restarts) and the serving replica-kill drill, reporting the drill
    # VERDICTS plus recovery-time p50 per arm. Outside the headline timed
    # window (the headline arms no chaos at all — the fire() points are
    # no-ops); DS_TPU_BENCH_CHAOS=0 skips, failure never costs the headline.
    chaos_line = None
    if os.environ.get("DS_TPU_BENCH_CHAOS", "1") != "0":
        try:
            from deepspeed_tpu.parallel import groups as _groups
            from tools.chaos_drill import serving_drill, training_drill

            _groups.reset()
            tr = training_drill(seed=7, steps=6)
            _groups.reset()
            sv = serving_drill(seed=3, n_requests=12, n_replicas=2)
            _groups.reset()
            chaos_line = {
                "training": {
                    "verdicts": {k: tr[k] for k in ("loss_parity", "resumed_tags_valid",
                                                    "stall_dumps_match")},
                    "events": tr["events"],
                    "restarts": tr["restarts"],
                    "warm_resumes": tr["warm_resumes"],
                    "recovery_ms_p50": tr["recovery_ms_p50"],
                },
                "serving": {
                    "verdicts": {k: sv[k] for k in ("zero_unreported", "retry_after_on_503",
                                                    "replica_failure_counted",
                                                    "readyz_flipped", "recovered")},
                    "recovery_ms": sv["recovery_ms"],
                },
            }
            print(f"# chaos: train[parity={tr['loss_parity']} tags_valid="
                  f"{tr['resumed_tags_valid']} dumps={tr['stall_dumps_match']} "
                  f"recover_p50={tr['recovery_ms_p50']}ms] serve[unreported="
                  f"{0 if sv['zero_unreported'] else 'SOME'} "
                  f"recover={sv['recovery_ms']}ms]", flush=True)
        except Exception as e:
            print(f"# WARNING: chaos bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    # --tenants: tenant-scoped metering & fairness (ISSUE 15) — the
    # multi-tenant closed-loop HTTP workload (Zipf tenant shares + one
    # adversarial hot tenant) with the metering plane armed: fairness
    # index (higher-better for the sentinel), per-tenant hit rates and
    # spend, hot-tenant compute share, starvation count. Per-tenant rows
    # are ACCOUNTING fields (perf_sentinel treats the block as neutral
    # except fairness_index). Outside the headline timed window;
    # DS_TPU_BENCH_TENANTS=0 skips, failure never costs the headline.
    tenants_line = None
    if os.environ.get("DS_TPU_BENCH_TENANTS", "1") != "0":
        try:
            from tools.serving_load import multi_tenant_bench

            mt = multi_tenant_bench(on_tpu)
            tenants_line = {k: mt[k] for k in
                            ("fairness_index", "starvations", "tenants_seen",
                             "hot_tenant_compute_share", "rest_ttft_p99_ms",
                             "achieved_rps", "shed_rate", "per_tenant")}
            print(f"# tenants: fairness={mt['fairness_index']} "
                  f"hot_compute_share={mt['hot_tenant_compute_share']} "
                  f"starvations={mt['starvations']} "
                  f"rest_ttft_p99={mt['rest_ttft_p99_ms']}ms "
                  f"(n={mt['tenants_seen']} tenants)", flush=True)
        except Exception as e:
            print(f"# WARNING: tenants bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    # --control: self-driving serving A/B (ISSUE 19) — the same interactive
    # stream under a batch prefill storm, controller-off vs controller-on
    # (admission policy sheds the batch victim class live). The leaves
    # perf_sentinel trends: fg_{off,on}_miss_rate carry the _miss_rate
    # lower-better suffix; actuations is a neutral accounting field.
    # Outside the headline timed window; DS_TPU_BENCH_CONTROL=0 skips,
    # failure never costs the headline.
    control_line = None
    if os.environ.get("DS_TPU_BENCH_CONTROL", "1") != "0":
        try:
            from tools.serving_load import control_ab

            ca = control_ab(on_tpu)
            off, on = ca["control_off"], ca["control_on"]
            control_line = {
                "ttft_target_ms": ca["ttft_target_ms"],
                "fg_off_miss_rate": off["fg_miss_rate"],
                "fg_on_miss_rate": on["fg_miss_rate"],
                "fg_ttft_p99_off_ms": off["fg_ttft"].get("p99_ms"),
                "fg_ttft_p99_on_ms": on["fg_ttft"].get("p99_ms"),
                "slo_miss_improved": ca["slo_miss_improved"],
                "token_parity": ca["token_parity"],
                "actuations": on["actuations"],
                "deferred": on["deferred"],
                "controller_errors": on["errors"],
                "decisions_justified": on["decisions_justified"],
            }
            print(f"# control: fg_miss_rate {off['fg_miss_rate']} -> "
                  f"{on['fg_miss_rate']} (target {ca['ttft_target_ms']}ms) "
                  f"improved={ca['slo_miss_improved']} parity={ca['token_parity']} "
                  f"actuations={on['actuations']}", flush=True)
        except Exception as e:
            print(f"# WARNING: control bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    # --timeline: causal timeline rounds (ISSUE 20) — the same disagg
    # workload captured clean vs under a seeded 80ms handoff stall, each
    # round's assembled timelines written to disk and diffed with
    # tools/trace_explain.py. The leaves perf_sentinel trends are neutral
    # accounting fields (timeline. prefix); the attribution verdict
    # (dominant stage = broker_verify) is the honesty check. Outside the
    # headline window; DS_TPU_BENCH_TIMELINE=0 skips, failure never costs
    # the headline.
    timeline_line = None
    if os.environ.get("DS_TPU_BENCH_TIMELINE", "1") != "0":
        try:
            from tools.serving_load import timeline_rounds

            tr = timeline_rounds(on_tpu)
            base, stalled = tr["rounds"]["base"], tr["rounds"]["stalled"]
            timeline_line = {
                "n_timelines_base": base["n_timelines"],
                "n_timelines_stalled": stalled["n_timelines"],
                "migrated_base": base["migrated"],
                "migrated_stalled": stalled["migrated"],
                "migrated_coverage_ok_frac": base["migrated_coverage_ok_frac"],
                "chaos_stalls": stalled["chaos_stalls"],
                "delta_e2e_ms": tr["explain"]["delta_e2e_ms"],
                "dominant_stage": tr["explain"]["dominant_stage"],
                "dominant_cause": tr["explain"]["dominant_cause"],
                "rounds_dir": tr["out_dir"],
            }
            print(f"# timeline: {base['n_timelines']}/{stalled['n_timelines']} "
                  f"timelines (migrated {base['migrated']}/{stalled['migrated']}, "
                  f"coverage {base['migrated_coverage_ok_frac']}); stall delta "
                  f"{tr['explain']['delta_e2e_ms']}ms -> "
                  f"{tr['explain']['dominant_stage']}/"
                  f"{tr['explain']['dominant_cause']}", flush=True)
        except Exception as e:
            print(f"# WARNING: timeline bench phase failed "
                  f"({type(e).__name__}: {str(e)[:200]})", flush=True)

    if trace_path:
        # eager 3-call path demo: genuine fwd/bwd/step spans plus an eager
        # device collective (comm/all_reduce span with real bytes + bandwidth)
        try:
            trace_demo(seq=128)
        except Exception as e:
            print(f"# WARNING: trace demo failed ({type(e).__name__}: {e}); "
                  "trace keeps the train_batch/serving/compile spans", flush=True)

    n_params = model.num_params()
    # fwd+bwd ≈ 6 FLOPs/param/token + attention term (PaLM MFU convention)
    from deepspeed_tpu.profiling.flops_profiler import training_flops_per_token

    flops_per_token = training_flops_per_token(n_params, num_layers=cfg.num_layers,
                                               hidden_size=cfg.hidden_size, seq_len=seq)
    peak = 197e12 if on_tpu else 1e12  # v5e bf16 peak
    mfu = tok_per_sec_per_chip * flops_per_token / peak
    mfu4 = gas4_tps * flops_per_token / peak
    line = {
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        # MFU ratios are v5e-peak-relative: null on the CPU fallback so the
        # JSON cannot be misread as a perf regression (VERDICT r4)
        "vs_baseline": round(mfu / 0.54, 4) if on_tpu else None,
        "gas4_vs_baseline": round(mfu4 / 0.54, 4) if on_tpu else None,
        # single-chip proxy disclosure (round-2 advisor): the 7B/70B-class
        # BASELINE workloads need a pod; this measures MFU on the largest
        # llama-arch model one v5e chip fits, against the same 54% bar
        "workload": f"{n_params/1e6:.1f}M llama-arch, seq {seq}, ZeRO-3, single v5e chip",
        "serving": {k: serving[k] for k in ("value", "ttft_p50_ms", "vs_baseline")
                    if k in serving} | ({"prefix_cache": serving["prefix_cache"]}
                                       if "prefix_cache" in serving else {})
                                     | ({"gateway": serving["gateway"]}
                                        if "gateway" in serving else {})
                                     | ({"speculative": serving["speculative"]}
                                        if "speculative" in serving else {}),
        # achieved MFU fraction (null on the CPU fallback — the v5e-peak
        # denominator would read as a 99.9% regression, the VERDICT r4 trap)
        "mfu": round(mfu, 4) if on_tpu else None,
        # p50 host time train_batch blocked on data during the timed window
        # (stack+reshape+H2D placement on the batch= path)
        "input_wait_ms_p50": round(input_wait_p50, 3),
        "on_tpu": on_tpu,
        # machine-checkable comparability stamp (BENCH_r04/r05 lesson):
        # cross-round tooling compares `value` ONLY within one backend+chip
        **backend_stamp(on_tpu),
    }
    # DS_TPU_BENCH_BASELINE=<prior BENCH_rXX.json or raw line>: attach the
    # round-over-round ratio — or the refusal — computed by the same rules
    baseline_path = os.environ.get("DS_TPU_BENCH_BASELINE")
    if baseline_path:
        try:
            line["vs_prev"] = compare_to_baseline(line, baseline_path)
        except Exception as e:  # belt-and-braces: the headline always prints
            line["vs_prev"] = {"refused": f"comparison failed: {type(e).__name__}"}
    if prefetch_line is not None:
        line["prefetch"] = prefetch_line
    if ckpt_line is not None:
        line["checkpoint"] = ckpt_line
    if health_line is not None:
        line["health"] = health_line
    if chaos_line is not None:
        line["chaos"] = chaos_line
    if cache_line is not None:
        line["cache"] = cache_line
    if disagg_line is not None:
        line["disagg"] = disagg_line
    if memory_line is not None:
        line["memory"] = memory_line
    if tenants_line is not None:
        line["tenants"] = tenants_line
    if control_line is not None:
        line["control"] = control_line
    if timeline_line is not None:
        line["timeline"] = timeline_line
    # goodput block: every bench second attributed (training ledger spans
    # the whole run; the serving ledger covers the timed serving phases),
    # plus the sentinel's steady-state-recompile verdict
    try:
        from deepspeed_tpu.monitor.goodput import conservation_ok, get_goodput

        rep = get_goodput().report()
        gp_line = {"unexpected_compiles": {
            src: sc["unexpected_compiles"] for src, sc in rep["sentinel"].items()}}
        for scope, led_rep in [("train", rep["train"])] + sorted(rep["serving"].items()):
            if led_rep is None:
                continue
            gp_line[scope] = {
                "wall_s": led_rep["wall_s"],
                "fractions": led_rep["fractions"],
                "unattributed_s": led_rep["unattributed_s"],
                "conserved": conservation_ok(led_rep),
            }
        line["goodput"] = gp_line
        tr_fr = gp_line.get("train", {}).get("fractions", {})
        top = sorted(((v, k) for k, v in tr_fr.items() if v > 0), reverse=True)[:4]
        print("# goodput: train[" + " ".join(f"{k}={v:.0%}" for v, k in top)
              + "] unexpected_compiles=" + " ".join(
                  f"{s}:{n}" for s, n in gp_line["unexpected_compiles"].items()),
              flush=True)
    except Exception as e:  # the headline line never forfeits to telemetry
        print(f"# WARNING: goodput block failed ({type(e).__name__}: {e})", flush=True)
    # roofline block: the cost-vs-measured verdict for every post-warmup
    # compiled bucket (train step, serving put/decode/verify buckets, tuned
    # Pallas entrypoints) + the top gap-to-roof offenders — the buckets the
    # online re-tuner should attack (ROADMAP 5c). On CPU the peaks are null
    # and every verdict reads `unknown` (disclosed, never guessed).
    if os.environ.get("DS_TPU_BENCH_ROOFLINE", "1") != "0":
        try:
            from deepspeed_tpu.monitor.roofline import get_roofline

            rrep = get_roofline().report()
            gaps = sorted(((r["gap_to_roof"], b) for b, r in rrep["buckets"].items()
                           if r["gap_to_roof"] is not None), reverse=True)[:5]
            line["roofline"] = {
                "peak_flops": rrep["peak_flops"], "peak_hbm_bw": rrep["peak_hbm_bw"],
                "buckets": rrep["buckets"],
                "top_gap": [{"bucket": b, "gap_to_roof": g,
                             "verdict": rrep["buckets"][b]["verdict"]} for g, b in gaps],
            }
            counts = {}
            for r in rrep["buckets"].values():
                counts[r["verdict"]] = counts.get(r["verdict"], 0) + 1
            print("# roofline: " + " ".join(f"{v}={n}" for v, n in sorted(counts.items()))
                  + (f" worst={gaps[0][1]}@{gaps[0][0]}x" if gaps else ""), flush=True)
        except Exception as e:
            print(f"# WARNING: roofline block failed ({type(e).__name__}: {e})", flush=True)
    if trace_path:
        from deepspeed_tpu.comm.comm import comms_logger
        from deepspeed_tpu.monitor.trace import get_tracer

        if comms_logger.comms_dict:
            line["comms"] = comms_logger.summary()
        line["trace"] = trace_path
        get_tracer().close()
    print(json.dumps(line))


if __name__ == "__main__":
    # --trace OUT.jsonl: Chrome-trace/Perfetto JSONL artifact (README
    # "Observability")
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        if i + 1 >= len(sys.argv):
            print("usage: bench.py [--trace OUT.jsonl] [--prefetch]", file=sys.stderr)
            sys.exit(2)
        os.environ["DS_TPU_BENCH_TRACE"] = os.path.abspath(sys.argv[i + 1])
    # --prefetch: add the async-input-pipeline A/B (sync vs prefetched input
    # wait + throughput) to the final JSON
    if "--prefetch" in sys.argv:
        os.environ["DS_TPU_BENCH_PREFETCH"] = "1"
    # --ckpt: add the checkpoint-plane A/B (per-save blocked ms, sync full
    # write vs async host-snapshot + background writer) to the final JSON
    if "--ckpt" in sys.argv:
        os.environ["DS_TPU_BENCH_CKPT"] = "1"
    # --history [DIR] [--out V.json] [--threshold R] [--strict]: don't run a
    # bench — read the BENCH_r*.json round trajectory on disk through
    # tools/perf_sentinel.py and print its regression verdicts
    if "--history" in sys.argv:
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
        from perf_sentinel import main as _sentinel_main

        sys.exit(_sentinel_main(sys.argv[sys.argv.index("--history") + 1:]))
    run_bench()
