"""The serving system under test for a model with a LATENT cache (latent
attention, served in the absorbed form): what ``builders/serve.py`` builds and
``builders/serve_routed.py`` decides, with three things of its own.

* **The draws.** ``serve.make_params`` with every norm gain (a leaf named
  ``*scale``: the two norms INSIDE the low-rank projections among them) drawn
  about one, ``1 + 0.1 N(0, 1)``, and the router's selection bias (float32,
  trained by no gradient) the mid-quantile points of ``N(0, bias_std^2)`` in a
  random order a layer, as ``serve_share.make_params`` draws them; here from
  ``--seed`` (every expert is held, so no chip's share follows the draw).
* **The check's prompt is one of the cycle's own**, tens of thousands of
  tokens, fed in chunks of the gateway's token budget as the timed prompts are
  (over the tiled kernel, a chunk's own tokens and its history through ONE
  path), then ``decode_tokens`` positions decoded through the cache: the
  first ``ride_positions`` of them as one-token rows RIDING beside the
  ``budget - 1``-token chunks of a second prompt, as decode rows ride in the
  window (the tiled kernel's short tiles), the others alone (the decode
  kernel). ``serve_routed.decide`` then holds the lower quartile of the
  per-position relative L2 and every position. **And what is CACHED is held
  too**: before the sequence is flushed its entries of layer 0 are read back
  out of the pool and held to the reference's ``[rmsnorm(ckv) | rope(kr)]``
  (``latent_tol`` on the median relative L2 a token, ``latent_max_tol`` on
  every token). The logits cannot hold the cache's precision: with 64
  sigmoid-routed experts a flipped expert behind any of the thousands of
  cached tokens a position reads puts every position on one common floor
  (2.03-2.09e-2 over six seeds), which an 8-bit latent lifts by 4-7%; layer
  0's input is the embedding, so its entries are at the rounding of their
  type and an 8-bit latent reads three times that.
* **The controls' runner** (``python3 benchmark/builders/serve_latent.py
  --workload <cell> --seeds a,b``; ``--rehearsal`` with the twin on the CPU):
  the check over seeds, sound and under each control, one JSON line a
  reading. A control changes ONE thing on the reference's side
  (``glm_reference.hyper_from_published``'s switches) or, ``latent8``, rounds
  the cached latent to 8 bits a value behind the program's back (the pool is
  rewritten after every ``put``; no switch in the program). Each must come
  out NOT correct.
"""

import gc
import json
import os
import sys
import time

# what a control changes: a switch of the reference, or (latent8) the pool itself
CONTROLS = {"no_rope_key": {"rope_key": False}, "no_kv_norm": {"kv_norm": False},
            "no_selection_bias": {"selection_bias": False}, "route_scale_1": {"route_scale": 1.0},
            "score_scale_nope": None, "latent8": None}


def make_params(serve, bias_std: float):
    """``serve.make_params`` with this builder's draws laid over it."""
    plain = serve.make_params

    def draw(model, seed_word, dtype):
        import jax
        import jax.numpy as jnp
        from jax.scipy.special import ndtri

        params = plain(model, seed_word, dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(1), seed_word)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        # the large leaves stay as they are: no second copy of the weights is ever alive
        leaves = [(1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, i), leaf.shape, jnp.float32)).astype(dtype)
                  if str(getattr(path[-1], "key", path[-1])).endswith("scale") else leaf
                  for i, (path, leaf) in enumerate(leaves)]
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        layers, experts = params["blocks"]["gate_bias"].shape
        points = bias_std * ndtri((jnp.arange(experts, dtype=jnp.float32) + 0.5) / experts)
        runs = jax.random.split(jax.random.fold_in(key, len(leaves)), layers)
        params["blocks"]["gate_bias"] = jax.vmap(lambda r: jax.random.permutation(r, points))(runs).astype(jnp.float32)
        return params

    return draw


def riding_logits(chunk: int, ride: int, vocab: int):
    """``serve.system_logits``: the prompt in chunks of ``chunk``, the first
    ``ride`` decoded positions beside a second prompt's chunks, the rest
    alone (see the module's docstring)."""

    def system_logits(engine, check_ids, n_prompt: int, uid: int = 2**30):
        import numpy as np

        for c0 in range(0, n_prompt, chunk):
            out = engine.put([uid], [check_ids[c0:min(c0 + chunk, n_prompt)]], sample=None)
        got = [np.asarray(out, np.float32)[0]]
        filler = np.random.default_rng([int(check_ids[0]), 11]).integers(0, vocab, size=ride * (chunk - 1), dtype=np.int32)
        for j in range(n_prompt, len(check_ids)):
            i = j - n_prompt
            if i < ride:  # a one-token row beside a chunk: rows in the order given, ours first
                out = engine.put([uid, uid + 1], [check_ids[j:j + 1], filler[i * (chunk - 1):(i + 1) * (chunk - 1)]],
                                 sample=None)
            else:
                out = engine.put([uid], [check_ids[j:j + 1]], sample=None)
            got.append(np.asarray(out, np.float32)[0])
        # what the sequence cached in layer 0, read back out of the pool before it is flushed
        kv = engine.state_manager.kv_cache
        at = np.arange(len(check_ids))
        slots = np.asarray(engine.state_manager.get_sequence(uid).kv_blocks)[at // kv.block_size] * kv.block_size \
            + at % kv.block_size
        system_logits.entries = np.asarray(kv.k_pool[0, slots, 0], np.float32)
        engine.flush(uid)
        if ride and len(check_ids) > n_prompt:
            engine.flush(uid + 1)
        return got

    return system_logits


def patched_serve(cell):
    """``builders/serve.py`` with this builder's draws and check laid in."""
    from benchmark.lib import loader

    serve = loader.load_module("builders", "serve", cell["root"])
    cf, tf = cell["config_file"], cell["traffic_file"]
    chunk = int(tf.get("gateway", {}).get("token_budget") or cf["engine"]["max_ragged_batch_size"])
    serve.make_params = make_params(serve, float(cf["check"]["bias_std"]))
    serve.system_logits = riding_logits(chunk, int(cf["check"]["ride_positions"]), int(cf["vocab_size"]))
    return serve


def decide(cell, check: dict, entries, params, ids) -> dict:
    """``serve_routed.decide`` on the logits, and the cached entries of layer
    0 (``entries``, as read back from the pool) against the reference's."""
    import numpy as np

    from benchmark.lib import loader

    routed = loader.load_module("builders", "serve_routed", cell["root"])
    reference = loader.load_reference(cell)
    cf = cell["config_file"]
    ck = cf["check"]
    out = routed.decide(check, float(ck["quantile"]), float(ck["quantile_tol"]))
    want = np.asarray(reference.first_layer_cache_entries(reference.hyper_from_published(cf), params, ids))
    rel = np.linalg.norm(entries[:, :want.shape[1]] - want, axis=-1) / np.linalg.norm(want, axis=-1)
    padding = float(np.abs(entries[:, want.shape[1]:]).max()) if entries.shape[1] > want.shape[1] else 0.0
    out.update(latent_rel_l2_median=float(np.median(rel)), latent_rel_l2_max=float(rel.max()), latent_padding_max=padding,
               latent_tol=float(ck["latent_tol"]), latent_max_tol=float(ck["latent_max_tol"]), logits_ok=out["ok"])
    out["ok"] = bool(out["ok"] and np.median(rel) <= ck["latent_tol"] and rel.max() <= ck["latent_max_tol"]
                     and padding == 0.0)
    return out


def check_ids(cell, seed: int, vocab: int):
    """The check's sequence, as ``serve.build`` draws it from the seed."""
    import numpy as np

    ck = cell["config_file"]["check"]
    return np.random.default_rng([int(seed), 7]).integers(0, vocab, size=int(ck["prompt_tokens"]) + int(ck["decode_tokens"]),
                                                          dtype=np.int32)


def build(cell: dict, seed: int, devices, rehearsal: bool, phases):
    serve = patched_serve(cell)
    system = serve.build(cell, seed, devices, rehearsal, phases)
    system.check = decide(cell, system.check, serve.system_logits.entries, system.engine.params,
                          check_ids(cell, seed, system.cfg.vocab_size))
    return system


def round_pool_to_8_bits(engine):
    """Wrap ``engine.put`` so that after every call each cached entry is what an
    int8 cache would hand back: rounded to 255 levels of its own largest
    magnitude (a scale a token a layer), in float32, stored in the pool's type."""
    import jax
    import jax.numpy as jnp

    kv = engine.state_manager.kv_cache
    put = engine.put

    def rounded(pool):
        x = pool.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-8)
        return (jnp.round(x / scale) * scale).astype(pool.dtype)

    rounded = jax.jit(rounded, donate_argnums=0)  # in place: the pool fills what the weights leave

    def put_rounded(*args, **kwargs):
        out = put(*args, **kwargs)
        kv.k_pool = rounded(kv.k_pool)
        return out

    engine.put = put_rounded
    return lambda: setattr(engine, "put", put)


def main(argv=None) -> int:
    """The check over seeds, sound and under each control; one JSON line a reading."""
    import argparse

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated: each draws a model and the check's ids")
    ap.add_argument("--controls", default="latent8", help="run on every seed")
    ap.add_argument("--more-controls", default="", help="run on the first --more-on seeds only")
    ap.add_argument("--more-on", type=int, default=0)
    ap.add_argument("--prompt-tokens", type=int, default=None,
                    help="a shorter prompt than check.prompt_tokens: how the reading grows with the context")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    from benchmark.lib import loader
    from benchmark.lib.model import model_config, seed_word

    enable_compile_cache()
    cell = loader.resolve_cell(args.workload, root, rehearsal=args.rehearsal)
    serve = patched_serve(cell)
    diffusion = loader.load_module("builders", "serve_diffusion", root)  # its ``without_pools``
    reference = loader.load_reference(cell)
    cf = cell["config_file"]
    ck, ec = cf["check"], cf["engine"]
    dtype = jnp.float32 if args.rehearsal else jnp.bfloat16
    model = TransformerLM(model_config(cf, dtype))
    n_prompt, n_decode = int(args.prompt_tokens or ck["prompt_tokens"]), int(ck["decode_tokens"])
    positions = list(range(n_prompt - 1, n_prompt + n_decode))
    sm = DSStateManagerConfig(max_tracked_sequences=ec["max_tracked_sequences"],
                              max_ragged_batch_size=ec["max_ragged_batch_size"],
                              max_ragged_sequence_count=ec["max_ragged_sequence_count"], max_context=ec["max_context"])
    icfg = RaggedInferenceEngineConfig(kv_block_size=ec["kv_block_size"], num_kv_blocks=ec["num_kv_blocks"],
                                       kv_memory_fraction=ec.get("kv_memory_fraction", 0.8), kv_dtype=dtype,
                                       state_manager=sm)
    if args.rehearsal:
        icfg.modules.attention = {"name": "paged_pallas_attention", "implementation_config": {"interpret": True}}
    engine = None
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = jax.block_until_ready(serve.make_params(model, seed_word(seed), dtype))
        if engine is None:
            engine = InferenceEngineV2(model, icfg, params=params)
        engine.params = params
        ids = np.random.default_rng([int(seed), 7]).integers(0, model.config.vocab_size, size=n_prompt + n_decode,
                                                             dtype=np.int32)
        hp = reference.hyper_from_published(cf)
        controls = [c for c in (args.controls + ("," + args.more_controls if n < args.more_on else "")).split(",") if c]
        logits = {}
        for control in [None] + controls:
            t0 = time.perf_counter()
            switches = dict(CONTROLS[control] or {}) if control else {}
            if control == "score_scale_nope":
                switches = {"score_dim": cf["qk_nope_head_dim"]}
            key = json.dumps(switches, sort_keys=True)
            if key not in logits:  # the reference under these switches, the pool off the device meanwhile
                logits[key] = diffusion.without_pools(engine, lambda: np.asarray(reference.forward_logits(
                    {**hp, **switches}, params, jnp.asarray(ids[None, :]), positions))[0])
            restore = round_pool_to_8_bits(engine) if control == "latent8" else (lambda: None)
            try:
                got = serve.system_logits(engine, ids, n_prompt)
            finally:
                restore()
            ref = logits[key]
            rel = [float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(got, ref)]
            check = decide(cell, {"positions": positions, "rel_l2": rel, "rel_l2_tol": ck["rel_l2_tol"],
                                  "finite": bool(all(np.isfinite(g).all() for g in got)),
                                  "argmax_equal": [int(g.argmax()) == int(r.argmax()) for g, r in zip(got, ref)],
                                  "ok": bool(max(rel) <= ck["rel_l2_tol"])}, serve.system_logits.entries, params, ids)
            quantiles = {f"q{int(q * 100):02d}": round(float(np.quantile(rel, q)), 6) for q in (0.1, 0.25, 0.5, 0.75, 0.9)}
            print(json.dumps({"seed": seed, "control": control or "sound", "seconds": round(time.perf_counter() - t0, 1),
                              **{k: check[k] for k in ("ok", "logits_ok", "within_loose", "rel_l2_low", "rel_l2_max",
                                                       "quantile_tol", "rel_l2_tol", "argmax_equal_share",
                                                       "latent_rel_l2_median", "latent_rel_l2_max", "latent_tol",
                                                       "latent_max_tol")}, **quantiles}), flush=True)
        params = engine.params = None  # the next seed's weights do not fit beside these
        logits.clear()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
