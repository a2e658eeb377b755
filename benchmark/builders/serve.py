"""The serving system under test: one ``InferenceEngineV2`` replica behind
``ServingGateway``, requests entering through ``gateway.submit`` (the normal
path without the HTTP socket). Weights are made on the device from the seed
in one jitted call, in the type they are served in. The benchmark logs every
engine step from outside, by wrapping ``engine.put`` and ``engine.decode`` in
its own spans."""

import math
import time
from types import SimpleNamespace


def make_params(model, seed_word, dtype):
    """The model's parameter tree drawn on the device in ``dtype``: the
    shapes are the program's, the draws this function's own (matrices
    N(0, 1/fan_in), output projections further by 1/sqrt(2L), embeddings
    N(0, 0.02^2), norm scales 1, biases 0), so that no float32 copy of a
    14 GB model is ever alive."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda k: model.init(k, None), jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    n_layers = model.config.num_layers

    def draw(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if leaf.ndim >= 2 and name == "embedding":
                x = jax.random.normal(k, leaf.shape, dtype) * 0.02
            elif leaf.ndim >= 2:
                scale = 1.0 / math.sqrt(leaf.shape[-2])
                if name in ("wo", "w_down", "moe_wo"):
                    scale /= math.sqrt(2 * n_layers)
                x = jax.random.normal(k, leaf.shape, dtype) * scale
            elif "scale" in name:
                x = jnp.ones(leaf.shape, dtype)
            else:
                x = jnp.zeros(leaf.shape, dtype)
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(jax.random.fold_in(jax.random.PRNGKey(0), seed_word))


def instrument(engine, log: list):
    """Log every ``put`` and ``decode`` of ``engine`` from outside: rows, sizes
    and the host clock before the call and after its result was fetched (the
    scheduler calls both blocking, so the second stamp is a fence)."""
    import numpy as np

    from benchmark.lib.common import span

    put, decode = engine.put, engine.decode

    def put_logged(batch_uids, batch_tokens, *args, **kwargs):
        uids, tokens = list(batch_uids), list(batch_tokens)
        sizes = [int(np.size(t)) for t in tokens]
        t0 = time.perf_counter()
        with span("put"):
            out = put(uids, tokens, *args, **kwargs)
        log.append({"kind": "put", "uids": uids, "sizes": sizes, "t0": t0, "t1": time.perf_counter()})
        return out

    def decode_logged(batch_uids, first_tokens, n_steps, *args, **kwargs):
        uids = list(batch_uids)
        t0 = time.perf_counter()
        with span("decode"):
            out = decode(uids, first_tokens, n_steps, *args, **kwargs)
        log.append({"kind": "decode", "uids": uids, "sizes": [int(n_steps)] * len(uids),
                    "t0": t0, "t1": time.perf_counter()})
        return out

    engine.put, engine.decode = put_logged, decode_logged


def system_logits(engine, check_ids, n_prompt: int, uid: int = 2**30):
    """The engine's float32 logits at the last position of a prefill of
    ``check_ids[:n_prompt]`` and at each further position of ``check_ids``,
    decoded one token at a time through the cache."""
    import numpy as np

    got = [np.asarray(engine.put([uid], [check_ids[:n_prompt]], sample=None), np.float32)[0]]
    for j in range(n_prompt, len(check_ids)):
        got.append(np.asarray(engine.put([uid], [check_ids[j:j + 1]], sample=None), np.float32)[0])
    engine.flush(uid)
    return got


def _buckets_up_to(buckets, n: int):
    """The static buckets that batches of at most ``n`` can round up to."""
    out = []
    for b in buckets:
        out.append(b)
        if b >= n:
            break
    return out


def build(cell: dict, seed: int, devices, rehearsal: bool, phases):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.serving import GatewayConfig, ServingGateway

    from benchmark.lib import common, loader
    from benchmark.lib.model import model_config, seed_word

    mark = phases.mark
    cf, tf = cell["config_file"], cell["traffic_file"]
    dtype = jnp.float32 if rehearsal else jnp.bfloat16
    cfg = model_config(cf, dtype)
    model = TransformerLM(cfg)
    word = seed_word(seed)
    with common.span("weights"):
        params = jax.block_until_ready(make_params(model, word, dtype))

    mark("weights")
    # correct, first half: the reference's logits, before the engine takes the
    # rest of the memory for its KV pool. A seeded sequence: a prompt, then a
    # few positions that the system will decode through its cache.
    ck = cf["check"]
    n_prompt, n_decode = int(ck["prompt_tokens"]), int(ck["decode_tokens"])
    rng = np.random.default_rng([int(seed), 7])
    check_ids = rng.integers(0, cfg.vocab_size, size=n_prompt + n_decode, dtype=np.int32)
    positions = list(range(n_prompt - 1, n_prompt + n_decode))
    reference = loader.load_reference(cell)
    with common.span("reference"):
        ref = np.asarray(reference.forward_logits(reference.hyper_from_published(cf), params,
                                                  jnp.asarray(check_ids[None, :]), positions))[0]

    mark("reference")
    ec, gwc = cf["engine"], tf.get("gateway", {})
    sm = DSStateManagerConfig(max_tracked_sequences=ec["max_tracked_sequences"],
                              max_ragged_batch_size=ec["max_ragged_batch_size"],
                              max_ragged_sequence_count=ec["max_ragged_sequence_count"],
                              max_context=ec["max_context"])
    icfg = RaggedInferenceEngineConfig(kv_block_size=ec["kv_block_size"], num_kv_blocks=ec["num_kv_blocks"],
                                       kv_memory_fraction=ec.get("kv_memory_fraction", 0.8),
                                       kv_dtype=dtype, state_manager=sm)
    if rehearsal:  # the same kernel program through the Pallas interpreter
        icfg.modules.attention = {"name": "paged_pallas_attention",
                                  "implementation_config": {"interpret": True}}
    engine = InferenceEngineV2(model, icfg, params=params)
    mark("engine")

    # warm exactly the programs this traffic can reach: sequence buckets up to
    # the replica's in-flight limit, token buckets up to its token budget,
    # decode horizons 2^k below the longest answer
    inflight = int(gwc.get("max_inflight_per_replica") or ec["max_ragged_sequence_count"])
    budget = int(gwc.get("token_budget") or ec["max_ragged_batch_size"])
    seq_buckets = _buckets_up_to(engine.batch.seq_buckets, inflight)
    token_buckets = _buckets_up_to(engine.batch.token_buckets, budget)
    longest = int(tf["output_tokens"].get("hi", tf["output_tokens"].get("value", 1)))
    horizons = [h for h in (1, 2, 4, 8, 16, 32) if h < longest]
    with common.span("warmup"):
        warmed = engine.warmup(seq_buckets, horizons, token_buckets=token_buckets)
        mark("warmup_programs")
        # the engine cuts each step's result to its live rows ON the device
        # (``out[:n_seqs]``), an eager slice that XLA compiles once per
        # (bucket, n): run each once here, so that none compiles in the window
        zeros = jax.jit(lambda shape: jnp.zeros(shape, jnp.int32), static_argnums=0)
        for bucket in seq_buckets:
            for shape in [(bucket, )] + [(bucket, h) for h in horizons]:
                padded = zeros(shape)
                for n in range(1, min(bucket, inflight) + 1):
                    padded[:n]
        n_slices = sum(min(b, inflight) for b in seq_buckets) * (1 + len(horizons))

    mark("warmup_slices")
    # correct, second half: the system's logits for the same sequence, the
    # prompt through one prefill and each further position through the cache
    got = system_logits(engine, check_ids, n_prompt)
    rel_l2 = [float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(got, ref)]
    check = {"positions": positions, "rel_l2": rel_l2, "rel_l2_tol": ck["rel_l2_tol"],
             "finite": bool(all(np.isfinite(g).all() for g in got)),
             "argmax_equal": [int(g.argmax()) == int(r.argmax()) for g, r in zip(got, ref)]}
    check["ok"] = bool(check["finite"] and max(rel_l2) <= ck["rel_l2_tol"])

    mark("system_logits")
    steps: list = []
    instrument(engine, steps)
    gateway = ServingGateway([engine], GatewayConfig(
        enabled=True, port=0, token_budget=int(gwc.get("token_budget", 0)),
        max_inflight_per_replica=int(gwc.get("max_inflight_per_replica", 0)))).start()
    return SimpleNamespace(engine=engine, gateway=gateway, cfg=cfg, steps=steps, check=check,
                           programs_warmed=len(warmed), slices_warmed=n_slices, kv_blocks=engine.num_kv_blocks,
                           kv_itemsize=jnp.dtype(dtype).itemsize)
