"""The Mellum 2 family (``models/mellum.py``) on the serving path at a small
size, on seeded random weights: the engine's prefill and decode through the
paged cache against the benchmark's plain float32 reference
(``benchmark/lib/mellum_reference.py``, which imports nothing of the program),
the per-layer window and rope, YaRN's closed form, the refusal of the
whole-sequence forwards, and the expert layers' span counts against a batch
counted by hand. Tiny shapes: one period of the layer pattern (window,
window, window, full), 8 experts top-2, a window of 16 under sequences of 48."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmark.lib import mellum_reference, program_spans  # noqa: E402
from deepspeed_tpu.models import TransformerLM, mellum_config  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.monitor.metrics import get_metrics  # noqa: E402
from deepspeed_tpu.monitor.trace import get_tracer  # noqa: E402

PUBLISHED_FULL = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                  "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                  "attention_factor": 1.2772588722239782}


@pytest.fixture(autouse=True)
def _fresh_tracer():
    get_tracer().reset()
    yield
    get_tracer().reset()
    get_metrics().disable()
    get_metrics().reset()


def _engine(cfg, params, attention="dense_blocked_attention"):
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=64,
                              max_ragged_sequence_count=4, max_context=128, token_buckets=(64, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=16, num_kv_blocks=32, kv_dtype=jnp.float32,
                                       state_manager=sm)
    icfg.modules.attention = {"name": attention, "implementation_config": {"interpret": True}}
    return InferenceEngineV2(TransformerLM(cfg), icfg, params=params)


def _published(cfg) -> dict:
    """The configuration-file keys the reference reads, from a program config."""
    return {"num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps, "sliding_window": cfg.sliding_window,
            "layer_types": list(cfg.layer_types), "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk_prob, "rope_parameters": cfg.rope_parameters}


def _prefill_then_decode(engine, ids, n_prompt, uid=7):
    got = [np.asarray(engine.put([uid], [ids[:n_prompt]], sample=None), np.float32)[0]]
    for j in range(n_prompt, len(ids)):
        got.append(np.asarray(engine.put([uid], [ids[j:j + 1]], sample=None), np.float32)[0])
    engine.flush(uid)
    return np.stack(got)


@pytest.mark.parametrize("attention,norm_topk_prob", [("dense_blocked_attention", True),
                                                      ("paged_pallas_attention", True),
                                                      ("dense_blocked_attention", False)])
def test_engine_prefill_and_decode_match_the_plain_reference(attention, norm_topk_prob):
    """(a) A 40-token prefill (2.5 windows) and 8 positions decoded through the
    paged cache against the reference's full forward pass. Both sides are
    float32 on the same weights and the routing agrees at every (position,
    layer), so what is left is the order of float32 sums: measured 4e-7 to
    1e-6 relative L2; 2e-5 leaves an order of magnitude and is three orders
    under what one flipped expert, a wrong window or the wrong rope gives.
    ``norm_topk_prob`` false (the top-k probabilities as the softmax over all
    experts left them) is no published model's here, but the option exists on
    both sides and is held to the same reference."""
    cfg = mellum_config("tiny", dtype=jnp.float32, moe_norm_topk_prob=norm_topk_prob)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, size=48, dtype=np.int32)
    got = _prefill_then_decode(_engine(cfg, params, attention), ids, 40)
    hp = mellum_reference.hyper_from_published(_published(cfg))
    ref = np.asarray(mellum_reference.forward_logits(hp, params, jnp.asarray(ids[None]), list(range(39, 48))))[0]
    rel = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    assert rel.max() < 2e-5, rel


@pytest.mark.parametrize("call", ["forward", "forward_with_cache", "pipeline_stage"])
def test_whole_sequence_forwards_refuse_the_family(call):
    """(b) ``models/transformer.py`` scans one block over the layers, with one
    window and one rope table: it refuses a model whose layers differ rather
    than run every layer as a window layer."""
    cfg = mellum_config("tiny", dtype=jnp.float32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="layer_types"):
        if call == "forward":
            tfm.forward(cfg, params, ids)
        elif call == "forward_with_cache":
            tfm.forward_with_cache(cfg, params, ids, tfm.init_kv_cache(cfg, 1, 16))
        else:
            tfm._stage_scan_fn(cfg)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_yarn_inverse_frequencies_match_the_closed_form(side):
    """(c) The published full-attention rope (theta 5e5, factor 16, original
    8,192, beta 32 / 1, head 128). By hand: the dimension that turns 32 times
    in 8,192 positions is 128 ln(8192 / (32 * 2 pi)) / (2 ln 5e5) = 18.08 and
    the one that turns once 34.98, so pairs 0..18 keep theta^(-2i/128), pairs
    35..63 have it divided by 16, and pair i between them mixes the two with
    weight (i - 18) / 17; sin and cos are scaled by 0.1 ln 16 + 1."""
    if side == "program":
        cfg = mellum_config("12b-a2.5b", num_layers=4)
        assert cfg.rope_parameters["full_attention"] == PUBLISHED_FULL
        inv, factor = tfm.rope_inv_freq(cfg, "full_attention")
        plain, one = tfm.rope_inv_freq(cfg, "sliding_attention")
    else:
        inv, factor = mellum_reference.rope_inverse_frequencies(PUBLISHED_FULL, 128)
        plain, one = mellum_reference.rope_inverse_frequencies(
            {"rope_type": "default", "rope_theta": 500000}, 128)
    closed = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(plain, closed, rtol=1e-6)
    assert one == 1.0 and factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-15)
    assert math.floor(128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))) == 18
    assert math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5))) == 35
    ramp = np.clip((np.arange(64) - 18) / 17.0, 0.0, 1.0)
    np.testing.assert_allclose(inv, closed / 16 * ramp + closed * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv[:19], closed[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], closed[35:] / 16, rtol=1e-6)
    assert closed[26] / 16 < inv[26] < closed[26]


@pytest.mark.parametrize("n_tokens,same", [(16, True), (40, False)])
def test_window_and_full_layers_part_past_the_window(n_tokens, same):
    """(d) One layer, once of each kind, on the same weights and (plain) rope:
    up to the window's 16 tokens a window layer sees what a full layer sees and
    the logits are equal; past it they are not."""
    logits = {}
    for kind in ("sliding_attention", "full_attention"):
        cfg = mellum_config("tiny", dtype=jnp.float32, num_layers=1, layer_types=(kind, ),
                            rope_parameters=None, rope_theta=10000.0)
        params = TransformerLM(cfg).init(jax.random.PRNGKey(5))
        ids = np.random.default_rng(1).integers(0, cfg.vocab_size, size=n_tokens, dtype=np.int32)
        logits[kind] = np.asarray(_engine(cfg, params).put([1], [ids], sample=None), np.float32)[0]
    diff = np.abs(logits["sliding_attention"] - logits["full_attention"]).max()
    assert (diff == 0.0) if same else (diff > 1e-3), diff


def test_a_layers_kind_picks_its_window_and_rope():
    cfg = mellum_config("12b-a2.5b", num_layers=12)
    assert cfg.layer_types == ("sliding_attention", ) * 3 + ("full_attention", ) \
        + ("sliding_attention", ) * 3 + ("full_attention", ) + ("sliding_attention", ) * 3 + ("full_attention", )
    assert [cfg.layer_window(l) for l in range(4)] == [1024, 1024, 1024, None]
    assert (cfg.head_dim, cfg.expert_size, cfg.moe_num_experts, cfg.moe_top_k) == (128, 896, 64, 8)
    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k), jax.random.PRNGKey(0))["blocks"]
    assert shapes["moe_wi"].shape == (12, 64, 2304, 896) and shapes["wq"].shape == (12, 2304, 4096)
    assert shapes["wk"].shape == (12, 2304, 512) and shapes["gate_wg"].shape == (12, 2304, 64)
    # a published list longer than a cut depth is read from its start
    assert mellum_config("12b-a2.5b", num_layers=8, layer_types=list(cfg.layer_types)).layer_types \
        == cfg.layer_types[:8]


def _profiled(tmp_path, fn):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path, ) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    return program_spans.read(str(path))


def test_expert_span_counts_match_a_batch_counted_by_hand(tmp_path, monkeypatch):
    """(f) With the router's weights zero every expert's probability is 1/8
    and ``top_k`` takes the first two, so each live token puts one slot on
    expert 0 and one on expert 1 in each of the 4 layers. A put of 10 + 5
    tokens and a 3-step decode of both rows, read back from the profiler's file
    with the benchmark's own reader."""
    from deepspeed_tpu.ops.pallas import paged_attention

    # the table of recorded choices is the process's, keyed by shapes alone: without this, another
    # file's paged engine of these shapes (a worker runs several files) names ITS kernel on this one's spans
    monkeypatch.setattr(paged_attention, "KERNEL_CHOICES", {})
    cfg = mellum_config("tiny", dtype=jnp.float32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(2))
    params["blocks"]["gate_wg"] = jnp.zeros_like(params["blocks"]["gate_wg"])
    engine = _engine(cfg, params)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32) for n in (10, 5)]

    def serve(uids):
        first = engine.put(uids, prompts, sample="greedy")
        engine.decode(uids, [np.asarray([t], np.int32) for t in first], 3)
        for uid in uids:
            engine.flush(uid)

    serve([1, 2])  # compile first: the traced run is warm
    trace = _profiled(tmp_path, lambda: serve([3, 4]))
    (put, ) = program_spans.spans_named(trace, "serving/prefill")
    (dec, ) = program_spans.spans_named(trace, "serving/decode")
    L, E, k = 4, 8, 2
    moe = engine._modules["moe"]
    assert put.args["bucket_tokens"] == 64 and dec.args["bucket_rows"] == 4   # the engine's one token bucket
    want_put = {"moe_slots": 15 * k * L, "moe_rows": moe.padded_rows(64) * L, "experts_hit": 2 * L,
                "experts_total": E * L, "expert_load_max": 15}
    want_dec = {"moe_slots": 2 * 3 * k * L, "moe_rows": moe.padded_rows(4) * L * 3, "experts_hit": 2 * L * 3,
                "experts_total": E * L * 3, "expert_load_max": 2}
    assert {name: put.args[name] for name in want_put} == want_put
    assert {name: dec.args[name] for name in want_dec} == want_dec
    # 16 tokens x 2 = 32 slots over 8 experts run 8-row blocks: at most 32 // 8 + 7 blocks
    assert moe.padded_rows(16) == 8 * ((32 + 8 * 7) // 8) and moe.padded_rows(4) == 8 * 8
    # the kernel argument still names the paged kernel (none here: the gather module served)
    assert not dec.args.get("kernel") and not put.args.get("kernel")


_LOWERED_TEXT_HASH = """
import hashlib, sys
import jax, jax.numpy as jnp
from deepspeed_tpu.inference.v2.model_implementations.flat_model import ragged_forward
from deepspeed_tpu.models import TransformerLM, mellum_config
cfg = mellum_config("tiny", dtype=jnp.float32)
params = jax.eval_shape(lambda k: TransformerLM(cfg).init(k), jax.random.PRNGKey(0))
i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
pool = jax.ShapeDtypeStruct((cfg.num_layers, 8 * 16, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
step = jax.jit(lambda p, t, s, q, v, b, l, k, w: ragged_forward(cfg, 16, p, t, s, q, v, b, l, k, w))
text = step.lower(params, i32(8), i32(8), i32(8), jax.ShapeDtypeStruct((8, ), jnp.bool_), i32(4, 8), i32(4),
                  pool, pool).as_text()
print(hashlib.sha256(text.encode()).hexdigest(), "sliding" if list({"sliding_attention", "full_attention"})[0]
      == "sliding_attention" else "full")
"""


def test_the_step_program_is_the_same_text_in_every_process():
    """The ragged step of a model with two attention kinds lowers to the same
    text whatever the process's string hash seed: a program assembled in a
    set's order gets another compile-cache key in about every second process,
    and a serving replica (72 programs of 8-20 s at the served size) then
    starts cold from a warm cache. The seeds are two under which a set of the
    two kinds' names iterates in the two orders."""
    import subprocess

    root = os.path.join(os.path.dirname(__file__), os.pardir)
    seen = {}
    for seed in ("0", "1"):  # CPython 3.12: "full_attention" comes first in the set under 0, second under 1
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu", PYTHONPATH=root)
        out = subprocess.run([sys.executable, "-c", _LOWERED_TEXT_HASH], env=env, capture_output=True, text=True)
        assert out.returncode == 0, out.stderr[-2000:]
        text_hash, order = out.stdout.split()[-2:]
        seen[order] = text_hash
    assert len(seen) == 2, f"hash seeds 0 and 1 no longer put the two names in both orders: {seen}"
    assert len(set(seen.values())) == 1, seen
