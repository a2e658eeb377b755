"""What PR 31 added to the benchmark for Trinity-Large-Preview served as one
chip's share of an expert-parallel group: the configuration file against the
published ``config.json`` key by key, its cut and the bytes it comes to, the
check's positions, the plain reference's functions, the builder's draws and
decision, the chip's share of the routed slots read from a hand-made trace,
and where the manifest's new entries stand."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import loader, xplane, xplane_write
from benchmark.lib.model import model_config, seed_word

MS = 1_000_000  # ns
CELL = "trinity-large-preview.decode-heavy-64"
PERIOD = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
# the language model's settings as https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json has them
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 3072,
    "intermediate_size": 12288, "layer_types": PERIOD * 15, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe", "moe_intermediate_size": 3072, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60, "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32}


def _config(name="trinity-large-preview"):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", name + ".json"))


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_holds_each_published_key(key):
    """Every key of the published config under the same name, unchanged
    unless ``reduced`` lists it; no width is among those."""
    cf = _config()
    if key in CUT:
        assert cf[key] == CUT[key] and key in cf["reduced"]
        assert cf[key + "_published"] == PUBLISHED[key], "the published count stands beside the held one"
    else:
        assert cf[key] == PUBLISHED[key] and key not in cf["reduced"]


def test_the_cut_and_the_deployment_are_stated():
    cf = _config()
    # not vocab_size: test_bench_loader.py refuses a reduced key ending in _size, so the vocabulary is whole here
    assert cf["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts"]
    assert cf["source"] == "https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json"
    assert (cf["first_expert"], cf["chips_sharing_a_layer"]) == (0, 8)
    assert cf["num_experts"] * 8 == cf["num_experts_published"] and cf["vocab_size"] == PUBLISHED["vocab_size"]
    # the floors: a whole period after the dense layers, 8 experts or more (the vocabulary is whole)
    kinds = cf["layer_types"][:cf["num_hidden_layers"]]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert sorted(kinds[cf["num_dense_layers"]:]) == sorted(PERIOD) and len(cf["layer_types"]) == 60
    for words in ("eight", "32 of the 256", "the embedding and the head are whole", "pipeline stages", "without its exchange"):
        assert words in cf["deployment"], words
    for assumed in ("embedding_factor", "attention_gate", "qk_norm", "no_rope_in_full_layers", "selection_bias",
                    "post_norms", "share_held", "vocabulary", "depth"):
        assert assumed in cf["assumed"], assumed
    for dagger in ("embedding_factor", "attention_gate", "qk_norm", "no_rope_in_full_layers", "selection_bias", "post_norms"):
        assert "the afmoe model's published modelling code; the catalog's `described_as`" in cf["assumed"][dagger]
    assert (cf["builder"], cf["reference"], cf["family"], cf["family_size"]) == \
        ("serve_share", "trinity_reference", "trinity_config", "large-preview")
    ec = cf["engine"]
    assert (ec["kv_block_size"], ec["max_tracked_sequences"], ec["max_ragged_sequence_count"]) == (128, 64, 64)
    mellum = _config("mellum2-12b-a2.5b")["engine"]
    assert {k: v for k, v in ec.items()} == mellum, "the rest as mellum2-12b-a2.5b's"


def test_the_bytes_of_the_cut_recomputed_from_the_file():
    """ISSUE 31's table: attention 62.9M, an expert 28.31M, an expert layer
    here 998.0M (whole 7.34B), the dense layer 176.2M; embedding and head
    whole, 1,230.0M where the issue's eighth was 153.7M, so together 5.40B
    parameters, 10.80 GB in bf16, where the issue counted 4.32B and 8.65 GB;
    and the program's own parameter tree for this file has exactly that many."""
    cf = _config()
    h, d, f, fe = cf["hidden_size"], cf["head_dim"], cf["intermediate_size"], cf["moe_intermediate_size"]
    nq, nkv = cf["num_attention_heads"], cf["num_key_value_heads"]
    attention = h * nq * d * 3 + 2 * h * nkv * d  # q, o, gate; k, v
    expert = 3 * h * fe
    router = h * cf["num_experts_published"]
    expert_layer = cf["num_experts"] * expert + cf["num_shared_experts"] * expert + router + attention
    whole_layer = cf["num_experts_published"] * expert + expert + router + attention
    dense_layer = attention + 3 * h * f
    vocabulary = 2 * cf["vocab_size"] * h
    n_expert_layers = cf["num_hidden_layers"] - cf["num_dense_layers"]
    total = cf["num_dense_layers"] * dense_layer + n_expert_layers * expert_layer + vocabulary
    assert attention == pytest.approx(62.9e6, rel=1e-3) and expert == pytest.approx(28.31e6, rel=1e-3)
    assert expert_layer == pytest.approx(998.0e6, rel=1e-3) and whole_layer * 2 == pytest.approx(14.7e9, rel=5e-3)
    assert dense_layer == pytest.approx(176.2e6, rel=1e-3) and vocabulary == pytest.approx(1230.0e6, rel=1e-3)
    assert vocabulary // 8 == pytest.approx(153.7e6, rel=1e-3), "the eighth ISSUE 31 counted"
    assert total == pytest.approx(5.40e9, rel=2e-3) and 2 * total == pytest.approx(10.80e9, rel=2e-3)
    assert 2 * (total - vocabulary + vocabulary // 8) == pytest.approx(8.65e9, rel=2e-3), "ISSUE 31's total, with the eighth"
    assert 2 * total > 0.25 * 16.9e9, "weights alone clear the floor of a quarter of the chip"
    from deepspeed_tpu.models import TransformerLM

    cfg = model_config(cf, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, None), jax.random.PRNGKey(0))
    norms = cf["num_hidden_layers"] * (4 * h + 2 * d) + h + n_expert_layers * cf["num_experts_published"]  # gains, bias
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(shapes)) == total + norms
    assert shapes["blocks"]["moe_wi"].shape == (4, 32, 3072, 3072), "nothing for an absent expert or a dense layer"
    assert (cfg.experts_held, cfg.moe_num_experts, cfg.moe_first_expert, cfg.vocab_size) == (32, 256, 0, 200192)
    assert cfg.layer_types == tuple(cf["layer_types"][:5]) and cfg.moe_num_dense_layers == 1
    assert 2 * nkv * d * 2 == 4096, "KV bytes a token a layer"


@pytest.mark.parametrize("name,least", [("trinity-large-preview", 128), ("tiny-trinity", 4)])
def test_the_checks_positions_lie_on_both_sides_of_the_window(name, least):
    routed = loader.load_module("builders", "serve_routed")
    cf = _config(name)
    ck, window = cf["check"], routed.window_of(cf)
    first, last = ck["prompt_tokens"] - 1, ck["prompt_tokens"] + ck["decode_tokens"] - 1
    assert window == cf["sliding_window"] and window - first >= least and last + 1 - window >= least
    assert 0 < ck["quantile"] < ck["upper_quantile"] < 1 and ck["quantile_tol"] <= ck["upper_tol"] < ck["rel_l2_tol"]
    assert last < cf["engine"]["max_context"]


# --- the plain reference --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def reference():
    return loader.load_module("lib", "trinity_reference")


def test_the_reference_imports_nothing_of_the_program(reference):
    source = open(os.path.join(loader.ROOT, "benchmark", "lib", "trinity_reference.py")).read()
    assert "deepspeed_tpu" not in source.replace("``deepspeed_tpu``", "")
    assert "import" not in source.split('"""', 2)[1], "the docstring"
    assert all(hasattr(reference, name) for name in ("hyper_from_published", "forward_logits"))


def test_hyper_from_published_reads_the_share_and_the_switches(reference):
    hp = reference.hyper_from_published(_config())
    assert (hp["n_q"], hp["n_kv"], hp["d"], hp["window"], hp["top_k"]) == (48, 8, 128, 4096, 4)
    assert (hp["n_experts"], hp["n_held"], hp["first_expert"], hp["n_dense"]) == (256, 32, 0, 1)
    assert hp["embed_scale"] == pytest.approx(math.sqrt(3072)) and hp["route_scale"] == 2.448
    assert (hp["score_func"], hp["route_norm"], hp["rope_theta"], hp["eps"]) == ("sigmoid", True, 10000.0, 1e-5)
    assert (hp["gate"], hp["qk_norm"], hp["selection_bias"], hp["post_norms"], hp["rope_in_full_layers"]) == \
        (True, True, True, True, False)
    whole = reference.hyper_from_published({**PUBLISHED})  # the published file itself: every expert held
    assert (whole["n_experts"], whole["n_held"], whole["first_expert"]) == (256, 256, 0)


@pytest.mark.parametrize("case", ["bias_chooses", "no_bias", "bias_switched_off", "unnormalised"])
def test_the_references_router_by_hand(reference, case):
    """Logits (2, 1, 0, -1) through an identity router, top 2: the scores are
    their sigmoids; a bias of 0.5 on expert 3 puts it in place of expert 1 and
    the weights stay the scores', over their sum, times 2.448."""
    hp = {"score_func": "sigmoid", "top_k": 2, "route_norm": case != "unnormalised", "route_scale": 2.448,
          "selection_bias": case != "bias_switched_off"}
    h, s = jnp.asarray([[2.0, 1.0, 0.0, -1.0]]), 1 / (1 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    bias = None if case == "no_bias" else jnp.asarray([0.0, 0.0, 0.0, 0.5])
    w = np.asarray(reference.router_weights(h, jnp.eye(4), bias, hp))[0]
    chosen = [0, 3] if case in ("bias_chooses", "unnormalised") else [0, 1]
    total = s[chosen].sum() if hp["route_norm"] else 1.0
    want = np.zeros(4)
    want[chosen] = s[chosen] / total * 2.448
    np.testing.assert_allclose(w, want, rtol=1e-6)


def test_the_references_attention_in_blocks_is_attention_in_one_piece(reference, monkeypatch):
    """A block of queries at a time against all keys gives the numbers of one
    piece, window and full, at a length that is no multiple of the block."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (45, n, 16), jnp.float32) for i, n in ((0, 6), (1, 2), (2, 2)))
    for window in (None, 16):
        whole = reference._attention(q, k, v, 2, window)
        monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
        np.testing.assert_allclose(np.asarray(reference._attention(q, k, v, 2, window)), np.asarray(whole), rtol=1e-5, atol=1e-6)
        monkeypatch.undo()
        i, j = np.arange(45)[:, None], np.arange(45)[None, :]
        mask = (j <= i) if window is None else (j <= i) & (i - j < window)
        scores = np.einsum("sngd,tnd->ngst", np.asarray(q).reshape(45, 2, 3, 16), np.asarray(k)) / 4.0
        p = np.where(mask, np.exp(scores - scores.max(-1, keepdims=True)), 0.0)
        by_hand = np.einsum("ngst,tnd->sngd", p / p.sum(-1, keepdims=True), np.asarray(v)).reshape(45, 6, 16)
        np.testing.assert_allclose(np.asarray(whole), by_hand, rtol=1e-4, atol=1e-5)


# --- the builder: draws, chunks, decision ---------------------------------------------------------

@pytest.fixture(scope="module")
def twin():
    from deepspeed_tpu.models import TransformerLM

    cell = loader.resolve_cell("tiny-trinity.decode-heavy-64", rehearsal=True)
    cfg = model_config(cell["config_file"], jnp.float32)
    return cell, cfg, TransformerLM(cfg)


def test_the_builder_serves_one_model_with_gains_about_one_and_a_small_balanced_bias(twin):
    cell, cfg, model = twin
    serve, share = loader.load_module("builders", "serve"), loader.load_module("builders", "serve_share")
    draw = share.make_params(serve, 0.01)
    a, b = (draw(model, seed_word(seed), jnp.float32) for seed in (3, 2**31 + 11))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))  # ONE model, whatever the seed
    for name in ("ln1_scale", "ln2_scale", "ln1_post_scale", "ln2_post_scale", "q_norm_scale", "k_norm_scale"):
        gains = np.asarray(a["blocks"][name])
        assert abs(gains.mean() - 1) < 0.05 and 0.05 < gains.std() < 0.2, name
    assert abs(np.asarray(a["final_norm"]["scale"]).mean() - 1) < 0.05
    bias = np.asarray(a["blocks"]["gate_bias"])
    assert bias.shape == (4, 16) and bias.dtype == np.float32
    runs = np.sort(bias.reshape(4, 4, 4), axis=-1)  # [layer, chip's run, held]: the same multiset on every run
    np.testing.assert_allclose(runs, np.broadcast_to(runs[0, 0], (4, 4, 4)), rtol=1e-6)
    assert abs(bias.sum()) < 1e-6 and 0.005 < bias.std() < 0.015 and len(np.unique(bias[0, :4])) == 4
    # every other leaf is serve.make_params's own draw for the builder's constant word
    plain = serve.make_params(model, np.uint32(share.WEIGHTS_WORD), jnp.float32)
    np.testing.assert_array_equal(np.asarray(plain["blocks"]["wq"]), np.asarray(a["blocks"]["wq"]))
    np.testing.assert_array_equal(np.asarray(plain["blocks"]["moe_wi"]), np.asarray(a["blocks"]["moe_wi"]))


SOUND = [1.0e-2 + 1e-4 * (i % 7) for i in range(64)]


@pytest.mark.parametrize("name,errors,ok", [
    ("sound", SOUND, True),
    ("a tenth of the positions carry a flipped expert", [6 * e if i % 10 == 0 else e for i, e in enumerate(SOUND)], True),
    ("a wrong chosen set at two fifths of the positions: the lower quartile is sound, the upper is not",
     [6 * e if i % 5 < 2 else e for i, e in enumerate(SOUND)], False),
    ("a lower precision raises every position by a half", [1.5 * e for e in SOUND], False),
])
def test_the_decision_holds_an_upper_quantile_too(name, errors, ok):
    share, routed = loader.load_module("builders", "serve_share"), loader.load_module("builders", "serve_routed")
    check = {"positions": list(range(40, 104)), "rel_l2": list(errors), "finite": True, "rel_l2_tol": 0.3,
             "argmax_equal": [True] * 64, "ok": max(errors) <= 0.3}
    ck = {"quantile": 0.25, "quantile_tol": 1.3e-2, "upper_quantile": 0.75, "upper_tol": 2.0e-2}
    out = share.decide(routed, check, ck, 64)
    assert out["ok"] is ok, (name, out["rel_l2_low"], out["rel_l2_high"])
    assert out["rel_l2_low"] <= out["rel_l2_high"] <= out["rel_l2_max"]
    assert out["rel_l2_high"] == max(out["rel_l2_high_by_side"].values()) and out["upper_tol"] == 2.0e-2


def _twin_check(twin, seed, switches=None, **engine_kwargs):
    """The check of ``builders/serve_share.py`` on the CPU twin, with the
    engine's precision options and the reference's switches open."""
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    cell, cfg, model = twin
    cf = cell["config_file"]
    serve, share = loader.load_module("builders", "serve"), loader.load_module("builders", cf["builder"])
    routed, reference = loader.load_module("builders", "serve_routed"), loader.load_reference(cell)
    ck, ec = cf["check"], cf["engine"]
    n_prompt, n_decode = ck["prompt_tokens"], ck["decode_tokens"]
    params = share.make_params(serve, ck["bias_std"])(model, seed_word(seed), jnp.float32)
    ids = np.random.default_rng([seed, 7]).integers(0, cfg.vocab_size, size=n_prompt + n_decode, dtype=np.int32)
    positions = list(range(n_prompt - 1, n_prompt + n_decode))
    hp = {**reference.hyper_from_published(cf), **(switches or {})}
    ref = np.asarray(reference.forward_logits(hp, params, jnp.asarray(ids[None, :]), positions))[0]
    sm = DSStateManagerConfig(max_tracked_sequences=2, max_ragged_batch_size=ec["max_ragged_batch_size"],
                              max_ragged_sequence_count=2, max_context=ec["max_context"])
    icfg = RaggedInferenceEngineConfig(kv_block_size=ec["kv_block_size"], num_kv_blocks=16, state_manager=sm,
                                       **{"kv_dtype": jnp.float32, **engine_kwargs})
    got = share.chunked_logits(5)(InferenceEngineV2(model, icfg, params=params), ids, n_prompt)  # 12 = 5 + 5 + 2
    errors = [float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(got, ref)]
    check = {"positions": positions, "rel_l2": errors, "finite": True, "ok": max(errors) <= ck["rel_l2_tol"]}
    return share.decide(routed, check, ck, routed.window_of(cf))


@pytest.mark.parametrize("control", [None, "int8_kv", "window", "rope_in_full_layers", "gate", "selection_bias",
                                     "route_scale", "qk_norm"])
def test_the_twin_is_correct_and_each_control_is_not(twin, control):
    """The chip check's controls on the CPU twin (float32, a prompt in three
    chunks, positions on both sides of the window of 16): sound it is at
    float32 rounding; with the program's int8 KV cache, or with one mechanism
    off on the reference's side, it is not correct."""
    if control is None:
        sound = _twin_check(twin, 3)
        assert sound["ok"] and sound["rel_l2_high"] * 3 <= sound["upper_tol"], sound
        return
    off = {"window": 10**6, "rope_in_full_layers": True, "gate": False, "selection_bias": False, "route_scale": 1.0,
           "qk_norm": False}
    out = _twin_check(twin, 3, kv_dtype="int8") if control == "int8_kv" else _twin_check(twin, 3, {control: off[control]})
    assert not out["ok"], (control, out["rel_l2_low"], out["rel_l2_high"], out["rel_l2_max"])


# --- the step spans' new counts and the manifest ---------------------------------------------------------------

def _ctx(tmp_path, decode_args, put_args):
    planes = {"/device:TPU:0": {"XLA Ops": [("%moe_gmm.1 = bf16[480,3072] custom-call()", 0, 12 * MS)]},
              "/host:CPU": {"driver": [
                  (f"dstpu/serving/decode#rows=64,{decode_args}steps=8,bucket_rows=64#", 0, 20 * MS),
                  (f"dstpu/serving/decode_step#rows=60,{put_args}steps=1,bucket_rows=64#", 20 * MS, 5 * MS),
                  (f"dstpu/serving/prefill#rows=3,{put_args}steps=1,bucket_rows=8#", 25 * MS, 20 * MS)]}}
    d = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))), "peaks": None, "kind": "serve",
            "cell": {"root": str(tmp_path), "name": "cell", "config_file": {}}}


STEP_SPANS = ("serving/decode", "serving/decode_step", "serving/prefill")
# ``moe_local_slot_share.tput`` as ISSUE 31 defines it. The manifest does not list it: a new
# ``per_layer`` entry has to be the last one and ``test_bench_kv_live.py`` holds
# ``decode_kv_live_share.tput`` there (``PERF.md`` section 7, PR 31 (g)). The spans carry what
# it reads, so a ``benchmark`` PR adds it as this file of data and nothing else.
LOCAL_SLOT_SHARE = {"name": "moe_local_slot_share.tput", "layer": "Model / memory", "unit": "%", "better": "higher",
                    "source": "program_counter", "moves": "serve_tokens_per_s", "reader": "span_arg_ratio",
                    "args": {"numerator": [{"span": s, "product": ["moe_slots"]} for s in STEP_SPANS],
                             "denominator": [{"span": s, "product": ["moe_slots_routed"]} for s in STEP_SPANS]}}


def test_the_step_spans_give_held_slots_over_routed_slots_to_the_accepted_reader(tmp_path):
    metric = LOCAL_SLOT_SHARE
    assert os.path.isfile(os.path.join(loader.ROOT, "benchmark", "readers", metric["reader"] + ".py"))
    read = lambda ctx: loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric["args"]})
    ctx = _ctx(tmp_path, "moe_slots=1010,moe_slots_routed=8192,", "moe_slots=250,moe_slots_routed=2048,")
    assert read(ctx) == pytest.approx(100.0 * (1010 + 250 + 250) / (8192 + 2048 + 2048))
    # the parent's program has no moe_slots_routed: nothing to read, nothing raised
    assert read(_ctx(tmp_path / "parent", "moe_slots=8192,", "moe_slots=2048,")) is None
    assert read({"reduced": None, "cell": {"root": str(tmp_path), "name": "none"}}) is None


TPUT_LISTS = ["device_idle_share.tput", "peak_hbm_bytes.tput", "host_gap_sched_share.tput", "host_gap_engine_share.tput",
              "decode_step_p50_ms.tput", "decode_batch_mean.tput", "decode_row_occupancy.tput",
              "decode_rows_mixed_share.tput", "decode_horizon_mean.tput", "paged_decode_time_share.tput",
              "paged_roofline_share_by_layer.tput", "moe_time_share.tput", "moe_roofline_share.tput",
              "moe_row_occupancy.tput", "moe_experts_hit_share.tput"]


def test_the_manifests_new_entries_stand_where_issue_31_put_them():
    manifest = loader.load_manifest()
    assert [c["name"] for c in manifest["configs"]][-1] == "trinity-large-preview"
    assert manifest["configs"][-1]["reduced"] == _config()["reduced"]
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL and len(manifest["workloads"]) == 7
    assert manifest["workloads"][-1] == {"name": CELL, "config": "trinity-large-preview", "traffic": "decode-heavy-64",
                                         "chips": 1, "why": manifest["workloads"][-1]["why"]}
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-1] == "decode_kv_live_share.tput" and LOCAL_SLOT_SHARE["name"] not in names, "no entry put in the middle"
    listed = sorted(m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ()))
    assert listed == sorted(TPUT_LISTS)
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, "appended"
    (judged, ) = [m for m in manifest["end_to_end"] if CELL in m.get("workloads", ())]
    assert judged["name"] == "serve_tokens_per_s" and judged["workloads"][-1] == CELL
    resolved = loader.resolve_cell(CELL)
    assert sorted(m["name"] for m in resolved["end_to_end"]) == ["serve_tokens_per_s", "setup_s"]
    assert sorted(m["name"] for m in resolved["layer_metrics"]) == sorted(TPUT_LISTS + ["compiles_in_window"])


def test_the_traffic_is_decode_heavy_with_twice_the_rows():
    mix = lambda name: loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", name + ".json"))
    old, new = mix("decode-heavy"), mix("decode-heavy-64")
    assert (new["clients"], new["count"], new["gateway"]["max_inflight_per_replica"]) == (64, 64, 64)
    changed = {"clients", "count", "gateway", "why", "start"}
    assert {k: v for k, v in new.items() if k not in changed} == {k: v for k, v in old.items() if k not in changed}
    assert new["gateway"]["token_budget"] == old["gateway"]["token_budget"] == 512 and 0 <= new["start"] < 64
    twin = loader._read_json(os.path.join(loader.ROOT, "benchmark", "rehearsal", "tiny-trinity.decode-heavy-64.json"))
    assert twin == {"config": "tiny-trinity", "traffic": "decode-heavy-64-tiny", "chips": 1}
