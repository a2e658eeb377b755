"""What PR 27 added to the benchmark for a model with routed experts and mixed
attention layers: the count function of the expert matmuls, the two new
readers on a small hand-made trace, the new metrics' files through the readers
that were there, the decision of ``builders/serve_routed.py``, and its control
on the CPU twin (the program's int8 KV cache must come out NOT correct)."""

import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import loader, opcount, opcount_moe, xplane, xplane_write
from benchmark.lib.model import model_config, seed_word

MS = 1_000_000  # ns
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
SPANS = ["serving/decode", "serving/decode_step", "serving/prefill"]
MELLUM = {"hidden_size": 2304, "moe_intermediate_size": 896, "hidden_act": "silu", "num_hidden_layers": 4,
          "sliding_window": 1024, "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}


@pytest.mark.parametrize("slots,hit,gated,want_flops,want_bytes", [
    # one decode step of 32 rows, top-8, 12 layers, every expert of every layer hit: 3 matrices of
    # 2304 x 896 a hit expert in bf16 and each slot's 2304-wide row in and out
    (256 * 12, 64 * 12, True, 3072 * 3 * 2 * 2304 * 896, 768 * 3 * 2304 * 896 * 2 + 3072 * 2 * 2304 * 2),
    (8, 8, True, 8 * 3 * 2 * 2304 * 896, 8 * 3 * 2304 * 896 * 2 + 8 * 2 * 2304 * 2),
    (8, 3, False, 8 * 2 * 2 * 2304 * 896, 3 * 2 * 2304 * 896 * 2 + 8 * 2 * 2304 * 2),
    (0, 0, True, 0, 0),
])
def test_expert_ffn_cost_counts_by_hand(slots, hit, gated, want_flops, want_bytes):
    assert opcount_moe.expert_ffn_cost(slots, hit, 2304, 896, 2, gated) == (want_flops, want_bytes)
    if slots == 256 * 12:  # bytes bound: 9.5 GB at 819 GB/s against 38 GFLOP at 197 TFLOP/s
        seconds, bound = opcount.min_seconds(want_flops, want_bytes, PEAKS)
        assert bound == "bytes" and seconds == pytest.approx(want_bytes / 819e9)


def _ctx(tmp_path, planes, config_file, cell="cell", **more):
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))), "peaks": PEAKS,
            "kind": "serve", "cell": {"root": str(tmp_path), "name": cell, "config_file": config_file}, **more}


def _read(ctx, reader, args):
    return loader.load_module("readers", reader).read({**ctx, "args": args})


def _metric(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json"))


def _moe_planes(counts: bool):
    """30 ms of ``moe_gmm`` in a 50 ms window, under one decode call of 2 steps
    and one put, whose spans carry the expert counts (or, a program from before
    them, do not)."""
    dec = "moe_slots=6144,moe_rows=16896,experts_hit=1500,experts_total=1536,expert_load_max=11," if counts else ""
    put = "moe_slots=49152,moe_rows=145920,experts_hit=768,experts_total=768,expert_load_max=80," if counts else ""
    return {
        "/device:TPU:0": {"XLA Ops": [("%moe_gmm.1 = bf16[704,896] custom-call()", 0, 12 * MS),
                                      ("%fusion.3 = f32[] fusion()", 12 * MS, 8 * MS),
                                      ("%moe_gmm.2 = bf16[704,2304] custom-call()", 20 * MS, 18 * MS),
                                      ("%paged_attn_kv_split = bf16[8] custom-call()", 40 * MS, 10 * MS)]},
        "/host:CPU": {"driver": [
            (f"dstpu/serving/decode#rows=32,{dec}steps=2,bucket_rows=32#", 0, 20 * MS),
            (f"dstpu/serving/prefill#rows=1,{put}steps=1,bucket_rows=1#", 20 * MS, 30 * MS)]},
    }


def _system(kv_itemsize=2, dtype=jnp.bfloat16):
    return SimpleNamespace(kv_itemsize=kv_itemsize, cfg=SimpleNamespace(dtype=dtype))


@pytest.mark.parametrize("kv_itemsize", [2, 1])
def test_moe_roofline_share_is_least_time_from_the_spans_counts_over_traced_kernel_time(tmp_path, kv_itemsize):
    """The experts' bytes follow the weights' type: an int8 KV cache beside
    bf16 weights (``kv_itemsize`` 1) reads the same share."""
    ctx = _ctx(tmp_path, _moe_planes(True), MELLUM, system=_system(kv_itemsize))
    metric = _metric("moe_roofline_share.tput")
    least = sum(opcount.min_seconds(*opcount_moe.expert_ffn_cost(slots, hit, 2304, 896, 2, True), PEAKS)[0]
                for slots, hit in ((6144, 1500), (49152, 768)))
    assert _read(ctx, metric["reader"], metric["args"]) == pytest.approx(100.0 * least / 0.030)
    assert _read(ctx, _metric("moe_time_share.tput")["reader"], _metric("moe_time_share.tput")["args"]) \
        == pytest.approx(100.0 * 30 / 48)
    occupancy = _metric("moe_row_occupancy.tput")
    assert _read(ctx, occupancy["reader"], occupancy["args"]) == pytest.approx(100.0 * 55296 / 162816)
    hit = _metric("moe_experts_hit_share.tput")
    assert _read(ctx, hit["reader"], hit["args"]) == pytest.approx(100.0 * 2268 / 2304)


@pytest.mark.parametrize("config_file,counts", [(MELLUM, False), ({"hidden_size": 4096, "num_hidden_layers": 2}, True)])
def test_moe_metrics_read_nothing_without_counts_or_experts(tmp_path, config_file, counts):
    """The parent's program has no such counts, a dense configuration no expert
    width: the readers return nothing and do not raise."""
    ctx = _ctx(tmp_path, _moe_planes(counts), config_file, system=_system())
    metric = _metric("moe_roofline_share.tput")
    assert _read(ctx, metric["reader"], metric["args"]) is None
    if not counts:
        for name in ("moe_row_occupancy.tput", "moe_experts_hit_share.tput"):
            assert _read(ctx, _metric(name)["reader"], _metric(name)["args"]) is None
    assert _read({**ctx, "reduced": None}, metric["reader"], metric["args"]) is None


def _paged_ctx(tmp_path, config_file):
    """Two logged steps inside the traced window: a 2,000-token prefill of one
    row, then a decode call of 2 steps of it; 10 ms of paged kernels."""
    planes = {"/device:TPU:0": {"XLA Ops": [("%paged_attn_q_tiled = bf16[8] custom-call()", 0, 6 * MS),
                                            ("%paged_attn_kv_split.1 = bf16[8] custom-call()", 10 * MS, 4 * MS)]},
              "/host:CPU": {"driver": [("dstpu/serving/prefill#rows=1#", 0, 8 * MS)]}}
    steps = [{"kind": "put", "uids": [5], "sizes": [2000], "t0": 1.0, "t1": 1.1},
             {"kind": "decode", "uids": [5], "sizes": [2], "t0": 1.2, "t1": 1.3},
             {"kind": "decode", "uids": [5], "sizes": [4], "t0": 1.9, "t1": 2.1}]  # straddles the edge: not counted
    cfg = SimpleNamespace(num_heads=32, num_kv_heads=4, head_dim=128, num_layers=config_file["num_hidden_layers"],
                          sliding_window=config_file.get("sliding_window"))
    return _ctx(tmp_path, planes, config_file, trace_window=(0.9, 2.0),
                system=SimpleNamespace(steps=steps, cfg=cfg, kv_itemsize=2))


def test_paged_roofline_share_by_layer_gives_each_layer_its_own_window(tmp_path):
    by_layer = loader.load_module("readers", "paged_roofline_share_by_layer")
    assert by_layer.layer_windows(MELLUM) == [1024, 1024, 1024, None]
    assert by_layer.layer_windows({"num_hidden_layers": 2, "sliding_window": 4096}) == [4096, 4096]
    assert by_layer.layer_windows({"num_hidden_layers": 2}) == [None, None]
    twelve = dict(MELLUM, num_hidden_layers=12, layer_types=MELLUM["layer_types"] * 7)  # the published list, whole
    assert by_layer.layer_windows(twelve) == [1024, 1024, 1024, None] * 3

    ctx = _paged_ctx(tmp_path, MELLUM)
    metric = _metric("paged_roofline_share_by_layer.tput")
    rows = [[(0, 2000)], [(2000, 1)], [(2001, 1)]]
    least = sum(layers * opcount.min_seconds(*opcount.paged_attention_cost(r, 32, 4, 128, window, 2, 2), PEAKS)[0]
                for r in rows for window, layers in ((1024, 3), (None, 1)))
    got = _read(ctx, metric["reader"], metric["args"])
    assert got == pytest.approx(100.0 * least / 0.010)
    # the reader that was there gives all four layers the window, and reads lower
    assert _read(ctx, "paged_roofline_share", metric["args"]) < got


def test_paged_roofline_share_by_layer_is_the_old_reading_for_layers_of_one_kind(tmp_path):
    mistral = {"num_hidden_layers": 4, "sliding_window": 1024}
    ctx = _paged_ctx(tmp_path, mistral)
    args = _metric("paged_roofline_share_by_layer.tput")["args"]
    assert _read(ctx, "paged_roofline_share_by_layer", args) == pytest.approx(_read(ctx, "paged_roofline_share", args))
    assert _read({**ctx, "reduced": None}, "paged_roofline_share_by_layer", args) is None


# --- the decision of builders/serve_routed.py -------------------------------------------------------

SOUND = [1.0e-2 + 1e-4 * (i % 7) for i in range(64)]  # the rounding error of positions whose routing agreed
FIRST = 40  # the check's first position: the window of 64 then has 24 positions below it and 40 past it


def _decide(errors, finite=True, loose=0.3, tight=1.3e-2, window=64):
    routed = loader.load_module("builders", "serve_routed")
    check = {"positions": list(range(FIRST, FIRST + len(errors))), "rel_l2": list(errors), "finite": finite,
             "rel_l2_tol": loose, "argmax_equal": [True] * len(errors), "ok": bool(finite and max(errors) <= loose)}
    return routed.decide(check, 0.25, tight, window)


def _past(errors, factor, start=64):
    """``errors`` with every position from ``start`` on raised by ``factor``."""
    return [e * factor if FIRST + i >= start else e for i, e in enumerate(errors)]


@pytest.mark.parametrize("name,errors,finite,ok", [
    ("no flip", SOUND, True, True),
    ("half the positions carry a flipped expert", [e if i % 2 else 6 * e for i, e in enumerate(SOUND)], True, True),
    ("a lower precision raises every position by a half", [1.5 * e for e in SOUND], True, False),
    ("a lower precision under flips", [1.5 * e if i % 2 else 6 * e for i, e in enumerate(SOUND)], True, False),
    ("one position of order one: a wrong position or block table", SOUND[:-1] + [0.9], True, False),
    ("not finite", SOUND, False, False),
    # a window the kernels ignore or bound elsewhere: below the loose limit at every position, nothing
    # below the window moves, and most positions (here 24 + 0) or the lower quartile of all of them
    # (within the window) are sound: only the quartile PAST the window sees it
    ("a wrong window raises the positions past it five times", _past(SOUND, 5.0), True, False),
    ("a wrong window under flips", _past([e if i % 2 else 6 * e for i, e in enumerate(SOUND)], 5.0), True, False),
    ("a window a block too narrow also moves the last positions below it", _past(SOUND, 5.0, start=56), True, False),
])
def test_flips_cannot_fool_the_decision_and_lower_precision_cannot_pass(name, errors, finite, ok):
    out = _decide(errors, finite)
    assert out["ok"] is ok, (name, out["rel_l2_low"], out["rel_l2_max"])
    assert out["rel_l2_low"] <= out["rel_l2_max"] and out["rel_l2_low"] == max(out["rel_l2_low_by_side"].values())
    assert out["within_loose"] == (finite and max(errors) <= 0.3)
    assert out["positions_past_window"] == 40 and out["positions_first_last"] == [40, 103]
    assert out["argmax_equal_share"] == 1.0 and "argmax_equal" not in out and len(out["rel_l2"]) == 64


@pytest.mark.parametrize("window,sides", [(None, ["within_window"]), (1024, ["within_window"]), (8, ["past_window"]),
                                          (64, ["within_window", "past_window"])])
def test_the_decision_splits_the_positions_where_the_window_starts_to_leave_keys_out(window, sides):
    """Position ``window`` is the first whose query no longer sees key 0. One
    quartile over all positions passes a wrong window as long as a quarter of
    the positions lie below it (here 24 of 64, in the cell 641 of 1,024)."""
    errors = _past(SOUND, 5.0)
    out = _decide(errors, window=window)
    assert sorted(out["rel_l2_low_by_side"]) == sorted(sides)
    assert out["positions_past_window"] == {None: 0, 1024: 0, 8: 64, 64: 40}[window]
    assert out["ok"] is (window != 64)  # on one side, the 24 sound positions of 64 hold the quartile down


def test_the_cells_check_runs_past_the_window_it_names():
    """The configuration's check positions cross ``sliding_window`` with some
    hundreds of positions on each side, and the twin's cross its own."""
    routed = loader.load_module("builders", "serve_routed")
    for name, least in (("mellum2-12b-a2.5b", 256), ("tiny-mellum", 4)):
        cf = loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", name + ".json"))
        ck, window = cf["check"], routed.window_of(cf)
        first, last = ck["prompt_tokens"] - 1, ck["prompt_tokens"] + ck["decode_tokens"] - 1
        assert window == cf["sliding_window"] and window - first >= least and last + 1 - window >= least, (name, first, last)
    assert routed.window_of({"sliding_window": 4096}) is None  # one kind of layer: serve.py's case


@pytest.fixture(scope="module")
def twin():
    from deepspeed_tpu.models import TransformerLM

    cell = loader.resolve_cell("tiny-mellum.decode-heavy", rehearsal=True)
    cfg = model_config(cell["config_file"], jnp.float32)
    return cell, cfg, TransformerLM(cfg)


def _twin_check(twin, seed, **engine_kwargs):
    """The check of ``builders/serve_routed.py`` on the CPU twin with the
    engine's precision options open."""
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    cell, cfg, model = twin
    serve = loader.load_module("builders", "serve")
    routed = loader.load_module("builders", cell["config_file"]["builder"])
    reference = loader.load_reference(cell)
    ck, ec = cell["config_file"]["check"], cell["config_file"]["engine"]
    n_prompt, n_decode = ck["prompt_tokens"], ck["decode_tokens"]
    params = serve.make_params(model, seed_word(seed), jnp.float32)
    ids = np.random.default_rng([seed, 7]).integers(0, cfg.vocab_size, size=n_prompt + n_decode, dtype=np.int32)
    ref = np.asarray(reference.forward_logits(reference.hyper_from_published(cell["config_file"]), params,
                                              jnp.asarray(ids[None, :]),
                                              list(range(n_prompt - 1, n_prompt + n_decode))))[0]
    sm = DSStateManagerConfig(max_tracked_sequences=2, max_ragged_batch_size=ec["max_ragged_batch_size"],
                              max_ragged_sequence_count=2, max_context=ec["max_context"])
    icfg = RaggedInferenceEngineConfig(kv_block_size=ec["kv_block_size"], num_kv_blocks=16, state_manager=sm,
                                       **{"kv_dtype": jnp.float32, **engine_kwargs})
    got = serve.system_logits(InferenceEngineV2(model, icfg, params=params), ids, n_prompt)
    errors = [float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(got, ref)]
    check = {"positions": list(range(n_prompt - 1, n_prompt + n_decode)), "rel_l2": errors, "finite": True,
             "ok": max(errors) <= ck["rel_l2_tol"]}
    return routed.decide(check, ck["quantile"], ck["quantile_tol"], routed.window_of(cell["config_file"]))


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_the_twin_is_correct_and_its_int8_kv_cache_is_not(twin, seed):
    sound = _twin_check(twin, seed)
    assert sound["ok"] and sound["rel_l2_low"] * 3 <= sound["quantile_tol"], sound
    control = _twin_check(twin, seed, kv_dtype="int8")
    assert not control["ok"] and control["rel_l2_low"] > 3 * control["quantile_tol"], control


def test_the_configuration_file_holds_the_published_widths_and_names_its_files():
    cf = loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", "mellum2-12b-a2.5b.json"))
    published = {"hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
                 "num_experts": 64, "num_experts_per_tok": 8, "moe_intermediate_size": 896, "sliding_window": 1024,
                 "vocab_size": 98304, "intermediate_size": 7168, "max_position_embeddings": 131072,
                 "norm_topk_prob": True, "rms_norm_eps": 1e-06}
    assert {k: cf[k] for k in published} == published
    assert cf["num_hidden_layers"] == 12 and cf["reduced"] == ["num_hidden_layers"]
    assert cf["layer_types"][:12] == (["sliding_attention"] * 3 + ["full_attention"]) * 3
    assert cf["rope_parameters"]["full_attention"]["factor"] == 16
    assert cf["rope_parameters"]["sliding_attention"] == {"rope_type": "default", "rope_theta": 500000}
    assert (cf["builder"], cf["reference"], cf["family"]) == ("serve_routed", "mellum_reference", "mellum_config")
    cfg = model_config(cf, jnp.bfloat16)
    assert (cfg.num_layers, cfg.head_dim, cfg.expert_size, cfg.moe_top_k) == (12, 128, 896, 8)
    assert cfg.layer_types == tuple(cf["layer_types"][:12])
    source = open(os.path.join(loader.ROOT, "benchmark", "lib", "mellum_reference.py")).read()
    assert "deepspeed_tpu" not in source.replace("``deepspeed_tpu``", "")
