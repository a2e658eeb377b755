"""What PR 41 added to the benchmark for Solar-Open2-250B, a model whose
sequences hold a recurrent state beside the paged K/V cache: the configuration
file against the catalog row key by key, what it states of its cut, the
arithmetic of the cut recomputed from the file and from the program's own
tree, the traffic's 128 rows, the manifest's own entries BY NAME (never by
place or count), the delta rule's count function by hand, and
``kda_roofline_share.tput`` / ``kda_time_share.tput`` /
``state_slot_occupancy.tput`` read from hand-made spans and kernel lines (the
twin joins ``test_bench_rehearsal.py``'s cases by being a file)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import loader, opcount, opcount_kda, traffic, xplane, xplane_write

MS = 1_000_000  # ns
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL, CONFIG, TWIN = "solar-open2-250b.decode-heavy-128", "solar-open2-250b", "tiny-solar.decode-heavy-128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config``, as https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json has it
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False, "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8}
CUT = {"num_hidden_layers": (4, "num_hidden_layers_published", 48), "n_routed_experts": (40, "n_routed_experts_published", 320)}
JOINED = ["device_idle_share.tput", "peak_hbm_bytes.tput", "host_gap_sched_share.tput", "host_gap_engine_share.tput",
          "decode_step_p50_ms.tput", "decode_batch_mean.tput", "decode_row_occupancy.tput", "decode_rows_mixed_share.tput",
          "decode_horizon_mean.tput", "paged_decode_time_share.tput", "moe_time_share.tput", "moe_roofline_share.tput",
          "moe_row_occupancy.tput", "moe_experts_hit_share.tput"]
OWN = {"kda_time_share.tput": ("lower", "device_trace", "Kernels: linear attention", "kernel_time_share"),
       "kda_roofline_share.tput": ("higher", "device_trace", "Kernels: linear attention", "kda_roofline_share"),
       "state_slot_occupancy.tput": ("higher", "program_counter", "Model / memory", "span_arg_ratio")}


def _config(name=CONFIG):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", name + ".json"))


def _metric(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json"))


def _mix(name="decode-heavy-128"):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", name + ".json"))


def test_the_published_keys_here_are_the_catalog_rows():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog in this container")
    (row, ) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "Solar-Open2-250B"]
    assert row["config"] == PUBLISHED and row["source_url"] == _config()["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_holds_each_published_key(key):
    cf = _config()
    if key in CUT:
        here, beside, published = CUT[key]
        assert cf[key] == here and cf[beside] == published == PUBLISHED[key] and key in cf["reduced"]
    else:
        assert cf[key] == PUBLISHED[key] and key not in cf["reduced"]


def test_the_cut_the_deployment_and_what_is_assumed_are_stated():
    cf = _config()
    assert cf["reduced"] == sorted(CUT, reverse=True) and cf["chips_sharing_a_layer"] == 8 and cf["first_expert"] == 0
    for assumed in ("kda_gates", "kda_decay", "kda_step_size", "kda_conv", "kda_norms", "kda_heads", "gqa_gate", "router",
                    "shared_expert", "share_held", "depth", "vocabulary", "caches", "weights", "eos", "unused_keys"):
        assert assumed in cf["assumed"], assumed
    for words in ("arXiv:2510.26692", "rank 128"):
        assert words in cf["assumed"]["kda_gates"], words
    for words in ("3.2 tokens", "25.6", "38 against 40"):
        assert words in cf["assumed"]["share_held"], words
    for words in ("4,194,304", "147,456", "4,096 bytes a token", "1.67 GB"):
        assert words in cf["assumed"]["caches"], words
    for words in ("eight v5e chips share each layer", "40 of the 320", "pipeline stages", "host's share", "128 state slots"):
        assert words in cf["deployment"], words
    assert (cf["builder"], cf["reference"], cf["family"], cf["family_size"]) == \
        ("serve_state", "solar_reference", "solar_config", "open2-250b")
    ec, mix = cf["engine"], _mix()
    assert (ec["kv_block_size"], ec["num_kv_blocks"], ec["max_context"]) == (128, "auto", 65 * 128)
    assert ec["max_ragged_sequence_count"] == ec["max_tracked_sequences"] == mix["gateway"]["max_inflight_per_replica"] == 128
    assert ec["max_ragged_batch_size"] == mix["gateway"]["token_budget"] == 512
    ck = cf["check"]
    # two buckets of rows and two of tokens: 16 programs to warm where the powers of two are 55
    assert (ec["seq_buckets"], ec["token_buckets"]) == ([32, 128], [128, 512])
    chunk = mix["gateway"]["token_budget"] - (ec["max_tracked_sequences"] - 2)  # beside a token of every other row
    assert ck["prompt_tokens"] == 1013 and ck["prompt_tokens"] % chunk % 8, "no multiple of the tile"
    assert ck["decode_tokens"] >= 1020 and ck["ride_positions"] > 0 and ck["tail_positions"] > 0 and ck["horizon"] == 32
    assert ck["prompt_tokens"] + ck["ride_positions"] + ck["decode_tokens"] + ck["tail_positions"] <= ec["max_context"]
    assert len(ck["state_tol"]) == 3 and all(0 < t for t in ck["state_tol"]) and 0 < ck["quantile_tol"] < ck["rel_l2_tol"]
    assert 0 < ck["rule_tol"] < min(ck["state_tol"]) and "state_short_tol" not in ck
    for control in ("bfloat16", "default precision", "decay", "doubled", "tail", "L2 norm", "padding", "selection bias"):
        assert control in ck["why"], control


def test_the_bytes_of_the_cut_recomputed_from_the_file():
    """ISSUE 41's table: an expert 15.73M, 40 held 629.1M; a linear layer
    outside them 154.8M (the convolutions' 0.1M counted), the softmax layer
    126.1M; embedding and head 1,610.6M; 4 layers 4,717.6M parameters, 9.44 GB;
    a sequence's state 13.03 MB, a token's K/V 4,096 bytes."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.model import model_config
    from deepspeed_tpu.models import TransformerLM

    cf = _config()
    h, lin, r = cf["hidden_size"], cf["linear_attn_config"], 128
    c = lin["num_heads"] * lin["head_dim"]
    expert = 3 * h * cf["moe_intermediate_size"]
    outside = cf["n_shared_experts"] * expert + h * cf["n_routed_experts_published"]    # shared expert and router
    kda = 3 * h * c + c * h + 2 * (h * r + r * c) + h * lin["num_heads"] + 3 * lin["short_conv_kernel_size"] * c
    gqa = 2 * h * cf["num_attention_heads"] * cf["head_dim"] + 2 * h * cf["num_key_value_heads"] * cf["head_dim"] \
        + h * cf["num_attention_heads"] * cf["head_dim"]
    head = 2 * cf["vocab_size"] * h
    assert expert == 15_728_640 and round(40 * expert / 1e6, 1) == 629.1
    assert round((kda + outside) / 1e6, 1) == 154.8 and round((gqa + outside) / 1e6, 1) == 126.1
    assert round(head / 1e6, 1) == 1610.6
    matrices = 4 * (cf["n_routed_experts"] * expert + outside) + 3 * kda + gqa + head
    assert round(matrices / 1e6, 1) == 4717.6 and round(2 * matrices / 1e9, 2) == 9.44
    cfg = model_config(cf, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, None), jax.random.PRNGKey(0))
    held = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    # gains (two a layer, the final one, the linear layers' per-head one), the selection bias, A_log and dt_bias
    small = 4 * 2 * h + h + 3 * lin["head_dim"] + 4 * cf["n_routed_experts_published"] + 3 * (lin["num_heads"] + c)
    assert held == matrices + small, "the program's tree is the file's arithmetic"
    assert cfg.kv_layers == (0, ) and cfg.state_layers == (1, 2, 3) and cfg.kv_entry == ((8, 128), (8, 128))
    assert (cfg.num_layers, cfg.experts_held, cfg.moe_num_experts, cfg.moe_top_k, cfg.vocab_size) == (4, 40, 320, 8, 196608)
    (heads, dk, dv), (taps, channels) = cfg.state_entry
    state, tail = heads * dk * dv * 4, taps * channels * 2
    assert (state, tail) == (4_194_304, 147_456) and round(3 * (state + tail) / 1e6, 2) == 13.03
    assert round(128 * 3 * (state + tail) / 1e9, 2) == 1.67 and 2 * 8 * 128 * 2 * len(cfg.kv_layers) == 4096


def test_the_traffic_is_decode_heavy_64_with_twice_the_rows():
    mix, half = _mix(), _mix("decode-heavy-64")
    assert (mix["clients"], mix["count"], mix["gateway"]["max_inflight_per_replica"]) == (128, 128, 128)
    same = lambda m: {k: v for k, v in m.items() if k not in ("why", "clients", "count", "gateway")}
    assert same(mix) == same(half) and mix["gateway"]["token_budget"] == half["gateway"]["token_budget"] == 512
    assert (mix["driver"], mix["order_seed"], mix["start"], mix["cycle_seconds"], mix["trace_seconds"]) == \
        ("closed_loop", 23, 28, 10, 10)
    assert mix["prompt_tokens"] == {"kind": "loguniform", "lo": 256, "hi": 1024}
    assert mix["output_tokens"] == {"kind": "uniform", "lo": 512, "hi": 1024}
    cycle = traffic.make_cycle(mix)
    prompts, outputs = sorted(r["prompt_len"] for r in cycle), sorted(r["max_new_tokens"] for r in cycle)
    assert len(cycle) == 128 and 256 <= prompts[0] and prompts[-1] <= 1024 and 512 <= outputs[0] and outputs[-1] <= 1024
    assert round(sum(prompts) / 128) == 554 and round(sum(outputs) / 128) == 768
    a, b = (traffic.make_requests(mix, seed, 1000, 2, with_tokens=False) for seed in (1, 2**31 + 5))
    assert [(r["prompt_len"], r["position"]) for r in a] == [(r["prompt_len"], r["position"]) for r in b]
    assert a[0]["position"] == 28


def test_the_manifests_own_entries_by_name():
    manifest = loader.load_manifest()
    (config, ) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == _config()["reduced"] and config["source"] == _config()["source"]
    assert config["file"] == "benchmark/configs/solar-open2-250b.json" and len(config["why"]) <= 200
    (cell, ) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "decode-heavy-128", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "3.2 tokens" in cell["why"]
    assert [w["name"] for w in manifest["workloads"] if w["config"] == CONFIG] == [CELL], "one cell, no second"
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert set(JOINED) | set(OWN) <= listed, "a later PR may list the cell under more"
    for name, (better, source, layer, reader) in OWN.items():
        (entry, ) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry == {"name": name, "unit": "%", "better": better, "source": source, "layer": layer,
                         "moves": "serve_tokens_per_s", "workloads": entry["workloads"]} and CELL in entry["workloads"]
        metric = _metric(name)
        assert {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
            {k: entry[k] for k in ("name", "unit", "better", "source", "layer", "moves")} and metric["reader"] == reader
    # not where the reader gives every layer an attention call, nor where a list is pinned
    for name in ("paged_decode_roofline_share.tput", "paged_prefill_roofline_share", "paged_roofline_share_by_layer.tput",
                 "decode_kv_live_share.tput", "mla_roofline_share.tput"):
        (other, ) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL not in other["workloads"], name
    assert "serve_tokens_per_s" in [m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", ())]
    resolved = loader.resolve_cell(CELL)
    assert {"serve_tokens_per_s", "setup_s"} == {m["name"] for m in resolved["end_to_end"]}
    assert set(JOINED + list(OWN) + ["compiles_in_window"]) <= {m["name"] for m in resolved["layer_metrics"]}
    twin = loader.resolve_cell(TWIN, rehearsal=True)
    assert (twin["config"], twin["traffic"], twin["chips"]) == ("tiny-solar", "decode-heavy-128-tiny", 1)
    assert twin["config_file"]["builder"] == "serve_state" and twin["traffic_file"]["driver"] == "closed_loop"


@pytest.mark.parametrize("row_calls,tokens,want_flops,want_bytes", [
    (1, 1, 64 * 6 * 128 * 128, 2 * 64 * 128 * 128 * 4 + 64 * (512 * 2 + 128 * 4 + 4)),
    (3 * 128, 3 * 128, 3 * 128 * 64 * 6 * 16384, 3 * 128 * (8_388_608 + 64 * 1540)),
    (3, 3 * 512, 3 * 512 * 64 * 6 * 16384, 3 * 8_388_608 + 3 * 512 * 64 * 1540),
])
def test_delta_rule_cost_counts_by_hand(row_calls, tokens, want_flops, want_bytes):
    """One token of one row in one layer; a decode step of 128 rows over 3
    layers; a 512-token chunk of one row over 3 layers, whose state moves once
    a layer however many tokens it is fed."""
    assert opcount_kda.delta_rule_cost(row_calls, tokens, 64, 128, 128, 2) == (want_flops, want_bytes)
    flops, nbytes = opcount_kda.delta_rule_cost(row_calls, tokens, 64, 128, 128, 2)
    assert opcount.min_seconds(flops, nbytes, PEAKS)[1] == "bytes", "the rule is bound by memory in every form of call"


def _planes(kernel_ms, counts=True):
    """A 512-token chunk of one row beside 100 one-token rows, and a decode
    call of 128 rows x 32 steps, each span with its counts (or, the parent's
    program, without); ``kernel_ms`` of the two delta-rule kernels."""
    a = "state_rows=101,lin_tokens=1836,state_slots_live=101,state_slots_total=128," if counts else ""
    b = "state_rows=4096,lin_tokens=12288,state_slots_live=128,state_slots_total=128," if counts else ""
    return {
        "/device:TPU:0": {"XLA Ops": [("%kda_chunk_scan.1 = f32[192,64,8,128] custom-call()", 0, kernel_ms[0] * MS),
                                      ("%moe_gmm.1 = bf16[8192,1280] custom-call()", 100 * MS, 20 * MS),
                                      ("%kda_recurrent_step.3 = f32[128,8,8,128] custom-call()", 130 * MS,
                                       kernel_ms[1] * MS)]},
        "/host:CPU": {"driver": [(f"dstpu/serving/prefill#rows=101,{a}tokens=612,steps=1#", 0, 125 * MS),
                                 (f"dstpu/serving/decode#rows=128,{b}tokens=4096,steps=32#", 128 * MS, 60 * MS)]},
    }


def _ctx(tmp_path, planes, config_file):
    d = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))), "peaks": PEAKS,
            "kind": "serve", "cell": {"root": str(tmp_path), "name": "cell", "config_file": config_file},
            "system": SimpleNamespace(kv_itemsize=2, cfg=SimpleNamespace(dtype="bfloat16"))}


def _read(ctx, metric):
    return loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric["args"]})


def test_the_three_metrics_read_from_the_spans_counts_and_the_traced_kernel_time(tmp_path):
    ctx = _ctx(tmp_path, _planes((30, 50)), _config())
    least = sum(opcount.min_seconds(*opcount_kda.delta_rule_cost(rows * 3, tokens, 64, 128, 128, 2), PEAKS)[0]
                for rows, tokens in ((101, 1836), (4096, 12288)))
    assert _read(ctx, _metric("kda_roofline_share.tput")) == pytest.approx(100.0 * least / 0.080)
    assert _read(ctx, _metric("kda_time_share.tput")) == pytest.approx(100.0 * 80 / 100)
    assert _read(ctx, _metric("state_slot_occupancy.tput")) == pytest.approx(100.0 * 229 / 256)
    # a kernel that moved every row's state once at the peak bandwidth and did nothing else reads just under 100
    at_peak_ms = 4096 * 3 * 8_388_608 / PEAKS["hbm_bytes_per_s"] * 1e3
    planes = _planes((0, at_peak_ms))
    planes["/host:CPU"]["driver"] = planes["/host:CPU"]["driver"][1:]
    planes["/device:TPU:0"]["XLA Ops"] = planes["/device:TPU:0"]["XLA Ops"][1:]
    (tmp_path / "b").mkdir()
    value = _read(_ctx(tmp_path / "b", planes, _config()), _metric("kda_roofline_share.tput"))
    assert 100.0 < value < 101.5, "the tokens' own bytes are the 1.2% over the state's"


@pytest.mark.parametrize("config_file,counts", [(None, False), ({"hidden_size": 4096, "num_hidden_layers": 2}, True)])
def test_the_readers_read_nothing_without_counts_or_linear_layers(tmp_path, config_file, counts):
    """The parent's program has no such counts, another configuration no
    linear layers: the readers return nothing and do not raise."""
    ctx = _ctx(tmp_path, _planes((30, 50), counts), config_file or _config())
    assert _read(ctx, _metric("kda_roofline_share.tput")) is None
    assert _read({**ctx, "reduced": None}, _metric("kda_roofline_share.tput")) is None
    if not counts:
        assert _read(ctx, _metric("state_slot_occupancy.tput")) is None


def test_the_rule_alone_sees_what_the_whole_model_hides(monkeypatch):
    """``serve_state.rule_check``: the program's two forms of the delta rule
    on a pool of slots, fed what a reference would feed its own, against the
    reference's rule token by token. Sound it is at float32 rounding; a pool
    rounded to bfloat16 after every call, or a row's padding through the rule,
    is far over the limit, in a house of 8 rows of which one comes late."""
    import jax.numpy as jnp
    import numpy as np

    builder = loader.load_module("builders", "serve_state")
    cell = loader.resolve_cell(TWIN, rehearsal=True)
    rng = np.random.default_rng(3)
    n, heads, d = 73, 4, 16
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    fed = tuple(jnp.asarray(a, jnp.float32) for a in (
        unit(rng.normal(size=(n, heads, d))) / 4, unit(rng.normal(size=(n, heads, d))), rng.normal(size=(n, heads, d)),
        -rng.uniform(0.001, 0.5, size=(n, heads, d)), rng.uniform(0.0, 2.0, size=(n, heads))))
    kv = SimpleNamespace(state_pool=jnp.zeros((3, 8, heads, d, d), jnp.float32))
    engine = SimpleNamespace(state_manager=SimpleNamespace(kv_cache=kv))
    worst = lambda reading: max(max(part.values()) for part in reading.values())
    sound = builder.rule_check(cell, engine, fed, rehearsal=True)
    assert set(sound["chunks"]) == {0, 2, 3, 4, 6} and set(sound["steps"]) == {0, 2, 3, 4, 6, 7}, "the late row has no chunk"
    assert worst(sound) < 1e-5 < cell["config_file"]["check"]["rule_tol"]
    assert not np.asarray(kv.state_pool).any(), "the pool is handed back as it began"
    rounded = builder.rule_check(cell, engine, fed, rehearsal=True, after_call=builder._control_hand("state_bf16"))
    assert 1e-3 < min(rounded["steps"].values()) and worst(rounded) < 1e-2
    builder._patch_padding(True)
    try:
        assert min(builder.rule_check(cell, engine, fed, rehearsal=True)["chunks"].values()) > 1e-2
    finally:
        builder._patch_padding(False)
