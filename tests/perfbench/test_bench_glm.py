"""What PR 38 added to the benchmark for GLM-4.7-Flash, a model whose paged
cache holds one latent a token a layer: the configuration file against the
catalog row key by key, what it states of its cut, the traffic's 8 lengths,
the manifest's own entries, the count function of attention over a latent
cache on hand-made counts, and ``mla_roofline_share.tput`` read from
hand-made spans and a hand-made kernel line (the twin joins
``test_bench_rehearsal.py``'s cases by being a file)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import loader, opcount, opcount_mla, traffic, xplane, xplane_write

MS = 1_000_000  # ns
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL, CONFIG, TWIN = "glm-4.7-flash.longdoc", "glm-4.7-flash", "tiny-glm.longdoc"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config``, as https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json has it
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47, "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
JOINED = ["paged_prefill_time_share", "paged_decode_time_share.tput", "moe_time_share.tput", "moe_roofline_share.tput",
          "moe_row_occupancy.tput", "moe_experts_hit_share.tput", "prefill_token_occupancy",
          "prefill_step_tokens_mean", "prefill_tokens_per_s", "device_idle_share.tput", "peak_hbm_bytes.tput",
          "host_gap_sched_share.tput", "host_gap_engine_share.tput", "mla_roofline_share.tput"]


def _config(name=CONFIG):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", name + ".json"))


def _metric(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json"))


def test_the_published_keys_here_are_the_catalog_rows():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog in this container")
    (row, ) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "GLM-4.7-Flash"]
    assert row["config"] == PUBLISHED and row["source_url"] == _config()["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_holds_each_published_key(key):
    cf = _config()
    if key == "num_hidden_layers":
        assert cf[key] == 8 and cf["num_hidden_layers_published"] == 47 and cf["reduced"] == [key], "the cut: depth only"
    else:
        assert cf[key] == PUBLISHED[key] and key not in cf["reduced"]


def test_the_cut_the_deployment_and_what_is_assumed_are_stated():
    cf = _config()
    for assumed in ("rope_pairing", "latent_attention", "cache_entry", "router", "depth", "pool", "weights", "eos",
                    "unused_keys"):
        assert assumed in cf["assumed"], assumed
    for words in ("max_position_embeddings", "num_nextn_predict_layers", "topk_method"):
        assert words in cf["assumed"]["unused_keys"], words
    for words in ("1,280", "1,152", "640"):
        assert words in cf["assumed"]["cache_entry"], "the padded entry is stated beside the published one"
    for words in ("every expert", "the whole vocabulary", "pipeline stages", "host's share"):
        assert words in cf["deployment"], words
    assert (cf["builder"], cf["reference"], cf["family"], cf["family_size"]) == \
        ("serve_latent", "glm_reference", "glm_config", "4.7-flash")
    ec = cf["engine"]
    assert (ec["kv_block_size"], ec["num_kv_blocks"], ec["kv_memory_fraction"]) == (128, "auto", 0.85)
    assert ec["max_context"] == 257 * 128 == 32896 and ec["max_ragged_batch_size"] == 2048
    mix = loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", "longdoc.json"))
    assert ec["max_ragged_sequence_count"] == mix["gateway"]["max_inflight_per_replica"] == 8
    ck = cf["check"]
    lengths = [r["prompt_len"] for r in traffic.make_cycle(mix)]
    assert ck["prompt_tokens"] in lengths and ck["prompt_tokens"] >= 17109, "a prompt of one of the cycle's own lengths"
    assert ck["decode_tokens"] + 1 == 256 and 0 < ck["ride_positions"] <= 16
    assert 0 < ck["latent_tol"] < ck["latent_max_tol"] < ck["quantile_tol"] < ck["rel_l2_tol"], "the cache's limits are the tight ones"
    assert ck["prompt_tokens"] + ck["decode_tokens"] <= ec["max_context"]
    assert ck["ride_positions"] * (mix["gateway"]["token_budget"] - 1) <= ec["max_context"], "the riding prompt fits"
    for control in ("rope key part", "norm on the latent", "selection bias", "routed_scaling_factor", "1/sqrt(192)",
                    "8 bits"):
        assert control in ck["why"], control


def test_the_bytes_of_the_cut_recomputed_from_the_file():
    """ISSUE 38's table: attention 21.76M a layer, an expert 9.437M, an expert
    layer 635.3M, the dense layer 84.7M, embedding and head 634.4M; 1 + 7 layers
    5,166M parameters, 10.33 GB; the cache 576 values a token a layer published,
    640 in the pool."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.model import model_config
    from deepspeed_tpu.models import TransformerLM

    cf = _config()
    h, nq = cf["hidden_size"], cf["num_attention_heads"]
    qk, c, rope = cf["qk_nope_head_dim"] + cf["qk_rope_head_dim"], cf["kv_lora_rank"], cf["qk_rope_head_dim"]
    attention = h * cf["q_lora_rank"] + cf["q_lora_rank"] * nq * qk + h * (c + rope) \
        + c * nq * (cf["qk_nope_head_dim"] + cf["v_head_dim"]) + nq * cf["v_head_dim"] * h
    expert = 3 * h * cf["moe_intermediate_size"]
    expert_layer = cf["n_routed_experts"] * expert + cf["n_shared_experts"] * expert + h * cf["n_routed_experts"] + attention
    dense_layer = attention + 3 * h * cf["intermediate_size"]
    head = 2 * cf["vocab_size"] * h
    assert (attention, expert) == (21_757_952, 9_437_184)
    assert round(expert_layer / 1e6, 1) == 635.3 and round(dense_layer / 1e6, 1) == 84.7 and round(head / 1e6, 1) == 634.4
    matrices = dense_layer + 7 * expert_layer + head
    assert round(matrices / 1e6) == 5166 and round(2 * matrices / 1e9, 2) == 10.33
    cfg = model_config(cf, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, None), jax.random.PRNGKey(0))
    held = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    norms = 8 * (2 * h + cf["q_lora_rank"] + c) + h + 7 * cf["n_routed_experts"]   # gains, and the selection bias
    assert held == matrices + norms, "the program's tree is the file's arithmetic"
    assert cfg.kv_entry == ((1, 640), ) and c + rope == 576
    assert (cfg.num_layers, cfg.moe_num_dense_layers, cfg.experts_held, cfg.vocab_size) == (8, 1, 64, 154880)


def test_the_traffic_is_the_issues():
    mix = loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", "longdoc.json"))
    assert (mix["driver"], mix["clients"], mix["count"], mix["order_seed"], mix["trace_seconds"]) == \
        ("closed_loop", 8, 8, 23, 10)
    assert mix["prompt_tokens"] == {"kind": "loguniform", "lo": 16384, "hi": 32768}
    assert mix["output_tokens"] == {"kind": "uniform", "lo": 64, "hi": 128}
    assert mix["gateway"] == {"token_budget": 2048, "max_inflight_per_replica": 8} and "start" in mix
    cycle = traffic.make_cycle(mix)
    assert sorted(r["prompt_len"] for r in cycle) == [17109, 18658, 20347, 22188, 24196, 26386, 28774, 31379]
    assert sum(r["prompt_len"] for r in cycle) == 189037
    assert sorted(r["max_new_tokens"] for r in cycle) == [68, 76, 84, 92, 100, 108, 116, 124]
    # the fixed start: two seeds offer the same requests at the same places
    a, b = (traffic.make_requests(mix, seed, 1000, 2, with_tokens=False) for seed in (1, 2**31 + 5))
    assert [(r["prompt_len"], r["position"]) for r in a] == [(r["prompt_len"], r["position"]) for r in b]


def test_the_manifests_own_entries():
    manifest = loader.load_manifest()
    (config, ) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"] == _config()["reduced"] and config["source"] == _config()["source"]
    assert config["file"] == "benchmark/configs/glm-4.7-flash.json" and len(config["why"]) <= 200
    (cell, ) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "longdoc", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert set(JOINED) <= listed, "a later PR may list the cell under more"
    (entry, ) = [m for m in manifest["per_layer"] if m["name"] == "mla_roofline_share.tput"]
    assert entry == {"name": "mla_roofline_share.tput", "unit": "%", "better": "higher", "source": "device_trace",
                     "layer": "Kernels: paged attention", "moves": "serve_tokens_per_s",
                     "workloads": entry["workloads"]} and CELL in entry["workloads"]
    metric = _metric("mla_roofline_share.tput")
    assert {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
        {k: entry[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    # not where the reader counts per-head K and V bytes
    for name in ("paged_decode_roofline_share.tput", "paged_prefill_roofline_share", "paged_roofline_share_by_layer.tput"):
        (other, ) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL not in other["workloads"], name
    assert "serve_tokens_per_s" in [m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", ())]
    resolved = loader.resolve_cell(CELL)
    assert {"serve_tokens_per_s", "setup_s"} <= {m["name"] for m in resolved["end_to_end"]}
    assert set(JOINED + ["compiles_in_window"]) <= {m["name"] for m in resolved["layer_metrics"]}
    twin = loader.resolve_cell(TWIN, rehearsal=True)
    assert (twin["config"], twin["traffic"], twin["chips"]) == ("tiny-glm", "longdoc-tiny", 1)
    assert twin["config_file"]["builder"] == "serve_latent" and twin["traffic_file"]["driver"] == "closed_loop"


@pytest.mark.parametrize("pairs,ctx,queries,want_flops,want_bytes", [
    # one 2,048-token chunk after 16,384 cached tokens, 8 layers, 20 heads of 256 + 256
    (8 * (2048 * 16384 + 2048 * 2049 // 2), 8 * 18432, 8 * 2048,
     8 * (2048 * 16384 + 2048 * 2049 // 2) * 20 * 2 * 512, 8 * 18432 * 1152 + 8 * 2048 * 20 * 512 * 2),
    # 8 decode rows at 24,000 tokens: a row reads its context once a layer
    (8 * 8 * 24001, 8 * 8 * 24001, 8 * 8, 8 * 8 * 24001 * 20 * 1024, 8 * 8 * 24001 * 1152 + 8 * 8 * 20 * 512 * 2),
    (0, 0, 0, 0, 0),
])
def test_latent_attention_cost_counts_by_hand(pairs, ctx, queries, want_flops, want_bytes):
    assert opcount_mla.latent_attention_cost(pairs, ctx, queries, 20, 256, 256, 1152, 2) == (want_flops, want_bytes)
    if queries == 8 * 2048:  # a chunk is bound by FLOP/s, a decode step by bytes/s
        assert opcount.min_seconds(want_flops, want_bytes, PEAKS)[1] == "flops"
    elif queries:
        assert opcount.min_seconds(want_flops, want_bytes, PEAKS)[1] == "bytes"


def test_the_absorbed_form_cannot_read_over_47_percent_where_flops_bound():
    assert opcount_mla.absorbed_share_of_expanded(256, 256, 512, 64) == pytest.approx(1024 / 2176)
    assert 0.47 < opcount_mla.absorbed_share_of_expanded(256, 256, 512, 64) < 0.471


def _planes(kernel_ms, counts=True):
    """One 2,048-token chunk after 16,384 cached tokens and a decode call of 8
    rows x 4 steps at 24,000, each span with its counts (or, the parent's
    program, without); ``kernel_ms`` of the two attention kernels."""
    chunk = 8 * (2048 * 16384 + 2048 * 2049 // 2)
    dec = 8 * sum(8 * (24000 + j + 1) for j in range(4))
    a = f"attn_pairs={chunk},attn_ctx_tokens={8 * 18432},kv_entry_bytes=1280," if counts else ""
    b = f"attn_pairs={dec},attn_ctx_tokens={8 * 8 * 24004},kv_entry_bytes=1280," if counts else ""
    return {
        "/device:TPU:0": {"XLA Ops": [("%paged_attn_q_tiled.1 = bf16[2048,20,512] custom-call()", 0, kernel_ms[0] * MS),
                                      ("%moe_gmm.1 = bf16[8192,1536] custom-call()", 100 * MS, 20 * MS),
                                      ("%paged_attn_kv_split.3 = bf16[8,20,512] custom-call()", 130 * MS,
                                       kernel_ms[1] * MS)]},
        "/host:CPU": {"driver": [(f"dstpu/serving/prefill#rows=1,{a}tokens=2048,steps=1#", 0, 125 * MS),
                                 (f"dstpu/serving/decode#rows=8,{b}tokens=32,steps=4#", 128 * MS, 20 * MS)]},
    }, chunk, dec


def _ctx(tmp_path, planes, config_file):
    d = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))), "peaks": PEAKS,
            "kind": "serve", "cell": {"root": str(tmp_path), "name": "cell", "config_file": config_file},
            "system": SimpleNamespace(kv_itemsize=2)}


def _read(ctx, metric):
    return loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric["args"]})


def test_mla_roofline_share_is_least_time_from_the_spans_counts_over_traced_kernel_time(tmp_path):
    planes, chunk, dec = _planes((90, 10))
    ctx = _ctx(tmp_path, planes, _config())
    least = opcount.min_seconds(*opcount_mla.latent_attention_cost(chunk, 8 * 18432, 8 * 2048, 20, 256, 256, 1152), PEAKS)[0] \
        + opcount.min_seconds(*opcount_mla.latent_attention_cost(dec, 8 * 8 * 24004, 8 * 32, 20, 256, 256, 1152), PEAKS)[0]
    metric = _metric("mla_roofline_share.tput")
    assert _read(ctx, metric) == pytest.approx(100.0 * least / 0.100)
    # the file's entry is the published 576 values, not what the pool pads it to
    assert _config()["kv_lora_rank"] + _config()["qk_rope_head_dim"] == 576
    share = _metric("paged_prefill_time_share")
    assert _read(ctx, share) == pytest.approx(100.0 * 90 / 120)


def test_a_kernel_in_the_absorbed_form_at_the_mxus_peak_reads_47_percent(tmp_path):
    """The chunk's absorbed work, 2 x (576 + 512) operations a pair a head, at
    the peak FLOP/s takes 2,176 / 1,024 of the least time: the share reads
    47.1% and no change to the program can make this form read more."""
    chunk = 8 * (2048 * 16384 + 2048 * 2049 // 2)
    at_peak_ms = chunk * 20 * 2 * (576 + 512) / PEAKS["flops_bf16"] * 1e3
    planes, _, _ = _planes((at_peak_ms, 0))
    planes["/host:CPU"]["driver"] = planes["/host:CPU"]["driver"][:1]
    planes["/device:TPU:0"]["XLA Ops"] = planes["/device:TPU:0"]["XLA Ops"][:2]
    value = _read(_ctx(tmp_path, planes, _config()), _metric("mla_roofline_share.tput"))
    assert value == pytest.approx(100.0 * 1024 / 2176, rel=1e-3) and value < 47.1


@pytest.mark.parametrize("config_file,counts", [(None, False), ({"hidden_size": 4096, "num_hidden_layers": 2}, True)])
def test_the_reader_reads_nothing_without_counts_or_a_latent_entry(tmp_path, config_file, counts):
    """The parent's program has no such counts, another configuration no
    latent entry: the reader returns nothing and does not raise."""
    planes, _, _ = _planes((90, 10), counts)
    ctx = _ctx(tmp_path, planes, config_file or _config())
    metric = _metric("mla_roofline_share.tput")
    assert _read(ctx, metric) is None
    assert _read({**ctx, "reduced": None}, metric) is None


def test_the_controls_runner_on_the_twin_fails_the_8_bit_latent_by_the_cached_entries(tmp_path):
    """``builders/serve_latent.py``'s own runner at the twin's size on the CPU:
    the sound program is correct, and with the pool rounded to 8 bits a value
    behind the program's back the entries read back out of layer 0 stand far
    from the reference's (``latent_tol``), whatever the logits say."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))  # not the checkout's own cache
    p = subprocess.run([sys.executable, os.path.join(loader.ROOT, "benchmark", "builders", "serve_latent.py"), "--workload",
                        TWIN, "--seeds", "5", "--controls", "latent8", "--rehearsal"], cwd=loader.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, rounded = [json.loads(line) for line in p.stdout.splitlines() if line.startswith("{")]
    assert (sound["control"], rounded["control"]) == ("sound", "latent8")
    assert sound["ok"] and sound["latent_rel_l2_median"] < sound["latent_tol"] and sound["rel_l2_low"] < sound["quantile_tol"]
    assert not rounded["ok"] and rounded["latent_rel_l2_median"] > 100 * rounded["latent_tol"]
    assert rounded["latent_rel_l2_max"] > rounded["latent_max_tol"]
