"""What PR 47 added to the benchmark for MiniCPM-SALA, a model whose softmax
layers attend a learned block selection over pooled keys between lightning
layers that hold a state a sequence: the configuration file against the
catalog row key by key, what it states of its cut, the arithmetic of the cut
recomputed from the file and from the program's own tree, the traffic letter
for letter, the manifest's own entries BY NAME (never by place or count), the
two count functions by hand, and the new metrics read from hand-made spans and
kernel lines, which read nothing from a program without the counts (the twin
joins ``test_bench_rehearsal.py``'s cases by being a file)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import loader, opcount, opcount_lightning, opcount_sparse, traffic, xplane, xplane_write

MS = 1_000_000  # ns
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL, CONFIG, TWIN = "minicpm-sala.longctx", "minicpm-sala", "tiny-minicpm-sala.longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SPARSE_AT = (0, 9, 16, 17, 22, 29, 30, 31)
# the catalog row's ``config``, as https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json has it
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True, "max_position_embeddings": 524288,
    "model_type": "minicpm_sala", "mixer_types": ["minicpm4" if l in SPARSE_AT else "lightning-attn" for l in range(32)],
    "num_attention_heads": 32, "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 32, "dim_model_base": 256, "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True}
JOINED = ["device_idle_share.tput", "peak_hbm_bytes.tput", "host_gap_sched_share.tput", "host_gap_engine_share.tput",
          "decode_step_p50_ms.tput", "decode_batch_mean.tput", "decode_rows_mixed_share.tput", "paged_decode_time_share.tput", "paged_prefill_time_share", "prefill_step_tokens_mean",
          "prefill_tokens_per_s", "prefill_token_occupancy", "state_slot_occupancy.tput"]
OWN = {"sparse_blocks_read_share.tput": ("lower", "program_counter", "Kernels: paged attention", "span_arg_ratio"),
       "sparse_attn_roofline_share.tput": ("higher", "device_trace", "Kernels: paged attention", "sparse_attn_roofline_share"),
       "index_sort_time_share.tput": ("lower", "device_trace", "Kernels: selection indexer", "kernel_time_share"),
       "lightning_time_share.tput": ("lower", "device_trace", "Kernels: linear attention", "kernel_time_share"),
       "lightning_roofline_share.tput": ("higher", "device_trace", "Kernels: linear attention", "lightning_roofline_share")}


def _config(name=CONFIG):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", name + ".json"))


def _metric(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json"))


def test_the_published_keys_here_are_the_catalog_rows():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog in this container")
    (row, ) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "MiniCPM-SALA"]
    assert row["config"] == PUBLISHED and row["source_url"] == _config()["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_holds_each_published_key(key):
    cf = _config()
    if key == "num_hidden_layers":
        assert cf[key] == 16 and cf["num_hidden_layers_published"] == 32 == PUBLISHED[key] and cf["reduced"] == [key]
        assert cf["first_layer"] == 9
    else:
        assert cf[key] == PUBLISHED[key] and key not in cf["reduced"]


def test_the_cut_the_deployment_and_what_is_assumed_are_stated():
    cf = _config()
    first, n = cf["first_layer"], cf["num_hidden_layers"]
    run = cf["mixer_types"][first:first + n]
    assert [first + l for l, m in enumerate(run) if m == "minicpm4"] == [9, 16, 17, 22] and run.count("lightning-attn") == 12
    assert run[0] == "minicpm4", "the cut begins with a sparse layer, as the model does"
    # of the runs of 16 published layers that hold the published 1 : 3, the one that begins with a sparse layer
    ratio = [s for s in range(17) if cf["mixer_types"][s:s + 16].count("minicpm4") == 4]
    assert ratio == [7, 8, 9, 14] and [s for s in ratio if cf["mixer_types"][s] == "minicpm4"] == [9]
    assert cf["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64, "init_blocks": 1,
                                   "window_size": 2048, "dense_len": 8192}
    for assumed in ("sparse_config", "selection", "lightning_decay", "lightning_output", "sparse_gate", "norms", "residual",
                    "weights", "unread_keys"):
        assert assumed in cf["assumed"], assumed
    for words in ("arXiv:2506.07900", "MiniCPM4"):
        assert words in cf["assumed"]["sparse_config"], words
    for words in ("arXiv:2401.04658", "PUBLISHED layer index"):
        assert words in cf["assumed"]["lightning_decay"], words
    assert "NOT built" in cf["assumed"]["selection"] and "second chip" in cf["deployment"]
    assert cf["engine"]["kv_block_size"] == cf["sparse_config"]["block_size"], "the KV block is the selection's block"
    assert cf["engine"]["max_context"] == 66176 == 1034 * 64 and cf["engine"]["max_context"] >= 65536 + 512
    assert (cf["engine"]["max_tracked_sequences"], cf["engine"]["max_ragged_batch_size"]) == (8, 2048)
    assert cf["check"]["prompt_tokens"] == 62757, "the mix's longest prompt"
    assert sum(cf["check"][k] for k in ("ride_positions", "decode_tokens", "tail_positions")) == 496, "its longest answer"
    assert len(cf["check"]["state_tol"]) == 12


def test_the_bytes_of_the_cut_recomputed_from_the_file():
    """ISSUE 47's arithmetic: a lightning layer 5 x 16.78M + 201.33M = 285.2M, a
    sparse layer 3 x 16.78M + 2 x 1.05M + 201.33M = 253.8M (the issue rounds
    it to 253.7M), embedding and head 601.7M: 5,039M parameters, 10.08 GB in bf16; 4,224 bytes of K, V and pooled
    keys a token; 25.2 MB of state a sequence."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.model import model_config
    from deepspeed_tpu.models import TransformerLM

    cf = _config()
    h, f, d = cf["hidden_size"], cf["intermediate_size"], cf["head_dim"]
    mlp = 3 * h * f
    lightning = 5 * h * cf["lightning_nh"] * cf["lightning_head_dim"] + mlp
    sparse = 3 * h * cf["num_attention_heads"] * d + 2 * h * cf["num_key_value_heads"] * d + mlp
    head = 2 * cf["vocab_size"] * h
    assert round(mlp / 1e6, 2) == 201.33 and round(lightning / 1e6, 1) == 285.2
    assert round(sparse / 1e6, 1) == 253.8   # (the issue wrote 253.7)
    assert round(head / 1e6, 1) == 601.7
    matrices = 12 * lightning + 4 * sparse + head
    assert round(matrices / 1e6) == 5039 and round(2 * matrices / 1e9, 2) == 10.08
    whole = 24 * lightning + 8 * sparse + head
    assert round(whole / 1e9, 2) == 9.48 and round(2 * whole / 1e9, 2) == 18.95
    cfg = model_config(cf, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, None), jax.random.PRNGKey(0))
    held = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    # gains: two a layer and the final one; q and k a head in both mixers; the lightning output norm; the decay's exponents
    small = 16 * 2 * h + h + 4 * 2 * d + 12 * (2 * d + h) + 12 * cf["lightning_nh"]
    assert held == matrices + small, "the program's tree is the file's arithmetic"
    assert cfg.kv_layers == (0, 7, 8, 13) and len(cfg.state_layers) == 12 and cfg.kv_entry == ((2, 128), (2, 128))
    assert cfg.index_entry == (16, 2, 128) and cfg.state_entry == ((32, 128, 128), )
    assert (cfg.residual_scale, cfg.logit_scale, cfg.embed_scale) == (1.4 / 32 ** 0.5, 1 / 16, 12.0)
    token = len(cfg.kv_layers) * (2 * 2 * 128 * 2 + 2 * 128 * 2 // 16)
    assert token == 4224 and 2 * 2 * 128 * 2 == 1024 and 16 * (2 * 128 * 2 // 16) == 512
    state = 32 * 128 * 128 * 4
    assert state == 2_097_152 and round(12 * state / 1e6, 1) == 25.2
    # the ramp's peak: eight sequences at the mean length are 1.6 GB of the three caches; the pool for the longest, 2.4
    assert round(8 * (47260 + 384) * token / 1e9, 2) == 1.61
    assert round(8 * cf["engine"]["max_context"] * token / 1e9 + 8 * 12 * state / 1e9, 2) == 2.44


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", "longctx.json"))
    assert {k: mix[k] for k in ("driver", "clients", "count", "cycle_seconds", "trace_seconds", "order_seed", "start")} == \
        {"driver": "closed_loop", "clients": 8, "count": 8, "cycle_seconds": 20, "trace_seconds": 10, "order_seed": 23, "start": 0}
    assert mix["prompt_tokens"] == {"kind": "loguniform", "lo": 32768, "hi": 65536}
    assert mix["output_tokens"] == {"kind": "uniform", "lo": 256, "hi": 512}
    assert mix["gateway"] == {"token_budget": 2048, "max_inflight_per_replica": 8}
    cycle = traffic.make_cycle(mix)
    prompts, outputs = sorted(r["prompt_len"] for r in cycle), sorted(r["max_new_tokens"] for r in cycle)
    assert (prompts[0], prompts[-1], sum(prompts), round(sum(prompts) / 8)) == (34219, 62757, 378076, 47260)
    assert (outputs[0], outputs[-1], sum(outputs) / 8) == (272, 496, 384)
    assert all(p > _config()["sparse_config"]["dense_len"] for p in prompts), "every request crosses dense_len"


def test_the_manifests_own_entries_by_name():
    manifest = loader.load_manifest()
    (config, ) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == _config()["reduced"] == ["num_hidden_layers"] and config["source"] == _config()["source"]
    assert config["file"] == "benchmark/configs/minicpm-sala.json" and len(config["why"]) <= 200
    (cell, ) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "longctx", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
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
    # not where the reader gives every layer a dense causal attention call, nor where a list is pinned
    for name in ("paged_decode_roofline_share.tput", "paged_prefill_roofline_share", "paged_roofline_share_by_layer.tput",
                 "decode_kv_live_share.tput", "mla_roofline_share.tput", "host_gap_fetch_share.tput",
                 "host_gap_launch_share.tput", "host_gap_observe_share.tput", "driver_offcpu_share.tput",
                 "kda_time_share.tput", "kda_roofline_share.tput",
                 # no decode horizon runs in the window (a prefill is always pending): their readers find nothing
                 "decode_horizon_mean.tput", "decode_row_occupancy.tput"):
        (other, ) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL not in other["workloads"], name
    assert "serve_tokens_per_s" in [m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", ())]
    resolved = loader.resolve_cell(CELL)
    assert {"serve_tokens_per_s", "setup_s"} == {m["name"] for m in resolved["end_to_end"]}
    assert set(JOINED + list(OWN) + ["compiles_in_window"]) <= {m["name"] for m in resolved["layer_metrics"]}
    twin = loader.resolve_cell(TWIN, rehearsal=True)
    assert (twin["config"], twin["traffic"], twin["chips"]) == ("tiny-minicpm-sala", "longctx-tiny", 1)
    assert twin["config_file"]["builder"] == "serve_sparse" and twin["traffic_file"]["driver"] == "closed_loop"
    assert twin["config_file"]["reference"] == _config()["reference"] == "minicpm_sala_reference"


@pytest.mark.parametrize("row_calls,tokens,want_flops,want_bytes", [
    (1, 1, 32 * 4 * 128 * 128, 2 * 32 * 128 * 128 * 4 + 32 * 512 * 2),
    (12 * 8, 12 * 8, 96 * 32 * 4 * 16384, 96 * (4_194_304 + 32 * 1024)),
    (12, 12 * 2048, 12 * 2048 * 32 * 4 * 16384, 12 * 4_194_304 + 12 * 2048 * 32 * 1024),
])
def test_recurrence_cost_counts_by_hand(row_calls, tokens, want_flops, want_bytes):
    """One token of one row in one layer; a decode step of 8 rows over 12
    layers; a 2,048-token chunk of one row over
    12 layers, whose state moves once a layer (its tokens' q, k, v and o are then most of the bytes)."""
    assert opcount_lightning.recurrence_cost(row_calls, tokens, 32, 128, 128, 2) == (want_flops, want_bytes)
    assert opcount.min_seconds(want_flops, want_bytes, PEAKS)[1] == "bytes", "the recurrence is bound by memory in every call"


def test_selected_attention_cost_counts_by_hand():
    """A one-token row at 47k in four sparse layers: 64 blocks less the part of
    its own block past it, every selected token's K and V once; a 2,048-token
    chunk is charged its row's context once, not a block a token."""
    pairs, entry = 4 * (63 * 64 + 20), 2 * 2 * 128 * 2
    assert opcount_sparse.selected_attention_cost(pairs, pairs, 4, 32, 128, entry, 2) == \
        (4 * pairs * 32 * 128, pairs * entry + 2 * 4 * 32 * 128 * 2)
    chunk_pairs, ctx = 4 * 2048 * 4096, 4 * 47000
    flops, nbytes = opcount_sparse.selected_attention_cost(chunk_pairs, ctx, 4 * 2048, 32, 128, entry, 2)
    assert opcount.min_seconds(flops, nbytes, PEAKS)[1] == "flops" and nbytes < chunk_pairs * entry // 64
    assert opcount_sparse.index_cost(10, 16, 128, 256) == (2 * 10 * 16 * 128, 2560)


def _planes(counts=True, paged_ms=(40, 10), lightning_ms=(6, 4)):
    """A 2,048-token chunk of one row beside 7 one-token rows, and a decode
    call of 8 rows x 32 steps, each span with its counts (or, the parent's
    program, without); the paged and the lightning kernels' lines."""
    a = ("attn_pairs=33554432,attn_ctx_tokens=220000,kv_entry_bytes=1024,attn_blocks_visible=12000000,"
         "attn_blocks_selected=1048576,attn_blocks_read=9000000,state_rows=8,lin_tokens=24660,state_slots_live=8,"
         "state_slots_total=8,") if counts else ""
    b = ("attn_pairs=4194304,attn_ctx_tokens=4194304,kv_entry_bytes=1024,attn_blocks_visible=1500000,"
         "attn_blocks_selected=131072,attn_blocks_read=190000,state_rows=256,lin_tokens=3072,state_slots_live=8,"
         "state_slots_total=8,") if counts else ""
    return {
        "/device:TPU:0": {"XLA Ops": [("%paged_attn_q_tiled.1 = bf16[25,4096,128] custom-call()", 0, paged_ms[0] * MS),
                                      ("%lightning_chunk_scan.1 = f32[25,32,128,128] custom-call()", 50 * MS, lightning_ms[0] * MS),
                                      ("%fusion.7 = bf16[2048,4096] fusion()", 60 * MS, 35 * MS),
                                      ("%sort.50 = (f32[3,128,2,1034], s32[3,128,2,1034]) sort()", 95 * MS, 5 * MS),
                                      ("%paged_attn_kv_split.2 = bf16[8,32,128] custom-call()", 130 * MS, paged_ms[1] * MS),
                                      ("%lightning_recurrent_step.3 = f32[8,4,8,128] custom-call()", 145 * MS,
                                       lightning_ms[1] * MS)]},
        "/host:CPU": {"driver": [(f"dstpu/serving/prefill#rows=8,{a}tokens=2055,steps=1#", 0, 125 * MS),
                                 (f"dstpu/serving/decode#rows=8,{b}tokens=256,steps=32#", 128 * MS, 60 * MS)]},
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


def test_the_new_metrics_read_from_the_spans_counts_and_the_traced_kernel_time(tmp_path):
    ctx = _ctx(tmp_path, _planes(), _config())
    assert _read(ctx, _metric("sparse_blocks_read_share.tput")) == pytest.approx(100.0 * 9_190_000 / 13_500_000)
    assert _read(ctx, _metric("lightning_time_share.tput")) == pytest.approx(100.0 * 10 / 100)
    least = sum(opcount.min_seconds(*opcount_lightning.recurrence_cost(rows * 12, tokens, 32, 128, 128, 2), PEAKS)[0]
                for rows, tokens in ((8, 24660), (256, 3072)))
    assert _read(ctx, _metric("lightning_roofline_share.tput")) == pytest.approx(100.0 * least / 0.010)
    least = sum(opcount.min_seconds(*opcount_sparse.selected_attention_cost(pairs, ctx_tokens, tokens * 4, 32, 128, 1024, 2),
                                    PEAKS)[0]
                for pairs, ctx_tokens, tokens in ((33554432, 220000, 2055), (4194304, 4194304, 256)))
    value = _read(ctx, _metric("sparse_attn_roofline_share.tput"))
    assert value == pytest.approx(100.0 * least / 0.050) and 0 < value < 100
    # the indexer is XLA's and has no name of its own on the device's line but its top-k's ``sort``
    assert _read(ctx, _metric("index_sort_time_share.tput")) == pytest.approx(100.0 * 5 / 100)


@pytest.mark.parametrize("config_file,counts", [(None, False), ({"hidden_size": 4096, "num_hidden_layers": 2}, True)])
def test_the_readers_read_nothing_without_counts_or_such_layers(tmp_path, config_file, counts):
    """The parent's program has no such counts, another configuration no such
    layers: the readers return nothing and do not raise."""
    ctx = _ctx(tmp_path, _planes(counts), config_file or _config())
    for name in ("sparse_attn_roofline_share.tput", "lightning_roofline_share.tput"):
        assert _read(ctx, _metric(name)) is None, name
        assert _read({**ctx, "reduced": None}, _metric(name)) is None
    if not counts:
        assert _read(ctx, _metric("sparse_blocks_read_share.tput")) is None


LIMITS = ("rel_l2_tol", "quantile_tol", "select_margin", "select_share", "select_share_all", "state_tol", "pooled_tol",
          "rule_tol", "attn_tol")
CONTROLS = ("mask_ignored", "block_shifted", "stale_pooled", "state_bf16", "padding_touches", "top_half", "window_not_forced",
            "one_head", "no_lightning_rope", "branch_cut_depth")


@pytest.mark.parametrize("limit", LIMITS)
def test_each_limit_of_the_check_stands_in_both_files_with_its_reason(limit):
    check, twin = _config()["check"], _config("tiny-minicpm-sala")["check"]
    assert limit in check and limit in twin
    assert limit in check["why"]   # the reading on both sides of it is beside its name there


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_one_the_runner_runs_and_the_file_says_what_catches_it(control):
    builder = loader.load_module("builders", "serve_sparse", loader.ROOT)
    assert control in builder.PROGRAM_CONTROLS + tuple(builder.REFERENCE_CONTROLS)
    assert control in _config()["check"]["why"].split("Every control NOT correct:")[1]
