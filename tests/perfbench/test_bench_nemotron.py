"""What PR 51 added to the benchmark for NVIDIA-Nemotron-3-Nano-30B-A3B, a
model whose layers are ONE branch each (a Mamba-2 mixer, a relu-squared expert
layer or no-rope GQA) and whose sequences hold a state-space state beside the
paged K/V cache: the configuration file against the catalog row key by key,
what it states of its cut, the arithmetic of the cut recomputed from the file
and from the program's own tree, the traffic letter for letter, the
manifest's own entries BY NAME (never by place or count), the count function
by hand, the new metrics read from hand-made spans and kernel lines (and
nothing from a program without the counts), and the twin's command on the CPU
with the spans its metrics read (the twin joins ``test_bench_rehearsal.py``'s
cases by being a file)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark.lib import loader, opcount, opcount_mamba, traffic, xplane, xplane_write

MS = 1_000_000  # ns
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CELL, CONFIG, TWIN = "nemotron-3-nano-30b-a3b.decode-heavy-256", "nemotron-3-nano-30b-a3b", "tiny-nemotron.decode-heavy-256"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the catalog row's ``config``, as https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json has it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": (13, 52), "n_routed_experts": (64, 128)}
OWN = {"mamba_time_share.tput": ("lower", "kernel_time_share"), "mamba_roofline_share.tput": ("higher", "mamba_roofline_share")}
KERNELS = ["mamba2_recurrent_step", "mamba2_chunk_scan"]


def _config(name=CONFIG):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", name + ".json"))


def _metric(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json"))


def test_the_published_keys_here_are_the_catalog_rows():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog in this container")
    (row, ) = [r for r in map(json.loads, open(CATALOG)) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    assert row["config"] == PUBLISHED and row["source_url"] == _config()["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_holds_each_published_key(key):
    cf = _config()
    if key in REDUCED:
        here, published = REDUCED[key]
        assert cf[key] == here and cf[key + "_published"] == published == PUBLISHED[key] and key in cf["reduced"]
    else:
        assert cf[key] == PUBLISHED[key] and key not in cf["reduced"]


def test_the_cut_the_deployment_and_what_is_assumed_are_stated():
    cf = _config()
    assert cf["reduced"] == sorted(REDUCED, reverse=True) == ["num_hidden_layers", "n_routed_experts"]
    letters = cf["hybrid_override_pattern"][:cf["num_hidden_layers"]]
    assert letters == "MEMEM*EMEMEM*" and [letters.count(c) for c in "ME*"] == [6, 5, 2]
    assert [PATTERN.count(c) for c in "ME*"] == [23, 23, 6], "the published 23 : 23 : 6 is 5.75 : 5.75 : 1.5 a stage of four"
    assert (cf["first_expert"], cf["chips_sharing_a_layer"]) == (0, 2) and cf["hidden_act"] == "relu2" == cf["mlp_hidden_act"]
    # the floors of the model-configs guide's section 4: the longest period, four layers, eight experts, the vocabulary
    period = max(len(run) + 1 for run in PATTERN.split("*")[:-1])   # (the nine letters after the last star end the model)
    assert cf["num_hidden_layers"] >= period == 9 and cf["n_routed_experts"] >= 8 and cf["vocab_size"] == 131072
    for assumed in ("no_rope", "layer_is_one_branch", "mamba_projection", "mamba_conv", "mamba_scan", "gated_norm", "router",
                    "experts", "hidden_act", "share_held", "depth", "vocabulary", "caches", "weights", "eos", "unused_keys"):
        assert assumed in cf["assumed"], assumed
    for words in ("arXiv:2504.03624", "no rotary table", "rope_theta"):
        assert words in cf["assumed"]["no_rope"], words
    assert "FLOAT32" in cf["assumed"]["mamba_scan"] and "bf16" in cf["assumed"]["mamba_conv"]
    assert "1e-20" in cf["assumed"]["router"] and "12 tokens" in cf["assumed"]["share_held"]
    assert "moe_roofline_share" in cf["assumed"]["hidden_act"]
    assert "13 layers of 52 a chip make the host's share of a step larger" in cf["deployment"]
    assert "four pipeline stages" in cf["deployment"] and "TWO chips" in cf["deployment"]
    eng = cf["engine"]
    assert (eng["kv_block_size"], eng["num_kv_blocks"], eng["kv_memory_fraction"]) == (128, "auto", 0.85)
    assert (eng["max_tracked_sequences"], eng["max_ragged_batch_size"], eng["max_ragged_sequence_count"]) == (256, 1024, 256)
    assert eng["token_buckets"][-1] == 1024 and eng["seq_buckets"][-1] == 256 and eng["max_context"] >= 2048 + 128
    assert (cf["builder"], cf["reference"], cf["family"]) == ("serve_mamba", "nemotron_reference", "nemotron_config")
    assert len(cf["check"]["state_tol"]) == 6


def test_the_bytes_of_the_cut_recomputed_from_the_file_and_from_the_programs_tree():
    """ISSUE 51's arithmetic: a Mamba layer 38.74M, an attention layer 23.40M,
    an expert 9.978M, an expert layer here 658.9M (whole 1,297.5M), embedding
    and head 704.6M: 4,278M parameters, 8.56 GB in bf16 (the whole model
    31.58B); 2,048 bytes of K and V a token; 12.80 MB of state a sequence."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.model import model_config
    from deepspeed_tpu.models import TransformerLM

    cf = _config()
    h, v = cf["hidden_size"], cf["vocab_size"]
    inner = cf["mamba_num_heads"] * cf["mamba_head_dim"]
    conv = inner + 2 * cf["n_groups"] * cf["ssm_state_size"]
    assert (inner, conv) == (4096, 6144)
    w_in = h * (inner + conv + cf["mamba_num_heads"])
    mamba = w_in + conv * cf["conv_kernel"] + conv + 3 * cf["mamba_num_heads"] + inner + inner * h + h
    assert round(w_in / 1e6, 2) == 27.70 and round(inner * h / 1e6, 2) == 11.01 and round(mamba / 1e6, 2) == 38.74
    q = h * cf["num_attention_heads"] * cf["head_dim"]
    kv = h * cf["num_key_value_heads"] * cf["head_dim"]
    attention = 2 * q + 2 * kv + h
    assert round(q / 1e6, 2) == 11.01 and round(kv / 1e6, 2) == 0.69 and round(attention / 1e6, 2) == 23.40
    expert = 2 * h * cf["moe_intermediate_size"]
    shared, router = 2 * h * cf["moe_shared_expert_intermediate_size"], h * cf["n_routed_experts_published"]
    assert round(expert / 1e6, 3) == 9.978 and round(shared / 1e6, 2) == 19.96 and round(router / 1e6, 2) == 0.34
    expert_layer = lambda held: held * expert + shared + router + cf["n_routed_experts_published"] + h
    assert round(expert_layer(128) / 1e6, 1) == 1297.5 and round(expert_layer(64) / 1e6, 1) == 658.9
    head = 2 * v * h
    assert round(head / 1e6, 1) == 704.6
    whole = 23 * mamba + 23 * expert_layer(128) + 6 * attention + head + h
    assert round(whole / 1e9, 2) == 31.58 and round(2 * whole / 1e9) == 63
    here = 6 * mamba + 5 * expert_layer(64) + 2 * attention + head + h
    assert round(here / 1e6) == 4278 and round(2 * here / 1e9, 2) == 8.56
    cfg = model_config(cf, jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, None), jax.random.PRNGKey(0))
    # an expert's matrices are STORED at whole lane tiles, 1,856 -> 1,920 (``TransformerConfig.expert_rows``: the chip's
    # tiled memory holds such a row as 1,920 whatever its shape says): 64 zero hidden units an expert, no parameter
    assert (cfg.expert_size, cfg.expert_rows) == (1856, 1920)
    padding = 5 * 64 * 2 * h * (cfg.expert_rows - cfg.expert_size)
    held = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert held - padding == here and round(padding / 1e6) == 110, "the program's tree is the file's arithmetic"
    assert shapes["blocks"]["moe_wi"].shape == (5, 64, 2688, 1920) and shapes["blocks"]["moe_wo"].shape == (5, 64, 1920, 2688)
    assert cfg.kv_layers == (5, 12) and cfg.state_layers == (0, 2, 4, 7, 9, 11) and cfg.expert_layers == (1, 3, 6, 8, 10)
    assert (cfg.experts_held, cfg.moe_num_experts, cfg.moe_first_expert, cfg.mlp) == (64, 128, 0, "relu2")
    assert cfg.kv_entry == ((2, 128), (2, 128)) and cfg.state_entry == ((64, 64, 128), (3, 6144))
    token = len(cfg.kv_layers) * 2 * 2 * 128 * 2
    state, tail = 64 * 64 * 128 * 4, 3 * 6144 * 2
    assert token == 2048 and (state, tail) == (2_097_152, 36_864)
    assert round(6 * (state + tail) / 1e6, 2) == 12.80 and round(256 * 6 * (state + tail) / 1e9, 2) == 3.28
    assert round(256 * 2044 * token / 1e9, 2) == 1.07
    assert round((2 * here + 256 * 6 * (state + tail) + 256 * 2044 * token) / 1e9, 1) == 12.9


def test_the_traffic_is_decode_heavy_128_with_twice_the_rows_and_twice_the_budget():
    mix = loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", "decode-heavy-256.json"))
    half = loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", "decode-heavy-128.json"))
    assert {k: mix[k] for k in ("driver", "clients", "count", "cycle_seconds", "trace_seconds", "order_seed", "start")} == \
        {"driver": "closed_loop", "clients": 256, "count": 256, "cycle_seconds": 10, "trace_seconds": 10, "order_seed": 23, "start": 28}
    assert mix["prompt_tokens"] == {"kind": "loguniform", "lo": 256, "hi": 1024} == half["prompt_tokens"]
    assert mix["output_tokens"] == {"kind": "uniform", "lo": 512, "hi": 1024} == half["output_tokens"]
    assert mix["gateway"] == {"token_budget": 1024, "max_inflight_per_replica": 256}
    assert half["gateway"] == {"token_budget": 512, "max_inflight_per_replica": 128}
    changed = {k for k in mix if mix[k] != half.get(k)}
    assert changed == {"clients", "count", "gateway", "why"}, "twice the rows, twice the budget and nothing else"
    cycle = traffic.make_cycle(mix)
    prompts, outputs = sorted(r["prompt_len"] for r in cycle), sorted(r["max_new_tokens"] for r in cycle)
    assert len(cycle) == 256 and 256 <= prompts[0] and prompts[-1] <= 1024 and round(sum(prompts) / 256) == 554
    assert 512 <= outputs[0] and outputs[-1] <= 1024 and round(sum(outputs) / 256) == 768
    assert max(p + o for p, o in zip(prompts[::-1], outputs[::-1])) <= _config()["engine"]["max_context"]


def test_the_manifests_own_entries_by_name():
    manifest = loader.load_manifest()
    (config, ) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == _config()["reduced"] and config["source"] == _config()["source"]
    assert config["file"] == "benchmark/configs/nemotron-3-nano-30b-a3b.json" and len(config["why"]) <= 200
    (cell, ) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "decode-heavy-256", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "12 tokens" in cell["why"] and "256 rows" in cell["why"]
    assert [w["name"] for w in manifest["workloads"] if w["config"] == CONFIG] == [CELL], "one cell, no second"
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert set(OWN) <= listed and "state_slot_occupancy.tput" in listed and "moe_roofline_share.tput" in listed
    for name, (better, reader) in OWN.items():
        (entry, ) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert entry == {"name": name, "unit": "%", "better": better, "source": "device_trace", "layer": "Kernels: state-space scan",
                         "moves": "serve_tokens_per_s", "workloads": entry["workloads"]} and CELL in entry["workloads"]
        metric = _metric(name)
        assert {k: metric[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
            {k: entry[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
        assert metric["reader"] == reader and metric["args"]["kernels"] == KERNELS
    # not where the reader would count another family's work
    for name in ("kda_time_share.tput", "kda_roofline_share.tput", "lightning_time_share.tput", "lightning_roofline_share.tput",
                 "mla_roofline_share.tput", "sparse_attn_roofline_share.tput"):
        (other, ) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL not in other["workloads"], name
    assert "serve_tokens_per_s" in [m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", ())]
    resolved = loader.resolve_cell(CELL)
    assert {"serve_tokens_per_s", "setup_s"} == {m["name"] for m in resolved["end_to_end"]}
    assert set(OWN) | {"compiles_in_window"} <= {m["name"] for m in resolved["layer_metrics"]}
    twin = loader.resolve_cell(TWIN, rehearsal=True)
    assert (twin["config"], twin["traffic"], twin["chips"]) == ("tiny-nemotron", "decode-heavy-256-tiny", 1)
    assert twin["config_file"]["builder"] == "serve_mamba" and twin["traffic_file"]["driver"] == "closed_loop"
    assert twin["config_file"]["reference"] == _config()["reference"] and twin["config_file"]["fields"] == _config()["fields"]
    assert twin["config_file"]["hybrid_override_pattern"] == PATTERN and twin["config_file"]["reduced"] == []


@pytest.mark.parametrize("row_calls,tokens,want_flops,want_bytes", [
    (1, 1, 64 * 4 * 64 * 128, 2 * 64 * 64 * 128 * 4 + (2 * 4096 + 2 * 1024) * 2 + 64 * 4),
    (6 * 256, 6 * 256, 1536 * 64 * 4 * 8192, 1536 * (4_194_304 + 20_480 + 256)),
    (6, 6 * 770, 4620 * 64 * 4 * 8192, 6 * 4_194_304 + 4620 * (20_480 + 256)),
])
def test_selective_scan_cost_counts_by_hand(row_calls, tokens, want_flops, want_bytes):
    """One token of one row in one layer; a decode step of 256 rows over 6
    layers (6.44 GB of state read and written: the issue's count); a
    770-token chunk of one row over 6 layers, whose state moves once a
    layer."""
    assert opcount_mamba.selective_scan_cost(row_calls, tokens, 64, 64, 128, 8, 2) == (want_flops, want_bytes)
    assert opcount.min_seconds(want_flops, want_bytes, PEAKS)[1] == "bytes", "the recurrent form is bound by memory in every call"
    if row_calls == 1536:
        assert round(1536 * 4_194_304 / 1e9, 2) == 6.44


def _planes(counts=True, mamba_ms=(30, 160)):
    """A 770-token chunk beside 254 one-token rows, and a decode call of 256
    rows x 8 steps, each span with its counts (or, the parent's program,
    without); the two kernels' lines among others."""
    a = "mamba_row_calls=1530,mamba_tokens=6144,state_rows=255,lin_tokens=6144,state_slots_live=256,state_slots_total=256," if counts else ""
    b = "mamba_row_calls=12288,mamba_tokens=12288,state_rows=2048,lin_tokens=12288,state_slots_live=256,state_slots_total=256," if counts else ""
    return {
        "/device:TPU:0": {"XLA Ops": [("%mamba2_chunk_scan.1 = f32[4,64,128,64] custom-call()", 0, mamba_ms[0] * MS),
                                      ("%fusion.7 = bf16[1024,2688] fusion()", 30 * MS, 30 * MS),
                                      ("%moe_gmm.2 = bf16[2048,1920] custom-call()", 60 * MS, 20 * MS),
                                      ("%mamba2_recurrent_step.3 = f32[256,8,64,128] custom-call()", 80 * MS, mamba_ms[1] * MS),
                                      ("%fusion.9 = bf16[256,2688] fusion()", 240 * MS, 24 * MS)]},
        "/host:CPU": {"driver": [(f"dstpu/serving/prefill#rows=255,{a}tokens=1024,steps=1#", 0, 78 * MS),
                                 (f"dstpu/serving/decode#rows=256,{b}tokens=2048,steps=8#", 79 * MS, 190 * MS)]},
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


def test_the_two_metrics_read_from_the_spans_counts_and_the_traced_kernel_time(tmp_path):
    ctx = _ctx(tmp_path, _planes(), _config())
    assert _read(ctx, _metric("mamba_time_share.tput")) == pytest.approx(100.0 * 190 / 264)
    least = sum(opcount.min_seconds(*opcount_mamba.selective_scan_cost(calls, tokens, 64, 64, 128, 8, 2), PEAKS)[0]
                for calls, tokens in ((1530, 6144), (12288, 12288)))
    value = _read(ctx, _metric("mamba_roofline_share.tput"))
    assert value == pytest.approx(100.0 * least / 0.190) and 30 < value < 45
    # a kernel twice as fast reads twice the share: the work counted is the spans', not the kernel's
    faster = _read(_ctx(tmp_path / "b", _planes(mamba_ms=(15, 80)), _config()), _metric("mamba_roofline_share.tput"))
    assert faster == pytest.approx(100.0 * least / 0.095) and faster <= 100


@pytest.mark.parametrize("config_file,counts", [(None, False), ({"hidden_size": 4096, "num_hidden_layers": 2}, True)])
def test_the_reader_reads_nothing_without_counts_or_such_layers(tmp_path, config_file, counts):
    """The parent's program has no such counts, another configuration no such
    layers: the reader returns nothing and does not raise."""
    ctx = _ctx(tmp_path, _planes(counts), config_file or _config())
    assert _read(ctx, _metric("mamba_roofline_share.tput")) is None
    assert _read({**ctx, "reduced": None}, _metric("mamba_roofline_share.tput")) is None


LIMITS = ("rel_l2_tol", "quantile_tol", "state_tol", "rule_tol")
CONTROLS = ("no_dt_bias", "no_D_skip", "one_norm_group", "group_of_head_wrong", "relu", "route_scale_1", "no_selection_bias",
            "state_bf16", "no_tail", "padding_touches", "products_default")


@pytest.mark.parametrize("limit", LIMITS)
def test_each_limit_of_the_check_stands_in_both_files_with_its_reason(limit):
    check, twin = _config()["check"], _config("tiny-nemotron")["check"]
    assert limit in check and limit in twin
    assert limit in check["why"]   # the reading on both sides of it is beside its name there


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_one_the_runner_runs_and_the_file_says_what_it_read(control):
    builder = loader.load_module("builders", "serve_mamba", loader.ROOT)
    state = builder._state_module(loader.ROOT)
    assert control in state.PROGRAM_CONTROLS + state.RULE_CONTROLS + tuple(state.REFERENCE_CONTROLS)
    assert control in _config()["check"]["why"].split("Controls")[1]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The twin's command on the CPU with the JSONL bus on: its result line and its spans."""
    tmp = tmp_path_factory.mktemp("nemotron")
    log = tmp / "spans.jsonl"
    code = ("import sys, runpy; sys.argv = ['run.py'] + sys.argv[1:]\n"
            "from deepspeed_tpu.monitor.trace import configure_tracer\n"
            f"configure_tracer(enabled=True, path={str(log)!r})\n"
            f"runpy.run_path({os.path.join(loader.ROOT, 'benchmark', 'run.py')!r}, run_name='__main__')\n")
    # not the checkout's own compile cache: another worker's test watches that directory
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache")}
    env.pop("BENCH_RUN", None)
    out = subprocess.run([sys.executable, "-c", code, "--workload", TWIN, "--seed", "3000000177", "--seconds", "2",
                          "--trace", "0", "--rehearsal"], cwd=loader.ROOT, env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    spans = [json.loads(line) for line in log.read_text().splitlines() if '"serving/' in line]
    return json.loads(out.stdout.strip().splitlines()[-1]), [s for s in spans if s.get("ph") == "X"]


def test_the_rehearsal_twin_runs_the_cells_command_and_is_correct(rehearsed):
    line, _ = rehearsed
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {} and line["rehearsal"] is True
    check = line["check"]
    assert check["ok"] and check["rel_l2_max"] < 1e-4 and max(check["state_rel_l2"]) < 1e-4 and len(check["state_rel_l2"]) == 6
    assert check["rule_rel_l2_max"] < 1e-5 and check["rows"] == 8 and check["horizon_argmax_share"] == 1.0
    assert line["counts"]["compiles_in_window"] == 0


def test_the_twins_spans_carry_what_the_two_metrics_and_the_joined_ones_read(rehearsed):
    _, spans = rehearsed
    steps = [s for s in spans if s["name"] in ("serving/prefill", "serving/decode", "serving/decode_step")]
    assert {s["name"] for s in steps} >= {"serving/prefill", "serving/decode"}, "the twin prefills and decodes"
    for s in steps:
        a = s["args"]
        horizon = s["name"] == "serving/decode"
        # six Mamba layers: a row's state read and written once a call (a horizon's step is a call) a layer
        assert a["mamba_row_calls"] == 6 * a["state_rows"] == 6 * a["rows"] * (a["steps"] if horizon else 1)
        assert a["mamba_tokens"] == a["lin_tokens"] == 6 * a["tokens"]
        assert 0 < a["state_slots_live"] <= a["state_slots_total"] == 8
        assert a["kernel"].endswith("mamba2_recurrent_step:1:one-token-rows")
        assert ("mamba2_chunk_scan:128:ragged" in a["kernel"]) == (not horizon)
    # the experts' counts over the FIVE expert layers, a share of 8 of 16 held (on the spans whose call fetched them)
    counted = [s["args"] for s in steps if "moe_slots_routed" in s["args"]]
    assert counted, "some step fetched the program's expert counts"
    for a in counted:
        assert a["moe_slots_routed"] == a["tokens"] * 2 * 5 and a["experts_total"] == 8 * 5 * a["steps"]
        assert 0 <= a["moe_slots"] <= a["moe_slots_routed"] and (a["experts_held"], a["experts_published"]) == (8, 16)
