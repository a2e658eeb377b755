"""What PR 33 added to the benchmark for SDAR-30B-A3B-Chat, a model generated
four tokens a block by masked diffusion: the manifest's additions against the
parent's manifest, the configuration file against the published
``config.json`` key by key and the bytes its cut comes to, the traffic's
parameters, the rehearsal twin's command with its spans, the step log's
tokens against the streams', and the metrics that wait for a ``benchmark``
PR, read from hand-made spans by readers that are there."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import loader, rates, xplane, xplane_write

MS = 1_000_000  # ns
CELL = "sdar-30b-a3b-chat.block-diffusion-64"
TWIN = "tiny-sdar.block-diffusion-64"
# the language model's settings as https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json has them
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
JOINED = ["device_idle_share.tput", "peak_hbm_bytes.tput", "host_gap_sched_share.tput", "host_gap_engine_share.tput",
          "decode_step_p50_ms.tput", "decode_batch_mean.tput", "decode_row_occupancy.tput",
          "decode_rows_mixed_share.tput", "decode_horizon_mean.tput", "paged_decode_time_share.tput",
          "paged_prefill_time_share", "moe_time_share.tput", "moe_roofline_share.tput", "moe_row_occupancy.tput",
          "moe_experts_hit_share.tput"]


def _config(name="sdar-30b-a3b-chat"):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", name + ".json"))


def _is_extension(old, new, path="manifest"):
    """``new`` holds everything ``old`` holds, in the old order, and adds only
    at the ends of lists."""
    if isinstance(old, dict):
        assert isinstance(new, dict) and list(new)[:len(old)] == list(old), path
        for key in old:
            _is_extension(old[key], new[key], f"{path}.{key}")
    elif isinstance(old, list):
        assert isinstance(new, list) and len(new) >= len(old), path
        for i, item in enumerate(old):
            _is_extension(item, new[i], f"{path}[{i}]")
    else:
        assert old == new, path


def test_the_manifests_additions_are_appended_and_nothing_moved():
    """Against the manifest of the commit this tree stands on (on a later
    commit that is this manifest or a further extension of it)."""
    manifest = loader.load_manifest()
    shown = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=loader.ROOT, capture_output=True, text=True)
    if shown.returncode == 0:
        parent = json.loads(shown.stdout)
        _is_extension({k: v for k, v in parent.items() if k != "per_layer"},
                      {k: v for k, v in manifest.items() if k != "per_layer"})
        assert [m["name"] for m in manifest["per_layer"]][:len(parent["per_layer"])] == \
            [m["name"] for m in parent["per_layer"]], "no per-layer entry added in the middle"
        for old, new in zip(parent["per_layer"], manifest["per_layer"]):
            _is_extension(old, new, old["name"])
    (config, ) = [c for c in manifest["configs"] if c["name"] == "sdar-30b-a3b-chat"]
    assert config["reduced"] == ["num_hidden_layers"] == _config()["reduced"] and config["source"] == _config()["source"]
    (cell, ) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "sdar-30b-a3b-chat", "traffic": "block-diffusion-64", "chips": 1,
                    "why": cell["why"]}
    assert "1 token a request high" in cell["why"], "the known over-count is said where the cell is"
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert set(JOINED) <= listed, "a later PR may list the cell under more"
    # not where the reader would count other work than the kernel did, nor where the cells are pinned
    for name in ("decode_kv_live_share.tput", "paged_decode_roofline_share.tput", "paged_prefill_roofline_share",
                 "paged_roofline_share_by_layer.tput"):
        (entry, ) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"], name
    assert "serve_tokens_per_s" in [m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", ())]
    resolved = loader.resolve_cell(CELL)
    assert {"serve_tokens_per_s", "setup_s"} <= {m["name"] for m in resolved["end_to_end"]}
    assert set(JOINED + ["compiles_in_window"]) <= {m["name"] for m in resolved["layer_metrics"]}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_file_holds_each_published_key(key):
    cf = _config()
    if key == "num_hidden_layers":
        assert cf[key] == 8 and cf["reduced"] == [key], "the cut: depth only"
    else:
        assert cf[key] == PUBLISHED[key] and key not in cf["reduced"]


def test_the_cut_the_deployment_and_what_is_assumed_are_stated():
    cf = _config()
    assert cf["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    for assumed in ("depth", "block_length", "mask_token_id", "denoising_steps", "remasking", "no_shift",
                    "block_alignment", "qk_norm", "intermediate_size", "weights", "eos"):
        assert assumed in cf["assumed"], assumed
    for words in ("all 128 experts", "the whole vocabulary", "pipeline stages"):
        assert words in cf["deployment"], words
    assert (cf["builder"], cf["reference"], cf["family"], cf["family_size"]) == \
        ("serve_diffusion", "sdar_reference", "sdar_config", "30b-a3b")
    assert cf["overrides"] == {"diffusion_block_size": 4, "mask_token_id": 151669}
    ec = cf["engine"]
    assert ec["generation"] == {"block_length": 4, "denoising_steps": 4, "remasking": "low_confidence_static",
                                "confidence_threshold": 0.9, "temperature": 0.0}
    # a chat deployment's context, not this mix's longest request (2,048): why is said under `assumed`
    assert 2048 < ec["max_context"] <= cf["max_position_embeddings"] and "max_context" in cf["assumed"]
    assert ec["max_ragged_sequence_count"] == _mix("block-diffusion-64")["gateway"]["max_inflight_per_replica"]
    ck = cf["check"]
    # the check fills the rows the window keeps in flight and probes four of them, one for each P mod 4
    assert [n % 4 for n in ck["prompt_tokens"]] == [3, 2, 1, 0] and max(ck["prompt_tokens"]) == 1023
    assert sorted(r % 4 for r in ck["probe_rows"]) == [0, 1, 2, 3] and max(ck["probe_rows"]) == 63
    assert ck["blocks"] % ck["blocks_a_call"] == 0
    assert len(ck["probe_rows"]) * ck["blocks"] == 64, "64 blocks are held to the reference"
    assert ck["blocks_a_call"] * 4 in (4, 8, 16, 32), "a call of the check is one of the window's horizons"
    for control in ("int8 KV", "causal mask", "commit left out", "q/k norm left out"):
        assert control in ck["why"], control


def test_the_bytes_of_the_cut_recomputed_from_the_file():
    """ISSUE 33's table: attention 18.87M (q and o 8.39M each, k and v 1.05M
    each), router 0.26M, experts 603.98M; a layer 623.1M, 1.246 GB; embedding
    and head 622.3M, 1.245 GB; 8 layers 5.607B, 11.21 GB; 48 layers 30.5B, 61 GB;
    KV 2,048 bytes a token a layer, 16 KiB a token."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.model import model_config
    from deepspeed_tpu.models import TransformerLM

    cf = _config()
    h, d, nq, nkv = cf["hidden_size"], cf["head_dim"], cf["num_attention_heads"], cf["num_key_value_heads"]
    qo, kv = h * nq * d, h * nkv * d
    attention, router = 2 * qo + 2 * kv, h * cf["num_experts"]
    experts = cf["num_experts"] * 3 * h * cf["moe_intermediate_size"]
    norms = 2 * h + 2 * d
    layer = attention + router + experts + norms
    ends = 2 * cf["vocab_size"] * h
    assert (round(qo / 1e6, 2), round(kv / 1e6, 2), round(attention / 1e6, 2)) == (8.39, 1.05, 18.87)
    assert (round(router / 1e6, 2), round(experts / 1e6, 2), round(layer / 1e6, 1)) == (0.26, 603.98, 623.1)
    assert (round(2 * layer / 1e9, 3), round(ends / 1e6, 1), round(2 * ends / 1e9, 3)) == (1.246, 622.3, 1.245)
    total = cf["num_hidden_layers"] * layer + ends + h
    assert (round(total / 1e9, 3), round(2 * total / 1e9, 2)) == (5.607, 11.21)
    whole = PUBLISHED["num_hidden_layers"] * layer + ends + h
    assert (round(whole / 1e9, 1), round(2 * whole / 1e9)) == (30.5, 61)
    assert 2 * nkv * d * 2 == 2048 and 2048 * cf["num_hidden_layers"] == 16 * 1024
    # and the program's own parameter tree is that many
    model = TransformerLM(model_config(cf, jnp.bfloat16))
    shapes = jax.eval_shape(lambda k: model.init(k, None), jax.random.PRNGKey(0))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes)) == total
    for text in ("623.1M", "18.87M", "603.98M", "622.3M", "5.607B, 11.21 GB", "30.5B", "61 GB", "16 KiB a token"):
        assert text in cf["assumed"]["depth"], text


def _mix(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "traffic", name + ".json"))


def test_the_traffic_is_what_the_issue_names_letter_for_letter():
    mix = _mix
    new = mix("block-diffusion-64")
    assert {k: v for k, v in new.items() if k != "why"} == {
        "driver": "closed_loop", "clients": 64, "count": 64, "cycle_seconds": 10,
        "prompt_tokens": {"kind": "loguniform", "lo": 256, "hi": 1024},
        "output_tokens": {"kind": "uniform", "lo": 512, "hi": 1024},
        "gateway": {"token_budget": 512, "max_inflight_per_replica": 64},
        "trace_seconds": 10, "order_seed": 23, "start": 28}
    # the same requests as decode-heavy-64 offers: the generation is what differs
    old = mix("decode-heavy-64")
    assert {k: v for k, v in new.items() if k != "why"} == {k: v for k, v in old.items() if k != "why"}
    from benchmark.lib import traffic

    cycle = traffic.make_cycle(new)
    # the answers' lengths are multiples of 4 (516 + 8 i): an answer ends inside a block where its prompt did
    assert sum(r["prompt_len"] % 4 != 0 for r in cycle) > 32 and \
        sum((r["prompt_len"] + r["max_new_tokens"]) % 4 != 0 for r in cycle) > 32, \
        "most prompts and most answers end inside a block"
    twin = loader._read_json(os.path.join(loader.ROOT, "benchmark", "rehearsal", TWIN + ".json"))
    assert twin == {"config": "tiny-sdar", "traffic": "block-diffusion-64-tiny", "chips": 1}
    tiny = _config("tiny-sdar")
    assert tiny["engine"]["generation"] == _config()["engine"]["generation"] and tiny["reduced"] == []
    assert set(PUBLISHED) <= set(tiny) and tiny["fields"] == _config()["fields"]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The twin's command on the CPU with the JSONL bus on: its result line and its spans."""
    tmp = tmp_path_factory.mktemp("sdar")
    log = tmp / "spans.jsonl"
    code = ("import sys, runpy; sys.argv = ['run.py'] + sys.argv[1:]\n"
            "from deepspeed_tpu.monitor.trace import configure_tracer\n"
            f"configure_tracer(enabled=True, path={str(log)!r})\n"
            f"runpy.run_path({os.path.join(loader.ROOT, 'benchmark', 'run.py')!r}, run_name='__main__')\n")
    # not the checkout's own compile cache: another worker's test watches that directory
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp / "cache")}
    env.pop("BENCH_RUN", None)
    out = subprocess.run([sys.executable, "-c", code, "--workload", TWIN, "--seed", "2600000077", "--seconds", "2",
                          "--trace", "0", "--rehearsal"], cwd=loader.ROOT, env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    spans = [json.loads(line) for line in log.read_text().splitlines() if '"serving/' in line]
    return json.loads(out.stdout.strip().splitlines()[-1]), [s for s in spans if s.get("ph") == "X"]


def test_the_rehearsal_twin_runs_the_cells_command_and_is_correct(rehearsed):
    line, _ = rehearsed
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 16
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {} and line["rehearsal"] is True
    check = line["check"]
    assert check["ok"] and check["rel_l2_max"] < 1e-4 and check["rel_l2_centred_low"] < 1e-4
    # all 8 rows advance together: a block takes the forwards of the row with most masks (one row's prompt
    # ends on a block, so 4), and each of the 4 probed rows is held to the reference at every one of them
    assert (check["rows"], check["probe_rows"]) == (8, [0, 1, 6, 7])
    assert check["forwards"] == 4 * 8 * 4 and len(check["rel_l2"]) == 4 * check["forwards"]
    # the timed program (no probe) wrote the cache and gave the same tokens; every choice is the rule's
    assert check["timed_tokens_equal_share"] == 1.0 and check["unmask_own_share"] == 1.0 == check["unmask_ref_share"]
    assert check["unmask_choices"] > 64
    assert line["counts"]["compiles_in_window"] == 0


def test_the_twins_spans_carry_what_the_listed_and_the_waiting_metrics_read(rehearsed):
    _, spans = rehearsed
    bursts = [s["args"] for s in spans if s["name"] == "serving/decode"]
    assert bursts, "the twin decodes"
    for a in bursts:
        assert a["block_size"] == 4 and a["blocks"] in (1, 2, 4, 8) and a["commit_forwards"] == a["blocks"]
        assert a["steps"] == a["denoise_forwards"] + a["commit_forwards"] <= 5 * a["blocks"]
        assert a["tokens_fed"] == a["rows"] * 4 * a["steps"] and 0 < a["masked_fed"] < a["tokens_fed"]
        assert a["tokens"] == a["rows"] * 4 * a["blocks"] >= a["tokens_committed"] + a["tokens_dropped"]
        assert 0 < a["rows"] <= a["bucket_rows"] and a["bucket_tokens"] == 4 * a["bucket_rows"] and a["block_ms"] > 0
        # the experts' counts of the forwards the call made (the commits stop before the last layer's experts)
        layer_forwards = 3 * a["steps"] - a["commit_forwards"]
        assert a["experts_total"] == 8 * layer_forwards and a["moe_slots_routed"] == a["rows"] * 4 * 2 * layer_forwards
        assert 0 < a["moe_slots"] == a["moe_slots_routed"] <= a["moe_rows"] and 0 < a["experts_hit"] <= a["experts_total"]
    forwards, kept = sum(a["rows"] * a["steps"] for a in bursts), sum(a["tokens_committed"] for a in bursts)
    dropped, slots = sum(a["tokens_dropped"] for a in bursts), sum(a["tokens"] for a in bursts)
    # 5 forwards a block of 4 tokens a row, fewer where a prompt's tail opened the block: 1.25 a token of
    # a row, within the tails dropped and the open tokens, which were no new tokens
    assert 1.0 < forwards / kept <= 1.25 * slots / kept and dropped < kept
    kinds = {s["args"]["kind"] for s in spans if s["name"] == "serving/sched_step"}
    assert "diffuse" in kinds and "decode" not in kinds
    prefills = [s["args"] for s in spans if s["name"] == "serving/prefill"]
    assert prefills and all(a["tokens"] % 4 == 0 and a["rows_decode"] == 0 for a in prefills)
    # the prompt tokens that opened first blocks: fewer than a block a row, and no new tokens
    assert 0 < sum(a["open_tokens"] for a in bursts) and all(a["open_tokens"] <= 3 * a["rows"] for a in bursts)


def test_the_step_logs_output_tokens_are_the_streams_tokens_and_one_a_request():
    """The builder's step log through ``rates.classify_serving_steps``: a
    decode call counts what its rows kept, every prompt token is counted once
    (the open ones where they are handed over), and the one token that the
    classification credits to the put that completes a prompt is the whole of
    the over-count."""
    from benchmark.lib import common, serving, traffic

    cell = loader.resolve_cell(TWIN, rehearsal=True)
    builder = loader.load_module("builders", cell["config_file"]["builder"])
    phases = common.Phases(0.0)
    import jax

    system = builder.build(cell, 3, jax.devices()[:1], True, phases)
    try:
        requests = traffic.make_requests(cell["traffic_file"], 3, system.cfg.vocab_size, 2)[:10]
        records = [serving.submit(system, r, 0.0) for r in requests]
        records = [serving.finish(r) for r in records]
    finally:
        system.gateway.stop()
    assert all(r["ok"] for r in records) and system.check["ok"]
    prompt_len = {r["uid"]: r["prompt_len"] for r in records}
    tokens = rates.classify_serving_steps(system.steps, prompt_len)
    assert sum(p for p, _ in tokens) == sum(prompt_len.values())
    assert sum(o for _, o in tokens) == sum(r["n_out"] for r in records) + len(records)
    assert sum(r["n_out"] for r in records) == sum(r["max_new_tokens"] for r in records)
    decodes = [st for st in system.steps if st["kind"] == "decode"]
    assert all(0 < size <= st["n_steps"] for st in decodes for size in st["sizes"])
    assert any(size < st["n_steps"] for st in decodes for size in st["sizes"]), "some row stops inside a block"


# The metrics ISSUE 33 defines and the manifest does not list yet: a new ``per_layer`` entry has to be
# the last one and ``test_bench_kv_live.py`` holds ``decode_kv_live_share.tput`` there (PERF.md section
# 7). The spans carry what they read, so a ``benchmark`` PR adds each as this file of data and nothing
# else. ``span_arg_ratio`` reads percent: 125 is 1.25 forwards of a row a token it kept (``steps`` counts
# a call's forwards once, whatever its rows, so the numerator is ``rows`` x ``steps``).
WAITING = [
    {"name": "diffusion_forwards_per_token.tput", "layer": "Serving engine", "unit": "%", "better": "lower",
     "source": "program_counter", "moves": "serve_tokens_per_s", "reader": "span_arg_ratio",
     "args": {"numerator": [{"span": "serving/decode", "product": ["rows", "steps"]}],
              "denominator": [{"span": "serving/decode", "product": ["tokens_committed"]}]}},
    {"name": "diffusion_masked_share.tput", "layer": "Serving engine", "unit": "%", "better": "lower",
     "source": "program_counter", "moves": "serve_tokens_per_s", "reader": "span_arg_ratio",
     "args": {"numerator": [{"span": "serving/decode", "product": ["masked_fed"]}],
              "denominator": [{"span": "serving/decode", "product": ["tokens_fed"]}]}},
    # the issue asks for a p50; no reader that is there takes a percentile of a span's argument, so this is
    # the mean of ``block_ms`` (a call's host time over its blocks) until a benchmark PR brings that reader
    {"name": "diffusion_block_mean_ms.tput", "layer": "Serving engine", "unit": "ms", "better": "lower",
     "source": "host_clock", "moves": "serve_tokens_per_s", "reader": "span_arg_mean",
     "args": {"span": "serving/decode", "arg": "block_ms"}},
]


def _ctx(tmp_path, *decode_args):
    spans = [(f"dstpu/serving/decode#rows=64,{args}bucket_rows=64#", i * 900 * MS, 800 * MS)
             for i, args in enumerate(decode_args)]
    planes = {"/device:TPU:0": {"XLA Ops": [("%moe_gmm.1 = bf16[2048,768] custom-call()", 0, 12 * MS)]},
              "/host:CPU": {"driver": spans}}
    d = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xplane_write.encode_xspace(planes))
    return {"reduced": xplane.reduce_trace(xplane.read_trace(str(d / "host.xplane.pb"))), "peaks": None, "kind": "serve",
            "cell": {"root": str(tmp_path), "name": "cell", "config_file": {}}}


@pytest.mark.parametrize("metric", WAITING, ids=[m["name"] for m in WAITING])
def test_a_waiting_metric_is_read_from_the_spans_by_a_reader_that_is_there(metric, tmp_path):
    assert os.path.isfile(os.path.join(loader.ROOT, "benchmark", "readers", metric["reader"] + ".py"))
    assert metric["layer"] in {m["layer"] for m in loader.load_manifest()["per_layer"]}
    assert set(metric) == {"name", "layer", "unit", "better", "source", "moves", "reader", "args"}
    read = lambda ctx: loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric["args"]})
    # 8 blocks of 64 rows (40 forwards, 3 tokens dropped), and 2 blocks after prefill whose open tokens
    # (96 of them) saved 30 of the first block's forwards' masks and no forward
    ctx = _ctx(tmp_path, "steps=40,tokens_committed=2045,tokens_fed=10240,masked_fed=5120,block_ms=100.0,",
               "steps=10,tokens_committed=416,tokens_fed=2560,masked_fed=1184,block_ms=110.0,")
    want = {"diffusion_forwards_per_token.tput": 100.0 * 64 * 50 / 2461, "diffusion_masked_share.tput": 100.0 * 6304 / 12800,
            "diffusion_block_mean_ms.tput": 105.0}[metric["name"]]
    assert read(ctx) == pytest.approx(want)
    # the parent's program has no such arguments: nothing to read, nothing raised
    assert read(_ctx(tmp_path / "parent", "steps=8,", "steps=2,")) is None
    assert read({"reduced": None, "cell": {"root": str(tmp_path), "name": "none"}}) is None
