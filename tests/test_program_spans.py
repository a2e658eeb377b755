"""The one span primitive (``monitor/trace.py``) and the spans the serving
loop, the scheduler, the engine and ``train_batch`` open with it: under a
profiler session they are ``dstpu/<name>`` annotations in the xplane file,
properly nested on the thread that ran them, with their arguments; with the
JSONL bus on they are the same spans once each under their bus names; with
both off nothing is allocated. Every live span says its wall time and how
long its thread did not run; what an engine step does only because its span
is live runs under ``serving/engine_observe``, and nothing of the device's
comes to the host outside ``serving/engine_fetch``. Read back with the
benchmark's own reader (``benchmark/lib/program_spans.py``), the one the
per-layer metrics use."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmark.lib import program_spans  # noqa: E402
from deepspeed_tpu.models import TransformerConfig, TransformerLM  # noqa: E402
from deepspeed_tpu.monitor import trace as trace_mod  # noqa: E402
from deepspeed_tpu.monitor.metrics import get_metrics  # noqa: E402
from deepspeed_tpu.monitor.trace import NULL_SPAN, get_tracer  # noqa: E402
from deepspeed_tpu.ops.pallas import paged_attention as pa  # noqa: E402

from conftest import tiny_batch  # noqa: E402

ENGINE_CHILDREN = {"serving/engine_batch", "serving/engine_dispatch", "serving/engine_fetch",
                   "serving/engine_commit", "serving/engine_observe"}
ENGINE_STEPS = {"serving/prefill", "serving/decode_step", "serving/decode"}
SCHED_ARGS = {"kind", "rows", "rows_decode", "tokens", "prefill_tokens", "pending", "active",
              "budget_left", "first_wait_ms"}


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """The tracer is a process singleton and ``--dist loadfile`` puts several
    test files in one process: start and end every test from ``reset()``."""
    get_tracer().reset()
    yield
    get_tracer().reset()
    get_metrics().disable()
    get_metrics().reset()


def _engine(window=None):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                            intermediate_size=128, max_seq_len=128, dtype=jnp.float32,
                            attention_impl="reference", sliding_window=window)
    icfg = RaggedInferenceEngineConfig()
    icfg.use_pallas_kernels = "always"  # the paged kernel's module: off the TPU it takes its reference
    icfg.kv_block_size = 16
    icfg.num_kv_blocks = 32
    icfg.state_manager.max_tracked_sequences = 4
    icfg.state_manager.max_ragged_sequence_count = 4
    icfg.state_manager.max_ragged_batch_size = 32
    icfg.state_manager.max_context = 96
    return InferenceEngineV2(TransformerLM(cfg), icfg)


def _serve(engine, uid_base=0):
    """Three requests through a scheduler of its own: SplitFuse puts (a
    40-token prompt against a 32-token budget is chunked), then decode bursts."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    sched = DynamicSplitFuseScheduler(engine, token_budget=32)
    rng = np.random.default_rng(0)
    for i, (n_prompt, n_new) in enumerate(((40, 6), (12, 9), (5, 4))):
        sched.submit(uid_base + i, rng.integers(0, 128, size=n_prompt, dtype=np.int32), max_new_tokens=n_new)
    return sched.run()


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


@pytest.fixture(scope="module")
def engine():
    return _engine()


@pytest.fixture(scope="module")
def serve_trace(engine, tmp_path_factory):
    _serve(engine, uid_base=100)  # compile every program first: the traced run is warm
    return _profiled(tmp_path_factory.mktemp("serve_trace"), lambda: _serve(engine, uid_base=200))


def _children(trace, parent):
    return [s for s in trace["spans"] if s is not parent and s.line == parent.line
            and s.start_s >= parent.start_s and s.end_s <= parent.end_s]


def test_profiler_sees_sched_step_with_nested_children_on_one_thread(serve_trace):
    steps = program_spans.spans_named(serve_trace, "serving/sched_step")
    assert len(steps) >= 4
    assert len({s.line for s in serve_trace["spans"]}) == 1, "one thread ran them all"
    for step in steps:
        inside = _children(serve_trace, step)
        engine_steps = [s for s in inside if s.name in ENGINE_STEPS]
        assert len(engine_steps) == 1, [s.name for s in inside]
        leaves = _children(serve_trace, engine_steps[0])
        assert {s.name for s in leaves} == ENGINE_CHILDREN
        assert sum(s.end_s - s.start_s for s in leaves) <= engine_steps[0].end_s - engine_steps[0].start_s
        assert engine_steps[0].end_s - engine_steps[0].start_s <= step.end_s - step.start_s
    # no span outside a sched_step: the scheduler's step encloses the engine's
    covered = {id(s) for step in steps for s in _children(serve_trace, step)} | {id(s) for s in steps}
    assert all(id(s) in covered for s in serve_trace["spans"])


def test_sched_step_arguments_are_present_and_consistent(serve_trace):
    steps = program_spans.spans_named(serve_trace, "serving/sched_step")
    kinds = [s.args["kind"] for s in steps]
    assert set(kinds) == {"put", "decode"}
    fed_first = []
    for step in steps:
        assert SCHED_ARGS <= set(step.args), sorted(step.args)
        a = step.args
        assert a["rows_decode"] <= a["rows"] and a["prefill_tokens"] <= a["tokens"]
        (eng, ) = [s for s in _children(serve_trace, step) if s.name in ENGINE_STEPS]
        assert eng.args["rows"] == a["rows"]
        if a["kind"] == "put":
            assert eng.name in ("serving/prefill", "serving/decode_step")
            assert eng.args["tokens"] == a["tokens"] and eng.args["rows_decode"] == a["rows_decode"]
            assert a["tokens"] == a["rows_decode"] + a["prefill_tokens"] and a["budget_left"] == 32 - a["tokens"]
        else:
            assert eng.name == "serving/decode" and a["tokens"] == a["rows"] * eng.args["steps"]
        fed_first += program_spans.numbers(a["first_wait_ms"])
    assert len(fed_first) == 3 and all(w >= 0 for w in fed_first), "one wait per request, at its first chunk"


def test_engine_step_spans_carry_buckets_steps_and_kernel(serve_trace):
    found = set()
    for span in serve_trace["spans"]:
        if span.name not in ENGINE_STEPS:
            continue
        found.add(span.name)
        a = span.args
        assert a["rows"] <= a["bucket_rows"], a
        assert a["tokens"] <= a["bucket_tokens"] * a["steps"], a
        assert a["steps"] == 1 or span.name == "serving/decode"
        # off the TPU the paged kernel is the gather reference, and the span says so
        assert a["kernel"] == "paged_attention_reference:1:off_tpu", a
    assert {"serving/prefill", "serving/decode"} <= found
    dispatches = program_spans.spans_named(serve_trace, "serving/engine_dispatch")
    assert dispatches and all(d.args["compiled"] == 0 for d in dispatches), "the traced run was warm"


def _live_pairs(contexts, steps, block, window):
    """(row, KV block) pairs with a key in sight, by brute force: row ``r`` at
    step ``j`` sits at position ``contexts[r] + j`` and sees block ``b`` when
    one of its tokens is at or before that position and inside the window."""
    pairs = 0
    for c in contexts:
        for p in range(c, c + steps):
            pairs += sum(1 for b in range(p // block + 1)
                         if window is None or (b + 1) * block - 1 > p - window)
    return pairs


@pytest.mark.parametrize("window", [None, 24])
def test_decode_spans_count_the_kernels_grid_and_the_live_pairs(window):
    """``serving/decode`` and ``serving/decode_step`` carry ``kv_steps`` and
    ``kv_live``: ``kv_live`` is the live (row, block) pairs of the rows'
    contexts over the steps and layers, window applied, and ``kv_steps`` what
    the kernel that ran walks for the bucket (off the TPU the gather: every
    table column of every bucket row)."""
    engine = _engine(window)
    rng = np.random.default_rng(1)
    contexts = [30, 9, 17]
    uids = [7, 8, 9]
    for uid, n in zip(uids, contexts):
        engine.put([uid], [rng.integers(0, 128, size=n, dtype=np.int32)])
    one = [np.asarray([3], np.int32)] * 3
    engine.put(uids, one), engine.decode(uids, one, 5)        # trace both programs first
    contexts = [c + 6 for c in contexts]
    tracer = get_tracer().configure(enabled=True)
    engine.put(uids, one)
    engine.decode(uids, one, 5)
    events = {e["name"]: e.get("args", {}) for e in tracer.drain() if e["ph"] == "X"}
    step, burst = events["serving/decode_step"], events["serving/decode"]
    layers, max_blocks = 2, 96 // 16
    assert step["kv_live"] == layers * _live_pairs(contexts, 1, 16, window)
    assert burst["kv_live"] == layers * _live_pairs([c + 1 for c in contexts], 5, 16, window)
    assert step["kernel"].startswith("paged_attention_reference")
    assert step["kv_steps"] == layers * step["bucket_tokens"] * max_blocks
    assert burst["kv_steps"] == layers * 5 * burst["bucket_rows"] * max_blocks
    assert 0 < burst["kv_live"] <= burst["kv_steps"]


# the tiled choice these tests plant: four 16-token blocks a grid step, the rule's over token-major float32 pools
PLANTED = {"kernel": "paged_attn_q_tiled", "q_tile": 8, "blocks_per_step": 4, "rule": "planted"}


@pytest.mark.parametrize("window", [None, 24])
def test_spans_of_a_shape_the_tiled_kernel_took_count_its_work_list(window, monkeypatch):
    """``serving/prefill`` of a shape ``paged_attn_q_tiled`` took carries
    ``tile_kv_live``, the work list's live (tile, column) pairs on the same
    batch (its ``total`` at one block an item), ``tile_kv_steps``, its
    ``total`` at the choice's four 16-token blocks an item, and
    ``tile_kv_bound``, the tiles x columns rectangle of the shapes, each times
    the layers; not ``kv_live``/``kv_steps``, which stay the decode grid's;
    a shape another kernel took carries none of the three. (Off
    the TPU no shape takes the tiled kernel: the choice is planted.)"""
    engine = _engine(window)
    rng = np.random.default_rng(2)
    tokens = lambda n: rng.integers(0, 128, size=n, dtype=np.int32)
    engine.put([7], [tokens(30)]), engine.put([8], [tokens(9)])
    one = [np.asarray([3], np.int32)]
    engine.put([7, 8, 9], one * 2 + [tokens(20)])              # trace the programs first
    engine.put([7, 8], one * 2)
    max_blocks, layers = 96 // 16, 2
    monkeypatch.setitem(pa.KERNEL_CHOICES, (32, 4, max_blocks), PLANTED)
    batches = []
    finalize = engine.batch.finalize
    monkeypatch.setattr(engine.batch, "finalize", lambda: batches.append(finalize()) or batches[-1])
    tracer = get_tracer().configure(enabled=True)
    engine.put([7, 8, 9], one * 2 + [tokens(20)])              # 22 tokens: the bucket of 32 x 4, two decode rows
    engine.put([7, 8], one * 2)                                # 8 x 4: the decode grid's
    events = {e["name"]: e.get("args", {}) for e in tracer.drain() if e["ph"] == "X"}
    mixed, step = events["serving/prefill"], events["serving/decode_step"]
    assert mixed["kernel"] == "paged_attn_q_tiled:8:planted" and (mixed["bucket_tokens"], mixed["bucket_rows"]) == (32, 4)
    rb = batches[0]
    pairs, steps = (int(pa._tiled_work_list(jnp.asarray(rb.block_tables), jnp.asarray(rb.token_seq_idx),
                                            jnp.asarray(rb.token_pos), 16, window, 8, per_step=per)[8]) for per in (1, 4))
    n_tiles = 32 // 8 + 4 + 1
    cols = max_blocks if window is None else min(max_blocks, (window + 8 - 2) // 16 + 2)
    assert mixed["tile_kv_live"] == layers * pairs and mixed["tile_kv_bound"] == layers * n_tiles * cols
    assert mixed["tile_kv_steps"] == layers * steps and 0 < steps < pairs
    assert 0 < mixed["tile_kv_live"] < mixed["tile_kv_bound"]
    assert not {"kv_live", "kv_steps"} & set(mixed)
    assert {"kv_live", "kv_steps"} <= set(step) and not {"tile_kv_live", "tile_kv_bound", "tile_kv_steps"} & set(step)


def _latent_engine(monkeypatch, min_tokens=8):
    """The tiny GLM (latent attention: 4 heads, 5 layers) on the paged
    kernel's module, its rows long from ``min_tokens`` tokens on (the constant
    is 768: no row of a 64-token batch reaches it)."""
    from deepspeed_tpu.inference.v2.model_implementations import flat_model
    from deepspeed_tpu.models import glm_config

    monkeypatch.setattr(flat_model, "_EXPAND_MIN_TOKENS", min_tokens)
    cfg = glm_config("tiny", dtype=jnp.float32)
    return cfg, _small_engine(TransformerLM(cfg), TransformerLM(cfg).init(jax.random.PRNGKey(3)), 4, 128)


def test_a_latent_models_spans_say_the_pairs_attended_expanded_and_name_both_calls(monkeypatch):
    """``serving/prefill`` of a model with latent attention carries
    ``attn_expanded_pairs`` beside ``attn_pairs``, from the rows' lengths
    alone (the pairs of the rows fed at least the threshold, times the
    layers), its ``kernel`` names the absorbed call and the expanded one with
    their tiles, and its ``tile_kv_live`` is both calls' work lists, as is its
    ``tile_kv_steps`` (a latent pool and pools by head are read a block a grid
    step); a step of decode rows and a decode horizon say 0. (Off the TPU no
    shape takes the tiled kernel: both calls' choices are planted.)"""
    cfg, engine = _latent_engine(monkeypatch)
    rng = np.random.default_rng(4)
    tokens = lambda n: rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
    one = [np.asarray([3], np.int32)]
    engine.put([7], [tokens(30)]), engine.put([8], [tokens(9)])
    engine.put([7, 8, 9], one * 2 + [tokens(20)]), engine.put([7, 8], one * 2), engine.decode([7, 8], one * 2, 3)  # trace first
    max_blocks, layers = 128 // 16, cfg.num_layers
    monkeypatch.setitem(pa.KERNEL_CHOICES, (32, 4, max_blocks), {**PLANTED, "blocks_per_step": 1})
    monkeypatch.setitem(pa.KERNEL_CHOICES, (32, 2 * 2 + 1, max_blocks), {**PLANTED, "q_tile": 16, "blocks_per_step": 1})
    engine._kernel_labels.clear()
    batches = []
    finalize = engine.batch.finalize
    monkeypatch.setattr(engine.batch, "finalize", lambda: batches.append(finalize()) or batches[-1])
    tracer = get_tracer().configure(enabled=True)
    # 22 tokens in the bucket of 32 x 4: decode rows behind 35 and 14 tokens, one long row of 20
    engine.put([7, 8, 10], one * 2 + [tokens(20)])
    engine.put([7, 8], one * 2)
    engine.decode([7, 8], one * 2, 3)
    events = {e["name"]: e.get("args", {}) for e in tracer.drain() if e["ph"] == "X"}
    mixed, step, burst = events["serving/prefill"], events["serving/decode_step"], events["serving/decode"]
    assert mixed["kernel"] == "paged_attn_q_tiled:8:planted+paged_attn_q_tiled:16:planted"
    assert mixed["attn_expanded_pairs"] == layers * (20 * 21 // 2) and mixed["attn_pairs"] == layers * (210 + 36 + 15)
    assert step["attn_expanded_pairs"] == 0 == burst["attn_expanded_pairs"] and step["attn_pairs"] > 0 < burst["attn_pairs"]
    # the two work lists on the same batch: the absorbed call without the long row, the expanded one over slot 0
    rb = batches[0]
    long_tok = (rb.token_seq_idx == 2) & rb.token_valid
    totals = []
    for tile, tables, seq_idx, pos in (
            (8, rb.block_tables, rb.token_seq_idx, np.where(long_tok, -1, rb.token_pos)),
            (16, np.zeros((5, max_blocks), np.int32), np.where(long_tok, 0, np.r_[[2] * 2, [0] * 20, [3] * 10]),
             np.where(long_tok, rb.token_pos, -1))):
        *_, total = pa._tiled_work_list(jnp.asarray(tables), jnp.asarray(seq_idx, jnp.int32), jnp.asarray(pos, jnp.int32),
                                        16, None, tile)
        totals.append(int(total))
    assert totals[1] == 1 + 2                                  # positions 0-19: a tile of 16 over one block, one of 4 over two
    assert mixed["tile_kv_live"] == mixed["tile_kv_steps"] == layers * sum(totals)
    assert mixed["tile_kv_bound"] == layers * ((32 // 8 + 4 + 1) + (32 // 16 + 5 + 1)) * max_blocks


@pytest.mark.parametrize("latent", [False, True])
def test_the_pool_is_sized_from_what_the_weights_and_the_workspace_leave(latent, monkeypatch):
    """``_auto_kv_blocks`` on a device that reports its memory: a model with
    latent attention whose largest program keeps a workspace of per-head K and
    V counts it as used BEFORE the pool takes its fraction of the rest; every
    other model's pool is what it was."""
    from deepspeed_tpu.inference.v2 import DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.model_implementations import flat_model
    from deepspeed_tpu.models import glm_config

    monkeypatch.setattr(flat_model, "_EXPAND_MIN_TOKENS", 8)
    cfg = glm_config("tiny", dtype=jnp.float32) if latent else TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, intermediate_size=128, max_seq_len=128,
        dtype=jnp.float32, attention_impl="reference")
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    param_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    limit = param_bytes + (8 << 20)

    class Device:
        def memory_stats(self):
            return {"bytes_limit": limit, "bytes_in_use": 0}

    monkeypatch.setattr(jax, "devices", lambda *a: [Device()])
    sm = DSStateManagerConfig(max_tracked_sequences=1024, max_ragged_batch_size=64, max_ragged_sequence_count=4,
                              max_context=128)
    icfg = RaggedInferenceEngineConfig(kv_block_size=16, num_kv_blocks="auto", kv_dtype=jnp.float32, state_manager=sm,
                                       kv_memory_fraction=0.5)
    engine = InferenceEngineV2.__new__(InferenceEngineV2)
    engine.params, engine._kv_entry = params, tuple(cfg.kv_entry)
    per_block = cfg.num_layers * sum(h * w for h, w in cfg.kv_entry) * 16 * 4
    workspace = flat_model.expanded_workspace_bytes(cfg, 64, 128 // 16, 16, 4)
    assert workspace == (2 * 8 * 2 * cfg.num_heads * 16 * cfg.head_dim * 4 if latent else 0)
    assert engine._auto_kv_blocks(cfg, icfg, 128) == int(((8 << 20) - workspace) * 0.5) // per_block
    if latent:  # a program under the threshold keeps none
        sm.max_ragged_batch_size = 4
        assert engine._auto_kv_blocks(cfg, icfg, 128) == int((8 << 20) * 0.5) // per_block


def _small_engine(model, params, rows, context):
    from deepspeed_tpu.inference.v2 import DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig

    sm = DSStateManagerConfig(max_tracked_sequences=rows, max_ragged_batch_size=64, max_ragged_sequence_count=rows,
                              max_context=context)
    icfg = RaggedInferenceEngineConfig(kv_block_size=16, num_kv_blocks=48, kv_dtype=jnp.float32, state_manager=sm)
    icfg.use_pallas_kernels = "always"  # as in _engine
    return InferenceEngineV2(model, icfg, params=params)


def _step_case(name, plant):
    """``(step span's name, prepare)``: ``prepare(uid)`` prefills a fixed
    batch's rows under uids from ``uid`` (the rows of the round before it
    flushed) and returns ONE call of one step function on them. Run a round to
    build the program and another to read its spans: but for ``uids``, nothing
    a span says depends on which uids or which KV blocks the rows were given.
    Off the TPU no shape takes the tiled kernel: ``plant(key, choice)`` puts
    its choice into ``paged_attention.KERNEL_CHOICES``.

    ``dense_*``: this file's engine, rows of 30 and 9 tokens, the tiled
    kernel's choice planted for the mixed put's shape (32 tokens x 4 rows).
    ``experts_*``: the tiny Mellum (8 experts top-2, three window layers of 16
    and a full one), rows of 40 and 12. ``experts_decode_blocks``: the tiny
    SDAR (blocks of 4, 8 experts top-2), rows of 40, 12 and 23 prompt tokens,
    the last three of the third left to open its first block, the tiled
    kernel's choice planted for the block program's shape."""
    from deepspeed_tpu.models import mellum_config, sdar

    family, _, step = name.partition("_")
    planted = None
    if family == "dense":
        engine, vocab, lengths, planted = _engine(), 128, (30, 9), (32, 4, 96 // 16)
    elif step == "decode_blocks":
        model = sdar("tiny", dtype=jnp.float32)
        engine = _small_engine(model, jax.jit(lambda k: model.init(k, None))(jax.random.PRNGKey(5)), 8, 192)
        vocab, lengths, planted = 500, (40, 12, 23), (8 * 4, 8, 192 // 16)
    else:
        cfg = mellum_config("tiny", dtype=jnp.float32)
        engine = _small_engine(TransformerLM(cfg), TransformerLM(cfg).init(jax.random.PRNGKey(3)), 4, 128)
        vocab, lengths = cfg.vocab_size, (40, 12)
    held = []

    def prepare(uid):
        while held:
            engine.flush(held.pop())
        rng = np.random.default_rng(2)
        uids, rest = [uid + i for i in range(len(lengths))], []
        for u, n in zip(uids, lengths):
            prompt = rng.integers(0, vocab, size=n, dtype=np.int32)
            whole = n // 4 * 4 if step == "decode_blocks" else n
            engine.put([u], [prompt[:whole]], sample="greedy")
            rest.append(prompt[whole:])
        held.extend(uids + [uid + len(lengths)])
        if planted:
            plant(planted, PLANTED)  # again every round: the table is rewritten when jit traces the program
        one = [np.asarray([3], np.int32)] * len(uids)
        if step == "put_with_a_prefill_chunk":
            chunk = rng.integers(0, vocab, size=20, dtype=np.int32)
            return lambda: engine.put(held, one + [chunk], sample="greedy")
        held.pop()
        if step == "put_of_decode_rows":
            return lambda: engine.put(uids, one, sample="greedy")
        if step == "decode":
            return lambda: engine.decode(uids, one, 5)
        return lambda: engine.decode(uids, rest, 8)

    return {"put_with_a_prefill_chunk": "serving/prefill", "put_of_decode_rows": "serving/decode_step"}.get(
        step, "serving/decode"), prepare


# what the step span of each case said at commit 3963d3a (the parent of the PR that moved the observation under
# a span of its own), but ``seqs``, which repeated ``rows``, and ``block_ms``, a time; uids from 50; and, since PR 38,
# what a causal step says of its attention work by hand: ``attn_pairs``, ``attn_ctx_tokens``, ``kv_entry_bytes``; and, since
# PR 48, ``tile_kv_steps`` beside ``tile_kv_live`` (the live pairs, as before): the grid steps at the planted four blocks a
# step (the mixed put's seven tiles have one or two live columns each: 7 steps a layer for 9 pairs)
STEP_SPAN_ARGS = {
    "dense_put_with_a_prefill_chunk": {
        "blocked": True, "bucket_rows": 4, "bucket_tokens": 32, "kernel": "paged_attn_q_tiled:8:planted",
        "rows": 3, "rows_decode": 2, "steps": 1, "tile_kv_bound": 108, "tile_kv_live": 18, "tile_kv_steps": 14, "tokens": 22,
        "uids": [50, 51, 52], "attn_pairs": 2 * (31 + 10 + 210), "attn_ctx_tokens": 2 * (31 + 10 + 20), "kv_entry_bytes": 256},
    "dense_put_of_decode_rows": {
        "blocked": True, "bucket_rows": 4, "bucket_tokens": 8, "kernel": "paged_attention_reference:1:off_tpu",
        "kv_live": 6, "kv_steps": 96, "rows": 2, "rows_decode": 2, "steps": 1, "tokens": 2, "uids": [50, 51],
        "attn_pairs": 2 * (31 + 10), "attn_ctx_tokens": 2 * (31 + 10), "kv_entry_bytes": 256},
    "dense_decode": {
        "blocked": True, "bucket_rows": 4, "bucket_tokens": 4, "kernel": "paged_attention_reference:1:off_tpu",
        "kv_live": 36, "kv_steps": 240, "rows": 2, "steps": 5, "tokens": 10, "uids": [50, 51],
        "attn_pairs": 2 * (165 + 60), "attn_ctx_tokens": 2 * (35 + 14), "kv_entry_bytes": 256},
    "experts_put_with_a_prefill_chunk": {
        "blocked": True, "bucket_rows": 4, "bucket_tokens": 32, "expert_load_max": 19, "experts_held": 8,
        "experts_hit": 29, "experts_published": 8, "experts_total": 32,
        "kernel": "paged_attention_reference:1:off_tpu", "moe_rows": 704, "moe_slots": 176,
        "moe_slots_routed": 176, "rows": 3, "rows_decode": 2, "steps": 1, "tokens": 22, "uids": [50, 51, 52],
        # three window layers of 16 (16 + 13 + 200 pairs over 16 + 13 + 20 tokens) and a full one
        "attn_pairs": 3 * 229 + 264, "attn_ctx_tokens": 3 * 49 + 74, "kv_entry_bytes": 512},
    "experts_decode": {
        "blocked": True, "bucket_rows": 4, "bucket_tokens": 4, "expert_load_max": 2, "experts_held": 8,
        "experts_hit": 68, "experts_published": 8, "experts_total": 160,
        "kernel": "paged_attention_reference:1:off_tpu", "kv_live": 69, "kv_steps": 640, "moe_rows": 1280,
        "moe_slots": 80, "moe_slots_routed": 80, "rows": 2, "steps": 5, "tokens": 10, "uids": [50, 51],
        "attn_pairs": 3 * (80 + 74) + 215 + 75, "attn_ctx_tokens": 3 * (20 + 17) + 45 + 17, "kv_entry_bytes": 512},
    # PR 50: the first block's commit rides in the second's first forward (``fused_commits``), so the call runs ONE
    # commit forward and 9 forwards in all, two of them of 2 x 32 tokens (24 live in the one that carries the commit, 12
    # in block 0's, whose half before is padding: 696 = 2 slots x 3 layers x (12 + 24) + 2 x 12 x (3 x 7 - 1)); the tiled
    # choice is planted for the 32-token shape alone, so the grid's counts are those of its 3 x 7 - 1 attention calls
    "experts_decode_blocks": {
        "block_size": 4, "blocked": True, "blocks": 2, "bucket_rows": 8, "bucket_tokens": 32, "commit_forwards": 1,
        "denoise_forwards": 8, "expert_load_max": 15, "experts_held": 8, "experts_hit": 184, "experts_published": 8,
        "experts_total": 208, "fused_commits": 1,
        "kernel": "paged_attn_q_tiled:8:planted+paged_attention_reference:1:off_tpu", "masked_fed": 51, "moe_rows": 5632,
        "moe_slots": 696, "moe_slots_routed": 696, "open_tokens": 3, "rows": 3, "steps": 9, "tile_kv_bound": 3120,
        "tile_kv_live": 191, "tile_kv_steps": 120, "tokens": 24, "tokens_committed": 21, "tokens_dropped": 0, "tokens_fed": 120,
        "uids": [50, 51, 52]},
}


class _HostReads:
    """Every way ``engine_v2`` has brought a device array to the host, watched:
    ``np.asarray`` / ``np.array`` of one, ``jax.device_get``, and ``int()`` of
    one. ``at`` holds a ``perf_counter`` stamp a read (a ``device_get`` of a
    tuple is one)."""

    def __init__(self, monkeypatch):
        from jax._src.array import ArrayImpl

        from deepspeed_tpu.inference.v2 import engine_v2

        self.at = []
        reads, real_get, value = self, jax.device_get, ArrayImpl._value

        class Numpy:

            def __getattr__(self, name):
                return getattr(np, name)

            def asarray(self, a, *args, **kw):
                if isinstance(a, jax.Array):
                    reads.at.append(time.perf_counter())
                return np.asarray(a, *args, **kw)

            array = asarray

        def device_get(x):
            reads.at.append(time.perf_counter())
            reads.getting = True  # it reads each leaf's _value: the one read just counted
            try:
                return real_get(x)
            finally:
                reads.getting = False

        def _value(a):
            if not reads.getting:
                reads.at.append(time.perf_counter())
            return value.fget(a)

        self.getting = False
        monkeypatch.setattr(engine_v2, "np", Numpy())
        monkeypatch.setattr(jax, "device_get", device_get)
        monkeypatch.setattr(ArrayImpl, "_value", property(_value))


def _inside(events, parent):
    """The bus events of ``parent``'s thread that lie inside it."""
    t0, t1 = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in events if e is not parent and e["tid"] == parent["tid"] and e["ts"] >= t0
            and e["ts"] + e["dur"] <= t1 + 1e-3]


@pytest.mark.parametrize("case", ["dense_put_with_a_prefill_chunk", "dense_put_of_decode_rows", "dense_decode",
                                  "experts_put_with_a_prefill_chunk", "experts_decode", "experts_decode_blocks"])
def test_a_step_observes_itself_under_a_span_of_its_own_and_off_the_fetch(case, tmp_path, monkeypatch):
    """One call of each step function on a fixed batch, both sinks on: the
    step span says what it said on the parent commit; what is counted for it
    runs under ``serving/engine_observe`` children, on the driver thread,
    before the fetch and last in the step; and the device's results come to
    the host in ONE read, inside ``serving/engine_fetch``, the counts a live
    span watches with the tokens. With both sinks off the same call reads
    once and opens nothing."""
    name, prepare = _step_case(case, lambda key, choice: monkeypatch.setitem(pa.KERNEL_CHOICES, key, choice))
    prepare(10)()                                                       # builds the program
    call = prepare(50)
    reads = _HostReads(monkeypatch)
    built = []
    init = trace_mod._Span.__init__
    monkeypatch.setattr(trace_mod._Span, "__init__", lambda self, *a: built.append(a[1]) or init(self, *a))
    tracer = get_tracer().configure(enabled=True)
    tracer.drain()
    trace = _profiled(tmp_path, call)
    events = [e for e in tracer.drain() if e["ph"] == "X"]
    (step, ) = [e for e in events if e["name"] == name]
    (in_profile, ) = program_spans.spans_named(trace, name)
    want = dict(STEP_SPAN_ARGS[case], wall_us=step["args"]["wall_us"], offcpu_us=step["args"]["offcpu_us"])
    want.update({k: step["args"][k] for k in ("block_ms", ) if k in step["args"]})
    assert step["args"] == want
    assert {k: in_profile.args[k] for k in STEP_SPAN_ARGS[case]} == STEP_SPAN_ARGS[case]
    # the children, in order, in both sinks: observed while the device runs, and last
    order = [e["name"].rpartition("/")[2] for e in sorted(_inside(events, step), key=lambda e: e["ts"])]
    commit_first = ["engine_commit", "engine_fetch"] if name != "serving/decode" else ["engine_fetch", "engine_commit"]
    assert order == ["engine_batch", "engine_dispatch", "engine_observe"] + commit_first + ["engine_observe"]
    kids = sorted(_children(trace, in_profile), key=lambda s: s.start_s)
    assert [s.name.rpartition("/")[2] for s in kids] == order and {s.line for s in kids} == {in_profile.line}
    # one read of the device's results, inside engine_fetch
    origin = tracer._origin
    (fetch, ) = [e for e in events if e["name"] == "serving/engine_fetch"]
    assert len(reads.at) == 1, "the counts a live span watches come with the tokens"
    assert fetch["ts"] <= (reads.at[0] - origin) * 1e6 <= fetch["ts"] + fetch["dur"]
    # both sinks off: the same call reads once, builds no span, reads no thread clock
    get_tracer().reset()
    call = prepare(90)
    del reads.at[:], built[:]
    clock = []
    monkeypatch.setattr(time, "thread_time_ns", lambda: clock.append(1) or 0)
    call()
    assert len(reads.at) == 1 and built == [] and clock == []


def test_a_verify_step_observes_itself_too(engine):
    """``_speculate`` watches no count of the device's: its two observations
    say the sizes while the program runs and, last, what was accepted."""
    rng = np.random.default_rng(7)
    uids = [600, 601]
    engine.put(uids, [rng.integers(0, 128, size=n, dtype=np.int32) for n in (20, 7)], sample="greedy")
    first = [np.asarray([3], np.int32)] * 2
    drafts = [np.asarray([5, 6, 7], np.int32), np.asarray([9], np.int32)]
    tracer = get_tracer().configure(enabled=True)
    tracer.drain()
    out = engine.speculate_decode(uids, first, drafts, 3)
    events = [e for e in tracer.drain() if e["ph"] == "X"]
    for uid in uids:
        engine.flush(uid)
    (step, ) = [e for e in events if e["name"] == "serving/spec_verify"]
    order = [e["name"].rpartition("/")[2] for e in sorted(_inside(events, step), key=lambda e: e["ts"])]
    assert order == ["engine_batch", "engine_dispatch", "engine_observe", "engine_fetch", "engine_commit",
                     "engine_observe"]
    said = {k: v for k, v in step["args"].items() if k not in ("wall_us", "offcpu_us", "kernel")}
    assert said == {"rows": 2, "tokens": 8, "bucket_tokens": 8, "bucket_rows": 4, "steps": 1, "k": 3, "tree_width": 1,
                    "sampled": False, "uids": uids, "drafted": 4, "accepted": [len(o) - 1 for o in out]}


@pytest.mark.parametrize("sinks", ["bus", "profiler", "both"])
def test_every_live_span_says_its_wall_time_and_how_long_its_thread_did_not_run(sinks, tmp_path):
    """``wall_us >= offcpu_us >= 0`` on every span; a span that sleeps reads
    nearly all of its wall time as off the CPU, one that spins nearly none
    (the least of 25 spins of 2 ms, each short enough to fit one turn on a
    core: the workers beside this one take it between turns)."""
    tracer = get_tracer()
    if sinks != "profiler":
        tracer.configure(enabled=True)

    def work():
        with tracer.span("outer", tid="serving"):
            with tracer.span("sleeps", tid="serving"):
                time.sleep(0.02)
            for _ in range(25):
                with tracer.span("spins", tid="serving"):
                    t_end = time.perf_counter() + 0.002
                    while time.perf_counter() < t_end:
                        pass

    if sinks == "bus":
        work()
    else:
        trace = _profiled(tmp_path, work)
    found = []
    if sinks != "profiler":
        found.append({(e["name"], i): e["args"] for i, e in enumerate(tracer.drain()) if e["ph"] == "X"})
    if sinks != "bus":
        found.append({(s.name, i): s.args for i, s in enumerate(trace["spans"])})
    for spans in found:
        assert len(spans) == 27
        for args in spans.values():
            assert args["wall_us"] >= args["offcpu_us"] >= 0, args
        (sleeps, ) = [a for (n, _), a in spans.items() if n == "sleeps"]
        assert 20e3 <= sleeps["wall_us"] < 200e3 and sleeps["offcpu_us"] > 0.8 * sleeps["wall_us"], sleeps
        spins = min((a for (n, _), a in spans.items() if n == "spins"), key=lambda a: a["offcpu_us"])
        assert spins["wall_us"] >= 2e3 and spins["offcpu_us"] < 0.2 * spins["wall_us"], spins
        (outer, ) = [a for (n, _), a in spans.items() if n == "outer"]
        assert outer["offcpu_us"] >= sleeps["offcpu_us"] - 1.0


def test_bus_sees_the_same_spans_once_each_under_their_bus_names(engine, serve_trace):
    tracer = get_tracer().configure(enabled=True)  # pathless buffer
    _serve(engine, uid_base=300)
    events = [e for e in tracer.drain() if e["ph"] == "X"]
    assert all(not e["name"].startswith("dstpu/") for e in events)
    by_name = lambda spans, key: sorted(key(s) for s in spans)
    on_bus = by_name(events, lambda e: e["name"])
    in_profile = by_name(serve_trace["spans"], lambda s: s.name)
    assert on_bus == in_profile, "same schedule, same spans, each once"
    step = next(e for e in events if e["name"] == "serving/sched_step")
    assert SCHED_ARGS <= set(step["args"])


def test_process_name_event_carries_both_clock_origins():
    tracer = get_tracer().configure(enabled=True)
    (meta, ) = [e for e in tracer.drain() if e["name"] == "process_name"]
    origin_ns, origin_pc = meta["args"]["origin_unix_ns"], meta["args"]["origin_perf_counter"]
    # the same instant on both clocks: now, measured on each, is equally far from it
    assert abs((time.time_ns() - origin_ns) * 1e-9 - (time.perf_counter() - origin_pc)) < 0.05


def test_both_sinks_off_allocates_nothing_and_the_kernel_table_fills_at_trace_time_only(engine, monkeypatch):
    tracer = get_tracer()
    assert not tracer.enabled and not jax.profiler.TraceAnnotation.is_enabled()
    assert tracer.span("serving/sched_step", tid="serving") is NULL_SPAN
    built, clock = [], []
    monkeypatch.setattr(trace_mod._Span, "__init__", lambda self, *a: built.append(a[1]))
    monkeypatch.setattr(time, "thread_time_ns", lambda: clock.append(1) or 0)
    _serve(engine, uid_base=400)
    assert tracer.drain() == [] and built == [] and clock == [], "no span object, no read of the thread's clock"
    # the table is written while jit traces a program, never when one runs
    q, pool = jnp.ones((8, 4, 16)), jnp.ones((64, 4, 16))
    tables, seq_idx, pos = jnp.zeros((2, 4), jnp.int32), jnp.zeros((8, ), jnp.int32), jnp.arange(8)
    fn = jax.jit(lambda q: pa.paged_attention(q, pool, pool, tables, seq_idx, pos, 16))
    pa.KERNEL_CHOICES.pop((8, 2, 4), None)
    fn(q)
    assert pa.kernel_choice(8, 2, 4) == {"kernel": "paged_attention_reference", "q_tile": 1,
                                         "rule": "off_tpu", "blocks_per_step": 1}
    pa.KERNEL_CHOICES.clear()
    fn(q)
    assert pa.KERNEL_CHOICES == {}


@pytest.mark.parametrize("shape,env,want", [
    ((2048, 8, 65), {}, ("paged_attn_q_tiled", 128, "heuristic:long_rows")),  # mistral-7b.longprompt
    ((512, 32, 65), {}, ("paged_attn_q_tiled", 32, "heuristic:short_rows")),  # mistral-7b.chat's put
    ((512, 8, 65), {}, ("paged_attn_q_tiled", 128, "heuristic:long_rows")),
    ((32, 32, 65), {}, ("paged_attn_kv_split", 1, "heuristic:long_table")),
    ((32, 32, 4), {}, ("paged_attn_kv_split", 1, "heuristic:short_table")),
    # the variables that once overrode the choice are read by nothing
    ((32, 32, 65), {"DS_TPU_PAGED_KV_SPLITS": "1"}, ("paged_attn_kv_split", 1, "heuristic:long_table")),
    ((512, 8, 65), {"DS_TPU_PAGED_Q_TILE": "16"}, ("paged_attn_q_tiled", 128, "heuristic:long_rows")),
])
def test_kernel_choice_names_the_rule_that_decided(monkeypatch, shape, env, want):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    choice = pa.choose_kernel(*shape, nq=32, block_rows=128 * 8, d=128, itemsize=2)
    assert (choice["kernel"], choice["q_tile"], choice["rule"]) == want


@pytest.mark.parametrize("T,S,max_blocks,want", [
    # the tiled kernel: 16-token blocks are an eighth of a lane tile, so four a grid step (the most)
    (512, 32, 65, {"kernel": "paged_attn_q_tiled", "q_tile": 32, "rule": "heuristic:short_rows",
                   "blocks_per_step": 4}),
    (2048, 8, 65, {"kernel": "paged_attn_q_tiled", "q_tile": 128, "rule": "heuristic:long_rows",
                   "blocks_per_step": 4}),
    # the decode kernel: 16-token blocks of 8 kv heads of 128 in float32 are 128 KiB, so four a grid step
    (32, 32, 65, {"kernel": "paged_attn_kv_split", "q_tile": 1, "rule": "heuristic:long_table",
                  "blocks_per_step": 4}),
    (32, 32, 4, {"kernel": "paged_attn_kv_split", "q_tile": 1, "rule": "heuristic:short_table",
                 "blocks_per_step": 4}),
])
def test_on_the_tpu_branch_the_table_names_the_grid_that_runs(monkeypatch, T, S, max_blocks, want):
    """The TPU branch of ``paged_attention`` with the kernel itself stubbed
    out: the recorded kernel is the one ``_pallas_paged`` is handed."""
    handed = {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pa, "_pallas_paged", lambda q, *a, q_tile, **kw: handed.update(q_tile=q_tile) or q)
    try:
        q, pool = jnp.ones((T, 8, 128)), jnp.ones((max_blocks * 16, 8, 128))
        pa.paged_attention(q, pool, pool, jnp.zeros((S, max_blocks), jnp.int32), jnp.zeros((T, ), jnp.int32),
                           jnp.zeros((T, ), jnp.int32), 16)
        assert pa.kernel_choice(T, S, max_blocks) == want
        assert handed == {"q_tile": want["q_tile"]}
    finally:
        pa.KERNEL_CHOICES.pop((T, S, max_blocks), None)


def test_contiguity_demotion(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    interleaved = np.tile(np.arange(2), 32).astype(np.int32)
    choice = pa.choose_kernel(64, 2, 65, 32, 128 * 8, 128, 2, seq_idx=interleaved)
    assert (choice["kernel"], choice["rule"]) == ("paged_attn_kv_split", "contiguity_demoted")


def _train_engine(extra):
    import deepspeed_tpu

    model = TransformerLM(TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                                            max_seq_len=64, intermediate_size=128,
                                            attention_impl="reference", dtype=jnp.float32))
    cfg = {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "tpu": {"mesh": {"data": 8}},
           "steps_per_print": 1000}
    cfg.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


def test_traced_train_batch_does_not_block_and_yields_input_wait_and_dispatch(tmp_path, eight_devices):
    engine = _train_engine({"trace": {}})  # presence-enables the bus, pathless
    assert get_tracer().enabled
    blocked = []
    real = jax.block_until_ready
    batch = tiny_batch(batch_size=16, seq=32)
    engine.train_batch(batch)  # builds the program
    get_tracer().drain()
    jax.block_until_ready = lambda x: (blocked.append(1), real(x))[1]
    try:
        trace = _profiled(tmp_path, lambda: [engine.train_batch(batch) for _ in range(2)])
    finally:
        jax.block_until_ready = real
    assert blocked == [], "a traced step stays in flight like an untraced one"
    events = [e for e in get_tracer().drain() if e["ph"] == "X"]
    names = [e["name"] for e in events]
    assert names.count("input_wait") == 2 and names.count("train/dispatch") == 2 and names.count("train_batch") == 2
    assert all(e["args"]["blocked"] is False for e in events if e["name"] == "train_batch")
    wait = next(e for e in events if e["name"] == "input_wait")
    assert wait["args"]["prefetched"] is False and "step" in wait["args"]
    dispatch = program_spans.spans_named(trace, "train/dispatch")
    assert len(dispatch) == 2 and all(d.args["compiled"] == 0 for d in dispatch)
    assert len(program_spans.spans_named(trace, "input_wait")) == 2
    # the driver thread of a training run is the one that dispatches
    assert {name for name, _, _ in program_spans.driver_segments(trace)} == {"input_wait", "train/dispatch"}
    engine.destroy()


def test_reset_returns_the_singleton_to_a_fresh_state(tmp_path):
    class Mirror:
        def record_event(self, ev):
            pass

    tracer = get_tracer().configure(enabled=True, path=str(tmp_path / "t.jsonl"), flush_every=1)
    tracer.set_mirror(Mirror())
    with tracer.span("fwd"):
        pass
    tracer.reset()
    assert not tracer.enabled and tracer._path is None and tracer._mirror is None and tracer._fh is None
    assert tracer.span("fwd") is NULL_SPAN
    tracer.configure(enabled=True)  # pathless again: events stay in the buffer
    with tracer.span("bwd"):
        pass
    assert [e["name"] for e in tracer.drain() if e["ph"] == "X"] == ["bwd"]


def test_synchronized_timer_covers_an_unfetched_jitted_call():
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

    @jax.jit
    def heavy(x):
        return jax.lax.fori_loop(0, 40, lambda i, a: jnp.tanh(a @ a) * 0.5 + a * 0.5, x)

    x = jnp.ones((500, 500))
    heavy(x).block_until_ready()
    t0 = time.perf_counter()
    y = heavy(x)
    enqueue_s = time.perf_counter() - t0
    y.block_until_ready()
    run_s = time.perf_counter() - t0
    assert enqueue_s < run_s / 5, "the call returns long before it has run"
    timer = SynchronizedWallClockTimer.Timer("heavy")
    timer.start()
    y = heavy(x)  # never fetched: only the timer's fence waits for it
    timer.stop()
    assert timer.elapsed(reset=False) > run_s / 3, "the timer covers the run, not the enqueue"
