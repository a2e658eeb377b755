"""The GLM family (``models/glm.py``: latent attention, MLA) on the serving
path at a small size, on seeded random weights, against the benchmark's plain
float32 reference (``benchmark/lib/glm_reference.py``, which imports nothing
of the program and computes the EXPANDED form: per-head keys and values made
from the latent): a prompt fed in chunks and every position decoded through
the latent cache, for chunk edges inside and on a KV block's edge; a
prefix-cache hit on latent blocks with copy-on-write on the partial tail;
rollback, export / import and a host-tier spill and restore of a latent
sequence; the pool's bytes; the controls that must FAIL the same comparison;
the published widths; the refusals. Tiny shapes whose nope, rope, value and
latent sizes all differ (12, 8, 16, 24), so that a mix-up of two of them
fails: hidden 64, 4 heads, q rank 40, 8 experts top-2 of width 48 beside one
shared expert, 1 dense + 4 expert layers."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmark.lib import glm_reference  # noqa: E402
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import (HostTierConfig, PrefixCacheConfig,  # noqa: E402
                                                  SpeculativeConfig)
from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache  # noqa: E402
from deepspeed_tpu.models import TransformerLM, glm_config, llama2_config  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.monitor.trace import get_tracer  # noqa: E402

BLOCK = 16


@pytest.fixture(autouse=True)
def _fresh_tracer():
    get_tracer().reset()
    yield
    get_tracer().reset()


@pytest.fixture(scope="module")
def tiny():
    """The tiny model, its parameters (norm gains about one, a selection bias
    wide enough to change the chosen set) and a seeded sequence."""
    cfg = glm_config("tiny", dtype=jnp.float32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    params["blocks"]["gate_bias"] = params["blocks"]["gate_bias"] * 20.0
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, size=72, dtype=np.int32)
    return cfg, params, ids


def _engine(cfg, params, attention="dense_blocked_attention", prefix_cache=None, kv_dtype=jnp.float32, **kwargs):
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=64,
                              max_ragged_sequence_count=4, max_context=128, token_buckets=(64, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=BLOCK, num_kv_blocks=48, kv_dtype=kv_dtype,
                                       state_manager=sm, **kwargs)
    if prefix_cache is not None:
        icfg.prefix_cache = prefix_cache
    icfg.modules.attention = {"name": attention, "implementation_config": {"interpret": True}}
    return InferenceEngineV2(TransformerLM(cfg), icfg, params=params)


@pytest.fixture(scope="module")
def engine_of(tiny):
    """One engine of the tiny model an attention module for the module's
    tests, and one more for each threshold of long rows a test sets: the
    threshold is read while a program is traced, so an engine is asked for and
    fed under the threshold it is to run. Who feeds one flushes what it fed."""
    from deepspeed_tpu.inference.v2.model_implementations import flat_model

    cfg, params, _ = tiny
    built = {}

    def engine_of(attention="dense_blocked_attention"):
        key = (attention, flat_model._EXPAND_MIN_TOKENS)
        if key not in built:
            built[key] = _engine(cfg, params, attention)
        return built[key]

    return engine_of


@pytest.fixture(scope="module")
def served(tiny, engine_of):
    """The program's logits for a 40-token prompt fed in chunks of 24 and 32
    positions decoded through the cache."""
    _, _, ids = tiny
    return _chunks_then_decode(engine_of(), ids, 40, 24)


def _published(cfg) -> dict:
    """The configuration-file keys the reference reads, from a program config."""
    return {"num_attention_heads": cfg.num_heads, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "first_k_dense_replace": cfg.moe_num_dense_layers, "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk_prob, "routed_scaling_factor": cfg.moe_route_scale}


def _reference(cfg, params, ids, positions, **switches):
    hp = {**glm_reference.hyper_from_published(_published(cfg)), **switches}
    return np.asarray(glm_reference.forward_logits(hp, params, jnp.asarray(ids[None]), positions))[0]


def _chunks_then_decode(engine, ids, n_prompt, chunk, uid=7, flush=True):
    """Logits at the last position of a prompt fed in chunks of ``chunk`` and
    at every further position of ``ids``, decoded through the cache."""
    for c0 in range(0, n_prompt, chunk):
        out = engine.put([uid], [ids[c0:min(c0 + chunk, n_prompt)]], sample=None)
    got = [np.asarray(out, np.float32)[0]]
    for j in range(n_prompt, len(ids)):
        got.append(np.asarray(engine.put([uid], [ids[j:j + 1]], sample=None), np.float32)[0])
    if flush:
        engine.flush(uid)
    return np.stack(got)


def _rel(got, ref):
    return np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


TOL = 2e-5  # float32 on both sides, the same routing: the order of float32 sums (measured 1e-6 to 3e-6)


@pytest.mark.parametrize("attention,n_prompt,chunk", [
    ("dense_blocked_attention", 40, 24),   # a chunk edge inside a KV block (24), the prompt's end inside one
    ("dense_blocked_attention", 48, 16),   # every chunk edge and the prompt's end ON a block's edge
    ("paged_pallas_attention", 40, 24),    # the decode kernel's body over the latent pool (the interpreter)
    ("paged_pallas_attention", 64, 64),    # one chunk; 8 positions decoded across a block's edge
])
def test_chunked_prefill_and_decode_through_the_latent_cache_match_the_expanded_reference(tiny, engine_of, attention,
                                                                                         n_prompt, chunk):
    """The program attends in the absorbed form over cached latents; the
    reference expands per-head keys and values and caches nothing. Equal
    logits at every position hold the absorption, the cached entry (after the
    norm and the rope), the rope on the one shared key part, the score scale
    and the router."""
    cfg, params, ids = tiny
    got = _chunks_then_decode(engine_of(attention), ids, n_prompt, chunk)
    rel = _rel(got, _reference(cfg, params, ids, list(range(n_prompt - 1, len(ids)))))
    assert rel.max() < TOL, rel


def test_the_tiled_kernels_body_attends_a_latent_pool(tiny):
    """``paged_attn_q_tiled`` through the interpreter on a latent pool (a tile
    of 8 tokens x 4 heads, a chunk with history, a decode row beside it)
    against the gather reference."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(1)
    W, dv, nq, bs = 128, 24, 4, BLOCK
    pool = jnp.asarray(rng.normal(size=(8 * bs, 1, W)), jnp.float32)
    tables = jnp.asarray([[3, 5, 1, 0], [2, 7, 0, 0]], jnp.int32)
    # row 0: 24 tokens after 20 cached; row 1: one decode token at position 17; then the pad run
    seq_idx = jnp.asarray([0] * 24 + [1] + [0] * 7, jnp.int32)
    pos = jnp.asarray(list(range(20, 44)) + [17] + [0] * 7, jnp.int32)
    q = jnp.asarray(rng.normal(size=(32, nq, W)), jnp.float32)
    kw = dict(value_dim=dv, softmax_scale=0.25)
    want = pa.paged_attention_reference(q, pool, None, tables, seq_idx, pos, bs, **kw)
    for q_tile in (8, 1):
        got = pa._pallas_paged(q, pool, None, tables, seq_idx, pos, block_size=bs, interpret=True,
                               q_tile=q_tile, **kw)
        assert got.shape == (32, nq, dv)
        np.testing.assert_allclose(np.asarray(got)[:25], np.asarray(want)[:25], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the form of the attention follows the tokens a row is fed (PR 40)
# ---------------------------------------------------------------------------

@pytest.fixture
def long_rows_of_8(monkeypatch):
    """The tiny engine's rows count as long from 8 tokens on (the constant is
    768: no row of a 64-token batch reaches it)."""
    from deepspeed_tpu.inference.v2.model_implementations import flat_model

    monkeypatch.setattr(flat_model, "_EXPAND_MIN_TOKENS", 8)
    return flat_model


@pytest.mark.parametrize("t_bucket", [8, 16, 32, 64])
def test_a_buckets_plan_is_a_slot_a_threshold_of_tokens_up_to_two(tiny, long_rows_of_8, t_bucket):
    """The module's engines pad every ``put`` to the 64-token bucket (one
    program a module); what a smaller bucket's program would be given is
    still ``expanded_plan``'s to say."""
    assert long_rows_of_8.expanded_plan(tiny[0], t_bucket, 128 // BLOCK, BLOCK, 4) == (min(2, t_bucket // 8), 128 // BLOCK)


# (tokens cached, tokens fed) a row of ONE put; the expanded form takes the FIRST TWO rows fed 8 or more
_MIXED = {
    "a_chunk_beside_decode_rows": [(20, 1), (16, 24), (9, 1)],
    "two_long_rows": [(5, 16), (30, 1), (16, 24)],
    "no_long_row": [(20, 1), (12, 7), (9, 1)],
    "at_the_threshold_and_under_it": [(12, 8), (10, 7), (3, 1)],
    "a_chunk_with_no_history": [(0, 24), (20, 1), (31, 1)],
    "a_third_long_row_stays_absorbed": [(4, 12), (16, 16), (7, 9), (20, 1)],
    "a_long_row_to_the_tables_last_block": [(100, 28), (20, 1)],
}


@pytest.mark.parametrize("attention", ["dense_blocked_attention", "paged_pallas_attention"])
@pytest.mark.parametrize("case", sorted(_MIXED))
def test_long_rows_attended_expanded_beside_the_rest_absorbed(tiny, engine_of, long_rows_of_8, case, attention):
    """One ``put`` whose rows are a mix: the logits of every row equal the
    float32 reference's and those of an engine that attends absorbed alone,
    and the rows the program attended expanded are the first two fed at least
    the threshold, by the span's count and by the call it traced."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    cfg, params, _ = tiny
    rows = _MIXED[case]
    rng = np.random.default_rng(len(case))
    seqs = [rng.integers(0, cfg.vocab_size, size=seen + new, dtype=np.int32) for seen, new in rows]
    want = np.stack([_reference(cfg, params, ids, [len(ids) - 1])[0] for ids in seqs])

    def served(engine):
        for uid, (ids, (seen, _)) in enumerate(zip(seqs, rows)):
            for c0 in range(0, seen, 64):  # the history, in chunks the engine's batch holds
                engine.put([uid], [ids[c0:min(c0 + 64, seen)]], sample=None)
        out = np.asarray(engine.put(list(range(len(rows))), [ids[seen:] for ids, (seen, _) in zip(seqs, rows)],
                                    sample=None), np.float32)
        for uid in range(len(rows)):
            engine.flush(uid)
        return out

    engine = engine_of(attention)
    got = served(engine)
    assert _rel(got, want).max() < TOL
    fed = sum(new for _, new in rows)
    t_bucket = next(b for b in engine.batch.token_buckets if b >= fed)   # (the module's engines pad to ONE bucket)
    long_rows = [r for r in rows if r[1] >= 8][:2]
    pairs = lambda seen, new: new * seen + new * (new + 1) // 2
    said = engine._attn_span_args([s for s, _ in rows], [n for _, n in rows], t_bucket)
    assert said["attn_pairs"] == cfg.num_layers * sum(pairs(*r) for r in rows)
    assert said["attn_expanded_pairs"] == cfg.num_layers * sum(pairs(*r) for r in long_rows)
    plan = long_rows_of_8.expanded_plan(cfg, t_bucket, 128 // BLOCK, BLOCK, 4)
    assert plan == (min(2, t_bucket // 8), 128 // BLOCK)
    if attention == "paged_pallas_attention":  # the module that records its calls: the second one was traced
        assert pa.kernel_choice(t_bucket, 2 * plan[0] + 1, plan[1])["kernel"] == "paged_attn_interpreted"
        assert engine._kernel_of(t_bucket, 4) == "paged_attn_interpreted:1:interpret+paged_attn_interpreted:8:interpret"
    # absorbed alone: the same rows through an engine whose rows never count as long
    long_rows_of_8._EXPAND_MIN_TOKENS = 768
    alone = served(engine_of(attention))
    assert _rel(got, alone).max() < TOL


@pytest.mark.parametrize("n_blocks", [0, 1, 2, 3, 4, 5])
def test_the_expansion_walks_whole_segments_up_to_the_rows_length(long_rows_of_8, n_blocks, monkeypatch):
    """``_expand_latents`` at segments of 2 blocks under a table of 5 columns
    (not a whole number of segments): the blocks of ``ceil(n_blocks / 2)``
    segments hold ``[ckv W_K_h | kr]`` and ``ckv W_V_h`` of the row's entries,
    the last segment of a full table starting a block early; no block past
    them is touched, in this slot or the other."""
    fm = long_rows_of_8
    monkeypatch.setattr(fm, "_EXPAND_SEGMENT_BLOCKS", 2)
    cfg = glm_config("tiny", dtype=jnp.float32)
    c, nope, rope, dv, nq, d = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, \
        cfg.num_heads, cfg.head_dim
    rng = np.random.default_rng(n_blocks)
    cols, bs, W = 5, 4, 40
    entries = rng.normal(size=(9, bs, W)).astype(np.float32)
    entries[..., c + rope:] = 0.0
    row_blocks = np.asarray([7, 2, 8, 0, 5], np.int32)
    w_k = rng.normal(size=(nq, c, nope)).astype(np.float32)
    w_v = rng.normal(size=(nq, c, dv)).astype(np.float32)
    ws = (jnp.full((nq, 2 * cols, bs, d), 7.0), jnp.full((nq, 2 * cols, bs, d), 7.0))
    k, v = (np.asarray(a).reshape(nq, 2, cols, bs, d).swapaxes(0, 1) for a in jax.jit(
        lambda n: fm._expand_latents(cfg, bs, jnp.asarray(entries), jnp.asarray(row_blocks), n, jnp.asarray(w_k),
                                     jnp.asarray(w_v), ws, 1))(n_blocks))
    made = {0: [], 1: [0, 1], 2: [0, 1], 3: [0, 1, 2, 3], 4: [0, 1, 2, 3], 5: [0, 1, 2, 3, 4]}[n_blocks]
    e = entries[row_blocks]                                                    # [cols, bs, W]
    want_k = np.concatenate([np.einsum("jtc,hcn->hjtn", e[..., :c], w_k),
                             np.broadcast_to(e[None, ..., c:c + rope], (nq, cols, bs, rope))], axis=-1)
    want_v = np.pad(np.einsum("jtc,hcv->hjtv", e[..., :c], w_v), ((0, 0), ) * 3 + ((0, d - dv), ))
    np.testing.assert_allclose(k[1][:, made], want_k[:, made], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v[1][:, made], want_v[:, made], rtol=1e-5, atol=1e-5)
    rest = [j for j in range(cols) if j not in made]
    assert (k[0] == 7.0).all() and (v[0] == 7.0).all() and (k[1][:, rest] == 7.0).all() and (v[1][:, rest] == 7.0).all()


def test_the_workspace_is_planned_from_static_shapes():
    """``expanded_plan`` at the published widths: two rows of the whole table
    for the cell's 2,048-token program (1.35e9 bytes of K and V), one for a
    1,024-token one, none under the threshold, for a model without a latent
    cache, or where values are wider than the scores' heads; a table so wide
    that two rows would pass the workspace's bound is cut to what fits, and
    ``expanded_slots`` leaves a row past the cut, or past the static count,
    absorbed."""
    from deepspeed_tpu.inference.v2.model_implementations import flat_model as fm

    cfg = glm_config("4.7-flash", dtype=jnp.bfloat16)
    assert fm.expanded_plan(cfg, 2048, 257, 128) == (2, 257)
    assert fm.expanded_workspace_bytes(cfg, 2048, 257, 128) == 2 * 257 * 128 * 20 * 512 * 2 == 1347420160
    assert fm.expanded_plan(cfg, 1024, 257, 128) == (1, 257) and fm.expanded_plan(cfg, 512, 257, 128) == (0, 0)
    assert fm.expanded_workspace_bytes(cfg, 512, 257, 128) == 0
    assert fm.expanded_plan(llama2_config("tiny"), 2048, 65, 128) == (0, 0)
    assert fm.expanded_workspace_bytes(llama2_config("tiny"), 2048, 65, 128) == 0
    wide = fm.expanded_plan(cfg, 2048, 1024, 128)                              # a table of 131,072 tokens
    assert wide == (2, 409) and fm.expanded_workspace_bytes(cfg, 2048, 1024, 128) <= fm._EXPAND_WORKSPACE_BYTES
    new, total = np.asarray([2040, 1, 900, 768, 767]), np.asarray([60000, 900, 30000, 768, 4000])
    assert fm.expanded_slots(new, total, 2, 409, 128, xp=np).tolist() == [-1, -1, 0, 1, -1]    # 60,000 > 409 x 128
    assert fm.expanded_slots(new, total, 1, 1024, 128, xp=np).tolist() == [0, -1, -1, -1, -1]
    assert np.asarray(fm.expanded_slots(jnp.asarray(new), jnp.asarray(total), 2, 1024, 128)).tolist() == [0, -1, 1, -1, -1]


@pytest.mark.parametrize("switch,value", [("rope_key", False), ("kv_norm", False), ("selection_bias", False),
                                          ("route_scale", 1.0), ("score_dim", 12)])
def test_the_reference_without_one_mechanism_is_far_from_the_program(tiny, served, switch, value):
    """The controls the chip check runs, at the small size: the same
    comparison FAILS against a reference with the rope key part zeroed, the
    latent's norm left out, the selection bias ignored, the routed scaling
    factor 1.0, or the score scaled by ``1 / sqrt(nope)`` alone."""
    cfg, params, ids = tiny
    rel = _rel(served, _reference(cfg, params, ids, list(range(39, len(ids))), **{switch: value}))
    assert np.quantile(rel, 0.25) > 100 * TOL, rel


def test_a_prefix_hit_on_latent_blocks_gives_the_references_logits(tiny):
    """A second request that shares the first's 40-token prompt takes its two
    whole latent blocks from the radix tree (and, the prompt repeated exactly,
    the partial tail by copy-on-write), computes the rest, and reads the
    reference's logits at every position it decodes."""
    cfg, params, ids = tiny
    eng = _engine(cfg, params, prefix_cache=PrefixCacheConfig(enabled=True))
    _chunks_then_decode(eng, ids[:56], 40, 24, uid=1)
    other = np.concatenate([ids[:40], ids[50:66]])          # the same prompt, then another continuation
    got = _chunks_then_decode(eng, other, 40, 40, uid=2)
    pc = eng.prefix_cache
    assert pc.stats["hits"] >= 1 and pc.stats["cached_tokens"] >= 2 * BLOCK, pc.stats
    rel = _rel(got, _reference(cfg, params, other, list(range(39, len(other)))))
    assert rel.max() < TOL, rel
    # an exact repeat of a published sequence: the whole blocks are shared, the partial tail is copied
    cow = pc.stats["cow_copies"]
    got = _chunks_then_decode(eng, ids[:56], 40, 40, uid=3)
    assert pc.stats["cow_copies"] >= cow
    assert _rel(got, _reference(cfg, params, ids[:56], list(range(39, 56)))).max() < TOL


def test_rollback_rewinds_a_latent_sequence(tiny):
    """``rollback_to`` drops the tail of a latent sequence; what is decoded
    after it is the reference's continuation of the kept prefix."""
    cfg, params, ids = tiny
    eng = _engine(cfg, params)
    _chunks_then_decode(eng, ids[:52], 40, 24, uid=4, flush=False)
    seq = eng.state_manager.get_sequence(4)
    assert seq.seen_tokens == 52
    eng.state_manager.rollback_to(seq, 45)
    other = np.concatenate([ids[:45], ids[60:68]])
    got = np.stack([np.asarray(eng.put([4], [other[j:j + 1]], sample=None), np.float32)[0]
                    for j in range(45, len(other))])
    assert _rel(got, _reference(cfg, params, other, list(range(45, len(other))))).max() < TOL


def test_export_import_and_the_host_tier_round_trip_latent_blocks(tiny):
    """A latent sequence's whole blocks leave one engine through
    ``export_sequence_kv`` (one part a block, no V), land in another's host
    tier through ``install_prefix_kv``, are promoted on the hit, and the
    request that hits them reads the reference's logits; a demotion to the
    host pool and back changes nothing either."""
    from deepspeed_tpu.serving.handoff import _payload_crc

    cfg, params, ids = tiny
    src = _engine(cfg, params)
    _chunks_then_decode(src, ids[:48], 48, 24, uid=5, flush=False)
    chunks, payloads = src.export_sequence_kv(5, ids[:48])
    assert len(chunks) == 3 and all(p[0].shape == (cfg.num_layers, BLOCK, 1, 128) for p in payloads)
    assert all(p[1] is None and p[2] is None for p in payloads), "a latent block has one part"
    assert len({_payload_crc(p) for p in payloads}) == 3

    tier = PrefixCacheConfig(enabled=True, host_tier=HostTierConfig(host_blocks=16))
    dst = _engine(cfg, params, prefix_cache=tier)
    assert dst.install_prefix_kv(chunks, payloads) == 3
    got = _chunks_then_decode(dst, ids[:60], 52, 52, uid=6)
    assert dst.prefix_cache.stats["promotions"] >= 3 and dst.prefix_cache.stats["cached_tokens"] >= 48
    assert _rel(got, _reference(cfg, params, ids[:60], list(range(51, 60)))).max() < TOL
    # spill what the tree now holds to the host pool and restore it on the next hit
    assert dst.prefix_cache.demote_cold(8) >= 1
    deadline = time.time() + 5
    while dst.tiered_store.queued and time.time() < deadline:
        time.sleep(0.01)
    promoted = dst.prefix_cache.stats["promotions"]
    got = _chunks_then_decode(dst, ids[:60], 52, 52, uid=8)
    assert dst.prefix_cache.stats["promotions"] > promoted
    assert _rel(got, _reference(cfg, params, ids[:60], list(range(51, 60)))).max() < TOL
    dst.shutdown()


def test_the_pool_holds_one_latent_entry_a_token_a_layer():
    """At the published sizes a token's entry in a layer is 512 + 64 = 576
    values, padded to whole 128-lane tiles as the chip's tiled memory pads it
    whatever the shape says: 640 values, 1,280 bytes in bf16 (per-head K and V
    of this model would be 20 x (256 + 256) x 2 = 20,480), ONE pool and no V."""
    cfg = glm_config("4.7-flash", num_layers=8)
    assert cfg.kv_entry == ((1, 640), )
    kv = BlockedKVCache(8, cfg.num_kv_heads, cfg.head_dim, num_blocks=2, block_size=128, entry=cfg.kv_entry)
    assert kv.v_pool is None and kv.k_pool.shape == (8, 256, 1, 640) and len(kv.pools()) == 1
    assert kv.block_bytes() == 128 * 1280 * 8 and kv.memory_bytes() == 2 * kv.block_bytes()
    dense = llama2_config("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2)
    assert dense.kv_entry == ((2, 16), (2, 16))
    assert BlockedKVCache(2, 2, 16, num_blocks=2, block_size=8).block_bytes() == 2 * 8 * 2 * 16 * 2 * 2


def test_the_published_preset_is_the_catalog_row():
    """``glm_config("4.7-flash")`` against the catalog's ``config``, key by key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog in this container")
    rows = [json.loads(line) for line in open(path)]
    pub = next(r for r in rows if r["name"] == "GLM-4.7-Flash")["config"]
    cfg = glm_config("4.7-flash")
    got = {"hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
           "max_position_embeddings": cfg.max_seq_len, "moe_intermediate_size": cfg.expert_size,
           "norm_topk_prob": cfg.moe_norm_topk_prob, "num_attention_heads": cfg.num_heads,
           "n_routed_experts": cfg.moe_num_experts, "n_shared_experts": cfg.moe_num_shared_experts,
           "routed_scaling_factor": cfg.moe_route_scale, "num_experts_per_tok": cfg.moe_top_k,
           "first_k_dense_replace": cfg.moe_num_dense_layers, "num_hidden_layers": cfg.num_layers,
           "num_key_value_heads": cfg.num_kv_heads, "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
           "tie_word_embeddings": cfg.tie_embeddings, "q_lora_rank": cfg.q_lora_rank,
           "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim, "vocab_size": cfg.vocab_size,
           "attention_bias": cfg.use_bias, "partial_rotary_factor": cfg.rotary_dim / cfg.qk_rope_head_dim}
    assert got == {k: pub[k] for k in got}
    assert cfg.head_dim == 256 and cfg.moe_score_func == "sigmoid" and cfg.moe_route_bias and cfg.mlp == "swiglu"
    tiny = glm_config("tiny")
    sizes = (tiny.qk_nope_head_dim, tiny.qk_rope_head_dim, tiny.v_head_dim, tiny.kv_lora_rank, tiny.q_lora_rank)
    assert len(set(sizes)) == 5, "the tiny preset's sizes all differ, so a mix-up of two of them fails"


@pytest.mark.parametrize("call", ["forward_hidden", "forward_with_cache", "pipeline_stages", "int8_kv",
                                  "speculative_config", "speculate_decode"])
def test_what_has_no_latent_form_is_refused_by_name(tiny, call):
    cfg, params, ids = tiny
    if call == "forward_hidden":
        with pytest.raises(NotImplementedError, match="latent attention"):
            tfm.forward_hidden(cfg, params, jnp.asarray(ids[None, :8]))
    elif call == "forward_with_cache":
        with pytest.raises(NotImplementedError, match="latent attention"):
            tfm.forward_with_cache(cfg, params, jnp.asarray(ids[None, :8]), None)
    elif call == "pipeline_stages":
        with pytest.raises(NotImplementedError, match="latent attention"):
            tfm._stage_scan_fn(cfg)
    elif call == "int8_kv":
        with pytest.raises(NotImplementedError, match="int8 KV cache beside a latent entry"):
            _engine(cfg, params, kv_dtype="int8")
    elif call == "speculative_config":
        with pytest.raises(NotImplementedError, match="latent attention"):
            _engine(cfg, params, speculative=SpeculativeConfig(mode="ngram", k=2))
    else:
        eng = _engine(cfg, params)
        eng.put([1], [ids[:8]], sample=None)
        with pytest.raises(NotImplementedError, match="latent attention"):
            eng.speculate_decode([1], [ids[8:9]], [ids[9:11]])


def test_a_step_span_says_what_attention_had_to_do(tiny, tmp_path):
    """``attn_pairs``, ``attn_ctx_tokens`` and ``kv_entry_bytes`` on the step
    spans, by hand: a 24-token chunk after 16 cached tokens beside a decode
    row at 40, over 5 layers; then a decode horizon of 4."""
    from benchmark.lib import program_spans

    cfg, params, ids = tiny

    def serve(eng, a, b):
        eng.put([a], [ids[:16]], sample=None)
        eng.put([b], [ids[:40]], sample=None)
        eng.put([a, b], [ids[16:40], ids[40:41]], sample=None)
        eng.decode([a, b], [ids[40:41], ids[41:42]], 4)

    eng = _engine(cfg, params)
    serve(eng, 1, 2)  # compile first: the traced run is warm
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        serve(eng, 3, 4)
    finally:
        jax.profiler.stop_trace()
    (path, ) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    trace = program_spans.read(str(path))
    prefill = program_spans.spans_named(trace, "serving/prefill")[-1].args
    pairs = sum(16 + i + 1 for i in range(24)) + 41
    assert (prefill["attn_pairs"], prefill["attn_ctx_tokens"]) == (5 * pairs, 5 * (40 + 41))
    assert prefill["kv_entry_bytes"] == 128 * 4  # one float32 entry of 128 lanes
    (decode, ) = program_spans.spans_named(trace, "serving/decode")
    assert decode.args["attn_pairs"] == 5 * (sum(40 + j + 1 for j in range(4)) + sum(41 + j + 1 for j in range(4)))
    assert decode.args["attn_ctx_tokens"] == 5 * (44 + 45) and decode.args["kv_entry_bytes"] == 512


def test_a_published_latent_attention_block_converts_to_the_tree_the_forward_reads(tiny):
    """``parameter_spec.latent_attention_rows`` on a hand-made checkpoint in
    the published layout (torch ``[out, in]``, ``kv_b_proj`` whole, the rope
    columns interleaved): the converted arrays have the program's shapes, the
    two parts of ``W_kvb`` are its columns cut by head, and the interleaved
    pairs (2i, 2i + 1) land on the halves (i, i + rope / 2)."""
    from deepspeed_tpu.inference.v2.model_implementations import parameter_spec as ps

    cfg, params, _ = tiny
    rng = np.random.default_rng(2)
    nq, d, c, rope, nope, dv = cfg.num_heads, cfg.head_dim, cfg.kv_lora_rank, cfg.qk_rope_head_dim, 12, 16
    shapes = {"q_a_proj.weight": (40, 64), "q_a_layernorm.weight": (40, ), "q_b_proj.weight": (nq * d, 40),
              "kv_a_proj_with_mqa.weight": (c + rope, 64), "kv_a_layernorm.weight": (c, ),
              "kv_b_proj.weight": (nq * (nope + dv), c), "o_proj.weight": (64, nq * dv)}
    sd = {f"model.layers.{i}.self_attn.{name}": rng.normal(size=shape).astype(np.float32)
          for i in range(cfg.num_layers) for name, shape in shapes.items()}
    blocks = ps.convert_with_spec(sd, cfg, ps.latent_attention_rows())["blocks"]
    for name, got in blocks.items():
        assert got.shape == params["blocks"][name].shape, name
    kvb = sd["model.layers.2.self_attn.kv_b_proj.weight"].reshape(nq, nope + dv, c)
    np.testing.assert_array_equal(blocks["wkv_b_k"][2], kvb[:, :nope].transpose(0, 2, 1))
    np.testing.assert_array_equal(blocks["wkv_b_v"][2], kvb[:, nope:].transpose(0, 2, 1))
    qb = sd["model.layers.1.self_attn.q_b_proj.weight"].T.reshape(40, nq, d)
    got = blocks["wq_b"][1].reshape(40, nq, d)
    np.testing.assert_array_equal(got[..., :nope], qb[..., :nope])
    np.testing.assert_array_equal(got[..., nope:nope + rope // 2], qb[..., nope::2])
    np.testing.assert_array_equal(got[..., nope + rope // 2:], qb[..., nope + 1::2])
    kva = sd["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"].T
    np.testing.assert_array_equal(blocks["wkv_a"][0][:, :c], kva[:, :c])
    np.testing.assert_array_equal(blocks["wkv_a"][0][:, c:c + rope // 2], kva[:, c::2])
