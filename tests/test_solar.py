"""The Solar Open 2 family (``models/solar.py``: three delta-rule
linear-attention layers to one gated no-rope GQA layer, a share of the experts)
on the serving path at a small size, on seeded random weights, against the
benchmark's plain float32 reference (``benchmark/lib/solar_reference.py``, which
imports nothing of the program and runs the delta rule token by token): a
prompt in uneven chunks, mixed ``put`` steps, a multi-step decode horizon; the
state slots' life (flush, reuse, cancel); bucket padding as a no-op; both
forms of the rule against the recurrence at boundaries that do not divide the
kernel's tile; the shares adding up; the spans' counts; the refusals. Tiny
shapes: hidden 64, 6/2 heads of 16 in the softmax layer, 4 linear heads of 16,
16 experts top-2 of width 48 beside one shared expert, one period of 4 layers.
ONE engine serves most tests, so that few programs compile."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmark.lib import solar_reference  # noqa: E402
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import HostTierConfig, PrefixCacheConfig, SpeculativeConfig  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.prefix_cache import PrefixKVCache  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.tiered_store import TieredBlockStore  # noqa: E402
from deepspeed_tpu.models import TransformerLM, solar_config  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.monitor.trace import get_tracer  # noqa: E402
from deepspeed_tpu.ops.pallas import kda  # noqa: E402

BLOCK = 16


@pytest.fixture(autouse=True)
def _fresh_tracer():
    get_tracer().reset()
    yield
    get_tracer().reset()


def _published(cfg, held=None, first=0) -> dict:
    """The configuration-file keys the reference reads, from a program config."""
    return {"num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.norm_eps, "num_hidden_layers": cfg.num_layers, "gqa_layers": list(cfg.kv_layers),
            "linear_attn_config": {"num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
                                   "short_conv_kernel_size": cfg.kda_conv_size},
            "kda_allow_neg_eigval": cfg.kda_neg_eigval, "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk_prob, "routed_scaling_factor": cfg.moe_route_scale,
            "n_routed_experts_published": cfg.moe_num_experts, "first_expert": first,
            "n_routed_experts": cfg.experts_held if held is None else held}


@pytest.fixture(scope="module")
def tiny():
    """The tiny model holding experts 4-11 of 16, its parameters (a selection
    bias wide enough to change the chosen set) and a seeded sequence."""
    cfg = solar_config("tiny", dtype=jnp.float32, moe_experts_held=8, moe_first_expert=4)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    params["blocks"]["gate_bias"] = params["blocks"]["gate_bias"] * 20.0
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, size=96, dtype=np.int32)
    return cfg, params, ids


def _engine(cfg, params, **kwargs):
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=32, max_ragged_sequence_count=4,
                              max_context=128, token_buckets=(32, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=BLOCK, num_kv_blocks=32, kv_dtype=kwargs.pop("kv_dtype", jnp.float32),
                                       state_manager=sm, **kwargs)
    return InferenceEngineV2(TransformerLM(cfg), icfg, params=params)


@pytest.fixture(scope="module")
def engine(tiny):
    return _engine(*tiny[:2])


def _state(eng, uid):
    kv = eng.state_manager.kv_cache
    slot = eng.state_manager.get_sequence(uid).state_slot
    return np.asarray(kv.state_pool[:, slot]), np.asarray(kv.tail_pool[:, slot])


@pytest.fixture(scope="module")
def served(tiny, engine):
    """One sequence through the engine as traffic is: a 45-token prompt in
    chunks of 32 and 13 (neither a multiple of the tile of 8), 3 positions as
    one-token rows beside another prompt's chunks, 8 through the decode
    horizon in two calls of 4, 2 more one-token puts. Logits by position, the
    final sequence and the state read back before the flush."""
    cfg, params, ids = tiny
    got = {}
    engine.put([1], [ids[:32]], sample=None)
    got[44] = np.asarray(engine.put([1], [ids[32:45]], sample=None))[0]
    for i in range(3):  # ours first, the other prompt's chunk (24 + 7 + 7 tokens) behind it
        other = ids[50 + 8 * i:50 + 8 * i + (24 if i == 0 else 7)]
        got[45 + i] = np.asarray(engine.put([1, 2], [ids[45 + i:46 + i], other], sample=None))[0]
    engine.flush(2)
    seq = [int(t) for t in ids[:48]]
    nxt = int(got[47].argmax())
    for _ in range(2):
        toks = np.asarray(engine.decode([1], [np.asarray([nxt], np.int32)], 4))[0]
        seq += [nxt] + [int(t) for t in toks[:-1]]
        nxt = int(toks[-1])
    for t in ids[90:92]:
        seq.append(int(t))
        got[len(seq) - 1] = np.asarray(engine.put([1], [np.asarray(seq[-1:], np.int32)], sample=None))[0]
    state, tail = _state(engine, 1)
    engine.flush(1)
    return got, np.asarray(seq, np.int32), state, tail


def _reference(tiny, seq, positions, **switches):
    cfg, params, _ = tiny
    hp = {**solar_reference.hyper_from_published(_published(cfg, first=cfg.moe_first_expert)), **switches}
    logits, states = solar_reference.forward(hp, params, jnp.asarray(seq), list(positions))
    return np.asarray(logits), np.asarray(states)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_chunks_riding_rows_and_the_horizon_match_the_token_by_token_reference(tiny, served):
    got, seq, state, _ = served
    positions = sorted(got)
    assert positions == [44, 45, 46, 47, 56, 57] and len(seq) == 58
    want, want_states = _reference(tiny, seq, positions)
    assert max(_rel(got[p], w) for p, w in zip(positions, want)) < 2e-5
    assert max(_rel(s, w) for s, w in zip(state, want_states)) < 2e-5 and state.shape == (3, 4, 16, 16)
    # the horizon fed back the model's own greedy tokens: the reference's argmax at the positions before them
    horizon, _ = _reference(tiny, seq, range(47, 55))
    assert list(horizon.argmax(-1)) == list(seq[48:56])


@pytest.mark.parametrize("switch,value", [("decay", False), ("beta_scale", 1.0), ("l2_norm", False),
                                          ("selection_bias", False), ("gate", False)])
def test_the_reference_without_one_mechanism_is_far_from_the_program(tiny, served, switch, value):
    got, seq, state, _ = served
    want, want_states = _reference(tiny, seq, sorted(got), **{switch: value})
    assert min(_rel(got[p], w) for p, w in zip(sorted(got), want)) > 1e-3
    if switch in ("decay", "beta_scale", "l2_norm"):  # the rule itself: the first linear layer's state shows it
        assert _rel(state[0], want_states[0]) > 0.05


def test_a_freed_slots_old_state_never_reaches_a_new_sequence(tiny, engine, served):
    """Flush, reuse, cancel: the slot of a flushed sequence goes to the next
    one with what it held still in it, and the new sequence's first token
    starts from zero whatever that is; a cancelled request's terminal rewind
    is allowed and frees the slot too."""
    cfg, params, ids = tiny
    kv = engine.state_manager.kv_cache
    assert kv.free_state_slots == kv.state_slots == 4 and float(jnp.abs(kv.state_pool).max()) > 0  # dirty slots
    kv.state_pool = kv.state_pool + 7.0   # and dirtier: nothing may read it
    kv.tail_pool = kv.tail_pool + 7.0
    engine.put([5], [ids[:32]], sample=None)
    got = np.asarray(engine.put([5], [ids[32:45]], sample=None))[0]
    assert _rel(got, served[0][44]) < 1e-5 and kv.free_state_slots == 3
    seq = engine.state_manager.get_sequence(5)
    with pytest.raises(NotImplementedError, match="recurrent state layer"):
        engine.state_manager.rollback_to(seq, 40)
    engine.state_manager.rollback_to(seq, 40, final=True)  # what the scheduler does to a cancelled request
    engine.flush(5)
    assert kv.free_state_slots == 4 and seq.state_slot == -1
    kv.state_pool, kv.tail_pool = kv.state_pool - 7.0, kv.tail_pool - 7.0


def test_bucket_padding_touches_no_state(tiny, engine):
    """A step whose tokens and rows are mostly padding (5 tokens in a bucket of
    8, 1 row of 4) and the warm-up's all-padding descriptor leave every slot
    but the fed row's exactly as it was."""
    cfg, params, ids = tiny
    kv = engine.state_manager.kv_cache
    kv.state_pool, kv.tail_pool = kv.state_pool.at[:].set(3.0), kv.tail_pool.at[:].set(3.0)
    engine.put([7], [ids[:5]], sample=None)
    slot = engine.state_manager.get_sequence(7).state_slot
    others = [s for s in range(4) if s != slot]
    assert float(jnp.abs(kv.state_pool[:, others] - 3.0).max()) == 0 and float(jnp.abs(kv.tail_pool[:, others] - 3.0).max()) == 0
    assert float(jnp.abs(kv.state_pool[:, slot] - 3.0).min()) > 0
    before = np.asarray(kv.state_pool), np.asarray(kv.tail_pool)
    engine.decode([7], [ids[5:6]], 4)  # one live row of four
    assert np.array_equal(np.asarray(kv.state_pool)[:, others], before[0][:, others])
    engine.flush(7)
    before = np.asarray(kv.state_pool), np.asarray(kv.tail_pool)
    engine._compiled.pop(("decode", 4, 4, False))
    engine.warmup([4], [4])  # the zero descriptor: no live row at all
    assert np.array_equal(np.asarray(kv.state_pool), before[0]) and np.array_equal(np.asarray(kv.tail_pool), before[1])


def _rule_inputs(n, H=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(2, n, H, d))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(-4, 1.5, size=(n, H, d)))   # a channel keeps from 1% to 98% of its state a token
    b = 2 / (1 + np.exp(-rng.normal(size=(n, H))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, rng.normal(size=(n, H, d)), g, b)]


def _chunks_against_the_rule(n_tok, slots, fresh, interpret, T=64, seed=0):
    """``kda_chunks`` over one ragged batch of ``T`` flat tokens from a pool of
    12 random slots: every fed row's outputs and final state against the rule
    token by token (a fresh row from zero, the others from what their slots
    held), and every slot no fed row names bit-equal."""
    x = _rule_inputs(T, seed=seed)
    pool = jnp.asarray(np.random.default_rng(1).normal(size=(12, 4, 16, 16)), jnp.float32)
    o, new = jax.jit(lambda *a: kda.kda_chunks(*a, interpret=interpret))(*x, pool, jnp.asarray(slots), jnp.asarray(fresh),
                                                                         jnp.asarray(n_tok))
    new, untouched, t0 = np.asarray(new), np.ones(12, bool), 0
    for r, n in enumerate(n_tok):
        if n:
            oo, S = kda.recurrence_reference(*[a[t0:t0 + n] for a in x], jnp.zeros((4, 16, 16)) if fresh[r] else pool[slots[r]])
            assert float(jnp.abs(oo - o[t0:t0 + n]).max()) < 2e-6, (r, n)
            assert float(np.abs(np.asarray(S) - new[slots[r]]).max()) < 5e-6, (r, n)
            untouched[slots[r]] = False
            t0 += n
    assert np.array_equal(new[untouched], np.asarray(pool)[untouched])


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
def test_the_chunkwise_form_matches_the_recurrence_at_boundaries_that_do_not_divide(interpret):
    """Rows of 13, 1, 0, 21, 1 and 8 tokens in one ragged batch of 64 (tile 8):
    outputs and final states against the rule token by token, a fresh row
    from zero, the others from what their slots held; no other slot moves."""
    _chunks_against_the_rule(np.array([13, 1, 0, 21, 1, 8, 0, 0]), np.array([3, 0, 5, 7, 2, 9, 1, 1]),
                             np.array([0, 1, 0, 0, 0, 1, 0, 0]), interpret)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
@pytest.mark.parametrize("n_live", [0, 5])
def test_the_recurrent_step_advances_the_live_rows_alone(interpret, n_live):
    slots, fresh = np.array([3, 0, 5, 7, 2, 9, 1, 1]), np.array([0, 1, 0, 0, 0, 1, 0, 0])
    x = _rule_inputs(8, seed=2)
    pool = jnp.asarray(np.random.default_rng(1).normal(size=(12, 4, 16, 16)), jnp.float32)
    o, new = jax.jit(lambda *a: kda.kda_step(*a, interpret=interpret))(*x, pool, jnp.asarray(slots), jnp.asarray(fresh),
                                                                       jnp.asarray(n_live))
    want = np.asarray(pool).copy()
    for r in range(n_live):
        oo, S = kda.recurrence_reference(*[a[r:r + 1] for a in x], jnp.zeros((4, 16, 16)) if fresh[r] else pool[slots[r]])
        assert float(jnp.abs(oo[0] - o[r]).max()) < 1e-6
        want[slots[r]] = np.asarray(S)
    assert float(np.abs(want - np.asarray(new)).max()) < 1e-6


def test_a_tile_plan_gives_every_row_its_own_tiles():
    """A row of two or more tokens takes its own tiles, a row of one token
    none (the recurrent step's), and every row keeps its place in the flat
    order: the row of 21 still starts at token 14."""
    row, tok0, cnt, first, n_tiles = kda.tile_plan(np.array([13, 1, 0, 21]), 64, xp=np)
    assert int(n_tiles) == 2 + 0 + 0 + 3 and len(row) == 64 // 8 + 4
    assert list(row[:5]) == [0, 0, 3, 3, 3] and list(tok0[:5]) == [0, 8, 14, 22, 30]
    assert list(cnt[:5]) == [8, 5, 8, 8, 5] and list(first[:5]) == [True, False, True, False, False]
    assert not cnt[5:].any() and set(row[5:]) == {3}  # a dead tile names the last live one's row and holds nothing


# what a ragged batch may look like: (tokens a row, rows that open their sequence); the flat batch is 64 tokens
_SPLITS = {
    "ones-before-a-chunk": ([1, 1, 1, 13, 0, 0, 0, 0], [0, 0, 1, 0, 0, 0, 0, 0]),
    "ones-between-chunks": ([13, 1, 1, 21, 0, 0, 0, 0], [1, 0, 0, 0, 0, 0, 0, 0]),
    "ones-after-chunks": ([21, 10, 1, 1, 1, 0, 0, 0], [0, 1, 0, 0, 0, 0, 0, 0]),
    "a-fresh-one-token-row": ([1, 9, 1, 0, 0, 0, 0, 0], [1, 0, 0, 0, 0, 0, 0, 0]),
    "one-token-rows-alone": ([1, 1, 1, 1, 1, 1, 1, 1], [0, 1, 0, 0, 0, 0, 1, 0]),
    "no-one-token-row": ([2, 13, 8, 17, 0, 0, 0, 0], [0, 1, 0, 0, 0, 0, 0, 0]),
    "padded-rows-among-them": ([1, 0, 11, 0, 1, 0, 2, 1], [0, 0, 1, 0, 0, 1, 0, 1]),
    "every-place-of-a-tile": ([1, 7, 1, 9, 1, 15, 1, 3], [0, 0, 0, 1, 0, 0, 0, 0]),
    "nothing-fed": ([0, 0, 0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
@pytest.mark.parametrize("batch", list(_SPLITS))
def test_a_ragged_batch_is_split_by_what_each_row_is_fed(batch, interpret):
    """``kda_chunks`` sends a row fed ONE token through the recurrent step
    and a row fed more through the chunk scan, wherever each stands: outputs
    and states against the rule token by token, a fresh row from zero; the
    state of a row not fed (and of every slot no row names) bit-equal."""
    n_tok, fresh = (np.asarray(a) for a in _SPLITS[batch])
    _chunks_against_the_rule(n_tok, np.array([3, 0, 5, 7, 2, 9, 1, 10]), fresh, interpret, seed=3)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
@pytest.mark.parametrize("block", [1, 3])
def test_a_chunk_row_goes_on_where_the_block_of_tiles_before_it_left_its_state(monkeypatch, block, interpret):
    """The chunk scan is called a block of tiles at a time, as many times as
    live tiles fill blocks: with blocks of 1 and of 3 tiles, rows of 21 (fresh),
    1, 13, 1, 0 and 17 tokens (3 + 2 + 3 tiles) cross block boundaries in the
    middle of a row, and outputs and states are what the rule gives."""
    monkeypatch.setattr(kda, "_TILE_BLOCK", block)
    _chunks_against_the_rule(np.array([21, 1, 13, 1, 0, 17]), np.array([3, 0, 5, 7, 2, 9]), np.array([1, 0, 0, 1, 0, 0]),
                             interpret, T=56, seed=4)


@pytest.mark.parametrize("batch", list(_SPLITS))
def test_no_tile_holds_a_one_token_row_and_the_step_takes_them_all(batch):
    """The chunk scan's live tiles count the rows of two or more tokens
    alone; the step's live rows are the one-token rows, in order, each at the
    place the plan says."""
    n_tok = np.asarray(_SPLITS[batch][0])
    row, tok0, cnt, first, n_tiles = kda.tile_plan(n_tok, 64, xp=np)
    rows, place, n_live = kda.step_rows(n_tok, xp=np)
    ones = np.flatnonzero(n_tok == 1)
    assert int(n_live) == len(ones) and list(rows[:n_live]) == list(ones) and list(place[ones]) == list(range(len(ones)))
    assert int(n_tiles) == sum(-(-n // 8) for n in n_tok if n > 1)
    assert not set(row[:n_tiles]) & set(ones) and int(cnt.sum()) == sum(n for n in n_tok if n > 1)
    starts = np.cumsum(n_tok) - n_tok   # every tile's first token lies in its own row's run of the flat order
    assert all(starts[r] <= t < starts[r] + n_tok[r] for r, t in zip(row[:n_tiles], tok0[:n_tiles]))


def test_the_shares_add_up(tiny):
    """The 2 shares' routed parts plus the shared expert counted once equal
    the uncut layer: the reference's expert MLP with every expert held."""
    cfg, params, ids = tiny
    full = solar_config("tiny", dtype=jnp.float32)
    whole = TransformerLM(full).init(jax.random.PRNGKey(3))
    whole["blocks"]["gate_bias"] = whole["blocks"]["gate_bias"] * 20.0
    h = jnp.asarray(np.random.default_rng(5).normal(size=(12, 64)), jnp.float32)
    layer = 2
    blk = {k: jnp.asarray(v[layer]) for k, v in whole["blocks"].items() if k in ("gate_wg", "gate_bias", "shared_wi", "shared_wg",
                                                                                "shared_wo")}
    experts = {k: whole["blocks"][k] for k in ("moe_wi", "moe_wg", "moe_wo")}
    hp = solar_reference.hyper_from_published(_published(full))
    with jax.default_matmul_precision("highest"):
        uncut = solar_reference.mlp(h, blk, experts, layer, hp)
        shared = solar_reference._swiglu(h, blk["shared_wi"], blk["shared_wg"], blk["shared_wo"])
        total = shared
        for first in (0, 8):
            part = {k: v[:, first:first + 8] for k, v in experts.items()}
            total = total + solar_reference.mlp(h, blk, part, layer, {**hp, "first_expert": first, "n_held": 8}) - shared
    assert _rel(np.asarray(total), np.asarray(uncut)) < 1e-5 and _rel(np.asarray(shared), np.asarray(uncut)) > 0.1


def test_the_pools_are_of_two_kinds(tiny, engine):
    """K/V for the ONE softmax layer, a slot a tracked sequence for the three
    linear layers, which are no K/V layers."""
    cfg = tiny[0]
    kv = engine.state_manager.kv_cache
    assert cfg.kv_layers == (0, ) and cfg.state_layers == (1, 2, 3)
    assert cfg.kv_entry == ((2, 16), (2, 16))
    assert cfg.state_entry == ((4, 16, 16), (3, 3 * 4 * 16))
    assert kv.k_pool.shape == (1, 32 * BLOCK, 2, 16) and kv.num_layers == 1
    assert kv.state_pool.shape == (3, 4, 4, 16, 16) and kv.state_pool.dtype == jnp.float32
    assert kv.tail_pool.shape == (3, 4, 3, 192) and kv.state_entry_bytes() == 4 * 16 * 16 * 4 + 3 * 192 * 4
    assert len(kv.pools()) == 4 and kv.block_bytes() == 2 * BLOCK * 2 * 16 * 4
    assert not BlockedKVCache(2, 2, 16, 4, BLOCK).has_state


def test_the_published_preset_is_the_catalog_row():
    cfg = solar_config("open2-250b")
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        48, 4096, 196608, 64, 8, 128)
    assert cfg.kv_layers == tuple(range(0, 48, 4)) and len(cfg.state_layers) == 36
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size, cfg.kda_neg_eigval) == (64, 128, 4, True)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.expert_size, cfg.moe_num_shared_experts, cfg.moe_num_dense_layers) == (
        320, 8, 1280, 1, 0)
    assert cfg.state_entry == ((64, 128, 128), (3, 24576)) and cfg.rope_layer_types == () and cfg.attention_gate
    cut = solar_config("open2-250b", num_layers=4, gqa_layers=list(range(0, 48, 4)),
                       linear_attn_config={"num_heads": 64, "head_dim": 128, "short_conv_kernel_size": 4})
    assert cut.layer_types == ("full_attention", ) + ("linear_attention", ) * 3


@pytest.mark.parametrize("call", ["forward_hidden", "forward_with_cache", "pipeline_stages", "int8_kv", "speculative_config",
                                  "speculate_decode", "prefix_cache", "host_tier", "prefix_cache_config", "rollback_to",
                                  "export_sequence_kv", "every_layer_linear"])
def test_what_takes_state_to_be_blocks_is_refused_by_name(tiny, engine, call):
    cfg, params, ids = tiny
    kv = engine.state_manager.kv_cache
    if call == "forward_hidden":
        with pytest.raises(NotImplementedError, match="linear-attention layer"):
            tfm.forward_hidden(cfg, params, jnp.asarray(ids[None, :8]))
    elif call == "forward_with_cache":
        with pytest.raises(NotImplementedError, match="linear-attention layer"):
            tfm.forward_with_cache(cfg, params, jnp.asarray(ids[None, :8]), None)
    elif call == "pipeline_stages":
        with pytest.raises(NotImplementedError, match="linear-attention layer"):
            tfm._stage_scan_fn(cfg)
    elif call == "int8_kv":
        with pytest.raises(NotImplementedError, match="int8 KV cache beside a recurrent state layer"):
            _engine(cfg, params, kv_dtype="int8")
    elif call == "speculative_config":
        with pytest.raises(NotImplementedError, match="speculative decoding of a model with a recurrent state layer"):
            _engine(cfg, params, speculative=SpeculativeConfig(mode="ngram", k=2))
    elif call == "speculate_decode":
        with pytest.raises(NotImplementedError, match="speculate_decode .* recurrent state layer"):
            engine.speculate_decode([1], [ids[8:9]], [ids[9:11]])
    elif call == "prefix_cache":
        with pytest.raises(NotImplementedError, match="PrefixKVCache for a model with a recurrent state layer"):
            PrefixKVCache(kv)
    elif call == "host_tier":
        with pytest.raises(NotImplementedError, match="TieredBlockStore for a model with a recurrent state layer"):
            TieredBlockStore(kv, HostTierConfig(enabled=True, host_blocks=4))
    elif call == "prefix_cache_config":
        with pytest.raises(NotImplementedError, match="PrefixKVCache"):
            _engine(cfg, params, prefix_cache=PrefixCacheConfig(enabled=True))
    elif call in ("rollback_to", "export_sequence_kv"):
        engine.put([9], [ids[:8]], sample=None)
        try:
            if call == "rollback_to":
                with pytest.raises(NotImplementedError, match="rollback_to.*keeps no snapshot"):
                    engine.state_manager.rollback_to(engine.state_manager.get_sequence(9), 4)
            else:
                with pytest.raises(NotImplementedError, match="export_sequence_kv of a model with a recurrent state layer"):
                    engine.export_sequence_kv(9, ids[:8])
        finally:
            engine.flush(9)
    else:
        with pytest.raises(NotImplementedError, match="every layer 'linear_attention'"):
            solar_config("tiny", layer_types=("linear_attention", ) * 4)


def test_a_step_span_says_what_the_state_layers_and_the_one_kv_layer_had_to_do(tiny, engine, tmp_path):
    """``state_rows``, ``state_rows_stepped`` (of them, the rows whose state
    went through the recurrent step), ``state_bytes``, ``lin_tokens`` and the
    slots on the step spans, and ``attn_pairs`` / ``attn_ctx_tokens`` counted
    over the ONE layer that caches K and V, by hand: a 13-token chunk after 32
    cached tokens beside a one-token row at 5, then a decode horizon of 4."""
    from benchmark.lib import program_spans

    cfg, params, ids = tiny

    def serve(a, b):
        engine.put([a], [ids[:32]], sample=None)
        engine.put([b], [ids[:5]], sample=None)
        engine.put([a, b], [ids[32:45], ids[5:6]], sample=None)
        engine.decode([a, b], [ids[45:46], ids[6:7]], 4)
        engine.flush(a), engine.flush(b)

    serve(11, 12)  # compile first: the traced run is warm
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        serve(13, 14)
    finally:
        jax.profiler.stop_trace()
    (path, ) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    trace = program_spans.read(str(path))
    prefill = program_spans.spans_named(trace, "serving/prefill")[-1].args
    entry = 4 * 16 * 16 * 4 + 3 * 192 * 4
    assert (prefill["attn_pairs"], prefill["attn_ctx_tokens"]) == (sum(32 + i + 1 for i in range(13)) + 6, 45 + 6)
    assert prefill["kv_entry_bytes"] == 2 * 2 * 16 * 4
    assert (prefill["state_rows"], prefill["lin_tokens"], prefill["state_entry_bytes"]) == (2, 3 * 14, entry)
    assert prefill["state_rows_stepped"] == 1   # the mixed put: the one-token row is the step's, the chunk the scan's
    assert prefill["state_bytes"] == 2 * 3 * entry * 2
    assert (prefill["state_slots_live"], prefill["state_slots_total"]) == (2, 4)
    assert prefill["kernel"].endswith("kda_chunk_scan:8:ragged+kda_recurrent_step:1:one-token-rows")
    # the two puts before it fed a chunk each and no one-token row
    assert [s.args["state_rows_stepped"] for s in program_spans.spans_named(trace, "serving/prefill")] == [0, 0, 1]
    (decode, ) = program_spans.spans_named(trace, "serving/decode")
    assert decode.args["attn_pairs"] == sum(45 + j + 1 for j in range(4)) + sum(6 + j + 1 for j in range(4))
    assert (decode.args["state_rows"], decode.args["lin_tokens"], decode.args["state_bytes"]) == (8, 24, 8 * 3 * entry * 2)
    assert decode.args["state_rows_stepped"] == 8   # a horizon: every row x step
    assert decode.args["kernel"].endswith("kda_recurrent_step:1:one-token-rows")
    assert "kda_chunk_scan" not in decode.args["kernel"]
