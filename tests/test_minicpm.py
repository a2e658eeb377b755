"""The MiniCPM-SALA family (``models/minicpm.py``: learned-sparse GQA layers
between scalar-decay linear-attention layers on a scaled residual path) on the
serving path at a small size, on seeded random weights, against the benchmark's
plain float32 reference (``benchmark/lib/minicpm_sala_reference.py``, which
imports nothing of the program, runs the recurrence token by token and the
selection as written): a prompt in uneven chunks that cross ``dense_len`` and
straddle pooling kernels, mixed ``put`` steps, a multi-step decode horizon;
the selection the program made against the reference's; flush, slot and block
reuse, cancel; bucket padding as a no-op on state and pooled keys; both forms
of the recurrence against the token-by-token one at boundaries that do not
divide the tile; both paged kernels' work lists under a hand-made selection
against the gather, and under an all-true one bit-equal to the lists without;
the spans' counts; the refusals. Tiny shapes: hidden 64, 6/2 heads of 16, 4
lightning heads of 16, layers 2-5 of the tiny preset's 8 (lightning, sparse,
sparse, lightning: two sparse ones adjacent), pooling kernel 4 at stride 2,
blocks of 8, top 6, window 16, ``dense_len`` 48. ONE engine serves most
tests, in ONE mode (``sample="probe"``: logits, and beside them what a few
tokens a row selected and attended), so that few programs compile."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmark.lib import minicpm_sala_reference as reference  # noqa: E402
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import HostTierConfig, PrefixCacheConfig, SpeculativeConfig  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.prefix_cache import PrefixKVCache  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.tiered_store import TieredBlockStore  # noqa: E402
from deepspeed_tpu.models import TransformerLM, minicpm_config  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.monitor.trace import get_tracer  # noqa: E402
from deepspeed_tpu.ops.pallas import lightning  # noqa: E402
from deepspeed_tpu.ops.pallas import paged_attention as pa  # noqa: E402

BLOCK = 8
MIXERS = ["lightning-attn"] * 3 + ["minicpm4"] * 2 + ["lightning-attn"] * 3
SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=8, topk=6, init_blocks=1, window_size=16, dense_len=48)


@pytest.fixture(autouse=True)
def _fresh_tracer():
    get_tracer().reset()
    yield
    get_tracer().reset()


def _published(cfg) -> dict:
    """The configuration-file keys the reference reads, from a program config."""
    return {"mixer_types": MIXERS, "num_hidden_layers": cfg.num_layers, "first_layer": 2, "num_hidden_layers_published": 8,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim, "lightning_nkv": cfg.lightning_num_heads,
            "lightning_head_dim": cfg.lightning_head_dim, "rms_norm_eps": cfg.norm_eps, "scale_emb": 12.0,
            "scale_depth": 1.4, "hidden_size": cfg.hidden_size, "dim_model_base": 16, "sparse_config": SPARSE}


@pytest.fixture(scope="module")
def tiny():
    cfg = minicpm_config("tiny", num_layers=4, first_layer=2, dtype=jnp.float32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, size=200, dtype=np.int32)
    return cfg, params, ids


def _engine(cfg, params, **kwargs):
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=32, max_ragged_sequence_count=4,
                              max_context=256, token_buckets=(32, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=BLOCK, num_kv_blocks=100, kv_dtype=kwargs.pop("kv_dtype", jnp.float32),
                                       state_manager=sm, **kwargs)
    return InferenceEngineV2(TransformerLM(cfg), icfg, params=params)


@pytest.fixture(scope="module")
def engine(tiny):
    return _engine(*tiny[:2])


def _logits(eng, uids, tokens):
    """The rows' logits through the one program mode this file compiles."""
    return np.asarray(eng.put(uids, tokens, sample="probe")[0])


def _held(eng, uid, n_tokens):
    """A sequence's lightning states and its pooled keys, read out of the pools."""
    kv = eng.state_manager.kv_cache
    seq = eng.state_manager.get_sequence(uid)
    per = BLOCK // SPARSE["kernel_stride"]
    m = np.arange(max((n_tokens - SPARSE["kernel_size"]) // SPARSE["kernel_stride"] + 1, 0))
    at = np.asarray(seq.kv_blocks, np.int64)[m // per] * per + m % per
    return np.asarray(kv.state_pool[:, seq.state_slot]), np.asarray(kv.index_pool[:, at])


@pytest.fixture(scope="module")
def served(tiny, engine):
    """One sequence through the engine as traffic is: a 131-token prompt in
    chunks of 13, 27, 31, 9, 30 and 21 (odd sizes: pooling kernels of 4 at
    stride 2, blocks of 8 and ``dense_len`` 48 all fall inside chunks), 3
    positions as one-token rows beside another prompt's chunks, 8 through the
    decode horizon in two calls of 4, 3 more one-token puts. Logits by
    position; the program's selection and its attention's output by position,
    at every step's last token and three tokens inside each chunk; the final
    sequence; the states and pooled keys read back before the flush."""
    cfg, params, ids = tiny
    got, chosen, attended = {}, {}, {}

    def note(pos, out):
        logits, (at, picked, ctx) = out
        got[pos] = np.asarray(logits)[0]
        assert at[0, -1] == pos
        for j, p in enumerate(at[0]):
            chosen[int(p)], attended[int(p)] = picked[0, j], ctx[0, j]

    c0 = 0
    for n in (13, 27, 31, 9, 30, 21):
        out = engine.put([1], [ids[c0:c0 + n]], sample="probe")
        c0 += n
        note(c0 - 1, out)
    for i in range(3):  # ours first, the other prompt's chunk behind it
        other = ids[140 + 9 * i:140 + 9 * i + (24 if i == 0 else 9)]
        note(131 + i, engine.put([1, 2], [ids[131 + i:132 + i], other], sample="probe"))
    engine.flush(2)
    seq = [int(t) for t in ids[:134]]
    nxt = int(got[133].argmax())
    for _ in range(2):
        toks = np.asarray(engine.decode([1], [np.asarray([nxt], np.int32)], 4))[0]
        seq += [nxt] + [int(t) for t in toks[:-1]]
        nxt = int(toks[-1])
    for t in ids[190:193]:
        seq.append(int(t))
        note(len(seq) - 1, engine.put([1], [np.asarray(seq[-1:], np.int32)], sample="probe"))
    states, pooled = _held(engine, 1, len(seq))
    engine.flush(1)
    return got, chosen, np.asarray(seq, np.int32), states, pooled, attended


def _reference(tiny, seq, positions, selection=None, probes=None, **switches):
    cfg, params, _ = tiny
    hp = {**reference.hyper_from_published(_published(cfg)), **switches}
    out = reference.forward(hp, params, jnp.asarray(seq), list(positions), selection=selection,
                            probes=list(positions if probes is None else probes))
    return {k: (np.asarray(v) if not isinstance(v, list) else [np.asarray(x) for x in v]) for k, v in out.items()}


@pytest.fixture(scope="module")
def expected(tiny, served):
    """The reference on its OWN selection: logits where the program has them,
    scores, choice and attention output wherever the program was probed."""
    got, chosen, seq = served[:3]
    return _reference(tiny, seq, sorted(got), probes=sorted(chosen))


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_chunks_riding_rows_and_the_horizon_match_the_plain_reference(served, expected):
    got, _, _, states, pooled, _ = served
    for i, pos in enumerate(sorted(got)):
        assert _rel(got[pos], expected["logits"][i]) < 2e-5, pos
    for mine, theirs in zip(states, expected["states"]):
        assert _rel(mine, theirs) < 2e-5
    for mine, theirs in zip(pooled, expected["pooled"]):
        assert mine.shape == theirs.shape and np.abs(mine - theirs).max() < 1e-5


def test_the_program_selects_what_the_reference_selects_where_the_scores_are_apart(served, expected):
    """Past ``dense_len`` exactly ``topk`` blocks a KV head, the forced ones
    among them, and the reference's own set wherever its last admitted and
    first refused scores differ by more than rounding; under it every visible block."""
    chosen = served[1]
    topk, bs = SPARSE["topk"], BLOCK
    compared = 0
    for i, pos in enumerate(sorted(chosen)):
        mine, theirs, scores = chosen[pos], expected["chosen"][i], expected["scores"][i]
        n = scores.shape[-1]
        visible = np.arange(mine.shape[-1]) <= pos // bs
        if pos + 1 <= SPARSE["dense_len"]:
            assert (mine == visible).all()
            continue
        assert (mine.sum(-1) == topk).all() and not mine[..., ~visible].any()
        forced = visible[:n] & ((np.arange(n) < 1) | (np.arange(n) >= max(pos - 15, 0) // bs))
        assert mine[..., :n][..., forced].all()
        for layer in range(2):
            for head in range(2):
                s = np.where(forced | ~visible[:n], np.nan, scores[layer, head])
                inside, outside = s[theirs[layer, head] & ~forced], s[~theirs[layer, head] & visible[:n] & ~forced]
                if inside.size and outside.size and np.nanmin(inside) - np.nanmax(outside) > 1e-6:
                    assert (mine[layer, head, :n] == theirs[layer, head]).all(), (pos, layer, head)
                    compared += 1
    assert compared > 20


@pytest.mark.parametrize("switch,value,what", [("lightning_rope", False, "logits"), ("branch_depth", 4, "logits"),
                                               ("group_sum", False, "chosen"), ("topk", 3, "chosen")])
def test_the_reference_without_one_mechanism_is_far_from_the_program(tiny, served, switch, value, what):
    got, chosen, seq = served[:3]
    positions = sorted(got)
    ref = _reference(tiny, seq, positions, **{switch: value})
    if what == "logits":
        assert min(_rel(got[p], ref["logits"][i]) for i, p in enumerate(positions)) > 1e-2
    else:
        past = [i for i, p in enumerate(positions) if p + 1 > SPARSE["dense_len"]]
        n = ref["chosen"].shape[-1]
        assert np.mean([(chosen[positions[i]][..., :n] == ref["chosen"][i]).all() for i in past]) < 0.5


def test_the_logits_stand_on_the_selection_they_are_given(tiny, served, expected):
    """The reference run ON another selection at one position moves that
    position's logits and attention output and no earlier one's."""
    got, chosen, seq, _, _, attended = served
    positions = sorted(got)
    last = positions[-1]
    other = chosen[last].copy()
    other[:, :, 1:4] = ~other[:, :, 1:4]
    moved = _reference(tiny, seq, positions, selection={last: other})
    assert _rel(moved["logits"][-1], expected["logits"][-1]) > 1e-4
    assert _rel(moved["logits"][0], expected["logits"][0]) < 1e-6
    assert min(_rel(attended[last][l], moved["attn"][-1][l]) for l in range(2)) > 1e-2
    assert _rel(attended[positions[0]], moved["attn"][0]) < 2e-5


def test_the_paged_kernels_give_back_the_references_attention_over_the_programs_selection(served, expected):
    """What the paged kernels made of the selection, at each step's last token
    and at three tokens inside every chunk (the middle of a tile, its ends),
    against the reference's masked softmax over the same blocks, before the
    gate and ``W_o``: wherever the two chose the same blocks, past ``dense_len``
    and under it, through the chunks, the riding rows and the one-token puts."""
    chosen, attended = served[1], served[5]
    same = 0
    for i, pos in enumerate(sorted(chosen)):
        n = expected["chosen"].shape[-1]
        if (chosen[pos][..., :n] == expected["chosen"][i]).all():
            assert _rel(attended[pos], expected["attn"][i]) < 2e-5, pos
            same += pos + 1 > SPARSE["dense_len"]
    assert len(chosen) >= 30 and same >= 15


def test_a_freed_slot_and_freed_blocks_never_reach_a_new_sequence(tiny, engine, served, expected):
    """Flush, slot and block reuse, cancel: a second sequence fed the same
    tokens in the slot and on the blocks the first left dirty reads the same."""
    cfg, params, ids = tiny
    got, seq = served[0], served[2]
    first = sorted(got)[0]
    _logits(engine, [5], [ids[40:72]])       # dirties a slot and blocks, then goes mid-prompt (a cancel)
    engine.flush(5)
    assert engine.state_manager.kv_cache.free_state_slots == 4
    out = _logits(engine, [6], [seq[:first + 1]])[0]
    assert _rel(out, expected["logits"][0]) < 2e-5
    engine.flush(6)
    assert engine.state_manager.kv_cache.free_state_slots == 4 and engine.free_blocks == 100


def test_bucket_padding_touches_no_state_and_no_pooled_key(tiny, engine):
    """A step of 5 tokens in the 32-token bucket beside three empty rows: the
    other slots' states and every pooled key outside the sequence's blocks
    stay bit for bit as they were."""
    cfg, params, ids = tiny
    kv = engine.state_manager.kv_cache
    kv.state_pool = jnp.ones_like(kv.state_pool)
    kv.index_pool = jnp.ones_like(kv.index_pool)
    _logits(engine, [7], [ids[:5]])
    seq = engine.state_manager.get_sequence(7)
    state, index = np.asarray(kv.state_pool), np.asarray(kv.index_pool)
    others = [s for s in range(4) if s != seq.state_slot]
    assert (state[:, others] == 1.0).all() and not (state[:, seq.state_slot] == 1.0).all()
    per = BLOCK // SPARSE["kernel_stride"]
    mine = np.zeros(index.shape[1], bool)
    for b in seq.kv_blocks:
        mine[b * per:(b + 1) * per] = True
    assert (index[:, ~mine] == 1.0).all()
    assert (index[:, seq.kv_blocks[0] * per] != 1.0).any() and (index[:, seq.kv_blocks[0] * per + 1] == 1.0).all()  # 5 tokens: one kernel whole
    engine.flush(7)
    kv.state_pool, kv.index_pool = jnp.zeros_like(kv.state_pool), jnp.zeros_like(kv.index_pool)


# ---------------------------------------------------------------------------
# the recurrence's two forms
# ---------------------------------------------------------------------------

def _ragged(seed=0, H=4, d=16, T=80):
    rng = np.random.default_rng(seed)
    n_tok = np.array([5, 1, 0, 37, 1, 20, 0, 0], np.int32)
    q, k, v = (jnp.asarray(rng.normal(size=(T, H, d)), jnp.float32) for _ in range(3))
    slope = jnp.asarray([0.6, 0.3, 0.05, 0.004], jnp.float32)
    slots = np.array([3, 9, 0, 1, 7, 4, 0, 0], np.int32)
    pool = jnp.asarray(rng.normal(size=(12, H, d, d)), jnp.float32)
    fresh = np.array([1, 0, 0, 0, 1, 0, 0, 0], np.int32)
    return n_tok, q, k, v, slope, slots, pool, fresh


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
@pytest.mark.parametrize("tile", [8, 128])
def test_the_chunkwise_form_matches_the_recurrence_at_boundaries_that_do_not_divide(tile, interpret):
    n_tok, q, k, v, slope, slots, pool0, fresh = _ragged()
    o, pool = lightning.lightning_chunks(q, k, v, slope, pool0, jnp.asarray(slots), jnp.asarray(fresh), jnp.asarray(n_tok),
                                         interpret=interpret, tile=tile)
    start = 0
    for r, n in enumerate(n_tok):
        if n:
            S0 = jnp.zeros_like(pool0[0]) if fresh[r] else pool0[slots[r]]
            want_o, want_S = lightning.recurrence_reference(q[start:start + n], k[start:start + n], v[start:start + n], slope, S0)
            assert np.abs(np.asarray(o[start:start + n] - want_o)).max() < 1e-4
            assert np.abs(np.asarray(pool[slots[r]] - want_S)).max() < 1e-4
            start += n
    untouched = [s for s in range(12) if s not in slots[n_tok > 0]]
    assert (np.asarray(pool)[untouched] == np.asarray(pool0)[untouched]).all()


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
@pytest.mark.parametrize("n_live", [0, 5])
def test_the_recurrent_step_advances_the_live_rows_alone(interpret, n_live):
    _, q, k, v, slope, slots, pool0, fresh = _ragged()
    slots = np.array([3, 9, 0, 1, 7, 4, 5, 6], np.int32)
    o, pool = lightning.lightning_step(q[:8], k[:8], v[:8], slope, pool0, jnp.asarray(slots), jnp.asarray(fresh),
                                       jnp.asarray(n_live), interpret=interpret)
    for r in range(n_live):
        S0 = jnp.zeros_like(pool0[0]) if fresh[r] else pool0[slots[r]]
        want_o, want_S = lightning.recurrence_reference(q[r:r + 1], k[r:r + 1], v[r:r + 1], slope, S0)
        assert np.abs(np.asarray(o[r] - want_o[0])).max() < 1e-5 and np.abs(np.asarray(pool[slots[r]] - want_S)).max() < 1e-5
    dead = [s for s in range(12) if s not in slots[:n_live]]
    assert (np.asarray(pool)[dead] == np.asarray(pool0)[dead]).all()


# ---------------------------------------------------------------------------
# the paged kernels' work lists under a selection
# ---------------------------------------------------------------------------

def _batch(nq, nkv, d, bs, lens, news, dtype, seed=0, all_true=False):
    rng = np.random.default_rng(seed)
    S = len(lens)
    MB = max(-(-l // bs) for l in lens) + 1
    NB = S * MB + 3
    tables = rng.permutation(NB)[:S * MB].reshape(S, MB).astype(np.int32)
    seq_idx = np.concatenate([np.full(n, i) for i, n in enumerate(news)]).astype(np.int32)
    pos = np.concatenate([np.arange(l - n, l) for l, n in zip(lens, news)]).astype(np.int32)
    n = len(pos)
    pad = -n % 8   # the pad run: sequence 0 at position 0
    seq_idx, pos = np.concatenate([seq_idx, np.zeros(pad, np.int32)]), np.concatenate([pos, np.zeros(pad, np.int32)])
    q = jnp.asarray(rng.normal(size=(n + pad, nq, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(NB * bs, nkv, d)), dtype) for _ in range(2))
    sel = rng.random((n + pad, nkv, MB)) < 0.3
    sel[np.arange(n + pad), :, pos // bs] = True   # a token's own block, as the forced window gives it
    sel[:, :, 0] = True
    if all_true:
        sel[:] = True
    return n, (q, k, v, jnp.asarray(tables), jnp.asarray(seq_idx), jnp.asarray(pos)), jnp.asarray(sel)


_HAND_MADE = {"tiled": (6, 2, 16, 8, [50, 33, 9], [20, 1, 9], 8), "decode": (6, 2, 16, 8, [50, 33, 9, 70], [1, 1, 1, 1], 1),
              "decode-multi-token": (6, 2, 16, 8, [50, 33], [3, 2], 1),
              "tiled-published-heads": (32, 2, 128, 64, [200, 70], [20, 1], 8)}


@pytest.mark.parametrize("case", list(_HAND_MADE))
def test_both_work_lists_under_a_hand_made_selection_match_the_gather(case):
    nq, nkv, d, bs, lens, news, q_tile = _HAND_MADE[case]
    n, args, sel = _batch(nq, nkv, d, bs, lens, news, jnp.float32)
    want, (own, *_) = pa.paged_attention_reference(*args, bs, selection=sel)
    got, (read, *_) = pa._pallas_paged(*args, block_size=bs, interpret=True, q_tile=q_tile, selection=sel)
    assert np.abs(np.asarray(got[:n] - want[:n])).max() < 2e-5
    # what the list says it served: a token's own columns a row a step, a tile's union for its tokens otherwise
    visible = int(np.sum(np.asarray(args[5]) // bs + 1))   # (the pad run is sequence 0's token 0 to a list)
    assert int(own) <= int(read) < visible and (q_tile > 1 or int(read) == int(own))
    # and the lists lay steps for selected blocks alone: fewer than the visible ones
    if q_tile > 1:
        items = jax.jit(lambda bt, si, p, s: pa._tiled_work_list(bt, si, p, bs, None, q_tile, s)[8])
    else:
        per = pa._decode_blocks_per_step(bs * nkv, d, 4)
        items = jax.jit(lambda bt, si, p, s: pa._decode_work_list(bt, si, p, bs, None, per, s)[3])
    assert int(items(*args[3:], sel)) < int(items(*args[3:], None))


_ALL_TRUE = {"mistral-prefill": (32, 8, 128, 128, [260, 130], [24, 1], 8, jnp.bfloat16),
             "mistral-decode": (32, 8, 128, 128, [300, 130, 50], [1, 1, 1], 1, jnp.bfloat16),
             "glm-expanded-prefill": (20, 20, 256, 128, [140], [16], 8, jnp.bfloat16),
             "glm-expanded-decode": (20, 20, 256, 128, [300, 40], [1, 1], 1, jnp.bfloat16)}


@pytest.mark.parametrize("case", list(_ALL_TRUE))
def test_an_all_true_selection_is_bit_equal_to_no_selection(case):
    """At Mistral's heads (32/8 of 128) and at the heads of GLM's expanded
    form (20 of 256, a group of one) over token-major pools: the selected
    lists hold the items the plain lists hold, in their order."""
    nq, nkv, d, bs, lens, news, q_tile, dtype = _ALL_TRUE[case]
    n, args, sel = _batch(nq, nkv, d, bs, lens, news, dtype, all_true=True)
    got, (read, *_) = pa._pallas_paged(*args, block_size=bs, interpret=True, q_tile=q_tile, selection=sel)
    plain = pa._pallas_paged(*args, block_size=bs, interpret=True, q_tile=q_tile)
    assert (np.asarray(got[:n]) == np.asarray(plain[:n])).all()
    assert int(read) == int(np.sum(np.asarray(args[5]) // bs + 1))   # every visible block, a token


def test_a_selection_is_refused_beside_what_its_lists_are_not_built_for():
    n, args, sel = _batch(6, 2, 16, 8, [30], [30], jnp.float32)
    with pytest.raises(NotImplementedError, match="block selection beside a sliding window"):
        pa.paged_attention(*args, 8, window=16, selection=sel)
    with pytest.raises(ValueError, match="a selection is"):
        pa.paged_attention(*args, 8, selection=sel[:, :1])


# ---------------------------------------------------------------------------
# the configuration, the pools, the refusals, the spans
# ---------------------------------------------------------------------------

def test_layer_types_says_which_linear_kind_a_layer_is(tiny):
    cfg = tiny[0]
    assert cfg.state_layers == (0, 3) and cfg.kv_layers == (1, 2) and cfg.kda_num_heads == 0
    assert cfg.state_entry == ((4, 16, 16), ) and cfg.index_entry == (2, 2, 16)
    assert cfg.rope_layer_types == ("lightning_attention", )
    why = " ".join(cfg.unscannable)
    assert "scalar-decay (lightning)" in why and "learned block-sparse selection" in why and "scaled residual" in why
    with pytest.raises(ValueError, match="lightning_num_heads=0 with 2 'lightning_attention' layers"):
        tfm.TransformerConfig(**{**cfg.__dict__, "lightning_num_heads": 0})
    with pytest.raises(ValueError, match="sparse_topk=0 with 2 'sparse_attention' layers"):
        tfm.TransformerConfig(**{**cfg.__dict__, "sparse_topk": 0})
    with pytest.raises(NotImplementedError, match="one model: a sequence's state slot holds one kind"):
        tfm.TransformerConfig(**{**cfg.__dict__, "kda_num_heads": 4,
                                 "layer_types": ("linear_attention", ) + cfg.layer_types[1:]})


def test_the_published_preset_is_the_catalog_row():
    cfg = minicpm_config("sala-9b", num_layers=16, first_layer=9)
    kinds = cfg.layer_types
    assert [l + 9 for l, k in enumerate(kinds) if k == "sparse_attention"] == [9, 16, 17, 22]
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.lightning_num_heads, cfg.hidden_size) == (32, 2, 128, 32, 4096)
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5) and cfg.logit_scale == 1 / 16 and cfg.embed_scale == 12
    slopes = tfm.lightning_slopes(cfg)
    assert slopes.shape == (12, 32)
    assert slopes[0, 0] == pytest.approx(2 ** (-8 / 32) * (1 - 10 / 31 + 1e-5))   # the first lightning layer is published layer 10
    whole = minicpm_config("sala-9b")
    assert whole.layer_types.count("sparse_attention") == 8 and whole.layer_types[0] == "sparse_attention"


def test_the_pools_are_of_three_kinds(tiny, engine):
    kv = engine.state_manager.kv_cache
    assert [p.shape for p in kv.pools()] == [(2, 800, 2, 16), (2, 800, 2, 16), (2, 400, 2, 16), (2, 4, 4, 16, 16)]
    assert kv.tail_pool is None and kv.has_index and kv.has_state
    assert kv.index_entry_bytes() == 2 * 16 * 4 and kv.state_entry_bytes() == 4 * 16 * 16 * 4
    assert kv.block_bytes() == 2 * (2 * 2 * 16 * 8 + 2 * 16 * 4) * 4   # a block's K and V and its four pooled keys, two layers


def test_a_program_compiled_ahead_on_a_thread_of_its_own_is_the_program_a_call_would_compile(tiny, engine):
    """``compile_ahead`` touches no pool and runs nothing: a horizon of 2 made
    on its own thread gives the first 2 tokens of the horizon of 4 the module
    compiled in a call; what is compiled already is left alone; a call that
    needs a program in the making waits for it; ``warmup`` runs one that no
    call has run and finds the others there; past the warm-up boundary the
    scheduler is given the longest horizon the engine has."""
    cfg, params, ids = tiny

    def decoded(uid, steps):
        first = int(_logits(engine, [uid], [ids[:20]])[0].argmax())
        toks = np.asarray(engine.decode([uid], [np.asarray([first], np.int32)], steps))[0]
        engine.flush(uid)
        return toks

    want = decoded(21, 4)
    before = dict(engine._compiled)
    made = engine.compile_ahead([("decode", 4, 2), ("decode", 4, 4), ("put", 32, 4, "probe"), ("decode", 3, 2)])
    assert len(made) == 1 and all(engine._compiled[k] is fn for k, fn in before.items())
    assert (decoded(22, 2) == want[:2]).all()     # the call waited for the program
    assert made[0].done() and not engine._ahead
    assert isinstance(engine._compiled[("decode", 4, 2, False)], jax.stages.Compiled)
    engine.compile_ahead([("decode", 4, 1)])         # one that no call runs: warmup runs it, on its zero descriptor
    assert [w["cached"] for w in engine.warmup([4], [1, 2, 4], declare_warmed=False)] == [False, True, True]
    assert not engine._ahead and isinstance(engine._compiled[("decode", 4, 1, False)], jax.stages.Compiled)
    with pytest.raises(ValueError, match="unknown program kind"):
        engine.compile_ahead([("verify", 4, 2)])
    # before the boundary a scheduler gets the horizon it wants; after it, the longest one the engine has
    assert [engine.compiled_horizon(3, h) for h in (1, 2, 4, 8, 32)] == [1, 2, 4, 8, 32]
    engine._gp_warmed = True
    try:
        assert [engine.compiled_horizon(3, h) for h in (1, 2, 3, 4, 8, 32)] == [1, 2, 2, 4, 4, 4]
        assert engine.compiled_horizon(3, 8, sampled=True) == 8      # no sampled program: it compiles, as ever
    finally:
        engine._gp_warmed = False


@pytest.mark.parametrize("call", ["forward_hidden", "forward_with_cache", "int8_kv", "speculative_config", "speculate_decode",
                                  "prefix_cache", "host_tier", "prefix_cache_config", "rollback_to", "export_sequence_kv",
                                  "token_tree", "block_size", "pooled_keys_alone"])
def test_what_takes_state_or_pooled_keys_to_be_blocks_is_refused_by_name(tiny, engine, call):
    cfg, params, ids = tiny
    kv = engine.state_manager.kv_cache
    if call == "forward_hidden":
        with pytest.raises(NotImplementedError, match="learned block-sparse selection"):
            tfm.forward_hidden(cfg, params, jnp.asarray(ids[None, :8]))
    elif call == "forward_with_cache":
        with pytest.raises(NotImplementedError, match="scalar-decay .lightning. state"):
            tfm.forward_with_cache(cfg, params, jnp.asarray(ids[None, :8]), None)
    elif call == "int8_kv":
        with pytest.raises(NotImplementedError, match="int8 KV cache beside"):
            _engine(cfg, params, kv_dtype="int8")
    elif call == "speculative_config":
        with pytest.raises(NotImplementedError, match="speculative decoding of a model with"):
            _engine(cfg, params, speculative=SpeculativeConfig(mode="ngram", k=2))
    elif call == "speculate_decode":
        with pytest.raises(NotImplementedError, match="speculate_decode .* pooled keys"):
            engine.speculate_decode([1], [ids[8:9]], [ids[9:11]])
    elif call == "token_tree":
        with pytest.raises(NotImplementedError, match="speculate_decode .* token tree"):
            engine.speculate_decode([1], [ids[8:9]], [[ids[9:11], ids[11:13]]])
    elif call == "prefix_cache":
        with pytest.raises(NotImplementedError, match="PrefixKVCache for a model with"):
            PrefixKVCache(kv)
    elif call == "host_tier":
        with pytest.raises(NotImplementedError, match="TieredBlockStore for a model with"):
            TieredBlockStore(kv, HostTierConfig(enabled=True, host_blocks=4))
    elif call == "prefix_cache_config":
        with pytest.raises(NotImplementedError, match="PrefixKVCache"):
            _engine(cfg, params, prefix_cache=PrefixCacheConfig(enabled=True))
    elif call in ("rollback_to", "export_sequence_kv"):
        _logits(engine, [9], [ids[:8]])
        try:
            if call == "rollback_to":
                with pytest.raises(NotImplementedError, match="rollback_to.*pooled keys"):
                    engine.state_manager.rollback_to(engine.state_manager.get_sequence(9), 4)
            else:
                with pytest.raises(NotImplementedError, match="export_sequence_kv of a model with .* pooled keys"):
                    engine.export_sequence_kv(9, ids[:8])
        finally:
            engine.flush(9)
    elif call == "block_size":
        sm = DSStateManagerConfig(max_tracked_sequences=2, max_ragged_batch_size=32, max_ragged_sequence_count=2, max_context=64)
        with pytest.raises(ValueError, match="the KV block is the selection's block"):
            InferenceEngineV2(TransformerLM(cfg), RaggedInferenceEngineConfig(kv_block_size=16, num_kv_blocks=16,
                                                                              kv_dtype=jnp.float32, state_manager=sm), params=params)
    else:  # pooled keys without a state: the block movers refuse them by their own name
        from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache

        alone = BlockedKVCache(2, 2, 16, 8, 8, dtype=jnp.float32, index_entry=(2, 2, 16))
        with pytest.raises(NotImplementedError, match="PrefixKVCache for a model with pooled keys"):
            PrefixKVCache(alone)
        with pytest.raises(NotImplementedError, match="TieredBlockStore for a model with pooled keys"):
            TieredBlockStore(alone, HostTierConfig(enabled=True, host_blocks=4))


def test_a_step_span_says_how_full_the_tiled_grids_steps_were(tiny, monkeypatch):
    """With the tiled kernel serving the ``put`` programs (its body on the
    interpreter, a tile of 8), ``serving/prefill`` says ``attn_items_live``,
    the (tile, column) pairs the lists of the two sparse layers laid, and
    ``attn_grid_steps``, the steps they ran in at four 8-token blocks a step,
    beside an ``attn_blocks_read`` that is what the gather's program counts
    for a row under ``dense_len`` (every visible block) and no less past it."""
    from deepspeed_tpu.inference.v2.modules.implementations import attention
    from deepspeed_tpu.monitor.trace import get_tracer

    def tiled(q, k, v, tables, seq_idx, pos, bs, window=None, alibi=None, selection=None, **kw):
        return pa._pallas_paged(q, k, v, tables, seq_idx.astype(jnp.int32), pos.astype(jnp.int32), block_size=bs,
                                interpret=True, q_tile=8, selection=selection)

    monkeypatch.setattr(attention, "paged_attention", tiled)
    cfg, params, ids = tiny
    eng = _engine(cfg, params, use_pallas_kernels="always")
    get_tracer().reset()
    tracer = get_tracer().configure(enabled=True)
    try:
        for c0 in range(0, 90, 30):
            _logits(eng, [21], [ids[c0:c0 + 30]])
        spans = [e["args"] for e in tracer.drain() if e["ph"] == "X" and e["name"] == "serving/prefill"]
    finally:
        get_tracer().reset()
    first, _, last = spans
    layers, nkv = 2, 2
    # positions 0-29: tiles of 8 tokens see 1, 2, 3 and 4 columns, a step each (the pad run's tokens select nothing)
    assert first["attn_items_live"] == layers * (1 + 2 + 3 + 4) and first["attn_grid_steps"] == layers * 4
    assert first["attn_blocks_read"] == first["attn_blocks_visible"] == layers * nkv * sum(p // 8 + 1 for p in range(30))
    # past dense_len a tile's union is at most its 8 tokens' 6 blocks and at least 6: two or more steps a tile
    assert last["attn_items_live"] / 4 <= last["attn_grid_steps"] < last["attn_items_live"]
    assert layers * 4 * 2 <= last["attn_grid_steps"] and last["attn_blocks_selected"] <= last["attn_blocks_read"]


def test_a_step_span_says_what_was_visible_selected_and_read(tiny, engine, tmp_path):
    """The selection's counters and Solar's six state counters on the step
    spans, by hand: a 10-token chunk after 180 cached tokens beside a one-token
    row at 5, then a decode horizon of 4 at 190 and 6."""
    from benchmark.lib import program_spans

    cfg, params, ids = tiny

    def serve(a, b):
        for c0 in range(0, 180, 30):
            _logits(engine, [a], [ids[c0:c0 + 30]])
        _logits(engine, [b], [ids[:5]])
        _logits(engine, [a, b], [ids[180:190], ids[5:6]])
        engine.decode([a, b], [ids[190:191], ids[6:7]], 4)
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
    layers, nkv, chunk = 2, 2, range(180, 190)
    assert prefill["attn_blocks_visible"] == layers * nkv * (sum(p // 8 + 1 for p in chunk) + 1)
    assert prefill["attn_blocks_selected"] == layers * nkv * (10 * 6 + 1)
    assert prefill["attn_blocks_selected"] <= prefill["attn_blocks_read"] <= prefill["attn_blocks_visible"]
    # (the tiled lists' pairs and grid steps: off the chip the gather serves every call and lays no tiles)
    assert prefill["attn_items_live"] == prefill["attn_grid_steps"] == 0
    assert (prefill["sparse_rows"], prefill["dense_rows"]) == (1, 1)
    assert prefill["index_keys"] == layers * nkv * sum((p - 3) // 2 + 1 for p in chunk)
    assert prefill["index_entry_bytes"] == 2 * 16 * 4 and prefill["kv_entry_bytes"] == 2 * 2 * 16 * 4
    assert prefill["attn_pairs"] == layers * (sum(5 * 8 + p % 8 + 1 for p in chunk) + 6)
    entry = 4 * 16 * 16 * 4
    assert (prefill["state_rows"], prefill["lin_tokens"], prefill["state_entry_bytes"]) == (2, 2 * 11, entry)
    assert prefill["state_bytes"] == 2 * 2 * entry * 2 and prefill["state_rows_stepped"] == 0
    assert (prefill["state_slots_live"], prefill["state_slots_total"]) == (2, 4)
    assert prefill["kernel"].endswith("sparse_index:1:top6+lightning_chunk_scan:128:ragged")
    # a row under dense_len reads what it sees: the first chunk's three counts are one
    first = program_spans.spans_named(trace, "serving/prefill")[0].args
    assert first["attn_blocks_read"] == first["attn_blocks_selected"] == first["attn_blocks_visible"] > 0
    (decode, ) = program_spans.spans_named(trace, "serving/decode")
    d = decode.args
    short = sum(p // 8 + 1 for p in range(6, 10))   # the row under dense_len selects what it sees
    assert d["attn_blocks_visible"] == layers * nkv * (sum(p // 8 + 1 for p in range(190, 194)) + short)
    assert d["attn_blocks_selected"] == layers * nkv * (4 * 6 + short)
    assert d["attn_blocks_selected"] <= d["attn_blocks_read"]
    assert d["attn_blocks_read"] / d["attn_blocks_visible"] < 0.5   # one-token rows: two heads' six of twenty-four
    assert (d["state_rows"], d["lin_tokens"], d["state_rows_stepped"]) == (8, 2 * 8, 8)
    assert d["kernel"].endswith("sparse_index:1:top6+lightning_recurrent_step:1:one-token-rows")
