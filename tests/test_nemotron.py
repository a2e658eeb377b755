"""The Nemotron-H family (``models/nemotron.py``: layers that are ONE branch
each, a Mamba-2 mixer, a relu-squared expert layer or no-rope GQA, a share of
the experts) on the serving path at a small size, on seeded random weights,
against the benchmark's plain float32 reference
(``benchmark/lib/nemotron_reference.py``, which imports nothing of the program
and runs the selective scan token by token): a prompt in uneven chunks that
span the scan's tile, mixed ``put`` steps, a multi-step decode horizon, a dirty
slot; each sequence's state and tail read back; both forms of the scan against
the recurrence; the two chips' shares adding up; the other state kinds and a
dense model still what they were; the spans' counts; the refusals. Tiny shapes:
hidden 64, 6/2 heads of 16 in the attention layers, 4 Mamba heads of 8 with a
state of 16 in 2 groups, 16 experts top-2 of width 48 beside one shared expert
of 96, the published first stage's 13 letters. ONE engine serves most tests,
so that few programs compile."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmark.lib import nemotron_reference  # noqa: E402
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import HostTierConfig, PrefixCacheConfig, SpeculativeConfig  # noqa: E402
from deepspeed_tpu.inference.v2.modules.heuristics import build_modules  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.prefix_cache import PrefixKVCache  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.tiered_store import TieredBlockStore  # noqa: E402
from deepspeed_tpu.models import TransformerLM, nemotron_config, solar_config  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.monitor.trace import get_tracer  # noqa: E402
from deepspeed_tpu.ops.pallas import mamba2  # noqa: E402

BLOCK = 16
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(autouse=True)
def _fresh_tracer():
    get_tracer().reset()
    yield
    get_tracer().reset()


def _published(cfg, held=None, first=0) -> dict:
    """The configuration-file keys the reference reads, from a program config."""
    return {"num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "layer_norm_epsilon": cfg.norm_eps, "num_hidden_layers": cfg.num_layers, "hybrid_override_pattern": PATTERN,
            "mamba_num_heads": cfg.mamba_num_heads, "mamba_head_dim": cfg.mamba_head_dim,
            "ssm_state_size": cfg.mamba_state_size, "n_groups": cfg.mamba_n_groups, "conv_kernel": cfg.mamba_conv_size,
            "num_experts_per_tok": cfg.moe_top_k, "norm_topk_prob": cfg.moe_norm_topk_prob,
            "routed_scaling_factor": cfg.moe_route_scale, "n_routed_experts_published": cfg.moe_num_experts,
            "first_expert": first, "n_routed_experts": cfg.experts_held if held is None else held,
            "moe_intermediate_size": cfg.expert_size}


@pytest.fixture(scope="module")
def tiny():
    """The tiny model holding experts 4-11 of 16, its parameters (a selection
    bias wide enough to change the chosen set) and a seeded sequence."""
    cfg = nemotron_config("tiny", dtype=jnp.float32, moe_experts_held=8, moe_first_expert=4)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    params["blocks"]["gate_bias"] = params["blocks"]["gate_bias"] * 20.0
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, size=320, dtype=np.int32)
    return cfg, params, ids


def _engine(cfg, params, **kwargs):
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=160, max_ragged_sequence_count=4,
                              max_context=256, token_buckets=(160, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=BLOCK, num_kv_blocks=48, kv_dtype=kwargs.pop("kv_dtype", jnp.float32),
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
    """One sequence through the engine as traffic is, in a slot that a flushed
    sequence left dirty: a 181-token prompt in chunks of 150 (two tiles of the
    scan, 128 + 22) and 31 (neither boundary a multiple of 128), 3 positions
    as one-token rows beside another prompt's chunks, 8 through the decode
    horizon in two calls of 4, 2 more one-token puts. Logits by position, the
    final sequence, and the state and tail read back before the flush."""
    cfg, params, ids = tiny
    engine.put([7], [ids[200:260]], sample=None)   # runs, and goes: its slot holds what it left
    dirty = engine.state_manager.get_sequence(7).state_slot
    engine.flush(7)
    got = {}
    engine.put([1], [ids[:150]], sample=None)
    assert engine.state_manager.get_sequence(1).state_slot == dirty
    got[180] = np.asarray(engine.put([1], [ids[150:181]], sample=None))[0]
    for i in range(3):  # ours first, the other prompt's chunk (130, 9 and 9 tokens) behind it
        other = ids[190:320] if i == 0 else ids[10 * i:10 * i + 9]
        got[181 + i] = np.asarray(engine.put([1, 2], [ids[181 + i:182 + i], other], sample=None))[0]
    engine.flush(2)
    seq = [int(t) for t in ids[:184]]
    nxt = int(got[183].argmax())
    for _ in range(2):
        toks = np.asarray(engine.decode([1], [np.asarray([nxt], np.int32)], 4))[0]
        seq += [nxt] + [int(t) for t in toks[:-1]]
        nxt = int(toks[-1])
    for t in ids[300:302]:
        seq.append(int(t))
        got[len(seq) - 1] = np.asarray(engine.put([1], [np.asarray(seq[-1:], np.int32)], sample=None))[0]
    state, tail = _state(engine, 1)
    engine.flush(1)
    return got, np.asarray(seq, np.int32), state, tail


def _reference(tiny, seq, positions, **switches):
    cfg, params, _ = tiny
    hp = {**nemotron_reference.hyper_from_published(_published(cfg, first=cfg.moe_first_expert)), **switches}
    logits, states, tails = nemotron_reference.forward(hp, params, jnp.asarray(seq), list(positions), with_tails=True)
    return np.asarray(logits), np.asarray(states), np.asarray(tails)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_chunks_riding_rows_and_the_horizon_match_the_token_by_token_reference(tiny, served):
    """(a) Float32 on both sides and routing that agrees: what is left is the
    order of float32 sums (the chunk scan's matmuls against the recurrence's
    running products), 1e-6; the limit stands twenty times over it."""
    got, seq, state, _ = served
    positions = sorted(got)
    assert positions == [180, 181, 182, 183, 192, 193] and len(seq) == 194
    want, _, _ = _reference(tiny, seq, positions)
    assert max(_rel(got[p], w) for p, w in zip(positions, want)) < 2e-5
    # the horizon fed back the model's own greedy tokens: the reference's argmax at the positions before them
    horizon, _, _ = _reference(tiny, seq, range(183, 191))
    assert list(horizon.argmax(-1)) == list(seq[184:192])


def test_each_layers_state_and_tail_read_back_out_of_the_pool_are_the_references(tiny, served):
    """(b) Every Mamba layer's float32 state of the sequence after its last
    token, and its convolution's last three inputs: the same float32 sums in
    another order (2e-5), the tail the projection alone (1e-5)."""
    cfg = tiny[0]
    _, seq, state, tail = served
    _, want_states, want_tails = _reference(tiny, seq, [len(seq) - 1])
    assert state.shape == (6, 4, 8, 16) and tail.shape == (6, 3, cfg.mamba_conv_channels) == (6, 3, 96)
    assert max(_rel(s, w) for s, w in zip(state, want_states)) < 2e-5
    assert max(_rel(t, w) for t, w in zip(tail, want_tails)) < 1e-5


@pytest.mark.parametrize("switch,value", [("dt_bias", False), ("D_skip", False), ("norm_groups", 1), ("group_of_head", "mod"),
                                          ("activation", "relu"), ("route_scale", 1.0), ("selection_bias", False)])
def test_the_reference_with_one_mechanism_changed_is_far_from_the_program(tiny, served, switch, value):
    got, seq, state, _ = served
    want, want_states, _ = _reference(tiny, seq, sorted(got), **{switch: value})
    assert min(_rel(got[p], w) for p, w in zip(sorted(got), want)) > 1e-3
    if switch in ("dt_bias", "group_of_head"):  # the rule itself: the first Mamba layer's state shows it
        assert _rel(state[0], want_states[0]) > 0.05


def _scan_inputs(T=64, H=4, G=2, P=8, N=16, slots=12, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(T, H, P), f(T, G, N), f(T, G, N), jnp.asarray(rng.uniform(0.001, 0.5, (T, H)), jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, (H, )), jnp.float32), f(slots, H, P, N))


def _row_by_row(x, B, C, dt, A, pool, slot, fresh, n_tok):
    """Each fed row through the recurrence as written, from its slot."""
    starts = np.cumsum(n_tok) - n_tok
    out, y = np.array(pool), np.zeros(x.shape, np.float32)
    for r, n in enumerate(n_tok):
        if n:
            s = slice(int(starts[r]), int(starts[r] + n))
            S0 = jnp.zeros(pool.shape[1:]) if fresh[r] else pool[slot[r]]
            y[s], out[slot[r]] = mamba2.recurrence_reference(x[s], B[s], C[s], dt[s], A, S0)
    return y, out


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
@pytest.mark.parametrize("tile", [8, 16])
def test_the_chunk_scan_matches_the_recurrent_step_on_the_same_inputs(tile, interpret):
    """(c) A ragged batch (rows of 5, 1, 0, 37, 1 and 2 tokens: tiles that
    are no multiple of a row, a row over two blocks of tiles, one-token rows
    between them, dead rows) through :func:`mamba2_chunks`, and the same rows
    token by token through :func:`mamba2_step`, against the recurrence:
    float32 sums in three orders, 5e-6 of values of order one."""
    x, B, C, dt, A, pool = _scan_inputs()
    n_tok = np.array([5, 1, 0, 37, 1, 2, 0, 0], np.int32)
    slot = np.array([3, 7, 0, 1, 9, 11, 0, 0], np.int32)
    fresh = np.array([0, 1, 0, 1, 0, 0, 0, 0], np.int32)
    want_y, want_pool = _row_by_row(x, B, C, dt, A, pool, slot, fresh, n_tok)
    live = int(n_tok.sum())
    y, got = jax.jit(lambda *a: mamba2.mamba2_chunks(*a, tile=tile, interpret=interpret))(
        x, B, C, dt, A, pool, jnp.asarray(slot), jnp.asarray(fresh), jnp.asarray(n_tok))
    assert np.abs(np.asarray(y)[:live] - want_y[:live]).max() < 5e-6 and np.abs(np.asarray(got) - want_pool).max() < 5e-6
    # the same rows one token a call through the step: the live rows first, as the horizon hands them over
    stepped, starts = pool, np.cumsum(n_tok) - n_tok
    rows = [r for r in range(len(n_tok)) if n_tok[r]]
    for t in range(int(n_tok.max())):
        now = [r for r in rows if t < n_tok[r]]
        idx = jnp.asarray([starts[r] + t for r in now] + [0] * (len(rows) - len(now)))
        _, stepped = mamba2.mamba2_step(x[idx], B[idx], C[idx], dt[idx], A, stepped,
                                        jnp.asarray([slot[r] for r in now] + [0] * (len(rows) - len(now))),
                                        jnp.asarray([int(fresh[r] and t == 0) for r in now] + [0] * (len(rows) - len(now))),
                                        len(now), interpret=interpret)
    assert np.abs(np.asarray(stepped) - np.asarray(got)).max() < 5e-6


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "kernel-body"])
@pytest.mark.parametrize("n_live", [0, 4])
def test_the_recurrent_step_advances_the_live_rows_alone(interpret, n_live):
    x, B, C, dt, A, pool = _scan_inputs()
    slot, fresh = jnp.asarray([3, 7, 0, 1, 9, 11]), jnp.asarray([0, 1, 0, 0, 1, 0])
    y, got = mamba2.mamba2_step(x[:6], B[:6], C[:6], dt[:6], A, pool, slot, fresh, n_live, interpret=interpret)
    want = np.array(pool)
    for r in range(n_live):
        S0 = jnp.zeros(pool.shape[1:]) if fresh[r] else pool[slot[r]]
        yr, want[int(slot[r])] = mamba2.recurrence_reference(x[r:r + 1], B[r:r + 1], C[r:r + 1], dt[r:r + 1], A, S0)
        assert np.abs(np.asarray(y[r]) - np.asarray(yr[0])).max() < 2e-6
    assert np.abs(np.asarray(got) - want).max() < 2e-6   # (no live row: every slot exactly as it was)


def _stepped_rows(x, B, C, dt, A, pool, slot, fresh, n_live):
    """The first ``n_live`` rows one token each through the recurrence as written: ``(y a row, pool)``."""
    want, ys = np.array(pool), []
    for r in range(n_live):
        S0 = jnp.zeros(pool.shape[1:]) if fresh[r] else pool[slot[r]]
        yr, want[int(slot[r])] = mamba2.recurrence_reference(x[r:r + 1], B[r:r + 1], C[r:r + 1], dt[r:r + 1], A, S0)
        ys.append(np.asarray(yr[0]))
    return ys, want


@pytest.mark.parametrize("n_live", [0, 1, 5])
@pytest.mark.parametrize("groups_a_step", [1, 2])
def test_the_recurrent_steps_body_with_fewer_groups_a_step_than_the_model_has(monkeypatch, groups_a_step, n_live):
    """The kernel's own body on a grid of several steps a row (the VMEM budget
    patched down to what one and what two of four groups take): live rows
    with their slots out of order, a fresh one among them, dead rows behind
    them, against the recurrence; no other slot moves."""
    H, G, P, N = 8, 4, 8, 16
    x, B, C, dt, A, pool = _scan_inputs(T=5, H=H, G=G, P=P, N=N, slots=9, seed=3)
    monkeypatch.setattr(mamba2, "_STEP_VMEM_BYTES", mamba2._step_vmem_bytes(groups_a_step, H // G, P, N))
    assert mamba2._groups_per_step(G, H // G, P, N) == groups_a_step
    slot, fresh = jnp.asarray([7, 2, 8, 0, 4]), jnp.asarray([0, 0, 1, 0, 1])
    y, got = mamba2.mamba2_step(x, B, C, dt, A, pool, slot, fresh, n_live, interpret=True)
    ys, want = _stepped_rows(x, B, C, dt, A, pool, slot, fresh, n_live)
    assert all(np.abs(np.asarray(y[r]) - yr).max() < 2e-6 for r, yr in enumerate(ys))
    assert np.abs(np.asarray(got) - want).max() < 2e-6
    untouched = [s for s in range(9) if s not in [int(a) for a in slot[:n_live]]]
    assert np.array_equal(np.asarray(got)[untouched], np.asarray(pool)[untouched])


@pytest.mark.parametrize("H,G,P,N", [(6, 2, 8, 16), (12, 2, 16, 256), (8, 4, 8, 128)],
                         ids=["three_heads_a_group", "a_state_of_two_lane_tiles", "a_state_of_one_lane_tile"])
def test_the_recurrent_steps_lane_sums_at_other_head_counts_and_state_widths(H, G, P, N):
    """The kernel's body where the heads of a step are no power of two (the
    tree of sums merges with nothing) and where a state row is two tiles of
    lanes (folded before the tree) or exactly one, against the recurrence."""
    x, B, C, dt, A, pool = _scan_inputs(T=3, H=H, G=G, P=P, N=N, slots=5, seed=5)
    slot, fresh = jnp.asarray([4, 0, 2]), jnp.asarray([0, 1, 0])
    y, got = mamba2.mamba2_step(x, B, C, dt, A, pool, slot, fresh, 3, interpret=True)
    ys, want = _stepped_rows(x, B, C, dt, A, pool, slot, fresh, 3)
    assert all(np.abs(np.asarray(y[r]) - yr).max() < 2e-5 * max(1.0, float(np.abs(yr).max())) for r, yr in enumerate(ys))
    assert np.abs(np.asarray(got) - want).max() < 2e-6
    with pytest.raises(ValueError, match="neither whole tiles"):
        mamba2.mamba2_step(x, B[..., :12], C[..., :12], dt, A, pool[..., :12], slot, fresh, 3, interpret=True)


@pytest.mark.parametrize("name,G,hb,P,N,want", [
    ("published", 8, 8, 64, 128, 8),          # a row's whole layer state, 2 MiB, one block
    ("tiny_twin", 2, 2, 8, 16, 2),
    ("state_four_times_as_wide", 8, 8, 64, 512, 4),
    ("head_four_times_as_wide", 8, 8, 256, 128, 4),
    ("six_groups_sixteen_times", 6, 8, 256, 512, 1),
])
def test_the_groups_a_grid_step_follow_from_the_static_shapes_alone(name, G, hb, P, N, want):
    gs = mamba2._groups_per_step(G, hb, P, N)
    assert gs == want and G % gs == 0
    assert gs == 1 or mamba2._step_vmem_bytes(gs, hb, P, N) <= mamba2._STEP_VMEM_BYTES
    # the next divisor up would not fit
    larger = [d for d in range(gs + 1, G + 1) if G % d == 0]
    assert not larger or mamba2._step_vmem_bytes(larger[0], hb, P, N) > mamba2._STEP_VMEM_BYTES


def test_a_tile_plan_gives_chunk_rows_their_own_tiles_and_one_token_rows_none():
    n_tok = np.array([5, 1, 0, 37, 1, 2, 0, 0], np.int32)
    row, tok0, cnt, first, n_tiles = mamba2.tile_plan(n_tok, 64, 8, xp=np)
    assert int(n_tiles) == 1 + 5 + 1 and len(row) == 64 // 8 + 8
    assert list(row[:7]) == [0, 3, 3, 3, 3, 3, 5] and list(cnt[:7]) == [5, 8, 8, 8, 8, 5, 2]
    assert list(tok0[:7]) == [0, 6, 14, 22, 30, 38, 44] and list(first[:7]) == [1, 1, 0, 0, 0, 0, 1]
    assert not cnt[7:].any()


def test_the_two_chips_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tiny):
    """(d) The PROGRAM's expert layer as each of the two chips runs it
    (experts 0-7 and 8-15 of 16 held, the router over all 16) plus the shared
    expert counted once, against the reference's uncut layer with every
    expert held: float32 grouped matmuls against dense ones, 1e-5."""
    full = nemotron_config("tiny", dtype=jnp.float32)
    whole = TransformerLM(full).init(jax.random.PRNGKey(3))
    whole["blocks"]["gate_bias"] = whole["blocks"]["gate_bias"] * 20.0
    h = jnp.asarray(np.random.default_rng(5).normal(size=(12, 64)), jnp.float32)
    layer = 2
    blk = {k: jnp.asarray(whole["blocks"][k][layer]) for k in ("gate_wg", "gate_bias", "shared_wi", "shared_wo")}
    experts = {k: whole["blocks"][k] for k in ("moe_wi", "moe_wo")}
    hp = nemotron_reference.hyper_from_published(_published(full))
    with jax.default_matmul_precision("highest"):
        uncut = nemotron_reference.expert_layer(h, blk, experts, layer, hp)
        shared = nemotron_reference._expert(h, blk["shared_wi"], blk["shared_wo"], "relu2")
        total = jnp.square(jax.nn.relu(h @ blk["shared_wi"])) @ blk["shared_wo"]
        for first in (0, 8):
            cut = nemotron_config("tiny", dtype=jnp.float32, moe_experts_held=8, moe_first_expert=first)
            moe = build_modules(cut, RaggedInferenceEngineConfig(kv_block_size=BLOCK))["moe"]
            total = total + moe(h, blk["gate_wg"], experts["moe_wi"][:, first:first + 8], None,
                                experts["moe_wo"][:, first:first + 8], layer=layer, gate_bias=blk["gate_bias"])
    assert _rel(np.asarray(total), np.asarray(uncut)) < 1e-5 and _rel(np.asarray(shared), np.asarray(uncut)) > 0.1


def test_an_expert_width_that_is_no_whole_number_of_lane_tiles_is_stored_padded_with_zeros():
    """1,856 is 14.5 lane tiles: the experts' matrices are stored at 1,920
    (``expert_rows``), the padding zeros, so that the device lays them with
    the expert's width last and ``moe_gmm`` reads its tiles in place. At 200
    -> 256: the program's expert layer over the padded arrays is the
    reference's over the published width (float32, 1e-5)."""
    assert nemotron_config("3-nano-30b-a3b").expert_rows == 1920 and nemotron_config("tiny").expert_rows == 48
    cfg = nemotron_config("tiny", dtype=jnp.float32, num_layers=6, moe_intermediate_size=200)
    assert (cfg.expert_size, cfg.expert_rows) == (200, 256)
    blocks = TransformerLM(cfg).init(jax.random.PRNGKey(4))["blocks"]
    assert blocks["moe_wi"].shape == (2, 16, 64, 256) and blocks["moe_wo"].shape == (2, 16, 256, 64)
    assert not np.asarray(blocks["moe_wi"][..., 200:]).any() and not np.asarray(blocks["moe_wo"][:, :, 200:]).any()
    assert np.asarray(blocks["moe_wi"][..., :200]).all()
    h = jnp.asarray(np.random.default_rng(6).normal(size=(10, 64)), jnp.float32)
    blk = {k: blocks[k][1] for k in ("gate_wg", "gate_bias", "shared_wi", "shared_wo")}
    hp = nemotron_reference.hyper_from_published(_published(cfg))
    moe = build_modules(cfg, RaggedInferenceEngineConfig(kv_block_size=BLOCK))["moe"]
    with jax.default_matmul_precision("highest"):
        want = nemotron_reference.expert_layer(h, blk, blocks, 1, hp, shared=False)
        got = moe(h, blk["gate_wg"], blocks["moe_wi"], None, blocks["moe_wo"], layer=1, gate_bias=blk["gate_bias"])
    assert hp["expert_width"] == 200 and _rel(np.asarray(got), np.asarray(want)) < 1e-5


def _ragged_logits(family):
    """A 12-token step of two rows (8 and 4 tokens) through ``ragged_forward``
    on seeded float32 weights: six logits and the sum of all, to 4 digits."""
    from deepspeed_tpu.inference.v2.model_implementations.flat_model import ragged_forward
    from deepspeed_tpu.models import minicpm_config, mistral_config, solar_config

    cfg = {"solar": lambda: solar_config("tiny", dtype=jnp.float32),
           "minicpm": lambda: minicpm_config("tiny", dtype=jnp.float32),
           "mistral": lambda: mistral_config("tiny", dtype=jnp.float32)}[family]()
    params = TransformerLM(cfg).init(jax.random.PRNGKey(1))
    block = cfg.sparse_block_size if cfg.sparse_topk else 16
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, size=12, dtype=np.int32)
    T, S, NB = 16, 2, 8
    seq_idx = np.zeros(T, np.int32)
    seq_idx[8:12] = 1
    pos = np.concatenate([np.arange(8), np.arange(4), np.zeros(4)]).astype(np.int32)
    tables = np.arange(S * 4, dtype=np.int32).reshape(S, 4)
    La = len(cfg.kv_layers)
    pool = lambda: jnp.zeros((La, NB * block, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    extra = {}
    if cfg.state_layers:
        state, *tail = cfg.state_entry
        extra.update(state_slots=jnp.asarray([2, 0], jnp.int32),
                     state_pools=tuple(jnp.zeros((len(cfg.state_layers), 4) + tuple(part), jnp.float32) for part in (state, *tail)))
    if cfg.sparse_topk:
        stride, heads, width = cfg.index_entry
        extra["index_pool"] = jnp.zeros((La, NB * block // stride, heads, width), jnp.float32)
    got = np.asarray(ragged_forward(cfg, block, params, jnp.asarray(np.pad(ids, (0, 4))), jnp.asarray(seq_idx), jnp.asarray(pos),
                                    jnp.asarray(np.arange(T) < 12), jnp.asarray(tables), jnp.asarray([7, 11], jnp.int32), pool(),
                                    pool(), **extra)[0], np.float64)
    return [round(float(v), 4) for v in got[0, :3].tolist() + got[1, :3].tolist() + [np.abs(got).sum()]]


# recorded on the parent commit (a8fa34f) by ``_ragged_logits``, the same lines on the same seeds
_PARENT_LOGITS = {"solar": [1.6172, -0.4366, -0.4653, 0.8729, 2.3377, -0.4036, 844.0829],
                  "minicpm": [0.33, -0.4555, -0.2645, -0.1273, 0.2275, 0.2924, 200.7493],
                  "mistral": [-1.7247, -2.0733, -1.0905, 1.1465, 0.0016, 0.0851, 51164.9982]}


@pytest.mark.parametrize("family", ["solar", "minicpm", "mistral"])
def test_the_other_state_kinds_and_a_dense_model_give_the_logits_they_gave(family):
    """(e) The table of mixers changed nothing: a delta-rule model, a
    lightning one and a dense one through ``ragged_forward`` give the logits
    they gave at the parent commit (float32 on the CPU; the lowered programs
    are equal text, so the bits are equal where the compiler's options are:
    the suite compiles without most optimisations, which moves a sum of
    50,000 in its eighth digit, hence 1e-5)."""
    assert _ragged_logits(family) == pytest.approx(_PARENT_LOGITS[family], rel=1e-5, abs=2e-4)


def test_the_pools_leave_the_expert_layers_out_of_both_kinds(tiny, engine):
    """K/V for the TWO attention layers, a slot a tracked sequence for the six
    Mamba layers, and the five expert layers in neither."""
    cfg = tiny[0]
    kv = engine.state_manager.kv_cache
    assert cfg.kv_layers == (5, 12) and cfg.state_layers == (0, 2, 4, 7, 9, 11) and cfg.expert_layers == (1, 3, 6, 8, 10)
    assert cfg.mlp_layers == cfg.expert_layers and cfg.dense_layers == () and cfg.num_expert_layers == 5
    assert len(cfg.kv_layers) + len(cfg.state_layers) < cfg.num_layers
    assert cfg.state_entry == ((4, 8, 16), (3, 96))
    assert kv.k_pool.shape == (2, 48 * BLOCK, 2, 16) and kv.num_layers == 2
    assert kv.state_pool.shape == (6, 4, 4, 8, 16) and kv.state_pool.dtype == jnp.float32
    assert kv.tail_pool.shape == (6, 4, 3, 96) and kv.state_entry_bytes() == 4 * 8 * 16 * 4 + 3 * 96 * 4
    assert len(kv.pools()) == 4
    blocks = tiny[1]["blocks"]
    assert "ln2_scale" not in blocks and "moe_wg" not in blocks and "shared_wg" not in blocks
    assert blocks["ln1_scale"].shape[0] == 13 and blocks["wq"].shape[0] == 2 and blocks["m2_w_in"].shape[0] == 6
    assert blocks["moe_wi"].shape[:2] == (5, 8) and blocks["shared_wi"].shape == (5, 64, 96)


def test_the_published_preset_is_the_catalog_row():
    cfg = nemotron_config("3-nano-30b-a3b")
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        52, 2688, 131072, 32, 2, 128)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_state_size, cfg.mamba_n_groups, cfg.mamba_conv_size) == (
        64, 64, 128, 8, 4)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.expert_size, cfg.moe_shared_expert_size, cfg.moe_route_scale) == (
        128, 6, 1856, 3712, 2.5)
    kinds = [cfg.layer_types.count(k) for k in ("state_space", "mlp_only", "full_attention")]
    assert kinds == [23, 23, 6] and cfg.mlp == "relu2" and cfg.single_branch_layers and not cfg.tie_embeddings
    assert cfg.state_entry == ((64, 64, 128), (3, 6144)) and cfg.kv_entry == ((2, 128), (2, 128))
    cut = nemotron_config("3-nano-30b-a3b", num_layers=13, moe_experts_held=64)
    assert "".join({"state_space": "M", "mlp_only": "E", "full_attention": "*"}[k] for k in cut.layer_types) == PATTERN[:13]


@pytest.mark.parametrize("call", ["forward_hidden", "forward_with_cache", "pipeline_stages", "int8_kv", "speculative_config",
                                  "speculate_decode", "prefix_cache", "host_tier", "prefix_cache_config", "rollback_to",
                                  "export_sequence_kv", "scan", "mlp_only_elsewhere", "no_kv_layer"])
def test_what_is_not_built_is_refused_by_name(tiny, engine, call):
    cfg, params, ids = tiny
    kv = engine.state_manager.kv_cache
    if call == "forward_hidden":
        with pytest.raises(NotImplementedError, match="state-space layer.*layers of ONE branch"):
            tfm.forward_hidden(cfg, params, jnp.asarray(ids[None, :8]))
    elif call == "forward_with_cache":
        with pytest.raises(NotImplementedError, match="state-space layer"):
            tfm.forward_with_cache(cfg, params, jnp.asarray(ids[None, :8]), None)
    elif call == "pipeline_stages":
        with pytest.raises(NotImplementedError, match="layers of ONE branch"):
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
    elif call == "scan":
        from deepspeed_tpu.inference.v2.model_implementations.flat_model import ragged_forward

        pools = kv.pools()
        with pytest.raises(NotImplementedError, match="state-space or mlp-only layers.*under lax.scan"):
            ragged_forward(cfg, BLOCK, params, jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32),
                           jnp.zeros(8, bool), jnp.zeros((4, 16), jnp.int32), jnp.zeros(4, jnp.int32), pools[0], pools[1],
                           unroll=False, state_pools=tuple(pools[2:]), state_slots=jnp.zeros(4, jnp.int32))
    elif call == "mlp_only_elsewhere":
        with pytest.raises(ValueError, match="'mlp_only' layer belongs to a model of single_branch_layers"):
            solar_config("tiny", layer_types=("full_attention", "mlp_only", "linear_attention", "linear_attention"))
    else:
        with pytest.raises(NotImplementedError, match="no layer that caches K and V"):
            nemotron_config("tiny", num_layers=5)   # MEMEM: no attention layer among them


def test_a_step_span_says_what_the_mamba_layers_the_experts_and_the_kv_layers_had_to_do(tiny, engine, tmp_path):
    """``mamba_row_calls`` and ``mamba_tokens`` beside Solar's state counts,
    the expert counts over the FIVE expert layers and the attention counts over
    the TWO layers that cache K and V, by hand: a 13-token chunk after 32
    cached tokens beside a one-token row at 5, then a decode horizon of 4."""
    from benchmark.lib import program_spans

    cfg, params, ids = tiny

    def serve(a, b):
        engine.put([a], [ids[:32]], sample=None)
        engine.put([b], [ids[:5]], sample=None)
        engine.put([a, b], [ids[32:45], ids[5:6]], sample="greedy")
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
    entry = 4 * 8 * 16 * 4 + 3 * 96 * 4
    assert (prefill["attn_pairs"], prefill["attn_ctx_tokens"]) == (2 * (sum(32 + i + 1 for i in range(13)) + 6), 2 * (45 + 6))
    assert (prefill["state_rows"], prefill["lin_tokens"], prefill["state_entry_bytes"]) == (2, 6 * 14, entry)
    assert (prefill["mamba_row_calls"], prefill["mamba_tokens"]) == (2 * 6, 14 * 6)
    assert prefill["state_rows_stepped"] == 1 and prefill["state_bytes"] == 2 * 6 * entry * 2
    assert (prefill["state_slots_live"], prefill["state_slots_total"]) == (2, 4)
    assert prefill["kernel"].endswith("mamba2_chunk_scan:128:ragged+mamba2_recurrent_step:1:one-token-rows")
    assert prefill["moe_slots_routed"] == 14 * 2 * 5 and prefill["experts_total"] == 8 * 5
    assert 0 < prefill["moe_slots"] <= prefill["moe_slots_routed"] and (prefill["experts_held"], prefill["experts_published"]) == (8, 16)
    (decode, ) = program_spans.spans_named(trace, "serving/decode")
    assert decode.args["attn_pairs"] == 2 * (sum(45 + j + 1 for j in range(4)) + sum(6 + j + 1 for j in range(4)))
    assert (decode.args["mamba_row_calls"], decode.args["mamba_tokens"]) == (8 * 6, 8 * 6)
    assert decode.args["state_rows_stepped"] == 8 and decode.args["moe_slots_routed"] == 8 * 2 * 5
    assert decode.args["kernel"].endswith("mamba2_recurrent_step:1:one-token-rows")
    assert "mamba2_chunk_scan" not in decode.args["kernel"]
