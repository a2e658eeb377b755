"""The Trinity family (``models/trinity.py``) on the serving path at a small
size, on seeded random weights, against the benchmark's plain float32
reference (``benchmark/lib/trinity_reference.py``, which imports nothing of
the program): prefill and decode through the paged cache, whole and as a
share of the experts; the shares of an expert layer add up to the uncut
layer; the router's rule case by case; what an assignment to an absent
expert costs (nothing) and what the counters say; which layers carry a
position; that the gate, the q/k norm and each of the four norms are in the
logits; the span arguments of a step; the refusal of the whole-sequence
forwards. Tiny shapes: hidden 64, 6 query / 2 KV heads of 16 (a group of 3),
16 experts top-2 of width 48 beside one shared expert, 1 dense + 4 expert
layers (window, window, window, full, window), a window of 16 under sequences
of 48."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmark.lib import program_spans, trinity_reference  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations.flat_model import ragged_forward  # noqa: E402
from deepspeed_tpu.inference.v2.modules.configs import DSMoEConfig  # noqa: E402
from deepspeed_tpu.inference.v2.modules.implementations.moe import GroupedGemmMoE  # noqa: E402
from deepspeed_tpu.models import TransformerLM, mellum_config, trinity_config  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.moe import grouped  # noqa: E402
from deepspeed_tpu.monitor.metrics import get_metrics  # noqa: E402
from deepspeed_tpu.monitor.trace import get_tracer  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_tracer():
    get_tracer().reset()
    yield
    get_tracer().reset()
    get_metrics().disable()
    get_metrics().reset()


def _engine(cfg, params, attention="dense_blocked_attention"):
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=64,
                              max_ragged_sequence_count=4, max_context=128, token_buckets=(64, ), seq_buckets=(4, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=16, num_kv_blocks=32, kv_dtype=jnp.float32,
                                       state_manager=sm)
    icfg.modules.attention = {"name": attention, "implementation_config": {"interpret": True}}
    return InferenceEngineV2(TransformerLM(cfg), icfg, params=params)


@pytest.fixture(scope="module")
def engine_of():
    """One engine a (configuration, attention) for the module. The weights are
    an argument of every step program (``engine.params``, no module here
    transforms them), so a test hands its own to the engine it shares, and
    ``_prefill_then_decode`` flushes what it fed: what a case compares is what
    an engine of its own would give, without tracing every program again."""
    built = {}

    def engine_of(cfg, params, attention="dense_blocked_attention"):
        key = (repr(cfg), attention)
        if key not in built:
            built[key] = _engine(cfg, params, attention)
        built[key].params = params
        return built[key]

    return engine_of


@pytest.fixture(scope="module")
def served_once():
    """What several cases of one parametrised test would each compute the
    same: ``served_once(key, make)`` makes it at the first ask and hands it to
    the others, so that every case still counts and the engine runs once."""
    kept = {}

    def once(key, make):
        if key not in kept:
            kept[key] = make()
        return kept[key]

    return once


def _published(cfg) -> dict:
    """The configuration-file keys the reference reads, from a program config."""
    return {"num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.norm_eps,
            "sliding_window": cfg.sliding_window, "rope_theta": cfg.rope_theta, "mup_enabled": True,
            "layer_types": list(cfg.layer_types), "num_dense_layers": cfg.moe_num_dense_layers,
            "num_experts_per_tok": cfg.moe_top_k, "route_norm": cfg.moe_norm_topk_prob,
            "route_scale": cfg.moe_route_scale, "score_func": cfg.moe_score_func,
            "num_experts": cfg.experts_held, "num_experts_published": cfg.moe_num_experts,
            "first_expert": cfg.moe_first_expert}


def _prefill_then_decode(engine, ids, n_prompt, uid=7):
    got = [np.asarray(engine.put([uid], [ids[:n_prompt]], sample=None), np.float32)[0]]
    for j in range(n_prompt, len(ids)):
        got.append(np.asarray(engine.put([uid], [ids[j:j + 1]], sample=None), np.float32)[0])
    engine.flush(uid)
    return np.stack(got)


def _ids(cfg, n=48):
    return np.random.default_rng(0).integers(0, cfg.vocab_size, size=n, dtype=np.int32)


def _reference_logits(cfg, params, ids, positions, **switches):
    hp = {**trinity_reference.hyper_from_published(_published(cfg)), **switches}
    return np.asarray(trinity_reference.forward_logits(hp, params, jnp.asarray(ids[None]), positions))[0]


def _rel(got, ref):
    return np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


@pytest.mark.parametrize("attention,held,first", [("dense_blocked_attention", None, 0),
                                                  ("paged_pallas_attention", None, 0),
                                                  ("dense_blocked_attention", 4, 4),
                                                  ("paged_pallas_attention", 4, 12)])
def test_engine_prefill_and_decode_match_the_plain_reference(attention, held, first, engine_of):
    """A 40-token prefill (2.5 windows) and 8 positions decoded through the
    paged cache against the reference's full forward pass, with every expert
    held and with a share of 4 of the 16 (the router still scores all 16, an
    absent expert's term is left out on both sides and the partial result
    goes on to the next layer). Both sides are float32 on the same weights
    and the routing agrees, so what is left is the order of float32 sums:
    measured 1.5e-6 to 2.1e-6 relative L2; 2e-5 is an order above and four
    under what a missing gate, norm, bias or scale gives (0.4 to 1.5)."""
    cfg = trinity_config("tiny", dtype=jnp.float32, moe_experts_held=held, moe_first_expert=first)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
    assert params["blocks"]["moe_wi"].shape == (4, held or 16, 64, 48), "expert layers x held experts only"
    assert params["blocks"]["w_up"].shape == (1, 64, 128) and params["blocks"]["gate_wg"].shape == (4, 64, 16)
    ids = _ids(cfg)
    got = _prefill_then_decode(engine_of(cfg, params, attention), ids, 40)
    rel = _rel(got, _reference_logits(cfg, params, ids, list(range(39, 48))))
    assert rel.max() < 2e-5, rel


@pytest.mark.parametrize("switch,value", [("gate", False), ("qk_norm", False), ("rope_in_full_layers", True),
                                          ("selection_bias", False), ("post_norms", False),
                                          ("route_scale", 1.0), ("window", 10**6)])
def test_the_reference_without_one_mechanism_is_far_from_the_program(switch, value, engine_of, served_once):
    """The controls the chip check runs, at the small size: the reference
    with one mechanism turned off is of order one away from the program, so
    each of them is in the program's logits (and the reference's switch does
    what it says). The program's side is the same for every switch and is run once."""
    def serve():
        cfg = trinity_config("tiny", dtype=jnp.float32, moe_experts_held=8)
        params = TransformerLM(cfg).init(jax.random.PRNGKey(3))
        ids = _ids(cfg)
        return cfg, params, ids, _prefill_then_decode(engine_of(cfg, params), ids, 40)

    cfg, params, ids, got = served_once("share_of_8", serve)
    assert _rel(got, _reference_logits(cfg, params, ids, list(range(39, 48)), **{switch: value})).max() > 0.05


@pytest.mark.parametrize("name,change", [
    ("w_attn_gate", lambda a: -a), ("q_norm_scale", jnp.ones_like), ("k_norm_scale", jnp.ones_like),
    ("ln1_scale", jnp.ones_like), ("ln1_post_scale", jnp.ones_like), ("ln2_scale", jnp.ones_like),
    ("ln2_post_scale", jnp.ones_like), ("gate_bias", jnp.zeros_like)])
def test_the_gate_the_qk_norm_the_four_norms_and_the_bias_each_change_the_logits(name, change, engine_of, served_once):
    """Each of the family's own parameters is read where the reference reads
    it: with that one array changed (a gain set to one, the gate's matrix
    negated, the selection bias zeroed) the program's logits move, and they
    still match the reference on the changed weights. (Halving the gate would
    not show: the norm after attention divides a factor out.)"""
    cfg = trinity_config("tiny", dtype=jnp.float32)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(5))
    if name == "gate_bias":  # drawn of the order of the gap between the k-th and the next score: widen it
        params["blocks"]["gate_bias"] = params["blocks"]["gate_bias"] * 10
    ids = _ids(cfg, 24)
    # the unchanged weights' logits are the same for every array but the widened bias: run once for each
    before = served_once(("before", name == "gate_bias"), lambda: _prefill_then_decode(engine_of(cfg, params), ids, 20))
    params["blocks"][name] = change(params["blocks"][name])
    after = _prefill_then_decode(engine_of(cfg, params), ids, 20)
    assert _rel(after, before).max() > 1e-3
    assert _rel(after, _reference_logits(cfg, params, ids, list(range(19, 24)))).max() < 2e-5


@pytest.mark.parametrize("seed,layer", [(0, 0), (1, 3)])
def test_the_shares_add_up_to_the_uncut_layer(seed, layer):
    """What ties a chip's share to the model: the routed parts that four
    shares of 4 experts compute (the serving module, told which experts it
    holds), with the shared expert counted once, equal the uncut reference's
    expert layer on the same tokens; and every routed slot lands on exactly
    one share."""
    cfg = trinity_config("tiny", dtype=jnp.float32)
    blocks = TransformerLM(cfg).init(jax.random.PRNGKey(seed))["blocks"]
    h = jax.random.normal(jax.random.PRNGKey(seed + 10), (24, cfg.hidden_size), jnp.float32)
    total, slots = jnp.zeros_like(h), 0
    for first in range(0, 16, 4):
        moe = GroupedGemmMoE(DSMoEConfig(n_experts=16, top_k=2, activation="swiglu", score_func="sigmoid",
                                         route_scale=cfg.moe_route_scale, n_held=4, first_expert=first,
                                         dtype=jnp.float32), {})
        held = {name: blocks[name][:, first:first + 4] for name in ("moe_wi", "moe_wg", "moe_wo")}
        part, stats = moe(h, blocks["gate_wg"][layer], held["moe_wi"], held["moe_wg"], held["moe_wo"],
                          with_stats=True, layer=layer, gate_bias=blocks["gate_bias"][layer])
        total, slots = total + part, slots + int(stats[2])
    shared = (jax.nn.silu(h @ blocks["shared_wg"][layer]) * (h @ blocks["shared_wi"][layer])) @ blocks["shared_wo"][layer]
    hp = trinity_reference.hyper_from_published(_published(cfg))
    assert (hp["n_held"], hp["n_experts"], hp["first_expert"]) == (16, 16, 0)
    blk = {name: blocks[name][layer] for name in ("gate_wg", "gate_bias", "shared_wi", "shared_wg", "shared_wo")}
    with jax.default_matmul_precision("highest"):
        whole = trinity_reference.expert_mlp(h, blk, {n: blocks[n] for n in ("moe_wi", "moe_wg", "moe_wo")}, layer, hp)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole), rtol=2e-5, atol=2e-5)
    assert slots == 24 * 2


@pytest.mark.parametrize("case", ["bias_chooses_scores_weigh", "normalised", "scaled", "unnormalised",
                                  "softmax_rule_unchanged"])
def test_the_routers_rule(case):
    """Scores are set through an identity router, ``x`` holding the logits.
    With logits (2, 1, 0, -1) the sigmoid scores are 0.881, 0.731, 0.5, 0.269."""
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    eye = jnp.eye(4)
    if case == "bias_chooses_scores_weigh":
        # a bias that lifts expert 3 over expert 1 changes the chosen set; the weights stay the scores'
        idx, w = grouped.route_topk(logits, eye, 2, True, "sigmoid", jnp.asarray([0.0, 0.0, 0.0, 0.5]))
        assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]
        by_expert = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(w)[0].tolist()))
        assert by_expert[0] == pytest.approx(s[0] / (s[0] + s[3])) and by_expert[3] == pytest.approx(s[3] / (s[0] + s[3]))
    elif case == "normalised":
        idx, w = grouped.route_topk(logits, eye, 2, True, "sigmoid", jnp.zeros(4))
        assert np.asarray(idx)[0].tolist() == [0, 1] and float(jnp.sum(w)) == pytest.approx(1.0)
        assert np.asarray(w)[0].tolist() == pytest.approx([s[0] / (s[0] + s[1]), s[1] / (s[0] + s[1])])
    elif case == "scaled":
        _, w = grouped.route_topk(logits, eye, 2, True, "sigmoid", jnp.zeros(4), 2.448)
        assert float(jnp.sum(w)) == pytest.approx(2.448)
    elif case == "unnormalised":
        _, w = grouped.route_topk(logits, eye, 2, False, "sigmoid", None, 2.0)
        assert np.asarray(w)[0].tolist() == pytest.approx([2 * s[0], 2 * s[1]])
    else:
        idx, w = grouped.route_topk(logits, eye, 2, True)
        p = np.asarray(jax.nn.softmax(logits))[0]
        assert np.asarray(idx)[0].tolist() == [0, 1]
        assert np.asarray(w)[0].tolist() == pytest.approx([p[0] / (p[0] + p[1]), p[1] / (p[0] + p[1])])


@pytest.mark.parametrize("case", ["some_absent", "all_here", "none_here"])
def test_an_assignment_to_an_absent_expert_takes_no_row(case):
    """6 tokens x top-2 over 16 experts, experts 4..7 held. An assignment to
    another expert gets no row (destination ``T_pad``), no block and weight
    zero, the counts are of the held experts alone; a bucket whose every slot
    lands here drops nothing (the buffer is sized for all of them)."""
    chosen = {"some_absent": [[4, 0], [5, 15], [7, 4], [9, 10], [4, 5], [12, 6]],
              "all_here": [[4, 5], [6, 7], [4, 6], [5, 7], [4, 7], [5, 6]],
              "none_here": [[0, 1], [2, 3], [8, 9], [10, 11], [12, 13], [14, 15]]}[case]
    top_idx = jnp.asarray(chosen, jnp.int32)
    local, w = grouped.hold_experts(top_idx, jnp.full(top_idx.shape, 0.5), first=4, held=4)
    here = (np.asarray(top_idx) >= 4) & (np.asarray(top_idx) < 8)
    assert (np.asarray(local)[here] == np.asarray(top_idx)[here] - 4).all() and (np.asarray(local)[~here] == 4).all()
    assert (np.asarray(w)[~here] == 0).all() and (np.asarray(w)[here] == 0.5).all()
    block_rows = grouped.pick_block_rows(12 * 4 // 16, 4)
    _, flat_w, dest, block_expert, t_pad, n_live, sizes = grouped.block_align_dispatch(
        local, w, 4, block_rows, cover_all_experts=False)
    dest, flat_here = np.asarray(dest), here.reshape(-1)
    assert block_rows == 8 and t_pad >= 12, "room for every slot of the bucket"
    assert (dest[~flat_here] == t_pad).all() and (dest[flat_here] < t_pad).all()
    assert len(set(dest[flat_here].tolist())) == flat_here.sum(), "every slot that lands here has a row of its own"
    assert int(jnp.sum(sizes)) == flat_here.sum() and int(n_live) == len({e for e in np.asarray(top_idx)[here].tolist()})
    # through the FFN: the held slots' terms alone
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 8), jnp.float32)
    wi, wg = (jax.random.normal(jax.random.PRNGKey(i), (4, 8, 16), jnp.float32) for i in (1, 2))
    wo = jax.random.normal(jax.random.PRNGKey(3), (4, 16, 8), jnp.float32)
    y, stats = grouped.grouped_moe_ffn(x, local, w, wi, wo, wg=wg, differentiable=False, with_stats=True,
                                       expected_slots=2)
    want = np.zeros((6, 8), np.float32)
    for t, row in enumerate(chosen):
        for e in row:
            if 4 <= e < 8:
                want[t] += 0.5 * np.asarray((jax.nn.silu(x[t] @ wg[e - 4]) * (x[t] @ wi[e - 4])) @ wo[e - 4])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    assert np.asarray(stats).tolist() == [int(n_live), int(jnp.max(sizes)), int(flat_here.sum())]


def _one_layer_logits(kind, pos_ids):
    """One expert layer of attention kind ``kind`` through ``ragged_forward``
    itself: 12 tokens of one sequence in one step, rope at ``pos_ids``."""
    cfg = trinity_config("tiny", dtype=jnp.float32, num_layers=1, moe_num_dense_layers=0, layer_types=(kind, ),
                         sliding_window=64)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(1))
    n, bs = 12, 16
    pool = jnp.zeros((1, 4 * bs, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    logits, _, _ = ragged_forward(cfg, bs, params, jnp.arange(n, dtype=jnp.int32) + 3, jnp.zeros(n, jnp.int32),
                                  jnp.arange(n, dtype=jnp.int32), jnp.ones(n, bool), jnp.asarray([[1, 0, 0, 0]], jnp.int32),
                                  jnp.asarray([n - 1], jnp.int32), pool, pool, pos_ids=pos_ids)
    return np.asarray(logits)


@pytest.mark.parametrize("kind,moves", [("full_attention", False), ("sliding_attention", True)])
def test_a_full_layer_carries_no_position_and_a_window_layer_does(kind, moves):
    """The positions the rope sees are shifted by 5 and stretched by 3 (a
    shift alone leaves rope's scores where they were: it is relative): a full
    layer's output does not move by a bit, a window layer's does."""
    plain = _one_layer_logits(kind, None)
    moved = _one_layer_logits(kind, 3 * jnp.arange(12, dtype=jnp.int32) + 5)
    diff = np.abs(plain - moved).max()
    assert (diff > 1e-3) if moves else (diff == 0.0), diff


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


@pytest.mark.parametrize("family", ["trinity_share", "mellum"])
def test_step_spans_count_the_slots_that_landed_here(family, tmp_path):
    """A put of 10 + 5 tokens and a 3-step decode of both rows on a live span,
    read back with the benchmark's reader. Trinity, experts 0-3 of 16 held,
    router weights zero so that every score is 0.5 and the bias alone
    chooses: experts 2 and 9 in every expert layer, one of them here, so one
    slot a token a layer lands here and half of those routed. The dense layer
    routes nothing: 4 expert layers, not 5. Mellum holds every expert and
    says what it said: ``moe_slots == moe_slots_routed``."""
    if family == "trinity_share":
        cfg = trinity_config("tiny", dtype=jnp.float32, moe_experts_held=4)
        params = TransformerLM(cfg).init(jax.random.PRNGKey(2))
        params["blocks"]["gate_bias"] = jnp.zeros((4, 16)).at[:, 2].set(0.2).at[:, 9].set(0.1)
        layers, held, total, here = 4, 4, 16, 1
    else:
        cfg = mellum_config("tiny", dtype=jnp.float32)
        params = TransformerLM(cfg).init(jax.random.PRNGKey(2))
        layers, held, total, here = 4, 8, 8, 2
    params["blocks"]["gate_wg"] = jnp.zeros_like(params["blocks"]["gate_wg"])
    engine = _engine(cfg, params)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32) for n in (10, 5)]

    def serve(uids):
        first = engine.put(uids, prompts, sample="greedy")
        engine.decode(uids, [np.asarray([t], np.int32) for t in first], 3)
        for uid in uids:
            engine.flush(uid)

    serve([1, 2])  # compile first: the traced run is warm
    trace = _profiled(tmp_path, lambda: serve([3, 4]))
    (put, ) = program_spans.spans_named(trace, "serving/prefill")
    (dec, ) = program_spans.spans_named(trace, "serving/decode")
    moe = engine._modules["moe"]
    want_put = {"moe_slots": 15 * here * layers, "moe_slots_routed": 15 * 2 * layers,
                "moe_rows": moe.padded_rows(64) * layers, "experts_hit": here * layers,   # the engine's one token bucket
                "experts_total": held * layers, "expert_load_max": 15, "experts_held": held, "experts_published": total}
    want_dec = {"moe_slots": 2 * 3 * here * layers, "moe_slots_routed": 2 * 3 * 2 * layers,
                "moe_rows": moe.padded_rows(4) * layers * 3, "experts_hit": here * layers * 3,
                "experts_total": held * layers * 3, "expert_load_max": 2, "experts_held": held, "experts_published": total}
    assert {name: put.args[name] for name in want_put} == want_put
    assert {name: dec.args[name] for name in want_dec} == want_dec
    if family == "trinity_share":
        # 16 tokens x 2 = 32 slots may all land on the 4 experts here; 8 are expected: 8-row blocks
        assert moe.padded_rows(16) == 8 * ((32 + 4 * 7) // 8)


@pytest.mark.parametrize("call", ["forward", "forward_with_cache", "pipeline_stage"])
def test_whole_sequence_forwards_refuse_the_family(call):
    """``models/transformer.py`` scans one block over the layers; it refuses
    this family and names what the block lacks."""
    cfg = trinity_config("tiny", dtype=jnp.float32, moe_experts_held=4)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError) as refusal:
        if call == "forward":
            tfm.forward(cfg, params, ids)
        elif call == "forward_with_cache":
            tfm.forward_with_cache(cfg, params, ids, tfm.init_kv_cache(cfg, 1, 16))
        else:
            tfm._stage_scan_fn(cfg)
    for reason in ("layer_types", "leading dense layer", "a share of the experts (4 of 16)", "a shared expert",
                   "sigmoid", "a q/k norm", "gated attention", "norms after attention and MLP",
                   "rope in some layer kinds only", "a scaled embedding"):
        assert reason in str(refusal.value), reason


@pytest.mark.parametrize("flag,reason", [
    (dict(moe_num_dense_layers=1), "leading dense layer"), (dict(moe_experts_held=4), "a share of the experts"),
    (dict(moe_num_shared_experts=1), "a shared expert"), (dict(moe_score_func="sigmoid"), "sigmoid"),
    (dict(qk_norm=True), "a q/k norm"), (dict(attention_gate=True), "gated attention"),
    (dict(post_norms=True), "norms after attention and MLP"), (dict(embed_scale=8.0), "a scaled embedding")])
def test_each_new_field_alone_is_refused_by_the_scanned_forward(flag, reason):
    """A field the scanned block does not implement is never silently
    ignored: any one of them, set on a family that trains, refuses."""
    cfg = tfm.TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, moe_num_experts=8,
                                moe_top_k=2, moe_dropless=True, **flag)
    with pytest.raises(NotImplementedError, match=reason):
        tfm.forward(cfg, None, jnp.zeros((1, 4), jnp.int32))


def test_the_published_preset_is_the_published_model():
    cfg = trinity_config("large-preview")
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (60, 3072, 48, 8, 128)
    assert (cfg.intermediate_size, cfg.expert_size, cfg.moe_num_experts, cfg.moe_top_k) == (12288, 3072, 256, 4)
    assert (cfg.moe_num_dense_layers, cfg.moe_num_shared_experts, cfg.vocab_size) == (6, 1, 200192)
    assert cfg.layer_types == (("sliding_attention", ) * 3 + ("full_attention", )) * 15
    assert [cfg.layer_window(l) for l in range(4)] == [4096, 4096, 4096, None]
    assert cfg.rope_layer_types == ("sliding_attention", ), "the full layers carry no rope"
    assert cfg.embed_scale == pytest.approx(3072**0.5) and cfg.moe_route_scale == 2.448
    # the benchmark's cut: one chip of eight, 1 dense + 4 expert layers, the vocabulary whole
    cut = trinity_config("large-preview", num_layers=5, moe_num_dense_layers=1, moe_experts_held=32,
                         layer_types=list(cfg.layer_types))
    shapes = jax.eval_shape(lambda k: TransformerLM(cut).init(k), jax.random.PRNGKey(0))
    blocks = shapes["blocks"]
    assert blocks["moe_wi"].shape == (4, 32, 3072, 3072) and blocks["gate_wg"].shape == (4, 3072, 256)
    assert blocks["w_up"].shape == (1, 3072, 12288) and blocks["w_attn_gate"].shape == (5, 3072, 6144)
    assert blocks["gate_bias"].shape == (4, 256) and blocks["gate_bias"].dtype == jnp.float32
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert 5.39e9 < n < 5.41e9, n  # 10.80 GB in bf16
