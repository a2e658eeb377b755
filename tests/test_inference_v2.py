"""Inference v2 (FastGen-equivalent) tests — mirrors the reference's
tests/unit/inference/v2 layout: ragged/ machinery units + model-level
numerics vs the dense forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig,
                                        SchedulingError, SchedulingResult, build_model_engine)
from deepspeed_tpu.inference.v2.ragged import (BlockedAllocator, DSStateManager, RaggedBatchWrapper)
from deepspeed_tpu.models import llama2, opt
from deepspeed_tpu.models.transformer import forward


# ---------------------------------------------------------------- allocator
def test_allocator_roundtrip():
    a = BlockedAllocator(8)
    b1 = a.allocate(3)
    assert a.free_blocks == 5 and len(set(b1.tolist())) == 3
    b2 = a.allocate(5)
    assert a.free_blocks == 0
    with pytest.raises(ValueError):
        a.allocate(1)
    a.free(b1)
    assert a.free_blocks == 3
    b3 = a.allocate(3)
    assert sorted(b3.tolist()) == sorted(b1.tolist())


def test_allocator_invalid():
    a = BlockedAllocator(4)
    with pytest.raises(ValueError):
        a.allocate(0)
    with pytest.raises(ValueError):
        a.free(99)


# ---------------------------------------------------------------- manager
def test_state_manager_lifecycle():
    m = DSStateManager(num_layers=2, num_kv_heads=2, head_dim=8, num_blocks=16, block_size=4, dtype=jnp.float32)
    s = m.get_or_create_sequence(7)
    m.allocate_blocks(s, 10)  # 10 tokens @ block 4 -> 3 blocks
    assert s.cur_allocated_blocks == 3
    s.pre_forward(10)
    s.post_forward()
    assert s.seen_tokens == 10
    m.allocate_blocks(s, 2)  # 12 tokens -> 3 blocks, no new
    assert s.cur_allocated_blocks == 3
    m.allocate_blocks(s, 3)  # 13 -> 4 blocks
    assert s.cur_allocated_blocks == 4
    free_before = m.free_blocks
    m.flush_sequence(7)
    assert m.free_blocks == free_before + 4
    assert m.get_sequence(7) is None


# ---------------------------------------------------------------- wrapper
def test_ragged_wrapper_packing():
    m = DSStateManager(num_layers=1, num_kv_heads=1, head_dim=4, num_blocks=32, block_size=4, dtype=jnp.float32)
    w = RaggedBatchWrapper(max_ragged_batch_size=64, max_ragged_sequence_count=8, max_blocks_per_seq=4, block_size=4)
    s1, s2 = m.get_or_create_sequence(1), m.get_or_create_sequence(2)
    m.allocate_blocks(s1, 5)
    m.allocate_blocks(s2, 3)
    s2.seen_tokens = 6  # pretend decode continuation
    m.allocate_blocks(s2, 1)
    w.insert_sequence(s1, np.arange(5))
    w.insert_sequence(s2, np.array([42]))
    rb = w.finalize()
    assert rb.n_tokens == 6 and rb.n_seqs == 2
    assert rb.token_ids.shape[0] == 8  # bucket pad
    np.testing.assert_array_equal(rb.token_pos[:6], [0, 1, 2, 3, 4, 6])
    np.testing.assert_array_equal(rb.token_seq_idx[:6], [0, 0, 0, 0, 0, 1])
    np.testing.assert_array_equal(rb.last_token_idx[:2], [4, 5])
    assert rb.token_valid[:6].all() and not rb.token_valid[6:].any()


# ---------------------------------------------------------------- engine e2e
def _tiny_engine(model=None, **sm_over):
    model = model or llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                            intermediate_size=128, vocab_size=128, max_seq_len=256, dtype=jnp.float32,
                            attention_impl="reference")
    sm = dict(max_tracked_sequences=8, max_ragged_batch_size=64, max_ragged_sequence_count=4, max_context=64)
    sm.update(sm_over)
    # ONE put program an engine, the same for every test's engine of these limits
    sm.setdefault("token_buckets", (sm["max_ragged_batch_size"], ))
    sm.setdefault("seq_buckets", (sm["max_ragged_sequence_count"], ))
    cfg = RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=32, kv_dtype=jnp.float32,
                                      state_manager=DSStateManagerConfig(**sm), use_pallas_kernels="never")
    return InferenceEngineV2(model, cfg)


def test_engine_prefill_matches_dense():
    eng = _tiny_engine()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, size=17).astype(np.int32)
    logits = eng.put([11], [prompt])
    dense = forward(eng.model_config, eng.params, prompt[None])[0, -1]
    np.testing.assert_allclose(logits[0], np.asarray(dense), atol=2e-4, rtol=2e-4)


def test_engine_decode_matches_dense():
    """prefill + several decode steps == dense forward on the full prefix."""
    eng = _tiny_engine()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 128, size=9).astype(np.int32)
    toks = list(prompt)
    out = eng.put([5], [prompt])
    for step in range(4):
        nxt = int(out[0].argmax())
        toks.append(nxt)
        out = eng.put([5], [np.array([nxt])])
        dense = forward(eng.model_config, eng.params, np.asarray(toks, np.int32)[None])[0, -1]
        np.testing.assert_allclose(out[0], np.asarray(dense), atol=3e-4, rtol=3e-4)


def test_engine_mixed_batch_continuous():
    """Mixed prefill+decode in one ragged forward (SplitFuse composition)."""
    eng = _tiny_engine()
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, 128, size=12).astype(np.int32)
    p2 = rng.integers(0, 128, size=5).astype(np.int32)
    out1 = eng.put([1], [p1])  # seq 1 prefill alone
    # now: seq 1 decodes while seq 2 prefills, same forward
    out = eng.put([1, 2], [np.array([int(out1[0].argmax())]), p2])
    full1 = np.concatenate([p1, [int(out1[0].argmax())]])
    d1 = forward(eng.model_config, eng.params, full1[None])[0, -1]
    d2 = forward(eng.model_config, eng.params, p2[None])[0, -1]
    np.testing.assert_allclose(out[0], np.asarray(d1), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(out[1], np.asarray(d2), atol=3e-4, rtol=3e-4)


def test_engine_gpt_style_model():
    """learned positions + biases + tied embeddings path (opt family)."""
    eng = _tiny_engine(model=opt("tiny", num_layers=2, hidden_size=64, num_heads=4, vocab_size=128,
                                 intermediate_size=128, max_seq_len=256, dtype=jnp.float32,
                                 attention_impl="reference"))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 128, size=7).astype(np.int32)
    logits = eng.put([3], [prompt])
    dense = forward(eng.model_config, eng.params, prompt[None])[0, -1]
    np.testing.assert_allclose(logits[0], np.asarray(dense), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------- scheduling
def test_can_schedule_limits():
    eng = _tiny_engine(max_ragged_sequence_count=2, max_ragged_batch_size=16, max_context=32)
    assert eng.can_schedule([1, 2, 3], [1, 1, 1]) is SchedulingResult.BatchSequenceLimitExceeded
    assert eng.can_schedule([1], [17]) is SchedulingResult.TokenLimitExceeded
    assert eng.can_schedule([1], [40 % 33]) is SchedulingResult.Success
    assert eng.can_schedule([1], [16]) is SchedulingResult.Success
    # context ceiling: max_context=32
    eng.put([1], [np.arange(16, dtype=np.int32)])
    eng.put([1], [np.arange(16, dtype=np.int32)])
    assert eng.can_schedule([1], [1]) is SchedulingResult.KVCacheLimitExceeded
    with pytest.raises(SchedulingError):
        eng.put([1], [np.array([0])])


def test_kv_exhaustion_and_flush():
    eng = _tiny_engine(max_tracked_sequences=8, max_ragged_batch_size=64, max_context=64)
    # pool = 32 blocks of 8 = 256 slots; each seq of 33 tokens takes 5 blocks
    uids = list(range(6))
    for u in uids:
        eng.put([u], [np.arange(33, dtype=np.int32)])
    assert eng.free_blocks == 32 - 6 * 5
    assert eng.can_schedule([99], [25]) is SchedulingResult.KVCacheLimitExceeded  # needs 4 > 2 free
    eng.flush(0)
    assert eng.free_blocks == 7
    assert eng.can_schedule([99], [25]) is SchedulingResult.Success
    st = eng.query()
    assert st["tracked"] == 5


def test_factory_families():
    eng = build_model_engine("llama_v2", "tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                             intermediate_size=128, vocab_size=128, dtype=jnp.float32,
                             attention_impl="reference")
    out = eng.put([1], [np.arange(5, dtype=np.int32)])
    assert out.shape == (1, 128)
    with pytest.raises(ValueError):
        build_model_engine("bloomz")


def test_pallas_paged_kernel_interpret():
    """Pallas paged-attention kernel (interpret mode) vs gather reference."""
    from deepspeed_tpu.ops.pallas.paged_attention import _pallas_paged, paged_attention_reference

    rng = np.random.default_rng(0)
    T, nq, nkv, d, bs, nb, S, maxb = 16, 8, 4, 128, 8, 32, 4, 4
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb * bs + 1, nkv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb * bs + 1, nkv, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(nb)[:S * maxb].reshape(S, maxb).astype(np.int32))
    seq_idx = jnp.asarray(rng.integers(0, S, T).astype(np.int32))
    pos = jnp.asarray(rng.integers(0, maxb * bs, T).astype(np.int32))
    out = _pallas_paged(q, kp, vp, bt, seq_idx, pos, block_size=bs, interpret=True)
    ref = paged_attention_reference(q, kp, vp, bt, seq_idx, pos, block_size=bs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_multi_step_decode_matches_stepwise_put(eight_devices):
    """engine.decode (one compiled scan, on-device greedy feedback) must
    produce the same tokens as n_steps stepwise put() calls."""
    import copy

    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    m = TransformerLM(TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                                        intermediate_size=128, max_seq_len=256, dtype=jnp.float32,
                                        attention_impl="reference"))
    params = jax.jit(lambda r: m.init(r, None))(jax.random.PRNGKey(3))

    def build():
        ic = RaggedInferenceEngineConfig()
        ic.num_kv_blocks = 64
        ic.state_manager.max_context = 256
        return InferenceEngineV2(m, ic, params=params)

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 128, size=12, dtype=np.int32) for _ in range(3)]
    uids = [10, 11, 12]
    n_steps = 6

    # stepwise reference
    e1 = build()
    first = [np.argmax(e1.put([u], [p]))[None].astype(np.int32) for u, p in zip(uids, prompts)]
    toks_ref = []
    cur = [t.copy() for t in first]
    for _ in range(n_steps):
        toks_ref.append([int(c[0]) for c in cur])
        logits = e1.put(uids, cur)
        cur = [np.argmax(logits[i])[None].astype(np.int32) for i in range(len(uids))]
    toks_ref = np.asarray(toks_ref).T  # [S, n_steps] tokens FED at each step

    # fused multi-step decode: returns the tokens PRODUCED at each step
    e2 = build()
    first2 = [np.argmax(e2.put([u], [p]))[None].astype(np.int32) for u, p in zip(uids, prompts)]
    out = e2.decode(uids, first2, n_steps)
    assert out.shape == (3, n_steps)
    # produced[t] corresponds to the token fed at step t+1
    np.testing.assert_array_equal(out[:, :-1], toks_ref[:, 1:])
    # bookkeeping advanced by the whole horizon
    assert e2.query(uids[0]).seen_tokens == e1.query(uids[0]).seen_tokens


# ---------------------------------------------------------------- int8 weights
def test_quantized_weight_roundtrip():
    from deepspeed_tpu.inference.quantization import QuantizedWeight, quantize_weight_int8

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(3, 32, 48)), jnp.float32) * jnp.asarray(
        rng.uniform(0.01, 4.0, size=(1, 1, 48)), jnp.float32)  # per-channel ranges
    qw = quantize_weight_int8(w)
    assert qw.q.dtype == jnp.int8 and qw.scale.shape == (3, 1, 48)
    back = qw.astype(jnp.float32)
    # per-channel symmetric int8: error bounded by scale/2 per element
    bound = np.asarray(qw.scale) / 2 + 1e-8
    assert (np.abs(np.asarray(back - w)) <= bound).all()
    # slicing preserves the pairing (the unrolled layer loop slices leaves)
    np.testing.assert_allclose(np.asarray(qw[1].astype(jnp.float32)),
                               np.asarray(back[1]))
    # pytree registration: tree_map hits q and scale
    leaves = jax.tree_util.tree_leaves(qw)
    assert len(leaves) == 2


def test_engine_quantized_weights_close_to_fp():
    """v2 engine with quantize_weights: logits stay close to the fp engine
    (weight-only int8, per-output-channel scales), and the weight leaves are
    actually int8 on device."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight

    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256, dtype=jnp.float32,
                   attention_impl="reference")
    params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=4, max_context=64)
    mk = lambda quant: InferenceEngineV2(
        model, RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=32,
                                           kv_dtype=jnp.float32, state_manager=sm,
                                           use_pallas_kernels="never",
                                           quantize_weights=quant), params=params)
    fp = mk(False)
    q8 = mk(True)
    assert isinstance(q8.params["blocks"]["wq"], QuantizedWeight)
    assert q8.params["blocks"]["wq"].q.dtype == jnp.int8
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 128, size=17).astype(np.int32)
    lf = fp.put([1], [prompt])
    lq = q8.put([1], [prompt])
    scale = np.abs(np.asarray(lf)).max()
    assert np.abs(np.asarray(lq) - np.asarray(lf)).max() / scale < 0.05
    # decode steps stay consistent too
    nf = int(lf[0].argmax())
    assert np.isfinite(np.asarray(q8.put([1], [np.array([nf])]))).all()


def test_v1_engine_quant_config_wired():
    """DeepSpeedInferenceConfig.quant.enabled must actually quantize (round-2
    lesson: accepted-but-ignored config flags are worse than absence)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    from deepspeed_tpu.parallel import groups

    groups.reset()
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=64, dtype=jnp.float32,
                   attention_impl="reference")
    cfg = DeepSpeedInferenceConfig(dtype="float32", quant={"enabled": True})
    eng = InferenceEngine(model, cfg)
    assert isinstance(eng.params["blocks"]["wq"], QuantizedWeight)
    ids = np.random.default_rng(3).integers(0, 128, size=(1, 12)).astype(np.int32)
    logits = np.asarray(eng.forward(ids))
    assert np.isfinite(logits).all()
    groups.reset()


def test_v1_engine_quant_survives_checkpoint_load(tmp_path):
    """load_checkpoint must re-apply config.quant — a loaded checkpoint
    silently reverting the engine to fp weights is the same ignored-flag bug
    one method over."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import OrbaxCheckpointEngine

    groups.reset()
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=64, dtype=jnp.float32,
                   attention_impl="reference")
    fp_params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(1))
    OrbaxCheckpointEngine().save({"module": fp_params}, str(tmp_path / "ckpt"))

    cfg = DeepSpeedInferenceConfig(dtype="float32", quant={"enabled": True})
    eng = InferenceEngine(model, cfg)
    eng.load_checkpoint(str(tmp_path / "ckpt"), template={"module": fp_params})
    assert isinstance(eng.params["blocks"]["wq"], QuantizedWeight)
    groups.reset()


def test_quantize_covers_moe_expert_weights():
    """moe_w* expert matmuls are the dominant MoE decode weight stream —
    quantization must cover them, not just the dense w* leaves."""
    from deepspeed_tpu.inference.quantization import (QuantizedWeight,
                                                      quantize_params_for_inference)
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                            intermediate_size=128, max_seq_len=64, dtype=jnp.float32,
                            attention_impl="reference", moe_num_experts=2, moe_top_k=1)
    model = TransformerLM(cfg)
    params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
    qp = quantize_params_for_inference(params)
    for name in ("wq", "moe_wi", "moe_wo"):
        assert isinstance(qp["blocks"][name], QuantizedWeight), name
    back = qp["blocks"]["moe_wi"].astype(jnp.float32)
    ref = np.asarray(params["blocks"]["moe_wi"], np.float32)
    assert np.abs(np.asarray(back) - ref).max() <= np.abs(ref).max() / 100


def test_dynamic_splitfuse_scheduler():
    """Continuous-batching policy loop: decodes compose with prefill chunks
    under a token budget; chunked prefill produces the SAME greedy tokens as
    feeding each whole prompt at once (identical KV), and eos/max_new_tokens
    terminate and free KV."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    rng = np.random.default_rng(0)
    prompts = {1: rng.integers(0, 128, size=40, dtype=np.int32),
               2: rng.integers(0, 128, size=9, dtype=np.int32),
               3: rng.integers(0, 128, size=23, dtype=np.int32)}

    eng = _tiny_engine(max_tracked_sequences=8, max_ragged_batch_size=64,
                       max_ragged_sequence_count=4, max_context=64)
    sched = DynamicSplitFuseScheduler(eng, token_budget=16)  # forces chunked prefill
    for uid, p in prompts.items():
        sched.submit(uid, p, max_new_tokens=5)
    out = sched.run()
    assert set(out) == {1, 2, 3}
    assert all(len(v) == 5 for v in out.values())
    assert eng.state_manager.n_tracked_sequences == 0  # all flushed

    # oracle: per-sequence whole-prompt prefill + stepwise decode
    eng2 = _tiny_engine(max_tracked_sequences=8, max_ragged_batch_size=64,
                        max_ragged_sequence_count=4, max_context=64)
    for uid, p in prompts.items():
        toks = []
        tok = int(np.asarray(eng2.put([uid], [p], sample="greedy"))[0])
        toks.append(tok)
        for _ in range(4):
            tok = int(np.asarray(eng2.put([uid], [np.asarray([tok], np.int32)],
                                          sample="greedy"))[0])
            toks.append(tok)
        assert out[uid] == toks, f"uid {uid}: splitfuse {out[uid]} != sequential {toks}"
        eng2.flush(uid)

    # eos termination
    eng3 = _tiny_engine()
    s3 = DynamicSplitFuseScheduler(eng3, token_budget=32)
    s3.submit(7, prompts[2], max_new_tokens=50, eos_token_id=out[2][0])
    got = s3.run()
    assert got[7] == [out[2][0]]  # stopped at the first (eos) token


def test_splitfuse_scheduler_rejections_and_stall():
    """Un-runnable work is loud, not dropped: oversize submissions are
    rejected up front, bad budgets raise, and a stalled queue raises with
    partial results preserved."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    eng = _tiny_engine(max_tracked_sequences=2, max_ragged_batch_size=32,
                       max_ragged_sequence_count=2, max_context=64)
    with pytest.raises(ValueError, match="positive"):
        DynamicSplitFuseScheduler(eng, token_budget=0)
    sched = DynamicSplitFuseScheduler(eng, token_budget=16)
    with pytest.raises(ValueError, match="max_context"):
        sched.submit(1, np.zeros(60, np.int32), max_new_tokens=10)  # 70 > 64

    # KV-pool reservation: engine has 32 blocks of 8 = 256 slots; two
    # 64-token lifetimes fit, a third concurrent one must wait (admission
    # reserves full lifetimes), and everything still completes.
    rng = np.random.default_rng(1)
    for uid in (1, 2, 3):
        sched.submit(uid, rng.integers(0, 128, size=20, dtype=np.int32), max_new_tokens=3)
    out = sched.run()
    assert set(out) == {1, 2, 3} and all(len(v) == 3 for v in out.values())


def test_splitfuse_head_of_line_skip_ahead():
    """ADVICE r3: a pending request that can NEVER be admitted (lifetime KV
    reservation exceeds the whole pool) must not starve later pending work
    that fits. The runnable request completes; the stall raises only once
    nothing else is runnable, with completed results preserved."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    # pool: 6 blocks x 8 = 48 slots; max_context 64 > pool, so an in-range
    # request can still be pool-infeasible
    eng = _tiny_engine(max_tracked_sequences=4, max_ragged_batch_size=64,
                       max_ragged_sequence_count=4, max_context=64)
    eng.state_manager = type(eng.state_manager)(
        eng.model_config.num_layers, eng.model_config.num_kv_heads, eng.model_config.head_dim,
        max_tracked_sequences=4, num_blocks=6, block_size=8, dtype=jnp.float32)
    sched = DynamicSplitFuseScheduler(eng, token_budget=32)
    rng = np.random.default_rng(0)
    sched.submit(1, rng.integers(0, 128, size=50, dtype=np.int32), max_new_tokens=10)  # 60 tok > 48-slot pool
    sched.submit(2, rng.integers(0, 128, size=10, dtype=np.int32), max_new_tokens=4)   # fits
    with pytest.raises(RuntimeError, match="stalled"):
        sched.run()
    assert sched.results.get(2) is not None and len(sched.results[2]) == 4, \
        "admissible request behind an infeasible head was starved"


def test_splitfuse_cumulative_admission_no_partial_state():
    """ADVICE r3: with max_tracked_sequences < max_ragged_sequence_count,
    same-step admissions that individually pass must be validated
    cumulatively — the composed put() must never raise SchedulingError after
    scheduler state was mutated. Both requests complete (serially)."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    eng = _tiny_engine(max_tracked_sequences=1, max_ragged_batch_size=32,
                       max_ragged_sequence_count=2, max_context=32)
    sched = DynamicSplitFuseScheduler(eng, token_budget=32)
    rng = np.random.default_rng(1)
    sched.submit(1, rng.integers(0, 128, size=6, dtype=np.int32), max_new_tokens=3)
    sched.submit(2, rng.integers(0, 128, size=6, dtype=np.int32), max_new_tokens=3)
    out = sched.run()
    assert set(out) == {1, 2} and all(len(v) == 3 for v in out.values())


def test_engine_int8_kv_cache_close_to_fp():
    """kv_dtype='int8' (FastGen quantized-KV analog): per-(token, head)
    absmax scales ride side pools; decode logits stay close to the fp32
    engine and the KV pools genuinely hold int8."""
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256, dtype=jnp.float32,
                   attention_impl="reference")
    sm = dict(max_tracked_sequences=8, max_ragged_batch_size=64,
              max_ragged_sequence_count=4, max_context=64)
    cfg_q = RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=32, kv_dtype="int8",
                                        state_manager=DSStateManagerConfig(**sm),
                                        use_pallas_kernels="never")
    eng_q = InferenceEngineV2(model, cfg_q)
    eng_fp = _tiny_engine(model=model)
    eng_fp.params = eng_q.params

    kv = eng_q.state_manager.kv_cache
    assert kv.quantized and kv.k_pool.dtype == jnp.int8 and kv.k_scale is not None

    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 128, size=21).astype(np.int32)
    out_q = eng_q.put([0], [prompt])
    out_fp = eng_fp.put([0], [prompt])
    # int8 KV: prefill logits close, greedy tokens overwhelmingly agree
    assert np.argmax(out_q[0]) == np.argmax(out_fp[0])
    np.testing.assert_allclose(out_q, out_fp, atol=0.15, rtol=0.15)
    assert int(np.abs(np.asarray(kv.k_pool)).max()) > 0, "nothing was written to the int8 pool"

    # stepwise decode stays in agreement
    nxt = np.array([int(out_fp[0].argmax())], np.int32)
    for _ in range(3):
        out_q = eng_q.put([0], [nxt])
        out_fp = eng_fp.put([0], [nxt])
        top_q = set(np.argsort(out_q[0])[-5:])
        top_fp = set(np.argsort(out_fp[0])[-5:])
        assert len(top_q & top_fp) >= 3
        nxt = np.array([int(out_fp[0].argmax())], np.int32)

    # multi-step on-device decode path carries the scale pools too
    toks = eng_q.decode([0], [nxt], 4)
    assert toks.shape == (1, 4)


def test_paged_kernel_int8_interpret_matches_reference():
    """The Pallas paged kernel's int8 dequant-at-tile-read path (interpret
    mode) vs the gather reference on the same quantized pools."""
    from deepspeed_tpu.ops.pallas.paged_attention import (_pallas_paged,
                                                          paged_attention_reference)

    rng = np.random.default_rng(3)
    T, nq, nkv, d, bs, NB = 4, 8, 4, 128, 8, 6
    pool_len = NB * bs
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.float32)
    kf = rng.normal(size=(pool_len, nkv, d)).astype(np.float32)
    vf = rng.normal(size=(pool_len, nkv, d)).astype(np.float32)
    ks = np.maximum(np.abs(kf).max(-1) / 127.0, 1e-8)  # [pool, nkv]
    vs = np.maximum(np.abs(vf).max(-1) / 127.0, 1e-8)
    k8 = jnp.asarray(np.round(kf / ks[..., None]), jnp.int8)
    v8 = jnp.asarray(np.round(vf / vs[..., None]), jnp.int8)
    ksT = jnp.asarray(ks.T)  # [nkv, pool]
    vsT = jnp.asarray(vs.T)
    tables = jnp.asarray(rng.permutation(NB)[:2 * 3].reshape(2, 3), jnp.int32)
    seq_idx = jnp.asarray([0, 0, 1, 1], jnp.int32)
    pos = jnp.asarray([5, 11, 3, 17], jnp.int32)

    ref = paged_attention_reference(q, k8, v8, tables, seq_idx, pos, bs,
                                    k_scale=ksT, v_scale=vsT)
    out = _pallas_paged(q, k8, v8, tables, seq_idx, pos, block_size=bs, interpret=True,
                        k_scale=ksT, v_scale=vsT)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_engine_serialize_roundtrip(tmp_path):
    """engine.serialize (reference engine_v2.py:237): persists the engine's
    transformed params + metadata; a fresh engine built from the saved tree
    produces identical logits."""
    import pickle

    from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import OrbaxCheckpointEngine

    eng = _tiny_engine()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, size=9).astype(np.int32)
    ref_logits = eng.put([0], [prompt])

    path = str(tmp_path / "ser")
    eng.serialize(path)
    with open(tmp_path / "ser" / "engine_meta.pkl", "rb") as f:
        meta = pickle.load(f)
    assert meta["kv_block_size"] == eng.config.kv_block_size and not meta["quantized"]

    loaded = OrbaxCheckpointEngine().load(path)["module"]
    eng2 = _tiny_engine()
    eng2.params = jax.device_put(loaded)
    out2 = eng2.put([0], [prompt])
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref_logits), rtol=1e-6, atol=1e-6)


def test_splitfuse_scheduler_over_int8_engine():
    """Policy loop x quantized KV plane: the Dynamic SplitFuse scheduler
    drives an int8-KV engine end to end (mixed prefill/decode composition,
    multi-step decode bursts carrying the scale pools)."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256, dtype=jnp.float32,
                   attention_impl="reference")
    sm = dict(max_tracked_sequences=8, max_ragged_batch_size=64,
              max_ragged_sequence_count=4, max_context=64)
    cfg = RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=32, kv_dtype="int8",
                                      state_manager=DSStateManagerConfig(**sm),
                                      use_pallas_kernels="never")
    eng = InferenceEngineV2(model, cfg)
    sched = DynamicSplitFuseScheduler(eng, token_budget=32)
    rng = np.random.default_rng(3)
    for uid in (1, 2, 3):
        sched.submit(uid, rng.integers(0, 128, size=int(rng.integers(5, 20)), dtype=np.int32),
                     max_new_tokens=6)
    out = sched.run()
    assert set(out) == {1, 2, 3} and all(len(v) == 6 for v in out.values())
    assert all(0 <= t < 128 for v in out.values() for t in v)


def test_engine_churn_invariants():
    """Serving-plane lifecycle fuzz (reference DSStateManager + BlockedKVCache
    free-list, ragged_manager.py / blocked_allocator.py): a random interleave
    of admissions, decode bursts, and flushes must (a) never corrupt the
    block free-list (free+held == total at every step), (b) produce the same
    greedy tokens as a fresh engine fed the same prompt (eviction/readmission
    cannot leak state between uids), and (c) return the pool to pristine
    after a full flush."""
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    rng = np.random.default_rng(0)
    cfg = TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                            max_seq_len=256, intermediate_size=128, dtype=jnp.float32,
                            attention_impl="reference")
    model = TransformerLM(cfg)
    icfg = RaggedInferenceEngineConfig()
    icfg.kv_block_size = 16
    icfg.num_kv_blocks = 40
    icfg.state_manager.max_tracked_sequences = 4
    icfg.state_manager.max_ragged_sequence_count = 4
    icfg.state_manager.max_ragged_batch_size = 128  # fits the 100-token prompts
    icfg.state_manager.max_context = 160
    icfg.use_pallas_kernels = "never"  # CPU-deterministic tokens for the replay check
    engine = InferenceEngineV2(model, icfg)
    total = engine.state_manager.free_blocks

    prompts = {}
    live = {}          # uid -> generated tokens so far
    next_uid = 0
    min_free_seen = total
    for step in range(80):
        # decode-heavy, flush-light schedule: sequences grow across multiple
        # 16-token blocks and the pool reaches real pressure (asserted below)
        op = rng.choice(["put", "decode", "flush"], p=[0.35, 0.5, 0.15])
        grown = [u for u in live if len(prompts[u]) + len(live[u]) > 140]
        for u in grown:  # retire near-max_context sequences instead of overflowing
            engine.flush(u)
            del live[u]
        if op == "put" and len(live) < 4:
            uid = next_uid; next_uid += 1
            prompts[uid] = rng.integers(0, 256, size=int(rng.integers(20, 100)), dtype=np.int32)
            tok = engine.put([uid], [prompts[uid]], sample="greedy")
            live[uid] = [int(tok[0])]
        elif op == "decode" and live:
            uids = sorted(live)
            last = [np.asarray([live[u][-1]], np.int32) for u in uids]
            out = np.asarray(engine.decode(uids, last, 8))
            for u, row in zip(uids, out):
                live[u].extend(int(t) for t in row)
        elif op == "flush" and live:
            uid = sorted(live)[int(rng.integers(0, len(live)))]
            engine.flush(uid)
            del live[uid]
        held = sum(engine.state_manager.query(u).cur_allocated_blocks for u in live)
        assert engine.state_manager.free_blocks + held == total, \
            f"block leak at step {step}: free={engine.state_manager.free_blocks} held={held}"
        min_free_seen = min(min_free_seen, engine.state_manager.free_blocks)
    # the schedule must have actually pressured the pool, or (a) proves little
    assert min_free_seen <= total // 2, \
        f"fuzz schedule too gentle: pool never dropped below {min_free_seen}/{total} free"

    # (b) per-uid isolation — UNCONDITIONAL: pick any sequence (admit one if
    # none survived), grow it to 9+ tokens amid the surviving churn, then
    # replay it alone on a fresh engine — tokens must match exactly
    if not live:
        uid = next_uid
        prompts[uid] = rng.integers(0, 256, size=37, dtype=np.int32)
        tok = engine.put([uid], [prompts[uid]], sample="greedy")
        live[uid] = [int(tok[0])]
    uid = sorted(live)[0]
    while len(live[uid]) < 9:
        out = np.asarray(engine.decode([uid], [np.asarray([live[uid][-1]], np.int32)], 8))
        live[uid].extend(int(t) for t in out[0])
    fresh = InferenceEngineV2(model, icfg)
    tok = fresh.put([0], [prompts[uid]], sample="greedy")
    replay = [int(tok[0])]
    while len(replay) < len(live[uid]):
        n = min(8, len(live[uid]) - len(replay))
        out = np.asarray(fresh.decode([0], [np.asarray([replay[-1]], np.int32)], n))
        replay.extend(int(t) for t in out[0])
    assert replay[:len(live[uid])] == live[uid], f"uid {uid} diverged from isolated replay"

    # (c) pristine pool after full flush
    for uid in sorted(live):
        engine.flush(uid)
    assert engine.state_manager.free_blocks == total
    assert engine.state_manager.n_tracked_sequences == 0


def test_engine_churn_invariants_prefix_cache():
    """Serving-plane lifecycle fuzz EXTENDED to refcounted/COW shared blocks
    (ISSUE 3 satellite): with ``ragged.prefix_cache`` on and prompts drawn
    from a shared-prefix pool, arbitrary submit/decode/flush churn must keep
    (a) every block's refcount equal to its live holder count (sequences
    whose table carries it + the radix tree), (b) the free list consistent
    (free + distinct-held == total at every step), and (c) after flushing
    all sequences AND the eviction flush (``prefix_cache.clear()``), the
    pool returns to pristine."""
    from deepspeed_tpu.inference.v2 import PrefixCacheConfig
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    rng = np.random.default_rng(1)
    cfg = TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                            max_seq_len=256, intermediate_size=128, dtype=jnp.float32,
                            attention_impl="reference")
    model = TransformerLM(cfg)
    icfg = RaggedInferenceEngineConfig()
    icfg.kv_block_size = 16
    icfg.num_kv_blocks = 40
    icfg.state_manager.max_tracked_sequences = 4
    icfg.state_manager.max_ragged_sequence_count = 4
    icfg.state_manager.max_ragged_batch_size = 128
    icfg.state_manager.max_context = 160
    icfg.use_pallas_kernels = "never"
    icfg.prefix_cache = PrefixCacheConfig(enabled=True)
    engine = InferenceEngineV2(model, icfg)
    alloc = engine.state_manager.kv_cache._allocator
    total = engine.state_manager.free_blocks
    pc = engine.prefix_cache
    # shared-prefix pool: radix hits + COW tails actually happen under churn
    pool = [rng.integers(0, 256, size=48, dtype=np.int32) for _ in range(3)]

    live = {}
    next_uid = 0
    for step in range(60):
        op = rng.choice(["put", "decode", "flush"], p=[0.4, 0.4, 0.2])
        grown = [u for u in live if engine.query(u).seen_tokens > 140]
        for u in grown:
            engine.flush(u)
            del live[u]
        if op == "put" and len(live) < 4:
            uid = next_uid; next_uid += 1
            prefix = pool[int(rng.integers(0, 3))]
            cut = int(rng.integers(8, 49))  # mid-block cuts exercise COW
            suffix = rng.integers(0, 256, size=int(rng.integers(4, 30)), dtype=np.int32)
            prompt = np.concatenate([prefix[:cut], suffix])
            tok = engine.put([uid], [prompt], sample="greedy")
            live[uid] = [int(tok[0])]
        elif op == "decode" and live:
            uids = sorted(live)
            last = [np.asarray([live[u][-1]], np.int32) for u in uids]
            out = np.asarray(engine.decode(uids, last, 8))
            for u, row in zip(uids, out):
                live[u].extend(int(t) for t in row)
        elif op == "flush" and live:
            uid = sorted(live)[int(rng.integers(0, len(live)))]
            engine.flush(uid)
            del live[uid]
        # (a) exact holder accounting: refcount == #sequences carrying the
        # block + 1 if the radix tree holds it — for EVERY block id
        holders = {}
        for u in live:
            for b in engine.query(u).kv_blocks:
                holders[b] = holders.get(b, 0) + 1
        for b in pc.cached_block_ids():
            holders[b] = holders.get(b, 0) + 1
        for b in range(total):
            assert alloc.refcount(b) == holders.get(b, 0), \
                (f"step {step}: block {b} refcount {alloc.refcount(b)} != "
                 f"{holders.get(b, 0)} live holders")
        # (b) free-list consistency against DISTINCT held blocks
        assert engine.state_manager.free_blocks + len(holders) == total, \
            f"step {step}: free={engine.state_manager.free_blocks} held={len(holders)}"
    assert pc.stats["hits"] > 0 and pc.stats["cow_copies"] > 0, \
        "fuzz schedule never exercised sharing/COW — weak run"
    assert pc.stats["evictions"] > 0, "pool never came under eviction pressure"

    # (c) pristine after full flush + eviction flush
    for uid in sorted(live):
        engine.flush(uid)
    pc.clear()
    assert engine.state_manager.free_blocks == total
    assert engine.state_manager.n_tracked_sequences == 0
    assert all(alloc.refcount(b) == 0 for b in range(total))


def test_v1_engine_int4_weights_close_to_fp():
    """INT4 weight-only path (reference deepspeed/inference/quantization
    utils.py:66 — asymmetric groups, uint8->uint4 packing): quant.num_bits=4
    packs two nibbles per byte along the contraction axis, and the engine's
    logits stay close to fp (looser than int8: 15 levels/channel)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.quantization import QuantizedWeight4
    from deepspeed_tpu.parallel import groups

    groups.reset()
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=64, dtype=jnp.float32,
                   attention_impl="reference")
    fp = InferenceEngine(model, DeepSpeedInferenceConfig(dtype="float32"))
    q4 = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="float32", quant={"enabled": True, "num_bits": 4}), params=fp.params)
    w = q4.params["blocks"]["wq"]
    assert isinstance(w, QuantizedWeight4)
    assert w.q.dtype == jnp.uint8
    # HALF the int8 bytes: packed contraction dim
    assert w.q.shape[-2] * 2 == fp.params["blocks"]["wq"].shape[-2]
    ids = np.random.default_rng(3).integers(0, 128, size=(1, 12)).astype(np.int32)
    lf = np.asarray(fp.forward(ids))
    lq = np.asarray(q4.forward(ids))
    scale = np.abs(lf).max()
    assert np.isfinite(lq).all()
    assert np.abs(lq - lf).max() / scale < 0.25, np.abs(lq - lf).max() / scale
    # int8 must stay tighter than int4 on the same weights
    q8 = InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype="float32", quant={"enabled": True, "num_bits": 8}), params=fp.params)
    l8 = np.asarray(q8.forward(ids))
    assert np.abs(l8 - lf).max() <= np.abs(lq - lf).max()
    groups.reset()


def test_v2_engine_int4_weights_close_to_fp():
    """v2 serving with quantize_weights=4 routes to int4_blockwise_linear:
    the weight stream quarters (packed nibbles) and prefill logits stay
    close to fp; int8 stays tighter than int4 on the same model."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight, QuantizedWeight4

    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256, dtype=jnp.float32,
                   attention_impl="reference")
    params = jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0))
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=4, max_context=64)
    mk = lambda quant: InferenceEngineV2(
        model, RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=32,
                                           kv_dtype=jnp.float32, state_manager=sm,
                                           use_pallas_kernels="never",
                                           quantize_weights=quant), params=params)
    fp, q8, q4 = mk(False), mk(True), mk(4)
    assert isinstance(q4.params["blocks"]["wq"], QuantizedWeight4)
    assert isinstance(q8.params["blocks"]["wq"], QuantizedWeight)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 128, size=17).astype(np.int32)
    lf = np.asarray(fp.put([1], [prompt]))
    l8 = np.asarray(q8.put([1], [prompt]))
    l4 = np.asarray(q4.put([1], [prompt]))
    scale = np.abs(lf).max()
    assert np.isfinite(l4).all()
    # random N(0,1) weights are the worst case for 15-level asymmetric quant
    # (real pretrained weights quantize far tighter); the ORDERING is the
    # meaningful invariant: int8 must be tighter than int4 on the same model
    assert np.abs(l4 - lf).max() / scale < 0.5
    assert np.abs(l8 - lf).max() <= np.abs(l4 - lf).max()


def test_the_wrapper_pads_to_the_buckets_it_is_given():
    """``DSStateManagerConfig.seq_buckets`` / ``token_buckets``: a replica
    that warms two buckets of each and no more; the last is the limit, so
    that a full batch has a bucket."""
    from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatchWrapper, next_bucket

    assert DSStateManagerConfig().seq_buckets is None and DSStateManagerConfig().token_buckets is None
    given = RaggedBatchWrapper(512, 128, seq_buckets=(32, 128), token_buckets=(128, 512))
    assert (given.seq_buckets, given.token_buckets) == ([32, 128], [128, 512])
    assert [next_bucket(n, given.seq_buckets) for n in (1, 32, 33, 128)] == [32, 32, 128, 128]
    assert RaggedBatchWrapper(512, 128).seq_buckets == [8, 16, 32, 64, 128]
    with pytest.raises(ValueError, match="must end at the limit 128"):
        RaggedBatchWrapper(512, 128, seq_buckets=(32, 64))


def test_rows_cut_on_the_host_are_the_rows_cut_on_the_device():
    """``cut_rows_on_host``: the same tokens for ``put`` and ``decode`` of 3
    rows in a bucket of 8, from an engine that pads to the buckets its
    configuration names; logits are cut on the device either way."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, size=n).astype(np.int32) for n in (5, 9, 3)]
    got = []
    for on_host in (False, True):
        eng = _tiny_engine(max_ragged_sequence_count=8, seq_buckets=(8, ), token_buckets=(32, 64))
        eng.config.cut_rows_on_host = on_host
        assert (eng.batch.seq_buckets, eng.batch.token_buckets) == ([8], [32, 64])
        first = eng.put([1, 2, 3], prompts, sample="greedy")
        toks = eng.decode([1, 2, 3], [np.asarray([t], np.int32) for t in first], 4)
        logits = eng.put([1, 2, 3], [np.asarray([t], np.int32) for t in toks[:, -1]])
        assert first.shape == (3, ) and toks.shape == (3, 4) and logits.shape == (3, 128)
        got.append((np.asarray(first), np.asarray(toks), np.asarray(logits)))
    for a, b in zip(*got):
        np.testing.assert_array_equal(a, b)
