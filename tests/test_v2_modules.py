"""Pluggable inference module layer (reference
``tests/unit/inference/v2/modules/``): registry mechanics, heuristics
selection, and logits parity when a config flip swaps the implementation
serving a slot."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2, ModulesConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.modules import (ConfigBundle, DSLinearConfig, DSMoEConfig,
                                                DSSelfAttentionBase, DSSelfAttentionConfig,
                                                DSSelfAttentionRegistry, DSLinearRegistry,
                                                DSMoERegistry, build_modules)
from deepspeed_tpu.models import llama2
from deepspeed_tpu.models.transformer import forward


def _engine(modules: ModulesConfig = None, **cfg_over):
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=8, num_kv_heads=4,
                   intermediate_size=128, vocab_size=128, max_seq_len=256, dtype=jnp.float32,
                   attention_impl="reference")
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=4, max_context=64)
    cfg = RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=32, kv_dtype=jnp.float32,
                                      state_manager=sm, use_pallas_kernels="never", **cfg_over)
    if modules is not None:
        cfg.modules = modules
    return InferenceEngineV2(model, cfg)


# ---------------------------------------------------------------- registry
def test_registry_lookup_and_errors():
    assert "dense_blocked_attention" in DSSelfAttentionRegistry.registry
    assert "paged_pallas_attention" in DSSelfAttentionRegistry.registry
    assert "blas_fp_linear" in DSLinearRegistry.registry
    assert "int8_blockwise_linear" in DSLinearRegistry.registry

    with pytest.raises(KeyError, match="Unknown DSModule"):
        DSSelfAttentionRegistry.instantiate_config(
            ConfigBundle(name="nope", config=DSSelfAttentionConfig()))
    # supports_config gate: nq not divisible by nkv is rejected at build
    bad = DSSelfAttentionConfig(num_heads=6, num_kv_heads=4, head_dim=8)
    with pytest.raises(ValueError, match="not supported"):
        DSSelfAttentionRegistry.instantiate_config(
            ConfigBundle(name="dense_blocked_attention", config=bad))


def test_registry_rejects_foreign_class():
    with pytest.raises(TypeError):
        DSLinearRegistry.register_module(type("NotALinear", (DSSelfAttentionBase,), {}))


def test_third_party_registration_selectable_by_config():
    """A user-registered implementation is reachable from the engine config
    string alone — the FastGen extensibility contract."""
    calls = []

    @DSSelfAttentionRegistry.register_module
    class TaggedDense(DSSelfAttentionRegistry.associated_class()):

        @staticmethod
        def name():
            return "tagged_dense_attention"

        @staticmethod
        def supports_config(config):
            return True

        def __call__(self, q, k_flat, v_flat, tables_l, seq_idx, pos):
            calls.append("hit")
            from deepspeed_tpu.ops.pallas.paged_attention import paged_attention_reference

            return paged_attention_reference(q, k_flat, v_flat, tables_l, seq_idx, pos,
                                             self.config.block_size)

    try:
        eng = _engine(modules=ModulesConfig(attention="tagged_dense_attention"))
        prompt = np.random.default_rng(0).integers(0, 128, size=9).astype(np.int32)
        logits = eng.put([0], [prompt])
        assert calls, "custom implementation was never traced"
        dense = forward(eng.model_config, eng.params, prompt[None])[0, -1]
        np.testing.assert_allclose(logits[0], np.asarray(dense), atol=3e-4, rtol=3e-4)
    finally:
        DSSelfAttentionRegistry.registry.pop("tagged_dense_attention", None)


# ---------------------------------------------------------------- heuristics
def test_heuristics_auto_selection():
    model_cfg = _engine().model_config
    ec = RaggedInferenceEngineConfig()
    mods = build_modules(model_cfg, ec, use_pallas=False)
    assert mods["attention"].name() == "dense_blocked_attention"
    assert mods["linear"].name() == "blas_fp_linear"
    mods = build_modules(model_cfg, ec, use_pallas=True)
    assert mods["attention"].name() == "paged_pallas_attention"
    ec_q = RaggedInferenceEngineConfig(quantize_weights=True)
    assert build_modules(model_cfg, ec_q, use_pallas=False)["linear"].name() == \
        "int8_blockwise_linear"


# ---------------------------------------------------------------- config flips
def test_attention_impl_flip_logits_parity():
    """dense gather oracle vs the Pallas paged kernel (interpreter) — the
    same compiled-bucket surface, two genuinely different attention
    implementations, same logits."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 128, size=13).astype(np.int32)
    eng_a = _engine(modules=ModulesConfig(attention="dense_blocked_attention"))
    eng_b = _engine(modules=ModulesConfig(attention={
        "name": "paged_pallas_attention", "implementation_config": {"interpret": True}}))
    out_a = eng_a.put([0], [prompt])
    out_b = eng_b.put([0], [prompt])
    np.testing.assert_allclose(out_a, out_b, atol=2e-3, rtol=2e-3)
    # and a decode step on each
    nxt = np.array([int(out_a[0].argmax())], np.int32)
    np.testing.assert_allclose(eng_a.put([0], [nxt]), eng_b.put([0], [nxt]),
                               atol=2e-3, rtol=2e-3)


def test_linear_impl_flip_int8_close_to_fp():
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 128, size=11).astype(np.int32)
    eng_fp = _engine(modules=ModulesConfig(linear="blas_fp_linear"))
    eng_q = _engine(modules=ModulesConfig(linear="int8_blockwise_linear"))
    from deepspeed_tpu.inference.quantization import QuantizedWeight

    leaves = jax.tree_util.tree_leaves(
        eng_q.params, is_leaf=lambda x: isinstance(x, QuantizedWeight))
    assert any(isinstance(x, QuantizedWeight) for x in leaves), \
        "int8 linear transform_params did not quantize the weight stream"
    out_fp = eng_fp.put([0], [prompt])
    out_q = eng_q.put([0], [prompt])
    top_fp = np.argsort(out_fp[0])[-5:]
    top_q = np.argsort(out_q[0])[-5:]
    assert len(set(top_fp) & set(top_q)) >= 3
    np.testing.assert_allclose(out_fp, out_q, atol=0.5, rtol=0.5)


def test_quantize_weights_flag_routes_to_int8_linear():
    eng = _engine(quantize_weights=True)
    assert eng._modules["linear"].name() == "int8_blockwise_linear"


# ---------------------------------------------------------------- moe module
def test_moe_module_matches_per_token_loop():
    T, H, F, E, K = 6, 8, 16, 4, 2
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    gate_w = jnp.asarray(rng.normal(size=(H, E)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(E, H, F)) * 0.1, jnp.float32)
    gt = jnp.asarray(rng.normal(size=(E, H, F)) * 0.1, jnp.float32)
    down = jnp.asarray(rng.normal(size=(E, F, H)) * 0.1, jnp.float32)

    moe = DSMoERegistry.instantiate_config(ConfigBundle(
        name="grouped_gemm_moe",
        config=DSMoEConfig(n_experts=E, top_k=K, activation="swiglu", dtype=jnp.float32)))
    out = np.asarray(moe(x, gate_w, up, gt, down))

    logits = np.asarray(x @ gate_w)
    for t in range(T):
        idx = np.argsort(logits[t])[-K:]
        w = np.exp(logits[t][idx] - logits[t][idx].max())
        w = w / w.sum()
        ref = np.zeros(H, np.float32)
        for j, e in enumerate(idx):
            a = np.asarray(jax.nn.silu(x[t] @ gt[e])) * np.asarray(x[t] @ up[e])
            ref += w[j] * np.asarray(a @ down[e])
        np.testing.assert_allclose(out[t], ref, atol=1e-4, rtol=1e-4)


def test_moe_supports_config_gate():
    with pytest.raises(ValueError, match="not supported"):
        DSMoERegistry.instantiate_config(ConfigBundle(
            name="grouped_gemm_moe", config=DSMoEConfig(n_experts=2, top_k=3)))
