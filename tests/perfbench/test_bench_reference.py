"""The benchmark's plain float32 reference against the system's own forward
pass and loss on seeded random weights, at a small size on the CPU, for the
two families the configurations use. The reference shares no code with the
system, so agreement to float32 rounding says both compute the published
block; GPT-NeoX's exact gelu against the system's tanh form is the one known
difference (at most 5e-4 per activation)."""

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark.lib import reference
from deepspeed_tpu.models import gpt_neox_config, mistral_config
from deepspeed_tpu.models.transformer import forward, init_params, loss_fn

CASES = {
    "gpt_neox": (
        dict(hidden_size=64, num_attention_heads=4, layer_norm_eps=1e-5, rotary_emb_base=10000, rotary_pct=0.25,
             hidden_act="gelu", use_parallel_residual=True),
        lambda: gpt_neox_config("tiny", hidden_size=64, num_layers=2, num_heads=4, rotary_dim=4,
                                intermediate_size=256, vocab_size=128, dtype=jnp.float32,
                                attention_impl="reference"),
        2e-3),
    "mistral": (
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=1e4,
             sliding_window=8, hidden_act="silu"),
        lambda: mistral_config("tiny", hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                               intermediate_size=128, vocab_size=128, sliding_window=8, dtype=jnp.float32,
                               attention_impl="reference"),
        2e-5),
}


@pytest.fixture(params=sorted(CASES))
def case(request):
    published, make_cfg, tol = CASES[request.param]
    cfg = make_cfg()
    params = init_params(cfg, jax.random.PRNGKey(1))
    # biases and norm offsets start at zero: move them, so that a dropped one shows
    params = jax.tree_util.tree_map(
        lambda a: a + 0.01 * jax.random.normal(jax.random.PRNGKey(2), a.shape), params)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 128)
    return reference.hyper_from_published(published), cfg, params, ids, tol


def test_reference_logits_agree_with_the_systems_forward(case):
    hyper, cfg, params, ids, tol = case
    with jax.default_matmul_precision("highest"):
        want = forward(cfg, params, ids)[:, [3, 15]]
    got = reference.forward_logits(hyper, params, ids, [3, 15])
    assert got.shape == (2, 2, 128)
    assert float(jnp.abs(got - want).max()) <= tol * float(jnp.abs(want).max())


def test_reference_loss_and_gradient_norm_agree_with_the_systems(case):
    hyper, cfg, params, ids, tol = case
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, {"input_ids": ids}))(params)
    ref_loss, ref_norm = reference.loss_and_grad_norm(hyper, params, ids)
    assert ref_loss == pytest.approx(float(loss), abs=tol)
    assert ref_norm == pytest.approx(float(optax.global_norm(grads)), rel=tol)


def test_sliding_window_changes_the_reference_beyond_the_window():
    hyper, make_cfg, _ = CASES["mistral"]
    cfg = make_cfg()
    params = init_params(cfg, jax.random.PRNGKey(1))
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0, 128)
    windowed = reference.forward_logits(reference.hyper_from_published(hyper), params, ids, [5, 15])
    full = reference.forward_logits(reference.hyper_from_published({**hyper, "sliding_window": None}),
                                    params, ids, [5, 15])
    assert float(jnp.abs(windowed[0, 0] - full[0, 0]).max()) < 1e-5   # position 5 sees all 6 keys either way
    assert float(jnp.abs(windowed[0, 1] - full[0, 1]).max()) > 1e-4   # position 15 loses keys 0-7
