"""Grouped-GEMM MoE (Pallas ragged matmul, interpret mode on CPU) vs the
one-hot einsum dispatch — reference counterpart: cutlass_ops moe_gemm
(VERDICT r4 missing #5, SURVEY §2.3 'megablocks-style ragged matmul')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.grouped import block_align_dispatch, grouped_moe_ffn
from deepspeed_tpu.ops.pallas.grouped_matmul import gmm, grouped_matmul, tgmm


def _ref_gmm(lhs, rhs, block_expert, bt):
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    for i, e in enumerate(np.asarray(block_expert)):
        out[i * bt:(i + 1) * bt] = np.asarray(lhs[i * bt:(i + 1) * bt], np.float32) @ \
            np.asarray(rhs[e], np.float32)
    return out


def test_gmm_matches_per_block_reference():
    rng = np.random.default_rng(0)
    T, K, N, E, bt = 64, 32, 48, 3, 8
    lhs = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    be = jnp.asarray(rng.integers(0, E, size=T // bt).astype(np.int32))
    be = jnp.sort(be)  # kernel contract: non-decreasing
    out = gmm(lhs, rhs, be, block_t=bt, block_k=16, block_n=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), _ref_gmm(lhs, rhs, be, bt),
                               rtol=1e-5, atol=1e-5)


def test_tgmm_matches_per_expert_reference():
    rng = np.random.default_rng(1)
    T, K, N, E, bt = 64, 32, 16, 4, 8
    lhs = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, N)), jnp.float32)
    # every expert owns >=1 block (the kernel contract)
    be = jnp.asarray(np.sort(np.concatenate([np.arange(E),
                                             rng.integers(0, E, size=T // bt - E)])
                             ).astype(np.int32))
    out = tgmm(lhs, dy, be, E, block_t=bt, block_k=16, block_n=16, interpret=True)
    ref = np.zeros((E, K, N), np.float32)
    for i, e in enumerate(np.asarray(be)):
        ref[e] += np.asarray(lhs[i * bt:(i + 1) * bt]).T @ np.asarray(dy[i * bt:(i + 1) * bt])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_grouped_matmul_gradients():
    """custom VJP: dx and dw must match autodiff through the dense oracle."""
    rng = np.random.default_rng(2)
    T, K, N, E, bt = 32, 16, 24, 2, 8
    lhs = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    be = jnp.asarray(np.sort(np.concatenate([np.arange(E), [0, 1]])).astype(np.int32))

    def loss_grouped(lhs, rhs):
        return jnp.sum(grouped_matmul(lhs, rhs, be, block_t=bt, block_k=8, block_n=8,
                                      interpret=True) ** 2)

    def loss_dense(lhs, rhs):
        w_rows = rhs[be]  # [nt, K, N]
        x_blocks = lhs.reshape(-1, bt, K)
        out = jnp.einsum("tbk,tkn->tbn", x_blocks, w_rows).reshape(T, N)
        return jnp.sum(out ** 2)

    gx, gw = jax.grad(loss_grouped, argnums=(0, 1))(lhs, rhs)
    rx, rw = jax.grad(loss_dense, argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=1e-4, atol=1e-4)


def test_block_align_dispatch_structure():
    w_se = jnp.asarray([[0.7, 0.0, 0.3],
                        [0.0, 1.0, 0.0],
                        [0.2, 0.8, 0.0],
                        [0.0, 0.0, 0.0]], jnp.float32)  # last token dropped
    top_w, top_idx = jax.lax.top_k(w_se, 2)
    tok, w_slot, dest, block_expert, T_pad, n_live, sizes = block_align_dispatch(top_idx, top_w, 3, 4)
    assert int(n_live) == 3 and np.asarray(sizes).tolist() == [4, 3, 1]
    assert T_pad % 4 == 0 and block_expert.shape == (T_pad // 4, )
    # non-decreasing block table covering every expert at least once
    beh = np.asarray(block_expert)
    assert (np.diff(beh) >= 0).all()
    assert set(range(3)) <= set(beh.tolist())
    # destinations are unique and live inside SOME expert's block-aligned
    # group whose block table row matches that expert
    assert len(set(np.asarray(dest).tolist())) == dest.shape[0]
    # recompute the routing the dispatcher used (top-2 over w_se) and check
    # each slot's dest row falls in a block owned by its routed expert
    wv, idx = jax.lax.top_k(w_se, 2)
    routed = np.asarray(idx).reshape(-1)  # slots stay in token order
    assert np.asarray(tok).tolist() == [0, 0, 1, 1, 2, 2, 3, 3]
    for slot_expert, d in zip(routed, np.asarray(dest)):
        assert beh[d // 4] == slot_expert, (slot_expert, int(d))


@pytest.mark.parametrize("top_k,mlp", [(1, "gelu"), (2, "gelu"), (2, "swiglu")])
def test_grouped_ffn_matches_einsum_dispatch(top_k, mlp):
    """End-to-end parity: same gate outputs → grouped path == the [S,E,C]
    one-hot dispatch/combine einsum, including dropped tokens."""
    from deepspeed_tpu.moe.sharded_moe import top1gating, top2gating

    rng = np.random.default_rng(3)
    S, M, F, E = 32, 16, 24, 4
    x = jnp.asarray(rng.normal(size=(S, M)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(S, E)), jnp.float32)
    gate_fn = top1gating if top_k == 1 else top2gating
    _, combine, dispatch, capacity = gate_fn(logits, 1.0, 4)
    wi = jnp.asarray(rng.normal(size=(E, M, F)), jnp.float32) / np.sqrt(M)
    wo = jnp.asarray(rng.normal(size=(E, F, M)), jnp.float32) / np.sqrt(F)
    wg = jnp.asarray(rng.normal(size=(E, M, F)), jnp.float32) / np.sqrt(M) \
        if mlp == "swiglu" else None

    def act(up, gate):
        return jax.nn.silu(gate) * up if gate is not None else jax.nn.gelu(up)

    # einsum path (sharded_moe formulation)
    dispatched = jnp.einsum("sec,sm->ecm", dispatch, x)
    up = jnp.einsum("ecm,emf->ecf", dispatched, wi)
    g = jnp.einsum("ecm,emf->ecf", dispatched, wg) if wg is not None else None
    mid = act(up, g)
    eo = jnp.einsum("ecf,efm->ecm", mid, wo)
    y_ref = jnp.einsum("sec,ecm->sm", combine, eo)

    w_se = combine.sum(axis=2)  # [S, E] per-token kept weights
    top_w, top_idx = jax.lax.top_k(w_se, top_k)
    y = grouped_moe_ffn(x, top_idx, top_w, wi, wo, wg=wg, activation=act, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)


def test_grouped_ffn_is_differentiable():
    rng = np.random.default_rng(5)
    S, M, F, E = 16, 8, 16, 2
    x = jnp.asarray(rng.normal(size=(S, M)), jnp.float32)
    w_se = jax.nn.softmax(jnp.asarray(rng.normal(size=(S, E)), jnp.float32))
    wi = jnp.asarray(rng.normal(size=(E, M, F)), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(E, F, M)), jnp.float32)

    def loss(wi, wo, w_se):
        top_w, top_idx = jax.lax.top_k(w_se, 1)
        return jnp.sum(grouped_moe_ffn(x, top_idx, top_w, wi, wo, interpret=True) ** 2)

    gwi, gwo, gse = jax.grad(loss, argnums=(0, 1, 2))(wi, wo, w_se)
    assert np.isfinite(np.asarray(gwi)).all() and np.abs(np.asarray(gwi)).max() > 0
    assert np.isfinite(np.asarray(gwo)).all() and np.abs(np.asarray(gwo)).max() > 0
    assert np.isfinite(np.asarray(gse)).all()


def _dense_expert_loop(x, top_idx, top_w, wi, wg, wo):
    """Every token through each of its experts, one token and one expert at
    a time: what the grouped path must equal."""
    x, wi, wg, wo = (np.asarray(a, np.float64) for a in (x, wi, wg, wo))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, w in zip(np.asarray(top_idx)[t], np.asarray(top_w, np.float64)[t]):
            gate = x[t] @ wg[e]
            out[t] += w * ((gate / (1.0 + np.exp(-gate)) * (x[t] @ wi[e])) @ wo[e])
    return out


@pytest.mark.parametrize("tokens,block_rows", [(4, 8), (128, 64), (2048, 128)])
def test_v2_grouped_gemm_moe_matches_dense_expert_loop(tokens, block_rows):
    """The serving MoE module (the one ``build_modules`` fills the ``moe`` slot
    with) against a dense per-expert loop at 8, 256 and 4,096 routed slots,
    with an expert that receives no slot and one that receives most, padding
    tokens that route nowhere, and its routing counts against numpy's."""
    from deepspeed_tpu.inference.v2.modules import ConfigBundle, DSMoEConfig, DSMoERegistry
    from deepspeed_tpu.moe.grouped import padded_rows, pick_block_rows, route_topk

    H, F, E, K = 32, 48, 8, 2
    rng = np.random.default_rng(tokens)
    x = jnp.asarray(rng.normal(size=(tokens, H)), jnp.float32)
    gate_w = rng.normal(size=(H, E)).astype(np.float32)
    # a router that sends every token to expert 0 first and none to the last:
    # tokens are |noise| + a large first coordinate, which expert 0 reads with
    # a large weight and the last expert with a large negative one
    x = x.at[:, 0].set(6.0 + jnp.abs(x[:, 0]))
    gate_w[0, 0], gate_w[0, E - 1] = 8.0, -8.0
    gate_w = jnp.asarray(gate_w)
    up = jnp.asarray(rng.normal(size=(E, H, F)) * 0.2, jnp.float32)
    gt = jnp.asarray(rng.normal(size=(E, H, F)) * 0.2, jnp.float32)
    down = jnp.asarray(rng.normal(size=(E, F, H)) * 0.2, jnp.float32)
    valid = jnp.asarray(np.arange(tokens) < tokens - tokens // 4)  # the bucket's tail is padding

    moe = DSMoERegistry.instantiate_config(ConfigBundle(
        name="grouped_gemm_moe",
        config=DSMoEConfig(n_experts=E, top_k=K, activation="swiglu", dtype=jnp.float32)))
    assert pick_block_rows(tokens * K, E) == block_rows
    assert moe.padded_rows(tokens) == padded_rows(tokens * K, E, block_rows, False)
    y, stats = moe(x, gate_w, up, gt, down, valid=valid, with_stats=True)

    top_idx, top_w = route_topk(x, gate_w, K)
    live = np.asarray(valid)
    counts = np.bincount(np.asarray(top_idx)[live].reshape(-1), minlength=E)
    assert counts[0] >= 0.95 * live.sum() and counts[E - 1] == 0, counts
    ref = _dense_expert_loop(x, top_idx, top_w, up, gt, down)
    np.testing.assert_allclose(np.asarray(y)[live], ref[live], rtol=2e-4, atol=2e-4)
    assert not np.asarray(y)[~live].any(), "a padding token has no expert output"
    assert np.asarray(stats).tolist() == [int((counts > 0).sum()), int(counts.max()), int(counts.sum())]


def test_transformer_dropless_topk_forward_and_grad():
    """``moe_dropless``: top-k of the softmax over all experts through the
    grouped matmul, against the dense per-expert loop on the same routing,
    and differentiable through the custom-VJP kernels (the weight gradient of
    an expert no token chose is zero, not unwritten memory)."""
    from deepspeed_tpu.models import TransformerConfig
    from deepspeed_tpu.models.transformer import _moe_mlp, init_params
    from deepspeed_tpu.moe.grouped import route_topk

    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
                            intermediate_size=64, moe_intermediate_size=24, max_seq_len=32,
                            dtype=jnp.float32, attention_impl="reference", moe_num_experts=8,
                            moe_top_k=3, moe_dropless=True)
    blocks = init_params(cfg, jax.random.PRNGKey(1))["blocks"]
    layer = jax.tree_util.tree_map(lambda a: a[0], blocks)
    assert layer["moe_wi"].shape == (8, 32, 24)
    # no token ever routes to the last expert
    layer["gate_wg"] = layer["gate_wg"].at[:, 7].set(0.0).at[0, 7].set(-8.0)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 16, 32)), jnp.float32)
    h = h.at[..., 0].set(3.0 + jnp.abs(h[..., 0]))

    y, aux = _moe_mlp(cfg, layer, h)
    top_idx, top_w = route_topk(h.reshape(32, 32), layer["gate_wg"], 3)
    assert 7 not in np.asarray(top_idx)
    np.testing.assert_allclose(np.asarray(top_w).sum(-1), 1.0, rtol=1e-6)
    ref = _dense_expert_loop(h.reshape(32, 32), top_idx, top_w, layer["moe_wi"], layer["moe_wg"],
                             layer["moe_wo"])
    np.testing.assert_allclose(np.asarray(y).reshape(32, 32), ref, rtol=2e-4, atol=2e-4)
    assert float(aux) == 0.0

    grads = jax.grad(lambda lyr: jnp.sum(_moe_mlp(cfg, lyr, h)[0] ** 2))(layer)
    g = np.asarray(grads["moe_wi"])
    assert np.isfinite(g).all() and np.abs(g[:7]).max() > 0 and not g[7].any()
    assert np.isfinite(np.asarray(grads["gate_wg"])).all()


@pytest.mark.parametrize("top_k", [3, 8])
def test_top_k_above_two_is_dropless_or_refused(top_k):
    """``moe_top_k`` > 2 never falls through to the top-2 capacity gate."""
    from deepspeed_tpu.models import TransformerConfig

    kwargs = dict(vocab_size=64, hidden_size=32, num_layers=1, num_heads=2, moe_num_experts=8,
                  moe_top_k=top_k)
    with pytest.raises(ValueError, match="moe_dropless"):
        TransformerConfig(**kwargs)
    assert TransformerConfig(moe_dropless=True, **kwargs).moe_top_k == top_k
