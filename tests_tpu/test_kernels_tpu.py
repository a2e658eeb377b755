"""Pallas kernel numerics ON THE REAL CHIP in bf16 — flash fwd/bwd (plain,
windowed, alibi), paged attention, blockwise quant, fused Adam. The CPU
suite runs these in interpret mode; Mosaic compilation differences only
show up here."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import alibi_slopes, reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import _pallas_flash
from deepspeed_tpu.ops.pallas.paged_attention import _pallas_paged, paged_attention_reference
from deepspeed_tpu.ops.pallas.quant import dequantize_blockwise, quantize_blockwise


def _qkv(rng, B=2, S=512, nq=8, nkv=8, d=128, dtype=jnp.bfloat16):
    q = jnp.asarray(rng.normal(size=(B, S, nq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, nkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, nkv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("mode", ["plain", "window", "alibi", "gqa"])
def test_flash_fwd_bwd_bf16_on_chip(mode):
    rng = np.random.default_rng(0)
    kw = {}
    nkv = 8
    if mode == "window":
        kw["window"] = 192
    if mode == "alibi":
        kw["alibi"] = True
    if mode == "gqa":
        nkv = 2
    q, k, v = _qkv(rng, nkv=nkv)
    ref_kw = dict(window=kw.get("window"),
                  alibi=alibi_slopes(q.shape[2]) if kw.get("alibi") else None)

    out = _pallas_flash(q, k, v, causal=True, block_q=256, block_k=256, **kw)
    ref = reference_attention(q, k, v, causal=True, **ref_kw)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)
    assert np.isfinite(np.asarray(out, np.float32)).all(), "NaNs from the compiled kernel"

    def loss_k(q, k, v):
        return jnp.sum(_pallas_flash(q, k, v, causal=True, block_q=256, block_k=256, **kw)
                       .astype(jnp.float32)**2)

    def loss_r(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True, **ref_kw).astype(jnp.float32)**2)

    g1 = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a32).all(), "NaNs in compiled backward"
        # bf16 grads: compare direction+magnitude, elementwise loose
        denom = max(np.abs(b32).max(), 1e-3)
        assert np.abs(a32 - b32).max() / denom < 0.12, f"grad mismatch in {mode}"


def test_paged_attention_bf16_on_chip():
    rng = np.random.default_rng(1)
    bs, n_blocks, nkv, g, d = 128, 8, 2, 4, 128
    nq = nkv * g
    pool = bs * n_blocks
    k_pool = jnp.asarray(rng.normal(size=(pool, nkv, d)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(pool, nkv, d)), jnp.bfloat16)
    tables = jnp.arange(2 * n_blocks // 2, dtype=jnp.int32).reshape(2, -1)
    T = 16
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)
    seq_idx = jnp.asarray(np.arange(T) % 2, jnp.int32)
    pos = jnp.asarray(rng.integers(0, bs * (n_blocks // 2), size=T), jnp.int32)

    out = _pallas_paged(q, k_pool, v_pool, tables, seq_idx, pos, block_size=bs)
    ref = paged_attention_reference(q, k_pool, v_pool, tables, seq_idx, pos, bs)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_quant_roundtrip_on_chip():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(256, 512)).astype(np.float32))
    for axis in (0, 1):
        q, s = jax.jit(lambda x: quantize_blockwise(x, 128, axis=axis))(x)
        back = jax.jit(lambda q, s: dequantize_blockwise(q, s, 128, axis=axis))(q, s)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=float(jnp.abs(x).max()) / 120)


def test_fused_adam_on_chip():
    import optax

    from deepspeed_tpu.ops.pallas.fused_adam import fused_adam_apply

    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(512, 1024)).astype(np.float32))}
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    grads = {"w": jnp.asarray(rng.normal(size=(512, 1024)).astype(np.float32))}

    tx = optax.adamw(1e-3, weight_decay=0.01)
    st = tx.init(params)
    upd, _ = tx.update(grads, st, params)
    want = optax.apply_updates(params, upd)

    p, m, v = fused_adam_apply(params, zeros, zeros, grads, lr_t=1e-3, b1=0.9, b2=0.999,
                               eps=1e-8, weight_decay=0.01, step=1, grad_scale=1.0, gate=1.0)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(want["w"]), rtol=2e-6, atol=2e-7)


def test_v1_fused_decode_matches_reference_on_chip():
    """The v1 dense-cache decode routes through the paged kernel (identity
    block table) on TPU; prefill and decode-step LOGITS must match the jnp
    reference numerically (token-stream comparison would be flaky: one bf16
    argmax tie would cascade through greedy feedback)."""
    from deepspeed_tpu.models.transformer import (TransformerConfig, _use_fused_decode,
                                                  forward_with_cache, init_kv_cache, init_params)

    rng = np.random.default_rng(4)
    prompt = jnp.asarray(rng.integers(0, 512, size=(2, 32), dtype=np.int32))

    def logits_pair(attention_impl):
        cfg = TransformerConfig(vocab_size=512, hidden_size=1024, num_layers=2, num_heads=8,
                                max_seq_len=128, intermediate_size=1024, dtype=jnp.bfloat16,
                                attention_impl=attention_impl)
        if attention_impl == "auto":
            assert _use_fused_decode(cfg, 8, 128, 128), "fused decode must engage on chip"
        params = init_params(cfg, jax.random.PRNGKey(7))
        cache = init_kv_cache(cfg, 2, 128)
        pre, cache = jax.jit(lambda p, i, c: forward_with_cache(cfg, p, i, c))(params, prompt, cache)
        tok = jnp.argmax(pre[:, -1:], axis=-1).astype(jnp.int32)
        dec, _ = jax.jit(lambda p, i, c: forward_with_cache(cfg, p, i, c))(params, tok, cache)
        return np.asarray(pre[:, -1], np.float32), np.asarray(dec[:, -1], np.float32)

    pre_f, dec_f = logits_pair("auto")
    pre_r, dec_r = logits_pair("reference")
    np.testing.assert_allclose(pre_f, pre_r, rtol=5e-2, atol=5e-1)
    np.testing.assert_allclose(dec_f, dec_r, rtol=5e-2, atol=5e-1)


def test_flash_non_1024_multiple_seq_keeps_kernel():
    """S=1536 (multiple of 512, not 1024): block auto-fit must keep the
    Pallas kernel engaged rather than regress to O(S^2) reference."""
    from deepspeed_tpu.ops.pallas.flash_attention import _fit_block, flash_attention

    assert _fit_block(1536, 1024) == 768  # largest lane-aligned divisor <= want
    assert _fit_block(2048, 1024) == 1024
    assert _fit_block(640, 1024) == 640  # divides S, lane-aligned
    # non-power-of-two caller hints must still yield true divisors (the old
    # halving loop returned 96/80 here and tripped the kernel's assert)
    assert _fit_block(1280, 768) == 640
    assert _fit_block(1024, 640) == 512
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 1536, 8, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 1536, 8, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 1536, 8, 128)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_block_sparse_attention_bf16_on_chip():
    """Block-sparse LUT-prefetch kernel vs the gathered jnp oracle in bf16
    on the real chip (BigBird layout, block=128 so the MXU gets full tiles)."""
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig, make_layout_lut
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention, block_sparse_attention_gathered)

    rng = np.random.default_rng(6)
    B, H, L, d = 1, 4, 1024, 128
    q = jnp.asarray(rng.normal(size=(B, H, L, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, H, L, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, H, L, d)), jnp.bfloat16)
    cfg = BigBirdSparsityConfig(num_heads=H, block=128, num_random_blocks=1,
                                num_sliding_window_blocks=3, num_global_blocks=1,
                                attention="unidirectional")
    layout = cfg.make_layout(L)
    lut, nvalid = make_layout_lut(layout)
    out = block_sparse_attention(q, k, v, layout, 128, causal=True)
    ref = block_sparse_attention_gathered(q, k, v, lut, nvalid, 128, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_bwd_large_tiles_on_chip():
    """Validate the 1024-tile BACKWARD on the real chip: the eager retry in
    flash_attention only guards the forward call — the custom_vjp backward
    compiles later, under jax.grad, where no retry can catch a VMEM failure.
    This test is the evidence that the large-tile backward actually fits."""
    rng = np.random.default_rng(7)
    S = 2048  # default tile resolves to 1024 on v5e-class chips
    q = jnp.asarray(rng.normal(size=(1, S, 8, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, S, 8, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, S, 8, 128)), jnp.bfloat16)
    from deepspeed_tpu.ops.pallas.flash_attention import (_LARGE_TILE_KINDS, _default_tile,
                                                          flash_attention)

    kind = jax.devices()[0].device_kind.lower()
    if not any(t in kind for t in _LARGE_TILE_KINDS):
        pytest.skip(f"{kind}: 512 default by design — no large-tile backward to validate")
    # on a large-tile generation this IS the regression gate for the default
    assert _default_tile() == 1024, f"large-tile default regressed on {kind}"

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=1e-1, atol=1.5)


def test_sparse_training_attention_bf16_on_chip():
    """TransformerConfig.sparse_attention on the real chip: the block-sparse
    kernel forward under the training model matches the gathered oracle
    (bf16, bigbird unidirectional), and grads are finite through the
    custom-vjp backward."""
    import dataclasses

    from deepspeed_tpu.models.transformer import TransformerConfig, forward, init_params, loss_fn

    cfg = TransformerConfig(vocab_size=512, hidden_size=1024, num_layers=2, num_heads=8,
                            max_seq_len=1024, intermediate_size=1024, dtype=jnp.bfloat16,
                            attention_impl="reference",
                            sparse_attention={"mode": "bigbird", "block": 128,
                                              "num_sliding_window_blocks": 3,
                                              "attention": "unidirectional"})
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(8)
    ids = jnp.asarray(rng.integers(0, 512, size=(1, 1024)), jnp.int32)
    logits = np.asarray(forward(cfg, params, ids), np.float32)
    assert np.isfinite(logits).all()
    # full-layout equivalence: fixed covering all rows == dense causal
    full = dataclasses.replace(cfg, sparse_attention={"mode": "fixed", "block": 128,
                                                      "num_local_blocks": 8,
                                                      "attention": "unidirectional"})
    dense = dataclasses.replace(cfg, sparse_attention=None)
    lf = np.asarray(forward(full, params, ids), np.float32)
    ld = np.asarray(forward(dense, params, ids), np.float32)
    np.testing.assert_allclose(lf, ld, rtol=5e-2, atol=5e-1)
    loss, grads = jax.value_and_grad(lambda p: loss_fn(cfg, p, {"input_ids": ids}))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g, np.float32)).all()
               for g in jax.tree_util.tree_leaves(grads))


def test_evoformer_biased_flash_on_chip():
    """Evoformer Pallas kernel on real TPU, bf16 inputs: fwd + all five
    cotangents vs the fp32 einsum oracle. VMEM residency is tile-bounded
    (q/k/v/o tiles + one [bq,bk] bias2 tile — independent of n_res), so
    n_res=256 here exercises multi-block grids in every pass."""
    from deepspeed_tpu.ops.evoformer_attn import evoformer_attention

    rng = np.random.default_rng(7)
    B, n_seq, n_res, h, d = 1, 4, 256, 4, 32
    q, k, v = (jnp.asarray(rng.normal(size=(B, n_seq, n_res, h, d)), jnp.bfloat16)
               for _ in range(3))
    mask_bias = jnp.asarray(rng.normal(size=(B, n_seq, 1, 1, n_res)), jnp.float32)
    pair_bias = jnp.asarray(rng.normal(size=(B, 1, h, n_res, n_res)), jnp.float32)

    def oracle(q, k, v, b1, b2):
        s = jnp.einsum("...qhd,...khd->...hqk", q.astype(jnp.float32) / np.sqrt(d),
                       k.astype(jnp.float32)) + b1 + b2
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("...hqk,...khd->...qhd", p, v.astype(jnp.float32))

    out = evoformer_attention(q, k, v, [mask_bias, pair_bias])
    ref = oracle(q, k, v, mask_bias, pair_bias)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=2e-2)

    g_pal = jax.grad(lambda *a: jnp.sum(evoformer_attention(a[0], a[1], a[2], a[3:]).astype(jnp.float32) * 0.01),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, mask_bias, pair_bias)
    g_ref = jax.grad(lambda *a: jnp.sum(oracle(*a) * 0.01),
                     argnums=(0, 1, 2, 3, 4))(q, k, v, mask_bias, pair_bias)
    for name, a, b in zip(("dq", "dk", "dv", "dbias1", "dbias2"), g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b, np.float32), np.asarray(a, np.float32),
                                   atol=3e-2, rtol=3e-2, err_msg=name)


def test_paged_attention_int8_kv_on_chip():
    """int8-KV paged kernel on real TPU: dequant at the tile read vs the
    gather reference on the same quantized pools."""
    rng = np.random.default_rng(13)
    T, nq, nkv, d, bs, NB = 8, 16, 16, 128, 128, 8
    pool_len = NB * bs
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)
    kf = rng.normal(size=(pool_len, nkv, d)).astype(np.float32)
    vf = rng.normal(size=(pool_len, nkv, d)).astype(np.float32)
    ks = np.maximum(np.abs(kf).max(-1) / 127.0, 1e-8)
    vs = np.maximum(np.abs(vf).max(-1) / 127.0, 1e-8)
    k8 = jnp.asarray(np.round(kf / ks[..., None]), jnp.int8)
    v8 = jnp.asarray(np.round(vf / vs[..., None]), jnp.int8)
    ksT, vsT = jnp.asarray(ks.T), jnp.asarray(vs.T)
    tables = jnp.asarray(rng.permutation(NB).reshape(2, 4), jnp.int32)
    seq_idx = jnp.asarray([0, 0, 0, 0, 1, 1, 1, 1], jnp.int32)
    pos = jnp.asarray([3, 100, 200, 511, 7, 120, 300, 450], jnp.int32)

    ref = paged_attention_reference(q, k8, v8, tables, seq_idx, pos, bs,
                                    k_scale=ksT, v_scale=vsT)
    out = _pallas_paged(q, k8, v8, tables, seq_idx, pos, block_size=bs,
                        k_scale=ksT, v_scale=vsT)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_paged_attention_q_tiled_on_chip():
    """Q-tiled paged kernel on real TPU (the PR 10 prefill-amortization
    grid): mixed prefill+decode batch with ragged tile tails vs the gather
    reference, bf16 and int8-KV, Mosaic-compiled (the interpret-mode parity
    matrix in tests/test_kernel_tuning.py cannot see lowering bugs)."""
    rng = np.random.default_rng(17)
    nq, nkv, d, bs, NB = 16, 16, 128, 128, 8
    pool_len = NB * bs
    # seq 0: 21-token prefill chunk (ragged tail at q_tile=8); seq 1: decode
    seq_idx = jnp.asarray([0] * 21 + [1] * 3, jnp.int32)
    pos = jnp.asarray(list(range(40, 61)) + [100, 101, 102], jnp.int32)
    T = int(seq_idx.shape[0])
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)
    tables = jnp.asarray(rng.permutation(NB).reshape(2, 4), jnp.int32)

    kf = rng.normal(size=(pool_len, nkv, d)).astype(np.float32)
    vf = rng.normal(size=(pool_len, nkv, d)).astype(np.float32)
    k_pool = jnp.asarray(kf, jnp.bfloat16)
    v_pool = jnp.asarray(vf, jnp.bfloat16)
    ref = paged_attention_reference(q, k_pool, v_pool, tables, seq_idx, pos, bs)
    for qt in (8, 16):
        out = _pallas_paged(q, k_pool, v_pool, tables, seq_idx, pos, block_size=bs, q_tile=qt)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                                   atol=5e-2, rtol=5e-2, err_msg=f"q_tile={qt}")

    # int8-KV through the tiled grid
    ks = np.maximum(np.abs(kf).max(-1) / 127.0, 1e-8)
    vs = np.maximum(np.abs(vf).max(-1) / 127.0, 1e-8)
    k8 = jnp.asarray(np.round(kf / ks[..., None]), jnp.int8)
    v8 = jnp.asarray(np.round(vf / vs[..., None]), jnp.int8)
    ksT, vsT = jnp.asarray(ks.T), jnp.asarray(vs.T)
    ref8 = paged_attention_reference(q, k8, v8, tables, seq_idx, pos, bs,
                                     k_scale=ksT, v_scale=vsT)
    out8 = _pallas_paged(q, k8, v8, tables, seq_idx, pos, block_size=bs, q_tile=8,
                         k_scale=ksT, v_scale=vsT)
    np.testing.assert_allclose(np.asarray(out8, np.float32), np.asarray(ref8, np.float32),
                               atol=2e-2, rtol=2e-2)


def _paged_reference_by_run(q, k_pool, v_pool, tables, rows, bs, window):
    """Float32 attention of ragged rows ``(context_before, new_tokens)``, one
    sequence at a time and 256 queries at a time: the gather oracle
    materialises a context per TOKEN, 71 GB at these shapes."""
    T, nq, d = q.shape
    nkv = k_pool.shape[1]
    outs, t0 = [], 0
    for r, (before, new) in enumerate(rows):
        slots = (tables[r][:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        k, v = k_pool[slots].astype(jnp.float32), v_pool[slots].astype(jnp.float32)
        ctx = jnp.arange(k.shape[0])[None, :]
        for c0 in range(0, new, 256):
            c1 = min(new, c0 + 256)
            qq = q[t0 + c0:t0 + c1].astype(jnp.float32).reshape(c1 - c0, nkv, nq // nkv, d) / np.sqrt(d)
            p = jnp.arange(before + c0, before + c1)[:, None]
            s = jnp.einsum("tngd,cnd->tngc", qq, k, precision="highest")
            vis = (ctx <= p) if window is None else (ctx <= p) & (p - ctx < window)
            w = jax.nn.softmax(jnp.where(vis[:, None, None, :], s, -1e30), axis=-1)
            outs.append(jnp.einsum("tngc,cnd->tngd", w, v, precision="highest").reshape(c1 - c0, nq, d))
        t0 += new
    return jnp.concatenate(outs, 0)


def _us_a_call(fn, *args, calls=50):
    """Microseconds a call of a compiled ``fn``, the jnp code around the
    kernel included (tile assembly, work list, scatter back): ``calls``
    dispatches behind a warm one, one wait at the end."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e6


# microseconds a call of the PARENT's rectangle grid (commit 6c807fc, this file's tests run in its tree on the same
# chip in the same call: my chip run, PR 34), to read beside what the tests print; nothing is asserted of either.
# A host clock over 50 dispatches: under about 400 us it reads the dispatch, not the device (PERF.md section 6, PR 34,
# has the device's own time, kernel and XLA code apart, from a profiler trace of the same shapes)
_PARENT_US = {
    ("serving_cells_shapes", "longprompt-32/8-4096"): 2039,
    ("serving_cells_shapes", "longprompt-48/8-4096"): 3026,
    ("serving_cells_shapes", "longprompt-48/8-None"): 4747,
    ("serving_cells_shapes", "longprompt-32/4-1024"): 994,
    ("serving_cells_shapes", "longprompt_mixed-32/8-4096"): 1776,
    ("serving_cells_shapes", "longprompt_mixed-48/8-4096"): 2645,
    ("serving_cells_shapes", "longprompt_mixed-48/8-None"): 2873,
    ("serving_cells_shapes", "longprompt_mixed-32/4-1024"): 1064,
    ("serving_cells_shapes", "chat-32/8-4096"): 595,
    ("serving_cells_shapes", "chat-48/8-4096"): 857,
    ("serving_cells_shapes", "chat-48/8-None"): 1086,
    ("serving_cells_shapes", "chat-32/4-1024"): 579,
    ("serving_cells_shapes", "put_64x8-32/8-4096"): 362,
    ("serving_cells_shapes", "put_64x8-48/8-4096"): 415,
    ("serving_cells_shapes", "put_64x8-48/8-None"): 479,
    ("serving_cells_shapes", "put_64x8-32/4-1024"): 381,
    ("serving_cells_shapes", "put_512x64-32/8-4096"): 1512,
    ("serving_cells_shapes", "put_512x64-48/8-4096"): 1829,
    ("serving_cells_shapes", "put_512x64-48/8-None"): 2298,
    ("serving_cells_shapes", "put_512x64-32/4-1024"): 1686,
    ("block_causal_bound", "forward_64x4"): 2640,
    ("block_causal_bound", "forward_8x4"): 229,
    ("block_causal_bound", "chunk_512"): 449,
}


def _say_us(test, case, us):
    print(f"\n{test}[{case}]: {us:.0f} us a call (parent's rectangle: {_PARENT_US.get((test, case), 'not measured')})")


@pytest.mark.parametrize("nq,nkv,window", [(32, 8, 4096), (48, 8, 4096), (48, 8, None), (32, 4, 1024)],
                         ids=["group4.window", "group6.window", "group6.full", "group8.window1024"])
@pytest.mark.parametrize("name,T,S,rows,want", [
    # mistral-7b.longprompt: the last 2,048-token chunk of an 8,192-token prompt
    ("longprompt", 2048, 8, [(6144, 2048)], (128, "heuristic:long_rows")),
    # the same bucket as the closed loop fills it: a chunk and six decode rows
    ("longprompt_mixed", 2048, 8,
     [(1200, 1), (2500, 1), (3600, 1), (5000, 1), (7000, 1), (8000, 1), (2048, 2042)],
     (128, "heuristic:long_rows")),
    # mistral-7b.chat: a SplitFuse put of 20 one-token rows and a 490-token chunk
    ("chat", 512, 32, [(100 + 59 * i, 1) for i in range(20)] + [(0, 490)], (32, "heuristic:short_rows")),
    # trinity-large-preview.decode-heavy-64: a 40-token chunk past the window beside 6 decode rows (64 tokens x 8 rows),
    # and a 512-token put of 60 decode rows and a 440-token chunk (512 x 64)
    ("put_64x8", 64, 8, [(300 + 700 * i, 1) for i in range(6)] + [(4200, 40)], (16, "heuristic:short_rows")),
    ("put_512x64", 512, 64, [(262 + 29 * i, 1) for i in range(60)] + [(100, 440)], (16, "heuristic:short_rows")),
])
def test_paged_q_tiled_at_the_serving_cells_shapes(name, T, S, rows, want, nq, nkv, window):
    """``paged_attention`` as the serving engine calls it, at the shapes of
    the benchmark's serving cells (32/8 heads of 128, and 48/8, a GQA group of
    6 that is no multiple of the 8 sublanes: PR 31; 32/4 under Mellum's window
    of 1,024: PR 34; 128-token blocks, tables 65 wide, window 4,096 or none,
    bf16): the tile the heuristic picks, on the grid of live (tile, KV block)
    pairs, against a float32 reference. A kernel that does not fit VMEM fails
    to compile here, loudly. Prints the microseconds a call."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    d, bs, mb, n_blocks = 128, 128, 65, 619
    rng = np.random.default_rng(25)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nkv, d)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nkv, d)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, n_blocks, size=(S, mb)), jnp.int32)
    seq_idx = np.concatenate([np.full(new, r) for r, (_, new) in enumerate(rows)])
    pos = np.concatenate([np.arange(before, before + new) for before, new in rows])
    n = seq_idx.size  # the rest is the pad run ragged_wrapper.finalize emits
    seq_idx = jnp.asarray(np.pad(seq_idx, (0, T - n)), jnp.int32)
    pos = jnp.asarray(np.pad(pos, (0, T - n)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)

    pa.KERNEL_CHOICES.pop((T, S, mb), None)
    # the batch as ARGUMENTS, as the engine's jitted step has it: the work list is then built on the device
    fn = jax.jit(lambda q, tables, seq_idx, pos: pa.paged_attention(q, k_pool, v_pool, tables, seq_idx, pos, bs,
                                                                    window=window))
    out = fn(q, tables, seq_idx, pos)
    choice = pa.kernel_choice(T, S, mb)
    assert (choice["kernel"], choice["q_tile"], choice["rule"]) == ("paged_attn_q_tiled", ) + want
    _say_us("serving_cells_shapes", f"{name}-{nq}/{nkv}-{window}", _us_a_call(fn, q, tables, seq_idx, pos))
    ref = np.asarray(_paged_reference_by_run(q, k_pool, v_pool, tables, rows, bs, window), np.float32)
    got = np.asarray(out[:n], np.float32)
    np.testing.assert_allclose(got, ref, atol=5e-2, rtol=5e-2)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-3


def test_v2_engine_serving_on_chip_bf16_and_int8():
    """Engine-level on-chip smoke of the composed ragged program (embed +
    quantized scatter + paged kernel + multi-step decode scan) — the exact
    compiled surface bench_serving times. bf16 and int8 KV must agree on
    greedy tokens for a short horizon."""
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    # head_dim 128: the paged kernel's shape gate (nq >= 8, d % 128 == 0)
    # must ADMIT this model, or the test only ever times the dense gather
    cfg = TransformerConfig(vocab_size=512, hidden_size=1024, num_layers=2, num_heads=8,
                            num_kv_heads=8, intermediate_size=512, max_seq_len=512,
                            dtype=jnp.bfloat16, attention_impl="flash")
    model = TransformerLM(cfg)
    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=256,
                              max_ragged_sequence_count=4, max_context=384)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 512, size=130, dtype=np.int32)

    outs = {}
    for kv in ("bf16", "int8"):
        icfg = RaggedInferenceEngineConfig(
            kv_block_size=128, num_kv_blocks=16,
            kv_dtype="int8" if kv == "int8" else cfg.dtype,
            state_manager=sm, use_pallas_kernels="always")
        eng = InferenceEngineV2(model, icfg)
        first = eng.put([0], [prompt], sample="greedy")
        toks = eng.decode([0], [np.asarray([int(first[0])], np.int32)], 8)
        outs[kv] = [int(first[0])] + np.asarray(toks)[0].tolist()
    # greedy agreement for a short horizon (int8 quantization noise may
    # eventually diverge a long rollout; the first steps must match)
    assert outs["bf16"][:4] == outs["int8"][:4], outs


def test_grouped_matmul_on_chip():
    """Grouped ragged matmul (MoE expert GEMM) compiled by Mosaic: gmm
    forward + tgmm weight-grad vs the per-block numpy oracle in bf16, and
    the dispatcher end-to-end vs the einsum MoE path."""
    from deepspeed_tpu.moe.grouped import grouped_moe_ffn
    from deepspeed_tpu.moe.sharded_moe import top2gating
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm, tgmm

    rng = np.random.default_rng(0)
    T, K, N, E, bt = 1024, 512, 512, 8, 128
    lhs = jnp.asarray(rng.normal(size=(T, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(E, K, N)), jnp.bfloat16)
    be = jnp.asarray(np.sort(np.concatenate(
        [np.arange(E), rng.integers(0, E, size=T // bt - E)])).astype(np.int32))
    out = np.asarray(gmm(lhs, rhs, be, block_t=bt)).astype(np.float32)
    ref = np.zeros((T, N), np.float32)
    lf, rf = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    for i, e in enumerate(np.asarray(be)):
        ref[i * bt:(i + 1) * bt] = lf[i * bt:(i + 1) * bt] @ rf[e]
    np.testing.assert_allclose(out, ref, rtol=5e-2, atol=5e-1)

    dy = jnp.asarray(rng.normal(size=(T, N)), jnp.bfloat16)
    dw = np.asarray(tgmm(lhs, dy, be, E, block_t=bt))
    dwr = np.zeros((E, K, N), np.float32)
    dyf = np.asarray(dy, np.float32)
    for i, e in enumerate(np.asarray(be)):
        dwr[e] += lf[i * bt:(i + 1) * bt].T @ dyf[i * bt:(i + 1) * bt]
    np.testing.assert_allclose(dw, dwr, rtol=5e-2, atol=2.0)

    # dispatcher end-to-end vs the einsum formulation, bf16 on chip
    S, M, F, Ee = 512, 256, 512, 8
    x = jnp.asarray(rng.normal(size=(S, M)), jnp.bfloat16)
    logits = jnp.asarray(rng.normal(size=(S, Ee)), jnp.float32)
    _, combine, dispatch, _ = top2gating(logits, 1.0, 4)
    wi = jnp.asarray(rng.normal(size=(Ee, M, F)) / np.sqrt(M), jnp.bfloat16)
    wo = jnp.asarray(rng.normal(size=(Ee, F, M)) / np.sqrt(F), jnp.bfloat16)
    disp = jnp.einsum("sec,sm->ecm", dispatch.astype(x.dtype), x)
    mid = jax.nn.gelu(jnp.einsum("ecm,emf->ecf", disp, wi))
    y_ref = jnp.einsum("sec,ecm->sm", combine.astype(x.dtype),
                       jnp.einsum("ecf,efm->ecm", mid, wo))
    top_w, top_idx = jax.lax.top_k(combine.sum(axis=2).astype(x.dtype), 2)
    y = grouped_moe_ffn(x, top_idx, top_w, wi, wo, activation=lambda up, g: jax.nn.gelu(up))
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
                               rtol=1e-1, atol=2e-1)


@pytest.mark.parametrize("K,N,bt", [(2304, 896, 128), (896, 2304, 128), (2304, 896, 8)])
def test_grouped_matmul_fwd_bwd_at_served_widths_on_chip(K, N, bt):
    """``grouped_matmul`` forward and backward (gmm, gmm against the transposed
    experts, tgmm) at the widths the serving cell runs (64 experts of 2304 x 896
    and 896 x 2304, bf16) with the DEFAULT tiles, which at these widths are the
    whole of K and N (``_resolve_gmm_tiles``): the tiles the training path takes
    too. Against a float32 einsum over the row blocks and its gradient; every
    expert owns a block (tgmm writes an expert's output when it visits it) and
    expert 0 owns many."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(K + bt)
    E, blocks = 64, 96
    T = blocks * bt
    lhs = jnp.asarray(rng.normal(size=(T, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(E, K, N)) / np.sqrt(K), jnp.bfloat16)
    dy = jnp.asarray(rng.normal(size=(T, N)), jnp.bfloat16)
    be = jnp.asarray(np.sort(np.concatenate([np.arange(E), np.zeros(blocks - E, np.int64)])).astype(np.int32))

    def by_block(lhs, rhs):  # [blocks, bt, K] x [blocks, K, N]: a block's rows times its expert
        return jnp.einsum("bik,bkn->bin", lhs.reshape(blocks, bt, K), rhs[be],
                          precision=jax.lax.Precision.HIGHEST).reshape(T, N)

    def loss(fn):
        return lambda lhs, rhs: jnp.sum(fn(lhs, rhs).astype(jnp.float32) * dy.astype(jnp.float32))

    out, (dx, dw) = jax.jit(lambda l, r: (grouped_matmul(l, r, be, block_t=bt), jax.grad(
        loss(lambda l, r: grouped_matmul(l, r, be, block_t=bt)), argnums=(0, 1))(l, r)))(lhs, rhs)
    ref, (rx, rw) = jax.jit(lambda l, r: (by_block(l, r), jax.grad(loss(by_block), argnums=(0, 1))(l, r)))(
        lhs.astype(jnp.float32), rhs.astype(jnp.float32))
    for name, got, want in (("out", out, ref), ("dx", dx, rx), ("dw", dw, rw)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert np.isfinite(got).all() and rel < 1e-2, (name, rel)  # bf16 operands and outputs: about 3e-3


def test_int4_weight_dequant_on_chip():
    """INT4 packed-nibble dequant compiled by XLA on the real chip: the
    unpack (shift/mask) + q*scale+zero must fuse into the matmul operand
    read and match the fp32 reference within the quantization step."""
    from deepspeed_tpu.inference.quantization import quantize_weight_int4

    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=(512, 1024)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(16, 512)), jnp.bfloat16)
    q4 = quantize_weight_int4(w)

    y = jax.jit(lambda x, q: x @ q.astype(jnp.bfloat16))(x, q4)
    back = np.asarray(q4.astype(jnp.float32))
    step = float((np.asarray(w).max(0) - np.asarray(w).min(0)).max()) / 15
    assert np.abs(back - np.asarray(w)).max() <= step / 2 + 1e-4
    y_ref = np.asarray(x, np.float32) @ back
    np.testing.assert_allclose(np.asarray(y, np.float32), y_ref, rtol=5e-2, atol=5e-1)


def _decode_rows(contexts):
    """One decode token a row at the end of its context, then the pad run
    ragged_wrapper.finalize emits (row 0, position 0)."""
    return [(r, c - 1) for r, c in enumerate(contexts)] + [(0, 0)]


def _chunks(*runs):
    """Tokens of (row, first position, count) runs, back to back."""
    return [(r, p + i) for r, p, n in runs for i in range(n)]


_CELL_CONTEXTS = [262 + 57 * i for i in range(31)]
_CELL_CONTEXTS_64 = [262 + 29 * i for i in range(60)] + [4097, 4500, 5000]
# the inputs the deleted per-token grid served (PR 29), by the rule that now hands them to the decode kernel
_SHORT_TABLE = _decode_rows([1 + (37 * i) % 512 for i in range(31)])                      # (a) 32 rows, 4 columns
_VERIFY = _chunks(*[(r, 100 + 251 * r, 5) for r in range(8)])                             # (b) 8 rows x 5 tokens
_INTERLEAVED = [(i % 4, 100 + 500 * (i % 4) + i // 4) for i in range(96)]                 # (c) not contiguous
# (d) the cells' own 32-token x 8-row put: a 20-token chunk at ~3,000, 4 decode rows, 8 pad tokens
_PUT_32x8 = _chunks((0, 2980, 20)) + [(1, 301), (2, 555), (3, 790), (4, 999)] + [(0, 0)] * 8


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("name,nq,nkv,window,S,mb,n_blocks,tokens,rule", [
    # mistral-7b.decode-heavy: 32 rows of 262-2,020 tokens, 8 kv heads (two 512 KiB blocks a grid step)
    ("mistral-7b", 32, 8, 4096, 31, 65, 619, _decode_rows(_CELL_CONTEXTS), "heuristic:long_table"),
    # mellum2-12b-a2.5b.decode-heavy: 4 kv heads (four blocks a step), its window layers and its full ones
    ("mellum2.window", 32, 4, 1024, 31, 65, 619, _decode_rows(_CELL_CONTEXTS), "heuristic:long_table"),
    ("mellum2.full", 32, 4, None, 31, 65, 619, _decode_rows(_CELL_CONTEXTS), "heuristic:long_table"),
    # one kv head a query head, a table it fills: one 1 MiB block a step, one long row beside short ones
    # trinity-large-preview.decode-heavy-64: 48/8 heads (a group of 6), 64 rows, contexts on both sides of the window
    ("trinity.window", 48, 8, 4096, 63, 65, 619, _decode_rows(_CELL_CONTEXTS_64), "heuristic:long_table"),
    ("trinity.full", 48, 8, None, 63, 65, 619, _decode_rows(_CELL_CONTEXTS_64), "heuristic:long_table"),
    ("trinity.put_32x8.window", 48, 8, 4096, 8, 65, 619, _PUT_32x8, "heuristic:multi_token"),
    ("mha16", 16, 16, None, 4, 16, 64, _decode_rows([2048, 131, 657, 3]), "heuristic:long_table"),
    ("short_table", 32, 8, None, 31, 4, 619, _SHORT_TABLE, "heuristic:short_table"),
    ("short_table.window", 32, 8, 256, 31, 4, 619, _SHORT_TABLE, "heuristic:short_table"),
    ("verify_8x5", 32, 8, None, 8, 17, 619, _VERIFY, "heuristic:multi_token"),
    ("verify_8x5.window", 32, 8, 1024, 8, 17, 619, _VERIFY, "heuristic:multi_token"),
    ("noncontiguous_96", 32, 8, None, 4, 17, 619, _INTERLEAVED, "contiguity_demoted"),
    ("noncontiguous_96.window", 32, 8, 1024, 4, 17, 619, _INTERLEAVED, "contiguity_demoted"),
    ("put_32x8.mistral", 32, 8, 4096, 8, 65, 619, _PUT_32x8, "heuristic:multi_token"),
    ("put_32x8.mistral.full", 32, 8, None, 8, 65, 619, _PUT_32x8, "heuristic:multi_token"),
    ("put_32x8.mellum.window", 32, 4, 1024, 8, 65, 619, _PUT_32x8, "heuristic:multi_token"),
    ("put_32x8.mellum.full", 32, 4, None, 8, 65, 619, _PUT_32x8, "heuristic:multi_token"),
])
def test_paged_attention_kv_split_on_chip(name, nq, nkv, window, S, mb, n_blocks, tokens, rule, kv):
    """The decode kernel on the chip, through ``paged_attention`` as the
    serving engine calls it, at the shapes of the two decode-heavy cells
    (heads of 128, 128-token blocks, tables 65 wide, one token a row, a pad
    row at position 0) and at every kind of batch the selector hands it
    (a short table, several tokens a row, a batch that is not contiguous, the
    cells' 32-token x 8-row ``put``), bf16 and int8 KV, against the gather
    reference. The interpret-mode matrix in tests/test_kernel_tuning.py
    cannot see Mosaic: the dynamic grid bound, the scalar-prefetched work
    list and the pool read as ``[blocks, block * nkv, d]`` only exist here."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    d, bs = 128, 128
    rng = np.random.default_rng(28)
    T = len(tokens)
    seq_idx = jnp.asarray([r for r, _ in tokens], jnp.int32)
    pos = jnp.asarray([p for _, p in tokens], jnp.int32)
    tables = np.zeros((S, mb), np.int32)
    for r in range(S):
        n = max((p for row, p in tokens if row == r), default=0) // bs + 1
        tables[r, :n] = rng.choice(n_blocks, size=n, replace=False)
    tables = jnp.asarray(tables)
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)
    kf = rng.normal(size=(n_blocks * bs, nkv, d)).astype(np.float32)
    vf = rng.normal(size=(n_blocks * bs, nkv, d)).astype(np.float32)
    if kv == "int8":
        ksc = np.maximum(np.abs(kf).max(-1) / 127.0, 1e-8)
        vsc = np.maximum(np.abs(vf).max(-1) / 127.0, 1e-8)
        k_pool = jnp.asarray(np.round(kf / ksc[..., None]), jnp.int8)
        v_pool = jnp.asarray(np.round(vf / vsc[..., None]), jnp.int8)
        kw = dict(k_scale=jnp.asarray(ksc.T), v_scale=jnp.asarray(vsc.T))
    else:
        k_pool, v_pool, kw = jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16), {}
    del kf, vf

    pa.KERNEL_CHOICES.pop((T, S, mb), None)
    out = jax.jit(lambda q, k_pool, v_pool, kw: pa.paged_attention(  # the pools as arguments, not constants
        q, k_pool, v_pool, tables, seq_idx, pos, bs, window=window, **kw))(q, k_pool, v_pool, kw)
    # a grid step streams 1 MiB of K and V where the blocks are smaller than that
    assert pa.kernel_choice(T, S, mb) == {
        "kernel": "paged_attn_kv_split", "q_tile": 1, "rule": rule,
        "blocks_per_step": max(1, min(4, (1 << 20) // (2 * bs * nkv * d * k_pool.dtype.itemsize)))}
    ref = np.asarray(paged_attention_reference(q, k_pool, v_pool, tables, seq_idx, pos, bs,
                                               window=window, **kw), np.float32)
    got = np.asarray(out, np.float32)
    np.testing.assert_allclose(got, ref, atol=5e-2, rtol=5e-2)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 6e-3


@pytest.mark.parametrize("name,T,S,rows,want", [
    # sdar-30b-a3b-chat.block-diffusion-64: a denoise forward of 64 rows x 4 tokens, contexts 256-2,048, no multiple of 128
    ("forward_64x4", 256, 64, [(260 + 28 * i, 4) for i in range(64)], ("paged_attn_q_tiled", 8, "heuristic:short_rows")),
    # the drain: 8 rows x 4 tokens, under 64 tokens: the decode kernel by its work list, 4 KV blocks a step
    ("forward_8x4", 32, 8, [(260 + 252 * i, 4) for i in range(7)], ("paged_attn_kv_split", 4, "heuristic:multi_token")),
    # a 512-token prefill chunk whose last block lies past a KV block's edge, beside a chunk of a second prompt
    ("chunk_512", 512, 8, [(508, 452), (0, 60)], ("paged_attn_q_tiled", 128, "heuristic:long_rows")),
])
def test_paged_kernels_under_the_block_causal_bound_on_chip(name, T, S, rows, want):
    """Both paged kernels as ``ragged_forward`` calls them for a block-diffusion
    model (SDAR's 32/4 heads of 128, blocks of 4 under 128-token KV blocks,
    tables 65 wide, bf16): given each token's BLOCK's last position to mask by,
    against a float32 reference that applies ``j // 4 <= i // 4`` to the true
    positions. ``forward_64x4`` is the claimed cell's forward: 64 run tiles of
    4-17 live blocks under 97 tiles x 65 columns. Prints the microseconds a
    call."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    nq, nkv, d, bs, mb, n_blocks, B = 32, 4, 128, 128, 65, 619, 4
    rng = np.random.default_rng(33)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nkv, d)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks * bs, nkv, d)), jnp.bfloat16)
    tables = jnp.asarray(rng.integers(0, n_blocks, size=(S, mb)), jnp.int32)
    seq_idx = np.concatenate([np.full(new, r) for r, (_, new) in enumerate(rows)])
    pos = np.concatenate([np.arange(before, before + new) for before, new in rows])
    n = seq_idx.size  # the rest is the pad run ragged_wrapper.finalize emits
    seq_idx = jnp.asarray(np.pad(seq_idx, (0, T - n)), jnp.int32)
    pos = jnp.asarray(np.pad(pos, (0, T - n)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)

    pa.KERNEL_CHOICES.pop((T, S, mb), None)
    fn = jax.jit(lambda q, vis: pa.paged_attention(q, k_pool, v_pool, tables, seq_idx, vis, bs))
    out = fn(q, pos | (B - 1))
    _say_us("block_causal_bound", name, _us_a_call(fn, q, pos | (B - 1)))
    choice = pa.kernel_choice(T, S, mb)
    assert (choice["kernel"], max(choice["q_tile"], choice["blocks_per_step"]), choice["rule"]) == want
    outs, t0 = [], 0
    for r, (before, new) in enumerate(rows):
        slots = (tables[r][:, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        k, v = k_pool[slots].astype(jnp.float32), v_pool[slots].astype(jnp.float32)
        qq = q[t0:t0 + new].astype(jnp.float32).reshape(new, nkv, nq // nkv, d) / np.sqrt(d)
        vis = jnp.arange(k.shape[0])[None, :] // B <= jnp.arange(before, before + new)[:, None] // B
        s = jnp.einsum("tngd,cnd->tngc", qq, k, precision="highest")
        w = jax.nn.softmax(jnp.where(vis[:, None, None, :], s, -1e30), axis=-1)
        outs.append(jnp.einsum("tngc,cnd->tngd", w, v, precision="highest").reshape(new, nq, d))
        t0 += new
    ref, got = np.asarray(jnp.concatenate(outs, 0), np.float32), np.asarray(out[:n], np.float32)
    np.testing.assert_allclose(got, ref, atol=5e-2, rtol=5e-2)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 6e-3
    # the bound is the one thing that differs from the causal kernel: a causal mask reads otherwise
    causal = np.asarray(fn(q, pos)[:n], np.float32)
    assert np.linalg.norm(causal - ref) / np.linalg.norm(ref) > 0.02


def test_both_paged_kernels_take_the_pool_as_the_same_rows_on_chip():
    """One compiled program of SDAR's shapes (32/4 heads of 128, 128-token
    blocks, a 65-column table) that calls both kernels on one pool, a denoise
    forward of 64 rows x 4 tokens through ``paged_attn_q_tiled`` and the
    drain's 8 x 4 through ``paged_attn_kv_split``: every pool-sized operand of
    either custom call is the rank-3 ``[blocks, block x nkv, d]`` view and a
    ``bitcast`` of the program's own parameter, so no relayout copy of the
    pool stands between the kernels (one would cost about a millisecond a
    layer call, and no parity test would see it)."""
    import re

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    nq, nkv, d, bs, mb, n_blocks = 32, 4, 128, 128, 65, 619
    rng = np.random.default_rng(37)
    pools = [jnp.asarray(rng.normal(size=(n_blocks * bs, nkv, d)), jnp.bfloat16) for _ in range(2)]
    batches = []
    for T, S, rows in [(256, 64, [(260 + 28 * i, 4) for i in range(64)]),
                       (32, 8, [(260 + 252 * i, 4) for i in range(7)])]:
        seq_idx = np.concatenate([np.full(new, r) for r, (_, new) in enumerate(rows)])
        pos = np.concatenate([np.arange(before, before + new) for before, new in rows]) | 3
        batches.append((jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16),
                        jnp.asarray(rng.integers(0, n_blocks, size=(S, mb)), jnp.int32),
                        jnp.asarray(np.pad(seq_idx, (0, T - seq_idx.size)), jnp.int32),
                        jnp.asarray(np.pad(pos, (0, T - pos.size)), jnp.int32)))

    def both(k_pool, v_pool, batches):
        return [pa.paged_attention(q, k_pool, v_pool, tables, seq_idx, pos, bs) for q, tables, seq_idx, pos in batches]

    text = jax.jit(both).lower(*pools, batches).compile().as_text()
    rows_view = f"bf16[{n_blocks},{bs * nkv},{d}]"
    defined = dict(re.findall(r"^\s*(%[\w.-]+) = (\S+ [\w-]+\([^)]*\))", text, re.M))
    for kernel in ("paged_attn_q_tiled", "paged_attn_kv_split"):
        call, = re.findall(rf"%{kernel}[\w.]* = .*? custom-call\(([^)]*)\)", text)
        operands = re.findall(r"%[\w.-]+", call)
        pool_operands = [defined[o] for o in operands if o in defined and str(n_blocks) in defined[o].split("{")[0]]
        assert len(pool_operands) >= 2, (kernel, operands)
        for definition in pool_operands:
            assert definition.startswith(rows_view), (kernel, definition)
            assert re.search(r" bitcast\(%[kv]_pool", definition), (kernel, definition)
    assert not re.search(rf"bf16\[{n_blocks},[\d,]*\]\S* (copy|transpose|fusion)\(", text)


def test_moe_serving_programs_of_every_bucket_pair_run_on_chip():
    """Every (token bucket, row bucket) ``put`` program and every row bucket's
    one-step ``decode`` program of a model with experts and both attention
    kinds, at the served widths and TWO layers (a window layer and a full
    one): PR 27 met a program of paged attention + ``moe_gmm`` in two or more
    layers (64 tokens x 8 rows) that never returned under XLA's default scoped
    VMEM, and the engine now compiles such a model's programs with a larger
    one (``engine._jit_options``). A serving cell warms only the buckets its
    traffic reaches; this runs all of them. A hang becomes an exit: the
    traceback of ``faulthandler`` names the bucket that did not return."""
    import faulthandler

    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import TransformerLM, mellum_config

    cfg = mellum_config("12b-a2.5b", num_layers=2, layer_types=("sliding_attention", "full_attention"),
                        dtype=jnp.bfloat16)
    sm = DSStateManagerConfig(max_tracked_sequences=64, max_ragged_batch_size=2048,
                              max_ragged_sequence_count=64, max_context=8320)
    icfg = RaggedInferenceEngineConfig(kv_block_size=128, num_kv_blocks=200, kv_dtype=jnp.bfloat16, state_manager=sm)
    model = TransformerLM(cfg)
    # bf16 as served: experts stored in the type they are multiplied in are read in place from the stack
    params = jax.jit(lambda k: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), model.init(k, None)))(
        jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, icfg, params=params)
    assert eng._jit_options, "a model with experts compiles its serving programs with the larger scoped VMEM"
    ran = []
    try:
        for tokens in eng.batch.token_buckets:
            for rows in eng.batch.seq_buckets:
                if rows > tokens:
                    continue  # no batch has more rows than tokens
                faulthandler.dump_traceback_later(150, exit=True)
                print(f"put tokens={tokens} rows={rows}", flush=True)
                ran += eng.warmup([rows], [], token_buckets=[tokens], declare_warmed=False)
        for rows in eng.batch.seq_buckets:
            faulthandler.dump_traceback_later(150, exit=True)
            print(f"decode rows={rows}", flush=True)
            ran += eng.warmup([rows], [1], declare_warmed=False)
    finally:
        faulthandler.cancel_dump_traceback_later()
    assert len(ran) >= len(eng.batch.seq_buckets) and not any(r["cached"] for r in ran), ran


def _compiled_train_step_text(head_dim, abstract=None, rotary_dim=64):
    """The text XLA compiled for value_and_grad of the model's loss with a
    2-layer scanned, rematerialised block at a Pythia's shapes. ``abstract``
    maps a ShapeDtypeStruct to one placed on a described device (the sandbox's
    compile-only rehearsal); on the chip the shapes compile for the attached
    device."""
    from deepspeed_tpu.models import TransformerLM, gpt_neox_config

    cfg = gpt_neox_config("pythia-1b", hidden_size=16 * head_dim, num_heads=16, num_kv_heads=16,
                          intermediate_size=4 * 16 * head_dim, num_layers=2, vocab_size=50304,
                          max_seq_len=2048, dtype=jnp.bfloat16, attention_impl="flash", remat=True,
                          remat_policy="save_only_these_names(attn_out)", rotary_dim=rotary_dim)
    model = TransformerLM(cfg)
    params = jax.eval_shape(lambda k: model.init(k, None), jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 2048), jnp.int32)
    if abstract is not None:
        params, ids = jax.tree_util.tree_map(abstract, (params, ids))
    step = jax.jit(jax.value_and_grad(lambda p, i: model.loss(p, {"input_ids": i})))
    return step.lower(params, ids).compile().as_text()


def _flash_calls_in_compiled_train_step(head_dim, abstract=None):
    """Each flash kernel's custom calls in that text, and all custom calls."""
    text = _compiled_train_step_text(head_dim, abstract)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    return {k: sum(k in line for line in calls) for k in ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")}, len(calls)


def _arrays_narrower_than_a_head(text, head_dim, rotary_dim):
    """Shapes in a compiled program whose minor dimension is a half of the
    rotated lanes or the pass-through lanes of partial rotary."""
    import re

    narrow = "|".join(str(n) for n in (rotary_dim // 2, head_dim - rotary_dim))
    return sorted(set(re.findall(rf"\b(?:f32|bf16)\[(?:\d+,)+(?:{narrow})\]", text)))


@pytest.mark.parametrize("head_dim,rotary_dim", [(64, 16), (128, 32)])
def test_train_step_of_partial_rotary_holds_nothing_narrower_than_a_head(head_dim, rotary_dim):
    """Pythia-410m rotates 16 of 64 head dims, Pythia-1.4b 32 of 128. Until
    PR 32 ``apply_rope`` sliced them off, split them into halves of 8 (16) and
    concatenated twice, and the compiled step held float32 ``[2, 2048, 16, 8]``
    arrays in 128-lane tiles, a sixth of the 410M step. At the full width of
    the head no array has a minor dimension of ``r/2`` or ``d - r``: the
    tables are made ``[S, d]`` and the partner is a matmul."""
    text = _compiled_train_step_text(head_dim, rotary_dim=rotary_dim)
    found = _arrays_narrower_than_a_head(text, head_dim, rotary_dim)
    print(f"heads of {head_dim}, {rotary_dim} rotated: {found}")
    assert "tpu_custom_call" in text and not found, found


@pytest.mark.parametrize("head_dim", [64, 128])
def test_remat_train_step_holds_one_flash_fwd_per_layer_body(head_dim):
    """Under ``save_only_these_names(attn_out)`` the kernel's output and its
    log-sum-exp cross the remat boundary, so the compiled step of the scanned
    block holds ONE ``flash_fwd`` (the forward scan's) beside the two backward
    kernels: three Mosaic calls where PR 21 counted four. The name is given
    inside a ``custom_vjp`` forward rule under ``jit``; only the compiled
    program says whether the policy saw it."""
    calls, n = _flash_calls_in_compiled_train_step(head_dim)
    print(f"heads of {head_dim}: {calls}, {n} x tpu_custom_call")
    assert calls == {"flash_fwd": 1, "flash_bwd_dkdv": 1, "flash_bwd_dq": 1} and n == 3, (calls, n)


def _latent_reference_by_run(q, pool, tables, rows, bs, dv, scale):
    """Float32 attention of ragged rows ``(context_before, new_tokens)`` over a
    latent pool ``[slots, 1, W]``: every head against the same entries, the
    value their first ``dv`` lanes; one sequence and 256 queries at a time."""
    outs, t0 = [], 0
    for r, (before, new) in enumerate(rows):
        n_ctx = -(-(before + new) // bs)
        slots = (tables[r][:n_ctx, None] * bs + jnp.arange(bs)[None, :]).reshape(-1)
        k = pool[slots, 0].astype(jnp.float32)
        ctx = jnp.arange(k.shape[0])[None, :]
        for c0 in range(0, new, 256):
            c1 = min(new, c0 + 256)
            p = jnp.arange(before + c0, before + c1)[:, None]
            s = jnp.einsum("thd,cd->thc", q[t0 + c0:t0 + c1].astype(jnp.float32) * scale, k, precision="highest")
            w = jax.nn.softmax(jnp.where((ctx <= p)[:, None, :], s, -1e30), axis=-1)
            outs.append(jnp.einsum("thc,cd->thd", w, k[:, :dv], precision="highest"))
        t0 += new
    return jnp.concatenate(outs, 0)


@pytest.mark.parametrize("name,T,S,rows,kernel", [
    # glm-4.7-flash.longdoc: a 2,048-token chunk at 30k of history
    ("chunk_at_30k", 2048, 8, [(30000, 2048)], "paged_attn_q_tiled"),
    # the same bucket as the closed loop fills it: a chunk behind seven riding decode rows
    ("chunk_mixed", 2048, 8, [(17000 + 2000 * i, 1) for i in range(7)] + [(12288, 2041)], "paged_attn_q_tiled"),
    # 8 decode rows at 17k-31k
    ("decode_8_rows", 8, 8, [(17109 + 2038 * i, 1) for i in range(8)], "paged_attn_kv_split"),
])
def test_paged_kernels_over_a_latent_pool_at_the_cells_shapes_on_chip(name, T, S, rows, kernel):
    """Both paged kernels on a LATENT pool at ``glm-4.7-flash.longdoc``'s
    shapes (20 heads against one 640-wide entry a token, the value its first
    512 lanes, 128-token blocks, tables 257 wide, bf16) through
    ``paged_attention`` as the engine calls it, against a float32 reference.
    Prints the microseconds a call."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    W, dv, nq, bs, mb, n_blocks = 640, 512, 20, 128, 257, 2100
    rng = np.random.default_rng(38)
    pool = jnp.asarray(rng.normal(size=(n_blocks * bs, 1, W)), jnp.bfloat16)
    tables = jnp.asarray(np.stack([rng.permutation(n_blocks)[:mb] for _ in range(S)]), jnp.int32)
    seq_idx = np.concatenate([np.full(new, r) for r, (_, new) in enumerate(rows)])
    pos = np.concatenate([np.arange(before, before + new) for before, new in rows])
    n = seq_idx.size
    seq_idx = jnp.asarray(np.pad(seq_idx, (0, T - n)), jnp.int32)
    pos = jnp.asarray(np.pad(pos, (0, T - n)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(T, nq, W)) / 4, jnp.bfloat16)
    scale = 1.0 / 16.0

    pa.KERNEL_CHOICES.pop((T, S, mb), None)
    fn = jax.jit(lambda q, tables, seq_idx, pos: pa.paged_attention(q, pool, None, tables, seq_idx, pos, bs,
                                                                    value_dim=dv, softmax_scale=scale))
    out = fn(q, tables, seq_idx, pos)
    assert out.shape == (T, nq, dv)
    choice = pa.kernel_choice(T, S, mb)
    assert choice["kernel"] == kernel, choice
    us = _us_a_call(fn, q, tables, seq_idx, pos, calls=10)
    pairs = sum(new * before + new * (new + 1) // 2 for before, new in rows)
    print(f"\nlatent_pool[{name}]: {us:.0f} us a call, {choice}; absorbed {pairs * nq * 2 * (W + dv) / us / 1e6:.1f} "
          f"TFLOP/s, entries read {sum(b + n_ for b, n_ in rows) * W * 2 / us / 1e3:.1f} GB/s")
    ref = np.asarray(_latent_reference_by_run(q, pool, tables, rows, bs, dv, scale), np.float32)
    got = np.asarray(out[:n], np.float32)
    np.testing.assert_allclose(got, ref, atol=5e-2, rtol=5e-2)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-3


@pytest.mark.parametrize("q_tile", [128, 256, 512, None])
@pytest.mark.parametrize("before", [12288, 30000])
def test_the_expanded_call_over_pools_by_head_at_the_cells_shapes_on_chip(before, q_tile):
    """Latent attention's EXPANDED call at ``glm-4.7-flash.longdoc``'s shapes
    (PR 40): a 2,048-token chunk behind ``before`` tokens in workspace slot 1
    of two, seven decode rows' tokens beside it at position -1 (they see
    nothing and cost no grid step), 20 heads of 256 over pools BY HEAD
    ``[20, 2 x 257, 128, 256]`` in bf16, ``paged_attn_q_tiled`` at each tile
    the rule can give and, ``q_tile`` None, through ``paged_attention`` as the
    model calls it: the rule names the largest, 512, and its working set and
    half again is under the limit the kernel asks the compiler for. Against a
    float32 reference; prints the microseconds a call."""
    from deepspeed_tpu.inference.v2.model_implementations.flat_model import expanded_batch
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    nq, d, bs, cols, rows, T, chunk = 20, 256, 128, 257, 2, 2048, 2041
    rng = np.random.default_rng(40)
    n_live = -(-(before + chunk) // bs)
    k_ws, v_ws = (jnp.asarray(rng.normal(size=(nq, rows * cols, bs, d)), jnp.bfloat16) for _ in range(2))
    tables = jnp.pad(jnp.arange(rows * cols, dtype=jnp.int32).reshape(rows, cols), ((0, rows + 1), (0, 0)))
    slot_of_tok = np.asarray([-1] * 7 + [1] * chunk, np.int32)
    pos = np.concatenate([17000 + 2000 * np.arange(7), np.arange(before, before + chunk)]).astype(np.int32)
    seq_idx, x_pos = (jnp.asarray(a) for a in expanded_batch(slot_of_tok, pos, rows, xp=np))
    assert np.asarray(seq_idx).tolist() == [2] * 7 + [1] * chunk and (np.asarray(x_pos)[:7] == -1).all()
    q = jnp.asarray(rng.normal(size=(T, nq, d)) / 4, jnp.bfloat16)
    scale = 1.0 / 16.0
    if q_tile is None:
        pa.KERNEL_CHOICES.pop((T, 2 * rows + 1, cols), None)
        fn = jax.jit(lambda q, k, v, seq_idx, pos: pa.paged_attention(q, k, v, tables, seq_idx, pos, bs, softmax_scale=scale))
    else:
        fn = jax.jit(lambda q, k, v, seq_idx, pos: pa._pallas_paged(q, k, v, tables, seq_idx, pos, block_size=bs,
                                                                    q_tile=q_tile, softmax_scale=scale))
    out = fn(q, k_ws, v_ws, seq_idx, x_pos)
    if q_tile is None:
        choice = pa.kernel_choice(T, 2 * rows + 1, cols)
        assert choice == {"kernel": "paged_attn_q_tiled", "q_tile": 512, "blocks_per_step": 1,
                          "rule": "heuristic:long_rows_one_head"}, choice
        need = pa._q_tiled_vmem_bytes(nq * 512, 512, d, bs, nq, 2, 2)
        assert need * 3 // 2 <= pa._Q_TILED_VMEM_LIMIT < pa._q_tiled_vmem_bytes(nq * 1024, 1024, d, bs, nq, 2, 2) * 3 // 2
    us = _us_a_call(fn, q, k_ws, v_ws, seq_idx, x_pos, calls=10)
    pairs = chunk * before + chunk * (chunk + 1) // 2
    print(f"\nexpanded_call[before={before}, q_tile={q_tile or 'the rule: 512'}]: {us:.0f} us a call, "
          f"{pairs * nq * 2 * 2 * d / us / 1e6:.1f} TFLOP/s")
    # the float32 reference reads a token-major pool: slot 1's blocks of every head, a token a row
    as_pool = lambda ws: jnp.moveaxis(ws[:, cols:cols + n_live], 0, 2).reshape(n_live * bs, nq, d)
    ref = _paged_reference_by_run(q[7:] * (np.sqrt(d) * scale), as_pool(k_ws), as_pool(v_ws),
                                  jnp.arange(n_live, dtype=jnp.int32)[None], [(before, chunk)], bs, None)
    got, ref = np.asarray(out[7:7 + chunk], np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(got, ref, atol=5e-2, rtol=5e-2)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) < 5e-3


# tokens a row of the ragged forms, 128 rows in a 512-token program
_DELTA_RULE_BATCHES = {"chunk_scan": [401] + [0] * 127, "mixed-401+100x1": [401] + [1] * 100 + [0] * 27,
                       "mixed-127x1+385": [1] * 127 + [385]}


@pytest.mark.parametrize("form", ["recurrent_step", *_DELTA_RULE_BATCHES])
def test_the_delta_rules_kernels_at_the_cells_shapes_on_chip(form):
    """``ops/pallas/kda.py`` at ``solar-open2-250b.decode-heavy-128``'s widths (64
    heads of 128 x 128, float32 state): the recurrent step of 128 one-token
    rows (``kda_step``), and ``kda_chunks`` over a 401-token chunk alone (the
    chunk scan's 51 tiles and no step row), over that chunk beside 100
    one-token rows (the shape PR 41 timed at 7,123 us a call, when each of
    those rows was a tile of the chunk scan; they go through the recurrent
    step since PR 44) and over 127 one-token rows BEFORE a 385-token chunk (a
    full house as the window has it), in a 512-token program. Each against the
    rule token by token from the same pool, with microseconds a call and the
    state's bytes over them."""
    from deepspeed_tpu.ops.pallas import kda

    H, d, slots = 64, 128, 160
    rng = np.random.default_rng(0)
    T, n_tok = (128, np.ones(128, np.int64)) if form == "recurrent_step" else (512, np.asarray(_DELTA_RULE_BATCHES[form]))
    q, k = rng.normal(size=(2, T, H, d))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(-4, 1.5, size=(T, H, d)))
    b = 2 / (1 + np.exp(-rng.normal(size=(T, H))))
    x = [jnp.asarray(a, jnp.float32) for a in (q, k, rng.normal(size=(T, H, d)), g, b)]
    pool = jnp.asarray(rng.normal(size=(slots, H, d, d)), jnp.float32)
    slot = jnp.asarray(rng.permutation(slots)[:128], jnp.int32)
    fresh = jnp.asarray(rng.integers(0, 2, size=128), jnp.int32)
    # the batch as ARGUMENTS, as the engine hands it over: closed over, XLA folds the plan into constants
    if form == "recurrent_step":
        fn = lambda q, k, v, g, b, pool, slot, fresh, n: kda.kda_step(q, k, v, g, b, pool, slot, fresh, jnp.sum(n),
                                                                      use_pallas=True)
    else:
        fn = lambda q, k, v, g, b, pool, slot, fresh, n: kda.kda_chunks(q, k, v, g, b, pool, slot, fresh, n,
                                                                        use_pallas=True)
    batch = (slot, fresh, jnp.asarray(n_tok, jnp.int32))
    o, new = jax.jit(fn)(*x, pool, *batch)
    in_place = jax.jit(fn, donate_argnums=5)  # as the engine calls it: the pool donated and advanced where it lies
    _, carried = in_place(*x, pool + 0.0, *batch)
    jax.block_until_ready(carried)
    t0 = time.perf_counter()
    for _ in range(10):
        _, carried = in_place(*x, carried, *batch)
    jax.block_until_ready(carried)
    us = (time.perf_counter() - t0) / 10 * 1e6
    rows = int((n_tok > 0).sum())
    print(f"\nkda[{form}]: {us:.0f} us a call, {rows} rows' state read and written at "
          f"{rows * 2 * H * d * d * 4 / us / 1e3:.0f} GB/s")
    t0, untouched = 0, np.ones(slots, bool)
    for r, n in enumerate(n_tok):
        if n:
            S0 = jnp.zeros((H, d, d)) if int(fresh[r]) else pool[slot[r]]
            if r < 3 or r % 37 == 0 or n > 1:  # the long row and a few of the others, token by token
                oo, S = kda.recurrence_reference(*[a[t0:t0 + n] for a in x], S0)
                assert float(jnp.abs(oo - o[t0:t0 + n]).max()) < 1e-4
                assert float(jnp.abs(S - new[slot[r]]).max()) < 1e-4
            untouched[int(slot[r])] = False
            t0 += n
    assert np.array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])


_SELECTIVE_SCAN_BATCHES = {
    # a 770-token chunk in the middle of 254 one-token rows: a mixed put of the window, a full house
    "chunk_among_254_rows": [1] * 127 + [770] + [1] * 127 + [0],
    # three chunks that cross the scan's blocks of tiles, no one-token row
    "three_chunks": [401, 130, 493] + [0] * 253,
}


# a decode horizon's full house, and a ``put``'s few one-token rows: fewer grid steps to hide a call's start under
_RECURRENT_STEP_ROWS = {"recurrent_step": 256, "recurrent_step_64_rows": 64, "recurrent_step_17_rows": 17}


@pytest.mark.parametrize("form", [*_RECURRENT_STEP_ROWS, *_SELECTIVE_SCAN_BATCHES])
def test_the_selective_scans_kernels_at_the_cells_shapes_on_chip(form):
    """``ops/pallas/mamba2.py`` at ``nemotron-3-nano-30b-a3b.decode-heavy-256``'s
    widths (64 heads of 64 x 128 in 8 groups, float32 state): the recurrent
    step of 256, 64 and 17 one-token rows (``mamba2_step``), and
    ``mamba2_chunks`` over a 770-token chunk among 254 one-token rows and over
    three chunks alone, in a 1,024-token program. Each against the rule token
    by token from the same pool, with microseconds a call and the state's
    bytes over them."""
    from deepspeed_tpu.ops.pallas import mamba2

    H, G, P, N, slots = 64, 8, 64, 128, 300
    R = _RECURRENT_STEP_ROWS.get(form, 256)
    rng = np.random.default_rng(0)
    T, n_tok = (R, np.ones(R, np.int64)) if form in _RECURRENT_STEP_ROWS else (1024, np.asarray(_SELECTIVE_SCAN_BATCHES[form]))
    x = [jnp.asarray(a, jnp.float32) for a in (rng.normal(size=(T, H, P)), rng.normal(size=(T, G, N)),
                                               rng.normal(size=(T, G, N)), np.exp(rng.uniform(np.log(3e-4), np.log(0.3), (T, H))))]
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(slots, H, P, N)), jnp.float32)
    slot = jnp.asarray(rng.permutation(slots)[:R], jnp.int32)
    fresh = jnp.asarray(rng.integers(0, 2, size=R), jnp.int32)
    # the batch as ARGUMENTS, as the engine hands it over: closed over, XLA folds the plan into constants
    if form in _RECURRENT_STEP_ROWS:
        fn = lambda x, B, C, dt, pool, slot, fresh, n: mamba2.mamba2_step(x, B, C, dt, A, pool, slot, fresh, jnp.sum(n),
                                                                          use_pallas=True)
    else:
        fn = lambda x, B, C, dt, pool, slot, fresh, n: mamba2.mamba2_chunks(x, B, C, dt, A, pool, slot, fresh, n,
                                                                            use_pallas=True)
    batch = (slot, fresh, jnp.asarray(n_tok, jnp.int32))
    y, new = jax.jit(fn)(*x, pool, *batch)
    in_place = jax.jit(fn, donate_argnums=4)  # as the engine calls it: the pool donated and advanced where it lies
    _, carried = in_place(*x, pool + 0.0, *batch)
    jax.block_until_ready(carried)
    t0 = time.perf_counter()
    for _ in range(10):
        _, carried = in_place(*x, carried, *batch)
    jax.block_until_ready(carried)
    us = (time.perf_counter() - t0) / 10 * 1e6
    rows = int((n_tok > 0).sum())
    print(f"\nmamba2[{form}]: {us:.0f} us a call, {rows} rows' state read and written at "
          f"{rows * 2 * H * P * N * 4 / us / 1e3:.0f} GB/s")
    t0, untouched = 0, np.ones(slots, bool)
    for r, n in enumerate(n_tok):
        if n:
            S0 = jnp.zeros((H, P, N)) if int(fresh[r]) else pool[slot[r]]
            if r < 3 or r % 37 == 0 or n > 1:  # the long rows and a few of the others, token by token
                yy, S = mamba2.recurrence_reference(*[a[t0:t0 + n] for a in x], A, S0)
                assert float(jnp.abs(yy - y[t0:t0 + n]).max()) < 2e-4
                assert float(jnp.abs(S - new[slot[r]]).max()) < 2e-4
            untouched[int(slot[r])] = False
            t0 += n
    assert np.array_equal(np.asarray(new)[untouched], np.asarray(pool)[untouched])


@pytest.mark.parametrize("name,T,S,rows,kernel", [
    # minicpm-sala.longctx: a 2,048-token chunk at 30k of history behind seven riding decode rows
    ("chunk_mixed", 2048, 8, [(34000 + 4000 * i, 1) for i in range(7)] + [(30000, 2041)], "paged_attn_q_tiled"),
    # 8 decode rows at 34k-62k
    ("decode_8_rows", 8, 8, [(34219 + 4000 * i, 1) for i in range(8)], "paged_attn_kv_split"),
])
@pytest.mark.parametrize("picked", ["selection", "all_true"])
def test_paged_kernels_under_a_selection_at_the_cells_shapes_on_chip(name, T, S, rows, kernel, picked):
    """Both paged kernels under a learned block selection at
    ``minicpm-sala.longctx``'s shapes (32 query / 2 KV heads of 128, 64-token
    blocks, tables 1,034 wide, bf16): 64 blocks a token a KV head (block 0, the
    last 33 and 30 drawn at random, each token its own draw) through
    ``paged_attention`` as the engine calls it, against the gather reference
    on the same selection; under an all-true selection bit-equal to the call
    without one. Prints the microseconds a call, and for the tiled kernel the
    same at ONE block a grid step beside the rule's two, a step and a key."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    nq, nkv, d, bs, mb, n_blocks = 32, 2, 128, 64, 1034, 8300
    rng = np.random.default_rng(47)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(n_blocks * bs, nkv, d)), jnp.bfloat16) for _ in range(2))
    tables = jnp.asarray(np.stack([rng.permutation(n_blocks)[:mb] for _ in range(S)]), jnp.int32)
    seq_idx = np.concatenate([np.full(new, r) for r, (_, new) in enumerate(rows)])
    pos = np.concatenate([np.arange(before, before + new) for before, new in rows])
    n = seq_idx.size
    own = pos // bs
    sel = np.zeros((T, nkv, mb), bool)
    if picked == "all_true":
        sel[:] = True
    else:
        for t in range(n):
            for h in range(nkv):
                sel[t, h, rng.choice(max(own[t] - 33, 1), size=30, replace=False)] = True
            sel[t, :, max(own[t] - 32, 0):own[t] + 1] = True
        sel[:, :, 0] = True
    seq_idx = jnp.asarray(np.pad(seq_idx, (0, T - n)), jnp.int32)
    pos = jnp.asarray(np.pad(pos, (0, T - n)), jnp.int32)
    sel = jnp.asarray(sel)
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)
    pa.KERNEL_CHOICES.pop((T, S, mb), None)
    fn = jax.jit(lambda q, tables, seq_idx, pos, sel: pa.paged_attention(q, k_pool, v_pool, tables, seq_idx, pos, bs,
                                                                         selection=sel))
    out, read = fn(q, tables, seq_idx, pos, sel)
    choice = pa.kernel_choice(T, S, mb)
    assert choice["kernel"] == kernel, choice
    us = _us_a_call(fn, q, tables, seq_idx, pos, sel, calls=10)
    visible = int(np.sum(np.asarray(pos) // bs + 1))
    pairs, items, steps = (int(c) for c in read)
    print(f"\nselection[{name}, {picked}]: {us:.0f} us a call, {choice}, served {pairs} of {visible} (token, column) pairs")
    one = None
    if kernel == "paged_attn_q_tiled":
        # the same call at ONE block a grid step, through the kernel's own test-only argument: the before and the
        # after from one tree on one chip (the parent's tree read 52.4 ms under this selection, 56.5 under the
        # all-true one and 45.6 without one: my chip runs, PR 47)
        assert choice["blocks_per_step"] == 2 and 0 < steps <= items <= 2 * steps
        tiled = lambda per, sel_too=True: jax.jit(lambda q, tables, seq_idx, pos, sel: pa._pallas_paged(
            q, k_pool, v_pool, tables, seq_idx, pos, block_size=bs, q_tile=choice["q_tile"], blocks_per_step=per,
            selection=sel if sel_too else None))
        one_fn = tiled(1)
        one, read_one = one_fn(q, tables, seq_idx, pos, sel)
        assert [int(c) for c in read_one] == [pairs, items, items]
        us_one = _us_a_call(one_fn, q, tables, seq_idx, pos, sel, calls=10)
        print(f"  two blocks a grid step: {us:.0f} us a call, {steps} steps for {items} (tile, column) pairs "
              f"({items / steps:.3f} a step), {us / steps:.2f} us a step, {us * 1e3 / (items * bs):.2f} ns a (tile, key)\n"
              f"  one block a grid step: {us_one:.0f} us a call, {items} steps, {us_one / items:.2f} us a step, "
              f"{us_one * 1e3 / (items * bs):.2f} ns a (tile, key): a two-block step costs {us / steps / (us_one / items):.2f} "
              f"of a one-block step")
    if picked == "all_true":
        assert pairs == visible
        plain = jax.jit(lambda q, tables, seq_idx, pos: pa.paged_attention(q, k_pool, v_pool, tables, seq_idx, pos, bs))
        assert (np.asarray(out[:n]) == np.asarray(plain(q, tables, seq_idx, pos)[:n])).all()
        print(f"  without a selection: {_us_a_call(plain, q, tables, seq_idx, pos, calls=10):.0f} us a call")
        if one is not None:
            us_plain_one = _us_a_call(tiled(1, False), q, tables, seq_idx, pos, sel, calls=10)
            print(f"  without a selection, one block a grid step: {us_plain_one:.0f} us a call")
        return
    if one is not None:  # the same keys under the same masks: rounding apart (one partial maximum a 128 keys, not a 64)
        a, b = np.asarray(out[:n], np.float32), np.asarray(one[:n], np.float32)
        assert np.linalg.norm(a - b) / np.linalg.norm(b) < 1e-2
    # the gather reference a token block at a time: [tokens, heads, 66k keys] float32 does not fit at once
    ref = jax.jit(lambda q, seq_idx, pos, sel: pa.paged_attention_reference(q, k_pool, v_pool, tables, seq_idx, pos, bs,
                                                                            selection=sel))
    step = 32
    for t0 in list(range(0, min(n, 8), step)) + [max(n - step, 0)]:
        cut = slice(t0, min(t0 + step, n))
        want = np.asarray(ref(q[cut], seq_idx[cut], pos[cut], sel[cut])[0], np.float32)
        got = np.asarray(out[cut], np.float32)
        assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-2


@pytest.mark.parametrize("context", [9000, 30000, 62757])
def test_the_selection_indexers_kernel_at_the_cells_shapes_on_chip(context):
    """``sparse_index_scores`` against the XLA form (``sparse_index.block_scores``,
    three tiles a pass) at ``minicpm-sala.longctx``'s shapes: a 2,041-token
    chunk that ends at ``context`` behind seven riding one-token rows, tiles of
    128, 32 query / 2 KV heads of 128, 64-token blocks, pooled keys at stride
    16 over a table 1,034 wide (4,136 a row), bf16. The relative L2 of the
    block scores over the tiles that have an item, and the share of (token, kv
    head, block) selections that are equal; prints both forms' microseconds a
    call (gathers, work list and epilogue included; the selection is no part
    of either)."""
    import types

    from deepspeed_tpu.inference.v2.model_implementations import sparse_index as si

    cfg = types.SimpleNamespace(sparse_kernel_size=32, sparse_kernel_stride=16, sparse_topk=64, sparse_init_blocks=1,
                                sparse_window_size=2048, sparse_dense_len=8192)
    T, S, nq, nkv, d, bs, mb, n_blocks = 2048, 8, 32, 2, 128, 64, 1034, 8300
    rows = [(34000 + 4000 * i, 1) for i in range(7)] + [(context - 2041, 2041)]
    rng = np.random.default_rng(54)
    p_flat = jnp.asarray(rng.normal(size=(n_blocks * bs // 16, nkv, d)), jnp.bfloat16)
    tables = jnp.asarray(np.stack([rng.permutation(n_blocks)[:mb] for _ in range(S)]), jnp.int32)
    seq_idx = np.concatenate([np.full(new, r) for r, (_, new) in enumerate(rows)])
    pos = np.concatenate([np.arange(before, before + new) for before, new in rows])
    n = seq_idx.size
    valid = jnp.asarray(np.arange(T) < n)
    seq_idx, pos = jnp.asarray(np.pad(seq_idx, (0, T - n)), jnp.int32), jnp.asarray(np.pad(pos, (0, T - n)), jnp.int32)
    q = jnp.asarray(rng.normal(size=(T, nq, d)), jnp.bfloat16)

    def form(use_pallas):
        def scores(q, p_flat, tables, seq_idx, pos, valid):
            r, (tile_id, place, _, tile_pos) = si.tile_scores(cfg, bs, q, p_flat, tables, seq_idx, pos, valid, use_pallas)
            return r, tile_id, place, tile_pos
        return jax.jit(scores)

    args = (q, p_flat, tables, seq_idx, pos, valid)
    (got, tile_id, place, tile_pos), (want, *_) = form(True)(*args), form(False)(*args)
    us = {name: _us_a_call(form(use_pallas), *args, calls=10) for name, use_pallas in (("kernel", True), ("xla", False))}
    scored = si.keys_scored([b for b, _ in rows], [m for _, m in rows], mb, 128, bs, 16, 32, cfg.sparse_dense_len)
    asked = sum((p - 31) // 16 + 1 for p in np.asarray(pos[:n]) if p + 1 > cfg.sparse_dense_len)
    print(f"\nsparse_index_scores[chunk to {context}]: kernel {us['kernel']:.0f} us a call, XLA form {us['xla']:.0f} us a call; "
          f"{asked} (token, pooled key) pairs asked of {scored} scored a kv head, the rectangle {(T // 128 + S + 1) * 128 * mb * 4}")
    got, want = np.asarray(got), np.asarray(want)
    # the tiles with an item: every one that holds a token past dense_len (a dense tile reads 0 from the kernel)
    has_item = np.asarray(tile_pos).max(axis=1) + 1 > cfg.sparse_dense_len
    assert has_item.sum() >= 8 + (context - 2041 > cfg.sparse_dense_len) * 15
    rel = np.linalg.norm(got[has_item] - want[has_item]) / np.linalg.norm(want[has_item])
    pick = jax.jit(lambda r, at: jax.lax.map(lambda a: si.selection_of(cfg, bs, *a), (r, at), batch_size=3))
    a, b = (np.asarray(pick(jnp.asarray(r), tile_pos)) for r in (got, want))
    at = (np.asarray(tile_id) * 128 + np.asarray(place))[:n]
    a, b = a.reshape(-1, nkv, mb)[at], b.reshape(-1, nkv, mb)[at]
    same = float((a == b).mean())
    print(f"  relative L2 of the block scores {rel:.2e}; equal selections {100 * same:.4f}% of {a.size}, "
          f"{int((a != b).any(axis=(1, 2)).sum())} of {n} tokens differ somewhere")
    assert rel < 1e-3 and same > 0.999


@pytest.mark.parametrize("form", ["recurrent_step", "chunk_mixed"])
def test_the_lightning_kernels_at_the_cells_shapes_on_chip(form):
    """``lightning_recurrent_step`` over 8 rows and ``lightning_chunk_scan``
    over a 2,041-token chunk behind seven one-token rows, 32 heads of 128 x
    128, against the recurrence token by token. Prints the microseconds a call."""
    from deepspeed_tpu.ops.pallas import lightning

    H, d, slots = 32, 128, 96
    rng = np.random.default_rng(47)
    n_tok = np.ones(8, np.int32) if form == "recurrent_step" else np.asarray([1] * 7 + [2041], np.int32)
    T = 8 if form == "recurrent_step" else 2048
    q, k, v = (jnp.asarray(rng.normal(size=(T, H, d)) / 4, jnp.float32) for _ in range(3))
    slope = jnp.asarray(2.0 ** (-8.0 * (np.arange(H) + 1) / H) * 0.68, jnp.float32)
    pool = jnp.asarray(rng.normal(size=(slots, H, d, d)), jnp.float32)
    slot = jnp.asarray(rng.permutation(slots)[:8], jnp.int32)
    fresh = jnp.asarray([0, 1, 0, 0, 0, 0, 0, 1], jnp.int32)
    if form == "recurrent_step":
        fn = jax.jit(lambda q, k, v, pool: lightning.lightning_step(q, k, v, slope, pool, slot, fresh, 8, use_pallas=True))
    else:
        fn = jax.jit(lambda q, k, v, pool: lightning.lightning_chunks(q, k, v, slope, pool, slot, fresh, jnp.asarray(n_tok),
                                                                     use_pallas=True))
    o, new_pool = fn(q, k, v, pool)
    print(f"\nlightning[{form}]: {_us_a_call(fn, q, k, v, pool, calls=10):.0f} us a call")
    start = 0
    with jax.default_matmul_precision("highest"):
        for r, n in enumerate(n_tok):
            S0 = jnp.zeros((H, d, d)) if int(fresh[r]) else pool[slot[r]]
            want_o, want_S = lightning.recurrence_reference(q[start:start + n], k[start:start + n], v[start:start + n], slope, S0)
            assert float(jnp.linalg.norm(o[start:start + n] - want_o) / jnp.linalg.norm(want_o)) < 1e-3
            assert float(jnp.linalg.norm(new_pool[slot[r]] - want_S) / jnp.linalg.norm(want_S)) < 1e-3
            start += int(n)


def test_a_block_diffusion_call_whose_commits_ride_at_the_cells_shapes_on_chip():
    """``chip_smoke.diffusion_phase`` as a test (`-s` to read its times): at
    ``sdar-30b-a3b-chat.block-diffusion-64``'s shapes (64 rows, blocks of 4, 8
    layers of 128 experts, contexts of 1,024) a ``decode`` call of four blocks,
    three of whose commits ride in the next block's first denoise forward,
    gives the tokens of the same call written out as four denoise forwards and
    a ``kv_only`` commit a block; a denoise forward, one that carries a commit
    and the commit are timed alone (PERF.md section 6, PR 50, has the readings:
    the call is worth its while as long as the second costs under 1.3 x the
    first)."""
    import chip_smoke

    smoke = chip_smoke.Smoke(rehearsal=False)
    chip_smoke.diffusion_phase(smoke)
    ms = smoke.record["diffusion_ms"]
    assert 0 < ms["commit"] < ms["denoise"] < ms["fused"] < 2 * ms["denoise"], ms
