"""Which of the flash kernel's values cross a remat boundary.

``_flash_core_fwd`` names the kernel's two outputs, ``out`` and the per-row
log-sum-exp, ``attn_out``. Under ``save_only_these_names(attn_out)`` the
backward of a ``jax.checkpoint``ed block therefore starts ``flash_bwd_dkdv``
and ``flash_bwd_dq`` from the saved pair; under ``nothing_saveable`` the name
is inert and the rematerialised forward runs the kernel a second time, as it
always did. The kernel runs through the Pallas interpreter: the jaxpr, not
the CPU, is what is read here.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import flash_attention as fa

SAVE = "save_only_these_names(attn_out)"
B, S, NQ, D = 2, 256, 4, 64


def _core(q, k, v, window=None):
    """The kernel's ``custom_vjp``: causal, 128 tiles, interpreted."""
    return fa._flash_core(True, 128, 128, True, window, False, q, k, v)


def _qkv(nkv=NQ, layers=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lead = () if layers is None else (layers, )
    return tuple(jax.random.normal(kk, lead + (B, S, n, D), jnp.float32)
                 for kk, n in zip(ks, (NQ, nkv, nkv)))


def _loss(policy, attn):
    """Sum of a checkpointed attention whose q is projected INSIDE the
    boundary, as the model's block projects it: the backward needs q, so the
    recompute is never dead code."""
    @functools.partial(jax.checkpoint, policy=T._remat_policy(policy))
    def f(q, k, v):
        return jnp.sum(attn(q * 1.5, k, v) ** 2)

    return f


def _scanned_loss(policy):
    body = _loss(policy, _core)

    def f(q, k, v):  # [L, B, S, n, D]: one checkpointed body over two layers
        return lax.scan(lambda c, x: (c + body(*x), None), jnp.float32(0.0), (q, k, v))[0]

    return f


def _sharded_core(q, k, v):
    mesh = Mesh(np.array(jax.devices()[:2]), ("data", ))
    spec = P("data", None, None, None)
    # as _attention wraps the kernel on a multi-device mesh: shard_map over the jitted call
    return jax.shard_map(jax.jit(_core), mesh=mesh, in_specs=(spec, ) * 3, out_specs=spec,
                         check_vma=False)(q, k, v)


CASES = {
    "causal": (_core, {}),
    "gqa": (_core, {"nkv": 2}),
    "window": (functools.partial(_core, window=96), {}),
    "jit": (jax.jit(_core), {}),
    "shard_map": (_sharded_core, {}),
    "scan": (None, {"layers": 2}),
}


def _kernel_calls(fn, args):
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(*args))
    return {k: text.count(f"name={k}") for k in ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_backward_starts_from_the_saved_pair_only_under_the_policy_that_names_it(case):
    attn, shape = CASES[case]
    args = _qkv(**shape)
    build = _scanned_loss if attn is None else functools.partial(_loss, attn=attn)
    assert _kernel_calls(build(SAVE), args) == {"flash_fwd": 1, "flash_bwd_dkdv": 1, "flash_bwd_dq": 1}
    # a policy that names nothing: the program it always was, second forward included
    assert _kernel_calls(build("nothing_saveable"), args) == {
        "flash_fwd": 2, "flash_bwd_dkdv": 1, "flash_bwd_dq": 1}


def test_the_gradients_are_the_same_bits_under_both_policies():
    args = _qkv(nkv=2, seed=3)
    saved = jax.jit(jax.grad(_loss(SAVE, _core), argnums=(0, 1, 2)))(*args)
    recomputed = jax.jit(jax.grad(_loss("nothing_saveable", _core), argnums=(0, 1, 2)))(*args)
    for a, b in zip(saved, recomputed):
        assert np.isfinite(np.asarray(a)).all() and np.abs(np.asarray(a)).max() > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _block_residuals(monkeypatch, policy, kernel=False, d=D, **cfg_kw):
    """What the model's own checkpointed ``_block`` saves for its backward:
    the shapes of at least ``B * S`` elements that are neither layer weights
    nor constants, and the kernel calls of its gradient. ``kernel`` forces
    ``flash_attention`` onto the Pallas kernel, interpreted."""
    if kernel:
        monkeypatch.setattr(fa, "_use_pallas", lambda: True)
        monkeypatch.setattr(fa, "_pallas_flash", functools.partial(fa._pallas_flash, interpret=True))
    cfg = T.TransformerConfig(vocab_size=128, hidden_size=NQ * d, num_layers=1, num_heads=NQ,
                              num_kv_heads=NQ, intermediate_size=2 * NQ * d, max_seq_len=S,
                              dtype=jnp.float32, remat=True, remat_policy=policy, **cfg_kw)
    layer = jax.tree_util.tree_map(lambda a: a[0], T.init_params(cfg, jax.random.PRNGKey(0))["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.hidden_size), jnp.float32)
    sin, cos = T.rope_table(cfg, jnp.arange(S))
    block = jax.checkpoint(lambda x, layer: jnp.sum(T._block(cfg, x, layer, sin, cos)[0]),
                           policy=T._remat_policy(policy))
    big = sorted(tuple(aval.shape) for aval, why in saved_residuals(block, x, layer)
                 if aval.size >= B * S and "argument layer" not in why and "constant" not in why)
    return big, _kernel_calls(lambda x, wq, wo: block(x, {**layer, "wq": wq, "wo": wo}),
                              (x, layer["wq"], layer["wo"]))


@pytest.mark.parametrize("d", [64, 128])
def test_the_checkpointed_block_saves_its_input_one_attention_output_and_lse(monkeypatch, d):
    big, calls = _block_residuals(monkeypatch, SAVE, kernel=True, d=d, attention_impl="flash")
    # the block input, the kernel's out and lse. out is saved ONCE (the ctx that
    # _attn_branch reshapes from it carries no second name), in the shape it was named in:
    # heads of 128 as they are, narrower ones merged so that no lane of the stack is padding
    out = (B, S, NQ, d) if d == 128 else (B, S, NQ * d)
    assert big == sorted([(B, S, NQ * d), out, (B, NQ, S)]), big
    assert calls == {"flash_fwd": 1, "flash_bwd_dkdv": 1, "flash_bwd_dq": 1}
    big, calls = _block_residuals(monkeypatch, "nothing_saveable", kernel=True, d=d, attention_impl="flash")
    assert big == [(B, S, NQ * d)] and calls["flash_fwd"] == 2, (big, calls)


PATHS = {
    "reference": dict(attention_impl="reference"),
    "flash_fallback": dict(attention_impl="flash"),  # off the TPU: flash_attention's jnp fallback
    "sparse": dict(attention_impl="reference",
                   sparse_attention={"mode": "fixed", "block": 16, "num_local_blocks": 2,
                                     "attention": "unidirectional"}),
    # over a seq=2 mesh: ring names the exchanged context in _attn_branch, Ulysses' local attention its own
    "ring": dict(attention_impl="reference", sequence_parallel=True, sequence_parallel_impl="ring"),
    "ulysses": dict(attention_impl="reference", sequence_parallel=True),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_paths_without_the_kernel_name_their_own_output_once(monkeypatch, path):
    """``_attn_branch`` no longer names ``ctx``: each attention path names
    what it computed, and the block still saves exactly one attention output
    beside its input."""
    mesh = contextlib.nullcontext()
    if PATHS[path].get("sequence_parallel"):
        from deepspeed_tpu.parallel import groups
        from deepspeed_tpu.parallel.mesh import MeshConfig

        mesh = groups.initialize_mesh(MeshConfig(data=1, seq=2), devices=jax.devices()[:2])
    with mesh:
        big, calls = _block_residuals(monkeypatch, SAVE, **PATHS[path])
    assert big == [(B, S, NQ * D), (B, S, NQ * D)], big
    assert calls["flash_fwd"] == 0
