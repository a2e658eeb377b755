"""Partial rotary at the full width of the head (PR 32).

``rope_table`` gives ``[S, d]`` tables when ``r < d`` and ``apply_rope``
computes ``x * cos + partner(x) * sin`` with the pass-through lanes selected
from ``x``. The oracle is the split form the function had until PR 32, kept
here: slice the first ``r`` lanes, split them into halves, rotate,
concatenate twice. ``r == d`` keeps that form in the program itself.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import TransformerLM, gpt_neox_config
from deepspeed_tpu.models import transformer as T

WIDTHS = [(64, 16), (128, 32), (256, 64), (80, 32), (128, 128)]
DTYPES = [jnp.bfloat16, jnp.float32]
B, S, N = 2, 24, 4


def oracle_tables(cfg, positions, kind=None):
    inv_freq, scale = T.rope_inv_freq(cfg, kind)
    freqs = jnp.einsum("s,f->sf", positions.astype(jnp.float32), jnp.asarray(inv_freq))
    return jnp.sin(freqs) * scale, jnp.cos(freqs) * scale


def oracle_rope(x, sin, cos):
    r = 2 * sin.shape[-1]
    d = x.shape[-1]
    xr = x[..., :r] if r < d else x
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    sinb = sin[None, :, None, :]
    cosb = cos[None, :, None, :]
    rot = jnp.concatenate([x1 * cosb - x2 * sinb, x2 * cosb + x1 * sinb], axis=-1).astype(x.dtype)
    if r < d:
        return jnp.concatenate([rot, x[..., r:]], axis=-1)
    return rot


def config(d, r, **extra):
    return gpt_neox_config("tiny", hidden_size=N * d, num_heads=N, rotary_dim=r, **extra)


def draw(d, dtype, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    shape = (B, S, N, d)
    return (jax.random.normal(k1, shape, jnp.float32).astype(dtype),
            jax.random.normal(k2, shape, jnp.float32).astype(dtype))


def within_one_ulp(got, want):
    """Each element within one unit in the last place of its dtype at the
    magnitude of the wanted value."""
    assert got.dtype == want.dtype and got.shape == want.shape
    g, w = np.asarray(got, np.float64), np.asarray(want, np.float64)
    tiny = float(jnp.finfo(want.dtype).tiny)
    ulp = 2.0**(np.floor(np.log2(np.maximum(np.abs(w), tiny))) - jnp.finfo(want.dtype).nmant)
    worst = np.max(np.abs(g - w) / ulp)
    assert worst <= 1.0, f"{worst} units in the last place"


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: t.__name__)
@pytest.mark.parametrize("d,r", WIDTHS)
def test_forward_matches_the_split_form(d, r, dtype):
    cfg = config(d, r)
    pos = jnp.arange(S)
    x, _ = draw(d, dtype)
    got = T.apply_rope(x, *T.rope_table(cfg, pos), cfg.rotary_dim)
    within_one_ulp(got, oracle_rope(x, *oracle_tables(cfg, pos)))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: t.__name__)
@pytest.mark.parametrize("d,r", WIDTHS)
def test_gradient_matches_the_split_form(d, r, dtype):
    cfg = config(d, r)
    pos = jnp.arange(S)
    x, g = draw(d, dtype, seed=1)
    tables, halves = T.rope_table(cfg, pos), oracle_tables(cfg, pos)

    def through(rope):
        return jax.grad(lambda x: jnp.sum((rope(x) * g).astype(jnp.float32)))(x)

    got = through(lambda x: T.apply_rope(x, *tables, cfg.rotary_dim))
    within_one_ulp(got, through(lambda x: oracle_rope(x, *halves)))


@pytest.mark.parametrize("d,r", WIDTHS)
def test_positions_with_an_offset(d, r):
    """Decode: the positions of a step start at the cache's length."""
    cfg = config(d, r)
    pos = 1000 + jnp.arange(S)
    x, _ = draw(d, jnp.bfloat16, seed=2)
    got = T.apply_rope(x, *T.rope_table(cfg, pos), cfg.rotary_dim)
    want = oracle_rope(x, *oracle_tables(cfg, pos))
    within_one_ulp(got, want)
    first = T.apply_rope(x, *T.rope_table(cfg, jnp.arange(S)), cfg.rotary_dim)
    assert not np.array_equal(np.asarray(got[..., :r], np.float32), np.asarray(first[..., :r], np.float32))
    assert np.array_equal(np.asarray(got[..., r:], np.float32), np.asarray(first[..., r:], np.float32))


@pytest.mark.parametrize("value", [np.inf, -np.inf])
@pytest.mark.parametrize("d,r", [(64, 16), (128, 32), (80, 32)])
def test_an_infinity_in_a_pass_through_lane_stays_an_infinity(d, r, value):
    """``x * 1 + partner * 0`` would make it a NaN; the lanes beyond ``r`` are
    selected from ``x``. The same in the gradient."""
    cfg = config(d, r)
    x, g = draw(d, jnp.bfloat16, seed=3)
    x = x.at[0, 3, 1, r].set(value).at[1, 5, 2, d - 1].set(value)
    g = g.at[0, 3, 1, r].set(value)
    tables = T.rope_table(cfg, jnp.arange(S))
    y, vjp = jax.vjp(lambda x: T.apply_rope(x, *tables, cfg.rotary_dim), x)
    (dx, ) = vjp(g)
    for out, src in ((y, x), (dx, g)):
        assert np.array_equal(np.asarray(out[..., r:], np.float32), np.asarray(src[..., r:], np.float32))
    assert float(y[0, 3, 1, r]) == value and float(y[1, 5, 2, d - 1]) == value and float(dx[0, 3, 1, r]) == value
    # heads without one are untouched by it
    clean = T.apply_rope(x.at[0, 3, 1, r].set(0.0).at[1, 5, 2, d - 1].set(0.0), *tables, cfg.rotary_dim)
    assert np.array_equal(np.asarray(y[0, 3, 0], np.float32), np.asarray(clean[0, 3, 0], np.float32))
    assert np.isfinite(np.asarray(y[:, :, 3], np.float32)).all()


@pytest.mark.parametrize("d,r", [(128, 128), (64, 16)], ids=["the_whole_head_rotates", "part_of_it"])
def test_an_infinity_in_a_rotated_lane_as_accepted(d, r):
    """What an infinity in a ROTATED lane does, pinned as accepted (ROADMAP D16, decided PR 46: the product is not
    guarded, since no cell and no model feeds one). The
    split form (``r == d``) loses that lane and its partner; the full-width form (``r < d``) loses every rotated
    lane of that head, NaN but for the partner (``0 x inf`` in ``x @ P``), and keeps the pass-through lanes. No other
    head is touched in either form."""
    cfg = config(d, r)
    x, _ = draw(d, jnp.float32, seed=3)
    lane, partner = 5, 5 + r // 2
    tables = T.rope_table(cfg, jnp.arange(S))
    clean = np.asarray(T.apply_rope(x, *tables, cfg.rotary_dim))
    y = np.asarray(T.apply_rope(x.at[0, 3, 1, lane].set(np.inf), *tables, cfg.rotary_dim))
    head, rest = y[0, 3, 1], np.ones(y.shape[:3], bool)
    rest[0, 3, 1] = False
    assert np.array_equal(y[rest], clean[rest])
    lost = np.flatnonzero(~np.isfinite(head))
    if r == d:
        assert lost.tolist() == [lane, partner] and np.isinf(head[lost]).all()
        kept = np.delete(np.arange(d), lost)
        assert np.array_equal(head[kept], clean[0, 3, 1][kept])
    else:
        assert lost.tolist() == list(range(r))
        assert np.isinf(head[partner]) and np.isnan(np.delete(head[:r], partner)).all()
        assert np.array_equal(head[r:], np.asarray(x)[0, 3, 1, r:])


@pytest.mark.parametrize("d,r", [w for w in WIDTHS if w[1] < w[0]])
def test_the_tables_of_partial_rotary_are_as_wide_as_the_head(d, r):
    cfg = config(d, r)
    pos = 5 + jnp.arange(S)
    sin, cos = T.rope_table(cfg, pos)
    hs, hc = oracle_tables(cfg, pos)
    assert sin.shape == cos.shape == (S, d) and sin.dtype == cos.dtype == jnp.float32
    h = r // 2
    assert np.array_equal(sin[:, :h], -hs) and np.array_equal(sin[:, h:r], hs) and not np.any(sin[:, r:])
    assert np.array_equal(cos[:, :h], hc) and np.array_equal(cos[:, h:r], hc) and np.all(np.asarray(cos[:, r:]) == 1.0)


def test_a_yarn_factor_scales_the_rotated_lanes_only():
    d, r = 64, 16
    yarn = {"rope_type": "yarn", "factor": 8.0, "original_max_position_embeddings": 64}
    cfg = config(d, r, rope_parameters={None: yarn})
    _, scale = T.rope_inv_freq(cfg)
    assert scale != 1.0
    pos = jnp.arange(S)
    sin, cos = T.rope_table(cfg, pos)
    hs, hc = oracle_tables(cfg, pos)
    np.testing.assert_array_equal(sin[:, r // 2:r], hs)
    np.testing.assert_array_equal(cos[:, :r // 2], hc)
    assert np.all(np.asarray(cos[:, r:]) == 1.0) and not np.any(sin[:, r:])
    x, _ = draw(d, jnp.bfloat16, seed=4)
    within_one_ulp(T.apply_rope(x, sin, cos, r), oracle_rope(x, hs, hc))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: t.__name__)
def test_full_rotary_is_the_program_it_was(dtype):
    """``r == d``: half-width tables and the split form, equation for
    equation, whether ``rotary_dim`` is left out or names the whole head."""
    d = 128
    cfg = config(d, d)
    pos = jnp.arange(S)
    sin, cos = T.rope_table(cfg, pos)
    assert sin.shape == cos.shape == (S, d // 2)
    x, _ = draw(d, dtype)
    want = str(jax.make_jaxpr(oracle_rope)(x, sin, cos))
    assert str(jax.make_jaxpr(T.apply_rope)(x, sin, cos)) == want
    assert str(jax.make_jaxpr(lambda x, s, c: T.apply_rope(x, s, c, d))(x, sin, cos)) == want
    assert str(jax.make_jaxpr(lambda p: T.rope_table(cfg, p))(pos)) == str(
        jax.make_jaxpr(lambda p: T.rope_table(dataclasses.replace(cfg, rotary_dim=None), p))(pos))


def test_half_width_tables_are_refused_for_partial_rotary():
    cfg = config(64, 16)
    x, _ = draw(64, jnp.bfloat16)
    with pytest.raises(AssertionError, match="full-width"):
        T.apply_rope(x, *oracle_tables(cfg, jnp.arange(S)), 16)


def test_forward_over_reverse_goes_through_the_rotation():
    """``runtime/eigenvalue.py`` takes ``jvp`` of ``grad``: the rotation's own
    backward rule is plain ``jax.numpy`` and differentiates again."""
    cfg = config(64, 16)
    tables = T.rope_table(cfg, jnp.arange(S))
    x, v = draw(64, jnp.float32, seed=5)

    def loss(rope):
        return lambda x: jnp.sum(rope(x)**3)

    new = jax.jvp(jax.grad(loss(lambda x: T.apply_rope(x, *tables, 16))), (x, ), (v, ))[1]
    old = jax.jvp(jax.grad(loss(lambda x: oracle_rope(x, *oracle_tables(cfg, jnp.arange(S))))), (x, ), (v, ))[1]
    np.testing.assert_allclose(new, old, rtol=2e-5, atol=2e-5)


def scanned_step_text(d, r, layers=2):
    """The lowered forward and backward of the scanned, rematerialised block at
    a Pythia's shapes (16 heads of ``d``, 2 x 2,048 tokens), as the benchmark's
    training cells configure it. Nothing is compiled or run."""
    cfg = gpt_neox_config("pythia-1b", hidden_size=16 * d, num_heads=16, num_kv_heads=16, rotary_dim=r,
                          intermediate_size=64 * d, num_layers=layers, vocab_size=50304, max_seq_len=2048,
                          dtype=jnp.bfloat16, remat=True, remat_policy="save_only_these_names(attn_out)")
    model = TransformerLM(cfg)
    params = jax.eval_shape(lambda k: model.init(k, None), jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 2048), jnp.int32)
    step = jax.jit(jax.value_and_grad(lambda p, i: model.loss(p, {"input_ids": i})))
    return step.lower(params, ids).as_text()


def minor_dims(text):
    """The minor dimension of every ranked tensor type in a lowered module."""
    return {int(m) for m in re.findall(r"tensor<(?:\d+x)*(\d+)x[a-z]+\d+>", text)}


@pytest.mark.parametrize("d,r,cell", [(64, 16, "pythia-410m"), (128, 32, "pythia-1.4b")])
def test_the_scanned_step_holds_nothing_narrower_than_a_head(d, r, cell):
    """No operand or result of the lowered train step has a minor dimension of
    ``r/2`` (a half of the rotated lanes) or ``d - r`` (the pass-through
    lanes): the split form had both, in the forward, the recompute and the
    backward, and the TPU paid for them in 128-lane tiles."""
    text = scanned_step_text(d, r)
    assert "stablehlo.while" in text and "stablehlo.dot_general" in text
    found = minor_dims(text)
    assert d in found
    assert not found & {r // 2, d - r}, sorted(found)


def test_the_reader_of_minor_dimensions_sees_the_split_form(monkeypatch):
    """The same lowering with the oracle in the block has both widths."""
    monkeypatch.setattr(T, "rope_table", oracle_tables)
    monkeypatch.setattr(T, "apply_rope", lambda x, sin, cos, rotary_dim=None: oracle_rope(x, sin, cos))
    assert {8, 48} <= minor_dims(scanned_step_text(64, 16))


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_stages_take_the_full_width_tables(eight_devices, schedule):
    """The pipeline hands the tables to its stages as arguments, so their
    (absent) cotangent is asked for: gradients equal the serial model's."""
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.mesh import MeshConfig

    groups.initialize_mesh(MeshConfig(pipe=2, data=1), devices=jax.devices()[:2])
    mesh = groups.get_mesh()
    m = TransformerLM(gpt_neox_config("tiny", vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
                                      rotary_dim=8, max_seq_len=64, intermediate_size=128,
                                      attention_impl="reference", dtype=jnp.float32))
    params = jax.jit(lambda r: m.init(r))(jax.random.PRNGKey(1))
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 2, 16), dtype=np.int32)
    with mesh:
        got = jax.jit(jax.grad(lambda p: m.pipeline_loss(p, {"input_ids": ids}, mesh=mesh, num_stages=2,
                                                         schedule=schedule)))(params)
    want = jax.jit(jax.grad(lambda p: (m.loss(p, {"input_ids": ids[0]}) + m.loss(p, {"input_ids": ids[1]})) / 2))(params)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
