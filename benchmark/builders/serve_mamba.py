"""The serving system under test for a model whose sequences hold a
STATE-SPACE state beside the paged K/V cache (Mamba-2 layers between expert
layers and a few softmax layers, each layer ONE branch), of which this chip
holds a share of the experts: ``builders/serve_state.py``'s system and check,
the full house, the five limits and the controls' runner, with what the
selective scan needs laid over that module (loaded by its path, as
``serve_share`` lays its draws over ``serve``).

* **The weights are ONE model for every seed** (``serve_share.WEIGHTS_WORD``);
  ``--seed`` draws the token ids. Norm gains are drawn about one, the
  selection bias as ``serve_share`` draws it, and what only a Mamba layer has
  is drawn in float32 so that the rule is felt: ``A_log = log U(1, 16)``,
  ``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform on the published
  ``[time_step_min, time_step_max]`` (the step's input adds about N(0, 1)
  before the softplus, so a token's ``dt A`` spreads over 4e-4 to 4 and its
  decay over (0.02, 1)), ``D = 1 + 0.1 N(0, 1)``. An expert's matrices are
  stored at whole lane tiles (1,856 -> 1,920, ``TransformerConfig.expert_rows``);
  the hidden units past the published width are set to zero, and the
  reference reads the published width alone.
* **The check** is ``serve_state``'s: the check's row in a FULL HOUSE in the
  window's buckets, its prompt in chunks beside a token of every other row
  (boundaries that are no multiple of the scan's tile of 128), riding
  positions, ``engine.decode``'s horizon (the recurrent step), tail positions;
  limits on (1, 2) the logits, (3) every Mamba layer's state read back out of
  the pool, (4) the rule alone: ``ops.pallas.mamba2``'s two forms on the
  engine's own pool fed the REFERENCE's x, B, C and dt of the first Mamba
  layer, against the reference's scan token by token on the same inputs.
* **Controls** (``python3 benchmark/builders/serve_mamba.py --workload <cell>
  --seeds a,b [--rehearsal]``), each NOT correct. On the reference's side
  (``nemotron_reference.hyper_from_published``'s switches): ``no_dt_bias``,
  ``no_D_skip``, ``one_norm_group`` (the gated norm over all channels at
  once), ``group_of_head_wrong`` (B and C of group ``h % groups``), ``relu``
  for relu squared, ``route_scale_1``, ``no_selection_bias``. Behind the
  program's back, no switch in it: ``state_bf16``, ``no_tail``,
  ``padding_touches`` (``ops.pallas.mamba2.tile_plan`` patched so that a row's
  last tile counts its padding as tokens), ``products_default`` (the chunk
  scan's products at the default precision; the rule alone is run again).
"""

import functools
import os
import sys
from types import SimpleNamespace

REFERENCE_CONTROLS = {"no_dt_bias": {"dt_bias": False}, "no_D_skip": {"D_skip": False},
                      "one_norm_group": {"norm_groups": 1}, "group_of_head_wrong": {"group_of_head": "mod"},
                      "relu": {"activation": "relu"}, "route_scale_1": {"route_scale": 1.0},
                      "no_selection_bias": {"selection_bias": False}}


def make_params(cell, serve):
    """``serve_share.make_params`` (one model, gains about one, the bias's
    mid-quantile points a chip's run) with the Mamba layers' ``A_log``,
    ``dt_bias`` and ``D`` drawn in float32 over it."""
    from benchmark.lib import loader

    share = loader.load_module("builders", "serve_share", cell["root"])
    cf = cell["config_file"]
    plain = share.make_params(serve, float(cf["check"]["bias_std"]))
    lo, hi = float(cf.get("time_step_min", 1e-3)), float(cf.get("time_step_max", 1e-1))

    def draw(model, seed_word, dtype):
        import math

        import jax
        import jax.numpy as jnp

        params = plain(model, seed_word, dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(5), share.WEIGHTS_WORD)
        blocks = params["blocks"]
        shape = blocks["m2_A_log"].shape
        blocks["m2_A_log"] = jnp.log(jax.random.uniform(jax.random.fold_in(key, 0), shape, jnp.float32, 1.0, 16.0))
        dt = jnp.exp(jax.random.uniform(jax.random.fold_in(key, 1), shape, jnp.float32, math.log(lo), math.log(hi)))
        blocks["m2_dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1(dt)
        blocks["m2_D"] = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 2), shape, jnp.float32)
        # an expert's matrices are stored at whole lane tiles (``TransformerConfig.expert_rows``): the hidden
        # units past the published width are zeros, in place, so that the model served is the published one
        width = model.config.expert_size
        if blocks["moe_wi"].shape[-1] != width:
            blocks["moe_wi"] = jax.jit(lambda w: w.at[..., width:].set(0), donate_argnums=0)(blocks["moe_wi"])
            blocks["moe_wo"] = jax.jit(lambda w: w.at[..., width:, :].set(0), donate_argnums=0)(blocks["moe_wo"])
        return params

    return draw


@functools.lru_cache(maxsize=1)
def _rule_programs(use_pallas: bool, interpret: bool):
    """``serve_state.rule_check``'s programs for the selective scan: the two
    forms on the pool ``[layers, slots, ...]``, the reference's scan over a
    row's tokens, and the pool filled with ones and with zeros. ``arrs``: what
    the reference's first Mamba layer fed its scan, ``(x, B, C, dt)`` a token
    and ``A`` a head."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import mamba2

    from benchmark.lib import nemotron_reference

    options = {"use_pallas": use_pallas, "interpret": interpret}

    def chunks(pool, arrs, src, slot, fresh, n_tok):
        *tok, A = arrs
        flat = pool.reshape((-1, ) + pool.shape[2:])
        _, flat = mamba2.mamba2_chunks(*(a[src] for a in tok), A, flat, slot, fresh, n_tok, **options)
        return flat.reshape(pool.shape)

    def step(pool, arrs, src, slot, fresh, n_live):
        *tok, A = arrs
        flat = pool.reshape((-1, ) + pool.shape[2:])
        _, flat = mamba2.mamba2_step(*(a[src] for a in tok), A, flat, slot, fresh, n_live, **options)
        return flat.reshape(pool.shape)

    def plain(arrs, idx):
        x, B, C, dt, A = arrs
        by_head = lambda a: nemotron_reference._of_head(a[idx], x.shape[1], "div")
        return nemotron_reference.selective_scan(x[idx], by_head(B), by_head(C), dt[idx], A)[1]

    return SimpleNamespace(chunks=jax.jit(chunks, donate_argnums=0), step=jax.jit(step, donate_argnums=0),
                           plain=jax.jit(plain), ones=jax.jit(jnp.ones_like, donate_argnums=0),
                           zeros=jax.jit(jnp.zeros_like, donate_argnums=0))


def _patch_padding(on: bool):
    """``padding_touches``: a row's last tile counts its padding as tokens,
    so that whatever follows the row in the flat batch (the next row's
    tokens, the bucket's padding) goes through the scan into its state."""
    from deepspeed_tpu.ops.pallas import mamba2

    if not hasattr(_patch_padding, "plain"):
        _patch_padding.plain = mamba2.tile_plan
    _rule_programs.cache_clear()
    if not on:
        mamba2.tile_plan = _patch_padding.plain
        return

    def touched(n_tok, T, tile=mamba2.TILE, **kw):
        import jax.numpy as jnp

        row, tok0, cnt, first, n_tiles = _patch_padding.plain(n_tok, T, tile, **kw)
        return row, tok0, jnp.where(cnt > 0, tile, 0).astype(cnt.dtype), first, n_tiles

    mamba2.tile_plan = touched


def _patch_products(on: bool):
    """``products_default``: the chunk scan's products (float32 at
    ``HIGHEST``) at the default precision instead, one bfloat16 pass on the
    chip."""
    from jax import lax

    from deepspeed_tpu.ops.pallas import mamba2

    mamba2._HI = lax.Precision.DEFAULT if on else lax.Precision.HIGHEST
    _rule_programs.cache_clear()


def _state_module(root: str):
    """``builders/serve_state.py`` with this family's draws, rule programs and
    controls in the place of the delta rule's."""
    from benchmark.lib import loader

    state = loader.load_module("builders", "serve_state", root)
    state.make_params, state._rule_programs = make_params, _rule_programs
    state._patch_padding, state._patch_products = _patch_padding, _patch_products
    state.REFERENCE_CONTROLS = REFERENCE_CONTROLS
    return state


def build(cell: dict, seed: int, devices, rehearsal: bool, phases):
    return _state_module(cell["root"]).build(cell, seed, devices, rehearsal, phases)


def main(argv=None) -> int:
    """``serve_state.main`` (the check over seeds, sound and under each
    control; one JSON line a reading) with this family's controls."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    return _state_module(root).main(argv)


if __name__ == "__main__":
    sys.exit(main())
