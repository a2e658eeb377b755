"""Sampling for the ragged serving plane: temperature / top-p decoding and
the speculative rejection-sampling verify step.

Until this module, the whole serving plane was greedy-only — ``put`` /
``decode`` / ``speculate_decode`` all argmax on device, so the gateway could
not expose ``temperature`` at all. Two pieces:

* :class:`SamplingParams` — the per-request knob set (``temperature`` /
  ``top_p`` / ``seed``), validated once at the gateway door. Temperature 0
  is EXACT greedy (the argmax code path, not a small-temperature limit), so
  greedy parity guarantees are untouched by this module's existence.

* The device-side draw helpers. Determinism contract: every random draw is
  keyed by ``fold_in(PRNGKey(seed), token_position)`` (plus a small
  substream index), so a fixed ``(seed, prompt)`` pair replays the same
  stream across runs, batch compositions, and decode-path choices (put
  loop vs multi-step scan) — the key depends on the REQUEST's seed and the
  token's absolute position, never on batch layout.

* :func:`spec_verify_draws` — standard speculative sampling (Leviathan et
  al. / Chen et al.): the drafter proposes token ``d_i``; since every
  drafter here is deterministic given context, its proposal distribution is
  a point mass, so the accept test degenerates to ``u_i < p_i(d_i)`` under
  the target's (temperature/top-p filtered) distribution ``p_i``, and a
  rejection resamples from the normalized residual — ``p_i`` with ``d_i``
  masked out. The committed stream is then distributed EXACTLY as direct
  sampling from the target (asserted statistically in
  ``tests/test_speculative.py``); speculation changes throughput, never the
  distribution.
"""

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs. ``temperature == 0`` is exact greedy;
    ``top_p`` keeps the smallest nucleus whose mass reaches it (top-1 is
    always kept); ``seed`` keys the request's whole random stream (None =
    derived from the request uid, so replays within one process are
    deterministic but two clients don't share draws by default)."""

    temperature: float = 0.0
    top_p: float = 1.0
    seed: Optional[int] = None

    def validate(self) -> "SamplingParams":
        t = float(self.temperature)
        if not np.isfinite(t) or t < 0.0 or t > 100.0:
            raise ValueError(f"temperature must be in [0, 100], got {self.temperature!r}")
        p = float(self.top_p)
        if not np.isfinite(p) or not 0.0 < p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p!r}")
        if self.seed is not None:
            s = int(self.seed)
            if not -2**31 <= s < 2**31:
                raise ValueError(f"seed must fit int32, got {self.seed!r}")
        return self

    @property
    def greedy(self) -> bool:
        return float(self.temperature) <= 0.0


def pack_sampling(params: Sequence[Optional[SamplingParams]], uids: Sequence[int],
                  s_bucket: int):
    """Pack per-sequence sampling params into the two device operands the
    compiled sampled paths take: float32 ``[S_bucket, 2]`` (temperature,
    top_p) and int32 ``[S_bucket]`` seeds. ``None`` entries are greedy rows
    (temperature 0 → the argmax branch on device); an unset seed derives
    from the uid."""
    f = np.zeros((s_bucket, 2), np.float32)
    f[:, 1] = 1.0
    seeds = np.zeros(s_bucket, np.int32)
    for i, (sp, uid) in enumerate(zip(params, uids)):
        if sp is None:
            continue
        f[i, 0] = float(sp.temperature)
        f[i, 1] = float(sp.top_p)
        seeds[i] = np.int32((int(uid) * 2654435761) & 0x7FFFFFFF) if sp.seed is None \
            else np.int32(int(sp.seed))
    return f, seeds


def all_greedy(params) -> bool:
    """True when no row needs the sampled code path (params absent or every
    entry None/temperature-0) — the caller then keeps the byte-identical
    greedy program."""
    return params is None or all(sp is None or sp.greedy for sp in params)


# ---------------------------------------------------------------------------
# device-side draws (pure jnp — called inside the engine's compiled paths,
# and directly by the distribution-equivalence test)
# ---------------------------------------------------------------------------

def _keys(seeds, ctrs):
    """One PRNG key per row: ``fold_in(PRNGKey(seed), ctr)`` — ctr is the
    token's absolute position, making draws batch-layout-independent."""
    import jax

    def one(s, c):
        return jax.random.fold_in(jax.random.PRNGKey(s), c)

    return jax.vmap(one)(seeds, ctrs)


def filter_top_p(logits, top_p):
    """Mask ``logits`` (last axis = vocab) outside the smallest nucleus
    whose probability mass reaches ``top_p`` (broadcastable; 1.0 = no-op
    mask in VALUE — the masked set is empty). Top-1 is always kept."""
    import jax
    import jax.numpy as jnp

    sorted_l = -jnp.sort(-logits, axis=-1)
    probs = jax.nn.softmax(sorted_l, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # a token is kept while the mass BEFORE it is < top_p (keeps top-1 even
    # when its own mass exceeds top_p)
    keep = (cum - probs) < jnp.asarray(top_p)[..., None]
    kept_min = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits >= kept_min, logits, -jnp.inf)


def _filtered(logits, temps, top_ps):
    """Temperature-scaled, top-p-filtered logits (f32). ``temps``/``top_ps``
    broadcast over the leading axes ([S] against [S, ..., V])."""
    import jax.numpy as jnp

    extra = logits.ndim - 1 - temps.ndim + 1
    t = temps.reshape(temps.shape + (1, ) * extra)
    p = top_ps.reshape(top_ps.shape + (1, ) * (extra - 1))
    scaled = logits.astype(jnp.float32) / jnp.maximum(t, 1e-6)
    return filter_top_p(scaled, p)


def sample_tokens(logits, temps, top_ps, seeds, ctrs):
    """One token per row from ``logits [S, V]``: argmax where
    ``temps <= 0``, else categorical over the temperature/top-p filtered
    distribution, keyed by ``(seed, ctr)`` (ctr = the sampled token's own
    absolute position)."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filt = _filtered(logits, temps, top_ps)
    sampled = jax.vmap(jax.random.categorical)(_keys(seeds, ctrs), filt).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def spec_verify_draws(logits, chunk, temps, top_ps, seeds, starts):
    """The speculative-sampling verify step over one ragged verify chunk.

    ``logits [S, k+1, V]``: the target's logits at every chunk position
    (position i's distribution conditions on chunk tokens ``..i``);
    ``chunk [S, k+1]`` the fed tokens (pending first token + k drafts, pads
    included); ``starts [S]`` each sequence's pre-chunk ``seen_tokens``.

    Returns ``(accept [S, k] bool, nxt [S, k+1] int32)``:

    * ``accept[s, i]`` — draft ``chunk[s, i+1]`` survives at position i
      (greedy rows: equals the argmax; sampled rows: ``u < p_i(d_i)``, the
      point-mass-draft acceptance test);
    * ``nxt[s, i]`` for ``i < k`` — the token to commit INSTEAD when i is
      the first rejection: greedy rows the argmax, sampled rows a draw from
      the normalized residual (``p_i`` with ``d_i`` masked out — the
      ``(p - q)^+`` of speculative sampling with a point-mass q);
    * ``nxt[s, k]`` — the bonus token when every draft survives (a fresh
      draw from position k's distribution / the argmax).

    The caller walks accept to the first False exactly as the greedy path
    walks its argmax mismatch — the host-side commit logic is shared.
    """
    import jax
    import jax.numpy as jnp

    S, k1, V = logits.shape
    k = k1 - 1
    lg = logits.astype(jnp.float32)
    greedy_row = jnp.argmax(lg, axis=-1).astype(jnp.int32)         # [S, k+1]
    filt = _filtered(lg, temps, top_ps)                            # [S, k+1, V]
    probs = jax.nn.softmax(filt, axis=-1)
    drafts = chunk[:, 1:]                                          # [S, k]
    p_draft = jnp.take_along_axis(probs[:, :k], drafts[..., None], axis=-1)[..., 0]

    # keys: one per (row, chunk position), keyed by the TARGET position the
    # draw decides (start + i + 1), substreams 0=accept, 1=residual, 2=bonus
    def row_keys(seed, start):
        base = jax.random.PRNGKey(seed)
        ks = jax.vmap(lambda i: jax.random.fold_in(base, start + 1 + i))(
            jnp.arange(k1, dtype=jnp.int32))
        return ks

    keys = jax.vmap(row_keys)(seeds, starts)                       # [S, k+1, 2]
    sub = jax.vmap(jax.vmap(jax.random.fold_in))
    u = jax.vmap(jax.vmap(jax.random.uniform))(sub(keys[:, :k], jnp.zeros((S, k), jnp.int32)))
    residual = jnp.where(
        jax.nn.one_hot(drafts, V, dtype=bool), -jnp.inf, filt[:, :k])
    # degenerate nucleus == {draft}: the residual is empty, but then
    # p(draft) == 1 and the accept test never consults the resample — keep
    # the draw well-defined rather than categorical over all -inf
    res_dead = jnp.all(jnp.isneginf(residual), axis=-1, keepdims=True)
    residual = jnp.where(res_dead, filt[:, :k], residual)
    res_tok = jax.vmap(jax.vmap(jax.random.categorical))(
        sub(keys[:, :k], jnp.ones((S, k), jnp.int32)), residual).astype(jnp.int32)
    # the bonus draw only ever applies at the LAST position (full
    # acceptance) — draw just there, with the same (position-k, substream-2)
    # key a full-width draw would have used, so streams are unchanged
    bonus_tok = jax.vmap(jax.vmap(jax.random.categorical))(
        sub(keys[:, k:], jnp.full((S, 1), 2, jnp.int32)), filt[:, k:]).astype(jnp.int32)

    sampled_rows = (temps > 0.0)[:, None]
    accept = jnp.where(sampled_rows, u < p_draft, drafts == greedy_row[:, :k])
    nxt = jnp.where(sampled_rows, jnp.concatenate(
        [res_tok, bonus_tok], axis=1), greedy_row)
    return accept, nxt


# ---------------------------------------------------------------------------
# masked diffusion over blocks (a model with ``diffusion_block_size``): what a
# denoise forward yields for each position of a block, and which masked
# positions it unmasks. Pure jnp, called inside the engine's compiled block
# program and by the tests.
# ---------------------------------------------------------------------------

def diffusion_candidates(logits):
    """For each row of ``logits [N, V]``: its argmax token (int32) and that
    token's float32 softmax probability, ``1 / sum(exp(l - max l))``."""
    import jax.numpy as jnp

    lg = logits.astype(jnp.float32)
    top = jnp.max(lg, axis=-1, keepdims=True)
    conf = 1.0 / jnp.sum(jnp.exp(lg - top), axis=-1)
    return jnp.argmax(lg, axis=-1).astype(jnp.int32), conf


def diffusion_quota(block_size: int, denoising_steps: int):
    """Positions each of the ``denoising_steps`` forwards of the static rule
    unmasks: ``B // steps`` each, one more in the first ``B % steps``."""
    return [block_size // denoising_steps + (i < block_size % denoising_steps)
            for i in range(denoising_steps)]


def diffusion_unmask(conf, masked, remasking: str, quota, threshold: float, last):
    """Which masked positions a denoise forward unmasks. ``conf``/``masked``
    ``[S, B]``; ``quota`` (static rule) the positions this forward may take;
    ``last``: the block's final denoise forward, which takes every masked
    position. Among equal confidences the earlier position goes first."""
    import jax.numpy as jnp

    score = jnp.where(masked, conf, -1.0)
    B = score.shape[-1]
    j = jnp.arange(B)
    ahead = (score[..., None, :] > score[..., :, None]) | \
        ((score[..., None, :] == score[..., :, None]) & (j[None, :] < j[:, None]))
    rank = jnp.sum(ahead, axis=-1)  # positions ahead of each one, masked ones first
    if remasking == "low_confidence_static":
        choose = rank < quota
    else:
        choose = (conf > threshold) | (rank == 0)
    return masked & (choose | last)
