"""``paged_attn_q_tiled`` at KV blocks narrower than a 128-lane tile: a grid
step takes as many of a tile's live columns as fill one (two 64-token blocks,
four of 32 or fewer), and at 128 the list and the body are what they were.
Interpret mode, small shapes; the chip's cases are ``tests_tpu -k
under_a_selection``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as pa

Q_TILE = 8
BLOCKS = {128: 1, 64: 2, 16: 4}   # a block's tokens -> the rule's blocks a grid step


def _batch(bs, dtype=jnp.float32, d=32, seed=0, all_true=False):
    """A ragged batch under tables 9 columns wide: a 29-token run that starts
    in the middle of a block (three whole tiles and a tail of 5, their last
    columns 3, 3, 3 and 4 at 128-token blocks: odd and even counts of live
    columns, so some last items have a dead slot), a 3-token run from position
    0 (one column: every slot but the first is dead, in a tile's ONLY item), two one-token rows deep in
    their contexts, and the pad run; a selection in which every token keeps
    its own block and block 0."""
    rng = np.random.default_rng(seed)
    nq, nkv, S, mb = 4, 2, 4, 9
    runs = [(2 * bs + bs // 2 + 3, 29), (0, 3), (6 * bs + 5, 1), (7 * bs + bs - 1, 1)]
    seq_idx = np.concatenate([np.full(n, r) for r, (_, n) in enumerate(runs)])
    pos = np.concatenate([np.arange(seen, seen + n) for seen, n in runs])
    n = pos.size
    pad = -n % 8 + 8
    seq_idx, pos = (np.pad(a, (0, pad)).astype(np.int32) for a in (seq_idx, pos))
    n_blocks = S * mb + 2
    tables = rng.permutation(n_blocks)[:S * mb].reshape(S, mb).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(n + pad, nq, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(n_blocks * bs, nkv, d)), dtype) for _ in range(2))
    sel = rng.random((n + pad, nkv, mb)) < 0.4
    sel[np.arange(n + pad), :, pos // bs] = True
    sel[:, :, 0] = True
    if all_true:
        sel[:] = True
    return n, (q, k, v, jnp.asarray(tables), jnp.asarray(seq_idx), jnp.asarray(pos)), jnp.asarray(sel)


def _tiled(args, bs, **kw):
    return pa._pallas_paged(*args, block_size=bs, interpret=True, q_tile=Q_TILE, **kw)


@pytest.mark.parametrize("case", ["plain", "selection", "window", "alibi", "bf16_words"])
@pytest.mark.parametrize("bs", list(BLOCKS))
def test_the_tiled_kernel_at_one_two_and_four_blocks_a_step_matches_the_gather(bs, case):
    """A long run from the middle of a block, a short tile, one-token rows and
    tiles with an odd number of live columns, against the gather: plain, under
    a selection, under a sliding window that cuts the long run's first
    columns, with alibi, and from a bf16 pool whose heads come out of the
    block by strided word loads."""
    dtype, d = (jnp.bfloat16, 128) if case == "bf16_words" else (jnp.float32, 32)
    n, args, sel = _batch(bs, dtype, d)
    kw = {"selection": sel} if case == "selection" else {"window": 2 * bs + 5} if case == "window" else {}
    if case == "alibi":
        kw["alibi"] = (0.5, 0.25, 0.125, 0.0625)
    if case == "bf16_words":
        assert pa._head_load_path(dtype, 2) == "words"
    want = pa.paged_attention_reference(*args, bs, **kw)
    got = _tiled(args, bs, **kw)
    if case == "selection":
        (want, own), (got, read) = want, got
        assert int(own[0]) <= int(read[0]) and (np.asarray(own[1:]) == 0).all()
    err = np.abs(np.asarray(got[:n], np.float32) - np.asarray(want[:n], np.float32)).max()
    assert err < (3e-2 if case == "bf16_words" else 2e-5)


@pytest.mark.parametrize("bs", list(BLOCKS))
def test_an_all_true_selection_lays_what_no_selection_lays(bs):
    """Bit-equal outputs: the items pair the same columns in the same order;
    and every visible (token, column) pair is served."""
    n, args, sel = _batch(bs, all_true=True)
    got, read = _tiled(args, bs, selection=sel)
    assert (np.asarray(got[:n]) == np.asarray(_tiled(args, bs)[:n])).all()
    assert int(read[0]) == int(np.sum(np.asarray(args[5]) // bs + 1))


@pytest.mark.parametrize("bs", list(BLOCKS))
def test_read_counts_blocks_not_items(bs):
    """``read`` under the rule's blocks a step is what one block a step reads
    on the same batch (the kernel's own test-only argument names the other
    list), the live pairs are one block a step's grid steps, and the grid
    steps are ``ceil(pairs / B)`` a tile."""
    B = BLOCKS[bs]
    n, args, sel = _batch(bs)
    got, read = _tiled(args, bs, selection=sel)
    one, read_one = _tiled(args, bs, selection=sel, blocks_per_step=1) if B > 1 else (got, read)
    assert np.abs(np.asarray(got[:n] - one[:n])).max() < 2e-5
    assert int(read[0]) == int(read_one[0]) and int(read[1]) == int(read_one[1]) == int(read_one[2])
    lists = [pa._tiled_work_list(*args[3:], bs, None, Q_TILE, sel, per) for per in (1, B)]
    (*_, w_tile, _, total, _, served, pairs), (*_, total_b, _, served_b, pairs_b) = lists
    per_tile = np.bincount(np.asarray(w_tile)[:int(total)])
    assert int(total) == int(pairs) == int(pairs_b) == int(read[1]) and int(served) == int(served_b) == int(read[0])
    assert int(total_b) == int(read[2]) == int((-(-per_tile // B)).sum())
    assert int(read[2]) < int(read[1]) or B == 1


@pytest.mark.parametrize("how", ["plain", "selection", "window"])
@pytest.mark.parametrize("bs", [64, 16])
def test_the_work_list_of_several_blocks_an_item(bs, how):
    """Against the list of one block an item on the same batch: a tile's items
    are consecutive, its live slots in order are its columns in order, only
    its last item has dead slots, a dead slot reads ``~c`` with ``c`` the
    column the slot held in the item before, ``total`` is the sum of ``ceil(n
    / B)``, past it ``w_tile`` reads ``n_tiles``, and the scalar memory is no
    more than one block an item takes."""
    B = BLOCKS[bs]
    _, args, sel = _batch(bs)
    window = 2 * bs + 5 if how == "window" else None
    sel = sel if how == "selection" else None
    *_, cnt, w_tile1, w_col1, total1 = (np.asarray(a) for a in pa._tiled_work_list(*args[3:], bs, window, Q_TILE, sel)[:9])
    *_, w_tile, w_col, total = (np.asarray(a) for a in pa._tiled_work_list(*args[3:], bs, window, Q_TILE, sel, B)[:9])
    n_tiles, bound = cnt.size, w_tile.size - 1
    cols = (w_tile1.size - 1) // n_tiles
    assert bound == n_tiles * -(-cols // B) and w_col.shape == (B * bound, )
    assert (w_tile[total:] == n_tiles).all() and (np.diff(w_tile[:total]) >= 0).all()
    slots = w_col.reshape(B, bound).T[:total]                              # [items, B]
    dead_slots = 0
    for t in range(n_tiles):
        mine = w_col1[:total1][w_tile1[:total1] == t]
        items = slots[w_tile[:total] == t]
        assert len(items) == -(-len(mine) // B)
        if not len(mine):
            assert cnt[t] == 0 or how != "plain"
            continue
        live = items.reshape(-1)[:len(mine)]
        assert live.tolist() == mine.tolist() and (np.diff(live) > 0).all()
        dead = items.reshape(-1)[len(mine):]                               # of the last item alone
        assert (dead < 0).all() and len(dead) < B
        if len(items) > 1:
            assert (~dead).tolist() == items[-2, B - len(dead):].tolist()
        dead_slots += len(dead)
    assert dead_slots > 0 and total == sum(-(-np.sum(w_tile1[:total1] == t) // B) for t in range(n_tiles))
    S, mb = args[3].shape
    assert pa._tiled_smem_bytes(n_tiles, cols, S, mb, B) <= pa._tiled_smem_bytes(n_tiles, cols, S, mb)
    if how != "selection":
        seq_idx, pos = np.asarray(args[4]), np.asarray(args[5])
        assert pa.tiled_kv_counts(Q_TILE, seq_idx, pos, [(window, 1)], bs, mb, S, B) == (n_tiles * cols, total1, total)


def test_the_rule_is_the_block_size_alone(monkeypatch):
    """``choose_kernel`` says one block a grid step at 128 tokens and above, two at
    64 and four at 32 or fewer, for token-major pools of bf16 or float32; a
    latent pool, pools by head and int8 pools keep one; and the kernel takes
    the same rule where no test names another."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiled = lambda bs, nkv=2, itemsize=2, **kw: pa.choose_kernel(2048, 8, 1034, 32, bs * nkv, 128, itemsize,
                                                                 block_size=bs, **kw)
    for bs, want in {256: 1, 128: 1, 64: 2, 32: 4, 16: 4, 8: 4}.items():
        choice = tiled(bs)
        assert (choice["kernel"], choice["q_tile"], choice["blocks_per_step"]) == ("paged_attn_q_tiled", 128, want)
    assert pa.choose_kernel(2048, 8, 1034, 32, 128 * 8, 128, 2)["blocks_per_step"] == 1   # the default: a whole lane tile
    assert tiled(64, itemsize=1)["blocks_per_step"] == 1                  # int8: a scale block a KV block
    assert tiled(64, nkv=1, parts=1)["blocks_per_step"] == 1              # a latent pool is read where it lies
    assert tiled(64, nkv=32, kv_by_head=32)["blocks_per_step"] == 1       # and so are pools by head
    assert [pa._tiled_blocks_per_step(bs, True) for bs in (128, 64, 32, 16)] == [1, 2, 4, 4]


def test_the_accounting_counts_every_block_of_a_step():
    """The K/V buffers and the scratch by kv head are ``B`` blocks; a step's
    scores are as wide as its keys."""
    one = pa._q_tiled_vmem_bytes(4096, 2048, 128, 64, 2, 2, 2)
    two = pa._q_tiled_vmem_bytes(4096, 2048, 128, 64, 2, 2, 2, 2)
    assert two - one == 2 * 2 * 64 * 2 * 128 * 2 + 2 * 2 * 64 * 128 * 4   # one block more: fetched twice over, by head
    assert pa._q_tiled_vmem_bytes(4096, 2048, 128, 128, 2, 2, 2) == pa._q_tiled_vmem_bytes(4096, 2048, 128, 128, 2, 2, 2, 1)
    assert pa._tiled_smem_bytes(25, 1034, 8, 1034, 2) < pa._tiled_smem_bytes(25, 1034, 8, 1034)
