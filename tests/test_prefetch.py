"""Async device-prefetching input pipeline tests.

Covers the DevicePrefetchIterator contract (overlap, bounded depth /
backpressure, exception propagation, clean shutdown), the engine fast path
for already-placed DeviceBatch inputs, bit-identical losses vs the
synchronous path on a fixed seed, the ``train/input_wait_ms`` telemetry,
the satellites (RepeatingLoader epoch reshuffle, NamedSharding cache,
InferenceEngineV2.warmup), and the ``tools/check_data_paths.py`` structural
gate that keeps every train_batch data path routed through the single
host-work helper."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.runtime.data_pipeline.prefetch import DeviceBatch, DevicePrefetchIterator
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, DistributedSampler, RepeatingLoader


def _wait_until(pred, timeout=10.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# ---------------------------------------------------------------------------
# DevicePrefetchIterator contract (no engine)
# ---------------------------------------------------------------------------
def test_prefetch_worker_runs_ahead():
    """The worker fills its buffer while the consumer sits idle — the
    overlap the whole subsystem exists for."""
    produced = []

    def gen():
        for i in range(10):
            produced.append(i)
            yield i

    pf = DevicePrefetchIterator(gen(), gas=1, depth=3)
    try:
        item = next(pf)
        assert isinstance(item, DeviceBatch) and item.data == 0 and item.step == 0
        # consumer does nothing; worker must still pull ahead: 1 consumed +
        # 3 buffered + 1 in hand
        assert _wait_until(lambda: len(produced) >= 4)
    finally:
        pf.close()


def test_prefetch_backpressure_at_depth():
    """A bounded queue, not unbounded HBM growth: with depth k and no
    consumer, the worker pulls at most k+1 items (k queued + 1 in hand)."""
    produced = []

    def gen():
        i = 0
        while True:
            produced.append(i)
            yield i
            i += 1

    pf = DevicePrefetchIterator(gen(), gas=1, depth=2)
    try:
        assert _wait_until(lambda: len(produced) >= 3)
        time.sleep(0.3)  # would keep growing without backpressure
        assert len(produced) <= 3  # depth + 1
        # consuming one frees exactly one slot
        next(pf)
        assert _wait_until(lambda: len(produced) == 4)
        time.sleep(0.2)
        assert len(produced) == 4
    finally:
        pf.close()


def test_prefetch_exception_propagates_in_order():
    """A worker exception reaches the consumer at the matching next() call,
    after the already-queued good batches drain."""

    def gen():
        yield 0
        yield 1
        raise ValueError("loader blew up")

    pf = DevicePrefetchIterator(gen(), gas=1, depth=4)
    with pf:
        assert next(pf).data == 0
        assert next(pf).data == 1
        with pytest.raises(ValueError, match="loader blew up"):
            next(pf)
        # the failure is sticky
        with pytest.raises(ValueError):
            next(pf)


def test_prefetch_gas_grouping_and_stop_iteration():
    """gas microbatches per item; a partial trailing group ends the stream
    (StopIteration, like the inline data_iter path would raise mid-pull)."""
    pf = DevicePrefetchIterator(iter(range(5)), gas=2, depth=2)
    with pf:
        assert next(pf).data == [0, 1]
        assert next(pf).data == [2, 3]
        with pytest.raises(StopIteration):
            next(pf)  # 5th microbatch has no partner
        with pytest.raises(StopIteration):
            next(pf)


def test_prefetch_close_mid_epoch():
    """close() stops a worker blocked on a full queue, joins the thread, and
    later next() calls fail loudly instead of hanging."""

    def gen():
        while True:
            yield 0

    pf = DevicePrefetchIterator(gen(), gas=1, depth=2)
    assert _wait_until(lambda: pf._queue.full())
    pf.close()
    assert not pf._thread.is_alive()
    # the worker blocked in put() when stop was set may fill the slot the
    # first drain freed — close() must leave NOTHING pinned in the queue
    assert pf._queue.qsize() == 0
    with pytest.raises(RuntimeError, match="closed"):
        next(pf)
    pf.close()  # idempotent


def test_prefetch_step_numbering_from_start_step():
    pf = DevicePrefetchIterator(iter(range(6)), gas=2, depth=2, start_step=7)
    with pf:
        assert next(pf).step == 7
        assert next(pf).step == 8


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------
SEQ = 32


def _make_engine(gas=2, curriculum=False, vocab=64):
    model = TransformerLM(TransformerConfig(vocab_size=vocab, hidden_size=32, num_layers=2, num_heads=2,
                                            intermediate_size=64, max_seq_len=SEQ, dtype=jnp.float32,
                                            attention_impl="reference"))
    config = {
        "train_batch_size": 8 * gas,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10**9,
        "tpu": {"mesh": {"data": 8}},
    }
    if curriculum:
        config["curriculum_learning"] = {
            "enabled": True, "curriculum_type": "seqlen",
            "min_difficulty": 16, "max_difficulty": SEQ,
            "schedule_type": "fixed_linear",
            "schedule_config": {"total_curriculum_step": 2, "difficulty_step": 16},
        }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    return engine


def _mb_stream(n_steps, gas, vocab=64, rows=8):
    """Deterministic microbatch stream: microbatch i is a pure function of i."""
    for i in range(n_steps * gas):
        rng = np.random.default_rng(1000 + i)
        yield {"input_ids": rng.integers(0, vocab, size=(rows, SEQ), dtype=np.int32)}


def test_prefetch_bit_identical_losses_vs_sync():
    """The acceptance bar: prefetched and synchronous paths produce IDENTICAL
    losses for the same seed — including a curriculum schedule running inside
    the prefetch worker (difficulty computed for the consuming step)."""
    n_steps, gas = 4, 2
    sync_engine = _make_engine(gas=gas, curriculum=True)
    it = _mb_stream(n_steps, gas)
    sync_losses = [float(sync_engine.train_batch(data_iter=it)) for _ in range(n_steps)]
    sync_engine.destroy()

    pf_engine = _make_engine(gas=gas, curriculum=True)
    pf = pf_engine.prefetching_loader(_mb_stream(n_steps, gas))
    pf_losses = [float(pf_engine.train_batch(data_iter=pf)) for _ in range(n_steps)]
    # main-thread housekeeping kept the shared scheduler state fresh even
    # though the worker used the side-effect-free accessors
    assert pf_engine.curriculum_scheduler.get_current_difficulty() == SEQ
    with pytest.raises(StopIteration):
        pf_engine.train_batch(data_iter=pf)  # stream exhausted, like inline
    pf_engine.destroy()
    assert not pf._thread.is_alive()  # destroy() closed the worker

    assert all(np.isfinite(l) for l in sync_losses)
    assert sync_losses == pf_losses  # bit-identical, not allclose


def test_train_batch_device_batch_fast_path():
    """An already-placed DeviceBatch skips the inline host work entirely."""
    engine = _make_engine(gas=1)
    rng = np.random.default_rng(0)
    raw = {"input_ids": rng.integers(0, 64, size=(8, SEQ), dtype=np.int32)}
    placed = engine._shard_batch(engine._host_prepare_batch(batch=raw), leading=("mb", ))
    assert all(isinstance(l, jax.Array) for l in jax.tree_util.tree_leaves(placed))

    def boom(*a, **k):
        raise AssertionError("fast path must not re-run host batch assembly")

    engine._host_prepare_batch = boom
    loss = engine.train_batch(batch=DeviceBatch(placed, 0))
    assert np.isfinite(float(loss))
    engine.destroy()


def test_input_wait_metric_and_span():
    """train/input_wait_ms histogram + input_wait span record every step."""
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics
    from deepspeed_tpu.monitor.trace import configure_tracer, get_tracer

    engine = _make_engine(gas=1)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, size=(8, SEQ), dtype=np.int32)}
    configure_metrics(enabled=True)
    get_metrics().reset()
    # the tracer is a process singleton: a path, a flush_every of 1 or a
    # mirror left by an earlier file of this process would take the events
    # this test drains from the buffer
    get_tracer().reset()
    configure_tracer(enabled=True)  # pathless buffer mode
    try:
        engine.train_batch(batch)
        engine.train_batch(batch)
        hist = get_metrics().histogram("train/input_wait_ms")
        assert hist.count >= 2
        waits = [e for e in get_tracer().drain() if e.get("name") == "input_wait"]
        assert len(waits) >= 2
        assert waits[0]["args"]["prefetched"] is False
    finally:
        get_tracer().reset()
        configure_metrics(enabled=False)
        get_metrics().reset()
        engine.destroy()


def test_prefetch_config_block():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    c = DeepSpeedConfig({"train_batch_size": 8,
                         "data_pipeline": {"prefetch": {"enabled": True, "depth": 3}}})
    assert c.data_pipeline_config.prefetch.enabled
    assert c.data_pipeline_config.prefetch.depth == 3
    c2 = DeepSpeedConfig({"train_batch_size": 8})
    assert not c2.data_pipeline_config.prefetch.enabled
    assert c2.data_pipeline_config.prefetch.depth == 2


def test_engine_auto_wraps_training_dataloader():
    """With the config block on, the engine-built dataloader comes back as a
    prefetching iterator of DeviceBatch items."""
    model = TransformerLM(TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                                            intermediate_size=64, max_seq_len=SEQ, dtype=jnp.float32,
                                            attention_impl="reference"))
    data = [{"input_ids": np.full((SEQ, ), i % 64, np.int32)} for i in range(64)]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, training_data=data,
        config={"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "data_pipeline": {"prefetch": {"enabled": True, "depth": 2}},
                "steps_per_print": 10**9, "tpu": {"mesh": {"data": 8}}})
    try:
        from deepspeed_tpu.runtime.data_pipeline.prefetch import LazyPrefetchingLoader

        assert isinstance(engine.training_dataloader, LazyPrefetchingLoader)
        assert engine.training_dataloader._pf is None  # worker not started yet
        # post-initialize configuration must be captured: the worker only
        # starts at first next(), AFTER this hook is installed
        seen = []
        engine.set_data_post_process_func(lambda mb: (seen.append(1), mb)[1])
        loss = engine.train_batch(data_iter=engine.training_dataloader)
        assert np.isfinite(float(loss))
        assert seen  # the prefetch worker ran the late-installed hook
        assert isinstance(engine.training_dataloader._pf, DevicePrefetchIterator)
        assert engine._prefetchers  # destroy() will close the worker
        # loader semantics survive the wrap: len in consumed items, sampler
        # delegation, and iter() restarting a fresh epoch (a bare prefetch
        # iterator would silently end multi-epoch loops after epoch 1)
        loader = engine.training_dataloader
        assert len(loader) == 8  # 64 samples / 8-row microbatches, gas=1
        assert loader.sampler is loader._loader.sampler
        assert sum(1 for _ in loader) == 8  # iter() restarts a full epoch
        loader.sampler.set_epoch(1)
        assert sum(1 for _ in loader) == 8  # epoch 2 runs too, not one-shot
    finally:
        engine.destroy()
        engine.training_dataloader.close()


def test_prefetch_through_zero_offload_path():
    """The already-placed fast path covers _offload_train_batch too: the
    host-Adam step consumes prefetched DeviceBatches without resharding."""
    model = TransformerLM(TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                                            intermediate_size=64, max_seq_len=SEQ, dtype=jnp.float32,
                                            attention_impl="reference"))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 16, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
        "zero_optimization": {"stage": 2, "offload_optimizer": {"device": "cpu"}},
        "steps_per_print": 10**9, "tpu": {"mesh": {"data": 8}}})
    assert engine.host_optimizer is not None
    pf = engine.prefetching_loader(_mb_stream(2, 2))
    losses = [float(engine.train_batch(data_iter=pf)) for _ in range(2)]
    assert all(np.isfinite(l) for l in losses)
    assert int(engine.state["step"]) == 2
    engine.destroy()


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------
def test_repeating_loader_reshuffles_per_epoch():
    """RepeatingLoader advances the wrapped sampler's epoch on restart, so
    each pass sees a fresh shuffle order (and exactly the sampler's own
    epoch-1 order, not some ad-hoc one)."""
    ds = list(range(32))
    dl = DeepSpeedDataLoader(ds, batch_size=4, data_parallel_rank=0, data_parallel_world_size=1,
                             shuffle=True, seed=0)
    rl = RepeatingLoader(dl)
    ep0 = np.concatenate([np.asarray(next(rl)) for _ in range(len(dl))])
    ep1 = np.concatenate([np.asarray(next(rl)) for _ in range(len(dl))])
    assert sorted(ep0.tolist()) == ds and sorted(ep1.tolist()) == ds
    assert not np.array_equal(ep0, ep1)  # the pre-fix behavior replayed ep0
    ref = DistributedSampler(32, rank=0, world_size=1, shuffle=True, seed=0)
    ref.set_epoch(1)
    np.testing.assert_array_equal(ep1, [ds[int(i)] for i in ref])
    assert rl.epoch == 1

    # resume case: an externally-set sampler epoch is ADVANCED, not clobbered
    dl2 = DeepSpeedDataLoader(ds, batch_size=4, data_parallel_rank=0, data_parallel_world_size=1,
                              shuffle=True, seed=0)
    dl2.sampler.set_epoch(7)
    rl2 = RepeatingLoader(dl2)
    for _ in range(len(dl2)):
        next(rl2)  # epoch 7 pass
    next(rl2)  # restart
    assert dl2.sampler.epoch == 8


def test_shard_batch_sharding_cache_and_idempotence():
    engine = _make_engine(gas=2)
    try:
        b = {"input_ids": np.zeros((2, 8, SEQ), np.int32)}
        p1 = engine._shard_batch(b, leading=("mb", ))
        assert (3, 1) in engine._sharding_cache
        cached = engine._sharding_cache[(3, 1)]
        p2 = engine._shard_batch({"input_ids": np.ones((2, 8, SEQ), np.int32)}, leading=("mb", ))
        assert p2["input_ids"].sharding is cached  # reused, not rebuilt
        # idempotent: already-placed leaves pass through untouched
        p3 = engine._shard_batch(p1, leading=("mb", ))
        assert p3["input_ids"] is p1["input_ids"]
    finally:
        engine.destroy()


def test_v2_warmup_precompiles_decode():
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import llama2

    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256, dtype=jnp.float32,
                   attention_impl="reference")
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=8, num_kv_blocks=32, kv_dtype=jnp.float32, use_pallas_kernels="never",
        state_manager=DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                                           max_ragged_sequence_count=4, max_context=64))
    eng = InferenceEngineV2(model, cfg)

    from deepspeed_tpu.monitor.trace import configure_tracer, get_tracer

    get_tracer().reset()
    configure_tracer(enabled=True)
    try:
        res = eng.warmup([2], 4)  # 2 seqs rounds up to the wrapper's bucket (4)
    finally:
        compiles = [e for e in get_tracer().drain()
                    if e.get("name") == "jax_compile" and e.get("args", {}).get("source") == "warmup"]
        get_tracer().reset()
    assert ("decode", 4, 4, False) in eng._compiled  # (seqs, steps, sampled)
    assert res == [{"seqs": 4, "steps": 4, "seconds": res[0]["seconds"], "cached": False}]
    assert compiles and compiles[0]["args"]["seqs"] == 4
    assert eng.warmup([4], [4])[0]["cached"] is True  # idempotent

    # serving after warmup must be unaffected: the decode scan (compiled by
    # warmup) matches a stepwise greedy put() loop token-for-token
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, size=9).astype(np.int32)
    n_keys = len(eng._compiled)
    first = eng.put([1], [prompt], sample="greedy")
    scan_toks = np.asarray(eng.decode([1], [np.asarray([int(first[0])], np.int32)], 4))[0]
    assert len(eng._compiled) - n_keys == 1  # the prefill bucket only: warmup pre-built the scan
    eng.flush(1)
    first2 = eng.put([2], [prompt], sample="greedy")
    cur, loop_toks = int(first2[0]), []
    for _ in range(4):
        out = eng.put([2], [np.asarray([cur], np.int32)], sample="greedy")
        cur = int(out[0])
        loop_toks.append(cur)
    np.testing.assert_array_equal(scan_toks, loop_toks)

    with pytest.raises(RuntimeError, match="before serving traffic"):
        eng.warmup([2], 4)  # uid 2 still tracked


def test_check_data_paths_gate():
    """Tier-1 structural gate: the stack/post-process logic must live only in
    the single host-work helper (check_timed_ops-style AST check)."""
    from tools.check_data_paths import check

    assert check() == []


def test_check_data_paths_catches_drift(tmp_path):
    """The gate actually fires on a second copy of the assembly logic."""
    from tools.check_data_paths import check

    bad = tmp_path / "engine.py"
    bad.write_text(
        "class DeepSpeedEngine:\n"
        "    def _host_prepare_batch(self, batch=None, mbs=None, step=None):\n"
        "        mbs = [self._data_post_process_func(m) for m in mbs]\n"
        "        batch = tree_map(lambda *xs: np.stack(xs), *mbs)\n"
        "        return self._apply_curriculum(batch)\n"
        "    def prefetching_loader(self, loader):\n"
        "        return self._host_prepare_batch\n"
        "    def train_batch(self, batch=None, data_iter=None):\n"
        "        batch = np.stack([next(data_iter)])  # drifted second copy\n"
        "        return batch\n"
        "    def _offload_train_batch(self, batch, rng):\n"
        "        return batch\n")
    violations = check(str(bad))
    assert any("train_batch" in v and "stack" in v for v in violations)
