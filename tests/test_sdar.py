"""SDAR (``models/sdar.py``): a decoder under a block-causal mask whose rows
are generated a BLOCK at a time by masked diffusion, through the normal
serving path (``InferenceEngineV2.put`` / ``decode``,
``DynamicSplitFuseScheduler``), against the plain reference
``benchmark/lib/sdar_reference.py`` on seeded weights, tiny preset, float32,
on the CPU."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import loader, sdar_reference as ref
from deepspeed_tpu.inference.v2 import (DiffusionConfig, DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, PrefixCacheConfig, RaggedInferenceEngineConfig,
                                        SamplingParams, SpeculativeConfig)
from deepspeed_tpu.models import TransformerLM, sdar, sdar_config

B = 4
TOL = 2e-4  # float32 on both sides: rounding; a wrong mask or a stale K/V is of order one


def _cf():
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "configs", "tiny-sdar.json"))


def _hp(**over):
    cf = _cf()
    cf["overrides"]["mask_token_id"] = 511  # the tiny preset's
    cf["engine"]["generation"].update(over)
    return ref.hyper_from_published(cf)


@pytest.fixture(scope="module")
def weights():
    model = sdar("tiny", dtype=jnp.float32)
    return model, jax.jit(lambda k: model.init(k, None))(jax.random.PRNGKey(5))


def _engine(model, params, kv_blocks=48, prefix=False, **diffusion):
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64, max_ragged_sequence_count=8,
                              max_context=192, token_buckets=(64, ), seq_buckets=(8, ))
    icfg = RaggedInferenceEngineConfig(kv_block_size=16, num_kv_blocks=kv_blocks, kv_dtype=jnp.float32,
                                       state_manager=sm, diffusion=DiffusionConfig(**diffusion),
                                       prefix_cache=PrefixCacheConfig(enabled=prefix))
    return InferenceEngineV2(model, icfg, params=params)


@pytest.fixture(scope="module")
def plain(weights):
    """The module's ONE engine of the default configuration, for the tests that
    change nothing of it: each flushes what it fed (``_run`` does, a scheduler
    that ran to its end has), so the next finds the pool whole."""
    return _engine(*weights)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 500, n).astype(np.int32)


def _prefill(engine, uid, prompt, chunks):
    """The prompt's whole blocks in the given chunks; returns its rest, which
    opens the first generated block."""
    whole, at = prompt.size // B * B, 0
    for n in chunks:
        engine.put([uid], [prompt[at:at + n]], sample="greedy")
        at += n
    assert at == whole
    return prompt[whole:]


def _forward_errors(hp, params, prompt, calls):
    """Relative L2 of EVERY denoise forward's logits, through the cache,
    against the reference's pass over (committed tokens + the forward's ids)."""
    committed, errors = list(prompt[:prompt.size // B * B]), []
    for toks, probe in calls:
        for b in range(probe["ids"].shape[0]):
            for i in range(int(probe["forwards"][b])):
                ids = probe["ids"][b, i, 0]
                seq = np.concatenate([np.asarray(committed, np.int32), ids])
                want = np.asarray(ref.forward_logits(hp, params, seq, np.arange(seq.size),
                                                     at=np.arange(seq.size - B, seq.size)))
                got = probe["logits"][b, i, 0]
                errors.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
            committed += list(toks[0, b * B:(b + 1) * B])
    return errors


def _run(engine, prompt, chunks, calls=(8, 4), uid=7):
    known = _prefill(engine, uid, prompt, chunks)
    out = [engine.decode([uid], [known] if c == 0 else None, n, probe=(0, )) for c, n in enumerate(calls)]
    engine.flush(uid)
    return out


@pytest.mark.parametrize("r", [0, 1, 2, 3])
def test_every_denoise_forward_through_chunked_prefill_and_the_cache_agrees_with_the_reference(weights, plain, r):
    model, params = weights
    prompt = _prompt(40 + r, seed=r)
    calls = _run(plain, prompt, (8, 20, 12))
    errors = _forward_errors(_hp(), params, prompt, calls)
    # an open block of r tokens takes 4 - r forwards, every other block 4
    assert len(errors) == (B - r) + 2 * 4 and max(errors) < TOL, errors
    assert [int(c[1]["forwards"].sum()) for c in calls] == [(B - r) + 4, 4]
    assert calls[0][0][0, :r].tolist() == prompt[40:].tolist(), "a row's open tokens lead its first block"
    assert not (np.concatenate([c[0] for c in calls], axis=1) == 511).any(), "no mask is left"


def _stale_kv(engine):
    """A denoise forward leaves the K/V of the positions already unmasked as
    the cache had them; only the commit writes them."""
    step, bs = engine._ragged_step, engine.config.kv_block_size

    def stale(params, packed, pools, T, S, **kw):
        out = step(params, packed, pools, T, S, **kw)
        if kw.get("kv_only"):
            return out
        ids, seq_idx, pos, valid = (packed[i * T:(i + 1) * T] for i in range(4))
        tables = packed[4 * T:4 * T + S * engine._max_blocks_per_seq].reshape(S, -1)
        slot = tables[seq_idx, pos // bs] * bs + pos % bs
        keep_old = jnp.zeros(pools[0].shape[1], bool).at[slot].max((ids != 511) & (valid > 0))
        new = tuple(jnp.where(keep_old[None, :, None, None], old, fresh) for old, fresh in zip(pools, out[1]))
        return (out[0], new) + tuple(out[2:])

    engine._ragged_step = stale


@pytest.mark.parametrize("control", ["causal_mask", "no_commit", "stale_kv"])
def test_a_control_fails_the_comparison(weights, control):
    from benchmark.builders import serve_diffusion

    model, params = weights
    engine = _engine(model, params)
    if control == "stale_kv":
        _stale_kv(engine)
    else:  # the controls of the benchmark's check, as its builder applies them
        serve_diffusion.apply_control(engine, control)
    prompt = _prompt(42)
    errors = _forward_errors(_hp(), params, prompt, _run(engine, prompt, (8, 20, 12)))
    assert max(errors) > 100 * TOL, (control, errors)


def test_one_pass_over_the_clean_sequence_and_every_noisy_block_equals_a_pass_a_forward(weights, plain):
    """What the benchmark's check does on the chip: ALL denoise forwards in ONE
    reference pass, the final sequence followed by each forward's noisy block
    under an explicit ``visible``."""
    from benchmark.builders import serve_diffusion

    model, params = weights
    hp, prompt = _hp(), _prompt(43)
    calls = _run(plain, prompt, (8, 20, 12))
    probe = serve_diffusion.probe_of_row([c[1] for c in calls], 0)
    tokens = np.concatenate([c[0][0] for c in calls])
    one = serve_diffusion.reference_logits(ref, hp, params, prompt, tokens, probe, B)
    got = [probe["logits"][b, i] for b in range(3) for i in range(int(probe["forwards"][b]))]
    assert len(one) == len(got) == 9
    per_forward = _forward_errors(hp, params, prompt, calls)
    for g, o, e in zip(got, one, per_forward):
        assert abs(float(np.linalg.norm(g - o) / np.linalg.norm(o)) - e) < 5e-5


def test_a_block_of_one_is_the_causal_path(weights):
    """``B = 1`` and one denoise step: a block is one MASK, its forward is the
    causal model fed MASK at that position, its commit the causal model fed
    the token; tokens and logits are the causal engine's."""
    _, params = weights
    one = TransformerLM(sdar_config("tiny", dtype=jnp.float32, diffusion_block_size=1))
    causal = TransformerLM(sdar_config("tiny", dtype=jnp.float32, diffusion_block_size=0, mask_token_id=None))
    assert jax.tree_util.tree_structure(jax.eval_shape(lambda k: one.init(k, None), jax.random.PRNGKey(0))) == \
        jax.tree_util.tree_structure(jax.eval_shape(lambda k: causal.init(k, None), jax.random.PRNGKey(0)))
    prompt = _prompt(21)
    eng = _engine(one, params, denoising_steps=1)
    eng.put([1], [prompt], sample="greedy")
    toks, probe = eng.decode([1], None, 6, probe=(0, ))
    ceng = _engine(causal, params)
    ceng.put([1], [prompt], sample="greedy")
    for j in range(6):
        seq = ceng.state_manager.get_sequence(1)
        logits = np.asarray(ceng.put([1], [np.asarray([511], np.int32)], sample=None))[0]
        assert int(logits.argmax()) == int(toks[0, j])
        np.testing.assert_allclose(logits, probe["logits"][j, 0, 0, 0], rtol=1e-5, atol=1e-5)
        ceng.state_manager.rollback_to(seq, seq.seen_tokens - 1)
        ceng.put([1], [toks[0, j:j + 1]], sample="greedy")  # the commit


@pytest.mark.parametrize("strategy", ["low_confidence_static", "low_confidence_dynamic"])
@pytest.mark.parametrize("steps", [4, 3, 2])
def test_the_unmasking_rules_on_given_logits_agree_with_the_reference(strategy, steps):
    from deepspeed_tpu.inference.v2.sampling import diffusion_candidates, diffusion_quota, diffusion_unmask

    rng = np.random.default_rng(steps)
    assert diffusion_quota(B, steps) == ref.quotas(B, steps) and sum(ref.quotas(B, steps)) == B
    for trial in range(40):
        sharp = rng.choice([0.5, 8.0, 30.0])  # flat rows never pass the threshold, sharp ones do
        logits = rng.normal(size=(3, B, 64)).astype(np.float32) * sharp
        logits[0, 1] = logits[0, 2]  # equal confidences go by position
        masked = rng.random((3, B)) < 0.7
        tok, conf = diffusion_candidates(jnp.asarray(logits.reshape(-1, 64)))
        top = logits.max(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(conf).reshape(3, B), 1.0 / np.exp(logits - top).sum(-1), rtol=1e-5)
        assert np.asarray(tok).reshape(3, B).tolist() == logits.argmax(-1).tolist()
        for i in range(steps):
            got = np.asarray(diffusion_unmask(conf.reshape(3, B), jnp.asarray(masked), strategy,
                                              diffusion_quota(B, steps)[i], 0.9, i == steps - 1))
            want = np.stack([ref.unmask(np.asarray(conf).reshape(3, B)[s], masked[s], strategy,
                                        ref.quotas(B, steps)[i], 0.9, i == steps - 1) for s in range(3)])
            assert got.tolist() == want.tolist(), (trial, i)


@pytest.mark.parametrize("strategy,steps", [("low_confidence_static", 4), ("low_confidence_dynamic", 4),
                                            ("low_confidence_static", 2)])
def test_the_engine_generates_what_the_reference_generates(weights, strategy, steps):
    model, params = weights
    prompt = _prompt(22, seed=3)
    engine = _engine(model, params, remasking=strategy, denoising_steps=steps, confidence_threshold=0.02)
    calls = _run(engine, prompt, (20, ), calls=(12, ))
    want, forwards = ref.generate(_hp(denoising_steps=steps, confidence_threshold=0.02), params, prompt, 3, strategy)
    assert calls[0][0][0].tolist() == want.tolist()
    assert int(calls[0][1]["forwards"].sum()) == len(forwards)
    if strategy == "low_confidence_dynamic":
        assert len(forwards) < 2 + 2 * 4, "over so low a threshold some forward unmasks several positions"


def _beside(a, b):
    """A row's block before, then its block: the rows of two ``[S x B]`` token arrays side by side."""
    return np.concatenate([np.asarray(a).reshape(-1, B), np.asarray(b).reshape(-1, B)], axis=1).reshape(-1)


def test_a_block_calls_span_counts_the_tiled_grid_forward_by_forward(weights, monkeypatch):
    """``serving/decode`` of shapes ``paged_attn_q_tiled`` took (planted: off
    the TPU none does) carries ``tile_kv_live``, the live (tile, column) pairs,
    ``tile_kv_steps``, the grid steps at the choice's four 16-token blocks a
    step, and ``tile_kv_bound``, each summed over the call's forwards and
    layers, a forward SHAPE at a time: a block's first forward is of 2B tokens
    a row (the block before, whose commit it is, then the block, under the
    bound ``pos | 3``, the pad run's too) at that shape's tile, its others of
    B tokens a row, and the call's one commit runs all but the last layer's
    attention; not ``kv_live``/``kv_steps``."""
    from deepspeed_tpu.monitor.trace import get_tracer
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    model, params = weights
    engine = _engine(model, params)
    uids, prompts = [3, 4, 5], [_prompt(40, 1), _prompt(12, 2), _prompt(23, 3)]
    known = [_prefill(engine, uid, p, (p.size // B * B, )) for uid, p in zip(uids, prompts)]
    engine.decode(uids, known, 8)                                    # trace the program first
    layers, max_blocks, S = model.config.num_layers, 192 // 16, 8
    for tokens, tile in ((S * B, 8), (S * 2 * B, 16)):
        monkeypatch.setitem(pa.KERNEL_CHOICES, (tokens, S, max_blocks),
                            {"kernel": "paged_attn_q_tiled", "q_tile": tile, "blocks_per_step": 4, "rule": "planted"})
    batches, finalize = [], engine._block_batch.finalize
    monkeypatch.setattr(engine._block_batch, "finalize", lambda: batches.append(finalize()) or batches[-1])
    get_tracer().reset()
    tracer = get_tracer().configure(enabled=True)
    try:
        engine.decode(uids, None, 8)
        (span, ) = [e["args"] for e in tracer.drain() if e["ph"] == "X" and e["name"] == "serving/decode"]
    finally:
        get_tracer().reset()
    assert span["kernel"] == "paged_attn_q_tiled:8:planted+paged_attn_q_tiled:16:planted" and span["blocks"] == 2
    assert (span["denoise_forwards"], span["commit_forwards"], span["fused_commits"], span["steps"]) == (8, 1, 1, 9)
    assert span["tokens_fed"] == 3 * B * (9 + 1)
    (rb, ) = batches                                                 # the call's descriptor: its first block
    tables = jnp.asarray(rb.block_tables)

    def grid(seq_idx, pos, tile):  # (live pairs, grid steps) of one attention call
        return [int(pa._tiled_work_list(tables, jnp.asarray(seq_idx), jnp.asarray(pos | (B - 1)), 16, None, tile,
                                        per_step=per)[8]) for per in (1, 4)]

    live = steps = 0
    for b in range(2):
        pos = rb.token_pos + b * B
        first = grid(_beside(rb.token_seq_idx, rb.token_seq_idx), _beside(np.maximum(pos - B, 0), pos), 16)
        others = grid(rb.token_seq_idx, pos, 8)
        calls = 3 * layers + (layers - 1 if b == 1 else 0)           # three more denoise forwards, the last commit
        live += layers * first[0] + calls * others[0]
        steps += layers * first[1] + calls * others[1]
    assert span["tile_kv_live"] == live > 0 and span["tile_kv_steps"] == steps and live / 4 <= steps < live
    assert span["tile_kv_bound"] == max_blocks * (2 * layers * (S * 2 * B // 16 + S + 1)
                                                  + (7 * layers - 1) * (S * B // 8 + S + 1))
    assert not {"kv_live", "kv_steps"} & set(span)


@pytest.fixture(scope="module")
def written_out(weights):
    """An engine a rule, with the two forwards a block took before a commit
    rode anywhere, jitted from the engine's own ``_ragged_step``: a denoise
    forward and a ``kv_only`` commit of ``S x B`` tokens."""
    model, params = weights
    made = {}

    def of(rule):
        if rule not in made:
            engine = _engine(model, params, remasking=rule, confidence_threshold=0.02)
            step = engine._ragged_step
            made[rule] = (engine,
                          jax.jit(lambda packed, pools: step(params, packed, pools, 8 * B, 8, gather_k=B - 1, moe_stats=True)),
                          jax.jit(lambda packed, pools: step(params, packed, pools, 8 * B, 8, moe_stats=True, kv_only=True)))
        return made[rule]

    return of


@pytest.mark.parametrize("probed", [False, True], ids=["timed", "probed"])
@pytest.mark.parametrize("rule", ["low_confidence_static", "low_confidence_dynamic"])
@pytest.mark.parametrize("n_blocks", [1, 2, 4])
def test_a_call_whose_commits_ride_equals_the_sequence_written_out(written_out, monkeypatch, n_blocks, rule, probed):
    """A call of ``n_blocks`` blocks against the same call written out forward
    by forward in the order it had before a commit rode anywhere: a block's
    denoise forwards while a mask is left, then a ``kv_only`` commit of its
    final ids, each of ``S x B`` tokens through the engine's own
    ``_ragged_step``, from the same pools and the same descriptor. Tokens,
    forwards a block, every forward's ids and logits (of the probed row) and
    the K/V pools after the call are the written-out sequence's; the call ran
    ONE commit forward, ``n_blocks - 1`` rode. Rows: a prompt of whole blocks,
    one shorter than a block (nothing committed: its block before would lie
    before position 0) and one that opens its first block."""
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatchWrapper
    from deepspeed_tpu.inference.v2.sampling import diffusion_candidates, diffusion_quota, diffusion_unmask
    from deepspeed_tpu.monitor.trace import get_tracer

    engine, denoise, commit = written_out(rule)
    uids, prompts = [3, 4, 5], [_prompt(40, 1), _prompt(3, 2), _prompt(23, 3)]
    engine.state_manager.get_or_create_sequence(4)                   # admitted with nothing to prefill
    known = [_prefill(engine, uid, p, (p.size // B * B, ) if p.size >= B else ()) for uid, p in zip(uids, prompts)]
    kv, S, T = engine.state_manager.kv_cache, 8, 8 * B
    before = [np.asarray(p) for p in kv.pools()]
    batches, finalize = [], RaggedBatchWrapper.finalize
    monkeypatch.setattr(RaggedBatchWrapper, "finalize", lambda self: batches.append(finalize(self)) or batches[-1])
    get_tracer().reset()
    tracer = get_tracer().configure(enabled=True)
    try:
        out = engine.decode(uids, known, n_blocks * B, probe=(2, ) if probed else ())
        (span, ) = [e["args"] for e in tracer.drain() if e["ph"] == "X" and e["name"] == "serving/decode"]
    finally:
        get_tracer().reset()
    toks, probe = out if probed else (out, None)
    after = [np.asarray(p) for p in kv.pools()]
    packed = batches[0].packed()                                     # the call's descriptor: its first block
    for uid in uids:
        engine.flush(uid)

    pools, valid = tuple(jnp.asarray(p) for p in before), packed[3 * T:4 * T] > 0
    want, forwards, fed = [], [], []
    for b in range(n_blocks):
        at = packed.copy()
        at[2 * T:3 * T] += b * B
        ids = packed[0:T].copy() if b == 0 else np.full(T, 511, np.int32)
        i = 0
        while i < 4 and (valid & (ids == 511)).any():
            at[0:T] = ids
            logits, pools, _ = denoise(jnp.asarray(at), pools)
            fed.append((b, i, ids.reshape(S, B)[2].copy(), np.asarray(logits).reshape(S, B, -1)[2]))
            tok, conf = diffusion_candidates(logits)
            masked = valid & (ids == 511)
            choose = np.asarray(diffusion_unmask(conf.reshape(S, B), jnp.asarray(masked.reshape(S, B)), rule,
                                                 diffusion_quota(B, 4)[i], 0.02, i == 3)).reshape(T)
            ids, i = np.where(choose, np.asarray(tok), ids), i + 1
        at[0:T] = ids
        _, pools, _ = commit(jnp.asarray(at), pools)
        want.append(ids.reshape(S, B)[:3])
        forwards.append(i)
    assert np.asarray(toks).tolist() == np.concatenate(want, axis=1).tolist()
    assert not (np.asarray(toks) == 511).any() and toks[1, :3].tolist() == prompts[1].tolist()
    assert (span["denoise_forwards"], span["commit_forwards"], span["fused_commits"]) == (sum(forwards), 1, n_blocks - 1)
    assert span["steps"] == span["denoise_forwards"] + 1 and span["tokens_fed"] == 3 * B * (span["steps"] + n_blocks - 1)
    if rule == "low_confidence_static":
        assert span["steps"] == 4 * n_blocks + 1
    else:
        assert span["steps"] < 4 * n_blocks + 1, "over so low a threshold some forward unmasks several positions"
    for got, kept in zip(after, pools):
        np.testing.assert_allclose(got, np.asarray(kept), rtol=1e-5, atol=1e-5)
    assert any(np.abs(a - b).max() > 1e-3 for a, b in zip(after, before)), "the call wrote the cache"
    if probed:
        assert probe["forwards"].tolist() == forwards
        for b, i, ids, logits in fed:
            assert probe["ids"][b, i, 0].tolist() == ids.tolist(), (b, i)
            np.testing.assert_allclose(probe["logits"][b, i, 0], logits, rtol=1e-4, atol=1e-4)


def test_a_row_buckets_calls_of_two_blocks_and_more_are_one_program(weights):
    """The blocks a call advances are an argument of the program: a row bucket
    has one program of one block (its forwards all of ``S x B`` tokens: two
    traces of the step) and one for every longer call up to the scheduler's
    burst (three traces: the forward that carries a commit, the others, the
    ``kv_only`` commit), so warming calls of 1, 2, 4 and 8 blocks builds two;
    a call past the burst builds one of its own. Whatever program serves it, a
    call generates what the reference generates."""
    model, params = weights
    engine = _engine(model, params, kv_blocks=64)
    step, traced = engine._ragged_step, []
    engine._ragged_step = lambda *a, **kw: traced.append((a[3], kw.get("kv_only", False))) or step(*a, **kw)
    warmed = engine.warmup([8], [B, 2 * B, 4 * B, 8 * B])
    assert [(w["steps"], w["cached"]) for w in warmed] == [(B, False), (2 * B, False), (4 * B, True), (8 * B, True)]
    assert traced == [(8 * B, False), (8 * B, True), (16 * B, False), (8 * B, False), (8 * B, True)]
    assert sorted(k[2] for k in engine._compiled if k[0] == "diffuse") == [1, 8]
    prompt = _prompt(22, seed=3)
    engine.put([7], [prompt[:20]], sample="greedy")
    n_traced = len(traced)
    out = np.concatenate([engine.decode([7], [prompt[20:]] if c == 0 else None, n) for c, n in enumerate((4, 8, 16, 32, 64))],
                         axis=1)
    engine.flush(7)
    assert len(traced) == n_traced + 3 and sorted(k[2] for k in engine._compiled if k[0] == "diffuse") == [1, 8, 16]
    want, _ = ref.generate(_hp(), params, prompt, 31, "low_confidence_static")
    assert out[0].tolist() == want.tolist()


def _scheduler(engine):
    return engine, DynamicSplitFuseScheduler(engine, token_budget=32)


def test_requests_through_the_scheduler_with_stops_inside_blocks(weights, plain):
    """Prompts that end inside a block and before one, answers that end inside
    one: every request gets exactly its tokens, the reference's, and the pool
    is whole again."""
    model, params = weights
    engine, sched = _scheduler(plain)
    seen = []
    sched.step_observer = lambda uids, sizes, t0, dur, kind: seen.append((kind, list(uids), list(sizes)))
    requests = {1: (_prompt(13, 1), 10), 2: (_prompt(3, 2), 7), 3: (_prompt(40, 3), 9), 4: (_prompt(16, 4), 8)}
    for uid, (prompt, n) in requests.items():
        sched.submit(uid, prompt, max_new_tokens=n)
    out = sched.run()
    for uid, (prompt, n) in requests.items():
        r = prompt.size % B
        want, _ = ref.generate(_hp(), params, prompt, -(-(n + r) // B), "low_confidence_static")
        assert out[uid] == want[r:r + n].tolist(), uid
    assert engine.free_blocks == 48 and engine.state_manager.n_tracked_sequences == 0
    emitted = {uid: 0 for uid in requests}
    for kind, uids, sizes in seen:
        if kind == "decode":
            for uid, n in zip(uids, sizes):
                emitted[uid] += n
    assert emitted == {uid: n for uid, (_, n) in requests.items()}, "the observer is told the tokens a row EMITTED"
    assert all(size % B == 0 for kind, _, sizes in seen if kind == "put" for size in sizes), "chunks end on blocks"


def test_an_eos_inside_a_block_drops_the_blocks_tail(weights, plain):
    model, params = weights
    prompt = _prompt(18, 9)
    want, _ = ref.generate(_hp(), params, prompt, 4, "low_confidence_static")
    new = want[2:].tolist()
    eos = new[5]  # inside the second generated block
    cut = new.index(eos) + 1
    engine, sched = _scheduler(plain)
    sched.submit(1, prompt, max_new_tokens=14, eos_token_id=eos)
    assert sched.run()[1] == new[:cut]
    assert engine.free_blocks == 48


def test_a_cancel_in_mid_block_leaves_the_committed_length_the_pool_and_the_prefix_hashes_right(weights):
    """A request cancelled before its first burst (3 prompt tokens wait for it,
    no K/V of theirs), and one cancelled after a burst that ended a row inside
    a block: the committed length is whole blocks, every block comes back, and
    the prefix cache holds whole committed KV blocks alone."""
    model, params = weights
    engine, sched = _scheduler(_engine(model, params, prefix=True))
    prompt = _prompt(39, 11)  # 36 whole + 3 open; KV blocks of 16
    sched.submit(1, prompt, max_new_tokens=30)
    sched.step()
    sched.step()  # 32 + 4 tokens prefilled
    seq = engine.state_manager.get_sequence(1)
    assert (seq.seen_tokens, seq.in_flight_tokens) == (36, 0), "the 3 open tokens wait in the scheduler"
    sched.cancel(1)
    pc = engine.prefix_cache
    assert engine.state_manager.n_tracked_sequences == 0 and engine.available_blocks == 48
    assert pc.n_cached_blocks == 2, "two whole KV blocks of committed prompt, not the third (4 of 16 tokens)"
    # the same prompt again: the 32 cached tokens are skipped, and a partial hit ends where a block does
    sched.submit(2, prompt, max_new_tokens=6)
    sched.step()
    assert sched.stats["prefill_tokens_skipped"] == 32
    sched.step()  # one burst of 2 blocks: 3 open + 5 new, of which the 6th token ... the request wants 6
    sched.step()
    out = sched.results[2]
    want, _ = ref.generate(_hp(), params, prompt, 3, "low_confidence_static")
    assert out == want[3:9].tolist()
    assert engine.state_manager.n_tracked_sequences == 0 and engine.available_blocks == 48
    # 39 + 6 = 45 tokens kept: 44 committed (11 blocks), of which 2 whole KV blocks of 16
    assert pc.n_cached_blocks == 2


def test_what_the_family_refuses_it_refuses_by_name(weights, plain):
    model, params = weights
    ids = jnp.zeros((1, 8), jnp.int32)
    from deepspeed_tpu.models.transformer import forward_hidden, forward_with_cache

    for whole_sequence in (lambda: forward_hidden(model.config, params, ids),
                           lambda: forward_with_cache(model.config, params, ids, None)):
        with pytest.raises(NotImplementedError, match="block-causal mask"):
            whole_sequence()
    engine = plain
    engine.put([1], [_prompt(8)], sample="greedy")
    with pytest.raises(NotImplementedError, match="diffusion_block_size=4"):
        engine.speculate_decode([1], [np.zeros(1, np.int32)], [np.zeros(2, np.int32)], 2)
    with pytest.raises(NotImplementedError, match="temperature sampling"):
        engine.decode([1], None, 4, sampling=[SamplingParams(temperature=0.7)])
    with pytest.raises(ValueError, match="whole blocks"):
        engine.decode([1], None, 6)
    with pytest.raises(ValueError, match="fewer than 4 known tokens"):
        engine.decode([1], [_prompt(4)], 4)
    with pytest.raises(ValueError, match="whole blocks"):
        engine.put([1], [_prompt(6)], sample="greedy")
    engine.flush(1)
    assert engine.free_blocks == 48 and engine.state_manager.n_tracked_sequences == 0, "a refusal kept nothing"
    with pytest.raises(NotImplementedError, match="speculative decoding"):
        DynamicSplitFuseScheduler(engine, speculative=SpeculativeConfig(mode="ngram"))
    with pytest.raises(NotImplementedError, match="temperature sampling"):
        DynamicSplitFuseScheduler(engine).submit(5, _prompt(9), sampling=SamplingParams(temperature=0.7))
    with pytest.raises(NotImplementedError, match="speculative decoding"):
        sm = DSStateManagerConfig(max_context=64)
        InferenceEngineV2(model, RaggedInferenceEngineConfig(
            kv_block_size=16, num_kv_blocks=8, state_manager=sm, speculative=SpeculativeConfig(mode="ngram")),
            params=params)


def test_the_published_widths_and_a_causal_model_as_it_was():
    cfg = sdar_config("30b-a3b")
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2048, 48, 32, 4, 128)
    assert (cfg.moe_num_experts, cfg.moe_top_k, cfg.expert_size, cfg.intermediate_size) == (128, 8, 768, 6144)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.rope_theta, cfg.norm_eps) == (151936, 32768, 1000000.0, 1e-6)
    assert (cfg.diffusion_block_size, cfg.mask_token_id, cfg.qk_norm, cfg.sliding_window) == (4, 151669, True, None)
    assert cfg.moe_norm_topk_prob and cfg.moe_dropless and not cfg.tie_embeddings and cfg.moe_num_shared_experts == 0
    from deepspeed_tpu.models import mellum_config, mistral_config

    for other in (mistral_config("tiny"), mellum_config("tiny")):
        assert other.diffusion_block_size == 0 and other.mask_token_id is None
        assert not any("block-causal" in why for why in other.unscannable)
    with pytest.raises(ValueError, match="power of two"):
        sdar_config("tiny", diffusion_block_size=3)
    with pytest.raises(NotImplementedError, match="sliding window"):
        sdar_config("tiny", sliding_window=16)
