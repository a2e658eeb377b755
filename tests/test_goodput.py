"""Goodput ledger + recompile sentinel (``monitor/goodput.py``) tests.

The PR 14 acceptance bars, test-enforced:

* **conservation** — every ledger's category sum matches measured wall
  clock within tolerance, with the residual disclosed as ``unattributed``
  (never silently absorbed) and double-booking disclosed as
  ``overbooked_s``: unit arithmetic, a real training engine, and the
  serving replicas under the closed-loop HTTP load of
  ``tools/serving_load.py`` (the chaos-drill arms assert the same bar in
  ``test_resilience_chaos.py``);
* **sentinel** — a steady-state run after the warmup boundary reports zero
  unexpected recompiles, while an injected cold-bucket request is flagged
  with its shape bucket and request uid/rid;
* **zero-overhead-off** — the PR 5 contract: no ledger objects, no
  threads, no compile-listener subscribers when the config block is
  absent;
* **taxonomy gate** — ``tools/check_goodput_taxonomy.py`` finds no
  unclassified tracer span in the engine/serving/resilience trees (and
  does flag a planted one).
"""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.models import TransformerConfig, TransformerLM, llama2
from deepspeed_tpu.monitor.goodput import (GoodputLedger, GoodputPlane,
                                           RecompileSentinel, SERVING_CATEGORIES,
                                           SPAN_ALLOWLIST, SPAN_TO_CATEGORY,
                                           TRAIN_CATEGORIES, configure_goodput,
                                           conservation_ok, get_goodput)
from deepspeed_tpu.monitor.health import get_health
from deepspeed_tpu.monitor.metrics import get_metrics
from deepspeed_tpu.monitor.trace import (current_compile_source, get_tracer,
                                         pop_compile_source, push_compile_source)
from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.parallel import groups

from conftest import tiny_batch

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture(autouse=True)
def _reset_goodput():
    """The plane is process-global: leave it (and the registries it implies)
    disarmed so engines in OTHER test files never pay the observing path."""
    yield
    get_goodput().shutdown()
    get_metrics().disable()
    get_metrics().reset()
    get_tracer().configure(enabled=False)
    hp = get_health()
    if hp.enabled:
        hp.shutdown()


# ---------------------------------------------------------------------------
# ledger arithmetic
# ---------------------------------------------------------------------------
def test_ledger_books_and_disloses_unattributed():
    led = GoodputLedger("train", "t")
    led.book("compute", 0.5)
    led.book("stall", -3.0)  # negative booking is ignored, never subtracts
    time.sleep(0.05)
    rep = led.report()
    assert rep["categories"]["compute"] == 0.5
    assert rep["categories"]["stall"] == 0.0
    # wall is tiny but the 0.5s booking exceeds it: disclosed as overbooked
    assert rep["overbooked_s"] > 0 and rep["unattributed_s"] == 0.0
    assert not conservation_ok(rep)


def test_ledger_conservation_and_residual_disclosure():
    led = GoodputLedger("serving", "r0")
    time.sleep(0.08)
    led.book("decode_active", 0.02)
    rep = led.report()
    # booked + unattributed == wall (exactly, by construction)
    total = sum(rep["categories"].values()) + rep["unattributed_s"]
    assert abs(total - rep["wall_s"]) < 1e-6
    assert rep["unattributed_s"] > 0  # the residual is DISCLOSED
    assert conservation_ok(rep)
    # ...and the optional bound turns under-attribution into a failure
    assert not conservation_ok(rep, max_unattributed_frac=0.25)
    assert set(rep["categories"]) == set(SERVING_CATEGORIES)
    assert abs(sum(rep["fractions"].values()) - 1.0) < 1e-3


def test_ledger_step_windows_and_explicit_subtraction():
    """step_entry books the inter-step gap as idle; step_boundary books
    input wait + the compute residual — and seconds an explicit source
    booked inside EITHER window are subtracted, never double-counted."""
    led = GoodputLedger("train", "t")
    led.step_entry()
    t0 = time.perf_counter()
    time.sleep(0.04)
    led.book("compile", 0.02)  # explicit source fires inside the step
    led.step_boundary(input_wait_s=0.01)
    step_wall = time.perf_counter() - t0
    cats = led.report()["categories"]
    assert cats["input_wait"] == pytest.approx(0.01)
    assert cats["compile"] == pytest.approx(0.02)
    # compute residual = step wall minus input wait minus the compile delta
    assert 0.0 < cats["compute"] <= step_wall - 0.03 + 5e-3
    # between-steps: an explicit booking inside the idle gap shrinks idle
    t1 = time.perf_counter()
    time.sleep(0.05)
    led.book("ckpt_blocked", 0.03)
    led.step_entry()
    gap = time.perf_counter() - t1
    led.step_boundary(0.0)
    cats = led.report()["categories"]
    assert cats["ckpt_blocked"] == pytest.approx(0.03)
    assert 0.0 < cats["idle"] <= gap - 0.03 + 5e-3  # gap minus ckpt seconds
    rep = led.report()
    assert conservation_ok(rep), rep


def test_ledger_recovery_window():
    led = GoodputLedger("train", "t")
    led.step_entry()
    led.step_boundary(0.0)
    led.note_recovery_begin()
    time.sleep(0.04)
    led.step_entry()  # restarted engine's first step entry ends the window
    cats = led.report()["categories"]
    assert cats["recovery"] >= 0.03
    assert cats["idle"] == 0.0  # the down-time is recovery, NOT idle


def test_ledger_stop_resume_books_downtime():
    led = GoodputLedger("serving", "r0")
    led.stop()
    wall_frozen = led.wall_s()
    time.sleep(0.05)
    assert led.wall_s() == pytest.approx(wall_frozen)  # clock is frozen
    led.resume("recovering")
    cats = led.report()["categories"]
    assert cats["recovering"] >= 0.04  # the frozen interval was booked
    assert conservation_ok(led.report())


# ---------------------------------------------------------------------------
# sentinel
# ---------------------------------------------------------------------------
def test_sentinel_warmup_boundary_and_attribution():
    s = RecompileSentinel()
    s.note_compile("serving", bucket="put/t64/s4", warmed=False)
    assert s.unexpected("serving") == 0  # pre-warmup compiles are expected
    s.declare_warmed("serving")
    s.set_uid_resolver("r0", lambda uid: f"req-{uid}")
    s.note_compile("serving", bucket="put/t32/s4", warmed=True, uids=[7, 9])
    rep = s.report()["serving"]
    assert rep["expected_compiles"] == 1 and rep["unexpected_compiles"] == 1
    assert rep["by_bucket"] == {"put/t32/s4": 1}
    ev = rep["recent"][-1]
    assert ev["uids"] == [7, 9] and ev["rids"] == ["req-7", "req-9"]


def test_sentinel_storm_latches_once_per_burst():
    s = RecompileSentinel(storm_k=3, storm_window_s=60.0)
    s.declare_warmed("train")
    for _ in range(5):  # one burst of 5 >= K=3 -> exactly ONE storm
        s.note_compile("train", bucket="train_step", warmed=True)
    assert s.report()["train"]["storms"] == 1
    assert s.report()["train"]["unexpected_compiles"] == 5


def test_sentinel_metrics_counters():
    get_metrics().enable()
    s = RecompileSentinel(storm_k=2, storm_window_s=60.0)
    s.note_compile("serving", bucket="b", warmed=True)
    s.note_compile("serving", bucket="b", warmed=True)
    reg = get_metrics()
    assert reg.counter("serving/unexpected_compiles_total").value == 2
    assert reg.counter("serving/compile_storms_total").value == 1


# ---------------------------------------------------------------------------
# config block + zero overhead off
# ---------------------------------------------------------------------------
def test_goodput_config_presence_enables():
    from deepspeed_tpu.monitor.config import get_monitor_config

    mc = get_monitor_config({"goodput": {}})
    assert mc.goodput.enabled and mc.goodput.train_warmup_steps == 2
    mc = get_monitor_config({"goodput": {"storm_k": 7, "stall_gap_s": 0.2}})
    assert mc.goodput.enabled and mc.goodput.storm_k == 7
    assert get_monitor_config({}).goodput.enabled is False


def test_configure_arms_and_shutdown_disarms_everything():
    plane = configure_goodput(enabled=True, storm_k=9, train_warmup_steps=5)
    assert plane.enabled and plane.sentinel.storm_k == 9
    assert plane.train_warmup_steps == 5
    assert dist.goodput_comm_hook is not None
    assert "goodput" in get_health()._gauge_providers
    led = plane.training
    assert led is not None and plane.serving_ledger("x") is not None
    plane.shutdown()
    assert not plane.enabled and plane.training is None
    assert dist.goodput_comm_hook is None
    assert "goodput" not in get_health()._gauge_providers


def test_providers_survive_health_shutdown_and_rearm():
    """HealthPlane.shutdown() clears ALL providers (drills arm/shutdown the
    health plane around the goodput plane's lifetime): a later
    configure_goodput must re-register, not early-return."""
    from deepspeed_tpu.monitor.health import configure_health

    configure_goodput(enabled=True)
    h = configure_health(enabled=True)
    assert "goodput" in h._gauge_providers
    h.shutdown()
    assert "goodput" not in h._gauge_providers
    configure_goodput(enabled=True)  # already-enabled re-arm re-registers
    h2 = configure_health(enabled=True)
    assert "goodput" in h2._gauge_providers
    assert "goodput" in h2._dump_providers


def test_gateway_warmup_token_buckets_only():
    """GatewayConfig(warmup_token_buckets=...) with NO decode warmup
    entries still pre-compiles the prefill buckets and declares the
    sentinel boundary (the knob must not be silently dead)."""
    from tools.serving_load import build_gateway

    plane = configure_goodput(enabled=True)
    gw = build_gateway(n_replicas=1, prefix_cache=False,
                       warmup_token_buckets=(16,))
    try:
        eng = gw.replicas[0].engine
        assert eng._gp_warmed  # boundary declared from token buckets alone
        assert (16, 4, "greedy") in eng._compiled or any(
            k[0] == 16 for k in eng._compiled if isinstance(k[0], int))
        assert plane.sentinel.report()["serving"]["warmed"]
        assert plane.sentinel.unexpected("serving") == 0
    finally:
        gw.stop()


def test_zero_overhead_when_block_absent(eight_devices):
    """PR 5 contract: with no ``goodput`` block the engine holds no ledger,
    the plane materializes nothing, no thread appears, and the compile
    listener has no subscribers."""
    from deepspeed_tpu.monitor import trace as trace_mod

    threads_before = set(threading.enumerate())
    groups.reset()
    model = TransformerLM(TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=64,
        intermediate_size=128, attention_impl="reference", dtype=jnp.float32))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 16, "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "tpu": {"mesh": {"data": 8}}})
    for i in range(2):
        engine.train_batch(tiny_batch(batch_size=16, seq=32, seed=i))
    plane = get_goodput()
    assert not plane.enabled
    assert engine._goodput is None and plane._training is None
    assert plane._serving == {}
    assert trace_mod._compile_subscribers == []
    new = [t for t in set(threading.enumerate()) - threads_before if t.is_alive()]
    assert not [t.name for t in new if "goodput" in t.name.lower()]
    engine.destroy()


# ---------------------------------------------------------------------------
# event feeds: compile-source routing + comm hook
# ---------------------------------------------------------------------------
def test_compile_source_routing_thread_local():
    assert current_compile_source() == "train"  # historical default
    prev = push_compile_source("serving")
    assert current_compile_source() == "serving"
    prev2 = push_compile_source("bogus")  # unknown labels clamp to train
    assert current_compile_source() == "train"
    pop_compile_source(prev2)
    pop_compile_source(prev)
    assert current_compile_source() == "train"
    # thread-locality: another thread still sees the default
    seen = {}
    push_compile_source("serving")
    t = threading.Thread(target=lambda: seen.update(s=current_compile_source()))
    t.start()
    t.join()
    pop_compile_source(None)
    assert seen["s"] == "train"


def test_compile_events_split_by_source():
    """A compile triggered under the serving scope lands in
    ``serving/compile_*`` and does NOT book into the training ledger; a
    train-scope compile does both."""
    plane = configure_goodput(enabled=True)
    led = plane.training
    reg = get_metrics()
    base_serving = reg.counter("serving/compile_events").value
    base_train = reg.counter("train/compile_events").value

    prev = push_compile_source("serving")
    try:
        jax.jit(lambda x: x * 2 + 1)(jnp.ones((17, 3))).block_until_ready()
    finally:
        pop_compile_source(prev)
    assert reg.counter("serving/compile_events").value > base_serving
    serving_compile_booked = led.report()["categories"]["compile"]

    jax.jit(lambda x: x * 3 - 1)(jnp.ones((19, 5))).block_until_ready()
    assert reg.counter("train/compile_events").value > base_train
    assert led.report()["categories"]["compile"] >= serving_compile_booked


def test_compile_interval_union_never_overbooks():
    """jax emits one duration event per compile PHASE (trace/lower/backend,
    with nested sub-traces): the ledger books the union of intervals, so
    booked compile seconds can never exceed the wall that passed."""
    plane = configure_goodput(enabled=True)
    led = plane.training
    t0 = time.perf_counter()
    for i in range(3):
        jax.jit(lambda x: x @ x.T + i)(jnp.ones((16 + i, 16 + i))).block_until_ready()
    wall = time.perf_counter() - t0
    booked = led.report()["categories"]["compile"]
    assert 0 < booked <= wall + 0.01, (booked, wall)


def test_comm_host_plane_hook_books_exposed():
    plane = configure_goodput(enabled=True)
    led = plane.training
    dist._watched_host_op("test_op", lambda: time.sleep(0.03))
    assert led.report()["categories"]["comm_exposed"] >= 0.02


# ---------------------------------------------------------------------------
# training engine end-to-end
# ---------------------------------------------------------------------------
def _train_engine(extra_cfg):
    groups.reset()
    model = TransformerLM(TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=64,
        intermediate_size=128, attention_impl="reference", dtype=jnp.float32))
    cfg = {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 2,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "tpu": {"mesh": {"data": 8}}}
    cfg.update(extra_cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


def test_training_ledger_conserves_and_sentinel_flags_recompile(eight_devices):
    engine = _train_engine({"goodput": {"train_warmup_steps": 2}})
    assert engine.config.monitor_config.goodput.enabled
    plane = get_goodput()
    for i in range(4):
        engine.train_batch(tiny_batch(batch_size=16, seq=32, seed=i))
    rep = plane.training.report()
    assert conservation_ok(rep, max_unattributed_frac=0.25), rep
    assert rep["categories"]["compute"] > 0 and rep["categories"]["compile"] > 0
    # steady state after the step-2 warmup boundary: zero unexpected
    assert plane.sentinel.unexpected("train") == 0
    assert plane.sentinel.report()["train"]["warmed"]
    # shape drift: the fused step is rebuilt post-warmup -> flagged
    engine._compiled.pop("train_step")
    engine._last_batch_struct = None
    engine.train_batch(tiny_batch(batch_size=16, seq=32, seed=9))
    assert plane.sentinel.unexpected("train") == 1
    assert plane.sentinel.report()["train"]["by_bucket"] == {"train_step": 1}
    assert get_metrics().counter("train/unexpected_compiles_total").value == 1
    engine.destroy()


def test_gauge_rows_and_health_providers():
    plane = configure_goodput(enabled=True)
    led = plane.training
    led.step_entry()
    led.step_boundary(0.0)
    plane.serving_ledger("r0").book("decode_active", 0.1)
    rows = plane.gauge_rows()
    names = {(n, lab.get("scope"), lab["category"]) for n, lab, _ in rows
             if n == "goodput/seconds_total"}
    assert ("goodput/seconds_total", "train", "compute") in names
    assert ("goodput/seconds_total", "serving:r0", "decode_active") in names
    # the disclosed residual is exported too, per scope
    assert ("goodput/seconds_total", "train", "unattributed") in names
    assert any(n == "goodput/fraction" for n, _, _ in rows)
    state = plane.report()
    assert state["train"] is not None and "r0" in state["serving"]


# ---------------------------------------------------------------------------
# serving engine + replica end-to-end
# ---------------------------------------------------------------------------
def _serving_engine():
    model = llama2("tiny", num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, vocab_size=128, max_seq_len=256,
                   dtype=jnp.float32, attention_impl="reference")
    sm = DSStateManagerConfig(max_tracked_sequences=8, max_ragged_batch_size=64,
                              max_ragged_sequence_count=4, max_context=64)
    cfg = RaggedInferenceEngineConfig(kv_block_size=8, num_kv_blocks=32,
                                      kv_dtype=jnp.float32, state_manager=sm,
                                      use_pallas_kernels="never")
    return InferenceEngineV2(model, cfg)


def test_serving_sentinel_steady_state_silent_cold_bucket_flagged():
    """Acceptance: after ``warmup()`` a steady-state run reports ZERO
    unexpected recompiles; an injected cold-bucket request is flagged with
    its bucket and request uid/rid."""
    plane = configure_goodput(enabled=True)
    eng = _serving_engine()
    eng.goodput_ledger = plane.serving_ledger("eng")
    eng.gp_rid_resolver = lambda uid: f"req-{uid}"
    # t8 covers the 1-token decode put, t16 the 12-token prefill below
    res = eng.warmup([4], [2], token_buckets=[8, 16], put_samples=("greedy",))
    assert any("tokens" in r for r in res)  # prefill buckets pre-compiled
    rng = np.random.default_rng(0)
    # steady state: both prompts land in warmed (token, seq) buckets
    first = eng.put([1], [rng.integers(0, 128, size=12).astype(np.int32)],
                    sample="greedy")
    eng.put([1], [np.asarray([int(first[0])], np.int32)], sample="greedy")
    assert plane.sentinel.unexpected("serving") == 0
    # injected cold bucket: logits-mode put was never warmed
    eng.put([2], [rng.integers(0, 128, size=5).astype(np.int32)])
    assert plane.sentinel.unexpected("serving") == 1
    rep = plane.sentinel.report()["serving"]
    [(bucket, n)] = rep["by_bucket"].items()
    assert bucket.startswith("put/") and bucket.endswith("/logits") and n == 1
    ev = rep["recent"][-1]
    assert ev["uids"] == [2] and ev["rids"] == ["req-2"]
    # the forward walltime landed in the ledger and the ledger conserves
    led_rep = eng.goodput_ledger.report()
    assert led_rep["categories"]["prefill_active"] > 0
    assert conservation_ok(led_rep), led_rep


def test_warmup_declare_warmed_false_defers_boundary():
    """A caller warming in several calls (the replica's per-entry loop)
    defers the sentinel boundary: entries 2..N's own warmup compiles stay
    EXPECTED, and the explicit declaration arms flagging afterwards."""
    plane = configure_goodput(enabled=True)
    eng = _serving_engine()
    eng.warmup([4], [2], declare_warmed=False)
    assert not eng._gp_warmed
    eng.warmup([4], [3], declare_warmed=False)  # 2nd entry compiles...
    assert plane.sentinel.unexpected("serving") == 0  # ...unflagged
    # the replica's prefill pass: empty decode_steps, token buckets only
    # (GatewayConfig.warmup_token_buckets reaches warmup through this shape)
    res = eng.warmup([4], [], token_buckets=[8], declare_warmed=False)
    assert res and all("tokens" in r for r in res)
    assert plane.sentinel.unexpected("serving") == 0
    eng.declare_gp_warmed()
    assert eng._gp_warmed and plane.sentinel.report()["serving"]["warmed"]


def test_serving_ledger_registry_fresh_per_generation():
    plane = configure_goodput(enabled=True)
    led1 = plane.serving_ledger("0")
    assert plane.serving_ledger("0") is led1  # live ledger is reused
    led1.stop()
    led2 = plane.serving_ledger("0")  # a stopped one belongs to a previous
    assert led2 is not led1            # generation: fresh clock


def test_closed_loop_http_load_ledgers_conserve():
    """Acceptance (a): under the closed-loop HTTP load of
    ``tools/serving_load.py`` every replica ledger sums to wall clock
    within tolerance, active categories are populated, and the idle wait
    is booked as idle — not laundered into an active bucket."""
    from tools.serving_load import build_gateway, make_workload, run_http_load

    configure_goodput(enabled=True)
    plane = get_goodput()
    gw = build_gateway(n_replicas=2, prefix_cache=True)
    try:
        wl = make_workload(10, prompt_lo=8, prompt_hi=24, new_lo=3, new_hi=8,
                           rate_rps=None, seed=0, uid_base=100)
        agg, recs = run_http_load(gw.config.host, gw.port, wl, concurrency=3,
                                  stream=False, timeout_s=60.0)
        assert agg["completed"] == len(recs)
        time.sleep(0.05)  # one idle-wait bracket lands after the last request
        reps = {r.name: r._goodput.report() for r in gw.replicas
                if r._goodput is not None}
        assert len(reps) == 2
        for name, rep in reps.items():
            # the unattributed bound makes silent hook-loss a failure here:
            # driver-loop wall is almost entirely attributable
            assert conservation_ok(rep, max_unattributed_frac=0.25), (name, rep)
            assert rep["categories"]["idle"] > 0
        active = sum(rep["categories"]["prefill_active"]
                     + rep["categories"]["decode_active"] for rep in reps.values())
        assert active > 0
    finally:
        gw.stop()
    # stop() froze the replica clocks: reports stay conserved afterwards,
    # and the sentinel dropped its strong refs to the dead replicas
    for rep in (r._goodput.report() for r in gw.replicas if r._goodput is not None):
        assert conservation_ok(rep)
    assert not plane.sentinel._uid_resolvers


# ---------------------------------------------------------------------------
# taxonomy gate
# ---------------------------------------------------------------------------
def test_taxonomy_gate_clean_on_repo():
    from tools.check_goodput_taxonomy import check, load_contract

    assert check() == []
    mapping, allowlist, categories = load_contract()
    assert mapping == SPAN_TO_CATEGORY and allowlist == set(SPAN_ALLOWLIST)
    assert set(mapping.values()) <= set(TRAIN_CATEGORIES) | set(SERVING_CATEGORIES)
    assert not set(mapping) & allowlist  # "exactly one" table per span


def test_taxonomy_gate_flags_planted_violations(tmp_path):
    from tools.check_goodput_taxonomy import find_violations

    pkg = tmp_path / "pkg"
    (pkg / "monitor").mkdir(parents=True)
    (pkg / "serving").mkdir()
    for scan in ("runtime", "elasticity", "inference"):
        (pkg / scan).mkdir()
    (pkg / "runtime" / "resilience").mkdir()
    (pkg / "runtime" / "engine.py").write_text("")
    (pkg / "monitor" / "goodput.py").write_text(
        'SPAN_TO_CATEGORY = {"serving/decode": "decode_active",\n'
        '                    "bad_span": "no_such_category"}\n'
        'SPAN_ALLOWLIST = ("serving/decode",)\n'
        'TRAIN_CATEGORIES = ("compute",)\n'
        'SERVING_CATEGORIES = ("decode_active",)\n')
    (pkg / "serving" / "x.py").write_text(
        'def f(tr, name):\n'
        '    tr.instant("never_classified")\n'
        '    tr.complete(f"dyn_{name}", 0, 1)\n'
        '    tr.span("serving/decode")\n')
    why = {v[3].split(" ")[0] + "|" + str(v[2]) for v in find_violations(str(pkg))}
    bad = find_violations(str(pkg))
    reasons = " | ".join(w for _, _, _, w in bad)
    assert "unknown category" in reasons            # broken contract value
    assert "BOTH" in reasons                        # span in both tables
    assert "not in goodput SPAN_TO_CATEGORY" in reasons  # unclassified span
    assert "dynamic span name" in reasons           # f-string emission
    assert why  # sanity: structured rows carry names/snippets
