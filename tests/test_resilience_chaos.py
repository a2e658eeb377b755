"""Chaos plane + elastic live remesh suite (ISSUE 12).

Covers: the generalized injection registry (handles, context managers,
seeded deterministic schedules), the elastic agent's retryable-exception
set and restart-budget decay, warm remesh from a live host snapshot
(bit-exact against the disk universal path, no checkpoint payload read),
``run_resilient`` falling back past a newest tag corrupted between
attempts, the gateway's dead-replica 503 contract, both chaos drill arms,
and the ``tools/check_chaos_points.py`` AST gate.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.resilience import chaos, fault_injection
from deepspeed_tpu.runtime.resilience.chaos import ChaosKill, ChaosSchedule, ChaosSpec


def _model():
    return TransformerLM(TransformerConfig(vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
                                           intermediate_size=32, max_seq_len=16, dtype=jnp.float32,
                                           attention_impl="reference"))


@pytest.fixture(autouse=True)
def _disarm_goodput():
    """The drills arm the process-global goodput plane (their verdicts need
    it); leave it disarmed so other test files never pay the booking path."""
    yield
    from deepspeed_tpu.monitor.goodput import get_goodput

    get_goodput().shutdown()


def _config(**ckpt):
    return {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10**9,
        "tpu": {"mesh": {"data": 8}},
        "checkpoint": dict(ckpt),
    }


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, 64, size=(8, 16), dtype=np.int32)}


def _engine(config=None):
    groups.reset()
    engine, _, _, _ = deepspeed_tpu.initialize(model=_model(), config=config or _config())
    return engine


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.clear()
    yield
    chaos.clear()
    from deepspeed_tpu.elasticity import remesh

    remesh.clear_snapshots()


# ----------------------------------------------------------------------
# registry: handles, context managers, determinism
# ----------------------------------------------------------------------
def test_inject_returns_removal_handle():
    fired = []
    h = chaos.inject("engine/step", lambda ctx: fired.append(ctx))
    assert chaos.armed("engine/step")
    chaos.fire("engine/step", {"step": 1})
    h.remove()
    h.remove()  # idempotent
    chaos.fire("engine/step", {"step": 2})
    assert fired == [{"step": 1}]
    assert not chaos.armed()


def test_inject_context_manager_scopes_hook():
    fired = []
    with chaos.inject("prefetch/item", lambda ctx: fired.append(1)):
        chaos.fire("prefetch/item")
        assert chaos.armed("prefetch/item")
    chaos.fire("prefetch/item")
    assert fired == [1]
    assert not chaos.armed("prefetch/item")


def test_fault_injection_compat_handle_and_cm():
    """The saver-stage face keeps its POINTS validation and now returns
    handles / context managers instead of leaking module-global hooks."""
    with pytest.raises(ValueError):
        fault_injection.inject("nonsense_point", lambda ctx: None)
    with fault_injection.crash_at("before_manifest"):
        assert chaos.armed("before_manifest")
        with pytest.raises(fault_injection.InjectedCrash):
            fault_injection.fire("before_manifest")
    assert not chaos.armed("before_manifest")
    # clear() only touches the saver points, not the rest of the registry
    h = chaos.inject("engine/step", lambda ctx: None)
    fault_injection.crash_at("after_arrays")
    fault_injection.clear()
    assert not chaos.armed("after_arrays")
    assert chaos.armed("engine/step")
    h.remove()


def test_schedule_deterministic_and_bounded():
    specs = lambda: [ChaosSpec("stall", "engine/step", rate=0.3, duration_s=0.0),
                     ChaosSpec("kill", "prefetch/item", rate=0.5, max_events=2,
                               start_after=3)]
    logs = []
    for _ in range(2):
        s = ChaosSchedule(21, specs())
        with s:
            for i in range(40):
                chaos.fire("engine/step", {"step": i})
                try:
                    chaos.fire("prefetch/item", {"step": i})
                except ChaosKill:
                    pass
        logs.append(s.event_log())
    assert logs[0] == logs[1]
    assert any(k == "stall" for _, _, k, _ in logs[0])
    kills = [e for _, idx, k, _ in logs[0] if k == "kill" for e in [idx]]
    assert len(kills) == 2 and min(kills) >= 3  # max_events + start_after honored
    assert not chaos.armed()  # context manager uninstalled everything


def test_schedule_runs_sleep_kinds_before_kill(monkeypatch):
    """A stall and a kill drawn on the same fire must BOTH take effect:
    sleep first, then die — the kill must not eat the stall."""
    order = []
    monkeypatch.setattr(time, "sleep", lambda s: order.append("slept"))
    # rate=1.0 on both: guaranteed collision on every fire
    s = ChaosSchedule(0, [ChaosSpec("kill", "engine/step", rate=1.0, max_events=1),
                          ChaosSpec("stall", "engine/step", rate=1.0, duration_s=9.0,
                                    max_events=1)])
    with s:
        with pytest.raises(ChaosKill):
            chaos.fire("engine/step", {"step": 0})
    assert order == ["slept"]
    assert s.counts() == {"kill": 1, "stall": 1}


def test_fire_is_noop_when_unhooked():
    assert not chaos.armed()
    chaos.fire("engine/step", {"step": 0})  # must not raise, allocate hooks
    chaos.fire("never/registered")
    assert not chaos.armed()


# ----------------------------------------------------------------------
# elastic agent: retryable set + restart-budget decay
# ----------------------------------------------------------------------
def test_agent_retryable_exception_set():
    from deepspeed_tpu.elasticity import ElasticAgent

    ds = {"elasticity": {"enabled": True, "max_train_batch_size": 8,
                         "micro_batch_sizes": [1], "min_gpus": 1, "max_gpus": 64,
                         "min_time": 0, "version": 0.2}}
    # default set does NOT retry a ValueError (a real bug propagates)
    agent = ElasticAgent(ds, max_restarts=3, restart_delay_s=0.0)
    calls = {"n": 0}

    def bad(cfg):
        calls["n"] += 1
        raise ValueError("not a worker failure")

    with pytest.raises(ValueError):
        agent.run(bad, world_size_fn=lambda: 8)
    assert calls["n"] == 1

    # a configured set retries it (XLA surfacing peer loss as a custom type)
    class PeerLost(ValueError):
        pass

    agent2 = ElasticAgent(ds, max_restarts=2, restart_delay_s=0.0,
                          retryable_exceptions=(PeerLost, ))
    calls2 = {"n": 0}

    def flaky(cfg):
        calls2["n"] += 1
        if calls2["n"] < 3:
            raise PeerLost("peer down")
        return "done"

    assert agent2.run(flaky, world_size_fn=lambda: 8) == "done"
    assert calls2["n"] == 3


def test_agent_restart_budget_resets_after_sustained_healthy_run():
    from deepspeed_tpu.elasticity import ElasticAgent

    ds = {"elasticity": {"enabled": True, "max_train_batch_size": 8,
                         "micro_batch_sizes": [1], "min_gpus": 1, "max_gpus": 64,
                         "min_time": 0, "version": 0.2}}
    # every attempt runs "healthy" for >= the window before failing: the
    # budget keeps resetting, so 5 transient failures survive max_restarts=1
    agent = ElasticAgent(ds, max_restarts=1, restart_delay_s=0.0,
                         restart_window_s=0.01)
    calls = {"n": 0}

    def transient(cfg):
        calls["n"] += 1
        time.sleep(0.02)
        if calls["n"] < 6:
            raise RuntimeError("transient blip")
        return "ok"

    assert agent.run(transient, world_size_fn=lambda: 8) == "ok"
    assert calls["n"] == 6
    assert agent.restart_count <= agent.max_restarts

    # without the window (default), the same shape exhausts the budget
    agent2 = ElasticAgent(ds, max_restarts=1, restart_delay_s=0.0)
    calls2 = {"n": 0}

    def transient2(cfg):
        calls2["n"] += 1
        time.sleep(0.02)
        raise RuntimeError("transient blip")

    with pytest.raises(RuntimeError):
        agent2.run(transient2, world_size_fn=lambda: 8)
    assert calls2["n"] == 2  # initial + 1 restart


# ----------------------------------------------------------------------
# warm remesh
# ----------------------------------------------------------------------
def test_warm_remesh_matches_disk_resume_bit_exact(tmp_path):
    """The snapshot restore must land on EXACTLY the state a disk resume
    lands on — same params, same moments, same adam count — so the next
    step's loss is bit-identical between the two paths."""
    from deepspeed_tpu.elasticity import remesh

    engine = _engine()
    for i in range(3):
        engine.train_batch(_batch(i))
    engine.save_checkpoint(str(tmp_path), tag="t", blocking=True)
    snap = remesh.capture_snapshot(engine)
    next_batch = _batch(9)
    engine.destroy()

    disk = _engine()
    disk.load_checkpoint(str(tmp_path), tag="t")
    loss_disk = float(disk.train_batch(next_batch))
    disk.destroy()

    warm = _engine()
    remesh.restore_snapshot(warm, snap)
    assert warm.global_steps == 3
    loss_warm = float(warm.train_batch(next_batch))
    warm.destroy()
    assert loss_warm == loss_disk  # bit-identical, not allclose


def test_warm_remesh_topology_change_pinned_to_universal_math(tmp_path):
    """Snapshot-restore onto a DIFFERENT mesh must agree bit-exactly with
    the disk ds_to_universal conversion of the same state — the reshape
    parity pin: both resolve through universal_state_from_tree /
    apply_universal_state."""
    from deepspeed_tpu.checkpoint import ds_to_universal, read_universal_checkpoint
    from deepspeed_tpu.elasticity import remesh

    def make(mesh, stage, bs, micro):
        groups.reset()
        cfg = _config()
        cfg["tpu"] = {"mesh": mesh}
        cfg["zero_optimization"] = {"stage": stage}
        cfg["train_batch_size"] = bs
        cfg["train_micro_batch_size_per_gpu"] = micro
        return deepspeed_tpu.initialize(model=_model(), config=cfg)[0]

    eng_a = make({"data": 8}, 2, 8, 1)
    for i in range(2):
        eng_a.train_batch(_batch(i))
    eng_a.save_checkpoint(str(tmp_path / "ck"), tag="t", blocking=True)
    snap = remesh.capture_snapshot(eng_a)
    eng_a.destroy()

    # disk universal conversion of the SAME state
    n = ds_to_universal(str(tmp_path / "ck"), str(tmp_path / "uni"), tag="t")
    sd_disk, meta_disk = read_universal_checkpoint(str(tmp_path / "uni"))
    assert n == len(snap.sd)
    assert set(sd_disk) == set(snap.sd)
    for key in sd_disk:
        for field in ("fp32", "exp_avg", "exp_avg_sq"):
            np.testing.assert_array_equal(sd_disk[key][field], snap.sd[key][field],
                                          err_msg=f"{key}/{field}")
    assert meta_disk.get("optimizer_scalar_leaves")  # adam count carried

    # warm re-shard onto dp2 x tp4 at a different zero stage
    eng_b = make({"data": 2, "model": 4}, 3, 4, 2)
    remesh.restore_snapshot(eng_b, snap)
    assert int(eng_b.state["step"]) == 2
    # round-trip: the re-sharded engine re-snapshots bit-exactly
    snap_b = remesh.capture_snapshot(eng_b)
    for key in snap.sd:
        for field in ("fp32", "exp_avg", "exp_avg_sq"):
            np.testing.assert_array_equal(snap.sd[key][field], snap_b.sd[key][field],
                                          err_msg=f"{key}/{field} across dp8 -> dp2xtp4")
    assert np.isfinite(float(eng_b.train_batch(
        {"input_ids": np.random.default_rng(5).integers(0, 64, size=(4, 16), dtype=np.int32)})))
    eng_b.destroy()
    groups.reset()


def test_warm_remesh_resume_without_disk_payload(tmp_path):
    """run_resilient(warm_remesh=True): a restart with a live snapshot must
    resume WITHOUT reading the checkpoint payload — proven by corrupting
    the payload on disk before the restart."""
    from deepspeed_tpu.elasticity import remesh
    from deepspeed_tpu.runtime.resilience import run_resilient

    ds = _config()
    ds["elasticity"] = {"enabled": True, "max_train_batch_size": 8,
                        "micro_batch_sizes": [1], "min_gpus": 1, "max_gpus": 64,
                        "min_time": 0, "version": 0.2}
    remesh.clear_snapshots()
    want = None
    state = {"attempt": 0}

    def train_fn(batch_config, resume):
        state["attempt"] += 1
        eng = _engine(_config(remesh_snapshot=True))
        try:
            if resume.snapshot is not None:
                remesh.restore_snapshot(eng, resume.snapshot)
            elif resume.tag is not None:
                eng.load_checkpoint(str(tmp_path), tag=resume.tag)
            start = eng.global_steps
            losses = []
            for i in range(start, 4):
                losses.append(float(eng.train_batch(_batch(i))))
                if state["attempt"] == 1 and i == 2:
                    eng.save_checkpoint(str(tmp_path), blocking=True)  # + snapshot
                    # torch the payload: a disk resume would now fail, so a
                    # completed run PROVES the snapshot path never read it
                    arrays = tmp_path / "global_step3" / "arrays"
                    for root, _dirs, files in os.walk(arrays):
                        for f in files:
                            p = os.path.join(root, f)
                            with open(p, "wb") as fh:
                                fh.write(b"\0" * os.path.getsize(p))
                    raise RuntimeError("injected worker failure")
            return losses
        finally:
            eng.destroy()

    out = run_resilient(train_fn, ds, save_dir=str(tmp_path), max_restarts=2,
                        restart_delay_s=0.0, warm_remesh=True)
    assert state["attempt"] == 2
    assert len(out) == 1  # resumed at step 3, ran step 3 only

    # and the parity claim: the same run WITHOUT corruption, resumed from
    # disk, produces the same tail loss
    want = _engine()
    for i in range(3):
        want.train_batch(_batch(i))
    loss_ref = float(want.train_batch(_batch(3)))
    want.destroy()
    assert out[0] == loss_ref


def test_snapshot_store_scope_isolation(tmp_path):
    """A previous job's snapshot (same process, different save_dir) must
    never warm-resume an unrelated job: the store is scope-checked, and a
    new scope's publish supersedes the old one regardless of step."""
    from deepspeed_tpu.elasticity.remesh import (HostSnapshot, clear_snapshots,
                                                 latest_snapshot, publish_snapshot)

    clear_snapshots()
    job_a = HostSnapshot({}, {"global_steps": 100}, scope=str(tmp_path / "job_a"))
    publish_snapshot(job_a)
    # job B's consumer (run_resilient passes its save_dir) sees nothing
    assert latest_snapshot(scope=str(tmp_path / "job_b")) is None
    assert latest_snapshot(scope=str(tmp_path / "job_a")) is job_a
    assert latest_snapshot() is job_a  # scope-less consumer: caller's risk
    # a NEW job's publish replaces the held one even at a lower step
    job_b = HostSnapshot({}, {"global_steps": 1}, scope=str(tmp_path / "job_b"))
    publish_snapshot(job_b)
    assert latest_snapshot(scope=str(tmp_path / "job_b")) is job_b
    assert latest_snapshot(scope=str(tmp_path / "job_a")) is None
    # same scope: the newer step wins
    older = HostSnapshot({}, {"global_steps": 0}, scope=str(tmp_path / "job_b"))
    assert publish_snapshot(older) is job_b
    clear_snapshots()


# ----------------------------------------------------------------------
# run_resilient: newest tag corrupted between attempts
# ----------------------------------------------------------------------
def test_run_resilient_newest_tag_corrupted_mid_restart_falls_back(tmp_path):
    """The newest tag's payload is corrupted BETWEEN attempts (size kept, so
    only deep verification can see it): the restart must fall back to the
    next valid tag instead of looping on the bad one."""
    from deepspeed_tpu.runtime.resilience import run_resilient

    ds = _config()
    ds["elasticity"] = {"enabled": True, "max_train_batch_size": 8,
                        "micro_batch_sizes": [1], "min_gpus": 1, "max_gpus": 64,
                        "min_time": 0, "version": 0.2}
    resumes = []
    state = {"attempt": 0}

    def train_fn(batch_config, resume):
        state["attempt"] += 1
        tag, _path = resume
        resumes.append(tag)
        eng = _engine()
        try:
            if tag is not None:
                eng.load_checkpoint(str(tmp_path), tag=tag)
            start = eng.global_steps
            for i in range(start, 4):
                eng.train_batch(_batch(i))
                eng.save_checkpoint(str(tmp_path), blocking=True)
            if state["attempt"] == 1:
                # tear the NEWEST tag torn-silently: flip payload bytes in
                # place, sizes unchanged — the torn window between the crash
                # and the restart's resume scan
                newest = tmp_path / "global_step4"
                for root, _dirs, files in os.walk(newest / "arrays"):
                    for f in files:
                        p = os.path.join(root, f)
                        size = os.path.getsize(p)
                        if size:
                            with open(p, "r+b") as fh:
                                fh.seek(0)
                                fh.write(bytes(b ^ 0xFF for b in fh.read(min(64, size))))
                raise RuntimeError("injected failure after corruption")
            return eng.global_steps
        finally:
            eng.destroy()

    out = run_resilient(train_fn, ds, save_dir=str(tmp_path), max_restarts=2,
                        restart_delay_s=0.0, deep_verify=True)
    assert out == 4
    assert state["attempt"] == 2
    assert resumes[0] is None
    # the restart skipped the corrupted global_step4 and took global_step3
    assert resumes[1] == "global_step3"


# ----------------------------------------------------------------------
# serving: dead-replica 503 contract (unit level, no engines)
# ----------------------------------------------------------------------
def test_fail_for_counts_replica_failures_distinct_from_shed():
    from deepspeed_tpu.monitor.metrics import configure_metrics, get_metrics
    from deepspeed_tpu.serving.admission import AdmissionController
    from deepspeed_tpu.serving.config import GatewayConfig
    from deepspeed_tpu.serving.replica import GatewayRequest

    configure_metrics(enabled=True)
    reg = get_metrics()
    failed_c = reg.counter("gateway/replica_failed_requests_total")
    base = failed_c.value

    class FakeEngine:
        def probe_prefix(self, prompt):
            return 0, 0, 0, None

    class FakeReplica:
        name = "r0"
        engine = FakeEngine()

    adm = AdmissionController(GatewayConfig(enabled=True))
    reqs = [GatewayRequest(i, [1, 2, 3], 4, "interactive") for i in range(3)]
    for r in reqs:
        ok, _ = adm.try_admit(r, FakeReplica())
        assert ok
    shed_before = adm.stats["shed"]
    n = adm.fail_for("r0", "replica_stopped")
    assert n == 3
    assert failed_c.value == base + 3          # the DISTINCT counter moved
    assert adm.stats["shed"] == shed_before    # ... and shed did not
    for r in reqs:
        assert r.stream.done and r.stream.error == "replica_stopped"


def test_error_status_maps_replica_death_to_503():
    """The HTTP mapping the drill relies on: a dead replica's terminal is a
    retryable 503 (with Retry-After at the response layer), a timeout 504."""
    import deepspeed_tpu.serving.gateway as gw_mod

    # _error_status lives on the handler class built in _start_http; its
    # contract is pinned through the module-level mapping used there
    src = open(gw_mod.__file__).read()
    assert '"replica_stopped", "gateway_shutdown"' in src and "503" in src
    assert '"request_timeout"' in src and "504" in src


# ----------------------------------------------------------------------
# the drills (the acceptance bar, smoke-sized)
# ----------------------------------------------------------------------
def test_training_drill_smoke(tmp_path, cold_compile):
    # cold_compile: the drill's restarts build the programs of the run they follow, and the ledger this test
    # holds to the wall clock books every "compile" duration: a cache hit reports the seconds it SAVED as one
    from tools.chaos_drill import training_drill

    out = training_drill(seed=7, steps=6, workdir=str(tmp_path))
    assert out["loss_parity"], out
    assert out["resumed_tags_valid"], out
    assert out["stall_dumps_match"], out
    assert out["events"].get("kill", 0) >= 1
    assert out["events"].get("stall", 0) >= 1
    assert out["restarts"] >= 1
    assert out["warm_resumes"] >= 1  # at least one restart skipped disk
    # PR 14 goodput verdicts: the stormed run's ledger conserves wall clock
    # (warm restarts included) and recovery badput is a measured number
    assert out["goodput_conserved"], out["goodput"]
    assert out["recovery_badput_measured"] and out["recovery_badput_s"] > 0
    assert out["goodput"]["categories"]["stall"] > 0  # injected stalls booked


@pytest.mark.slow
def test_training_drill_deterministic_with_preempt(tmp_path):
    """Two identical drills (a storm with kills, stalls, a clean preempt +
    requeue) produce the same event log, and every verdict holds on both."""
    from tools.chaos_drill import training_drill

    a = training_drill(seed=11, steps=8, workdir=str(tmp_path / "a"))
    b = training_drill(seed=11, steps=8, workdir=str(tmp_path / "b"))
    assert a["event_log"] == b["event_log"]
    for out in (a, b):
        assert out["loss_parity"] and out["resumed_tags_valid"] and out["stall_dumps_match"], out
    assert a["events"].get("preempt", 0) >= 1
    assert a["requeues"] >= 1


def test_serving_drill_smoke():
    from tools.chaos_drill import serving_drill

    out = serving_drill(seed=3, n_requests=16, n_replicas=2)
    assert out["killed"] and out["kill_observed"], out
    assert out["zero_unreported"], out
    assert out["retry_after_on_503"], out
    assert out["drained_503_retry_after"], out
    assert out["replica_failure_counted"], out
    assert out["readyz_flipped"], out
    assert out["recovered"], out
    # PR 14 (ROADMAP 5(b) leftover): the stall/straggle storm with serving
    # heartbeat deadlines armed — the watchdog trips on the super-deadline
    # stall and the ledger books the wedged interval as stalled, not idle
    st = out["stall_storm"]
    assert st["events"].get("stall", 0) >= 1, st
    assert st["watchdog_tripped"] and st["stall_dumps"] >= 1, st
    assert st["stalled_not_idle"] and st["stalled_s_booked"] > 0, st
    # every replica ledger conserves; the kill's down-time was measured
    assert out["goodput_conserved"], out["goodput"]
    assert out["recovery_badput_measured"], out


# ----------------------------------------------------------------------
# universal layout: adam count carried
# ----------------------------------------------------------------------
def test_universal_layout_carries_optimizer_scalar_leaves(tmp_path):
    """Converting and re-loading through the universal layout must restore
    optax's scalar chain leaves (adam bias-correction count) — without
    them the first post-restore step silently diverges from a native
    resume."""
    from deepspeed_tpu.checkpoint import (ds_to_universal, load_universal_checkpoint,
                                          read_universal_checkpoint)

    engine = _engine()
    for i in range(3):
        engine.train_batch(_batch(i))
    engine.save_checkpoint(str(tmp_path / "ck"), tag="t", blocking=True)
    counts_before = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        jax.device_get(engine.state["opt_state"])) if np.ndim(l) == 0]
    engine.destroy()

    ds_to_universal(str(tmp_path / "ck"), str(tmp_path / "uni"), tag="t")
    _sd, meta = read_universal_checkpoint(str(tmp_path / "uni"))
    assert meta.get("optimizer_scalar_leaves")

    eng2 = _engine()
    load_universal_checkpoint(eng2, str(tmp_path / "uni"))
    counts_after = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        jax.device_get(eng2.state["opt_state"])) if np.ndim(l) == 0]
    assert len(counts_before) == len(counts_after)
    for a, b in zip(counts_before, counts_after):
        np.testing.assert_array_equal(a, b)
    eng2.destroy()


# ----------------------------------------------------------------------
# CI gate
# ----------------------------------------------------------------------
def test_check_chaos_points_gate():
    from tools.check_chaos_points import check

    assert check() == [], "chaos-plane access discipline or a silent except drifted"


def test_check_chaos_points_catches_drift(tmp_path):
    from tools.check_chaos_points import check

    pkg = tmp_path / "pkg"
    (pkg / "runtime" / "resilience").mkdir(parents=True)
    (pkg / "elasticity").mkdir()
    # conditional import + test-only hook installation in "production" code
    (pkg / "rogue.py").write_text(
        "def f(testing):\n"
        "    if testing:\n"
        "        from deepspeed_tpu.runtime.resilience import chaos\n"
        "        chaos.inject('engine/step', lambda ctx: None)\n"
        "        chaos.clear()\n")
    # a silent swallow in the resilience plane
    (pkg / "runtime" / "resilience" / "sloppy.py").write_text(
        "def g():\n"
        "    try:\n"
        "        return open('/nope').read()\n"
        "    except OSError:\n"
        "        return None\n")
    # a compliant handler: raises
    (pkg / "elasticity" / "fine.py").write_text(
        "def h():\n"
        "    try:\n"
        "        return 1\n"
        "    except Exception:\n"
        "        raise\n")
    bad = check(str(pkg))
    assert any("conditional/nested import" in b for b in bad)
    assert any("inject" in b for b in bad)
    assert any("clear" in b for b in bad)
    assert any("silent swallow" in b.lower() or "health/" in b for b in bad)
    assert not any("fine.py" in b for b in bad)


def test_warm_remesh_carries_curriculum_and_random_ltd_state():
    """ROADMAP 5c leftover: a warm remesh of a data-efficiency run must
    resume curriculum difficulty and the random-LTD sequence budget exactly
    — without the universal meta carrying them, a restored engine silently
    re-ran its schedule from step 0 against an optimizer resumed at step N."""
    from deepspeed_tpu.elasticity import remesh

    cfg = _config()
    cfg["curriculum_learning"] = {
        "enabled": True, "curriculum_type": "seqlen",
        "min_difficulty": 4, "max_difficulty": 16,
        "schedule_type": "fixed_linear",
        "schedule_config": {"total_curriculum_step": 4, "difficulty_step": 4},
    }
    cfg["data_efficiency"] = {
        "enabled": True,
        "data_routing": {"enabled": True, "random_ltd": {
            "enabled": True, "total_layer_num": 1, "random_ltd_layer_num": 1,
            "random_ltd_layer_id": [0], "model_mask_name": None,
            "model_type": "decoder", "hidden_state_order": "batch_seq_dim",
            "random_ltd_schedule": {"min_value": 4, "max_value": 16,
                                    "schedule_type": "fixed_linear",
                                    "schedule_config": {"total_curriculum_step": 4,
                                                        "difficulty_step": 4}},
        }},
    }
    engine = _engine(cfg)
    assert engine.curriculum_scheduler is not None
    assert engine.random_ltd_scheduler is not None
    for i in range(3):
        engine.train_batch(_batch(i))
    cur_state = dict(engine.curriculum_scheduler.state_dict())
    ltd_state = dict(engine.random_ltd_scheduler.state_dict())
    snap = remesh.capture_snapshot(engine)
    engine.destroy()
    assert snap.meta.get("curriculum_scheduler") == cur_state
    assert snap.meta.get("random_ltd_scheduler") == ltd_state

    warm = _engine(cfg)  # fresh: schedules back at step 0
    assert warm.curriculum_scheduler.state_dict() != cur_state
    remesh.restore_snapshot(warm, snap)
    assert warm.curriculum_scheduler.state_dict() == cur_state, \
        "curriculum difficulty not restored through the universal meta"
    assert warm.random_ltd_scheduler.state_dict() == ltd_state, \
        "random-ltd schedule not restored through the universal meta"
    warm.destroy()
