"""Disaggregated prefill/decode serving (deepspeed_tpu/serving/disagg.py +
handoff.py): role-typed replica pools with cross-replica KV handoff through
the host tier.

What these pin: the handoff ledger's never-lose-a-request contract
(at-most-once begin, checksummed manifests, terminal fallback); greedy
token streams through a full prefill-pool -> migrate -> decode-pool run are
BIT-IDENTICAL to a direct single-engine run; chaos-injected handoff
failures (transport loss AND in-flight payload corruption) fall back to
decoding in place with zero unreported requests; the goodput ledgers prove
pool purity (a prefill replica books ~no decode seconds, a decode replica's
prefill share stays under 5%); the PR 15 meter's per-pool compute split
reconciles with the per-tenant ledgers within 5%; the router restricts new
placements to the prefill pool; ``GET /v1/pools`` serves the topology (404
without the config block); and the perf-sentinel direction table reads
``handoff_p50_ms`` / ``handoff_fallback_rate`` as lower-is-better despite
the generic ``_rate`` suffix rule.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.monitor.goodput import (SERVING_CATEGORIES, SPAN_TO_CATEGORY,
                                           configure_goodput, get_goodput)
from deepspeed_tpu.runtime.resilience import chaos
from deepspeed_tpu.serving import (DisaggConfig, GatewayConfig, HandoffLedger,
                                   MeteringConfig)
from tools.serving_load import build_engine, build_gateway


def _prompts(n, rng, lo=8, hi=16):
    """Unique prompts (no cross-request prefix hits contaminating the
    pool-purity arithmetic)."""
    return [rng.integers(1, 120, size=int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _run_gateway(gw, prompts, max_new, serial=False):
    """``serial=True`` completes each request before submitting the next:
    batch shapes (and so which XLA buckets compile when) become
    deterministic — what the goodput-purity warmup/measure pair needs."""
    reqs = []
    for i, p in enumerate(prompts):
        status, req = gw.submit(p, max_new_tokens=int(max_new[i]))
        assert status == 200, req
        reqs.append(req)
        if serial:
            assert req.stream.wait_done(timeout=120), f"request {i} hung"
    out = {}
    for i, req in enumerate(reqs):
        assert req.stream.wait_done(timeout=120), f"request {i} never finished"
        assert req.stream.error is None, f"request {i}: {req.stream.error}"
        out[i] = [int(t) for t in req.stream.all_tokens()]
    return out, reqs


def _reference_tokens(prompts, max_new):
    """Direct single-engine greedy run: the parity baseline."""
    from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler

    engine = build_engine()
    try:
        sched = DynamicSplitFuseScheduler(engine)
        for i, p in enumerate(prompts):
            sched.submit(1000 + i, p, max_new_tokens=int(max_new[i]))
        results = sched.run()
        return {i: [int(t) for t in results[1000 + i]]
                for i in range(len(prompts))}
    finally:
        engine.shutdown()


def _disagg_gateway(**extra):
    return build_gateway(
        n_replicas=2, prefix_cache=True, host_blocks=160,
        disagg=DisaggConfig(enabled=True, roles=("prefill", "decode")), **extra)


# ---------------------------------------------------------------------------
# taxonomy + direction-table drift pins (cheap, no engines)
# ---------------------------------------------------------------------------
def test_handoff_goodput_taxonomy_pinned():
    assert "handoff" in SERVING_CATEGORIES
    assert SPAN_TO_CATEGORY["serving/handoff"] == "handoff"


def test_disagg_config_validation():
    cfg = GatewayConfig.from_dict({"disagg": {"roles": ["prefill", "decode"]}})
    assert cfg.disagg.enabled  # presence-enables
    assert cfg.disagg.roles == ("prefill", "decode")
    with pytest.raises(ValueError, match="unknown keys"):
        GatewayConfig.from_dict({"disagg": {"rolez": []}})
    with pytest.raises(ValueError, match="unknown roles"):
        GatewayConfig.from_dict({"disagg": {"roles": ["prefil"]}})
    with pytest.raises(ValueError, match="handoff_after_tokens"):
        GatewayConfig.from_dict({"disagg": {"handoff_after_tokens": 0}})
    assert not GatewayConfig().disagg.enabled  # absent = off, all mixed


# ---------------------------------------------------------------------------
# ledger unit contract
# ---------------------------------------------------------------------------
def test_ledger_at_most_once_and_checksum():
    led = HandoffLedger()
    assert led.begin("r1", "0", "1")
    assert not led.begin("r1", "0", "1")  # second begin refused, forever
    assert led.stats["refused"] == 1
    payloads = [(np.arange(8, dtype=np.float32), np.ones(8, np.float32))]
    led.record_manifest("r1", [np.arange(8)], payloads)
    assert led.verify("r1", payloads)
    corrupted = [(payloads[0][0] + 1, payloads[0][1])]
    assert not led.verify("r1", corrupted)
    assert led.stats["checksum_failures"] == 1
    led.fail("r1", "checksum_mismatch")
    assert led.entry("r1")["state"] == "fallback"
    led.fail("r1", "again")  # idempotent: terminal states never re-count
    assert led.stats["fallbacks"] == 1
    assert not led.begin("r1", "0", "1")  # still at-most-once after fallback
    assert led.fallback_rate == 1.0
    # the happy path books latency + volume
    assert led.begin("r2", "0", "1")
    led.record_manifest("r2", [np.arange(8)], payloads)
    led.mark_installed("r2", 1)
    led.mark_resumed("r2")
    assert led.entry("r2")["state"] == "resumed"
    assert led.stats["blocks_moved"] == 1 and led.stats["bytes_moved"] > 0
    assert led.p50_ms is not None and led.p50_ms >= 0
    st = led.state()
    assert st["handoff_fallback_rate"] == 0.5 and st["inflight"] == 0


def test_ledger_fail_without_begin_is_safe():
    led = HandoffLedger()
    led.fail("never-opened", "whatever")  # refused-begin path records nothing
    assert led.stats["fallbacks"] == 0


# ---------------------------------------------------------------------------
# the migration itself: parity, placement, topology endpoint
# ---------------------------------------------------------------------------
def test_greedy_parity_through_migration():
    """Every request prefills on the prefill replica, migrates its KV
    through the host tier, and resumes on the decode replica — the token
    stream must be BIT-IDENTICAL to a direct single-engine greedy run (the
    handoff moves the request, never changes what it says)."""
    rng = np.random.default_rng(5)
    prompts = _prompts(8, rng, lo=8, hi=16)
    max_new = rng.integers(6, 12, size=len(prompts))
    want = _reference_tokens(prompts, max_new)
    gw = _disagg_gateway()
    try:
        got, reqs = _run_gateway(gw, prompts, max_new)
        assert got == want
        st = gw.disagg.state()
        assert st["migrated"] == len(prompts) and st["fallbacks"] == 0
        assert st["handoff"]["handoff_fallback_rate"] == 0.0
        # every request was PLACED on the prefill pool and FINISHED on the
        # decode replica (replica_name re-stamps at resume)
        assert all(r.replica_name == "1" for r in reqs)
        assert all(r.handoff_state == "migrated" for r in reqs)
        assert all(e["state"] == "resumed"
                   for e in st["handoff"]["recent"].values())
    finally:
        gw.stop()


def test_router_restricts_new_placements_to_prefill_pool():
    gw = _disagg_gateway()
    try:
        status, req = gw.submit([1, 2, 3, 4, 5], max_new_tokens=2)
        assert status == 200
        assert req.stream.wait_done(timeout=60)
        assert gw.router.state()["roles"] == {"0": "prefill", "1": "decode"}
        assert gw.router.stats["pool_restricted"] >= 1
    finally:
        gw.stop()


def test_pools_endpoint():
    gw = build_gateway(n_replicas=1, prefix_cache=False)  # no disagg block
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{gw.url}/v1/pools", timeout=10)
        assert ei.value.code == 404
        assert json.loads(ei.value.read())["error"] == "disagg_disabled"
    finally:
        gw.stop()
    gw = _disagg_gateway()
    try:
        with urllib.request.urlopen(f"{gw.url}/v1/pools", timeout=10) as resp:
            body = json.loads(resp.read())
        assert body["pools"] == {"prefill": ["0"], "decode": ["1"]}
        assert body["handoff"]["started"] == 0
        # the gauge provider is registered: handoff rows ride /metrics
        assert any(name.startswith("handoff/")
                   for name, _l, _v in gw.disagg.ledger.gauge_rows())
    finally:
        gw.stop()


# ---------------------------------------------------------------------------
# chaos drills: failed handoffs fall back in place, zero unreported
# ---------------------------------------------------------------------------
def test_handoff_transport_loss_falls_back_in_place():
    """A hook raising at ``serving/handoff`` (transport loss mid-export):
    every request still completes with the exact greedy tokens — decoded in
    place on the prefill replica — and the ledger records each fallback."""
    rng = np.random.default_rng(11)
    prompts = _prompts(6, rng)
    max_new = rng.integers(5, 10, size=len(prompts))
    want = _reference_tokens(prompts, max_new)
    gw = _disagg_gateway()

    def boom(ctx):
        raise RuntimeError("injected transport loss")

    handle = chaos.inject("serving/handoff", boom)
    try:
        got, reqs = _run_gateway(gw, prompts, max_new)
        assert got == want  # fallback never loses or alters a request
        st = gw.disagg.state()
        assert st["migrated"] == 0
        assert st["fallbacks"] == len(prompts)
        assert st["handoff"]["handoff_fallback_rate"] == 1.0
        assert all(r.handoff_state == "fallback" for r in reqs)
        assert all(r.replica_name == "0" for r in reqs)  # decoded in place
        assert all("transport loss" in e["reason"]
                   for e in st["handoff"]["recent"].values())
    finally:
        handle.remove()
        gw.stop()


def test_handoff_corruption_caught_by_checksum():
    """A hook that CORRUPTS a payload (bit-flip in the broker's hands —
    exported blocks are read-only D2H views, so the drill swaps in a
    mutated copy): the verify gate must fail the handoff before the
    destination installs a byte of wrong KV — fallback in place, tokens
    still exact."""
    rng = np.random.default_rng(13)
    prompts = _prompts(4, rng)
    max_new = rng.integers(5, 9, size=len(prompts))
    want = _reference_tokens(prompts, max_new)
    gw = _disagg_gateway()

    def corrupt(ctx):
        flipped = []
        hit = False
        for arr in ctx["payloads"][0]:
            if arr is not None and not hit:
                bad = np.array(arr)
                bad.reshape(-1)[0] += 1.0
                flipped.append(bad)
                hit = True
            else:
                flipped.append(arr)
        ctx["payloads"][0] = flipped

    handle = chaos.inject("serving/handoff", corrupt)
    try:
        got, _reqs = _run_gateway(gw, prompts, max_new)
        assert got == want
        st = gw.disagg.state()
        assert st["migrated"] == 0 and st["fallbacks"] == len(prompts)
        assert st["handoff"]["checksum_failures"] == len(prompts)
        assert all("checksum_mismatch" in e["reason"]
                   for e in st["handoff"]["recent"].values())
    finally:
        handle.remove()
        gw.stop()


# ---------------------------------------------------------------------------
# pool purity (goodput) + meter reconciliation
# ---------------------------------------------------------------------------
def test_pool_purity_and_meter_reconciliation():
    """Disaggregation must actually disaggregate: the prefill replica's
    goodput ledger books (approximately) zero decode seconds, the decode
    replica's prefill share (the un-exported tail it re-prefills at resume)
    stays under 5%, and the meter's per-pool compute split reconciles with
    the per-tenant ledgers within 5%. Purity is asserted on the DELTA past
    a warmup pass: the first forward on each bucket shape carries its XLA
    compile time, which would otherwise swamp the attribution (the decode
    replica's first tail re-prefill would book seconds of 'prefill')."""
    configure_goodput(enabled=True)
    try:
        rng = np.random.default_rng(17)
        # BLOCK-ALIGNED unique prompts (kv_block_size=8; prompt + the first
        # generated token leaves exactly a 1-token uncached tail at resume,
        # which books as decode work — single-token forwards are decode) so
        # the purity signal is the MECHANISM, not the smoke-scale padding
        # cost of a mid-block tail re-prefill. Warmup reuses the measured
        # run's LENGTHS (same padded buckets) over a disjoint token
        # alphabet (no cross-run prefix hits).
        lens = rng.choice([8, 16], size=8)
        max_new = rng.integers(36, 44, size=len(lens))
        warm = [rng.integers(1, 60, size=int(n)).astype(np.int32) for n in lens]
        prompts = [rng.integers(61, 120, size=int(n)).astype(np.int32)
                   for n in lens]
        gw = _disagg_gateway(metering=MeteringConfig(enabled=True))
        try:
            _run_gateway(gw, warm, max_new, serial=True)
            gp = get_goodput()
            base0 = dict(gp.serving_ledger("0").report()["categories"])
            base1 = dict(gp.serving_ledger("1").report()["categories"])
            _run_gateway(gw, prompts, max_new, serial=True)
            cur0 = gp.serving_ledger("0").report()["categories"]
            cur1 = gp.serving_ledger("1").report()["categories"]
            pre = {k: cur0.get(k, 0.0) - base0.get(k, 0.0) for k in cur0}
            dec = {k: cur1.get(k, 0.0) - base1.get(k, 0.0) for k in cur1}
            pre_active = pre.get("prefill_active", 0.0) + pre.get("decode_active", 0.0)
            dec_active = dec.get("prefill_active", 0.0) + dec.get("decode_active", 0.0)
            assert pre_active > 0 and dec_active > 0
            assert pre.get("decode_active", 0.0) <= 0.05 * pre_active, pre
            assert dec.get("prefill_active", 0.0) <= 0.05 * dec_active, dec
            # the prefill pool's broker time is visible, not hidden in idle
            assert pre.get("handoff", 0.0) > 0.0
            # meter reconciliation: the per-pool split and the per-tenant
            # ledgers integrate the SAME step-observer apportionment
            rep = gw.meter.usage_report()
            pools = rep["pools"]
            assert set(pools) == {"prefill", "decode"}
            assert pools["prefill"].get("decode", 0.0) <= \
                0.05 * sum(pools["prefill"].values())
            pool_total = sum(v for by_kind in pools.values()
                             for v in by_kind.values())
            tenant_total = sum(sum(s["compute_s"].values())
                               for s in rep["tenants"].values())
            if rep["other"] is not None:
                tenant_total += sum(rep["other"]["compute_s"].values())
            assert pool_total == pytest.approx(tenant_total, rel=0.05)
        finally:
            gw.stop()
    finally:
        get_goodput().shutdown()


def test_mixed_fleet_never_migrates():
    """The co-located baseline is untouched: without a disagg block there
    is no coordinator, roles are all mixed, and nothing ever migrates."""
    gw = build_gateway(n_replicas=2, prefix_cache=True)
    try:
        assert gw.disagg is None
        assert all(r.role == "mixed" for r in gw.replicas)
        status, req = gw.submit([3, 1, 4, 1, 5], max_new_tokens=4)
        assert status == 200 and req.stream.wait_done(timeout=60)
        assert req.handoff_state is None and req.resume_base == 0
    finally:
        gw.stop()
