"""The trace reduction, on a small trace recorded on a v5e (kept with the
benchmark) and on hand-made ones written with the benchmark's own XSpace
writer: busy and idle time, time per kernel by the six kernel names, exposed
collective time, gaps attributed to the benchmark's host spans."""

import os

import pytest

from benchmark.lib import loader, xplane, xplane_write

RECORDED = os.path.join(loader.ROOT, "benchmark", "testdata", "v5e_flash_step.xplane.pb")
MS = 1_000_000  # ns


def test_op_name_is_the_hlo_instruction_without_its_suffix():
    assert xplane.op_name("%flash_fwd.1 = (bf16[2,16,2048,64]{3,2,1,0}, f32[2]) custom-call(...)") == "flash_fwd"
    assert xplane.op_name("%paged_attn_q_tiled = bf16[8,32,128] custom-call(%a)") == "paged_attn_q_tiled"
    assert xplane.op_name("%all-gather-start.12 = (f32[4]) all-gather-start(%p)") == "all-gather-start"
    assert xplane.op_name("%convolution_bitcast_fusion = bf16[2] fusion(%x), kind=kOutput") == \
        "convolution_bitcast_fusion"
    assert xplane.op_name("bench/put") == "bench/put"


def test_recorded_v5e_trace_busy_idle_kernels_and_gaps():
    trace = xplane.read_trace(RECORDED)
    assert list(trace["devices"]) == [0] and len(trace["devices"][0]) == 48
    assert sorted({name for name, _, _ in trace["spans"]}) == ["bench/loss_fetch", "bench/train_batch"]
    r = xplane.reduce_trace(trace)
    # three steps of ~1.86 ms on a window of 31.3 ms: the sleeps between them are idle
    assert r["busy_s"] == pytest.approx(5.588e-3, rel=1e-3)
    assert r["window_s"] == pytest.approx(31.26e-3, rel=1e-3)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.821, abs=2e-3)
    by_op = r["seconds_by_op"]
    assert by_op["flash_bwd_dkdv"] == pytest.approx(1.902e-3, rel=1e-3)
    assert by_op["flash_bwd_dq"] == pytest.approx(1.553e-3, rel=1e-3)
    assert by_op["flash_fwd"] == pytest.approx(1.272e-3, rel=1e-3)
    assert r["calls_by_op"]["flash_fwd"] == 3
    assert xplane.kernel_seconds(r, ["flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"]) / r["busy_s"] == \
        pytest.approx(0.846, abs=2e-3)
    assert [name for name, _ in r["device_ops"][:3]] == ["flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]
    # the device idles while the host sleeps outside the spans, and inside the loss fetch
    gaps = dict(r["idle_gaps"])
    assert gaps[xplane.OUTSIDE_SPANS] == pytest.approx(19.09e-3, rel=1e-2)
    assert gaps["bench/loss_fetch"] == pytest.approx(6.59e-3, rel=1e-2)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["collective_exposed_s"] == 0.0


def _four_chip_trace(tmp_path):
    """Two chips' worth of a ZeRO-3 layer and a serving step, by hand: a
    ``while`` that encloses its body, an exposed all-gather, a hidden one
    (async: only its short start/done ops sit on the line), the six kernels."""
    def ops(shift):
        t = lambda ms: int((ms + shift) * MS)
        return [
            ("%while.3 = (s32[], f32[8]) while(%tuple)", t(0), 20 * MS),
            ("%all-gather.7 = f32[1024]{0} all-gather(%p)", t(0), 2 * MS),          # exposed: 2 ms
            ("%flash_fwd.1 = bf16[2] custom-call(%q)", t(2), 3 * MS),
            ("%all-gather-start.2 = (f32[4]) all-gather-start(%p)", t(5), MS // 100),
            ("%fusion.9 = bf16[8] fusion(%x), kind=kOutput", t(6), 4 * MS),
            ("%all-gather-done.2 = f32[4] all-gather-done(%s)", t(10), 1 * MS),      # exposed wait: 1 ms
            ("%flash_bwd_dkdv.1 = f32[2] custom-call(%q)", t(11), 4 * MS),
            ("%flash_bwd_dq.1 = f32[2] custom-call(%q)", t(15), 2 * MS),
            ("%reduce-scatter.4 = f32[256] reduce-scatter(%g)", t(17), 3 * MS),      # exposed: 3 ms
            ("%paged_attn_q_tiled = bf16[8] custom-call(%q)", t(30), 5 * MS),
            ("%paged_attn_per_token.2 = bf16[8] custom-call(%q)", t(35), 1 * MS),
            ("%paged_attn_kv_split = bf16[8] custom-call(%q)", t(36), 2 * MS),
        ]
    planes = {
        "/device:TPU:0": {"XLA Ops": ops(0), "Steps": [("0", 0, 40 * MS)]},
        "/device:TPU:1": {"XLA Ops": ops(0.5)},
        "/host:CPU": {"main/1": [("bench/train_batch", 0, 1 * MS), ("bench/loss_fetch", 1 * MS, 22 * MS),
                                 ("bench/put", 24 * MS, 16 * MS), ("not_ours", 0, 40 * MS)]},
    }
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(xplane_write.encode_xspace(planes))
    return str(path)


def test_collective_time_that_nothing_hid_and_the_six_kernels(tmp_path):
    trace = xplane.read_trace(_four_chip_trace(tmp_path))
    assert sorted(trace["devices"]) == [0, 1]
    assert {n for n, _, _ in trace["spans"]} == {"bench/train_batch", "bench/loss_fetch", "bench/put"}
    r = xplane.reduce_trace(trace)
    assert r["n_devices"] == 2
    assert r["collective_exposed_s"] == pytest.approx((2 + 0.01 + 1 + 3) * 1e-3, rel=1e-6)
    want = {"flash_fwd": 3, "flash_bwd_dkdv": 4, "flash_bwd_dq": 2, "paged_attn_q_tiled": 5,
            "paged_attn_per_token": 1, "paged_attn_kv_split": 2}
    for kernel, ms in want.items():
        assert r["seconds_by_op"][kernel] == pytest.approx(ms * 1e-3, rel=1e-6), kernel
    # the while keeps only what its body leaves: 20 - (2+3+0.01+4+1+4+2+3) ms
    assert r["seconds_by_op"]["while"] == pytest.approx(0.99e-3, rel=1e-3)
    assert r["busy_s"] == pytest.approx(28e-3, rel=1e-6)
    assert r["window_s"] == pytest.approx(40e-3, rel=1e-6)
    gaps = dict(r["idle_gaps"])  # chip 0 idles 20-30 ms: 3 ms in the loss fetch, 6 in put, 1 in neither
    assert gaps["bench/loss_fetch"] == pytest.approx(3e-3, rel=1e-6)
    assert gaps["bench/put"] == pytest.approx(6e-3 + 2e-3, rel=1e-6)  # and 38-40 ms after the last kernel
    assert gaps[xplane.OUTSIDE_SPANS] == pytest.approx(1e-3, rel=1e-6)


def test_self_segments_give_an_enclosing_op_only_what_its_children_leave():
    segs = xplane.self_segments([("outer", 0.0, 10.0), ("a", 1.0, 4.0), ("inner", 2.0, 3.0), ("b", 6.0, 12.0)])
    total = {}
    for name, a, b in segs:
        total[name] = total.get(name, 0.0) + b - a
    assert total == {"outer": 3.0, "a": 2.0, "inner": 1.0, "b": 4.0}  # b is cut to its parent
    assert all(x[2] <= y[1] for x, y in zip(sorted(segs, key=lambda s: s[1]), sorted(segs, key=lambda s: s[1])[1:]))


def test_a_trace_in_which_nothing_ran_on_the_device_is_refused(tmp_path):
    path = tmp_path / "empty.xplane.pb"
    path.write_bytes(xplane_write.encode_xspace({"/host:CPU": {"main/1": [("bench/put", 0, MS)]}}))
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce_trace(xplane.read_trace(str(path)))


def test_cut_trace_round_trips_the_recording():
    again = xplane_write.cut_trace(RECORDED, 10.0)
    with open(RECORDED, "rb") as f:
        assert again == f.read()
