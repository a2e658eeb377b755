"""Operation and byte counts of the attention kernels against hand-worked
values, the MFU arithmetic, and the table of peaks."""

import pytest

from benchmark.lib import opcount
from benchmark.lib.peaks import peaks_for


def test_attended_pairs_by_hand():
    assert opcount.attended_pairs(0, 4) == 1 + 2 + 3 + 4
    assert opcount.attended_pairs(10, 1) == 11                 # a decode token after 10 of context
    assert opcount.attended_pairs(0, 6, window=3) == 1 + 2 + 3 + 3 + 3 + 3
    assert opcount.attended_pairs(5, 2, window=3) == 6         # both queries see a full window
    assert opcount.attended_pairs(1, 3, window=4) == 2 + 3 + 4
    assert opcount.attended_pairs(0, 0) == 0


def test_flash_forward_cost_by_hand():
    # batch 1, 2 query heads over 1 KV head of 8, sequence 4, bf16
    flops, nbytes = opcount.flash_fwd_cost(1, 4, 2, 1, 8)
    assert flops == 2 * 10 * 4 * 8                             # heads x pairs x (2 products x 2 x d)
    assert nbytes == 4 * 8 * (2 * 2 + 2 * 1) * 2 + 2 * 4 * 4   # q,o per q head; k,v per kv head; f32 lse


def test_flash_backward_costs_are_four_and_three_products():
    fwd, _ = opcount.flash_fwd_cost(2, 16, 4, 4, 64)
    dkdv, b1 = opcount.flash_bwd_dkdv_cost(2, 16, 4, 4, 64)
    dq, b2 = opcount.flash_bwd_dq_cost(2, 16, 4, 4, 64)
    assert (dkdv, dq) == (2 * fwd, fwd * 3 // 2)
    assert b1 - b2 == 2 * 16 * 64 * (2 * 4 - 4) * 4            # dK,dV against dQ, float32
    assert set(opcount.FLASH_COSTS) == {"flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"}


def test_paged_attention_cost_by_hand():
    # one decode row after 7 tokens of context, one prefill row of 3 tokens; 4 q heads, 2 kv heads, d 16
    flops, nbytes = opcount.paged_attention_cost([(7, 1), (0, 3)], 4, 2, 16)
    assert flops == 4 * (8 + 6) * 4 * 16
    assert nbytes == (8 + 3) * 2 * 16 * 2 * 2 + (1 + 3) * 4 * 16 * 2 * 2
    # a sliding window caps what a decode row reads
    _, windowed = opcount.paged_attention_cost([(100, 1)], 4, 2, 16, window=10)
    assert windowed == 10 * 2 * 16 * 2 * 2 + 1 * 4 * 16 * 2 * 2


def test_roofline_bound_names_its_peak():
    peaks = peaks_for("TPU v5 lite")
    assert opcount.min_seconds(197e12, 1.0, peaks) == (1.0, "flops")
    assert opcount.min_seconds(1.0, 819e9, peaks) == (1.0, "bytes")


def test_train_flops_per_token_of_pythia_410m_by_hand():
    n = opcount.matmul_params(1024, 24, 16, 16, 64, 4096, 50304, gated_mlp=False)
    assert n == 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096) + 1024 * 50304
    per_token = opcount.train_flops_per_token(1024, 24, 16, 16, 64, 4096, 50304, False, 2048)
    assert per_token == pytest.approx(6 * n + 3 * 24 * 16 * (2049 / 2) * 4 * 64)
    assert opcount.mfu(22387.0, per_token, peaks_for("TPU v5 lite")) == pytest.approx(0.2751, abs=2e-3)


def test_peaks_are_keyed_by_device_kind_and_an_unknown_device_raises():
    assert peaks_for("TPU v5 lite") == {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
