"""The arithmetic of the end-to-end numbers: whole steps between fences,
percentiles, time per output token, prompt/output tokens per engine step."""

import math

import pytest

from benchmark.lib import rates

# fences of six steps of uneven length; step i did WORK[i]
FENCES = [10.0, 11.5, 12.5, 14.5, 15.0, 17.0, 18.25]
WORK = [0, 300, 200, 400, 100, 400, 250]


def test_rate_is_work_of_whole_steps_over_the_time_between_their_fences():
    rate, n, seconds = rates.whole_step_rate(FENCES, WORK, 10.0, 17.5)
    assert (n, seconds) == (5, 7.0)
    assert rate == pytest.approx((300 + 200 + 400 + 100 + 400) / 7.0)


@pytest.mark.parametrize("edge", [17.0, 17.01, 17.3, 17.6, 18.0, 18.2499])
def test_moving_the_nominal_end_inside_a_step_leaves_the_rate_unchanged(edge):
    assert rates.whole_step_rate(FENCES, WORK, 10.0, edge) == rates.whole_step_rate(FENCES, WORK, 10.0, 17.0)


@pytest.mark.parametrize("edge", [10.01, 10.7, 11.2, 11.5])
def test_moving_the_nominal_start_inside_a_step_leaves_the_rate_unchanged(edge):
    assert rates.whole_step_rate(FENCES, WORK, edge, 18.25) == rates.whole_step_rate(FENCES, WORK, 11.5, 18.25)


def test_rate_never_divides_by_the_nominal_window():
    # the same steps under a nominal window twice as long: the same rate
    assert rates.whole_step_rate(FENCES, WORK, 0.0, 100.0)[0] == pytest.approx(sum(WORK[1:]) / 8.25)


def test_rate_needs_a_whole_step_and_matching_lengths():
    with pytest.raises(ValueError, match="no whole step"):
        rates.whole_step_rate(FENCES, WORK, 11.6, 12.4)
    with pytest.raises(ValueError, match="fences for"):
        rates.whole_step_rate(FENCES, WORK[:-1], 10.0, 18.0)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 4.8), (100, 5.0), (25, 2.0)])
def test_percentile_interpolates_between_ranks(q, want):
    assert rates.percentile([5.0, 1.0, 4.0, 2.0, 3.0], q) == pytest.approx(want)


def test_percentile_counts_failures_as_the_worst():
    values = [10.0] * 18 + [math.inf, math.inf]
    assert rates.percentile(values, 50) == 10.0
    assert rates.percentile(values, 95) == math.inf
    with pytest.raises(ValueError):
        rates.percentile([], 50)


def test_tpot_is_per_request_last_minus_first_over_n_minus_one():
    assert rates.tpot_ms(2.0, 2.9, 10) == pytest.approx(100.0)
    assert rates.tpot_ms(2.0, 2.0, 1) is None


def test_prompt_tokens_count_where_prefilled_and_output_tokens_where_emitted():
    steps = [
        {"kind": "put", "uids": [1], "sizes": [6]},            # first chunk of a 10-token prompt
        {"kind": "put", "uids": [1, 2], "sizes": [4, 3]},      # its last chunk emits; 2's whole prompt emits
        {"kind": "put", "uids": [1, 2, 3], "sizes": [1, 1, 1]},  # two decode rows, and a 1-token prompt
        {"kind": "decode", "uids": [1, 2, 3], "sizes": [4, 4, 4]},
    ]
    got = rates.classify_serving_steps(steps, {1: 10, 2: 3, 3: 1})
    assert got == [(6, 0), (7, 2), (1, 3), (0, 12)]
    assert sum(p for p, _ in got) == 10 + 3 + 1
