"""The stratified generator: every seed offers the same multiset of lengths
and gaps, so the same requests, tokens and mean rate; only the order differs."""

import math

import numpy as np
import pytest

from benchmark.lib import loader, traffic

MIX = {"count": 40, "rate_per_s": 5.0,
       "prompt_tokens": {"kind": "loguniform", "lo": 64, "hi": 1024},
       "output_tokens": {"kind": "uniform", "lo": 32, "hi": 128}}


def test_quantile_points_are_the_mid_quantiles_of_the_distribution():
    assert traffic.quantile_points({"kind": "uniform", "lo": 0, "hi": 10}, 5) == pytest.approx([1, 3, 5, 7, 9])
    assert traffic.quantile_points({"kind": "fixed", "value": 7}, 3) == [7.0, 7.0, 7.0]
    pts = traffic.quantile_points({"kind": "loguniform", "lo": 1, "hi": 100}, 2)
    assert pts == pytest.approx([100 ** 0.25, 100 ** 0.75])
    exp = traffic.quantile_points({"kind": "exponential", "rate": 2.0}, 4)
    assert exp == pytest.approx([-math.log(1 - u) / 2.0 for u in (0.125, 0.375, 0.625, 0.875)])
    with pytest.raises(ValueError, match="unknown distribution"):
        traffic.quantile_points({"kind": "zipf"}, 3)


@pytest.mark.parametrize("seed_a,seed_b", [(1, 2), (7, 3000000019)])
def test_two_seeds_offer_the_same_work_in_another_order(seed_a, seed_b):
    a = traffic.make_requests(MIX, seed_a, 1000, cycles=3)
    b = traffic.make_requests(MIX, seed_b, 1000, cycles=3)
    assert len(a) == len(b) == 120
    for field in ("prompt_len", "max_new_tokens"):
        assert sorted(r[field] for r in a) == sorted(r[field] for r in b)
        assert [r[field] for r in a] != [r[field] for r in b]
    assert sum(r["prompt_len"] + r["max_new_tokens"] for r in a) == \
        sum(r["prompt_len"] + r["max_new_tokens"] for r in b)
    assert sorted(round(r["gap_s"], 12) for r in a) == sorted(round(r["gap_s"], 12) for r in b)
    assert all(len(r["prompt"]) == r["prompt_len"] for r in a)
    assert any(not np.array_equal(x["prompt"][:8], y["prompt"][:8]) for x, y in zip(a, b))


def test_the_same_seed_gives_the_same_requests():
    a = traffic.make_requests(MIX, 11, 1000, cycles=2)
    b = traffic.make_requests(MIX, 11, 1000, cycles=2)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))


def test_an_open_loop_cycle_lasts_exactly_count_over_rate_for_every_seed():
    for seed in (0, 5, 2**31 + 5):
        reqs = traffic.make_requests(MIX, seed, 1000, cycles=2, with_tokens=False)
        assert reqs[39]["due_s"] == pytest.approx(40 / 5.0)
        assert reqs[-1]["due_s"] == pytest.approx(80 / 5.0)
        assert all(x["due_s"] < y["due_s"] for x, y in zip(reqs, reqs[1:]))


def test_every_seed_plays_the_same_cycle_from_another_point():
    reqs = traffic.make_requests(MIX, 3, 1000, cycles=2, with_tokens=False)
    first, second = reqs[:40], reqs[40:]
    assert [r["prompt_len"] for r in first] == [r["prompt_len"] for r in second]
    key = lambda r: (r["prompt_len"], r["max_new_tokens"], round(r["gap_s"], 12))
    a = [key(r) for r in traffic.make_requests(MIX, 1, 1000, cycles=1, with_tokens=False)]
    b = [key(r) for r in traffic.make_requests(MIX, 2, 1000, cycles=1, with_tokens=False)]
    shift = b.index(a[0])
    assert shift != 0 and b[shift:] + b[:shift] == a  # the same neighbours in time, begun elsewhere
    other = [key(r) for r in traffic.make_requests({**MIX, "order_seed": 9}, 1, 1000, cycles=1, with_tokens=False)]
    for field in range(3):  # the order (and the pairing) is the file's, not the run's; the multiset is the mix's
        assert sorted(x[field] for x in other) == sorted(x[field] for x in a)
    assert other != a
    assert traffic.cycles_for(MIX, 30) == 5  # 8 s a cycle: four cover 30 s, one to spare
    assert traffic.cycles_for({"count": 32, "cycle_seconds": 8}, 30) == 5


@pytest.mark.parametrize("name", ["longprompt", "chat", "decode-heavy"])
def test_the_committed_mixes_offer_identical_totals_for_two_seeds(name):
    mix = loader._read_json(f"{loader.ROOT}/benchmark/traffic/{name}.json")
    a = traffic.make_requests(mix, 1, 32000, cycles=2, with_tokens=False)
    b = traffic.make_requests(mix, 2**31 + 11, 32000, cycles=2, with_tokens=False)
    assert len(a) == len(b)
    assert sum(r["prompt_len"] for r in a) == sum(r["prompt_len"] for r in b)
    assert sum(r["max_new_tokens"] for r in a) == sum(r["max_new_tokens"] for r in b)
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert all(lo <= r["prompt_len"] <= hi for r in a)


def test_a_mix_that_fixes_its_start_offers_every_seed_the_same_requests_in_the_same_order():
    """For a closed loop of long requests the point where the cycle starts decides how many requests
    begin inside the window; such a mix gives ``start`` and the seed draws token ids alone."""
    fixed = {**MIX, "start": 7}
    sizes = lambda reqs: [(r["position"], r["prompt_len"], r["max_new_tokens"]) for r in reqs]
    a = traffic.make_requests(fixed, 1, 1000, cycles=2)
    b = traffic.make_requests(fixed, 2**31 + 11, 1000, cycles=2)
    assert sizes(a) == sizes(b) and a[0]["position"] == 7 and a[40]["position"] == 7
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))  # the token ids are still the seed's
    assert traffic.make_requests({**MIX, "start": 47}, 1, 1000, cycles=1)[0]["position"] == 7  # taken modulo the cycle
    # the same seed draws the same token ids with and without a fixed start: the start's draw is still made
    free = traffic.make_requests(MIX, 1, 1000, cycles=1)
    same_point = traffic.make_requests({**MIX, "start": free[0]["position"]}, 1, 1000, cycles=1)
    assert sizes(free) == sizes(same_point)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(free, same_point))
    mix = loader._read_json(f"{loader.ROOT}/benchmark/traffic/decode-heavy.json")
    assert mix["start"] == 28 and mix["count"] == mix["clients"] == 32
