"""One general traffic generator. A traffic file gives each length
distribution as a distribution and a count; the generator takes the count's
quantile points (a stratified draw) and lays them out as one *cycle* in an
order fixed by the file's own ``order_seed`` (which also pairs prompts with
outputs and gaps). A run plays that cycle over and over; ``--seed`` chooses
where in the cycle it starts, and draws the token ids. So every seed offers
the same requests, total tokens, mean rate AND the same neighbours in time,
begun at another point: measured on the chip (PR 23), two runs of one order
differed by 1-4% in a p95 where six free permutations spread by 13%.

A mix may fix the point itself with ``"start"``; then ``--seed`` draws the
token ids and the weights alone. It is for a closed loop whose requests are so
long that a window holds one or two of each client's: there the point decides
how many requests BEGIN inside the window and with them the prompt tokens the
rate counts. Measured on the chip (PR 26, 32 clients, answers of 512-1,024
tokens, 40 s): eleven seeds read 1,658 to 1,747 tokens/s with 45 to 51
requests begun in the window, while four runs of one point under two seeds
stayed within 0.36%.

Derived from ``tools/serving_load.py`` ``make_workload`` (seeded length draws);
that one samples, this one stratifies.
"""

import math
from typing import List

import numpy as np


def quantile_points(dist: dict, n: int) -> List[float]:
    """The ``n`` mid-quantile points ``(i + 0.5) / n`` of ``dist``:
    ``{"kind": "fixed", "value": v}``, ``{"kind": "uniform"|"loguniform",
    "lo": a, "hi": b}`` or ``{"kind": "exponential", "rate": r}``."""
    if n <= 0:
        raise ValueError(f"count must be positive, got {n}")
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["kind"]
    if kind == "fixed":
        return [float(dist["value"])] * n
    if kind == "uniform":
        lo, hi = float(dist["lo"]), float(dist["hi"])
        return [lo + u * (hi - lo) for u in us]
    if kind == "loguniform":
        lo, hi = math.log(float(dist["lo"])), math.log(float(dist["hi"]))
        return [math.exp(lo + u * (hi - lo)) for u in us]
    if kind == "exponential":
        rate = float(dist["rate"])
        if rate <= 0:
            raise ValueError(f"exponential rate must be positive, got {rate}")
        return [-math.log(1.0 - u) / rate for u in us]
    raise ValueError(f"unknown distribution kind {kind!r}")


def int_lengths(dist: dict, n: int) -> List[int]:
    return [max(1, int(round(x))) for x in quantile_points(dist, n)]


def make_cycle(traffic: dict) -> List[dict]:
    """The mix's one cycle of ``traffic["count"]`` requests: the fixed
    multiset of prompt lengths, output lengths and (open loop) gaps, each
    permuted by the FILE's ``order_seed``, the same for every run. ``gap_s``
    is the time from the previous request's due time to this one's."""
    n = int(traffic["count"])
    order = np.random.default_rng(int(traffic.get("order_seed", 0)))
    prompts = int_lengths(traffic["prompt_tokens"], n)
    outputs = int_lengths(traffic["output_tokens"], n)
    prompts = [prompts[i] for i in order.permutation(n)]
    outputs = [outputs[i] for i in order.permutation(n)]
    gaps = None
    if "rate_per_s" in traffic:
        g = quantile_points({"kind": "exponential", "rate": traffic["rate_per_s"]}, n)
        # the mid-quantile points of an exponential sum to a little under
        # n / rate; scale so that a cycle lasts exactly n / rate
        scale = (n / float(traffic["rate_per_s"])) / sum(g)
        gaps = [g[i] * scale for i in order.permutation(n)]
    out = []
    for i in range(n):
        req = {"prompt_len": prompts[i], "max_new_tokens": outputs[i]}
        if gaps is not None:
            req["gap_s"] = gaps[i]
        out.append(req)
    return out


def make_requests(traffic: dict, seed: int, vocab: int, cycles: int, with_tokens: bool = True) -> List[dict]:
    """``cycles`` cycles in a row, begun at the point of the cycle that
    ``seed`` picks (or the mix's ``start``); token ids from ``seed``. For an open loop every request
    also gets its ``due_s`` from the start."""
    rng = np.random.default_rng(int(seed))
    cycle = make_cycle(traffic)
    start = int(rng.integers(0, len(cycle)))  # drawn in any case: the token ids that follow keep their draws
    if "start" in traffic:
        start = int(traffic["start"]) % len(cycle)
    out, due = [], 0.0
    for i in range(int(cycles) * len(cycle)):
        req = dict(cycle[(start + i) % len(cycle)], position=(start + i) % len(cycle))
        if "gap_s" in req:
            due += req["gap_s"]
            req["due_s"] = due
        if with_tokens:
            req["prompt"] = rng.integers(0, vocab, size=req["prompt_len"], dtype=np.int32)
        out.append(req)
    return out


def cycles_for(traffic: dict, seconds: float) -> int:
    """Cycles that outlast ``seconds``: an open loop lasts ``count / rate`` a
    cycle; a closed loop gives ``cycle_seconds``, a safe underestimate of how
    long the system needs for one cycle."""
    if "rate_per_s" in traffic:
        per = int(traffic["count"]) / float(traffic["rate_per_s"])
    else:
        per = float(traffic["cycle_seconds"])
    return int(math.ceil(seconds / per)) + 1
