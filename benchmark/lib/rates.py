"""Arithmetic of the end-to-end numbers: rates over whole steps between
fenced stamps, percentiles, time per output token.

Nothing here divides by the nominal ``--seconds``. A *fence* is a host-clock
stamp taken right after a value of that step was fetched from the device, so
all the step's device work lies before it. A rate is the work of the steps
that lie wholly between two fences over the time between those two fences.
"""

import math
from typing import Optional, Sequence, Tuple


def whole_step_window(fences: Sequence[float], t_begin: float, t_end: float) -> Optional[Tuple[int, int]]:
    """Indices ``(a, b)`` of the first fence at or after ``t_begin`` and the
    last fence at or before ``t_end``; ``None`` when fewer than one whole
    step lies between them. ``fences`` is non-decreasing."""
    a = next((i for i, t in enumerate(fences) if t >= t_begin), None)
    if a is None:
        return None
    b = None
    for i in range(len(fences) - 1, a, -1):
        if fences[i] <= t_end:
            b = i
            break
    return None if b is None else (a, b)


def whole_step_rate(fences: Sequence[float], work: Sequence[float], t_begin: float, t_end: float):
    """Work per second over the whole steps inside ``[t_begin, t_end]``.

    ``fences[i]`` closes step ``i`` and ``work[i]`` is what step ``i`` did.
    Returns ``(rate, n_steps, seconds)``; moving either edge anywhere inside
    a step selects the same fences and so the same rate."""
    if len(fences) != len(work):
        raise ValueError(f"{len(fences)} fences for {len(work)} steps")
    win = whole_step_window(fences, t_begin, t_end)
    if win is None:
        raise ValueError("no whole step between the edges: lengthen the window")
    a, b = win
    seconds = fences[b] - fences[a]
    if seconds <= 0:
        raise ValueError("the two fences coincide")
    return sum(work[a + 1:b + 1]) / seconds, b - a, seconds


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between the
    two nearest ranks. Infinite values (failed requests) sort last."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0-100")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or xs[lo] == xs[hi]:
        return float(xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tpot_ms(first_token_t: float, last_token_t: float, n_out: int) -> Optional[float]:
    """Time per output token of ONE request in milliseconds: (last - first) /
    (n_out - 1). Per request and not per gap, because the engine's decode
    emits a horizon of tokens at once. ``None`` for fewer than two tokens."""
    if n_out < 2:
        return None
    return (last_token_t - first_token_t) / (n_out - 1) * 1e3


def classify_serving_steps(steps, prompt_len):
    """Prompt and output tokens of each engine step.

    ``steps``: dicts with ``kind`` ('put' | 'decode'), ``uids`` and ``sizes``
    (tokens fed per row for a put, the horizon per row for a decode), in the
    order the engine ran them. ``prompt_len[uid]`` is each request's prompt
    length. A prompt token counts in the step that prefilled it; an output
    token in the step that emitted it: a put row emits one token when its
    prompt is complete after the row, a decode row emits its horizon.
    Returns a list of ``(prompt_tokens, output_tokens)``."""
    fed = {}
    out = []
    for st in steps:
        n_prompt = n_out = 0
        for uid, size in zip(st["uids"], st["sizes"]):
            if st["kind"] == "decode":
                n_out += size
                continue
            plen = prompt_len[uid]
            before = fed.get(uid, 0)
            n_prompt += max(0, min(size, plen - before))
            fed[uid] = before + size
            if fed[uid] >= plen:
                n_out += 1
        out.append((n_prompt, n_out))
    return out
