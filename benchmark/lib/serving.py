"""What the two serving drivers share: submitting a request and keeping its
record, and turning the step log and the request records of a run into the
end-to-end numbers and the readers' context."""

import math
import time

from . import rates

WAIT_S = 300.0  # a request that has not finished by then is a failure


def submit(system, request: dict, t_due: float) -> dict:
    """Submit one request through the gateway; the record keeps the handle
    until :func:`finish` reads the stream's stamps."""
    t_called = time.perf_counter()
    status, handle = system.gateway.submit(request["prompt"], max_new_tokens=request["max_new_tokens"])
    rec = {"prompt_len": int(request["prompt_len"]), "max_new_tokens": int(request["max_new_tokens"]),
           "t_due": t_due, "t_called": t_called, "status": status, "handle": None, "uid": None}
    if status == 200:
        rec["handle"], rec["uid"] = handle, handle.uid
    else:
        rec["error"] = str(handle)
    return rec


def finish(rec: dict, timeout: float = WAIT_S) -> dict:
    """Wait for the request's stream to end and read its stamps. ``ok`` means
    admitted, finished without error, with as many tokens as asked for."""
    handle = rec.pop("handle")
    if handle is None:
        rec.update(ok=False, n_out=0, first_token_t=None, last_token_t=None)
        return rec
    done = handle.stream.wait_done(timeout)
    stream = handle.stream
    rec.update(n_out=stream.produced, first_token_t=stream.first_token_t, last_token_t=stream.last_token_t,
               ok=bool(done and stream.error is None and stream.produced == rec["max_new_tokens"]))
    if stream.error:
        rec["error"] = stream.error
    return rec


def summarise(system, records, t_begin: float, t_end: float, measured) -> dict:
    """End-to-end numbers of a serving run.

    ``serve_tokens_per_s``: prompt and output tokens of the engine steps that
    lie wholly between the first fence at or after ``t_begin`` and the last
    at or before ``t_end``, over the time between those two fences.
    ``ttft_p50_ms`` / ``ttft_p95_ms`` / ``tpot_p95_ms``: over the ``measured``
    requests, time from due to first token and (last - first) / (n_out - 1);
    a failed or refused request counts as the worst (infinite). The manifest
    says which of them a cell reports."""
    steps = system.steps
    prompt_len = {r["uid"]: r["prompt_len"] for r in records if r["uid"] is not None}
    tokens = rates.classify_serving_steps(steps, prompt_len)
    first_step = {}
    for st in steps:
        for uid in st["uids"]:
            first_step.setdefault(uid, st["t0"])
    for r in records:
        r["gen_late_ms"] = (r["t_called"] - r["t_due"]) * 1e3
        r["ttft_ms"] = (r["first_token_t"] - r["t_due"]) * 1e3 if r["ok"] else math.inf
        tp = rates.tpot_ms(r["first_token_t"], r["last_token_t"], r["n_out"]) if r["ok"] else math.inf
        r["tpot_ms"] = tp
        r["queue_wait_ms"] = (first_step[r["uid"]] - r["t_due"]) * 1e3 if r["uid"] in first_step else math.inf
    fences = [st["t1"] for st in steps]
    rate, n_steps, seconds = rates.whole_step_rate(fences, [p + o for p, o in tokens], t_begin, t_end)
    out = {"serve_tokens_per_s": rate}
    ttfts = [r["ttft_ms"] for r in measured]
    tpots = [r["tpot_ms"] for r in measured if r["tpot_ms"] is not None]
    if ttfts:
        out["ttft_p50_ms"] = rates.percentile(ttfts, 50)
        out["ttft_p95_ms"] = rates.percentile(ttfts, 95)
    if tpots:
        out["tpot_p95_ms"] = rates.percentile(tpots, 95)
    return {"end_to_end": out, "step_tokens": tokens, "steps_in_window": n_steps, "window_seconds": seconds}


def window_steps(ctx, window):
    """The logged steps that lie wholly inside ``window``, each with its
    ``(prompt_tokens, output_tokens)``."""
    t0, t1 = window
    return [(st, tok) for st, tok in zip(ctx["system"].steps, ctx["step_tokens"])
            if st["t0"] >= t0 and st["t1"] <= t1]


def is_prefill(step: dict) -> bool:
    return step["kind"] == "put" and any(size > 1 for size in step["sizes"])


def result(cell, args, system, devices, compiles, phases, records, measured, window, setup_s,
           trace_window, reduced, counts) -> dict:
    """What a serving driver returns: the end-to-end numbers over ``window``,
    the readers' context and the counts, from its records."""
    from . import common
    from .peaks import peaks_for

    summary = summarise(system, records, window[0], window[1], measured)
    end_to_end = dict(summary["end_to_end"], setup_s=setup_s(system.steps))
    device = common.device_record(devices)
    failed = sum(1 for r in records if not r["ok"])
    ctx = {"kind": "serve", "cell": cell, "system": system, "requests": measured, "window": window,
           "trace_window": trace_window, "step_tokens": summary["step_tokens"], "compiles": compiles,
           "reduced": reduced, "device": device, "chips": len(devices), "end_to_end": end_to_end,
           "peaks": None if args.rehearsal else peaks_for(device["kind"])}
    return {"correct": bool(system.check["ok"] and failed == 0), "attempted": len(records), "failed": failed,
            "end_to_end": end_to_end, "ctx": ctx, "device": device,
            "counts": {"requests": len(records), **counts,
                       "steps_in_window": summary["steps_in_window"], "engine_steps": len(system.steps),
                       "tokens_offered": sum(r["prompt_len"] + r["max_new_tokens"] for r in records),
                       "programs_warmed": system.programs_warmed, "kv_blocks": system.kv_blocks,
                       "compiles_in_window": compiles.count_between(*window)},
            "check": dict(system.check, window_seconds=summary["window_seconds"], setup_phases=phases.marks)}
