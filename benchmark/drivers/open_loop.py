"""Driver ``open_loop``: requests are due on a schedule fixed by the traffic
file and the seed, whether or not earlier ones have finished. Judged on
tails, timed from when each request was DUE; how late the generator ran is
reported beside them. The schedule is the mix's cycle played over and over
from the point the seed picks. After a ramp of ``ramp_cycles`` cycles, the
requests of the WHOLE cycles that fit into ``--seconds`` are measured, so
every seed measures the same multiset of requests, each with the same
neighbours in time: measured on the chip (PR 23), a p95 over a window that
cut the cycle at a seed-dependent point spread by 24% across six seeds. No
request is submitted after the nominal window; those in flight finish outside
it and are counted. The set-up time runs to the start of the schedule."""

import threading
import time


def run(cell: dict, args, t_process_start: float) -> dict:
    from benchmark.lib import common, serving, traffic

    phases, devices, compiles, system = common.begin_run(cell, args, t_process_start)
    tf = cell["traffic_file"]
    trace_seconds = float(tf["trace_seconds"]) if args.trace else 0.0
    horizon = args.seconds + trace_seconds
    requests = [r for r in traffic.make_requests(tf, args.seed, system.cfg.vocab_size,
                                                 traffic.cycles_for(tf, horizon)) if r["due_s"] < horizon]
    records = []
    tracer = common.Tracer(cell["root"], cell["name"]) if args.trace else None
    trace_window = None

    t_start = time.perf_counter()
    setup_s = t_start - t_process_start
    for request in requests:  # one generator thread: this one
        due = t_start + request["due_s"]
        if tracer is not None and trace_window is None and request["due_s"] >= args.seconds:
            tracer.start()
            trace_window = (time.perf_counter(), None)
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        records.append(dict(serving.submit(system, request, due), position=request["position"]))
    waiter = threading.Thread(target=lambda: [serving.finish(r) for r in records], name="bench-waiter")
    waiter.start()
    waiter.join()
    reduced = None
    if tracer is not None:
        if trace_window is None:
            raise RuntimeError("no request fell into the traced window")
        trace_window = (trace_window[0], time.perf_counter())
        reduced = tracer.stop_and_reduce()
    system.gateway.stop()

    per_cycle = int(tf["count"])
    cycle_seconds = per_cycle / float(tf["rate_per_s"])
    ramp_cycles = int(tf["ramp_cycles"])
    n_cycles = int((args.seconds - ramp_cycles * cycle_seconds) / cycle_seconds + 1e-9)
    if n_cycles < 1:
        raise ValueError(f"--seconds {args.seconds} holds no whole cycle of {cycle_seconds} s after the ramp")
    measured = records[ramp_cycles * per_cycle:(ramp_cycles + n_cycles) * per_cycle]
    t_begin = t_start + ramp_cycles * cycle_seconds
    t_end = t_begin + n_cycles * cycle_seconds
    out = serving.result(cell, args, system, devices, compiles, phases, records, measured, (t_begin, t_end),
                         lambda steps: setup_s, trace_window, reduced,
                         {"requests_measured": len(measured),
                          "in_flight_at_window_end": sum(1 for r in records if r["ok"] and r["last_token_t"] > t_end)})
    # per measured request [position in the cycle, ttft_ms, tpot_ms]: a p95 is ten requests, and
    # which ones they were is the first thing a surprising tail is asked
    out["tails"] = [[r["position"], round(r["ttft_ms"], 1), round(r["tpot_ms"] or 0.0, 2)] for r in measured]
    return out
