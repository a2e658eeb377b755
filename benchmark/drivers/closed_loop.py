"""Driver ``closed_loop``: ``clients`` callers, each sending its next request
only when the previous one has completed, so a slow system receives less
load. Judged on throughput: the rate of tokens over the whole engine steps
between two fences. The window opens at the first fence after a ramp in which
every client has had a request complete, and the set-up time runs to there."""

import threading
import time


def run(cell: dict, args, t_process_start: float) -> dict:
    from benchmark.lib import common, serving, traffic

    phases, devices, compiles, system = common.begin_run(cell, args, t_process_start)
    tf = cell["traffic_file"]
    trace_seconds = float(tf["trace_seconds"]) if args.trace else 0.0
    requests = traffic.make_requests(tf, args.seed, system.cfg.vocab_size,
                                     traffic.cycles_for(tf, args.seconds + trace_seconds + 4 * tf["cycle_seconds"]))

    n_clients = int(tf["clients"])
    lock = threading.Lock()
    state = {"next": 0, "completed_by": [0] * n_clients}
    records, stop, ramped = [], threading.Event(), threading.Event()

    def client(index: int):
        while not stop.is_set():
            with lock:
                if state["next"] >= len(requests):
                    return
                request = requests[state["next"]]
                state["next"] += 1
            rec = serving.finish(serving.submit(system, request, time.perf_counter()))
            with lock:
                records.append(rec)
                state["completed_by"][index] += 1
                if all(state["completed_by"]):
                    ramped.set()

    threads = [threading.Thread(target=client, args=(i, ), name=f"bench-client-{i}", daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()
    if not ramped.wait(serving.WAIT_S):
        stop.set()
        raise RuntimeError("ramp: not every client completed a request")
    t_begin = time.perf_counter()
    time.sleep(args.seconds)
    t_end = time.perf_counter()
    reduced, trace_window = None, None
    if args.trace:
        tracer = common.Tracer(cell["root"], cell["name"])
        tracer.start()
        t_trace = time.perf_counter()
        time.sleep(trace_seconds)
        # the trace ends here, under the full load: the drain below, in which the rows empty one
        # by one, lasts as long as the longest answer and is no part of what the cell measures
        trace_window = (t_trace, time.perf_counter())
        reduced = tracer.stop_and_reduce()
    stop.set()  # no new request; those in flight finish outside the window and are counted
    for t in threads:
        t.join(serving.WAIT_S)
    system.gateway.stop()

    measured = [r for r in records if t_begin <= r["t_due"] <= t_end]
    # set-up runs to the window's first fence: the ramp is part of it
    setup_s = lambda steps: next(st["t1"] for st in steps if st["t1"] >= t_begin) - t_process_start
    return serving.result(cell, args, system, devices, compiles, phases, records, measured, (t_begin, t_end),
                          setup_s, trace_window, reduced, {"requests_in_window": len(measured)})
