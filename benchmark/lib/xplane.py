"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the device numbers
the benchmark reports: busy and idle time, time per operation, collective
time that no compute hid, and idle gaps attributed to the benchmark's own
host spans.

What a v5e trace looks like (jax 0.9, looked at by hand, PR 23): one plane
per chip named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds the
TensorCore's operations one after another (a ``while`` encloses its body's
operations); the event name is the HLO instruction, ``%flash_fwd.1 = ...``,
so a Pallas kernel appears under the ``name`` it was given. The plane
``/host:CPU`` has one line per thread, and ``jax.profiler.TraceAnnotation``
spans sit on those lines under their own names. Host and device stamps share
one clock to within about a millisecond.
"""

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
OUTSIDE_SPANS = "host:outside_the_benchmark_s_spans"
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
                        r"collective-broadcast|ragged-all-to-all)")
_HLO_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)? = ")

Event = Tuple[str, float, float]  # name, start_s, end_s


def op_name(event_name: str) -> str:
    """``%flash_fwd.1 = (...) custom-call(...)`` -> ``flash_fwd``: the HLO
    instruction's name without its numeric suffix. Other names pass."""
    m = _HLO_NAME.match(event_name)
    if m:
        return m.group(1)
    return re.sub(r"\.\d+$", "", event_name.lstrip("%"))


def read_trace(path: str) -> dict:
    """``{"devices": {n: [Event]}, "spans": [Event]}`` from an xplane file:
    each chip's ``XLA Ops`` events and the host's ``bench/`` spans, in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "spans": spans}


def self_segments(events: Iterable[Event]) -> List[Event]:
    """Disjoint segments, each named for the innermost event running then: an
    enclosing ``while`` keeps only the time its body's operations leave."""
    out: List[Event] = []
    stack: List[list] = []  # [name, end, emitted_up_to]

    def close():
        name, end, cur = stack.pop()
        if end > cur:
            out.append((name, cur, end))
        if stack:
            stack[-1][2] = max(stack[-1][2], end)

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close()
        if stack:
            top = stack[-1]
            if start > top[2]:
                out.append((top[0], top[2], start))
            top[2] = max(top[2], start)
            end = min(end, top[1])
        stack.append([name, end, start])
    while stack:
        close()
    return out


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def reduce_trace(trace: dict, top: int = 10) -> dict:
    """Busy and idle seconds, time by operation, exposed collective time and
    attributed gaps. Seconds by operation and exposed collective seconds are
    averaged over the chips; gaps are those of the lowest-numbered chip."""
    devices = trace["devices"]
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation: nothing ran on the device")
    spans = trace["spans"]
    starts = [e[1] for evs in devices.values() for e in evs] + [s[1] for s in spans]
    ends = [e[2] for evs in devices.values() for e in evs] + [s[2] for s in spans]
    w0, w1 = min(starts), max(ends)
    n = len(devices)
    by_op: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    busy = exposed = 0.0
    gaps_of_first: List[Tuple[float, float]] = []
    for idx, dev in enumerate(sorted(devices)):
        for name, a, b in devices[dev]:
            calls[op_name(name)] += 1
        segs = self_segments(devices[dev])
        for name, a, b in segs:
            base = op_name(name)
            by_op[base] += (b - a) / n
            if COLLECTIVE.match(base):
                exposed += (b - a) / n
        dev_busy, merged = union_seconds((a, b) for _, a, b in segs)
        busy += dev_busy / n
        if idx == 0:
            edges = [w0] + [t for ab in merged for t in ab] + [w1]
            gaps_of_first = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                             if edges[i + 1] > edges[i]]
    # a gap is shared out among the spans that cover it, each for the part it
    # covers (spans of several threads are first cut to the innermost one);
    # what no span covers is the host's time outside the benchmark's spans
    span_segs = self_segments(spans)
    gap_by_span: Dict[str, float] = defaultdict(float)
    for a, b in gaps_of_first:
        covered = 0.0
        for name, s0, s1 in span_segs:
            overlap = min(b, s1) - max(a, s0)
            if overlap > 0:
                gap_by_span[name] += overlap
                covered += overlap
        if b - a > covered:
            gap_by_span[OUTSIDE_SPANS] += b - a - covered
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": w1 - w0, "busy_s": busy, "n_devices": n,
            "seconds_by_op": dict(by_op), "calls_by_op": {k: v / n for k, v in calls.items()},
            "collective_exposed_s": exposed,
            "device_ops": [[k, v] for k, v in rank(by_op)],
            "idle_gaps": [[k, v] for k, v in rank(gap_by_span)]}


def kernel_seconds(reduced: dict, names: Sequence[str]) -> float:
    return sum(reduced["seconds_by_op"].get(n, 0.0) for n in names)
