"""The program's own spans in a profiler trace, and the device's idle gaps
shared out among them.

``deepspeed_tpu/monitor/trace.py`` writes every ``Tracer.span(name)`` as a
``jax.profiler.TraceAnnotation`` named ``dstpu/<name>`` while a profiler
session is active, so the spans of the serving loop, the scheduler, the
engine and ``train_batch`` lie in the same ``*.xplane.pb`` as the device's
operations, on one clock, under the thread that ran them. This module reads
them back from the file a ``--trace 1`` run just wrote under
``<root>/.bench_trace/<cell>/``. A program without such spans (a parent
commit from before them) yields none, and every reader built on this returns
``None``.

What a trace holds (jax 0.9, looked at by hand on the CPU and on the v5e, PR
24): the span's keyword arguments arrive as the event's ``stats``, numbers as
numbers, lists as their ``str()`` (``"[1.5, 2.25]"``), and arguments set after
entry (``set_metadata``) as further stats of the same event, the last of a
repeated key being the newest. A profiler that leaves the arguments in the
event's name (``name#k=v,k=v#``, the form ``TraceMe`` encodes them in) is read
too. Each thread is one line of the ``/host:CPU`` plane.
"""

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from .xplane import DEVICE_PLANE, HOST_PLANE, OPS_LINE, SPAN_PREFIX, self_segments, union_seconds

PROGRAM_PREFIX = "dstpu/"
# a thread whose spans explain the device's gaps: the replica's driver
# thread, or the thread that dispatches training steps
DRIVER_MARKS = ("serving/loop_", "train/dispatch")
UNCOVERED = "host:outside_the_program_s_spans"
_ARG_SPLIT = re.compile(r",(?=[A-Za-z_]\w*=)")


class ProgramSpan(NamedTuple):
    name: str  # without the ``dstpu/`` prefix
    start_s: float
    end_s: float
    line: Tuple[int, int]  # (plane index, line index): one thread
    args: dict


def _value(raw):
    """A stat as the program set it: numbers stay, a list comes back from its
    ``str()``, anything else stays a string."""
    if isinstance(raw, str):
        text = raw.strip()
        if text[:1] in "[{":
            try:
                return json.loads(text.replace("'", '"'))
            except ValueError:
                return raw
        try:
            return float(text) if any(c in text for c in ".eE") else int(text)
        except ValueError:
            return raw
    return raw


def _split_name(name: str) -> Tuple[str, dict]:
    """``name#k=v,k=v#`` -> ``(name, {k: v})``; a plain name has no arguments."""
    if not name.endswith("#") or "#" not in name[:-1]:
        return name, {}
    base, _, encoded = name[:-1].partition("#")
    args = {}
    for pair in _ARG_SPLIT.split(encoded):
        key, eq, val = pair.partition("=")
        if eq:
            args[key] = _value(val)
    return base, args


def trace_path(root: str, cell_name: str) -> Optional[str]:
    """The one xplane file of the cell's last traced run, or None."""
    paths = glob.glob(os.path.join(root, ".bench_trace", cell_name, "plugins", "profile", "*", "*.xplane.pb"))
    return paths[0] if len(paths) == 1 else None


_cache: Dict[Tuple[str, float], dict] = {}


def read(path: str) -> dict:
    """``{"devices": {n: [Event]}, "bench_spans": [Event], "spans":
    [ProgramSpan]}`` from an xplane file, in seconds. Kept for the file's
    modification time: a run's several readers parse it once."""
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = _read(path)
    return _cache[key]


def _read(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, bench, spans = {}, [], []
    for p, plane in enumerate(data.planes):
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for ln, line in enumerate(plane.lines):
                for e in line.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        bench.append((name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
                    elif name.startswith(PROGRAM_PREFIX):
                        base, args = _split_name(name)
                        for k, v in e.stats:
                            args[k] = _value(v)
                        spans.append(ProgramSpan(base[len(PROGRAM_PREFIX):], e.start_ns * 1e-9,
                                                 (e.start_ns + e.duration_ns) * 1e-9, (p, ln), args))
    return {"devices": devices, "bench_spans": bench, "spans": spans}


def for_run(ctx: dict) -> Optional[dict]:
    """The parsed trace of the run whose readers' context is ``ctx``; None
    for a run that was not traced or whose program emitted no span."""
    if not ctx.get("reduced"):
        return None
    path = trace_path(ctx["cell"]["root"], ctx["cell"]["name"])
    if path is None:
        return None
    trace = read(path)
    return trace if trace["spans"] else None


def spans_named(trace: dict, name: str) -> List[ProgramSpan]:
    return [s for s in trace["spans"] if s.name == name]


def numbers(value) -> List[float]:
    """An argument as a list of numbers: a number is one, a list its items."""
    if isinstance(value, (int, float)):
        return [float(value)]
    if isinstance(value, (list, tuple)):
        return [float(v) for v in value if isinstance(v, (int, float))]
    return []


def first_chip_gaps(trace: dict) -> Tuple[float, List[Tuple[float, float]]]:
    """``(window_s, gaps)``: the traced window and the idle gaps of the
    lowest-numbered chip inside it, exactly as ``xplane.reduce_trace`` takes
    them (the window runs from the first to the last stamp of any device
    operation or ``bench/`` span)."""
    devices, bench = trace["devices"], trace["bench_spans"]
    if not devices or not any(devices.values()):
        raise ValueError("the trace holds no device operation: nothing ran on the device")
    starts = [e[1] for evs in devices.values() for e in evs] + [s[1] for s in bench]
    ends = [e[2] for evs in devices.values() for e in evs] + [s[2] for s in bench]
    w0, w1 = min(starts), max(ends)
    _, merged = union_seconds((a, b) for _, a, b in self_segments(devices[min(devices)]))
    edges = [w0] + [t for ab in merged for t in ab] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    return w1 - w0, gaps


def driver_segments(trace: dict) -> list:
    """The spans of the driver threads (those that emitted a span of
    ``DRIVER_MARKS``) cut to the innermost: disjoint ``(name, start, end)``
    segments, an enclosing span keeping only its self time."""
    lines = {s.line for s in trace["spans"] if s.name.startswith(DRIVER_MARKS)}
    return self_segments((s.name, s.start_s, s.end_s) for s in trace["spans"] if s.line in lines)


def gaps_by_span(trace: dict) -> dict:
    """``{"window_s", "idle_s", "by_span": {name: s}, "uncovered_s"}``: each
    idle gap of the first chip shared out among the innermost program spans
    that cover it, each for the part it covers; ``uncovered_s`` is idle time
    under no program span. ``sum(by_span) + uncovered_s == idle_s``. Kept
    with the trace: a cell's several share metrics sweep its gaps once."""
    if "gaps_by_span" not in trace:
        trace["gaps_by_span"] = _gaps_by_span(trace)
    return trace["gaps_by_span"]


def _gaps_by_span(trace: dict) -> dict:
    window_s, gaps = first_chip_gaps(trace)
    segments = sorted(driver_segments(trace), key=lambda s: s[1])  # disjoint, so one sweep
    by_span: Dict[str, float] = defaultdict(float)
    idle = uncovered = 0.0
    first = 0  # segments before it end before the current gap starts
    for a, b in gaps:
        while first < len(segments) and segments[first][2] <= a:
            first += 1
        covered, i = 0.0, first
        while i < len(segments) and segments[i][1] < b:
            name, s0, s1 = segments[i]
            overlap = min(b, s1) - max(a, s0)
            if overlap > 0:
                by_span[name] += overlap
                covered += overlap
            i += 1
        idle += b - a
        uncovered += max(b - a - covered, 0.0)
    return {"window_s": window_s, "idle_s": idle, "by_span": dict(by_span), "uncovered_s": uncovered}


def gap_share(trace: dict, names: Sequence[str]) -> float:
    """Percent of the traced window in which the first chip was idle under
    one of the spans ``names`` (innermost; ``UNCOVERED`` names the rest)."""
    shared = gaps_by_span(trace)
    seconds = sum(shared["by_span"].get(n, 0.0) for n in names if n != UNCOVERED)
    if UNCOVERED in names:
        seconds += shared["uncovered_s"]
    return 100.0 * seconds / shared["window_s"]
