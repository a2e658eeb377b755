"""A minimal writer of the profiler's ``XSpace`` protobuf (planes, lines,
events with a name, a start and a duration), enough to cut a recorded trace
down to a sample small enough to keep, and to build one by hand in a test.
Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``.
"""

from typing import Dict, List, Sequence, Tuple


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(int(value))


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def encode_xspace(planes: Dict[str, Dict[str, Sequence[Tuple[str, int, int]]]]) -> bytes:
    """``{plane: {line: [(event_name, start_ns, duration_ns), ...]}}`` ->
    serialized ``XSpace``."""
    space = b""
    for plane_id, (plane_name, lines) in enumerate(planes.items(), start=1):
        ids: Dict[str, int] = {}
        body = _int(1, plane_id) + _bytes(2, plane_name.encode())
        for line_id, (line_name, events) in enumerate(lines.items(), start=1):
            t0 = min((s for _, s, _ in events), default=0)
            line = _int(1, line_id) + _bytes(2, line_name.encode()) + _int(3, t0)
            for name, start, dur in events:
                mid = ids.setdefault(name, len(ids) + 1)
                line += _bytes(4, _int(1, mid) + _int(2, (start - t0) * 1000) + _int(3, dur * 1000))
            body += _bytes(3, line)
        for name, mid in ids.items():
            meta = _int(1, mid) + _bytes(2, name.encode())
            body += _bytes(4, _int(1, mid) + _bytes(2, meta))
        space += _bytes(1, body)
    return space


def cut_trace(path: str, keep_seconds: float, max_name: int = 160) -> bytes:
    """The first ``keep_seconds`` of a recorded trace's device operations and
    ``bench/`` spans, names shortened, as a new ``XSpace``."""
    from jax.profiler import ProfileData

    from .xplane import DEVICE_PLANE, HOST_PLANE, OPS_LINE, SPAN_PREFIX

    data = ProfileData.from_file(path)
    kept: Dict[str, Dict[str, List[Tuple[str, int, int]]]] = {}
    t0 = None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        t0 = e.start_ns if t0 is None else min(t0, e.start_ns)
    if t0 is None:
        raise ValueError("no device operation in the trace")
    limit = t0 + keep_seconds * 1e9
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            evs = [(e.name[:max_name], int(e.start_ns), int(e.duration_ns)) for e in line.events
                   if e.start_ns < limit and (device or e.name.startswith(SPAN_PREFIX))]
            if evs:
                kept.setdefault(plane.name, {})[line.name] = evs
    return encode_xspace(kept)
