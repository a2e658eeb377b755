"""Which part of the model a device operation belongs to, read back from the
trace's own HLO.

The model code wraps each part of a step in ``jax.named_scope`` (the
vocabulary is ``deepspeed_tpu/monitor/scopes.py``); a scope is metadata and
survives into the compiled module as the ``op_name`` of every instruction
traced under it, fusions and custom calls included
(``jit(fwd)/while/body/closed_call/mlp/dot_general``). A profiler session
writes each program's HLO proto into the ``*.xplane.pb`` itself: the plane
``/host:metadata`` has no lines, and its event metadata are the programs,
named ``<module>(<program id>)``, each with one stat ``Hlo Proto`` whose
bytes are the serialized ``HloProto``. ``jax.profiler.ProfileData`` shows
nothing of it (it iterates an event's own stats and a plane without lines has
no events), so this module decodes those bytes by hand, as
``xplane_write.py`` encodes them (field numbers of
``tsl/profiler/protobuf/xplane.proto`` and ``xla/service/hlo.proto``).

How an operation is tied to its program: the device plane's line ``XLA
Modules`` holds one event an execution, named as the metadata plane names
the program, and encloses that execution's ``XLA Ops`` events in time. An
operation under no module event is looked up in every program and keeps a
scope only where they all agree.

    python -m benchmark.lib.op_scopes [--json] <file.xplane.pb>

prints the table of a capture (scope, seconds, share of busy time, the
operations that lead it; by ``program`` too where the program's
``serving/engine_dispatch`` spans carry it): the by-hand reading of a cell's
``.bench_trace/<cell>/...`` and of a capture taken through ``POST
/v1/profile``.
"""

import bisect
import re
import sys
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from .xplane import DEVICE_PLANE, op_name as base_name, self_segments, union_seconds

METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"
HLO_STAT = "Hlo Proto"
NONE = "<none>"          # an operation under no scope of the vocabulary
UNMAPPED = "<unmapped>"  # an operation whose instruction no HLO in the trace holds
# parts that every program with scopes has: one without them is from before the scopes
MARKS = ("attn_proj", "mlp", "lm_head")

_INSTRUCTION = re.compile(r"^%?([^\s=]+) = ")
_JIT = re.compile(r"\bp?jit\([^()]*\)")
_TOKEN = re.compile(r"([^/()]+)(\(?)")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def vocabulary() -> Optional[Tuple[str, ...]]:
    """The program's vocabulary of scopes; None for a program without one (a
    commit from before the scopes)."""
    try:
        from deepspeed_tpu.monitor.scopes import VOCABULARY
    except ImportError:
        return None
    return tuple(VOCABULARY)


# ---------------------------------------------------------------------------
# the raw protobuf
# ---------------------------------------------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of a serialized message: an int
    for a varint, the bytes for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not a serialized XSpace")
        yield number, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def hlo_op_names(hlo_proto) -> Dict[str, str]:
    """``{instruction name: metadata.op_name}`` over every computation of a
    serialized ``HloProto`` (the fused ones too; ``""`` where an instruction
    has no ``op_name``: one that XLA itself put in)."""
    out: Dict[str, str] = {}
    for number, _, module in fields(hlo_proto):
        if number != 1:  # HloProto.hlo_module
            continue
        for number, _, computation in fields(module):
            if number != 3:  # HloModuleProto.computations
                continue
            for number, _, instruction in fields(computation):
                if number != 2:  # HloComputationProto.instructions
                    continue
                name, op = None, ""
                for number, _, value in fields(instruction):  # (serialized in the order of the numbers)
                    if number == 1:  # HloInstructionProto.name
                        name = _text(value)
                    elif number == 7:  # .metadata (OpMetadata), whose field 2 is op_name
                        op = next((_text(v) for n, _, v in fields(value) if n == 2), "")
                    elif number > 7:
                        break
                if name is not None:
                    out[name] = op
    return out


def _plane_name(plane) -> str:
    for number, _, value in fields(plane):
        if number == 2:
            return _text(value)
        if number > 2:
            break
    return ""


def _metadata_map(plane, number_of: int) -> Dict[int, dict]:
    """A plane's ``event_metadata`` (field 4) or ``stat_metadata`` (5) map:
    ``{id: {"name", "stats": [raw XStat]}}``."""
    out = {}
    for number, _, entry in fields(plane):
        if number != number_of:
            continue
        key, meta = None, {"name": "", "stats": []}
        for n, _, value in fields(entry):
            if n == 1:
                key = value
            elif n == 2:
                for m, _, v in fields(value):
                    if m == 2:
                        meta["name"] = _text(v)
                    elif m == 5 and number_of == 4:  # XEventMetadata.stats
                        meta["stats"].append(v)
        if key is not None:
            out[key] = meta
    return out


def _stat(raw, stat_names: Dict[int, dict]):
    """``(name, value)`` of a raw ``XStat``: a number, a string, bytes, or the
    name a ``ref_value`` points at."""
    name, value = None, None
    for number, wire, v in fields(raw):
        if number == 1:
            name = stat_names.get(v, {}).get("name", str(v))
        elif number == 7:
            value = stat_names.get(v, {}).get("name", str(v))
        elif number == 5:
            value = _text(v)
        elif wire == 0 or number == 6:
            value = v
    return name, value


def read_hlo_protos(path: str) -> Dict[str, memoryview]:
    """``{program: its serialized HloProto}`` from the xplane file's metadata
    plane, a program under the name the plane gives it, ``<module>(<program
    id>)``. Empty where the file has no such plane. The plane holds every
    program the process compiled, dozens in a serving cell, and a window runs
    a few: a reader decodes the ones it meets."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    protos: Dict[str, memoryview] = {}
    for number, _, plane in fields(space):
        if number != 1 or _plane_name(plane) != METADATA_PLANE:
            continue
        stat_names = _metadata_map(plane, 5)
        for meta in _metadata_map(plane, 4).values():
            for raw in meta["stats"]:
                name, value = _stat(raw, stat_names)
                if name == HLO_STAT and value is not None:
                    protos[meta["name"]] = value
    return protos


def read_programs(path: str) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction name: op_name}}`` of every program in the
    file's metadata plane."""
    return {name: hlo_op_names(proto) for name, proto in read_hlo_protos(path).items()}


def read_event_metadata_stats(path: str, plane_name: str) -> Dict[str, dict]:
    """``{event name: {stat: value}}`` of one plane's event METADATA, which
    ``ProfileData`` hides: what a look at a new kind of trace starts from."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, _, plane in fields(space):
        if number == 1 and _plane_name(plane) == plane_name:
            stat_names = _metadata_map(plane, 5)
            return {meta["name"]: dict(_stat(raw, stat_names) for raw in meta["stats"])
                    for meta in _metadata_map(plane, 4).values()}
    return {}


# ---------------------------------------------------------------------------
# op_name -> scope
# ---------------------------------------------------------------------------
def scope_of(op_name: str, vocabulary: Sequence[str]) -> Optional[str]:
    """The innermost word of ``vocabulary`` that is a whole component of the
    path ``op_name``, also inside ``jvp(...)``, ``transpose(jvp(...))``,
    ``checkpoint/rematted_computation/`` and ``while/body/closed_call/``; None
    under no scope. The name of a jitted function (``jit(loss)``) and of a
    transform is no scope, and neither is a primitive that merely contains a
    word (``mlp_up``)."""
    found = None
    for token, applied in _TOKEN.findall(_JIT.sub("", op_name or "")):
        if not applied and token in vocabulary:
            found = token
    return found


# ---------------------------------------------------------------------------
# seconds by scope
# ---------------------------------------------------------------------------
def read_modules(path: str) -> Dict[int, List[Tuple[str, float, float]]]:
    """Each chip's ``XLA Modules`` events, ``(program, start_s, end_s)``."""
    from jax.profiler import ProfileData

    out: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[int(m.group(1))] = sorted(
                    ((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events),
                    key=lambda e: e[1])
    return out


class _Attribution:
    """``(scope, base name)`` of a device operation by its event name and the
    program that ran it, each pair worked out once."""

    def __init__(self, protos: Dict[str, memoryview], words: Sequence[str]):
        self.protos, self.words = protos, words
        self.by_id = {m.group(1): name for name in protos if (m := _PROGRAM_ID.search(name))}
        self.decoded: Dict[str, Dict[str, str]] = {}
        self.known: Dict[Tuple[str, Optional[str]], Tuple[str, str]] = {}

    def program_of(self, module_event: Optional[str]) -> Optional[str]:
        if module_event is None or module_event in self.protos:
            return module_event
        m = _PROGRAM_ID.search(module_event)
        return self.by_id.get(m.group(1)) if m else None

    def ops_of(self, program: str) -> Dict[str, str]:
        if program not in self.decoded:
            self.decoded[program] = hlo_op_names(self.protos[program])
        return self.decoded[program]

    def __call__(self, event_name: str, module_event: Optional[str]) -> Tuple[str, str]:
        key = (event_name, module_event)
        if key not in self.known:
            m = _INSTRUCTION.match(event_name)
            instruction = m.group(1) if m else event_name.lstrip("%")
            program = self.program_of(module_event)
            held = [self.ops_of(p) for p in ([program] if program is not None else self.protos)]
            scopes = {scope_of(ops[instruction], self.words) or NONE for ops in held if instruction in ops}
            scope = scopes.pop() if len(scopes) == 1 else UNMAPPED if not scopes else NONE
            self.known[key] = (scope, base_name(event_name))
        return self.known[key]


def _dispatches(trace: dict) -> List[Tuple[float, str]]:
    return sorted((s.start_s, str(s.args["program"])) for s in trace.get("spans", ())
                  if s.name == "serving/engine_dispatch" and "program" in s.args)


def seconds_by_scope(trace: dict, path: str, words: Sequence[str]) -> Optional[dict]:
    """``{"busy_s", "by": {(scope, base operation name): seconds},
    "by_program": {(program, scope): seconds}}`` of a parsed trace
    (``program_spans.read``) and the file it came from: the device's self-time
    segments exactly as ``xplane.reduce_trace`` takes them (an enclosing
    ``while`` keeps what its body leaves; chips averaged), each given to the
    scope of its instruction's ``op_name`` in the program that ran it. The
    seconds sum to ``busy_s``. ``by_program`` splits them by the ``program``
    of the ``serving/engine_dispatch`` span that last started before the
    module's execution (``""`` where the spans carry none). None for a file
    without the metadata plane's HLO."""
    protos = read_hlo_protos(path)
    if not protos:
        return None
    attribute = _Attribution(protos, words)
    modules = read_modules(path)
    dispatches = _dispatches(trace)
    dispatch_starts = [t for t, _ in dispatches]
    devices = {dev: evs for dev, evs in trace["devices"].items() if evs}
    n = len(devices)
    by: Dict[Tuple[str, str], float] = defaultdict(float)
    by_program: Dict[Tuple[str, str], float] = defaultdict(float)
    busy = 0.0
    for dev, events in devices.items():
        runs = modules.get(dev, [])
        run_starts = [r[1] for r in runs]
        segments = self_segments(events)
        for name, a, b in segments:
            i = bisect.bisect_right(run_starts, a) - 1
            run = runs[i] if i >= 0 and a < runs[i][2] else None
            scope, base = attribute(name, run[0] if run else None)
            by[(scope, base)] += (b - a) / n
            j = bisect.bisect_right(dispatch_starts, run[1] if run else a) - 1
            by_program[(dispatches[j][1] if j >= 0 else "", scope)] += (b - a) / n
        busy += union_seconds((a, b) for _, a, b in segments)[0] / n
    return {"busy_s": busy, "by": dict(by), "by_program": dict(by_program)}


def for_run(ctx: dict) -> Optional[dict]:
    """:func:`seconds_by_scope` of the run whose readers' context is ``ctx``,
    kept with the parsed trace (a cell's several shares read it once). None
    for a run that was not traced, a program without the vocabulary or
    without spans, and a trace without HLO."""
    from . import program_spans

    words = vocabulary()
    trace = program_spans.for_run(ctx)
    if words is None or trace is None:
        return None
    if "seconds_by_scope" not in trace:
        trace["seconds_by_scope"] = seconds_by_scope(
            trace, program_spans.trace_path(ctx["cell"]["root"], ctx["cell"]["name"]), words)
    return trace["seconds_by_scope"]


def share(table: dict, scopes: Sequence[str], except_ops: Sequence[str] = ()) -> Optional[float]:
    """Percent of the busy time under ``scopes`` in operations other than
    ``except_ops``; None for a program none of whose operations lies under
    one of ``MARKS`` (compiled before the scopes)."""
    by = table["by"]
    if table["busy_s"] <= 0 or not any(scope in MARKS for scope, _ in by):
        return None
    seconds = sum(s for (scope, base), s in by.items() if scope in scopes and base not in except_ops)
    return 100.0 * seconds / table["busy_s"]


# ---------------------------------------------------------------------------
# the table by hand
# ---------------------------------------------------------------------------
def format_table(table: dict, top: int = 6) -> str:
    busy = table["busy_s"]
    lines = [f"busy {busy:.4f} s (chips averaged); seconds, share of busy, leading operations"]
    by_scope: Dict[str, Dict[str, float]] = defaultdict(dict)
    for (scope, base), s in table["by"].items():
        by_scope[scope][base] = s
    for scope, ops in sorted(by_scope.items(), key=lambda kv: -sum(kv[1].values())):
        total = sum(ops.values())
        lead = ", ".join(f"{base} {s:.4f}" for base, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top])
        lines.append(f"{scope:<14}{total:>10.4f} s {100 * total / busy:>6.2f}%   {lead}")
    programs = sorted({p for p, _ in table["by_program"]})
    if programs != [""]:
        lines.append("by program (of the serving/engine_dispatch span before it):")
        for program in programs:
            row = {scope: s for (p, scope), s in table["by_program"].items() if p == program}
            total = sum(row.values())
            parts = ", ".join(f"{scope} {100 * s / total:.1f}%" for scope, s in sorted(row.items(), key=lambda kv: -kv[1]))
            lines.append(f"  {program or '(none)':<24}{total:>9.4f} s {100 * total / busy:>6.2f}%   {parts}")
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-2], file=sys.stderr)
        return 2
    from . import program_spans

    words = vocabulary()
    if words is None:
        print("this checkout has no deepspeed_tpu/monitor/scopes.py: nothing to read the trace by", file=sys.stderr)
        return 1
    table = seconds_by_scope(program_spans.read(argv[0]), argv[0], words)
    if table is None:
        print(f"{argv[0]} holds no {METADATA_PLANE} plane with a program's HLO", file=sys.stderr)
        return 1
    if as_json:  # every (scope, operation) pair, for a script
        import json

        print(json.dumps({"busy_s": table["busy_s"], "by": [[*k, v] for k, v in table["by"].items()],
                          "by_program": [[*k, v] for k, v in table["by_program"].items()]}))
    else:
        print(format_table(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
