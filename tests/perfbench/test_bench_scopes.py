"""What PR 53 added to the benchmark: the XLA half of a step gets names. The
program wraps each part of the model in ``jax.named_scope``
(``deepspeed_tpu/monitor/scopes.py``); ``benchmark/lib/op_scopes.py`` decodes
the HLO protos that a profiler session writes into the trace's own
``/host:metadata`` plane (by hand: ``ProfileData`` shows nothing of a plane
without lines), gives every self-time segment of the device to the scope of
its instruction in the program that ran it, and the reader
``scope_time_share`` makes seven shares of the busy time of it, for the eight
serving cells whose metric lists no test pins."""

import os

import pytest

from benchmark.lib import loader, op_scopes, program_spans, xplane
from benchmark.lib.xplane_write import _bytes, _int, encode_xspace

MS = 1_000_000  # ns
WORDS = ("embed", "attn_proj", "mixer", "sparse_index", "attn_out", "mlp", "moe", "lm_head", "sample", "loss",
         "optimizer")
EIGHT = ["mistral-7b.decode-heavy", "mellum2-12b-a2.5b.decode-heavy", "trinity-large-preview.decode-heavy-64",
         "sdar-30b-a3b-chat.block-diffusion-64", "glm-4.7-flash.longdoc", "solar-open2-250b.decode-heavy-128",
         "minicpm-sala.longctx", "nemotron-3-nano-30b-a3b.decode-heavy-256"]
SIX = [c for c in EIGHT if c not in ("mistral-7b.decode-heavy", "minicpm-sala.longctx")]
# the four cells whose metric lists tests/perfbench/test_bench_loader.py pins
PINNED = ["pythia-410m.pretrain", "mistral-7b.longprompt", "pythia-1.4b.zero3-x4", "mistral-7b.chat"]
KERNELS = ["paged_attn_q_tiled", "paged_attn_kv_split", "kda_chunk_scan", "kda_recurrent_step",
           "lightning_chunk_scan", "lightning_recurrent_step", "mamba2_chunk_scan", "mamba2_recurrent_step"]
# name -> (scopes, except_ops, layer, cells), in the order the manifest holds them
METRICS = {
    "proj_time_share.tput": (["attn_proj", "attn_out", "mlp"], [], "Model / memory", EIGHT),
    "lm_head_time_share.tput": (["lm_head"], [], "Model / memory", EIGHT),
    "sample_time_share.tput": (["sample"], [], "Serving engine", EIGHT),
    "mixer_glue_time_share.tput": (["mixer"], KERNELS, "Model / memory", EIGHT),
    "moe_glue_time_share.tput": (["moe"], ["moe_gmm"], "Kernels: grouped expert matmul", SIX),
    "index_time_share.tput": (["sparse_index"], [], "Kernels: selection indexer", ["minicpm-sala.longctx"]),
    "unscoped_time_share.tput": (["<none>", "<unmapped>"], [], "Device", EIGHT),
}
PARENT_METRICS = 65  # what ``per_layer`` held before this PR


def test_the_vocabulary_is_the_programs_own():
    from deepspeed_tpu.monitor import scopes

    assert op_scopes.vocabulary() == scopes.VOCABULARY == WORDS
    assert set(op_scopes.MARKS) <= set(WORDS)


# ---------------------------------------------------------------------------
# op_name -> scope
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/mlp/dot_general", "mlp"),
    ("jit(fwd)/while/body/closed_call/mixer/add", "mixer"),
    ("jit(step)/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attn_proj/dot_general",
     "attn_proj"),
    ("jit(fwd)/moe/mlp/dot_general", "mlp"),                      # a shared expert: the innermost word
    ("jit(fwd)/mixer/sparse_index/sort", "sparse_index"),
    ("jit(fwd)/mixer/attn_proj/dot_general", "attn_proj"),        # a mixer names its projections inside
    ("jit(step)/jvp(loss)/mul", "loss"),
    ("jit(fwd)/mlp_up/mul", None),                                # a name that merely contains a word
    ("jit(fwd)/sample_tokens/argmax", None),
    ("jit(loss)/mul", None),                                      # a jitted function's name is no scope
    ("jit(fwd)/pjit(sample)/argmax", None),
    ("jit(fwd)/while/body/closed_call", None),
    ("", None),
    (None, None),
])
def test_the_scope_of_a_path_is_its_innermost_whole_word(op_name, want):
    assert op_scopes.scope_of(op_name, WORDS) == want


# ---------------------------------------------------------------------------
# a hand-built trace: two programs that both own a ``fusion.1``
# ---------------------------------------------------------------------------
def _hlo_proto(module, instructions):
    """A serialized ``HloProto`` of one computation (and one fused one) whose
    instructions are ``{name: op_name}``; None: no metadata at all."""
    def instruction(name, op_name):
        body = _bytes(1, name.encode()) + _bytes(2, b"fusion")
        if op_name is not None:
            body += _bytes(7, _bytes(1, b"dot_general") + _bytes(2, op_name.encode()) + _int(4, 12))
        return _bytes(2, body + _int(35, 7))
    names = list(instructions)
    entry = _bytes(1, b"main") + b"".join(instruction(n, instructions[n]) for n in names[:-1])
    fused = _bytes(1, b"fused_computation") + instruction(names[-1], instructions[names[-1]])
    return _bytes(1, _bytes(1, module.encode()) + _bytes(2, b"main") + _bytes(3, fused) + _bytes(3, entry) + _int(5, 3))


def _metadata_plane(programs):
    """The ``/host:metadata`` plane as a profiler session writes it: no line,
    one event metadata a program, each with the stat ``Hlo Proto``."""
    body = _int(1, 99) + _bytes(2, op_scopes.METADATA_PLANE.encode())
    for i, (name, proto) in enumerate(programs.items(), start=1):
        meta = _int(1, i) + _bytes(2, name.encode()) + _bytes(5, _int(1, 1) + _bytes(6, proto))
        body += _bytes(4, _int(1, i) + _bytes(2, meta))
    body += _bytes(5, _int(1, 1) + _bytes(2, _int(1, 1) + _bytes(2, op_scopes.HLO_STAT.encode())))
    return _bytes(1, body)


PROGRAMS = {
    "jit_fwd(11)": {"fusion.1": "jit(fwd)/mlp/dot_general", "fusion.2": "jit(fwd)/lm_head/dot_general",
                    "while.3": "jit(fwd)/while", "copy.4": None, "paged_attn_kv_split.5": "jit(fwd)/mixer/pallas_call",
                    "fused.6": "jit(fwd)/mixer/add"},
    "jit_fwd(12)": {"fusion.1": "jit(fwd)/sample/argmax", "fusion.9": "jit(fwd)/attn_proj/dot_general"},
}


def _ops(t0, names):
    """Device events one after another from ``t0`` ms, ``(name, ms)`` each."""
    out = []
    for name, ms in names:
        out.append((f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", t0 * MS, ms * MS))
        t0 += ms
    return out


def _write(tmp_path, programs=PROGRAMS, modules=True, spans=True, cell="cell"):
    """One chip, 100 ms. Program 11 runs 0-40: ``fusion.1`` 10 ms, a ``while``
    of 20 ms whose body is the kernel (8) and ``fusion.2`` (6), then a
    ``copy`` XLA put in (6) and an operation no HLO holds (4). Program 12
    runs 50-70: ITS ``fusion.1`` 15 ms and ``fusion.9`` 5 ms."""
    ops = _ops(0, [("fusion.1", 10)]) + _ops(10, [("while.3", 20)]) + _ops(12, [("paged_attn_kv_split.5", 8), (
        "fusion.2", 6)]) + _ops(30, [("copy.4", 6), ("fusion.77", 4)]) + _ops(50, [("fusion.1", 15), ("fusion.9", 5)])
    device = {"XLA Ops": ops}
    if modules:
        device[op_scopes.MODULES_LINE] = [("jit_fwd(11)", 0, 40 * MS), ("jit_fwd(12)", 50 * MS, 20 * MS)]
    driver = [("dstpu/serving/loop_pull", 0, 1)]
    if spans:
        driver += [("dstpu/serving/engine_dispatch#program=put:64:4:greedy#", 0, 1 * MS),
                   ("dstpu/serving/engine_dispatch#program=decode:4:2#", 45 * MS, 1 * MS)]
    planes = {"/device:TPU:0": device, "/host:CPU": {"driver": driver, "bench": [("bench/decode", 0, 100 * MS)]}}
    d = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    extra = _metadata_plane({k: _hlo_proto("jit_fwd", v) for k, v in programs.items()}) if programs else b""
    path.write_bytes(encode_xspace(planes) + extra)  # an XSpace is its planes one after another
    return str(path), {"reduced": xplane.reduce_trace(xplane.read_trace(str(path))),
                       "cell": {"root": str(tmp_path), "name": cell}}


def test_the_decoder_finds_each_programs_instructions_the_fused_ones_too(tmp_path):
    path, _ = _write(tmp_path)
    programs = op_scopes.read_programs(path)
    assert set(programs) == set(PROGRAMS)
    assert programs["jit_fwd(11)"] == {k: v or "" for k, v in PROGRAMS["jit_fwd(11)"].items()}
    assert programs["jit_fwd(11)"]["fusion.1"] != programs["jit_fwd(12)"]["fusion.1"]
    # and the stats of an event's METADATA, which ProfileData hides
    stats = op_scopes.read_event_metadata_stats(path, op_scopes.METADATA_PLANE)
    assert set(stats) == set(PROGRAMS) and all(list(s) == [op_scopes.HLO_STAT] for s in stats.values())
    assert op_scopes.read_modules(path) == {0: [("jit_fwd(11)", 0.0, 0.04), ("jit_fwd(12)", 0.05, pytest.approx(0.07))]}


def test_a_file_without_the_plane_has_no_program(tmp_path):
    path, _ = _write(tmp_path, programs=None)
    assert op_scopes.read_programs(path) == {}
    assert op_scopes.seconds_by_scope(program_spans.read(path), path, WORDS) is None


def test_the_seconds_sum_to_busy_and_a_while_keeps_its_self_time(tmp_path):
    path, ctx = _write(tmp_path)
    table = op_scopes.seconds_by_scope(program_spans.read(path), path, WORDS)
    assert table["busy_s"] == pytest.approx(ctx["reduced"]["busy_s"]) == pytest.approx(0.060)
    assert sum(table["by"].values()) == pytest.approx(table["busy_s"])
    ms = {k: round(v * 1e3, 6) for k, v in table["by"].items()}
    assert ms == {("mlp", "fusion"): 10.0, ("<none>", "while"): 6.0, ("mixer", "paged_attn_kv_split"): 8.0,
                  ("lm_head", "fusion"): 6.0, ("<none>", "copy"): 6.0, ("<unmapped>", "fusion"): 4.0,
                  ("sample", "fusion"): 15.0, ("attn_proj", "fusion"): 5.0}
    by_program = {k: round(v * 1e3, 6) for k, v in table["by_program"].items()}
    assert by_program[("put:64:4:greedy", "mlp")] == 10.0 and by_program[("decode:4:2", "sample")] == 15.0
    assert sum(v for (p, _), v in by_program.items() if p == "put:64:4:greedy") == 40.0
    text = op_scopes.format_table(table)
    assert "sample" in text and "decode:4:2" in text and "paged_attn_kv_split" in text


def test_without_the_modules_line_an_operation_keeps_a_scope_only_where_the_programs_agree(tmp_path):
    path, _ = _write(tmp_path, modules=False)
    table = op_scopes.seconds_by_scope(program_spans.read(path), path, WORDS)
    ms = {k: round(v * 1e3, 6) for k, v in table["by"].items()}
    assert ms[("<none>", "fusion")] == 25.0, "both programs own a fusion.1, under two scopes"
    assert ms[("attn_proj", "fusion")] == 5.0 and ms[("lm_head", "fusion")] == 6.0
    assert sum(table["by"].values()) == pytest.approx(table["busy_s"])


# ---------------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------------
def _metric(name):
    return loader._read_json(os.path.join(loader.ROOT, "benchmark", "layer_metrics", name + ".json"))


def _read(ctx, name):
    metric = _metric(name)
    return loader.load_module("readers", metric["reader"]).read({**ctx, "args": metric.get("args", {})})


def test_each_share_is_its_scopes_seconds_over_busy_less_the_named_kernels(tmp_path):
    _, ctx = _write(tmp_path)
    got = {name: _read(ctx, name) for name in METRICS}
    assert got == pytest.approx({
        "proj_time_share.tput": 100 * 15 / 60, "lm_head_time_share.tput": 100 * 6 / 60,
        "sample_time_share.tput": 100 * 15 / 60, "mixer_glue_time_share.tput": 0.0,
        "moe_glue_time_share.tput": 0.0, "index_time_share.tput": 0.0,
        "unscoped_time_share.tput": 100 * 16 / 60})
    kernel = loader.load_module("readers", "kernel_time_share").read(
        {**ctx, "args": {"kernels": ["paged_attn_kv_split"]}})
    shares = [got[n] for n in METRICS if n not in ("moe_glue_time_share.tput", "index_time_share.tput")]
    assert sum(shares) + kernel == pytest.approx(100.0), "the parts, the kernel and the unscoped rest are the whole"


@pytest.mark.parametrize("case", ["untraced", "no_hlo", "before_the_scopes", "no_spans", "no_vocabulary"])
def test_the_reader_returns_none_where_there_is_nothing_to_read(tmp_path, monkeypatch, case):
    if case == "untraced":
        ctx = {"reduced": None, "cell": {"root": str(tmp_path), "name": "cell"}}
    elif case == "no_hlo":
        _, ctx = _write(tmp_path, programs=None)
    elif case == "before_the_scopes":  # the parent's program: its instructions have op_names, under no scope
        plain = {k: {i: (o and o.replace("/mlp/", "/").replace("/lm_head/", "/").replace("/attn_proj/", "/"))
                     for i, o in v.items()} for k, v in PROGRAMS.items()}
        _, ctx = _write(tmp_path, programs=plain)
    elif case == "no_spans":
        _, ctx = _write(tmp_path, spans=False)
        trace = program_spans.read(program_spans.trace_path(str(tmp_path), "cell"))
        trace["spans"].clear()
    else:
        _, ctx = _write(tmp_path)
        monkeypatch.setattr(op_scopes, "vocabulary", lambda: None)
    assert all(_read(ctx, name) is None for name in METRICS)


# ---------------------------------------------------------------------------
# the seven files and entries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(METRICS))
def test_the_metric_is_a_file_and_an_entry_appended_for_its_cells_and_no_pinned_one(name):
    manifest = loader.load_manifest()
    scopes, except_ops, layer, cells = METRICS[name]
    added = [m["name"] for m in manifest["per_layer"][PARENT_METRICS:PARENT_METRICS + len(METRICS)]]
    assert added == list(METRICS), "appended behind what the parent held, in the issue's order"
    (entry, ) = [m for m in manifest["per_layer"] if m["name"] == name]
    metric = _metric(name)
    said = ("name", "unit", "better", "source", "layer", "moves")
    assert {k: entry[k] for k in said} == {k: metric[k] for k in said}
    assert (entry["layer"], entry["moves"], entry["better"], entry["unit"], entry["source"]) == (
        layer, "serve_tokens_per_s", "lower", "%", "device_trace")
    assert entry["workloads"] == cells and not set(cells) & set(PINNED)
    assert metric["reader"] == "scope_time_share"
    assert metric["args"] == ({"scopes": scopes, "except_ops": except_ops} if except_ops else {"scopes": scopes})
    assert set(scopes) <= set(WORDS) | {op_scopes.NONE, op_scopes.UNMAPPED}
    assert layer in {m["layer"] for m in manifest["per_layer"][:PARENT_METRICS]}, "a layer the manifest names"
    for cell in manifest["workloads"]:
        resolved = loader.resolve_cell(cell["name"])
        assert (name in {m["name"] for m in resolved["layer_metrics"]}) == (cell["name"] in cells)
        if cell["name"] in cells:
            assert entry["moves"] in {m["name"] for m in resolved["end_to_end"]}


def test_the_glue_metric_leaves_out_every_mixer_kernel_a_time_share_names():
    """``mixer_glue`` is the mixer less its Pallas kernels: every kernel that a
    ``*_time_share`` of the paged, delta-rule, lightning and state-space layers
    names is in its ``except_ops``, so glue + kernels = the ``mixer`` scope."""
    named = set()
    for name in ("paged_decode_time_share.tput", "paged_prefill_time_share", "kda_time_share.tput",
                 "lightning_time_share.tput", "mamba_time_share.tput"):
        named |= set(_metric(name)["args"]["kernels"])
    # (PR 29 deleted the per-token grid; the accepted file still names it, and no program has it)
    assert named - {"paged_attn_per_token"} == set(KERNELS)
    assert _metric("moe_time_share.tput")["args"]["kernels"] == ["moe_gmm"]


# ---------------------------------------------------------------------------
# a real capture
# ---------------------------------------------------------------------------
def test_a_real_profiler_capture_holds_the_programs_hlo_with_its_scopes(tmp_path):
    """The CPU profiler writes the same ``/host:metadata`` plane the v5e's
    does: the HLO of a two-scope function is in it, scan body and all."""
    import glob

    import jax
    import jax.numpy as jnp

    def f(x, w):
        with jax.named_scope("attn_proj"):
            y = x @ w

        def body(c, _):
            with jax.named_scope("mlp"):
                return jnp.tanh(c @ w) + c, None

        return jax.lax.scan(body, y, None, length=3)[0]

    x = jnp.ones((16, 16))
    g = jax.jit(f)
    g(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        g(x, x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path, ) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    programs = op_scopes.read_programs(path)
    (name, ) = [p for p in programs if p.startswith("jit_f(")]
    op_names = set(programs[name].values())
    assert "jit(f)/attn_proj/dot_general" in op_names
    assert "jit(f)/while/body/closed_call/mlp/dot_general" in op_names
    found = {op_scopes.scope_of(o, WORDS) for o in op_names}
    assert {"attn_proj", "mlp"} <= found
