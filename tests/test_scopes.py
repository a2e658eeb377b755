"""The parts of a step are named in the program (``monitor/scopes.py``): the
matmuls of every step program lie under a scope of the vocabulary, little is
left under none, the train step has its loss and its optimizer, and a scope
is metadata: it adds no operation to any lowered program."""

import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from benchmark.lib import op_scopes
from deepspeed_tpu import models
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.monitor import scopes
from deepspeed_tpu.parallel import groups

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FREE = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")  # no work of their own
# the share of a compiled program's instructions that may lie under no scope: XLA:CPU's own expansions and
# broadcasts (no op_name) and the loop's plumbing
NONE_SHARE = 0.30


def _scope_of(op_name):
    return op_scopes.scope_of(op_name, scopes.VOCABULARY)


def _instructions(hlo_text):
    """``(name, opcode, scope)`` of every instruction of a compiled module's
    text that does work, the fused computations' among them; the scope is
    ``""`` for an instruction without any ``op_name``: one XLA made itself
    (XLA:CPU writes attention's batched dots anew and names them nothing)."""
    out = []
    for line in hlo_text.split("\n"):
        m = _INSTRUCTION.match(line)
        if m and m.group(2) not in _FREE:
            op = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), _scope_of(op.group(1)) if op else ""))
    return out


def _matmuls(found):
    """The matmuls that the program named, each with its scope."""
    return [(name, scope) for name, opcode, scope in found if opcode in ("dot", "convolution") and scope != ""]


def _unscoped(found):
    return sum(1 for _, _, scope in found if not scope)


def _engine(model):
    from deepspeed_tpu.inference.v2 import DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig

    sm = DSStateManagerConfig(max_tracked_sequences=4, max_ragged_batch_size=64, max_ragged_sequence_count=4,
                              max_context=128, token_buckets=(64, ), seq_buckets=(4, ))
    return InferenceEngineV2(model, RaggedInferenceEngineConfig(kv_block_size=16, num_kv_blocks=32,
                                                                kv_dtype=jnp.float32, state_manager=sm))


def _lowered(engine, kind):
    """The ``put`` (64 tokens, 4 rows, greedy) or ``decode`` (4 rows, 2
    steps) program of ``engine`` lowered from shapes, as ``compile_ahead``
    lowers it. Each call traces anew."""
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import packed_len

    shapes = lambda tree: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    engine._compiled.clear()
    fn = engine._get_compiled(64, 4, "greedy") if kind == "put" else engine._get_compiled_decode(4, 2)
    tokens = 64 if kind == "put" else 4
    packed = jax.ShapeDtypeStruct((packed_len(tokens, 4, engine._max_blocks_per_seq, bool(engine._state_layers)), ),
                                  jnp.int32)
    return fn.lower(shapes(engine.params), packed, shapes(engine.state_manager.kv_cache.pools()))


TWINS = {"dense": lambda: models.mistral("tiny", dtype=jnp.float32),
         "experts": lambda: models.trinity("tiny", dtype=jnp.float32),
         "state_layers": lambda: models.solar("tiny", dtype=jnp.float32, moe_experts_held=8)}
# the scopes a twin's step programs must hold (the state-layer twin has experts and one dense MLP too)
WANTED = {"dense": {"embed", "attn_proj", "mixer", "attn_out", "mlp", "lm_head", "sample"},
          "experts": {"embed", "attn_proj", "mixer", "attn_out", "mlp", "moe", "lm_head", "sample"},
          "state_layers": {"embed", "attn_proj", "mixer", "attn_out", "moe", "lm_head", "sample"}}


@pytest.fixture(scope="module")
def engine_of():
    built = {}

    def engine_of(twin):
        if twin not in built:
            built[twin] = _engine(TWINS[twin]())
        return built[twin]

    return engine_of


@pytest.mark.parametrize("kind", ["put", "decode"])
@pytest.mark.parametrize("twin", list(TWINS))
def test_every_matmul_of_a_step_program_lies_under_a_scope_and_little_under_none(engine_of, twin, kind):
    text = _lowered(engine_of(twin), kind).compile().as_text()
    found = _instructions(text)
    matmuls = _matmuls(found)
    assert matmuls and all(scope is not None for _, scope in matmuls), [n for n, s in matmuls if s is None]
    by_scope = collections.Counter(scope for _, _, scope in found)
    assert WANTED[twin] <= set(by_scope), sorted(WANTED[twin] - set(by_scope))
    assert _unscoped(found) < NONE_SHARE * len(found), by_scope
    # the projections are projections: no matmul is left to the mixer's glue but the mixer's own
    # (attention's scores and values off the TPU, the folded latent products, the delta rule's chunks)
    proj = [name for name, scope in matmuls if scope in ("attn_proj", "attn_out", "mlp", "moe", "lm_head")]
    assert len(proj) >= len(matmuls) // 2


@pytest.mark.parametrize("kind", ["put", "decode"])
def test_a_scope_adds_no_operation_to_a_step_program(engine_of, monkeypatch, kind):
    engine = engine_of("experts")
    with_scopes = _lowered(engine, kind).as_text()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = _lowered(engine, kind).as_text()
    assert with_scopes == without
    monkeypatch.undo()
    assert _lowered(engine, kind).as_text(debug_info=True) != with_scopes  # (the names are debug information)


def _train_engine(eight_devices, **over):
    groups.reset()
    cfg = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=64, intermediate_size=128,
               attention_impl="reference", dtype=jnp.float32)
    cfg.update(over)
    engine, _, _, _ = deepspeed_tpu.initialize(model=TransformerLM(TransformerConfig(**cfg)), config={
        "train_batch_size": 16, "train_micro_batch_size_per_gpu": 1, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}, "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1}, "tpu": {"mesh": {"data": 8}}, "steps_per_print": 100})
    return engine


@pytest.mark.parametrize("remat", [False, True])
def test_the_train_step_has_its_loss_and_its_optimizer_and_every_matmul_a_scope(eight_devices, remat):
    engine = _train_engine(eight_devices, remat=remat)
    found = _instructions(engine.aot_lower_train_step(32).compile().as_text())
    matmuls = _matmuls(found)
    assert matmuls and all(scope is not None for _, scope in matmuls), [n for n, s in matmuls if s is None]
    by_scope = collections.Counter(scope for _, _, scope in found)
    assert {"embed", "attn_proj", "mixer", "attn_out", "mlp", "lm_head", "loss", "optimizer"} <= set(by_scope)
    assert _unscoped(found) < NONE_SHARE * len(found), by_scope


def test_a_scope_adds_no_operation_to_the_train_step(eight_devices, monkeypatch):
    engine = _train_engine(eight_devices)
    with_scopes = engine.aot_lower_train_step(32).as_text()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    assert engine.aot_lower_train_step(32).as_text() == with_scopes
