"""The device's time by part of the model, on the chip: one traced ``decode``
call of the Mistral twin at published widths (4 of its layers) is read back by
the scopes of ``deepspeed_tpu/monitor/scopes.py`` from the trace's own HLO."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np


def test_a_traced_decode_call_is_read_back_by_the_scopes_of_the_program(tmp_path):
    from benchmark.lib import op_scopes, program_spans
    from deepspeed_tpu.inference.v2 import DSStateManagerConfig, InferenceEngineV2, RaggedInferenceEngineConfig
    from deepspeed_tpu.models import mistral

    rows, steps = 32, 4
    model = mistral("7b", num_layers=4, dtype=jnp.bfloat16)
    sm = DSStateManagerConfig(max_tracked_sequences=rows, max_ragged_batch_size=2048,
                              max_ragged_sequence_count=rows, max_context=2048, seq_buckets=(rows, ))
    # bf16 weights, as the cells serve them: float32 ones are converted a call, by operations of XLA's own
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
                                    jax.jit(lambda r: model.init(r, None))(jax.random.PRNGKey(0)))
    engine = InferenceEngineV2(model, RaggedInferenceEngineConfig(
        kv_block_size=128, num_kv_blocks=rows * 4, kv_dtype=jnp.bfloat16, state_manager=sm,
        use_pallas_kernels="always"), params=params)
    rng = np.random.default_rng(0)
    uids = list(range(rows))
    first = engine.put(uids, [rng.integers(0, 32000, size=30 + i, dtype=np.int32) for i in uids], sample="greedy")
    firsts = [np.asarray([int(t)], np.int32) for t in first]
    toks = engine.decode(uids, firsts, steps)  # compiles the program outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            toks = engine.decode(uids, [np.asarray([int(t[-1])], np.int32) for t in np.asarray(toks)], steps)
    finally:
        jax.profiler.stop_trace()
    (path, ) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))

    programs = op_scopes.read_programs(path)
    assert programs, "the v5e's trace holds no /host:metadata plane with the programs' HLO"
    table = op_scopes.seconds_by_scope(program_spans.read(path), path, op_scopes.vocabulary())
    print("\n" + op_scopes.format_table(table))
    busy = table["busy_s"]
    assert busy > 0 and abs(sum(table["by"].values()) - busy) < 1e-3 * busy
    by_scope = {}
    for (scope, _), s in table["by"].items():
        by_scope[scope] = by_scope.get(scope, 0.0) + 100 * s / busy
    assert abs(sum(by_scope.values()) - 100.0) < 0.1
    unscoped = {base: 100 * s / busy for (scope, base), s in table["by"].items()
                if scope in (op_scopes.NONE, op_scopes.UNMAPPED)}
    # what XLA put in itself (copies of the loop's carry, the while's own time) carries no op_name
    assert sum(unscoped.values()) < 10.0, sorted(unscoped.items(), key=lambda kv: -kv[1])[:8]
    assert ("mixer", "paged_attn_kv_split") in table["by"], "the Pallas kernel under ``mixer``, by its own name"
    # (no ``sample``: XLA fuses the greedy argmax into the LM head's matmul, ``iota_reduce_fusion``, and a
    # fusion takes the name of its matmul: the token choice has no operation of its own in a greedy program)
    assert {"attn_proj", "attn_out", "mlp", "lm_head"} <= set(by_scope)
    assert by_scope["mlp"] > by_scope["lm_head"] > 0
    assert ("lm_head", "iota_reduce_fusion") in table["by"]
    # the dispatch spans name the program that ran the window's operations
    assert "decode:%d:%d" % (rows, steps) in {p for p, _ in table["by_program"]}
