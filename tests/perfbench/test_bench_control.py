"""The control of the serving cells' ``correct``: the program with its own
lower-precision paths switched on (an int8 KV cache, an int8 weight stream)
must come out NOT correct against the plain reference, while the program as
the cells run it comes out correct. Here at the rehearsal twin's size on the
CPU, where the configuration states float32 and a tolerance of 1e-3, the
controls read ten times the tolerance and more. On the chip at the published
widths the builder read the same comparison (PR 26, PERF.md sections 2 and
7): the bf16 program 7.8-8.4e-3, its int8 KV cache 1.05-1.11e-2, both under
``mistral-7b``'s tolerance of 2e-2, so there the limit does not yet separate
them. A limit that a control passes lets a later PR trade precision for speed
unseen."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import loader
from benchmark.lib.model import model_config, seed_word

CONTROLS = {"int8_kv": {"kv_dtype": "int8"}, "int8_weights": {"quantize_weights": True}}


@pytest.fixture(scope="module")
def twin():
    from deepspeed_tpu.models import TransformerLM

    cell = loader.resolve_cell("tiny-mistral.decode-heavy", rehearsal=True)
    cfg = model_config(cell["config_file"], jnp.float32)
    return cell, cfg, TransformerLM(cfg)


def _rel_l2(twin, seed, **engine_kwargs):
    """The check of ``builders/serve.py`` with the engine's precision options
    open: a prompt through one prefill, then each further position through the
    cache, against the configuration's reference on the same weights."""
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    cell, cfg, model = twin
    serve = loader.load_module("builders", cell["config_file"]["builder"])
    reference = loader.load_reference(cell)
    ck, ec = cell["config_file"]["check"], cell["config_file"]["engine"]
    n_prompt, n_decode = ck["prompt_tokens"], ck["decode_tokens"]
    params = serve.make_params(model, seed_word(seed), jnp.float32)
    ids = np.random.default_rng([seed, 7]).integers(0, cfg.vocab_size, size=n_prompt + n_decode, dtype=np.int32)
    ref = np.asarray(reference.forward_logits(reference.hyper_from_published(cell["config_file"]), params,
                                              jnp.asarray(ids[None, :]),
                                              list(range(n_prompt - 1, n_prompt + n_decode))))[0]
    sm = DSStateManagerConfig(max_tracked_sequences=2, max_ragged_batch_size=ec["max_ragged_batch_size"],
                              max_ragged_sequence_count=2, max_context=ec["max_context"])
    icfg = RaggedInferenceEngineConfig(kv_block_size=ec["kv_block_size"], num_kv_blocks=16, state_manager=sm,
                                       **{"kv_dtype": jnp.float32, **engine_kwargs})
    engine = InferenceEngineV2(model, icfg, params=params)
    got = serve.system_logits(engine, ids, n_prompt)
    return max(float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(got, ref)), ck["rel_l2_tol"]


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2600000077])
def test_the_program_as_the_cells_run_it_is_correct_with_room(twin, seed):
    worst, tol = _rel_l2(twin, seed)
    assert worst * 3 <= tol, (worst, tol)


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2600000077])
def test_a_lower_precision_path_of_the_program_comes_out_not_correct(twin, seed, control):
    worst, tol = _rel_l2(twin, seed, **CONTROLS[control])
    assert worst > 3 * tol, (control, worst, tol)
