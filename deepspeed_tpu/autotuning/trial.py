"""One measured autotuning trial, run in a fresh process.

``python -m deepspeed_tpu.autotuning.trial <spec.pkl> <result.json>``

The spec (written by ``Autotuner.tune_measured``) carries the candidate
ds_config plus a model description: either ``model_spec`` —
``TransformerConfig`` kwargs, fully process-portable — or a pickled
``model_factory`` (must be an importable module-level callable). The trial
builds the engine, runs ``warmup + steps`` real train steps with a host
fetch as the timing barrier, and writes ``{"tokens_per_s": ...}``.
Any failure lands in the JSON as ``{"error": ...}`` — the ResourceManager
treats it as a failed experiment, never a crashed sweep.
"""

import json
import pickle
import sys
import time


def run_trial(spec: dict) -> dict:
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.parallel import groups

    if spec.get("model_spec") is not None:
        import jax.numpy as jnp

        from deepspeed_tpu.models import TransformerConfig, TransformerLM

        kwargs = dict(spec["model_spec"])
        if isinstance(kwargs.get("dtype"), str):
            kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
        model = TransformerLM(TransformerConfig(**kwargs))
        seq = kwargs.get("max_seq_len", 128)
        vocab = kwargs.get("vocab_size", 32000)
    else:
        model = spec["model_factory"]()
        seq, vocab = spec["seq"], spec["vocab"]

    groups.reset()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=spec["ds_config"])
    global_batch = engine.train_batch_size()
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, vocab, size=(global_batch, seq), dtype=np.int32)}

    steps, warmup = spec.get("steps", 3), spec.get("warmup", 1)
    for _ in range(warmup):
        engine.train_batch(batch)
    float(np.asarray(engine.state["step"]))  # host fetch = real barrier
    t0 = time.time()
    for _ in range(steps):
        engine.train_batch(batch)
    float(np.asarray(engine.state["step"]))
    dt = (time.time() - t0) / steps
    return {"tokens_per_s": global_batch * seq / dt,
            "global_batch": global_batch, "seq": seq}


def main():
    spec_path, result_path = sys.argv[1], sys.argv[2]
    with open(spec_path, "rb") as f:
        spec = pickle.load(f)
    try:
        result = run_trial(spec)
    except Exception as e:  # recorded, not raised: one bad candidate != dead sweep
        result = {"error": f"{type(e).__name__}: {e}"[:500]}
    with open(result_path, "w") as f:
        json.dump(result, f)
    sys.exit(0)


if __name__ == "__main__":
    main()
