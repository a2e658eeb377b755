"""Convergence-grade training sanity test — the repo's analog of the
reference's ``tests/model/Megatron_GPT2/run_sanity_check.py`` (real training
to a known-good loss curve, not a 5-step loss-goes-down smoke).

A ~0.5M-param GPT (flash attention path) trains 200 real optimizer steps
under ZeRO-2 on the 8-device CPU mesh over a FIXED order-1 Markov corpus
(learnable structure: each token has 8 likely successors, so the model can
push loss well below the ln(256)=5.55 unigram floor). The loss curve sampled
every 10 steps must match the committed known-good curve
``tests/data/tiny_gpt_curve.json`` within 10% at every point.

Regenerate the curve after an intentional numerics change with:
    python tests/test_convergence.py --regen
"""

import json
import os
import sys

if __name__ == "__main__":
    # standalone --regen must see the same 8-virtual-device CPU backend the
    # pytest run gets from conftest.py — set up BEFORE any jax import; the
    # repo root goes on sys.path too (script invocation only adds tests/)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.parallel import groups

CURVE_PATH = os.path.join(os.path.dirname(__file__), "data", "tiny_gpt_curve.json")
STEPS = 200
SAMPLE_EVERY = 10


def _markov_batches(n_batches=20, batch=16, seq=64, vocab=256):
    """Deterministic learnable corpus: fixed sparse transition structure."""
    rng = np.random.default_rng(42)
    trans = rng.dirichlet(np.full(8, 0.2), size=vocab)  # succ distribution per token
    succ = rng.integers(0, vocab, size=(vocab, 8))
    out = []
    for key in range(n_batches):
        r = np.random.default_rng(key)
        ids = np.zeros((batch, seq), np.int32)
        ids[:, 0] = r.integers(0, vocab, size=batch)
        for t in range(1, seq):
            choice = np.array([r.choice(8, p=trans[tok]) for tok in ids[:, t - 1]])
            ids[:, t] = succ[ids[:, t - 1], choice]
        out.append({"input_ids": ids})
    return out


def _train_curve():
    groups.reset()
    cfg = TransformerConfig(vocab_size=256, hidden_size=128, num_layers=2, num_heads=4,
                            max_seq_len=64, intermediate_size=512, dtype=jnp.float32,
                            attention_impl="flash")
    model = TransformerLM(cfg)
    assert 4e5 < model.num_params() < 7e5, model.num_params()  # ~0.5M-param class
    config = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3, "weight_decay": 0.01}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 20}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 10**9,
        "tpu": {"mesh": {"data": 8}},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    batches = _markov_batches()
    curve = []
    for step in range(STEPS):
        loss = engine.train_batch(batches[step % len(batches)])
        if step % SAMPLE_EVERY == 0:
            curve.append(round(float(loss), 4))
    groups.reset()
    return curve


def test_tiny_gpt_convergence_curve(eight_devices):
    assert os.path.exists(CURVE_PATH), (
        f"known-good curve missing at {CURVE_PATH}; generate it with "
        "`python tests/test_convergence.py --regen`")
    want = json.load(open(CURVE_PATH))["curve"]
    got = _train_curve()
    assert len(got) == len(want)
    # real convergence, not a smoke: well below the 5.55 unigram floor
    assert got[-1] < 2.0, f"final loss {got[-1]} did not converge"
    np.testing.assert_allclose(got, want, rtol=0.10,
                               err_msg=f"loss curve diverged from committed known-good\n"
                                       f"got:  {got}\nwant: {want}")


if __name__ == "__main__":
    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(CURVE_PATH), exist_ok=True)
        curve = _train_curve()
        with open(CURVE_PATH, "w") as f:
            json.dump({"curve": curve, "steps": STEPS, "sample_every": SAMPLE_EVERY}, f, indent=1)
        print(f"wrote {CURVE_PATH}: {curve}")
    else:
        print(__doc__)
