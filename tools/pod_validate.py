"""Pod-scale compile-only validation of the BASELINE.md north-star configs.

The flagship workloads (Llama-2-7B / 70B ZeRO-3 on a v5p-128 pod,
BASELINE.md:21-22) cannot execute in this container — but their full train
steps CAN be traced, GSPMD-partitioned, and memory-checked on a virtual
128-device mesh with nothing materialized (``tpu.abstract_init`` +
``DeepSpeedEngine.aot_lower_train_step``). For each config this prints one
JSON line with:

  - ``lowered``: the full fused train step traced + StableHLO built at the
    target mesh shape (proves the sharding/program construction)
  - ``compiled`` + ``xla_per_device_hbm_gb``: XLA CPU-backend compile of the
    partitioned program and its own per-device memory analysis (argument +
    output + temp + generated code); skipped gracefully if the 7B/70B-scale
    compile exceeds the budget on this host
  - analytic per-chip accounting (independent of XLA): param/optimizer/
    gradient-accumulator shard bytes from the actual state shardings, an
    activation-checkpoint estimate, and the per-step collective volume
    (ZeRO-3 allgather fwd+bwd + reduce-scatter, reference
    ``blogs/zeropp/README.md`` 3M-per-step accounting)
  - ``fits_95gb``: the v5p HBM bound from the analytic estimate

Run: ``python tools/pod_validate.py [--compile] [--devices 128]``
(compile-only is the default ladder; ``--compile`` also runs XLA compiles).
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V5P_HBM_GB = 95.0  # v5p: 95 GB HBM per chip
V5P_PEAK_BF16 = 459e12


def _cpu_mesh_env(n):
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


CONFIGS = [
    # (name, model size, mesh axes, zero stage, micro, gas, seq, extra)
    ("llama2_7b_zero3_dp128", "7b", {"data": 128}, 3, 1, 8, 4096, {}),
    ("llama2_7b_pp8_tp4_dp4", "7b", {"pipe": 8, "model": 4, "data": 4}, 1, 1, 8, 4096, {}),
    ("llama2_7b_ulysses_sp8", "7b", {"data": 16, "seq": 8}, 3, 1, 4, 32768,
     {"sequence_parallel": True, "loss_chunk": 2048}),
    ("llama2_70b_zero3_tp8", "70b", {"data": 16, "model": 8}, 3, 1, 8, 4096, {}),
]


def validate_one(name, size, mesh_axes, stage, micro, gas, seq, extra, do_compile):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import llama2_config
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.mesh import DATA_AXIS, DATA_REPL_AXIS, SEQ_AXIS

    groups.reset()
    n_devices = int(np.prod(list(mesh_axes.values())))
    assert len(jax.devices()) >= n_devices, (len(jax.devices()), n_devices)

    cfg = llama2_config(size, max_seq_len=seq, attention_impl="flash", remat=True,
                        remat_policy="save_only_these_names(attn_out)",
                        dtype=jnp.bfloat16, **extra)
    model = TransformerLM(cfg)
    dp = mesh_axes.get("data", 1)
    config = {
        "train_batch_size": micro * gas * dp,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": stage},
        "bf16": {"enabled": True},
        "steps_per_print": 10**9,
        "tpu": {"mesh": mesh_axes, "abstract_init": True},
    }
    if mesh_axes.get("pipe", 1) > 1:
        config["pipeline"] = {"schedule": "1f1b"}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)

    # ---- analytic per-chip accounting from the ACTUAL state shardings ----
    def shard_frac(leaf):
        spec = getattr(leaf.sharding, "spec", None) or ()
        denom = 1
        for entry in spec:
            for ax in (entry if isinstance(entry, (tuple, list)) else (entry, )):
                if ax is not None:
                    denom *= engine.mesh.shape[ax]
        return denom

    state_bytes = 0
    for leaf in jax.tree_util.tree_leaves(engine.state):
        state_bytes += int(np.prod(leaf.shape)) * leaf.dtype.itemsize // shard_frac(leaf)
    # fp32 gradient accumulator over gas microbatches shards like the params
    grad_acc_bytes = sum(
        int(np.prod(l.shape)) * 4 // shard_frac(l)
        for l in jax.tree_util.tree_leaves(engine.state["params"]))
    # remat(save attn_out): per layer one [B_local, S_local, H] bf16 boundary
    # + attn ctx; times 2 for the layer being recomputed in backward
    sp = mesh_axes.get("seq", 1)
    s_local = seq // sp
    act_bytes = cfg.num_layers * 2 * micro * s_local * cfg.hidden_size * 2 * 2
    logits_bytes = (micro * min(seq, extra.get("loss_chunk", seq)) * cfg.vocab_size * 4
                    // max(1, mesh_axes.get("model", 1)))
    total_gb = (state_bytes + grad_acc_bytes + act_bytes + logits_bytes) / 1e9

    n_params = model.num_params()
    # ZeRO-3 per-step collective volume per chip (reference zeropp blog "3M"):
    # allgather bf16 params fwd + bwd, reduce-scatter fp32->bf16 grads
    if stage == 3:
        coll_gb = 3 * n_params * 2 / 1e9
    elif stage in (1, 2):
        coll_gb = 2 * n_params * 2 / 1e9  # grad reduce + (stage>=1) param refresh
    else:
        coll_gb = n_params * 2 / 1e9

    out = {
        "config": name, "mesh": mesh_axes, "zero": stage, "seq": seq,
        "params_b": round(n_params / 1e9, 2),
        "n_devices": n_devices,
        "analytic_per_chip_gb": round(total_gb, 2),
        "collective_gb_per_step": round(coll_gb, 1),
        "fits_95gb": bool(total_gb < V5P_HBM_GB),
        "lowered": False, "compiled": None, "xla_per_device_hbm_gb": None,
    }

    lowered = engine.aot_lower_train_step(seq)
    out["lowered"] = True
    if do_compile:
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        if ma is not None and hasattr(ma, "argument_size_in_bytes"):
            per_dev = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                       + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
            # CPU-backend analysis reports the per-device partitioned program
            out["xla_per_device_hbm_gb"] = round(per_dev / 1e9, 2)
        out["compiled"] = True
    return out


def main():
    if "--child" in sys.argv:
        i = sys.argv.index("--child")
        name = sys.argv[i + 1]
        do_compile = "--compile" in sys.argv
        spec = next(c for c in CONFIGS if c[0] == name)
        print(json.dumps(validate_one(*spec, do_compile)), flush=True)
        return
    n = int(sys.argv[sys.argv.index("--devices") + 1]) if "--devices" in sys.argv else 128
    do_compile = "--compile" in sys.argv
    results = []
    for spec in CONFIGS:
        cmd = [sys.executable, os.path.abspath(__file__), "--child", spec[0]]
        if do_compile:
            cmd.append("--compile")
        proc = subprocess.run(cmd, env=_cpu_mesh_env(n), cwd=REPO, capture_output=True,
                              text=True, timeout=3600)
        line = next((ln for ln in reversed(proc.stdout.splitlines())
                     if ln.startswith("{")), None)
        if proc.returncode != 0 or line is None:
            results.append({"config": spec[0], "error": proc.stderr[-1500:]})
        else:
            results.append(json.loads(line))
        print(json.dumps(results[-1]), flush=True)
    ok = sum(1 for r in results if r.get("lowered") and r.get("fits_95gb"))
    print(f"POD_VALIDATE SUMMARY: {ok}/{len(CONFIGS)} configs lowered + fit 95GB "
          f"on their target mesh", flush=True)
    if ok < len(CONFIGS):
        sys.exit(1)


if __name__ == "__main__":
    main()
