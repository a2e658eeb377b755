"""MoE dispatch crossover: one-hot [S,E,C] einsum vs grouped ragged matmul.

VERDICT r4 missing #5 asked for a measured crossover table at E=8 and E=64:
the einsum dispatch materializes capacity-padded [E, C, M] buffers and pays
S*E*C dispatch/combine FLOPs, while the grouped path
(``ops/pallas/grouped_matmul.py``) scales with the routed tokens. One JSON
line per (E, impl) with tokens/s and the measured speedup per E.

Run on a TPU host: ``python tools/moe_crossover.py``. CPU fallback runs tiny
shapes (interpret-mode kernels) so the harness itself stays tested in CI.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_impl(impl, S, M, F, E, top_k, dtype, steps, on_tpu):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe.sharded_moe import MOELayer, TopKGate

    gate = TopKGate(M, E, k=top_k)
    layer = MOELayer(gate, M, F, num_local_experts=E, moe_impl=impl)
    params = layer.init(jax.random.PRNGKey(0))
    if dtype != jnp.float32:
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(S, M)), dtype)

    fwd = jax.jit(lambda p, x: layer(p, x, train=False)[0])
    out = fwd(params, x)
    float(np.asarray(out).reshape(-1)[0])  # compile + real barrier
    t0 = time.time()
    for _ in range(steps):
        out = fwd(params, x)
    float(np.asarray(out).reshape(-1)[0])
    dt = (time.time() - t0) / steps
    return S / dt


def main():
    import jax
    import jax.numpy as jnp

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if on_tpu:
        S, M, F, top_k, steps, dtype = 8192, 1024, 4096, 2, 10, jnp.bfloat16
        experts = (8, 64)
    else:
        S, M, F, top_k, steps, dtype = 256, 64, 128, 2, 2, jnp.float32
        experts = (4, 8)

    for E in experts:
        row = {"metric": "moe_dispatch_crossover", "E": E, "S": S, "M": M, "F": F,
               "top_k": top_k, "on_tpu": on_tpu}
        for impl in ("einsum", "grouped"):
            row[f"{impl}_tokens_per_s"] = round(_bench_impl(
                impl, S, M, F, E, top_k, dtype, steps, on_tpu), 1)
        row["grouped_speedup"] = round(row["grouped_tokens_per_s"] /
                                       row["einsum_tokens_per_s"], 3)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
