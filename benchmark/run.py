#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A single process that holds the cell's chips: it fails if it finds no TPU,
builds the system from the seed, warms only the cell's own shapes, measures
for ``--seconds``, and prints one JSON object as the last line of standard
output. ``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
adds a short window under the profiler after the measured one and reports the
cell's per-layer metrics and a breakdown. Which cells, configurations,
traffic mixes and metrics exist is data: ``BENCHMARK.json`` and the files
under ``benchmark/`` (see ``benchmark/lib/loader.py``).

``--rehearsal`` (needs ``JAX_PLATFORMS=cpu``) runs a tiny cell of
``benchmark/rehearsal/`` on the CPU to debug the harness itself: its line
names the CPU as the device and carries counts only, never a metric.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("benchmark: --rehearsal runs only with JAX_PLATFORMS=cpu in the environment", file=sys.stderr)
        return 2

    from benchmark.lib import common, loader

    cell = loader.resolve_cell(args.workload, ROOT, rehearsal=args.rehearsal)

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from deepspeed_tpu.utils.logging import logger

    for handler in logger.handlers:  # standard output carries the result line, nothing else
        handler.setStream(sys.stderr)
    enable_compile_cache()
    import jax

    # keep every program in the persistent cache, the sub-second ones too: a
    # serving cell has hundreds of tiny ones, and each run is a new process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    driver = loader.load_module("drivers", cell["traffic_file"]["driver"], ROOT)
    result = driver.run(cell, args, T_PROCESS_START)

    device, extra = dict(result["device"]), {}
    if args.rehearsal:
        metrics = {}
        extra = {"rehearsal": True, "counts": result["counts"], "check": result["check"]}
    elif args.trace:
        metrics = loader.read_layer_metrics(cell, result["ctx"])
        reduced = result["ctx"]["reduced"]
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        extra = {"breakdown": {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]},
                 "check": result["check"]}
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        metrics = {name: {"value": float(result["end_to_end"][name]), "unit": unit}
                   for name, unit in units.items()}
        extra = {"counts": result["counts"], "check": result["check"]}
        if "tails" in result:
            extra["tails"] = result["tails"]
    sys.stdout.flush()
    print(common.result_line(result["correct"], result["attempted"], result["failed"], metrics, device,
                             **extra), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
