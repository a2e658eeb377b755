"""Driver ``train_steps``: whole optimizer steps of a fixed token batch,
the loss fetched after every step.

The rate is the tokens of the ``k`` whole steps that fit into ``--seconds``
over the time between the fence after the last warm-up step and the fence
after step ``k``; each fence is the host's fetch of that step's loss.
"""

import math
import time


def run(cell: dict, args, t_process_start: float) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.runtime.data_pipeline.prefetch import DeviceBatch

    from benchmark.lib import common, loader, rates
    from benchmark.lib.peaks import peaks_for

    phases, devices, compiles, system = common.begin_run(cell, args, t_process_start)
    cf, tf = cell["config_file"], cell["traffic_file"]
    engine, n = system.engine, system.n
    losses = []

    def step(batch):
        with common.span("train_batch"):
            loss = engine.train_batch(DeviceBatch({"input_ids": batch}))
        with common.span("loss_fetch"):
            value = float(np.asarray(loss))  # the fence: all of the step's work lies before it
        losses.append(value)
        return time.perf_counter()

    # correct: the first step's loss and gradient norm against the plain
    # reference on the same weights. The check batch repeats a few distinct
    # sequences so that the reference computes only those; a mean over rows
    # that each appear equally often is the mean over the distinct ones.
    k = int(tf["check_sequences_per_chip"]) * n
    if system.sequences % k:
        raise ValueError(f"{k} distinct check sequences do not divide the batch of {system.sequences}")
    distinct = jax.jit(lambda key: jax.random.randint(key, (k, system.seq), 0, system.cfg.vocab_size, jnp.int32),
                       out_shardings=system.rows_sharding)(jax.random.fold_in(system.key, 10**6))
    reference = loader.load_reference(cell)
    hyper = reference.hyper_from_published(cf)
    with common.span("reference"):
        ref_loss, ref_norm = reference.loss_and_grad_norm(hyper, engine.state["params"], distinct)
    phases.mark("reference")
    check_batch = jax.jit(lambda d: jnp.tile(d, (system.sequences // k, 1)).reshape(system.gas, -1, system.seq),
                          out_shardings=system.batch_sharding)(distinct)
    step(check_batch)
    sys_loss, sys_norm = losses[0], float(np.asarray(engine.get_global_grad_norm()))
    tol = cf["check"]
    loss_err = abs(sys_loss - ref_loss)
    norm_err = abs(sys_norm - ref_norm) / max(abs(ref_norm), 1e-30)
    check_ok = bool(loss_err <= tol["loss_abs_tol"] and norm_err <= tol["grad_norm_rel_tol"])

    phases.mark("first_step")
    pool = [system.make_batch(i) for i in range(int(tf["batch_pool"]))]
    fence = time.perf_counter()
    for i in range(int(tf["warmup_steps"])):
        last, fence = fence, step(pool[i % len(pool)])
    t_begin = fence
    setup_s = t_begin - t_process_start
    phases.mark("warm_steps")

    fences, estimate, i = [t_begin], fence - last, 0
    while fences[-1] + estimate <= t_begin + args.seconds:
        fences.append(step(pool[i % len(pool)]))
        estimate = fences[-1] - fences[-2]
        i += 1
    n_window = len(fences) - 1
    work = [0] + [system.tokens_per_step] * n_window
    rate, n_steps, seconds = rates.whole_step_rate(fences, work, t_begin, t_begin + args.seconds)
    window = (t_begin, fences[-1])

    reduced = None
    if args.trace:
        tracer = common.Tracer(cell["root"], cell["name"])
        tracer.start()
        for j in range(int(tf["trace_steps"])):
            step(pool[j % len(pool)])
        reduced = tracer.stop_and_reduce()

    device = common.device_record(devices)
    failed = sum(1 for v in losses if not math.isfinite(v))
    end_to_end = {"train_tokens_per_s_per_chip": rate / n, "setup_s": setup_s}
    ctx = {"kind": "train", "cell": cell, "fences": fences, "window": window, "compiles": compiles,
           "reduced": reduced, "device": device, "chips": n, "system": system, "end_to_end": end_to_end,
           "peaks": None if args.rehearsal else peaks_for(device["kind"])}
    engine.destroy()
    return {"correct": check_ok and failed == 0, "attempted": len(losses), "failed": failed,
            "end_to_end": end_to_end, "ctx": ctx, "device": device,
            "counts": {"steps_in_window": n_steps, "tokens_per_step": system.tokens_per_step,
                       "compiles_in_window": compiles.count_between(*window)},
            "check": {"loss": sys_loss, "reference_loss": ref_loss, "grad_norm": sys_norm,
                      "reference_grad_norm": ref_norm, "loss_abs_err": loss_err, "grad_norm_rel_err": norm_err,
                      "window_seconds": seconds, "setup_phases": phases.marks}}
