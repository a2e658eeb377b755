"""Roofline share of the flash-attention kernels of a training step, in
percent: the least time the chip could take for the calls the trace counted
(per call the larger of operations over peak FLOP/s and bytes over peak
bytes/s, from ``lib/opcount``) over the time the trace measured for them. A
call is one microbatch of one layer on one chip."""

from benchmark.lib import opcount
from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx["peaks"]
    if not reduced or peaks is None:
        return None
    cfg, system = ctx["system"].cfg, ctx["system"]
    least = 0.0
    for name, cost in opcount.FLASH_COSTS.items():
        flops, nbytes = cost(system.micro, system.seq, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                             cfg.sliding_window)
        least += reduced["calls_by_op"].get(name, 0.0) * opcount.min_seconds(flops, nbytes, peaks)[0]
    measured = kernel_seconds(reduced, list(opcount.FLASH_COSTS))
    return 100.0 * least / measured if measured > 0 else None
