"""Share of the device's busy time, in percent, spent in the named Pallas
kernels. ``args``: ``{"kernels": [...]}``, names as the kernels declare them."""

from benchmark.lib.xplane import kernel_seconds


def read(ctx):
    reduced = ctx.get("reduced")
    if not reduced or reduced["busy_s"] <= 0:
        return None
    return 100.0 * kernel_seconds(reduced, ctx["args"]["kernels"]) / reduced["busy_s"]
