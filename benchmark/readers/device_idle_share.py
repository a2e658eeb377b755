"""Share of the traced window, in percent, in which no operation ran on the
device (averaged over the chips)."""


def read(ctx):
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
