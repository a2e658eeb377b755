"""Programs that XLA compiled, or read back from its persistent cache, inside
the measured window. Expected 0: every shape is warmed during set-up, so a
compile that leaves set-up lands here."""


def read(ctx):
    return ctx["compiles"].count_between(*ctx["window"])
