"""Share of the traced window, in percent, that a chip spent inside a
collective operation (all-gather, reduce-scatter, all-reduce, ...) with no
compute running on it: on the TensorCore's serial line of operations a
collective that is running is one that nothing hid. Averaged over the chips."""


def read(ctx):
    reduced = ctx.get("reduced")
    if not reduced or reduced["n_devices"] < 2:
        return None
    return 100.0 * reduced["collective_exposed_s"] / reduced["window_s"]
