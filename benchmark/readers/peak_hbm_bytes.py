"""Peak bytes in use on the fullest chip, as the runtime reports it after the
window (``memory_stats()["peak_bytes_in_use"]``)."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak if peak > 0 else None
