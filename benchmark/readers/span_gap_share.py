"""Share of the traced window, in percent, in which the first chip was idle
while the program's driver thread was inside one of the named spans (cut to
the innermost, so an enclosing span counts only its self time). ``args``:
``{"spans": [...]}``, names as ``Tracer.span`` takes them; the name
``host:outside_the_program_s_spans`` stands for idle time under no span."""

from benchmark.lib import program_spans


def read(ctx):
    trace = program_spans.for_run(ctx)
    if trace is None:
        return None
    return program_spans.gap_share(trace, ctx["args"]["spans"])
