"""Mean of one argument over the traced window's program spans of one name;
a list-valued argument (one wait per request) counts each item. ``args``:
``{"span": "serving/decode", "arg": "steps"}``."""

from benchmark.lib import program_spans


def read(ctx):
    trace = program_spans.for_run(ctx)
    if trace is None:
        return None
    values = [v for s in program_spans.spans_named(trace, ctx["args"]["span"])
              for v in program_spans.numbers(s.args.get(ctx["args"]["arg"]))]
    return sum(values) / len(values) if values else None
