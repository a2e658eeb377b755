"""Percent: a sum over program spans over another such sum, both taken in the
traced window. ``args``: ``{"numerator": [term, ...], "denominator": [term,
...]}``; a term ``{"span": name, "product": [arg, ...]}`` adds, for every span
of that name, the product of its named arguments (``rows`` x ``steps`` is a
decode call's row-steps)."""

from benchmark.lib import program_spans


def _total(trace, terms):
    total, seen = 0.0, 0
    for term in terms:
        for span in program_spans.spans_named(trace, term["span"]):
            factors = [span.args.get(arg) for arg in term["product"]]
            if all(isinstance(f, (int, float)) for f in factors):
                value = 1.0
                for f in factors:
                    value *= f
                total += value
                seen += 1
    return total, seen


def read(ctx):
    trace = program_spans.for_run(ctx)
    if trace is None:
        return None
    numerator, _ = _total(trace, ctx["args"]["numerator"])
    denominator, seen = _total(trace, ctx["args"]["denominator"])
    return 100.0 * numerator / denominator if seen and denominator > 0 else None
