"""Ratio of two program counters' deltas over the window.

Arguments: ``numerator`` and ``denominator`` (sample names of the program's
Prometheus exposition, labels included), ``scale`` (default 1) and
``one_minus`` (report ``1 - ratio``). Nothing to divide by: nothing read."""


def read(ctx, numerator, denominator, scale=1.0, one_minus=False):
    before, after = ctx["counters_before"], ctx["counters_after"]
    if numerator not in after or denominator not in after:
        return None
    num = after[numerator] - before.get(numerator, 0.0)
    den = after[denominator] - before.get(denominator, 0.0)
    if den <= 0:
        return None
    ratio = num / den
    return scale * ((1.0 - ratio) if one_minus else ratio)
