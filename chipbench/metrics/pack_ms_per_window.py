"""Pack + resolve on the host: median length of the program's
``rabia.cycle.pack`` span (one per window: parse, plane allocation and
gather), in milliseconds."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.pack")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
