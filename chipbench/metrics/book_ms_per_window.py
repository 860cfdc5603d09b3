"""Pack + resolve on the host: median length of the program's
``rabia.cycle.book`` span (from the end of a dispatch to the pipe: derived
versions, segment retention and eviction, the commit log, handing the
fetches to the pool), in milliseconds."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.book")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
