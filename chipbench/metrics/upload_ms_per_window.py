"""Upload + dispatch: median length of the program's
``rabia.dispatch.place`` span (every ``device_put`` of one dispatch: op
planes, kinds, base slots, alive mask), in milliseconds."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.dispatch.place")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
