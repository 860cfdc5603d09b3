"""Pack + resolve on the host: median length of the program's
``rabia.cycle.book.slots`` span (a settled lossy-network window's outcome
booked on the host: each op's slot from the null slots the device counted,
the next slots, the decision log and the phase histogram), in
milliseconds. A program without the span gives nothing to read."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.book.slots")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
