"""Readback + settle: median length of the program's
``rabia.cycle.book.handoff`` span, a child of ``rabia.cycle.book`` entered
once a window: the window's readbacks (flags, meta and, where it
prefetches, the value planes) handed to the readback workers, one to three
``submit``s to their pool, in milliseconds. A program without the span
gives nothing to read."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.book.handoff")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
