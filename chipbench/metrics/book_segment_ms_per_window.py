"""Pack + resolve on the host: median length of the program's
``rabia.cycle.book.segment`` span, a child of ``rabia.cycle.book`` entered
once a window: the window's value segment built and retained
(``_dev_push_segment``, with the byte cap's evictions), in milliseconds.
A program without the span gives nothing to read."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.book.segment")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
