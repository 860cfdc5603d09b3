"""Pack + resolve on the host: median length of the program's
``rabia.cycle.book.versions`` span, a child of ``rabia.cycle.book`` entered
once a window: the window's derived versions (a mixed window: the GET
count, the SET mask, its running sum down the waves, the versions and the
mirror's advance, ``[W, S]`` arrays made every window; a SET window: the
versions and the segment's range), in milliseconds. A program without the
span gives nothing to read."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.book.versions")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
