"""Readback + settle: median length of the program's
``rabia.cycle.settle.blocks`` span, a child of ``rabia.cycle.settle``
entered once a window around the loop over its blocks: each block's frame
groups built and its future settled, in milliseconds. The settle less this
and ``rabia.cycle.settle.download`` is the meta unpacking, the
resolvability test and the resolver. A program without the span gives
nothing to read."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.settle.blocks")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
