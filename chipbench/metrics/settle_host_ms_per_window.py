"""Readback + settle: median length of the program's ``rabia.cycle.settle``
span (adopting the state, version derivation, resolvability, frame groups
and the settling of every block future; no waiting), in milliseconds."""

import statistics


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.settle")
    if not spans:
        return None
    return statistics.median(spans) * 1e3
