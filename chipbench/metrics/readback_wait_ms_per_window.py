"""Readback + settle: time the window's thread spent blocked on a window's
flags and meta readbacks (the program's ``rabia.cycle.wait`` spans, one or
two per window), summed and divided by the windows dispatched in the traced
window, in milliseconds. Near 0 while the host sets the pace."""


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.wait")
    if not spans or not ctx["windows"]:
        return None
    return sum(spans) / ctx["windows"] * 1e3
