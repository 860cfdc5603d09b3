"""Readback + settle: time the window's thread spent downloading value
planes (the program's ``rabia.cycle.settle.download`` spans: a settle
takes one when a read's version has left the host segments), summed and
divided by the windows dispatched in the traced window, in milliseconds:
the one step in which bulk bytes cross from the device, from every chip of
the mesh, to the host."""


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.settle.download")
    if not spans or not ctx["windows"]:
        return None
    return sum(spans) / ctx["windows"] * 1e3
