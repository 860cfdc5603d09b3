"""Pipe: how often the window governor moved to another rung inside the
traced window: the program's ``rabia.governor.resize`` spans, one a resize.
0 where no governor runs or it has parked; a governor that hunts between
two rungs shows here and in the commit tail. A program without the
``rabia.window.w<W>`` markers has no such span either and gives nothing to
read."""


def read(ctx):
    spans = ctx["spans"]
    if not any(name.startswith("rabia.window.w") for name in spans):
        return None
    return len(spans.get("rabia.governor.resize", ()))
