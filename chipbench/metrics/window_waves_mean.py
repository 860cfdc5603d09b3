"""Pipe: the mean window size (waves a dispatch: the rung of the engine's
ladder) over the windows dispatched in the traced window, read from the
program's ``rabia.window.w<W>`` markers, one at each window's dispatch, which
carry the rung in their name. 64 where no governor runs and the window is
64; under a latency target it says where the governor sat. A program
without the markers gives nothing to read."""

_PREFIX = "rabia.window.w"


def rungs(spans: dict) -> dict:
    """``{W: windows dispatched at W}`` from the markers' names."""
    return {
        int(name[len(_PREFIX):]): len(durations)
        for name, durations in spans.items()
        if name.startswith(_PREFIX) and name[len(_PREFIX):].isdigit()
    }


def read(ctx):
    at = rungs(ctx["spans"])
    windows = sum(at.values())
    if not windows:
        return None
    return sum(w * n for w, n in at.items()) / windows
