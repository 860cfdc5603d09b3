"""Device window: the mean of the phase iterations the lossy network's
consensus loops ran a window, read from the program's
``rabia.consensus.p<P>`` markers (one a settled window, the phases in the
name). A program that decides with full views, where every slot decides
in its first phase, has no markers and gives nothing to read."""

_PREFIX = "rabia.consensus.p"


def read(ctx):
    at = {
        int(name[len(_PREFIX):]): len(durations)
        for name, durations in ctx["spans"].items()
        if name.startswith(_PREFIX) and name[len(_PREFIX):].isdigit()
    }
    windows = sum(at.values())
    if not windows:
        return None
    return sum(p * n for p, n in at.items()) / windows
