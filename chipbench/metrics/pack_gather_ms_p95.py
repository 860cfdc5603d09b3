"""Pack + resolve on the host: 95th percentile of the program's
``rabia.cycle.pack.gather`` span (the native pass that copies every block's
bytes into the window's planes) over the traced window's dispatches, in
milliseconds: the part of the pack in which the slow windows live."""

import numpy as np


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.pack.gather")
    if not spans:
        return None
    return float(np.percentile(spans, 95)) * 1e3
