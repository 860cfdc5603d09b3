"""Pack + resolve on the host: 95th percentile of the program's
``rabia.cycle.pack`` span over the traced window's dispatches, in
milliseconds: which part stretches in the windows that make the tail."""

import numpy as np


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.pack")
    if not spans:
        return None
    return float(np.percentile(spans, 95)) * 1e3
