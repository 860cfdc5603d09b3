"""Client surface: mean length of the program's ``rabia.submit.validate``
span, once a ``submit_block`` call: the block's shards as an array, the
range check and ``np.unique`` over them, in microseconds. A program
without the span gives nothing to read."""


def read(ctx):
    spans = ctx["spans"].get("rabia.submit.validate")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e6
