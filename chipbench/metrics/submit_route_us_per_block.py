"""Client surface: mean length of the program's ``rabia.submit.route``
span, once a ``submit_block`` call, everything after the validation: the
block's future, the full-width and read-lane tests, the shard -> index
permutation and the append to its lane (or one queue entry a shard), in
microseconds. A program without the span gives nothing to read."""


def read(ctx):
    spans = ctx["spans"].get("rabia.submit.route")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e6
