"""Pack + resolve on the host: time in the program's ``rabia.cycle.kinds``
span (the opcode scan of every staged block, ``_block_op_kind``, and the
run-length loop that picks the window's lane, before the pack), summed and
divided by the windows dispatched in the traced window, in milliseconds.
It lies inside ``run_cycle`` and outside the spans ``cycle_unattributed_ms``
subtracts, so it is a part of that metric. A program without the span
gives nothing to read."""


def read(ctx):
    spans = ctx["spans"].get("rabia.cycle.kinds")
    if not spans or not ctx["windows"]:
        return None
    return sum(spans) / ctx["windows"] * 1e3
