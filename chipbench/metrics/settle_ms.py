"""Readback + settle: mean of the engine's
``commit_stage_seconds{stage="window_settle"}`` samples (dispatch to settled
window) taken in the window, in milliseconds. The histogram keeps a sum and
a count, no samples, so this is a mean and not a median."""


def read(ctx):
    n = ctx["counters"]["settle_count"]
    if not n:
        return None
    return ctx["counters"]["settle_sum_s"] / n * 1e3
