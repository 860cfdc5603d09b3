"""Device window: the union of device-op intervals in the traced window,
per window dispatched in it, in milliseconds."""


def read(ctx):
    if not ctx["windows"] or ctx["trace"]["busy_s"] <= 0:
        return None
    return ctx["trace"]["busy_s"] / ctx["windows"] * 1e3
