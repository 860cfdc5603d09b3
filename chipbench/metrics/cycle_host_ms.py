"""Pack + resolve on the host: time inside ``run_cycle`` (the benchmark's
span) that is not inside one of the program's ``rabia.devkv.*`` dispatch
spans, per window dispatched in the traced window, in milliseconds."""


def read(ctx):
    cycles = ctx["spans"].get("chipbench.run_cycle")
    if not cycles or not ctx["windows"]:
        return None
    dispatch = sum(
        sum(v) for k, v in ctx["spans"].items() if k.startswith("rabia.devkv.")
    )
    return (sum(cycles) - dispatch) / ctx["windows"] * 1e3
