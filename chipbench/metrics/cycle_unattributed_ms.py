"""Pack + resolve on the host: time inside ``run_cycle`` (the benchmark's
span) that none of the program's top-level spans names, per window
dispatched in the traced window, in milliseconds: ``run_cycle`` minus
``rabia.cycle.pack``, ``.book``, ``.wait``, ``.settle`` and the
``rabia.devkv.*`` dispatch spans. The spans nested inside those
(``rabia.cycle.pack.*``, ``rabia.dispatch.*``) are not subtracted again."""

_TOP = ("rabia.cycle.pack", "rabia.cycle.book", "rabia.cycle.wait", "rabia.cycle.settle")


def read(ctx):
    spans = ctx["spans"]
    cycles = spans.get("chipbench.run_cycle")
    if not cycles or not ctx["windows"] or not any(k in spans for k in _TOP):
        return None
    named = sum(
        sum(v) for k, v in spans.items() if k in _TOP or k.startswith("rabia.devkv.")
    )
    return (sum(cycles) - named) / ctx["windows"] * 1e3
