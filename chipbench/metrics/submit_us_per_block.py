"""Client surface: mean time of one ``submit_block`` call (the benchmark's
span around it), in microseconds."""


def read(ctx):
    spans = ctx["spans"].get("chipbench.submit")
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e6
