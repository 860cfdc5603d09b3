"""Upload + dispatch: mean length of the program's ``rabia.devkv.*`` spans
(``decide_apply``, ``lookup_window``, ``mixed_apply``), in milliseconds."""


def read(ctx):
    spans = [
        d for k, v in ctx["spans"].items() if k.startswith("rabia.devkv.") for d in v
    ]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e3
