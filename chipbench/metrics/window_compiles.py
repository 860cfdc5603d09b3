"""Pipe: dispatches in the measured window after which the table's
``compiled_on_last_call`` was set, i.e. windows whose signature was new and
paid a trace, a lower and a compile or cache load. Must read 0."""


def read(ctx):
    return ctx["counters"]["window_compiles"]
