"""Tick program: device busy time in the traced sub-window over the ticks
executed in it."""


def read(ctx):
    trace, ticks = ctx["trace"], ctx["sub"]["engine"]["ticks"]
    if trace is None or not ticks:
        return None
    return trace["busy_s"] / ticks * 1e3
