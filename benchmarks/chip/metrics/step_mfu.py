"""Tick program, whole step: model operations of the work scheduled in the
traced sub-window (`counts.step_flops`, unpadded tokens) over the
sub-window's length times the chip's bf16 peak, in %."""

from counts import step_flops


def read(ctx):
    sub = ctx["sub"]
    if not sub["batches"]:
        return None
    flops = step_flops(ctx["cfg"], sub["batches"])
    return 100.0 * flops / (sub["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
