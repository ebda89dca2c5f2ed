"""Tick program, whole step: model operations of the work scheduled in the
traced sub-window (unpadded tokens, counted by the model module's
`step_flops`) over the sub-window's length times the chip's bf16 peak,
in %."""


def read(ctx):
    sub = ctx["sub"]
    if not sub["batches"]:
        return None
    flops = ctx["model"].step_flops(ctx["cfg"], sub["batches"])
    return 100.0 * flops / (sub["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
