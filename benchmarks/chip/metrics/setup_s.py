"""Set-up: from process start until the server is built, holds the seed's
weights and has served its warm-up request, before the lead-in begins."""


def read(ctx):
    return ctx["setup_s"]
