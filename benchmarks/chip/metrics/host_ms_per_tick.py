"""Host tick path: `EngineStats.host_s` over the ticks of the traced
sub-window."""


def read(ctx):
    eng = ctx["sub"]["engine"]
    return eng["host_s"] / eng["ticks"] * 1e3 if eng["ticks"] else None
