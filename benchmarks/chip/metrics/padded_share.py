"""Host tick path (bucket choice): padded tokens over scheduled plus padded
tokens in the ticks of the traced sub-window (`EngineStats`), in %."""


def read(ctx):
    eng = ctx["sub"]["engine"]
    padded = eng["padded_prefill"] + eng["padded_decode"]
    total = padded + eng["scheduled_prefill"] + eng["scheduled_decode"]
    return 100.0 * padded / total if total else None
