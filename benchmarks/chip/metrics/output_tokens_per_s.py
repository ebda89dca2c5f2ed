"""Output tokens that reached clients inside the measured window, over the
window's length."""

from serve_loop import tokens_in


def read(ctx):
    start, end = ctx["window"]
    return tokens_in(ctx["records"], start, end) / (end - start)
