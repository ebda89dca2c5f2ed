"""Load generator + frontend: 95th percentile of how late each request due
in the measured window was submitted (submit time - due time)."""

from stats import percentile


def read(ctx):
    lags = [r.submit_s - r.due_s for r in ctx["window_records"]
            if r.submit_s is not None]
    return percentile(lags, 95) * 1e3 if lags else None
