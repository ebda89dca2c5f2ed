"""95th percentile over every gap between consecutive output tokens of
every request, both tokens reaching the client inside the measured
window."""

from serve_loop import itls_s
from stats import percentile


def read(ctx):
    gaps = itls_s(ctx["records"], *ctx["window"])
    return percentile(gaps, 95) * 1e3 if gaps else None
