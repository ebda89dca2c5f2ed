"""Median time to first token over every request due in the measured
window, timed from when it was due; unanswered requests count the time
they waited."""

from serve_loop import ttfts_s
from stats import percentile


def read(ctx):
    ttft = ttfts_s(ctx["window_records"], ctx["window"][1])
    return percentile(ttft, 50) * 1e3 if ttft else None
