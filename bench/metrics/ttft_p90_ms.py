"""ttft_p90_ms: 90th percentile of time to first token over the requests
due in the window, from the moment each was due (closed loop: when its
client's previous request ended); one still waiting at the close counts
with the time it has waited."""
from harness.window import quantile, ttfts


def reduce(run):
    t = ttfts(run)
    return 1e3 * quantile(t, 0.90) if t else None
