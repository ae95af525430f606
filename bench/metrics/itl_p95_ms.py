"""itl_p95_ms: 95th percentile of every inter-token gap in the window,
pooled over requests (a gap counts when both of its tokens are in it)."""
from harness.window import gaps, quantile


def reduce(run):
    g = gaps(run)
    return 1e3 * quantile(g, 0.95) if g else None
