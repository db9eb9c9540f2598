"""p90 of time to first token over every request due inside the window
(misses included, as for ``ttft_p50_ms``).  A tail over a few tens of
requests: read beside the median, not held to a bound."""

from harness import first_token_waits, percentile


def read(run):
    waits = first_token_waits(run)
    return percentile(waits, 90) if waits else None
