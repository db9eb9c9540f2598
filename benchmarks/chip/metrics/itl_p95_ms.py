"""p95 of every gap between consecutive output tokens of a request, over
the gaps whose later token lands inside the window."""

from harness import percentile


def read(run):
    gaps = [
        (b - a) * 1e3
        for r in run.requests
        for a, b in zip(r.times, r.times[1:])
        if run.in_window(b)
    ]
    return percentile(gaps, 95) if gaps else None
