"""Median time to first token over every request due inside the window,
timed from when it was due.  A request never served counts as a miss: its
wait runs to the end of the drain."""

from harness import first_token_waits, percentile


def read(run):
    waits = first_token_waits(run)
    return percentile(waits, 50) if waits else None
