"""Process start to window open: weights, compile-cache reads, warm-up,
prefix fill and the lead of the schedule."""


def read(run):
    return run.setup_s
