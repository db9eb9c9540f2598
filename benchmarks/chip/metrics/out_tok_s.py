"""Output tokens produced inside the window, per second of window."""


def read(run):
    return run.window_tokens() / run.seconds
