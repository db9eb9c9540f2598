"""Traced window divided by the ``session.step()`` calls that ended in it."""


def read(run):
    n = run.window_steps()
    if run.trace is None or not n:
        return None
    return run.trace["window_s"] * 1e3 / n
