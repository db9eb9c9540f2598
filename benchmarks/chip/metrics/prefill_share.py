"""Prefill's share of a step: seconds in the session's ``serve.prefill``
spans over seconds in its ``serve.step`` spans, in the window."""

from spans import window_delta


def read(run):
    prefill = window_delta(run, "span_s.serve.prefill")
    step = window_delta(run, "span_s.serve.step")
    if prefill is None or not step:
        return None
    return 100.0 * prefill / step
