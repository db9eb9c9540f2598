"""Mean wait in the session's prefill queue: from a prompt's enqueue in
``add_request`` to the start of its first prefill slice, over the waits
that ended in the window (``prefill_wait_s`` / ``prefill_waits``)."""

from spans import window_delta


def read(run):
    waited = window_delta(run, "prefill_wait_s")
    waits = window_delta(run, "prefill_waits")
    if waited is None or not waits:
        return None
    return 1e3 * waited / waits
