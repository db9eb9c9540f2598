"""Share of the prompt tokens admitted in the window that the session's
prefix trie served from retained pages (``prefix_tokens_reused``)."""


def read(run):
    offered = sum(
        len(r.prompt)
        for r in run.requests
        if r.admitted is not None and run.in_window(r.admitted)
    )
    if not offered:
        return None
    reused = (
        run.counters_close["prefix_tokens_reused"]
        - run.counters_open["prefix_tokens_reused"]
    )
    return 100.0 * reused / offered
