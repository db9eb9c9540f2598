"""Page bytes the session's DMAs moved in the window (its exact
``page_dma_bytes`` counter) per output token of the window."""


def read(run):
    tokens = run.window_tokens()
    if not tokens:
        return None
    moved = run.counters_close["page_dma_bytes"] - run.counters_open["page_dma_bytes"]
    return moved / tokens
