"""Forward operations of every token the window processed (prompt tokens
prefilled and tokens generated; not prompt tokens an earlier request or
the system prompt already ran), over window x peak bf16 FLOP/s."""


def read(run):
    if run.trace is None:
        return None
    work = run.window_work()
    ops = work["dense_ops"] + work["attention_ops"]
    if not ops:
        return None
    return 100.0 * ops / (run.trace["window_s"] * run.peaks["bf16_flops_per_s"])
