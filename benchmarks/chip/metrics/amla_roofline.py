"""Share of its roofline that the paged MLA attention reaches in the window.

The least time is the larger of the attention's operations over peak
FLOP/s and its bytes over peak bytes/s, both counted from the benchmark's
own record of the window's tokens and the config's widths (``flops.py``).
The time taken is the device time of the cell's attention kernels in the
trace (the cell's ``kernels``)."""


def read(run):
    if run.trace is None or not run.trace["kernel_s"]:
        return None
    work = run.window_work()
    least = max(
        work["attention_ops"] / run.peaks["bf16_flops_per_s"],
        work["attention_bytes"] / run.peaks["hbm_bytes_per_s"],
    )
    if not least:
        return None
    return 100.0 * least / run.trace["kernel_s"]
