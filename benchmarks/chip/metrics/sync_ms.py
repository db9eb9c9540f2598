"""Host blocked on the device per step: seconds in the session's
``serve.sync`` spans in the window (every device-to-host read: the kernels'
``kv_len`` checks, the greedy tokens, each prompt's first token), per
``serve.step`` span."""

from spans import per_step_ms


def read(run):
    return per_step_ms(run, ["span_s.serve.sync"])
