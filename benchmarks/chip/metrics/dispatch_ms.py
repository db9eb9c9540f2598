"""The Python layer walk per step: self seconds of the session's
``serve.decode`` and ``serve.prefill`` spans in the window (their time
less the schedule and sync spans inside them), per ``serve.step`` span."""

from spans import per_step_ms


def read(run):
    return per_step_ms(run, ["span_self_s.serve.decode", "span_self_s.serve.prefill"])
