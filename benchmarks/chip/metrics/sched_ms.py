"""Host scheduling per step: seconds the session spent in its
``serve.schedule`` spans in the window (page reserves, block tables, decode
schedules, prefill slice plans), per ``serve.step`` span."""

from spans import per_step_ms


def read(run):
    return per_step_ms(run, ["span_s.serve.schedule"])
