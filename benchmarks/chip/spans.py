"""The program's own spans: their totals over the window, and the device's
idle time split over them.

The serving session keeps wall-clock totals of its ``serve.*`` spans and
its prefill-queue counters in ``work_stats()`` (``span_n.<name>``,
``span_s.<name>``, ``span_self_s.<name>``, ``prefill_wait_s``,
``prefill_waits``), which the harness snapshots when the window opens and
closes.  :func:`window_delta` reads one over the window; it gives None for
a program that has no such key, so the readers report nothing there.

In a traced run the same spans are ``TraceAnnotation`` events on the
profiler's host plane.  :func:`program_spans` reads them from the trace in
the harness's record format (``trace.py``), and :func:`idle_by_span` splits
the window's device-idle time over the innermost host span open at each
instant, the benchmark's own spans among them.
"""

from __future__ import annotations

import glob
import os

PREFIX = "serve."
OUTSIDE = "harness"  # time in no host span


def window_delta(run, key: str):
    """How much a ``work_stats()`` key grew over the window (None if the
    program does not report it)."""
    a, b = run.counters_open.get(key), run.counters_close.get(key)
    if a is None or b is None:
        return None
    return b - a


def per_step_ms(run, keys) -> float | None:
    """Milliseconds of the summed keys per ``serve.step`` span."""
    steps = window_delta(run, "span_n.serve.step")
    parts = [window_delta(run, k) for k in keys]
    if not steps or any(p is None for p in parts):
        return None
    return 1e3 * sum(parts) / steps


def program_spans(trace_dir: str) -> list:
    """``[name, start_ns, end_ns]`` of every ``serve.*`` host event of the
    trace; an annotation's args, where the profiler folds them into the
    name (``name#k=v#``), are dropped."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    out.append([e.name.split("#", 1)[0], s, s + int(e.duration_ns)])
    return out


def innermost(host, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """``[lo, hi)`` cut into pieces, each with the innermost host span open
    over it (the one opened last), ``OUTSIDE`` where none is: one sweep
    over the spans sorted by start."""
    pieces: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []  # (end, name), innermost last
    t = lo

    def cut(upto: int) -> None:
        nonlocal t
        upto = min(upto, hi)
        if upto > t:
            pieces.append((t, upto, stack[-1][1] if stack else OUTSIDE))
            t = upto

    for s, neg_e, name in sorted((s, -e, n) for n, s, e in host):
        while stack and stack[-1][0] <= s:
            cut(stack[-1][0])
            stack.pop()
        cut(s)
        stack.append((-neg_e, name))
    while stack:
        cut(stack[-1][0])
        stack.pop()
    cut(hi)
    return pieces


def idle_by_span(records: dict) -> dict[str, float]:
    """Seconds of device-idle time in the window under each innermost host
    span, averaged over the chips; the values sum to the window's idle
    time.  A gap that straddles several spans is split between them."""
    lo, hi = records["window"]
    pieces = innermost(records["host"], lo, hi)
    planes = records["device"]
    out: dict[str, float] = {}
    for evs in planes.values():
        busy = sorted((max(s, lo), min(e, hi)) for _, s, e in evs if min(e, hi) > max(s, lo))
        idle, t = [], lo
        for s, e in busy:
            if s > t:
                idle.append((t, s))
            t = max(t, e)
        if hi > t:
            idle.append((t, hi))
        i = 0
        for a, b, name in pieces:
            while i < len(idle) and idle[i][1] <= a:
                i += 1
            j = i
            while j < len(idle) and idle[j][0] < b:
                cover = min(b, idle[j][1]) - max(a, idle[j][0])
                out[name] = out.get(name, 0.0) + cover / 1e9
                j += 1
    return {k: v / len(planes) for k, v in sorted(out.items(), key=lambda kv: -kv[1])}
