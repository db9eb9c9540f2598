"""From a profiler trace to device busy time, kernel time and idle gaps.

:func:`events_from_xplane` reads the ``.xplane.pb`` that ``jax.profiler``
writes into plain records; :func:`reduce` turns those into numbers.  The
two are apart so the reduction can be checked on a small recorded trace
without a chip (``tests/data/trace_sample.json``).

Records are ``{"window": [start_ns, end_ns], "device": {plane: [[name,
start_ns, end_ns], ...]}, "host": [[name, start_ns, end_ns], ...]}``.  The
window is the benchmark's own ``window`` span; device events are the XLA
modules that ran on each chip; host spans are the benchmark's own
annotations around the calls into the session.
"""

from __future__ import annotations

import glob
import os
import re

HOST_SPANS = ("step", "add_request", "finish")
MODULE_LINE = "XLA Modules"
# Module events carry a program fingerprint: "jit_f(1234)".
_FINGERPRINT = re.compile(r"\(\d+\)$")


def events_from_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name != MODULE_LINE:
                    continue
                evs = device.setdefault(plane.name, [])
                for e in line.events:
                    name = _FINGERPRINT.sub("", e.name)
                    evs.append([name, int(e.start_ns), int(e.start_ns + e.duration_ns)])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "window":
                        window = [int(e.start_ns), int(e.start_ns + e.duration_ns)]
                    elif e.name in HOST_SPANS:
                        host.append([e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)])
    if window is None:
        raise ValueError("trace holds no 'window' span")
    return {"window": window, "device": device, "host": host}


def _clip(evs, lo, hi):
    out = []
    for name, s, e in evs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(host, t):
    """The innermost benchmark span around time ``t`` (or 'harness')."""
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "harness"


def reduce(records: dict, kernel_names=(), top: int = 10) -> dict:
    """Busy and idle time inside the window, averaged over the chips."""
    lo, hi = records["window"]
    window_s = (hi - lo) / 1e9
    planes = records["device"]
    if not planes:
        raise ValueError("trace holds no device events")
    busy, kernel, op_time = [], [], {}
    gaps: list[tuple[str, float]] = []
    for evs in planes.values():
        evs = _clip(evs, lo, hi)
        merged = _union([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        kernel.append(
            sum(e - s for n, s, e in evs if any(k in n for k in kernel_names)) / 1e9
        )
        for n, s, e in evs:
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label(records["host"], a), (b - a) / 1e9))
    n = len(planes)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "kernel_s": sum(kernel) / n,
        "device_ops": [[k, v / n] for k, v in ops],
        "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:top]],
    }
