"""Named host spans on the profiler's clock, with totals kept in memory.

``span(name, **args)`` opens ``jax.profiler.TraceAnnotation(name, **args)``,
so a profiled run shows the span on the host plane, on the same clock as
the device's XLA-module events.  While a :class:`Tracer` is current (see
:meth:`Tracer.active`) the span also adds to that tracer's totals for its
name: how many closed, their seconds, and their self seconds -- the seconds
not covered by spans opened inside them (``time.perf_counter``).  With no
tracer current a span only opens the annotation.  ``count(name, n)`` adds
to a counter of the current tracer in the same way (and does nothing with
none current).

Totals belong to whoever owns the tracer: a serving session makes its own
current around each call into it, so spans opened deep in ``models`` or
``kernels`` count for the session that called them, and two sessions in
one process keep apart.
"""

from __future__ import annotations

import contextlib
import contextvars
import time

import jax

_current: contextvars.ContextVar[Tracer | None] = contextvars.ContextVar(
    "repro_tracer", default=None
)


class Tracer:
    """Per span name: count, seconds and self seconds; and counters.

    ``names`` (spans) and ``counters`` are reported by :meth:`stats` even
    before any such span closes or count is added, so the key set is
    stable and totals of several tracers sum."""

    def __init__(self, names=(), counters=()):
        self.count = dict.fromkeys(names, 0)
        self.seconds = dict.fromkeys(names, 0.0)
        self.self_seconds = dict.fromkeys(names, 0.0)
        self.counters = dict.fromkeys(counters, 0)
        self._open: list[float] = []  # child seconds of each open span

    @contextlib.contextmanager
    def active(self):
        """Make this tracer the one spans add to, until the block ends."""
        token = _current.set(self)
        try:
            yield self
        finally:
            _current.reset(token)

    def _close(self, name: str, dt: float) -> None:
        child = self._open.pop()
        if self._open:
            self._open[-1] += dt
        self.count[name] = self.count.get(name, 0) + 1
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        self.self_seconds[name] = self.self_seconds.get(name, 0.0) + dt - child

    def stats(self) -> dict:
        """Flat keys that sum: ``span_n.<name>``, ``span_s.<name>`` and
        ``span_self_s.<name>`` per span, and each counter by its name."""
        out = {}
        for name in self.count:
            out[f"span_n.{name}"] = self.count[name]
            out[f"span_s.{name}"] = self.seconds[name]
            out[f"span_self_s.{name}"] = self.self_seconds[name]
        out.update(self.counters)
        return out


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the current tracer's counter ``name``."""
    tracer = _current.get()
    if tracer is not None:
        tracer.counters[name] = tracer.counters.get(name, 0) + n


class span:
    """``with span("serve.sync", site="greedy"): ...`` -- see the module."""

    __slots__ = ("_name", "_annotation", "_tracer", "_t0")

    def __init__(self, name: str, **args):
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **args)
        self._tracer = _current.get()

    def __enter__(self):
        self._annotation.__enter__()
        if self._tracer is not None:
            self._tracer._open.append(0.0)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._tracer is not None:
            self._tracer._close(self._name, time.perf_counter() - self._t0)
        return self._annotation.__exit__(*exc)
