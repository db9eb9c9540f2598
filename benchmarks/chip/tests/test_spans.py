"""CPU checks of the readers of the program's spans (``spans.py`` and the
per-layer metrics that use it): window deltas of ``work_stats()``, the
split of idle time over host spans, and the spans read from a trace."""

from __future__ import annotations

import json
import pathlib

import pytest

import harness
import spans
import trace as trace_mod

HERE = pathlib.Path(__file__).resolve().parent


def record(open_, close):
    return harness.RunRecord(
        conf={}, seconds=50.0, setup_s=0.0, t_open=0.0, t_close=50.0,
        requests=[], steps=[], counters_open=open_, counters_close=close,
        peaks={},
    )


def stats(steps, step_s, sched_s, sync_s, decode_self, prefill_s, prefill_self,
          waited, waits):
    return {
        "span_n.serve.step": steps, "span_s.serve.step": step_s,
        "span_s.serve.schedule": sched_s, "span_s.serve.sync": sync_s,
        "span_self_s.serve.decode": decode_self,
        "span_s.serve.prefill": prefill_s,
        "span_self_s.serve.prefill": prefill_self,
        "prefill_wait_s": waited, "prefill_waits": waits,
        "page_dma_bytes": 0,
    }


READERS = ["sched_ms", "sync_ms", "dispatch_ms", "prefill_share", "prefill_wait_ms"]


def test_readers_take_the_window_delta():
    run = record(
        stats(100, 10.0, 1.0, 2.0, 3.0, 4.0, 1.0, 5.0, 10),
        stats(300, 40.0, 1.5, 4.0, 9.0, 16.0, 3.0, 9.0, 18),
    )
    got = {m: harness.load_module("metrics", m).read(run) for m in READERS}
    assert got["sched_ms"] == pytest.approx(1e3 * 0.5 / 200)
    assert got["sync_ms"] == pytest.approx(1e3 * 2.0 / 200)
    assert got["dispatch_ms"] == pytest.approx(1e3 * (6.0 + 2.0) / 200)
    assert got["prefill_share"] == pytest.approx(100 * 12.0 / 30.0)
    assert got["prefill_wait_ms"] == pytest.approx(1e3 * 4.0 / 8)


@pytest.mark.parametrize("metric", READERS)
def test_readers_report_nothing_for_a_program_without_spans(metric):
    """A program older than its spans reports none of the keys (the parent
    commit on a check with this benchmark); an idle window reports no step
    and no wait."""
    mod = harness.load_module("metrics", metric)
    assert mod.read(record({"page_dma_bytes": 0}, {"page_dma_bytes": 5})) is None
    same = stats(100, 10.0, 1.0, 2.0, 3.0, 4.0, 1.0, 5.0, 10)
    assert mod.read(record(same, dict(same))) is None


def test_idle_is_split_over_the_spans_it_straddles():
    rec = {
        "window": [1000, 2000],
        "device": {"/device:TPU:0": [["a", 1200, 1300], ["b", 1700, 1800]]},
        "host": [
            ["step", 1000, 1600],
            ["serve.step", 1010, 1590],
            ["serve.decode", 1050, 1400],
            ["finish", 1600, 1650],
        ],
    }
    got = spans.idle_by_span(rec)
    want = {"serve.decode": 250, "harness": 250, "serve.step": 230,
            "finish": 50, "step": 20}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # The idle gap 1300-1700 alone spans five of them.
    assert sum(got.values()) == pytest.approx(800e-9)


def test_innermost_labels_nested_spans():
    pieces = spans.innermost(
        [["step", 0, 100], ["serve.step", 10, 90], ["serve.sync", 20, 30],
         ["serve.sync", 40, 50]], -10, 120,
    )
    assert pieces == [
        (-10, 0, "harness"), (0, 10, "step"), (10, 20, "serve.step"),
        (20, 30, "serve.sync"), (30, 40, "serve.step"), (40, 50, "serve.sync"),
        (50, 90, "serve.step"), (90, 100, "step"), (100, 120, "harness"),
    ]


def test_recorded_trace_idle_sums_to_the_window_idle():
    """A slice of a traced v2-agent-8k window on a v5e, the program's spans
    included: the split covers the idle time that the reduction counts,
    and most of it lies inside the program's spans."""
    rec = json.loads((HERE / "data" / "trace_sample_spans.json").read_text())
    assert any(n.startswith(spans.PREFIX) for n, _, _ in rec["host"])
    bench_only = {**rec, "host": [h for h in rec["host"] if h[0] in trace_mod.HOST_SPANS]}
    out = trace_mod.reduce(bench_only, kernel_names=["mla_decode_paged", "combine_"])
    split = spans.idle_by_span(rec)
    idle = out["window_s"] - out["busy_s"]
    assert sum(split.values()) == pytest.approx(idle, rel=0.005)
    inside = sum(v for k, v in split.items() if k.startswith(spans.PREFIX))
    assert inside > 0.5 * idle


def test_program_spans_read_from_a_profiler_trace(tmp_path):
    import jax

    from repro.core.tracing import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("serve.step", batch=2):
            with span("serve.sync", site="greedy"):
                pass
        with jax.profiler.TraceAnnotation("step"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = spans.program_spans(str(tmp_path))
    assert sorted(n for n, _, _ in got) == ["serve.step", "serve.sync"]
    (outer,) = [g for g in got if g[0] == "serve.step"]
    (inner,) = [g for g in got if g[0] == "serve.sync"]
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]
