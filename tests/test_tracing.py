"""Spans and counters of the serving path (``repro.core.tracing``).

A tiny ``PagedServingSession`` with a prefill budget serves a few prompts
in interpret mode under the profiler; the tests read the spans back from
the profiler's trace (their intervals) and from ``work_stats`` (their
totals).
"""

import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.tracing import Tracer, span
from repro.models.model_zoo import build_model
from repro.runtime.serve_loop import (
    SERVE_SPANS,
    PagedServingSession,
    ShardedPagedServingSession,
)

CFG = get_config("deepseek-v2-mla", smoke=True)
PAGE, BLOCK_K, CHUNK = 16, 32, 16
BUDGET = 3 * CHUNK
LENGTHS = (40, 20, 70)
# The chip benchmark's own span names (benchmarks/chip/trace.py): its
# reduction keys on them, so no span of the program may take one.
BENCHMARK_SPANS = {"window", "step", "add_request", "finish"}
# Spans that a session call opens; every other span lies inside one.
TOP = ("serve.step", "serve.admit", "serve.finish")
# Where each inner span may sit.
PARENTS = {
    "serve.decode": {"serve.step"},
    "serve.prefill": {"serve.step"},
    "serve.schedule": {"serve.step", "serve.decode", "serve.prefill"},
    "serve.sync": {"serve.decode", "serve.prefill", "serve.admit"},
}


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    return model, model.init(jax.random.PRNGKey(0))


def session_kwargs(**kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefill_budget", BUDGET)
    kw.setdefault("interpret", True)
    return kw


def prompts(seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, CFG.vocab_size, size=n).tolist() for n in LENGTHS]


def delta(a, b):
    return {k: b[k] - a[k] for k in b if isinstance(b[k], (int, float))}


def host_spans(trace_dir):
    """The program's spans in a profiler trace: (name, start, end, args)."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    start = int(e.start_ns)
                    out.append((e.name, start, start + int(e.duration_ns), dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def served(model_and_params, tmp_path_factory):
    """Three prompts admitted, stepped until each has its first token, one
    decode-only step, then finished -- all under the profiler."""
    model, params = model_and_params
    sess = PagedServingSession(model, params, **session_kwargs(max_batch=4))
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        snaps = {"start": sess.work_stats()}
        rids = [sess.add_request(p) for p in prompts(0)]
        snaps["admitted"] = sess.work_stats()
        steps = 0
        while not all(sess.outputs[r] for r in rids):
            sess.step()
            steps += 1
        snaps["prefilled"] = sess.work_stats()
        assert not sess.prefill_pending
        sess.step()
        snaps["decoded"] = sess.work_stats()
        for r in rids:
            sess.finish(r)
        snaps["finished"] = sess.work_stats()
    finally:
        jax.profiler.stop_trace()
    return {"sess": sess, "snaps": snaps, "steps": steps, "rids": rids,
            "spans": host_spans(trace_dir)}


def test_tracer_totals_count_self_time():
    tr = Tracer(("outer",))
    assert tr.stats() == {"span_n.outer": 0, "span_s.outer": 0.0,
                          "span_self_s.outer": 0.0}
    with tr.active():
        with span("outer"):
            time.sleep(0.002)
            for _ in range(2):
                with span("inner", site="x"):
                    time.sleep(0.001)
    st = tr.stats()
    assert st["span_n.outer"] == 1 and st["span_n.inner"] == 2
    assert st["span_self_s.inner"] == st["span_s.inner"]
    assert st["span_self_s.outer"] >= 0.002
    assert st["span_self_s.outer"] + st["span_s.inner"] == pytest.approx(
        st["span_s.outer"], rel=1e-9
    )


def test_spans_count_only_for_the_current_tracer():
    a, b = Tracer(("s",)), Tracer(("s",))
    with span("s"):
        pass
    with a.active():
        with span("s"):
            with b.active():
                with span("s"):
                    pass
    assert (a.stats()["span_n.s"], b.stats()["span_n.s"]) == (1, 1)
    # b's span ran inside a's, yet counts apart: a's self time keeps it.
    assert a.stats()["span_self_s.s"] == a.stats()["span_s.s"]


def test_child_spans_lie_inside_their_parents(served):
    spans = served["spans"]
    names = {n for n, *_ in spans}
    assert {"serve.step", "serve.decode", "serve.prefill", "serve.schedule",
            "serve.sync", "serve.admit", "serve.finish"} <= names
    for name, s, e, _ in spans:
        around = [
            (n2, s2, e2) for n2, s2, e2, _ in spans
            if (n2, s2, e2) != (name, s, e) and s2 <= s and e <= e2
        ]
        if name in TOP:
            assert not around, (name, around)
            continue
        assert around, f"{name} at {s} lies in no span"
        parent = min(around, key=lambda t: t[2] - t[1])
        assert parent[0] in PARENTS[name], (name, parent)
    # Any two spans either nest or do not meet.
    for _, s, e, _ in spans:
        for _, s2, e2, _ in spans:
            assert e <= s2 or e2 <= s or (s <= s2 and e2 <= e) or (s2 <= s and e <= e2)


def test_spans_carry_their_request_and_site(served):
    rids = set(served["rids"])
    for name, _, _, args in served["spans"]:
        if name in ("serve.admit", "serve.finish"):
            assert args["rid"] in rids
        elif name == "serve.prefill":
            assert args["rid"] in rids and 0 < args["tokens"] <= BUDGET
        elif name in ("serve.step", "serve.decode"):
            assert "batch" in args
        elif name == "serve.sync":
            assert args["site"] in ("kv_len_check", "greedy", "first_token")


def test_step_self_time_and_its_childrens_make_its_total(served):
    """Between two snapshots that only step, every span lies in a
    ``serve.step``: its self time plus the self times of all spans inside
    it is its total."""
    d = delta(served["snaps"]["admitted"], served["snaps"]["decoded"])
    inside = sum(d[f"span_self_s.{n}"] for n in SERVE_SPANS)
    assert d["span_s.serve.step"] > 0
    assert inside == pytest.approx(d["span_s.serve.step"], rel=1e-9)
    assert d["span_n.serve.admit"] == d["span_n.serve.finish"] == 0


def test_one_step_span_per_step_call(served):
    snaps = served["snaps"]
    assert delta(snaps["admitted"], snaps["prefilled"])["span_n.serve.step"] == served["steps"]
    assert delta(snaps["prefilled"], snaps["decoded"])["span_n.serve.step"] == 1
    d = delta(snaps["start"], snaps["finished"])
    assert d["span_n.serve.admit"] == d["span_n.serve.finish"] == len(LENGTHS)


def test_decode_step_syncs_once_per_layer_and_once_for_the_tokens(served):
    """A decode-only step reads the device back once, for the greedy
    tokens: the layer walk checks ``kv_len`` on the host copy it already
    has, so no layer reads anything back.  A change that adds or removes a
    read updates this count."""
    d = delta(served["snaps"]["prefilled"], served["snaps"]["decoded"])
    assert d["span_n.serve.decode"] == 1 and d["span_n.serve.prefill"] == 0
    assert d["span_n.serve.sync"] == 1
    assert d["span_n.serve.schedule"] == 1


def test_prefill_slices_sync_per_layer_per_chunk(served):
    """No chunk of a prefill slice reads the device back; each prompt's
    last slice reads its first token once, and each decode step its greedy
    tokens once."""
    d = delta(served["snaps"]["admitted"], served["snaps"]["prefilled"])
    assert d["span_n.serve.prefill"] >= len(LENGTHS)
    assert d["span_n.serve.sync"] == len(LENGTHS) + d["span_n.serve.decode"]


def test_each_prompt_waits_once(served):
    d = delta(served["snaps"]["start"], served["snaps"]["finished"])
    assert d["prefill_waits"] == len(LENGTHS)
    assert d["prefill_wait_s"] > 0
    assert d["span_n.serve.prefill"] >= len(LENGTHS)


def test_no_span_takes_a_benchmark_name(served):
    stats = served["sess"].work_stats()
    recorded = {k.split(".", 1)[1] for k in stats if k.startswith("span_n.")}
    assert recorded == set(SERVE_SPANS)
    for name in recorded | {n for n, *_ in served["spans"]}:
        assert name.startswith("serve.") and name not in BENCHMARK_SPANS
    for gone in ("first_tokens", "ttft_units_total", "max_inter_token_units"):
        assert gone not in stats


def test_sharded_span_totals_sum_the_shards(model_and_params):
    model, params = model_and_params
    sess = ShardedPagedServingSession(model, params, **session_kwargs(shards=2))
    rids = []
    for p in prompts(1):  # each prompt's pages land before the next routes
        rids.append(sess.add_request(p))
        while not sess.outputs[rids[-1]]:
            sess.step()
    sess.step()
    agg = sess.work_stats()
    per_shard = agg["per_shard"]
    assert sum(1 for st in per_shard if st["span_n.serve.admit"]) == 2
    keys = [k for k in agg if k.startswith("span_")]
    assert len(keys) == 3 * len(SERVE_SPANS)
    for k in keys + ["prefill_wait_s", "prefill_waits"]:
        assert agg[k] == pytest.approx(sum(st[k] for st in per_shard), rel=1e-12)
    assert agg["prefill_waits"] == len(LENGTHS)
    sess.close()
