"""Token-budgeted chunked-prefill/decode interleaving + SLA scheduling.

Layers of coverage:

* pure units — :func:`plan_prefill_slices` (budget split, anti-starvation
  grant, chunk alignment of non-final slices), :func:`admission_order`
  (priority desc, deadline slack asc, submission-index tiebreak), and
  :func:`latency_percentile` (nearest-rank, empty, validation);
* session tests on the smoke model — interleaved-vs-phased greedy token
  parity (bf16 + int8, single-host + two shards, prefix trie on), the
  no-decode-stall property (live decode emits every step while a long
  prompt chunks in), pending-request lifecycle (finish / suspend / close
  mid-prefill, leak-free);
* supervisor tests — skip-ahead admission (a later prompt admits when the
  FIFO head can't fit, results keyed by submission index), priority
  admission ordering under a constrained pool, deadline accounting under
  zero-emission steps (a prompt still mid-prefill expires with zero
  tokens), and SLA-aware victim selection (lowest priority evicts first;
  a request within deadline_guard of its deadline is never the victim
  while another candidate exists).
"""

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.kernels.decode_schedule import admission_order, plan_prefill_slices
from repro.models.model_zoo import build_model
from repro.runtime.serve_loop import (
    PagedServingSession,
    ServeSupervisor,
    ShardedPagedServingSession,
    latency_percentile,
)

CFG = get_config("deepseek-v2-mla", smoke=True)
PAGE, BLOCK_K, CHUNK = 16, 32, 16
BUDGET = 3 * CHUNK


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_single(model, params, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("interpret", True)
    return PagedServingSession(model, params, **kw)


def make_sharded(model, params, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("shards", 2)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("interpret", True)
    return ShardedPagedServingSession(model, params, **kw)


def prompts_for(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, CFG.vocab_size, size=n).tolist() for n in lengths]


def sweep_all(sess):
    """Refcount-sweep every pool; returns total live pages (0 = no leaks)."""
    caches = (
        [s.cache for s in sess.shards]
        if hasattr(sess, "shards")
        else [sess.cache]
    )
    return sum(c.refcount_sweep()["live_pages"] for c in caches)


def drain(sess, rids, gen_len, limit=400):
    """Step until every rid holds gen_len + 1 tokens; returns the outputs
    truncated to that shared horizon (later rids keep decoding while
    earlier ones wait, so raw lengths may differ)."""
    for _ in range(limit):
        if all(len(sess.outputs[r]) > gen_len for r in rids):
            break
        sess.step()
    else:
        raise AssertionError("drain() did not converge")
    return {r: sess.finish(r)[: gen_len + 1] for r in rids}


# --------------------------------------------------------------------------- #
# plan_prefill_slices / admission_order / latency_percentile units
# --------------------------------------------------------------------------- #


def test_plan_prefill_slices_splits_budget_shortest_first():
    # Oldest gets its anti-starvation chunk, the rest goes to the entry
    # closest to finishing (index 1 completes inside the leftover).
    assert plan_prefill_slices([64, 32], 48, 16) == [16, 32]


def test_plan_prefill_slices_oldest_always_progresses():
    # A one-chunk budget advances the queue head even with shorter entries
    # behind it — no starvation of the long prompt.
    assert plan_prefill_slices([320, 16], 16, 16) == [16, 0]


def test_plan_prefill_slices_final_slice_may_be_subchunk():
    assert plan_prefill_slices([10], 48, 16) == [10]
    assert plan_prefill_slices([40], 48, 16) == [40]


def test_plan_prefill_slices_nonfinal_slices_chunk_aligned():
    # 40 of the 100 remain after this step: both grants round down to the
    # chunk so the next call still lands on monolithic-prefill boundaries.
    assert plan_prefill_slices([100], 40, 16) == [32]


def test_plan_prefill_slices_zero_budget_and_validation():
    assert plan_prefill_slices([64, 32], 0, 16) == [0, 0]
    assert plan_prefill_slices([], 64, 16) == []
    with pytest.raises(ValueError):
        plan_prefill_slices([16], -1, 16)
    with pytest.raises(ValueError):
        plan_prefill_slices([16], 16, 0)


def test_admission_order_priority_then_slack_then_index():
    assert admission_order([(0, 0, None), (1, 2, None), (2, 1, 5.0)]) == [1, 2, 0]
    # Equal priority: tighter deadline slack first, None (no deadline) last.
    assert admission_order([(0, 1, None), (1, 1, 3.0), (2, 1, 1.0)]) == [2, 1, 0]
    # Full tie: submission order.
    assert admission_order([(5, 0, None), (3, 0, None)]) == [3, 5]


def test_latency_percentile_nearest_rank():
    assert latency_percentile([4, 1, 3, 2], 50) == 2
    assert latency_percentile([4, 1, 3, 2], 99) == 4
    assert latency_percentile([7], 50) == 7
    assert latency_percentile([], 99) == 0.0
    with pytest.raises(ValueError):
        latency_percentile([1], 101)


def test_prefill_budget_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="prefill_budget"):
        make_single(model, params, prefill_budget=CHUNK - 1)


# --------------------------------------------------------------------------- #
# interleaved-vs-phased greedy parity
# --------------------------------------------------------------------------- #

LENGTHS = [70, 9, 24, 40]
GEN = 4


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_interleaved_matches_phased_single_host(model_and_params, kv_dtype):
    model, params = model_and_params
    prompts = prompts_for(3, LENGTHS)
    outs = {}
    for budget in (None, BUDGET):
        sess = make_single(
            model, params, prefill_budget=budget, kv_dtype=kv_dtype
        )
        rids = [sess.add_request(p) for p in prompts]
        outs[budget] = list(drain(sess, rids, GEN).values())
        assert sweep_all(sess) == 0
        sess.close()
    assert outs[None] == outs[BUDGET]


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_interleaved_matches_phased_sharded(model_and_params, kv_dtype):
    model, params = model_and_params
    prompts = prompts_for(4, LENGTHS)
    outs = {}
    for budget in (None, BUDGET):
        sess = make_sharded(
            model, params, prefill_budget=budget, kv_dtype=kv_dtype
        )
        rids = [sess.add_request(p) for p in prompts]
        outs[budget] = list(drain(sess, rids, GEN).values())
        assert sweep_all(sess) == 0
        sess.close()
    assert outs[None] == outs[BUDGET]


def test_interleaved_matches_phased_with_trie(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(5)
    template = rng.integers(2, CFG.vocab_size, size=2 * BLOCK_K).tolist()
    prompts = [
        template + rng.integers(2, CFG.vocab_size, size=5 + 7 * i).tolist()
        for i in range(4)
    ]
    outs, hits = {}, {}
    for budget in (None, BUDGET):
        sess = make_single(
            model, params, prefill_budget=budget, prefix_cache="trie"
        )
        # Staggered admissions so later prompts hit the retained template.
        rids = []
        for p in prompts:
            rids.append(sess.add_request(p))
            for _ in range(2):
                sess.step()
        outs[budget] = list(drain(sess, rids, GEN).values())
        hits[budget] = sess.work_stats()["trie_hits"]
        sess.reclaim_retained(64)
        assert sweep_all(sess) == 0
        sess.close()
    assert outs[None] == outs[BUDGET]
    # Budgeted admission must still adopt retained prefixes, not re-prefill.
    assert hits[BUDGET] >= 1 and hits[BUDGET] == hits[None]


# --------------------------------------------------------------------------- #
# no-decode-stall property + pending lifecycle
# --------------------------------------------------------------------------- #


def test_long_prompt_never_stalls_live_decode(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params, prefill_budget=BUDGET)
    short = prompts_for(0, [8])[0]
    r0 = sess.add_request(short)
    for _ in range(10):
        if sess.outputs[r0]:
            break
        sess.step()
    long = prompts_for(1, [320])[0]
    r1 = sess.add_request(long)
    assert sess.prefill_pending == 1
    # Property: while the 20-chunk prompt slices in, the live request
    # emits exactly one greedy token every step — no stall, ever.
    while sess.prefill_pending:
        before = len(sess.outputs[r0])
        sess.step()
        assert len(sess.outputs[r0]) == before + 1
    work = sess.work_stats()
    assert work["prefill_stall_steps"] == 0
    assert work["prefill_chunks"] >= 320 // CHUNK
    sess.finish(r0)
    sess.finish(r1)
    assert sweep_all(sess) == 0
    sess.close()


def test_pending_finish_and_close_are_leak_free(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params, prefill_budget=BUDGET)
    long = prompts_for(2, [200])[0]
    rid = sess.add_request(long)
    sess.step()  # partial prefill only — request still pending
    assert sess.prefill_pending == 1
    assert sess.finish(rid) == []  # mid-prefill retire: no tokens yet
    assert sess.prefill_pending == 0
    rid = sess.add_request(long)
    sess.step()
    sweep = sess.close()  # teardown with a pending request in flight
    assert sweep["free_pages"] == 64


def test_pending_suspend_resume_replays_exactly(model_and_params):
    model, params = model_and_params
    baseline = make_single(model, params)
    prompt = prompts_for(6, [90])[0]
    rb = baseline.add_request(prompt)
    want = drain(baseline, [rb], GEN)[rb]
    baseline.close()

    sess = make_single(model, params, prefill_budget=BUDGET)
    rid = sess.add_request(prompt)
    sess.step()  # partial prefill
    rec = sess.suspend(rid)
    assert rec.outputs == [] and sess.prefill_pending == 0
    assert sess.resume(rid)
    got = drain(sess, [rid], GEN)[rid]
    assert got == want
    assert sweep_all(sess) == 0
    sess.close()


# --------------------------------------------------------------------------- #
# supervisor: skip-ahead, priority admission, deadlines, victim selection
# --------------------------------------------------------------------------- #


def test_supervisor_skip_ahead_admission(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params, num_pages=24)
    sup = ServeSupervisor(sess, gen_len=3)
    mid, huge, small = prompts_for(7, [180, 300, 30])
    for p in (mid, huge, small):
        sup.submit(p)
    results = sup.run()
    # Results keyed by submission index, everyone completes.
    assert set(results) == {0, 1, 2}
    assert all(len(v) == 4 for v in results.values())
    recs = sup.latency_records()
    # The 300-token head can't fit beside the 180-token request; the
    # 30-token prompt behind it must admit without waiting for it.
    assert recs[2]["admit_step"] < recs[1]["admit_step"]
    assert sweep_all(sess) == 0
    sess.close()


def test_supervisor_priority_admission_order(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params, num_pages=8)
    sup = ServeSupervisor(sess, gen_len=3)
    a, b = prompts_for(8, [100, 100])
    sup.submit(a, priority=0)
    sup.submit(b, priority=5)
    results = sup.run()
    assert set(results) == {0, 1}
    recs = sup.latency_records()
    # Only one 100-token prompt fits at a time: the higher class admits
    # first even though it was submitted second.
    assert recs[1]["admit_step"] < recs[0]["admit_step"]
    assert sweep_all(sess) == 0
    sess.close()


def test_deadline_expires_mid_prefill_with_zero_tokens(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params, prefill_budget=BUDGET)
    sup = ServeSupervisor(sess, gen_len=3)
    long, short = prompts_for(9, [320, 12])
    sup.submit(long, deadline=3)  # 20 chunks / 3-chunk budget: can't make it
    sup.submit(short)
    results = sup.run()
    # Steps count against the deadline whether or not the request ever
    # emitted: the long prompt expires still mid-prefill, zero tokens out.
    assert 0 in sup.abandoned_idx
    assert results[0] == []
    assert len(results[1]) == 4
    stats = sup.stats()
    assert stats["abandoned"] == 1 and stats["prefill_stall_steps"] == 0
    assert sweep_all(sess) == 0
    sess.close()


def test_victim_selection_prefers_lowest_priority(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params)
    sup = ServeSupervisor(sess, gen_len=4)
    a, b = prompts_for(10, [40, 40])
    sup.submit(a, priority=0)
    sup.submit(b, priority=2)
    sup._admit(0)
    live = [r for r in sup._live]
    assert len(live) == 2
    sup._suspend_victim(live)
    # Equal slack (no deadlines), equal completion: the lower class loses.
    victim = next(iter(sess.suspended))
    assert sup._live[victim]["priority"] == 0
    sess.resume(victim)
    for r in live:
        sess.finish(r)
    assert sweep_all(sess) == 0
    sess.close()


def test_victim_selection_spares_near_deadline_requests(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params)
    sup = ServeSupervisor(sess, gen_len=4, deadline_guard=2)
    a, b = prompts_for(11, [40, 40])
    # The low-priority request is 1 step from its deadline (inside the
    # guard); the high-priority one has slack — priority must lose to the
    # deadline guard.
    sup.submit(a, priority=0, deadline=1)
    sup.submit(b, priority=2, deadline=30)
    sup._admit(0)
    live = [r for r in sup._live]
    sup._suspend_victim(live)
    victim = next(iter(sess.suspended))
    assert sup._live[victim]["priority"] == 2
    sess.resume(victim)
    for r in live:
        sess.finish(r)
    assert sweep_all(sess) == 0
    sess.close()


def test_supervised_interleaved_matches_phased_end_to_end(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(12)
    subs = [
        (0, rng.integers(2, CFG.vocab_size, size=260).tolist(), 0),
        (2, rng.integers(2, CFG.vocab_size, size=9).tolist(), 2),
        (5, rng.integers(2, CFG.vocab_size, size=17).tolist(), 2),
        (9, rng.integers(2, CFG.vocab_size, size=33).tolist(), 1),
    ]
    runs = {}
    for budget in (None, BUDGET):
        sess = make_single(model, params, prefill_budget=budget)
        sup = ServeSupervisor(sess, gen_len=4, arrival_unit="work_units")
        for arr, p, pri in subs:
            sup.submit(p, priority=pri, arrival=arr)
        runs[budget] = (sup.run(), sup.stats())
        assert sweep_all(sess) == 0
        sess.close()
    assert runs[None][0] == runs[BUDGET][0]
    assert runs[BUDGET][1]["prefill_stall_steps"] == 0
    assert runs[None][1]["prefill_stall_steps"] > 0
