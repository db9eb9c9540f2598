"""Fault-tolerant serving: chaos plans, recoverable eviction/replay, shard
lifecycle, and the supervised serve loop.

Layers of coverage:

* pure units — :class:`FaultPlan` (validation, seeded determinism, event
  ordering), ``route_request``'s health mask, the
  :class:`PagedKVCache.refcount_sweep` leak audit, and the two satellite
  bug fixes (StragglerMonitor EWMA exclusion, TrainingSupervisor history
  truncation on restore);
* session tests on the smoke model — suspend/resume greedy parity on the
  single-host paged session (including forked prefix families: suspending
  a child releases only unshared pages, resume re-aliases via the parent),
  ballast pressure, drain/attach shard lifecycle;
* the acceptance scenario — a seeded shard loss mid-stream on a
  two-shard session under :class:`ServeSupervisor`: every victim is
  suspended, re-routed to the survivor, replayed, and completes with
  greedy outputs bit-identical to the fault-free run, across cache dtypes
  and with/without speculation, with a zero-leak refcount sweep after.
"""

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.kernels.decode_schedule import route_request
from repro.models.model_zoo import build_model
from repro.runtime.fault_injection import FAULT_KINDS, FaultEvent, FaultPlan
from repro.runtime.fault_tolerance import StragglerMonitor, TrainingSupervisor
from repro.runtime.kv_cache import PagedKVCache
from repro.runtime.serve_loop import (
    PagedServingSession,
    ServeSupervisor,
    ShardedPagedServingSession,
)

CFG = get_config("deepseek-v2-mla", smoke=True)
PAGE, BLOCK_K, CHUNK = 16, 32, 16


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


# --------------------------------------------------------------------------- #
# FaultPlan units
# --------------------------------------------------------------------------- #


def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(step=1, kind="meteor_strike")
    with pytest.raises(ValueError, match="step must be >= 0"):
        FaultEvent(step=-1, kind="shard_loss")
    with pytest.raises(ValueError, match="shard must be >= 0"):
        FaultEvent(step=1, kind="shard_loss", shard=-2)
    with pytest.raises(TypeError, match="FaultEvents"):
        FaultPlan([("shard_loss", 3)])


def test_fault_plan_orders_and_indexes_events():
    plan = FaultPlan(
        [
            FaultEvent(step=5, kind="abandon"),
            FaultEvent(step=2, kind="slow_shard"),
            FaultEvent(step=5, kind="shard_loss", shard=1),
        ]
    )
    assert [e.step for e in plan] == [2, 5, 5]
    # same step -> FAULT_KINDS order, so shard_loss precedes abandon
    assert [e.kind for e in plan.events_at(5)] == ["shard_loss", "abandon"]
    assert plan.events_at(3) == []
    assert len(plan) == 3
    assert "slow_shard" in plan.describe()


def test_fault_plan_generate_is_seed_deterministic():
    a = FaultPlan.generate(7, num_shards=2, horizon=16, pool_pages=32)
    b = FaultPlan.generate(7, num_shards=2, horizon=16, pool_pages=32)
    assert [e.describe() for e in a] == [e.describe() for e in b]
    c = FaultPlan.generate(8, num_shards=2, horizon=16, pool_pages=32)
    assert [e.describe() for e in a] != [e.describe() for e in c]


def test_fault_plan_generate_respects_shape():
    # single shard: no survivors to re-route onto -> no shard_loss event
    solo = FaultPlan.generate(3, num_shards=1, horizon=16, pool_pages=32)
    assert all(e.kind != "shard_loss" for e in solo)
    # abandon is opt-in: generated plans back the all-requests-complete gate
    assert all(e.kind != "abandon" for e in solo)
    multi = FaultPlan.generate(3, num_shards=2, horizon=16, pool_pages=32)
    losses = [e for e in multi if e.kind == "shard_loss"]
    assert losses and all(1 <= e.step <= 8 for e in losses)
    assert all(e.kind in FAULT_KINDS for e in multi)
    with pytest.raises(ValueError, match="horizon"):
        FaultPlan.generate(0, horizon=1)


# --------------------------------------------------------------------------- #
# satellite fixes: straggler EWMA + supervisor history truncation
# --------------------------------------------------------------------------- #


def test_straggler_burst_keeps_being_flagged():
    """Flagged durations must not feed the EWMA: under a sustained burst
    the old code inflated the baseline until stragglers looked normal."""
    m = StragglerMonitor(alpha=0.5, threshold=2.0)
    for s in range(4):
        assert not m.observe(s, 1.0)
    # a burst 10x the baseline: every single one must be flagged
    for s in range(4, 12):
        assert m.observe(s, 10.0), f"straggler at step {s} went unflagged"
    assert len(m.events) == 8
    assert m.ewma == pytest.approx(1.0)  # baseline still models healthy steps
    # healthy steps keep updating the EWMA as before
    assert not m.observe(12, 1.5)
    assert m.ewma == pytest.approx(1.25)


class _FakeCkpt:
    """In-memory checkpoint manager (the supervisor's protocol surface)."""

    def __init__(self):
        self.saved = {}

    def save(self, step, state):
        self.saved[step] = state

    def restore_latest(self, state):
        if not self.saved:
            return None, state
        step = max(self.saved)
        return step, self.saved[step]


class _CountData:
    def batch_at(self, step):
        return step


def test_training_supervisor_truncates_history_on_restore():
    """A failure after the checkpoint replays rolled-back steps; their old
    history entries must be dropped or every step appears twice."""
    fail_at = {6}

    def hook(step):
        if step in fail_at:
            fail_at.discard(step)
            raise RuntimeError("injected")

    def step_fn(state, batch, step):
        return state + 1, {"step": step}

    sup = TrainingSupervisor(
        ckpt_manager=_FakeCkpt(),
        data=_CountData(),
        ckpt_every=4,
        backoff=0.0,
        failure_hook=hook,
    )
    state, last, history = sup.run(step_fn, 0, start_step=0, num_steps=10)
    assert sup.restarts == 1
    assert last == 10
    # steps 4 and 5 ran, died at 6, restored to 4, replayed 4..9: exactly
    # one history entry per step, in order
    assert [s for s, _ in history] == list(range(10))
    assert state == 10  # restored state + replayed steps, nothing doubled


def test_training_supervisor_history_without_failures_unchanged():
    sup = TrainingSupervisor(
        ckpt_manager=_FakeCkpt(), data=_CountData(), ckpt_every=3, backoff=0.0
    )
    _, last, history = sup.run(
        lambda s, b, t: (s, {}), 0, start_step=0, num_steps=5
    )
    assert last == 5 and [s for s, _ in history] == list(range(5))


# --------------------------------------------------------------------------- #
# refcount sweep + routing health mask
# --------------------------------------------------------------------------- #


def test_refcount_sweep_clean_pool():
    c = PagedKVCache(num_pages=8, page_size=4)
    c.alloc(1)
    c.reserve(1, 6)  # 2 pages
    c.fork(1, 2, 4)  # aliases page 0
    s = c.refcount_sweep()
    assert s == {
        "live_pages": 2,
        "retained_pages": 0,
        "free_pages": 6,
        "aliased_pages": 1,
        "live_sequences": 2,
    }
    c.free(1)
    c.free(2)
    s = c.refcount_sweep()
    assert s["live_pages"] == 0 and s["free_pages"] == 8


def test_refcount_sweep_catches_corruption():
    c = PagedKVCache(num_pages=4, page_size=4)
    c.alloc(1)
    c.reserve(1, 4)
    c._ref[c._seq_pages[1][0]] += 1  # simulate a leaked alias
    with pytest.raises(AssertionError, match="refcount mismatch"):
        c.refcount_sweep()
    c._ref[c._seq_pages[1][0]] -= 1
    c._free.append(c._seq_pages[1][0])  # live page on the free list
    with pytest.raises(AssertionError, match="free list"):
        c.refcount_sweep()


def test_route_request_health_mask():
    # healthiest-but-masked shard is skipped even when least loaded
    assert route_request([0, 5], [8, 8], 1, shard_ok=[False, True]) == 1
    # draining + dead both masked -> no admission target
    assert route_request([0, 0], [8, 8], 1, shard_ok=[False, False]) is None
    # no mask behaves exactly as before
    assert route_request([0, 5], [8, 8], 1) == 0


# --------------------------------------------------------------------------- #
# suspend / resume on the single-host paged session
# --------------------------------------------------------------------------- #


def test_suspend_resume_greedy_parity(model_and_params):
    """Mid-stream suspend frees every page; resume replays and the final
    token streams are bit-identical to an uninterrupted run."""
    model, params = model_and_params
    prompts = prompts_for(0, [24, 17, 9])
    gen = 8

    base = make_single(model, params)
    b_rids = [base.add_request(p) for p in prompts]
    for _ in range(gen - 1):
        base.step()
    want = [base.finish(r) for r in b_rids]

    sess = make_single(model, params)
    rids = [sess.add_request(p) for p in prompts]
    for i in range(gen - 1):
        if i == 3:
            sess.suspend(rids[1])
            assert rids[1] not in sess.active
            assert sess.cache.refcount_sweep()["live_sequences"] == 2
        if i == 5:
            assert sess.resume_pending() == [rids[1]]
        sess.step()
    while len(sess.outputs[rids[1]]) < gen:
        sess.step()
    got = [sess.finish(r)[:gen] for r in rids]
    assert got == want
    ws = sess.work_stats()
    assert ws["suspends"] == 1 and ws["resumes"] == 1
    assert ws["replay_mismatches"] == 0
    assert ws["replay_prefill_tokens"] > 0
    assert sweep_all(sess) == 0


def test_suspend_resume_errors_and_discard(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params)
    (rid,) = [sess.add_request(p) for p in prompts_for(1, [10])]
    with pytest.raises(KeyError, match="not live"):
        sess.suspend(999)
    with pytest.raises(KeyError, match="not suspended"):
        sess.resume(rid)
    sess.suspend(rid)
    with pytest.raises(KeyError, match="not live"):
        sess.suspend(rid)  # already suspended
    out = sess.discard_suspended(rid)
    assert len(out) == 1  # the prefill token
    assert not sess.suspended and sweep_all(sess) == 0


def test_resume_fails_cleanly_without_room(model_and_params):
    """resume() under pool pressure returns False, allocates nothing, and
    succeeds once the ballast lifts."""
    model, params = model_and_params
    sess = make_single(model, params, num_pages=8)
    (rid,) = [sess.add_request(p) for p in prompts_for(2, [20])]
    sess.step()
    sess.suspend(rid)
    handle = sess.hold_pages(8)  # seize the whole pool
    assert sess.cache.num_free_pages == 0
    assert sess.resume(rid) is False
    assert rid in sess.suspended and rid not in sess.active
    sess.cache.refcount_sweep()  # no half-replay litter
    sess.release_pages(handle)
    with pytest.raises(KeyError, match="not a held ballast"):
        sess.release_pages(handle)
    assert sess.resume(rid) is True
    sess.step()
    assert len(sess.outputs[rid]) >= 2
    sess.finish(rid)
    assert sweep_all(sess) == 0


def test_suspend_resume_speculative_pending_state(model_and_params):
    """Suspending between speculative steps needs no extra rollback: the
    rows invariant (cache rows = prompt + outputs[:-1]) already holds, and
    the resumed stream stays identical to the undisturbed one."""
    model, params = model_and_params
    prompts = prompts_for(3, [12, 18])

    target = 8

    def drive(interrupt):
        sess = make_single(model, params, speculate="ngram", draft_k=3)
        rids = [sess.add_request(p) for p in prompts]
        for i in range(6):
            if interrupt and i == 2:
                sess.suspend(rids[0])
            if interrupt and i == 4:
                assert sess.resume(rids[0])
            sess.step()
        # retire the undisturbed request, then let the interrupted one
        # (which missed two steps) decode alone up to the target length
        out1 = sess.finish(rids[1])[:target]
        while len(sess.outputs[rids[0]]) < target:
            sess.step()
        out0 = sess.finish(rids[0])[:target]
        ws = sess.work_stats()
        assert ws["replay_mismatches"] == 0
        assert sweep_all(sess) == 0
        return [out0, out1]

    assert drive(False) == drive(True)


# --------------------------------------------------------------------------- #
# suspend/resume x prefix sharing (satellite 3)
# --------------------------------------------------------------------------- #


def test_suspend_forked_child_releases_only_unshared_pages(model_and_params):
    """The COW boundary survives: suspending a child decrements the shared
    prefix pages' refcounts by one (parent keeps them) and only the child's
    private pages return to the free list."""
    model, params = model_and_params
    sess = make_single(model, params)
    parent = sess.add_request(prompts_for(4, [40])[0])  # 3 pages (PAGE=16)
    child = sess.admit_with_prefix(parent, prompts_for(5, [20])[0], 32)
    for _ in range(2):
        sess.step()
    parent_pages = set(sess.cache.seq_pages(parent))
    child_pages = set(sess.cache.seq_pages(child))
    shared = parent_pages & child_pages
    private = child_pages - parent_pages
    assert shared and private  # the geometry actually aliases
    free_before = sess.cache.num_free_pages
    sess.suspend(child)
    # shared pages: still owned (by the parent alone now), not freed
    for pid in shared:
        assert sess.cache.page_refcount(pid) == 1
    for pid in private:
        assert sess.cache.page_refcount(pid) == 0
    assert sess.cache.num_free_pages == free_before + len(private)
    assert set(sess.cache.seq_pages(parent)) == parent_pages
    sess.cache.refcount_sweep()


def test_resume_forked_child_realiases_parent_prefix(model_and_params):
    """While the parent is live, a resumed child re-aliases the shared
    prefix (admit_with_prefix path: only the divergent suffix replays) and
    the family's greedy streams match undisturbed twins exactly."""
    model, params = model_and_params
    prompts = prompts_for(6, [40, 20])
    gen = 7

    def drive(interrupt):
        sess = make_single(model, params)
        parent = sess.add_request(prompts[0])
        child = sess.admit_with_prefix(parent, prompts[1], 32)
        replayed = 0
        for i in range(gen):
            if interrupt and i == 2:
                sess.suspend(child)
            if interrupt and i == 4:
                before = sess.cache.num_aliased_pages()
                assert sess.resume(child)
                assert sess.cache.num_aliased_pages() >= before
                # replay = suffix only, not the shared 32-row prefix
                replayed = sess.work_stats()["replay_prefill_tokens"]
                assert 0 < replayed < len(prompts[1]) + 32 + gen
            sess.step()
        while len(sess.outputs[child]) < gen + 1:
            sess.step()
        assert sess.work_stats()["replay_mismatches"] == 0
        out_p = list(sess.outputs[parent])[: gen + 1]
        out_c = list(sess.outputs[child])[: gen + 1]
        sess.finish(parent)
        sess.finish(child)
        assert sweep_all(sess) == 0
        return out_p, out_c

    assert drive(False) == drive(True)


def test_resume_child_standalone_after_parent_finishes(model_and_params):
    """With the parent gone the child replays its full history standalone
    — same tokens, no aliasing."""
    model, params = model_and_params
    prompts = prompts_for(7, [32, 12])
    gen = 6

    def drive(interrupt):
        sess = make_single(model, params)
        parent = sess.add_request(prompts[0])
        child = sess.admit_with_prefix(parent, prompts[1], 32)
        for i in range(gen):
            if interrupt and i == 2:
                sess.suspend(child)
                sess.finish(parent)
            if interrupt and i == 3:
                assert sess.resume(child)
                assert sess.cache.num_aliased_pages() == 0
            sess.step()
        if not interrupt:
            sess.finish(parent)
        while len(sess.outputs[child]) < gen + 1:
            sess.step()
        assert sess.work_stats()["replay_mismatches"] == 0
        out = list(sess.outputs[child])[: gen + 1]
        sess.finish(child)
        assert sweep_all(sess) == 0
        return out

    assert drive(False) == drive(True)


# --------------------------------------------------------------------------- #
# shard lifecycle: drain / fail / attach
# --------------------------------------------------------------------------- #


def test_drain_shard_stops_admission_keeps_live(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params)
    rids = [sess.add_request(p) for p in prompts_for(8, [10, 12])]
    homes = {sess.shard_of(r) for r in rids}
    assert homes == {0, 1}  # routing spread them
    draining = sess.drain_shard(1)
    assert draining == 1
    assert sess.shard_health == ["healthy", "draining"]
    # new admissions all land on the healthy shard
    more = [sess.add_request(p) for p in prompts_for(9, [8, 8])]
    assert all(sess.shard_of(r) == 0 for r in more)
    # the draining shard's request keeps decoding to completion
    victim = next(r for r in rids if sess.shard_of(r) == 1)
    sess.step()
    assert len(sess.outputs[victim]) == 2
    sess.finish(victim)
    assert sess.drain_shard(1) == 0  # idempotent; now empty


def test_attach_shard_grows_fleet_and_takes_traffic(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params)
    for p in prompts_for(10, [10, 12]):
        sess.add_request(p)
    idx = sess.attach_shard()
    assert idx == 2 and sess.num_shards == 3
    assert sess.shard_health == ["healthy", "healthy", "healthy"]
    assert sess.shards[idx].cache.num_pages == sess._pages_per_shard
    # empty pool + zero live blocks: the new shard wins the next admission
    rid = sess.add_request(prompts_for(11, [9])[0])
    assert sess.shard_of(rid) == idx
    sess.step()
    assert len(sess.outputs[rid]) == 2
    ws = sess.work_stats()
    assert len(ws["per_shard"]) == 3 and len(ws["shard_health"]) == 3


def test_fail_shard_reroutes_to_survivor(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params)
    rids = [sess.add_request(p) for p in prompts_for(12, [10, 12, 14])]
    sess.step()
    lost = [r for r in rids if sess.shard_of(r) == 1]
    kept = [r for r in rids if sess.shard_of(r) == 0]
    assert lost
    res = sess.fail_shard(1)
    assert res["suspended"] == lost and res["resumed"] == lost
    assert sess.shard_health == ["healthy", "dead"]
    assert all(sess.shard_of(r) == 0 for r in rids)
    # outputs views survived the move: same list objects keep growing
    views = {r: sess.outputs[r] for r in rids}
    sess.step()
    assert all(len(views[r]) == 3 for r in rids)
    # dead pool is empty; survivor holds everything
    assert sess.shards[1].cache.refcount_sweep()["live_pages"] == 0
    assert kept or True  # routing may have put all on shard 1 pre-fail
    assert sess.fail_shard(1) == {"suspended": [], "resumed": []}
    for r in rids:
        sess.finish(r)
    assert sweep_all(sess) == 0


def test_fail_shard_keeps_fork_family_together(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params, num_pages=128)
    parent = sess.add_request(prompts_for(13, [40])[0])
    child = sess.admit_with_prefix(parent, prompts_for(14, [8])[0], 32)
    assert sess.shard_of(child) == sess.shard_of(parent)
    home = sess.shard_of(parent)
    sess.step()
    res = sess.fail_shard(home)
    assert set(res["resumed"]) == {parent, child}
    new_home = sess.shard_of(parent)
    assert new_home != home
    assert sess.shard_of(child) == new_home  # family pinning after re-route
    # the prefix is aliased again on the new shard, not recomputed twice
    assert sess.shards[new_home].cache.num_aliased_pages() > 0
    sess.step()
    for r in (parent, child):
        sess.finish(r)
    assert sweep_all(sess) == 0


# --------------------------------------------------------------------------- #
# supervised serve loop
# --------------------------------------------------------------------------- #


def test_supervisor_requires_paged_session(model_and_params):
    with pytest.raises(ValueError, match="paged session"):
        ServeSupervisor(object(), gen_len=4)


def test_supervisor_plain_run_completes_everything(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params)
    sup = ServeSupervisor(sess, gen_len=4)
    prompts = prompts_for(15, [10, 14, 8])
    idxs = [sup.submit(p) for p in prompts]
    results = sup.run()
    assert sorted(results) == idxs
    assert all(len(results[i]) >= 4 for i in idxs)
    st = sup.stats()
    assert st["completed"] == 3 and st["abandoned"] == 0
    assert st["suspends"] == 0 and st["evictions"] == 0
    assert sweep_all(sess) == 0


def test_supervisor_admission_backoff_queues_overflow(model_and_params):
    """max_batch=1 forces head-of-line queuing: requests admit one at a
    time FIFO, with exponential backoff counted on each rejection."""
    model, params = model_and_params
    sess = make_single(model, params, max_batch=1)
    sup = ServeSupervisor(sess, gen_len=3)
    idxs = [sup.submit(p) for p in prompts_for(16, [8, 9, 10])]
    results = sup.run()
    assert sorted(results) == idxs
    st = sup.stats()
    assert st["completed"] == 3
    assert st["admission_retries"] > 0
    assert sweep_all(sess) == 0


def test_supervisor_deadline_abandons_with_partial_output(model_and_params):
    """A deadline shorter than gen_len abandons every request with its
    partial output intact (counted, never lost)."""
    model, params = model_and_params
    sess = make_single(model, params)
    sup = ServeSupervisor(sess, gen_len=50, deadline=3)
    idxs = [sup.submit(p) for p in prompts_for(17, [10, 12])]
    results = sup.run()
    assert sorted(results) == idxs and sup.abandoned_idx == set(idxs)
    st = sup.stats()
    assert st["abandoned"] == 2 and st["completed"] == 0
    for i in idxs:
        assert 1 <= len(results[i]) < 50
    assert sweep_all(sess) == 0


def test_supervisor_pool_pressure_evicts_and_recovers(model_and_params):
    """A pool-pressure fault squeezes the pool mid-stream: the supervisor
    recoverably evicts, waits out the ballast, resumes, and the outputs
    still match the calm run exactly."""
    model, params = model_and_params
    # prompts sit just under page boundaries (PAGE=16), so decode appends
    # demand fresh pages almost immediately — the squeeze must bite
    prompts = prompts_for(18, [15, 31, 14])
    gen = 6

    def drive(plan):
        sess = make_single(model, params, num_pages=12)
        sup = ServeSupervisor(sess, gen_len=gen, plan=plan)
        for p in prompts:
            sup.submit(p)
        res = sup.run()
        assert sweep_all(sess) == 0
        return res, sup.stats()

    calm, calm_stats = drive(None)
    plan = FaultPlan(
        [FaultEvent(step=1, kind="pool_pressure", pages=8, duration=3)]
    )
    squeezed, st = drive(plan)
    assert st["faults_applied"] == 1
    assert st["suspends"] >= 1  # the squeeze actually bit
    assert st["replay_mismatches"] == 0
    assert calm_stats["suspends"] == 0
    assert calm == squeezed


def test_supervisor_abandon_fault_drops_oldest(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params)
    sup = ServeSupervisor(
        sess,
        gen_len=6,
        plan=FaultPlan([FaultEvent(step=2, kind="abandon")]),
    )
    idxs = [sup.submit(p) for p in prompts_for(19, [10, 12])]
    results = sup.run()
    assert sup.abandoned_idx == {idxs[0]}  # oldest submission dropped
    assert len(results[idxs[0]]) < 7
    assert len(results[idxs[1]]) >= 6
    st = sup.stats()
    assert st["abandoned"] == 1 and st["completed"] == 1
    assert sweep_all(sess) == 0


def test_supervisor_slow_shard_flags_stragglers(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params)
    sup = ServeSupervisor(
        sess,
        gen_len=8,
        plan=FaultPlan(
            [FaultEvent(step=3, kind="slow_shard", duration=2, factor=9.0)]
        ),
    )
    for p in prompts_for(20, [10, 12]):
        sup.submit(p)
    sup.run()
    st = sup.stats()
    # the injected inflation (factor 9 > threshold 3) must flag, and the
    # fixed monitor keeps its baseline clean while doing so
    assert st["straggler_events"] >= 1
    assert st["completed"] == 2


def test_supervisor_never_kills_last_healthy_shard(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params)
    plan = FaultPlan(
        [
            FaultEvent(step=1, kind="shard_loss", shard=0),
            FaultEvent(step=2, kind="shard_loss", shard=1),
        ]
    )
    sup = ServeSupervisor(sess, gen_len=4, plan=plan)
    idxs = [sup.submit(p) for p in prompts_for(21, [10, 12, 9])]
    results = sup.run()
    assert sorted(results) == idxs
    st = sup.stats()
    assert st["faults_applied"] == 1 and st["faults_skipped"] == 1
    assert sess.shard_health.count("healthy") == 1
    assert sweep_all(sess) == 0


def test_supervisor_raises_on_unadmittable_prompt(model_and_params):
    model, params = model_and_params
    sess = make_single(model, params, num_pages=4)
    sup = ServeSupervisor(sess, gen_len=2)
    sup.submit(list(range(2, 2 + 2 * PAGE)))  # needs 2 of 4 pages: fine
    sup.submit(list(range(2, 2 + 10 * PAGE)))  # can never fit
    with pytest.raises(ValueError, match="can never be admitted|needs"):
        sup.run()


# --------------------------------------------------------------------------- #
# acceptance: seeded shard loss mid-stream, bit-identical recovery
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "kv_dtype, speculate",
    [
        (None, "off"),
        pytest.param("int8", "off", marks=pytest.mark.slow),
        pytest.param(None, "ngram", marks=pytest.mark.slow),
        ("int8", "ngram"),
    ],
)
def test_shard_loss_recovery_bit_identical(model_and_params, kv_dtype, speculate):
    """ISSUE-8 acceptance: a seeded FaultPlan kills one of two shards
    mid-stream; every live request is suspended, re-routed, replayed, and
    completes with greedy outputs bit-identical to the fault-free run —
    across cache dtypes and speculation modes — and the host-mirror
    refcount sweep finds no leaked pages."""
    model, params = model_and_params
    prompts = prompts_for(22, [24, 17, 9, 30])
    gen = 8
    plan = FaultPlan([FaultEvent(step=3, kind="shard_loss", shard=1)])

    def drive(active_plan):
        sess = make_sharded(
            model, params, kv_dtype=kv_dtype, speculate=speculate, draft_k=3
        )
        sup = ServeSupervisor(sess, gen_len=gen, plan=active_plan)
        for p in prompts:
            sup.submit(p)
        results = sup.run()
        assert sweep_all(sess) == 0
        return results, sup.stats(), sess

    base, base_stats, _ = drive(None)
    faulted, stats, sess = drive(plan)
    assert sorted(faulted) == sorted(base) == list(range(len(prompts)))
    assert stats["abandoned"] == 0
    assert stats["suspends"] >= 1  # the loss actually hit live requests
    assert stats["resumes"] == stats["suspends"]
    assert stats["replay_mismatches"] == 0
    assert sess.shard_health == ["healthy", "dead"]
    # speculation can overshoot gen_len by up to draft_k-1: compare the
    # common prefix, which must cover at least gen tokens
    for i in base:
        n = min(len(base[i]), len(faulted[i]))
        assert n >= gen
        assert base[i][:n] == faulted[i][:n], f"request {i} diverged"
