"""Radix prefix trie: retention/eviction invariants + automatic admission.

Three layers of acceptance for the prefix cache:

1. **Trie mechanics** on a bare :class:`PagedKVCache`: longest-prefix
   match (including partial-edge hits), insert with edge splitting,
   refcount-safe LRU eviction, the ``retain_pages`` budget, and the
   epoch counter the memoizing scheduler keys on.
2. **Property-style churn**: a seeded interleaving of admit / finish /
   evict / COW / fork operations with ``refcount_sweep()`` after every
   phase — refcounts must reconcile exactly (sequence owners + pins),
   evicted nodes must never leave aliased retained pages behind, and
   teardown must return the pool to fully free.
3. **Model-level parity**: greedy outputs with ``prefix_cache="trie"``
   are token-for-token identical to trie-off for the fp32-smoke and int8
   cache dtypes, across staggered admissions, retention reuse, and
   pool-pressure eviction churn.

Plus the scheduling half: nested (trie-topology) prefix groups must be a
pure reorganization of work — same numerics as flat and plain schedules —
and the sharded router must prefer prefix locality on load ties.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_schedule import (
    build_prefix_schedule,
    find_prefix_groups,
    prefix_queue_grid_items,
    route_request,
)
from repro.models.model_zoo import build_model
from repro.runtime.kv_cache import PagedKVCache, PrefixTrie
from repro.runtime.serve_loop import PagedServingSession

CFG = get_config("deepseek-v2-mla", smoke=True)
PAGE, BLOCK_K, CHUNK = 16, 32, 16


# --------------------------------------------------------------------------- #
# trie mechanics on a bare cache
# --------------------------------------------------------------------------- #


def make_cache(num_pages=32, page_size=4):
    return PagedKVCache(num_pages=num_pages, page_size=page_size,
                        width=8, dtype=jnp.float32)


def fill(cache, rid, n_tokens, seed=0):
    rows = np.random.default_rng(seed).normal(size=(n_tokens, cache.width))
    cache.alloc(rid)
    cache.append(rid, jnp.asarray(rows, jnp.float32))


def toks(*blocks):
    """Flatten block-sized token runs: toks(1, 2) -> block of 1s + block
    of 2s (block = 8 tokens at page_size 4, pages_per_block 2)."""
    out = []
    for b in blocks:
        out.extend([b] * 8)
    return out


def retained(cache, trie, rid, tokens):
    """finish-style retention: insert the complete blocks, then free."""
    n_blocks = len(tokens) // trie.block_tokens
    ppb = trie.pages_per_block
    trie.insert(tokens[: n_blocks * trie.block_tokens],
                cache.seq_pages(rid)[: n_blocks * ppb])
    cache.free(rid)


def test_trie_validates_block_tokens():
    c = make_cache()
    with pytest.raises(ValueError, match="multiple"):
        PrefixTrie(c, block_tokens=6)
    with pytest.raises(ValueError, match="retain_pages"):
        PrefixTrie(c, block_tokens=8, retain_pages=-1)


def test_match_empty_and_miss_counters():
    c = make_cache()
    t = PrefixTrie(c, block_tokens=8)
    assert t.match(toks(1, 2)) == (0, [])
    assert t.misses == 1 and t.hits == 0


def test_insert_match_roundtrip_and_partial_edge():
    c = make_cache()
    t = PrefixTrie(c, block_tokens=8)
    fill(c, 0, 24)  # 3 blocks
    pages = c.seq_pages(0)
    t.insert(toks(1, 2, 3), pages)
    c.free(0)
    # full match
    m, p = t.match(toks(1, 2, 3))
    assert m == 24 and p == pages
    # partial-edge match: 2 of 3 blocks, no split needed
    m, p = t.match(toks(1, 2, 9))
    assert m == 16 and p == pages[:4]
    assert t.num_nodes == 1  # partial match must NOT have split the edge
    # divergent first block
    assert t.match(toks(5)) == (0, [])
    assert t.hits == 2 and t.misses == 1 and t.hit_tokens == 40


def test_insert_splits_on_divergence_and_bumps_epoch():
    c = make_cache()
    t = PrefixTrie(c, block_tokens=8)
    fill(c, 0, 24, seed=0)
    t.insert(toks(1, 2, 3), c.seq_pages(0))
    c.free(0)
    e0 = t.epoch
    fill(c, 1, 24, seed=1)
    t.insert(toks(1, 2, 7), c.seq_pages(1))
    c.free(1)
    # shared [1,2] run became an inner node with two leaf children
    assert t.num_nodes == 3
    assert t.epoch > e0
    m, _ = t.match(toks(1, 2, 7))
    assert m == 24
    m, _ = t.match(toks(1, 2, 3))
    assert m == 24
    # only the divergent tail pinned new pages: 3 blocks + 1 block
    assert t.pinned_pages == 8
    c.refcount_sweep()


def test_insert_covered_prefix_pins_nothing():
    c = make_cache()
    t = PrefixTrie(c, block_tokens=8)
    fill(c, 0, 24)
    t.insert(toks(1, 2, 3), c.seq_pages(0))
    pinned_before = t.pinned_pages
    # same prefix again (e.g. a second same-template admission finishing)
    assert t.insert(toks(1, 2), c.seq_pages(0)[:4]) == 0
    assert t.pinned_pages == pinned_before
    c.free(0)
    c.refcount_sweep()


def test_eviction_is_lru_and_refcount_safe():
    c = make_cache()
    t = PrefixTrie(c, block_tokens=8)
    fill(c, 0, 16, seed=0)
    retained(c, t, 0, toks(1, 2))
    fill(c, 1, 16, seed=1)
    retained(c, t, 1, toks(5, 6))
    t.match(toks(1, 2))  # refresh [1,2]: [5,6] becomes LRU
    free0 = c.num_free_pages
    assert t.reclaim(1) == 4  # whole leaf [5,6] — the cold one
    assert c.num_free_pages == free0 + 4
    assert t.match(toks(5, 6), count=False) == (0, [])   # no stale match
    assert t.match(toks(1, 2), count=False)[0] == 16     # survivor intact
    c.refcount_sweep()


def test_reclaim_skips_pages_aliased_by_live_requests():
    c = make_cache()
    t = PrefixTrie(c, block_tokens=8)
    fill(c, 0, 16, seed=0)
    retained(c, t, 0, toks(1, 2))
    # a live request adopts the retained pages: ref > pin, not freeable
    c.adopt_pages(7, t.match(toks(1, 2), count=False)[1], 16)
    assert t.reclaim(4) == 0  # nothing freeable -> stops, frees nothing
    assert t.match(toks(1, 2), count=False)[0] == 16
    c.free(7)
    assert t.reclaim(4) == 4  # now it can
    c.refcount_sweep()


def test_retain_pages_budget_trims_lru():
    c = make_cache()
    t = PrefixTrie(c, block_tokens=8, retain_pages=4)
    fill(c, 0, 16, seed=0)
    retained(c, t, 0, toks(1, 2))
    assert t.pinned_pages == 4
    fill(c, 1, 16, seed=1)
    retained(c, t, 1, toks(5, 6))  # insert trims the older [1,2] leaf
    assert t.pinned_pages == 4
    assert t.match(toks(1, 2), count=False) == (0, [])
    assert t.match(toks(5, 6), count=False)[0] == 16
    c.refcount_sweep()


def test_pin_validates_free_and_unpinned_pages():
    c = make_cache()
    with pytest.raises(ValueError, match="free list"):
        c.pin_pages([0])
    fill(c, 0, 8)
    with pytest.raises(ValueError, match="retention pin"):
        c.unpin_pages(c.seq_pages(0))
    c.free(0)


def test_adopt_pages_requires_page_alignment_and_live_pages():
    c = make_cache()
    fill(c, 0, 8)
    pages = c.seq_pages(0)
    with pytest.raises(ValueError, match="page-aligned"):
        c.adopt_pages(1, pages, 7)
    c.free(0)
    with pytest.raises(ValueError, match="free"):
        c.adopt_pages(1, pages, 8)


# --------------------------------------------------------------------------- #
# property-style seeded churn
# --------------------------------------------------------------------------- #


def test_seeded_churn_refcounts_reconcile():
    """admit/finish(retain)/evict/fork/COW interleavings, swept each op."""
    rng = np.random.default_rng(42)
    c = make_cache(num_pages=48, page_size=4)
    t = PrefixTrie(c, block_tokens=8, retain_pages=24)
    templates = [toks(1, 2), toks(1, 2, 3), toks(5, 6), toks(1, 9)]
    live: dict[int, list[int]] = {}
    next_rid = 0
    for step in range(120):
        op = rng.integers(0, 5)
        if op == 0 and c.num_free_pages >= 6:  # admit (trie-style)
            prompt = list(templates[rng.integers(len(templates))])
            prompt.extend(int(x) for x in rng.integers(50, 60, size=4))
            matched, pages = t.match(prompt[: (len(prompt) // 8) * 8])
            rid = next_rid
            next_rid += 1
            if matched:
                c.adopt_pages(rid, pages, matched)
            else:
                c.alloc(rid)
            tail = rng.normal(size=(len(prompt) - matched, c.width))
            c.append(rid, jnp.asarray(tail, jnp.float32))
            live[rid] = prompt
        elif op == 1 and live:  # finish + retain
            rid = int(rng.choice(list(live)))
            retained(c, t, rid, live.pop(rid))
        elif op == 2:  # pool-pressure reclaim
            t.reclaim(int(rng.integers(1, 6)))
        elif op == 3 and live:  # fork + COW append (the PR 3 machinery)
            src = int(rng.choice(list(live)))
            rid = next_rid
            next_rid += 1
            c.fork(src, rid)
            c.append(rid, jnp.asarray(
                rng.normal(size=(3, c.width)), jnp.float32))
            live[rid] = list(live[src])  # same complete blocks
        elif op == 4 and live:  # plain finish without retention
            rid = int(rng.choice(list(live)))
            live.pop(rid)
            c.free(rid)
        sweep = c.refcount_sweep()  # raises on any inconsistency
        assert sweep["free_pages"] + sweep["live_pages"] \
            + sweep["retained_pages"] <= c.num_pages
    for rid in list(live):
        c.free(rid)
    t.clear()
    sweep = c.refcount_sweep()
    assert sweep["free_pages"] == c.num_pages
    assert t.pinned_pages == 0 and t.num_nodes == 0


# --------------------------------------------------------------------------- #
# nested (trie-topology) prefix scheduling
# --------------------------------------------------------------------------- #


def nested_family_cache(*, page=32, dk=64, seed=1):
    """root(2 blocks) shared by 6; inner(2 more blocks) shared by 0-3."""
    def rows(n, s):
        x = np.random.default_rng(s).normal(0, 0.3, (n, dk))
        return jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)

    kv = PagedKVCache(num_pages=96, page_size=page, width=dk,
                      dtype=jnp.float32)
    kv.alloc(0)
    kv.append(0, rows(128, seed))
    kv.append(0, rows(128, seed + 1))
    rids = [0]
    for r in range(1, 4):
        kv.fork(0, r, 256)
        rids.append(r)
    for r in range(4, 6):
        kv.fork(0, r, 128)
        rids.append(r)
    for r, n in zip(rids, [20, 55, 3, 0, 40, 9]):
        if n:
            kv.append(r, rows(n, 100 + r))
    return kv, rids


def test_nested_groups_follow_trie_topology():
    kv, rids = nested_family_cache()
    bt, kv_len = kv.block_table(rids)
    g = find_prefix_groups(bt, kv_len, page_size=32, block_k=64, nested=True)
    got = sorted(
        (int(g.group_start[i]), int(g.shared_blocks[i]),
         tuple(int(x) for x in g.group_member[i] if x >= 0))
        for i in range(g.num_groups)
    )
    # one group per trie node: root [0,2) x6 and inner [2,4) x4 — NOT a
    # single flat common-min group of 2 blocks
    assert got == [(0, 2, (0, 1, 2, 3, 4, 5)), (2, 4, (0, 1, 2, 3))]
    assert g.chain_of_req(0) == ((0, 0), (1, 0))
    assert g.chain_of_req(4) == ((0, 4),)
    # flat mode on the same tables: only the common-min run groups
    gf = find_prefix_groups(bt, kv_len, page_size=32, block_k=64)
    assert gf.num_groups == 1 and int(gf.shared_blocks[0]) == 2


def test_nested_dma_dedup_beats_flat():
    kv, rids = nested_family_cache()
    bt, kv_len = kv.block_table(rids)
    nested = build_prefix_schedule(kv_len, bt, page_size=32, block_k=64,
                                   nested=True)
    flat = build_prefix_schedule(kv_len, bt, page_size=32, block_k=64)
    an = prefix_queue_grid_items(nested, kv_len, 32)
    af = prefix_queue_grid_items(flat, kv_len, 32)
    # nested covers the inner run once for 4 members; flat re-reads it
    # per member through the suffix pass
    assert an["page_dmas"] < af["page_dmas"]
    assert an["unshared_prefix_page_dmas"] > af["unshared_prefix_page_dmas"]
    # suffixes start past the deepest covering group
    assert nested.start_blocks.tolist()[:4] == [4, 4, 4, 4]
    assert nested.start_blocks.tolist()[4:] == [2, 2]


@pytest.mark.parametrize("variant", ["base", "amla"])
def test_nested_schedule_numeric_parity(variant):
    kv, rids = nested_family_cache()
    bt, kv_len = kv.block_table(rids)
    ps = build_prefix_schedule(kv_len, bt, page_size=32, block_k=64,
                               nested=True)
    q = jnp.asarray(
        np.random.default_rng(50).normal(0, 0.3, (len(rids), 1, 4, 64)),
        jnp.bfloat16,
    ).astype(jnp.float32)
    kw = dict(d_v=32, variant=variant, scale=64**-0.5, block_k=64,
              num_splits=1, interpret=True)
    got = ops.mla_decode_paged(q, kv.pages, jnp.asarray(bt),
                               jnp.asarray(kv_len), schedule=ps, **kw)
    want = ops.mla_decode_paged(q, kv.pages, jnp.asarray(bt),
                                jnp.asarray(kv_len), **kw)
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-3


# --------------------------------------------------------------------------- #
# sharded routing: hit-length tiebreak
# --------------------------------------------------------------------------- #


def test_route_request_prefers_longer_prefix_hit_on_ties():
    # equal load + free: the hit decides
    assert route_request([4, 4], [10, 10], 3, shard_hit_pages=[0, 2]) == 1
    # load still dominates locality
    assert route_request([2, 8], [10, 10], 3, shard_hit_pages=[0, 9]) == 0
    # a big hit makes an otherwise-full shard eligible
    assert route_request([4, 4], [1, 10], 8, shard_hit_pages=[7, 0]) == 0
    # and without hits the old behavior holds (free-pages tiebreak)
    assert route_request([4, 4], [5, 9], 3) == 1


# --------------------------------------------------------------------------- #
# model-level: automatic admission parity + lifecycle
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_paged(model, params, **kw):
    kw.setdefault("num_pages", 96)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("interpret", True)
    return PagedServingSession(model, params, **kw)


def template_stream(seed, n, template_blocks=2):
    rng = np.random.default_rng(seed)
    template = rng.integers(
        2, CFG.vocab_size, size=template_blocks * BLOCK_K
    ).tolist()
    return [
        template + rng.integers(2, CFG.vocab_size, size=5 + i).tolist()
        for i in range(n)
    ]


def staggered_serve(model, params, prompts, **kw):
    """Waves of 2 admissions with overlapping lifetimes; returns
    (outputs, work_stats, sweep_report)."""
    sess = make_paged(model, params, **kw)
    outs, live = {}, []
    for wave in range(len(prompts) // 2):
        for j in range(2):
            rid = sess.add_request(prompts[wave * 2 + j])
            assert rid is not None
            live.append(rid)
        for _ in range(3):
            sess.step()
        if wave % 2 == 1:
            for r in live[:2]:
                outs[r] = sess.finish(r)
            live = live[2:]
    for _ in range(4):
        sess.step()
    for r in live:
        outs[r] = sess.finish(r)
    ws = sess.work_stats()
    return outs, ws, sess.close()


@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["fp32-smoke", "int8"])
def test_trie_greedy_parity_and_reuse(model_and_params, kv_dtype):
    model, params = model_and_params
    prompts = template_stream(7, 8)
    off, off_ws, _ = staggered_serve(model, params, prompts,
                                     kv_dtype=kv_dtype)
    on, on_ws, sweep = staggered_serve(model, params, prompts,
                                       prefix_cache="trie",
                                       kv_dtype=kv_dtype)
    assert on == off  # bit-identical greedy streams
    assert on_ws["trie_hits"] >= 6  # everyone after the first cold admit
    assert on_ws["prefix_tokens_reused"] >= 6 * 2 * BLOCK_K
    assert on_ws["page_dma_bytes"] < off_ws["page_dma_bytes"]
    assert sweep["free_pages"] == 96  # close() cleared every pin


def test_trie_parity_under_eviction_churn(model_and_params):
    """A pool too small to retain everything: by the third template the
    retained-pin budget exceeds free pages, forcing LRU reclaim of the
    coldest subtree mid-stream; outputs must still match trie-off."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    # three 2-block templates, visited twice each (cold + hit), so the
    # 12-page pool holds at most two retained subtrees (4 pages apiece)
    templates = [
        rng.integers(2, CFG.vocab_size, size=2 * BLOCK_K).tolist()
        for _ in range(3)
    ]
    prompts = []
    for i in range(6):
        base = templates[i // 2]
        prompts.append(base + rng.integers(
            2, CFG.vocab_size, size=4 + i).tolist())

    def serve(**kw):
        sess = make_paged(model, params, num_pages=12, **kw)
        outs = {}
        for p in prompts:  # strictly sequential: admit, decode, finish
            rid = sess.add_request(p)
            assert rid is not None, "reclaim must make room"
            for _ in range(4):
                sess.step()
            outs[rid] = sess.finish(rid)
        ws = sess.work_stats()
        sess.close()
        return outs, ws

    off, _ = serve()
    on, ws = serve(prefix_cache="trie")
    assert on == off
    assert ws["trie_evicted_pages"] > 0  # churn actually happened
    assert ws["trie_hits"] >= 3  # each template's second visit hits


def test_trie_rejects_misaligned_prefill_chunk(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="prefill_chunk"):
        make_paged(model, params, prefix_cache="trie", prefill_chunk=24)
    with pytest.raises(ValueError, match="cache policy"):
        make_paged(model, params, prefix_cache="lru")


def test_work_stats_keys_are_stable_with_trie_off(model_and_params):
    model, params = model_and_params
    sess = make_paged(model, params)
    ws = sess.work_stats()
    for key in ("live_pages", "retained_pages", "trie_hits", "trie_misses",
                "trie_admissions", "trie_hit_rate", "prefix_tokens_reused",
                "prefix_tokens_reused_per_admission", "trie_evicted_pages"):
        assert ws[key] == 0
    sess.close()


def test_reclaim_retained_noop_with_trie_off(model_and_params):
    model, params = model_and_params
    sess = make_paged(model, params)
    assert sess.reclaim_retained(8) == 0
    sess.close()
