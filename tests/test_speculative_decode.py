"""Speculative multi-token decode: rollback x sharing, drafting, parity.

Three layers of acceptance for the draft-verify loop:

* **cache rollback** — ``truncate(rid, n)`` is the bookkeeping inverse of
  an append: only pages wholly past the new boundary are released, and
  only this request's *reference* — pages aliased by fork/COW siblings
  survive with the sibling's data untouched, the COW boundary page stays
  with its owner, and double-truncate is a no-op.  Seeded
  admit/fork/speculate/evict sweeps pin refcount conservation and exact
  content (tests/test_quantized_cache.py style, hypothesis-free).
* **ops surface** — ``mla_decode_paged(q_positions=...)`` fails fast on
  shape/monotonicity/bounds violations and on the padded scheduler, and a
  fused k-row call is row-for-row the sequence of 1-row calls it replaces.
* **serving parity** — ``speculate="ngram"`` emits token-for-token the
  ``speculate="off"`` greedy stream (bf16-path and int8 cache, single-host
  and sharded), with honest work accounting (every verify row counted;
  at least one accepted token per request-step by construction).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models.model_zoo import build_model
from repro.runtime.kv_cache import PagedKVCache, pool_shape
from repro.runtime.serve_loop import (
    NGramProposer,
    PagedServingSession,
    ShardedPagedServingSession,
)

CFG = get_config("deepseek-v2-mla", smoke=True)
PAGE, BLOCK_K, CHUNK = 16, 32, 16


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def rows(n, width, seed, scale=0.3):
    return np.random.default_rng(seed).normal(0, scale, (n, width)).astype(
        np.float32
    )


def make_cache(num_pages=8, page_size=4, width=16):
    return PagedKVCache(
        num_pages=num_pages, page_size=page_size, width=width,
        dtype=jnp.float32,
    )


def prompts_for(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, CFG.vocab_size, size=n).tolist() for n in lengths]


# --------------------------------------------------------------------------- #
# truncate: rollback bookkeeping
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("length,new_len", [
    (10, 10), (10, 8), (10, 4), (10, 3), (9, 0), (4, 1),
])
def test_truncate_frees_tail_pages_and_keeps_prefix(length, new_len):
    kv = make_cache(num_pages=8, page_size=4)
    data = rows(length, 16, length * 31 + new_len)
    kv.alloc(0)
    kv.append(0, data)
    kv.truncate(0, new_len)
    keep = -(-new_len // 4)
    assert kv.seq_len(0) == new_len
    assert len(kv.seq_pages(0)) == keep
    assert kv.num_free_pages == 8 - keep
    if new_len:
        np.testing.assert_array_equal(
            np.asarray(kv.gather_contiguous(0)), data[:new_len]
        )


def test_truncate_double_truncate_is_noop():
    kv = make_cache()
    kv.alloc(0)
    kv.append(0, rows(10, 16, 0))
    kv.truncate(0, 10)  # truncate-to-current: nothing to do
    assert kv.seq_len(0) == 10 and len(kv.seq_pages(0)) == 3
    kv.truncate(0, 7)
    state = (kv.seq_len(0), list(kv.seq_pages(0)), kv.num_free_pages)
    kv.truncate(0, 7)  # double truncate: exact same state
    assert (kv.seq_len(0), list(kv.seq_pages(0)), kv.num_free_pages) == state


def test_truncate_validation():
    kv = make_cache()
    with pytest.raises(KeyError):
        kv.truncate(99, 0)
    kv.alloc(0)
    kv.append(0, rows(5, 16, 1))
    with pytest.raises(ValueError, match="cannot extend"):
        kv.truncate(0, 6)
    with pytest.raises(ValueError, match="must be in"):
        kv.truncate(0, -1)


def test_truncate_forked_child_frees_only_unshared_tail():
    """Rolling back a child's speculation releases its own fresh tail page
    but never a page the parent still references; the COW boundary copy
    (holding the child's live prefix rows) survives the rejection."""
    kv = make_cache(num_pages=10, page_size=4)
    parent_rows = rows(10, 16, 2)
    kv.alloc(0)
    kv.append(0, parent_rows)  # pages [A, B, C]; C half full
    kv.fork(0, 1)
    ppages = list(kv.seq_pages(0))
    assert all(kv.page_refcount(p) == 2 for p in ppages)
    kv.append(1, rows(6, 16, 3))  # COW C -> C', then a fresh tail page D
    cpages = list(kv.seq_pages(1))
    assert cpages[:2] == ppages[:2] and cpages[2] != ppages[2]
    free_before = kv.num_free_pages
    kv.truncate(1, 10)  # reject the whole speculation
    assert list(kv.seq_pages(1)) == cpages[:3]  # C' survives: rows 8-9 live
    assert kv.page_refcount(ppages[0]) == 2  # still shared
    assert kv.page_refcount(ppages[2]) == 1  # parent's own boundary page
    assert kv.page_refcount(cpages[2]) == 1  # child's COW copy
    assert kv.num_free_pages == free_before + 1  # only D freed
    np.testing.assert_array_equal(
        np.asarray(kv.gather_contiguous(0)), parent_rows
    )
    np.testing.assert_array_equal(
        np.asarray(kv.gather_contiguous(1)), parent_rows
    )


def test_truncate_into_shared_prefix_releases_refcounts_not_pages():
    kv = make_cache(num_pages=8, page_size=4)
    data = rows(8, 16, 4)
    kv.alloc(0)
    kv.append(0, data)  # pages [A, B], both exactly full
    kv.fork(0, 1)
    a, b = kv.seq_pages(0)
    kv.truncate(1, 4)  # child rolls back INTO the shared region
    assert kv.seq_pages(1) == [a]
    assert kv.page_refcount(a) == 2
    assert kv.page_refcount(b) == 1  # parent-only now, but NOT freed
    np.testing.assert_array_equal(np.asarray(kv.gather_contiguous(0)), data)
    np.testing.assert_array_equal(
        np.asarray(kv.gather_contiguous(1)), data[:4]
    )


@pytest.mark.parametrize("seed", range(4))
def test_truncate_churn_sweep_refcounts_and_content(seed):
    """Seeded admit/fork/speculate(append+truncate)/evict churn: refcounts
    stay conserved (one ref per holder per page) and every live request's
    rows match a host-side mirror exactly, every step."""
    rng = np.random.default_rng(seed)
    kv = make_cache(num_pages=16, page_size=4)
    live, mirror, nid = [], {}, 0
    for step in range(40):
        op = int(rng.integers(0, 4))
        if op == 0 or not live:  # admit
            n = int(rng.integers(1, 9))
            if kv.has_room(None, n):
                data = rows(n, 16, 1000 + 7 * seed + step)
                kv.alloc(nid)
                kv.append(nid, data)
                mirror[nid] = data
                live.append(nid)
                nid += 1
        elif op == 1:  # fork at full history
            src = int(rng.choice(live))
            kv.fork(src, nid)
            mirror[nid] = mirror[src].copy()
            live.append(nid)
            nid += 1
        elif op == 2:  # speculate: append k rows, accept a prefix
            r = int(rng.choice(live))
            k = int(rng.integers(1, 5))
            if kv.has_room(r, k):
                spec = rows(k, 16, 2000 + 7 * seed + step)
                kv.append(r, spec)
                accept = int(rng.integers(0, k + 1))
                kv.truncate(r, len(mirror[r]) + accept)
                mirror[r] = np.concatenate([mirror[r], spec[:accept]])
        else:  # evict
            r = live.pop(int(rng.integers(0, len(live))))
            kv.free(r)
            mirror.pop(r)
        total_refs = sum(kv.page_refcount(p) for p in range(16))
        assert total_refs == sum(len(kv.seq_pages(r)) for r in live)
        held = int(sum(kv.page_refcount(p) > 0 for p in range(16)))
        assert kv.num_free_pages == 16 - held
    for r in live:
        np.testing.assert_array_equal(
            np.asarray(kv.gather_contiguous(r)), mirror[r]
        )


# --------------------------------------------------------------------------- #
# NGramProposer
# --------------------------------------------------------------------------- #


def test_ngram_proposes_cycle_continuation():
    hist = [5, 1, 2, 3, 1, 2, 3, 1, 2]
    assert NGramProposer().propose(hist, 3) == [3, 1, 2]


def test_ngram_prefers_full_continuation_over_recent_short_one():
    # suffix [1, 2] matches most recently at i=1 with a short continuation;
    # the padded best is [3, 1, 2] + its own last token
    assert NGramProposer().propose([9, 1, 2, 3, 1, 2], 4) == [3, 1, 2, 2]


def test_ngram_fallback_repeats_last_token():
    assert NGramProposer().propose([7, 8, 9], 4) == [9, 9, 9, 9]
    assert NGramProposer().propose([], 2) == [0, 0]


def test_ngram_validation():
    with pytest.raises(ValueError, match="min_n"):
        NGramProposer(max_n=0)
    with pytest.raises(ValueError, match="k >= 1"):
        NGramProposer().propose([1, 2], 0)


# --------------------------------------------------------------------------- #
# ops.mla_decode_paged multi-row surface
# --------------------------------------------------------------------------- #


def _spec_kernel_args(kv_lens=(21, 13), sq=3, hq=2, dk=32, page=8):
    b = len(kv_lens)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 0.3, (b, sq, hq, dk)), jnp.float32)
    c = rng.normal(0, 0.3, (b, max(kv_lens), dk)).astype(np.float32)
    num_pages = sum(-(-l // page) for l in kv_lens) + 1
    w = max(-(-l // page) for l in kv_lens)
    pool = np.zeros(pool_shape(num_pages, page, dk), np.float32)
    bt = np.zeros((b, w), np.int32)
    nxt = 0
    for bb, l in enumerate(kv_lens):
        for j in range(-(-l // page)):
            hi = min((j + 1) * page, l)
            pool[nxt, : hi - j * page, :dk] = c[bb, j * page : hi]
            bt[bb, j] = nxt
            nxt += 1
    return q, jnp.asarray(pool), jnp.asarray(bt), jnp.asarray(
        kv_lens, jnp.int32
    )


def test_q_positions_validation_fails_fast():
    q, pool, bt, kv_len = _spec_kernel_args()
    kw = dict(d_v=16, scale=0.2, interpret=True)
    good = jnp.asarray(
        np.stack([np.arange(l - 3, l) for l in (21, 13)]), jnp.int32
    )
    with pytest.raises(NotImplementedError, match="scheduler='queue'"):
        ops.mla_decode_paged(
            q, pool, bt, kv_len, q_positions=good, scheduler="padded", **kw
        )
    with pytest.raises(ValueError, match="not both"):
        ops.mla_decode_paged(
            q, pool, bt, kv_len, q_positions=good,
            q_offset=jnp.zeros((2,), jnp.int32), **kw
        )
    with pytest.raises(ValueError, match="causal=False"):
        ops.mla_decode_paged(
            q, pool, bt, kv_len, q_positions=good, causal=False, **kw
        )
    with pytest.raises(ValueError, match="q_positions must be"):
        ops.mla_decode_paged(
            q, pool, bt, kv_len, q_positions=good[:, :2], **kw
        )
    with pytest.raises(ValueError, match="non-negative"):
        ops.mla_decode_paged(
            q, pool, bt, kv_len,
            q_positions=jnp.asarray([[-1, 1, 2], [0, 1, 2]], jnp.int32), **kw
        )
    with pytest.raises(ValueError, match="strictly increasing"):
        ops.mla_decode_paged(
            q, pool, bt, kv_len,
            q_positions=jnp.asarray([[18, 19, 20], [5, 5, 6]], jnp.int32),
            **kw
        )
    with pytest.raises(ValueError, match="append the k rows"):
        ops.mla_decode_paged(
            q, pool, bt, kv_len,
            q_positions=jnp.asarray([[18, 19, 21], [10, 11, 12]], jnp.int32),
            **kw
        )


def test_q_positions_equal_to_derived_ramp_is_bitwise_identical():
    q, pool, bt, kv_len = _spec_kernel_args()
    kw = dict(d_v=16, scale=0.2, interpret=True)
    qp = jnp.asarray(
        np.stack([np.arange(l - 3, l) for l in (21, 13)]), jnp.int32
    )
    a = ops.mla_decode_paged(q, pool, bt, kv_len, **kw)
    z = ops.mla_decode_paged(q, pool, bt, kv_len, q_positions=qp, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(z))


def test_fused_k_rows_match_sequential_single_rows():
    """Row j of a fused k-row verify call equals the 1-row decode it
    replaces (same query, kv_len trimmed to pos+1) — the invariant that
    makes accepted speculative logits exact, not approximate."""
    q, pool, bt, kv_len = _spec_kernel_args(kv_lens=(21,), sq=3)
    kw = dict(d_v=16, scale=0.2, interpret=True)
    qp = jnp.asarray([[18, 19, 20]], jnp.int32)
    fused = ops.mla_decode_paged(q, pool, bt, kv_len, q_positions=qp, **kw)
    for j, pos in enumerate((18, 19, 20)):
        one = ops.mla_decode_paged(
            q[:, j : j + 1], pool, bt,
            jnp.asarray([pos + 1], jnp.int32), **kw
        )
        np.testing.assert_allclose(
            np.asarray(fused[:, j]), np.asarray(one[:, 0]), atol=1e-5
        )


# --------------------------------------------------------------------------- #
# serving parity: ngram vs off, int8, sharded, churn
# --------------------------------------------------------------------------- #


def _mk_session(model, params, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("interpret", True)
    return PagedServingSession(model, params, **kw)


def _run_spec_to_match(spec, rspec, off, roff):
    """Step the speculative session until every request has at least the
    off twin's output length; returns the step count."""
    targets = [len(off.outputs[r]) for r in roff]
    it = 0
    while it < 4 * max(targets) and any(
        len(spec.outputs[r]) < t for r, t in zip(rspec, targets)
    ):
        spec.step()
        it += 1
    return it


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_speculative_greedy_parity_vs_off(model_and_params, kv_dtype):
    """The ngram stream is an exact prefix-extension of the off stream —
    drafting changes cost per token, never the tokens — and the work
    counters stay honest (all verify rows counted, >= 1 accepted/step)."""
    model, params = model_and_params
    prompts = prompts_for(0, (8, 12))
    kw = {} if kv_dtype is None else {"kv_dtype": kv_dtype}
    off = _mk_session(model, params, **kw)
    roff = [off.add_request(p) for p in prompts]
    for _ in range(12):
        off.step()
    spec = _mk_session(model, params, speculate="ngram", draft_k=4, **kw)
    rspec = [spec.add_request(p) for p in prompts]
    _run_spec_to_match(spec, rspec, off, roff)
    for ro, rs in zip(roff, rspec):
        want = off.outputs[ro]
        assert spec.outputs[rs][: len(want)] == want
    ws = spec.work_stats()
    assert ws["query_rows"] == 4 * ws["request_steps"]
    assert ws["accepted_tokens"] >= ws["request_steps"]
    assert ws["accepted_tokens_per_step"] >= 1.0
    assert off.work_stats()["accepted_tokens_per_step"] == 1.0


def test_speculative_sharded_matches_single_host(model_and_params):
    """Speculation is shard-local: the sharded session's greedy stream is
    bit-identical to one single-host session holding the same requests,
    step for step, and the aggregate stats recompute ratios from summed
    raw counters."""
    model, params = model_and_params
    prompts = prompts_for(3, (8, 10, 12))
    single = _mk_session(model, params, speculate="ngram", draft_k=3)
    sharded = ShardedPagedServingSession(
        model, params, num_pages=64, shards=2, page_size=PAGE,
        block_k=BLOCK_K, prefill_chunk=CHUNK, speculate="ngram", draft_k=3,
        interpret=True,
    )
    r_single = [single.add_request(p) for p in prompts]
    r_sharded = [sharded.add_request(p) for p in prompts]
    for _ in range(8):
        single.step()
        sharded.step()
    for a, b in zip(r_single, r_sharded):
        assert single.outputs[a] == sharded.outputs[b]
    agg = sharded.work_stats()
    assert agg["accepted_tokens"] == sum(
        st["accepted_tokens"] for st in agg["per_shard"]
    )
    assert agg["accepted_tokens_per_step"] == agg["accepted_tokens"] / max(
        agg["request_steps"], 1
    )
    assert agg["accepted_tokens_per_step"] >= 1.0


def test_speculative_churn_fork_admit_evict(model_and_params):
    """Admit/fork/speculate/evict churn under speculation: forked twins
    stay token-identical while live, the lone surviving stream matches a
    plain off session, and retiring everything returns every page."""
    model, params = model_and_params
    prompts = prompts_for(5, (9, 14))
    sess = _mk_session(model, params, num_pages=48, speculate="ngram",
                       draft_k=3)
    r0 = sess.add_request(prompts[0])
    sess.step()
    sess.step()
    child = sess.fork(r0)
    r1 = sess.add_request(prompts[1])
    for _ in range(3):
        sess.step()
    # greedy twins: same history => same drafts => same accepted tokens
    assert sess.outputs[child] == sess.outputs[r0]
    kid = sess.admit_with_prefix(r1, prompts_for(6, (4,))[0])
    sess.step()
    sess.finish(child)
    sess.finish(r1)
    sess.step()
    # r0's stream must match a non-speculative session serving it alone —
    # batch-mates, forks, and rollbacks never touch another request's math
    off = _mk_session(model, params, num_pages=48)
    o0 = off.add_request(prompts[0])
    while len(off.outputs[o0]) < len(sess.outputs[r0]):
        off.step()
    n = len(sess.outputs[r0])
    assert off.outputs[o0][:n] == sess.outputs[r0]
    sess.finish(r0)
    sess.finish(kid)
    assert sess.cache.num_free_pages == 48
    assert sess.work_stats()["accepted_tokens_per_step"] >= 1.0
