"""Shared-prefix decode parity + API tests (interpret mode).

Acceptance: |prefix-shared − unshared| <= 2e-3 FP32 across group sizes
{1, 4, 16} with ragged suffixes, including the fork/copy-on-write boundary
page.  The group-batched prefix pass must be *numerically* a pure
reorganization of work: same pages, same masks, same AMLA state machine —
only the partition of blocks over kernel invocations changes, and the
LSE-weighted combine makes that partition exact.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_schedule import (
    build_prefix_schedule,
    prefix_queue_grid_items,
)
from repro.runtime.kv_cache import PagedKVCache, pool_shape
from repro.runtime.serve_loop import PagedDecodeSession

INTERP = dict(interpret=True)
PARITY_ATOL = 2e-3


def bf16ish(shape, seed, scale=0.3):
    x = np.random.default_rng(seed).normal(0, scale, shape)
    return jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)


def fork_family(
    *, group_size, prefix_len, suffix_lens, page, dk, num_pages, seed=0
):
    """A fork family in a real PagedKVCache: parent prefix + ragged suffixes.

    ``prefix_len`` need not be page-aligned — the fork boundary page then
    exercises copy-on-write when each member appends its own suffix.
    Returns ``(kv, rids)``; member 0 is the parent (its suffix rides on the
    original pages past ``prefix_len``).
    """
    assert len(suffix_lens) == group_size
    kv = PagedKVCache(num_pages=num_pages, page_size=page, width=dk,
                      dtype=jnp.float32)
    kv.alloc(0)
    kv.append(0, bf16ish((prefix_len, dk), seed))
    rids = [0]
    for i in range(1, group_size):
        kv.fork(0, i, prefix_len)
        rids.append(i)
    for i, n in enumerate(suffix_lens):
        if n:
            kv.append(rids[i], bf16ish((n, dk), seed + 100 + i))
    return kv, rids


def both_paths(kv, rids, *, hq, dv, block_k, num_splits=1, variant="amla",
               seed=50):
    dk = kv.width
    scale = 1.0 / dk**0.5
    bt, kv_len = kv.block_table(rids)
    q = bf16ish((len(rids), 1, hq, dk), seed)
    kw = dict(
        d_v=dv, variant=variant, scale=scale, block_k=block_k,
        num_splits=num_splits, **INTERP,
    )
    shared = ops.mla_decode_paged(
        q, kv.pages, jnp.asarray(bt), jnp.asarray(kv_len),
        prefix_sharing=True, **kw,
    )
    unshared = ops.mla_decode_paged(
        q, kv.pages, jnp.asarray(bt), jnp.asarray(kv_len), **kw,
    )
    # contiguous oracle on each request's reassembled history
    maxlen = int(max(int(l) for l in kv_len))
    c = np.zeros((len(rids), maxlen, dk), np.float32)
    for i, r in enumerate(rids):
        c[i, : kv.seq_len(r)] = np.asarray(kv.gather_contiguous(r))
    contig = ops.mla_decode(
        q, jnp.asarray(c), d_v=dv, variant=variant, scale=scale,
        kv_len=jnp.asarray(kv_len), **INTERP,
    )
    return shared, unshared, contig


@pytest.mark.parametrize("variant", ["base", "amla"])
@pytest.mark.parametrize(
    "group_size,prefix_len,suffix_lens",
    [
        # group 1: no grouping possible — the path must degenerate cleanly
        pytest.param(1, 100, [37], id="g1"),
        # group 4: ragged suffixes incl. an empty one; prefix NOT page-aligned
        pytest.param(4, 135, [20, 55, 3, 0], id="g4-ragged-cow"),
        # group 16: the n-best / system-prompt shape
        pytest.param(16, 130, [int(3 + 7 * i) % 60 for i in range(16)],
                     id="g16-ragged"),
    ],
)
def test_prefix_shared_matches_unshared_and_contiguous(
    variant, group_size, prefix_len, suffix_lens
):
    page, dk, dv, hq, block_k = 32, 64, 32, 4, 64
    kv, rids = fork_family(
        group_size=group_size, prefix_len=prefix_len,
        suffix_lens=suffix_lens, page=page, dk=dk,
        num_pages=2 * (group_size + 1) + prefix_len // page + 2
        + sum(-(-n // page) for n in suffix_lens),
    )
    shared, unshared, contig = both_paths(
        kv, rids, hq=hq, dv=dv, block_k=block_k, variant=variant,
    )
    assert float(jnp.max(jnp.abs(shared - unshared))) <= PARITY_ATOL
    assert float(jnp.max(jnp.abs(shared - contig))) <= PARITY_ATOL


def test_prefix_sharing_with_split_suffixes():
    """Long ragged suffixes split flash-decoding style combine with the
    group prefix partial in one heterogeneous merge."""
    page, dk, dv, hq, block_k = 16, 64, 32, 2, 32
    kv, rids = fork_family(
        group_size=4, prefix_len=3 * 32 + 5, suffix_lens=[200, 90, 17, 0],
        page=page, dk=dk, num_pages=64,
    )
    shared, unshared, contig = both_paths(
        kv, rids, hq=hq, dv=dv, block_k=block_k, num_splits=3,
    )
    assert float(jnp.max(jnp.abs(shared - unshared))) <= PARITY_ATOL
    assert float(jnp.max(jnp.abs(shared - contig))) <= PARITY_ATOL


def test_mixed_families_and_loners():
    """Two independent fork families + ungrouped requests in one batch."""
    page, dk, dv, hq, block_k = 16, 64, 32, 2, 32
    kv = PagedKVCache(num_pages=96, page_size=page, width=dk,
                      dtype=jnp.float32)
    # family A: rids 0..2 share 70 rows
    kv.alloc(0); kv.append(0, bf16ish((70, dk), 1))
    kv.fork(0, 1, 70); kv.fork(0, 2, 70)
    # family B: rids 3..4 share 40 rows
    kv.alloc(3); kv.append(3, bf16ish((40, dk), 2))
    kv.fork(3, 4, 40)
    # loners
    kv.alloc(5); kv.append(5, bf16ish((90, dk), 3))
    kv.alloc(6)  # empty live slot
    for rid, n in [(0, 11), (1, 0), (2, 40), (3, 9), (4, 33)]:
        if n:
            kv.append(rid, bf16ish((n, dk), 10 + rid))
    rids = [0, 1, 2, 3, 4, 5, 6]
    bt, kv_len = kv.block_table(rids)
    ps = build_prefix_schedule(
        kv_len, bt, page_size=page, block_k=block_k
    )
    assert ps.num_groups == 2
    shared, unshared, contig = both_paths(
        kv, rids, hq=hq, dv=dv, block_k=block_k,
    )
    assert float(jnp.max(jnp.abs(shared - unshared))) <= PARITY_ATOL
    assert float(jnp.max(jnp.abs(shared - contig))) <= PARITY_ATOL
    # the empty slot stays exactly zero
    assert np.abs(np.asarray(shared[6])).max() == 0.0


def test_member_entirely_inside_shared_prefix():
    """A freshly-forked member (kv_len == shared_len, block-aligned) has
    ZERO suffix blocks — its only partial is the group prefix one."""
    page, dk, dv, hq, block_k = 16, 64, 32, 2, 32
    kv, rids = fork_family(
        group_size=3, prefix_len=2 * block_k, suffix_lens=[10, 0, 0],
        page=page, dk=dk, num_pages=32,
    )
    bt, kv_len = kv.block_table(rids)
    ps = build_prefix_schedule(kv_len, bt, page_size=page, block_k=block_k)
    assert ps.suffix.n_splits.tolist() == [1, 0, 0]
    _, n_live = ps.hetero_dest_tables()
    assert n_live.tolist() == [2, 1, 1]
    shared, unshared, contig = both_paths(
        kv, rids, hq=hq, dv=dv, block_k=block_k,
    )
    assert float(jnp.max(jnp.abs(shared - unshared))) <= PARITY_ATOL
    assert float(jnp.max(jnp.abs(shared - contig))) <= PARITY_ATOL


def test_prefix_dma_dedup_is_group_sized():
    """Accounting acceptance: shared-prefix page DMAs drop ~G x at group
    size G (the whole point: decode MLA is bandwidth-bound)."""
    page, block_k = 16, 32
    for group_size in (4, 16):
        kv, rids = fork_family(
            group_size=group_size, prefix_len=4 * block_k,
            suffix_lens=[5 * (i % 3) for i in range(group_size)],
            page=page, dk=64, num_pages=32 + group_size * 2,
        )
        bt, kv_len = kv.block_table(rids)
        ps = build_prefix_schedule(kv_len, bt, page_size=page,
                                   block_k=block_k)
        acc = prefix_queue_grid_items(ps, kv_len, page)
        ratio = acc["unshared_prefix_page_dmas"] / acc["prefix_page_dmas"]
        assert abs(ratio - group_size) / group_size <= 0.10


# --------------------------------------------------------------------------- #
# session-level fork / admit_with_prefix
# --------------------------------------------------------------------------- #


def session_oracle_check(sess, outputs, queries, variant="amla"):
    for rid, got in outputs.items():
        c = sess.kv.gather_contiguous(rid)[None]
        want = ops.mla_decode(
            jnp.asarray(queries[rid])[None, None], c, d_v=sess.d_v,
            variant=variant, scale=sess.scale,
            kv_len=jnp.asarray([c.shape[1]], jnp.int32), **INTERP,
        )[0, 0]
        assert float(jnp.max(jnp.abs(got - want))) <= PARITY_ATOL, rid


def test_session_fork_and_shared_prefix_decode():
    d_k, d_v, g, page = 64, 32, 2, 16
    sess = PagedDecodeSession(
        num_pages=48, page_size=page, d_k=d_k, d_v=d_v,
        scale=d_k**-0.5, interpret=True, dtype=jnp.float32,
        block_k=32, prefix_sharing=True,
    )
    lat = lambda n, s: np.asarray(bf16ish((n, d_k), s))
    parent = sess.admit(lat(70, 1))
    kids = [sess.admit_with_prefix(parent, lat(n, 10 + n)) for n in (5, 12, 0)]
    assert all(k is not None for k in kids)
    assert sess.kv.num_aliased_pages() > 0

    queries = {r: lat(g, 30 + r) for r in [parent] + kids}
    out = sess.step(queries, {r: lat(1, 60 + r)[0] for r in queries})
    assert set(out) == set(queries)
    session_oracle_check(sess, out, queries)
    # decode steps keep reusing the memoized prefix schedule
    for _ in range(2):
        out = sess.step(queries, {r: lat(1, 70 + r)[0] for r in queries})
    session_oracle_check(sess, out, queries)
    assert sess.scheduler_stats["hits"] >= 2

    # evicting the PARENT must not disturb the children (refcounts hold
    # their shared pages) — and the schedule must rebuild, not go stale
    rebuilds = sess.scheduler_stats["rebuilds"]
    sess.evict(parent)
    queries = {r: lat(g, 80 + r) for r in kids}
    out = sess.step(queries, {r: lat(1, 90 + r)[0] for r in queries})
    session_oracle_check(sess, out, queries)
    assert sess.scheduler_stats["rebuilds"] > rebuilds


def test_session_admit_with_prefix_pool_pressure():
    d_k, page = 16, 4
    sess = PagedDecodeSession(
        num_pages=6, page_size=page, d_k=d_k, d_v=8, scale=0.25,
        interpret=True, dtype=jnp.float32, prefix_sharing=True,
    )
    lat = lambda n: np.ones((n, d_k), np.float32)
    parent = sess.admit(lat(18))  # 5 pages: 1 free
    # suffix needs COW (boundary page shared) + growth: 2 pages > 1 free
    assert sess.admit_with_prefix(parent, lat(3)) is None
    assert sess.active == [parent]  # nothing half-admitted
    # an empty-suffix fork is free (pure aliasing)
    kid = sess.admit_with_prefix(parent, np.zeros((0, d_k), np.float32))
    assert kid is not None and sess.kv.seq_len(kid) == 18


def test_session_admit_with_prefix_accepts_single_1d_row():
    """A bare (d_k,) suffix row normalizes like step()'s single-token path:
    it must count as ONE row for admission control (not d_k rows) and land
    as the child's first private token — the un-normalized shape previously
    blew past has_room and then failed the append shape check."""
    d_k, page = 16, 4
    sess = PagedDecodeSession(
        num_pages=8, page_size=page, d_k=d_k, d_v=8, scale=0.25,
        interpret=True, dtype=jnp.float32,
    )
    parent = sess.admit(np.ones((10, d_k), np.float32))
    row = np.full((d_k,), 2.0, np.float32)  # 1-D, as step() also accepts
    kid = sess.admit_with_prefix(parent, row)
    assert kid is not None
    assert sess.kv.seq_len(kid) == 11
    got = np.asarray(sess.kv.gather_contiguous(kid))
    np.testing.assert_array_equal(got[-1], row)
    np.testing.assert_array_equal(got[:10], np.ones((10, d_k)))
    # parity with the 2-D spelling of the same suffix
    kid2 = sess.admit_with_prefix(parent, row[None])
    np.testing.assert_array_equal(
        np.asarray(sess.kv.gather_contiguous(kid2)), got
    )
    # a 0-length 1-D array still means "empty suffix" (pure aliasing fork)
    empty = sess.admit_with_prefix(parent, np.zeros((0,), np.float32))
    assert empty is not None and sess.kv.seq_len(empty) == 10


def test_session_fork_rejects_dead_parent():
    sess = PagedDecodeSession(
        num_pages=4, page_size=4, d_k=16, d_v=8, scale=0.25,
        interpret=True, dtype=jnp.float32,
    )
    rid = sess.admit(np.ones((4, 16), np.float32))
    sess.evict(rid)
    with pytest.raises(KeyError):
        sess.fork(rid)


# --------------------------------------------------------------------------- #
# ops validation (fail fast, actionable)
# --------------------------------------------------------------------------- #


def test_ops_validates_block_k_multiple_of_page():
    q = bf16ish((1, 1, 2, 64), 1)
    pool = bf16ish(pool_shape(4, 32, 64), 2)
    bt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="multiple of the pool's page_size"):
        ops.mla_decode_paged(
            q, pool, bt, jnp.asarray([40], jnp.int32), d_v=32, scale=0.1,
            block_k=48, **INTERP,
        )


def test_ops_validates_table_width_covers_kv_len():
    q = bf16ish((1, 1, 2, 64), 1)
    pool = bf16ish(pool_shape(4, 32, 64), 2)
    bt = jnp.zeros((1, 2), jnp.int32)  # reach: 64 rows
    with pytest.raises(ValueError, match="exceeds the block table's reach"):
        ops.mla_decode_paged(
            q, pool, bt, jnp.asarray([65], jnp.int32), d_v=32, scale=0.1,
            **INTERP,
        )


def test_ops_validates_table_shape_and_widths():
    q = bf16ish((2, 1, 2, 64), 1)
    pool = bf16ish(pool_shape(4, 32, 64), 2)
    with pytest.raises(ValueError, match="block_tables must be"):
        ops.mla_decode_paged(
            q, pool, jnp.zeros((1, 2), jnp.int32),
            jnp.asarray([8, 8], jnp.int32), d_v=32, scale=0.1, **INTERP,
        )
    with pytest.raises(ValueError, match="share D_k"):
        ops.mla_decode_paged(
            bf16ish((1, 1, 2, 192), 3), pool, jnp.zeros((1, 2), jnp.int32),
            jnp.asarray([8], jnp.int32), d_v=32, scale=0.1, **INTERP,
        )


def test_ops_rejects_mismatched_schedule_types():
    from repro.kernels.decode_schedule import build_schedule

    q = bf16ish((1, 1, 2, 64), 1)
    pool = bf16ish(pool_shape(4, 32, 64), 2)
    bt = jnp.zeros((1, 4), jnp.int32)
    plain = build_schedule([40], block_k=32)
    with pytest.raises(ValueError, match="PrefixSchedule"):
        ops.mla_decode_paged(
            q, pool, bt, jnp.asarray([40], jnp.int32), d_v=32, scale=0.1,
            block_k=32, schedule=plain, prefix_sharing=True, **INTERP,
        )
