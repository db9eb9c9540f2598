"""Parity tests for the paged MLA decode kernel (interpret mode).

The paged path (kernels.mla_decode_paged + runtime.kv_cache) must match the
contiguous kernel (kernels.mla_decode) and the pure-jnp oracle (kernels.ref)
to FP32 tolerance across ragged kv_len, non-multiple-of-page lengths, and
fragmented (shuffled) block tables.  Acceptance bound: max |paged − contig|
<= 2e-3 in FP32 for both variants.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.runtime.kv_cache import OutOfPagesError, PagedKVCache, pool_shape
from repro.runtime.serve_loop import PagedDecodeSession

INTERP = dict(interpret=True)
PARITY_ATOL = 2e-3


def bf16ish(shape, seed, scale=0.3):
    x = np.random.default_rng(seed).normal(0, scale, shape)
    return jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)


def paginate(c, kv_lens, page, *, num_pages, shuffle_seed=None):
    """Scatter contiguous (B, S, Dk) latents into a page pool + block tables.

    With ``shuffle_seed`` the physical placement is a random permutation —
    a maximally fragmented pool.
    """
    b, s, dk = c.shape
    w = max(-(-int(l) // page) for l in kv_lens)
    pool = np.zeros(pool_shape(num_pages, page, dk), np.float32)
    bt = np.zeros((b, w), np.int32)
    order = np.arange(num_pages)
    if shuffle_seed is not None:
        order = np.random.default_rng(shuffle_seed).permutation(num_pages)
    nxt = 0
    for bb in range(b):
        for j in range(-(-int(kv_lens[bb]) // page)):
            pid = int(order[nxt])
            nxt += 1
            lo = j * page
            hi = min(lo + page, int(kv_lens[bb]))
            pool[pid, : hi - lo, :dk] = np.asarray(c[bb, lo:hi])
            bt[bb, j] = pid
    return jnp.asarray(pool), jnp.asarray(bt)


GEOMETRIES = [
    # (b, hq, dk, dv, page, kv_lens)  — all kv_lens non-multiples of page
    pytest.param(1, 4, 128, 128, 64, [60], id="short-single-page"),
    pytest.param(3, 8, 128, 64, 64, [200, 37, 130], id="ragged-batch"),
    pytest.param(1, 8, 576, 512, 128, [1210], id="paper-multi-page-long"),
    # More query rows than one combine tile: the combine kernel runs 3 row
    # tiles of 368 (4 pad rows) while the queue kernel holds all 1100 rows
    # in one program, like a 32-token prefill chunk at 128 heads.
    pytest.param(2, 1100, 128, 64, 64, [200, 37], id="row-tiled"),
]


@pytest.mark.parametrize("scheduler", ["queue", "padded"])
@pytest.mark.parametrize("variant", ["base", "amla"])
@pytest.mark.parametrize("b,hq,dk,dv,page,kv_lens", GEOMETRIES)
def test_paged_matches_contiguous_and_ref(
    scheduler, variant, b, hq, dk, dv, page, kv_lens
):
    sq = 1
    s = max(kv_lens)
    q = bf16ish((b, sq, hq, dk), 1)
    c = bf16ish((b, s, dk), 2)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    scale = 1.0 / dk**0.5
    num_pages = sum(-(-l // page) for l in kv_lens) + 2
    pool, bt = paginate(c, kv_lens, page, num_pages=num_pages, shuffle_seed=7)

    got = ops.mla_decode_paged(
        q, pool, bt, kv_len, d_v=dv, variant=variant, scale=scale,
        scheduler=scheduler, **INTERP
    )
    contig = ops.mla_decode(
        q, c, d_v=dv, variant=variant, scale=scale, kv_len=kv_len, **INTERP
    )
    # acceptance bound: FP32 max-abs parity with the contiguous kernel
    assert float(jnp.max(jnp.abs(got - contig))) <= PARITY_ATOL

    rows_pos = jnp.repeat(
        jnp.maximum(kv_len - sq, 0)[:, None] + jnp.arange(sq, dtype=jnp.int32),
        hq,
        axis=1,
    )
    want = ref.mla_decode_ref(
        q.reshape(b, sq * hq, dk), c, kv_len, rows_pos, d_v=dv, scale=scale
    ).reshape(b, sq, hq, dv)
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    assert err <= 8e-3, err


@pytest.mark.parametrize("variant", ["base", "amla"])
def test_fragmented_block_table_equals_linear_one(variant):
    """Physical page placement must not affect the result at all."""
    b, hq, dk, dv, page = 2, 4, 128, 64, 32
    kv_lens = [150, 90]
    q = bf16ish((b, 1, hq, dk), 3)
    c = bf16ish((b, max(kv_lens), dk), 4)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    scale = 1.0 / dk**0.5
    num_pages = 12
    pool_lin, bt_lin = paginate(c, kv_lens, page, num_pages=num_pages)
    pool_shuf, bt_shuf = paginate(
        c, kv_lens, page, num_pages=num_pages, shuffle_seed=11
    )
    kw = dict(d_v=dv, variant=variant, scale=scale, **INTERP)
    a = ops.mla_decode_paged(q, pool_lin, bt_lin, kv_len, **kw)
    z = ops.mla_decode_paged(q, pool_shuf, bt_shuf, kv_len, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(z))


@pytest.mark.parametrize("scheduler", ["queue", "padded"])
def test_zero_length_slot_yields_zeros(scheduler):
    """Inactive serving slots (kv_len == 0) must produce exact zeros."""
    b, hq, dk, dv, page = 2, 4, 128, 64, 32
    q = bf16ish((b, 1, hq, dk), 5)
    c = bf16ish((b, 64, dk), 6)
    kv_len = jnp.asarray([64, 0], jnp.int32)
    pool, bt = paginate(c, [64, 0], page, num_pages=4)
    out = ops.mla_decode_paged(
        q, pool, bt, kv_len, d_v=dv, scale=0.1, scheduler=scheduler, **INTERP
    )
    assert np.abs(np.asarray(out[1])).max() == 0.0
    assert np.abs(np.asarray(out[0])).max() > 0.0


def test_clamp_tail_pages_points_at_own_last_page():
    """Satellite fix: padding table entries must redirect to the request's
    own last valid page (warm per-request data), never physical page 0."""
    from repro.kernels.mla_decode_paged import clamp_tail_pages

    bt = jnp.asarray(
        [[7, 3, 0, 0],    # 2 live pages, tail padded with 0
         [5, 0, 0, 0],    # 1 live page
         [0, 0, 0, 0]],   # empty request
        jnp.int32,
    )
    kv_len = jnp.asarray([40, 8, 0], jnp.int32)
    got = np.asarray(clamp_tail_pages(bt, kv_len, page_size=32, num_pages=9))
    np.testing.assert_array_equal(
        got,
        [[7, 3, 3, 3],   # tail -> last live page 3, not 0
         [5, 5, 5, 5],
         [0, 0, 0, 0]],  # empty: first (clamped) entry
    )
    # out-of-range ids in the live region are still clamped into the pool
    wild = jnp.asarray([[42, -3]], jnp.int32)
    got = np.asarray(
        clamp_tail_pages(wild, jnp.asarray([64], jnp.int32), 32, 9)
    )
    np.testing.assert_array_equal(got, [[8, 0]])


def test_paged_session_reuses_schedule_across_steps():
    """A decode step only changes a request's block count every block_k
    tokens — the session's scheduler must reuse one schedule in between."""
    d_k, g = 16, 2
    sess = PagedDecodeSession(
        num_pages=8, page_size=4, d_k=d_k, d_v=8, scale=0.25,
        interpret=True, dtype=jnp.float32,
    )
    assert sess.block_k == 32  # table capacity caps the §4.2 block here
    one = lambda n, v=1.0: np.full((n, d_k), v, np.float32)
    r1 = sess.admit(one(5))
    r2 = sess.admit(one(3))
    for _ in range(4):
        sess.step({r1: one(g), r2: one(g)}, {r1: one(1)[0], r2: one(1)[0]})
    stats = sess.scheduler_stats
    assert stats["rebuilds"] >= 1
    assert stats["hits"] >= 2  # most steps reuse the memoized schedule


def test_mtp_sq2_rows_positions():
    """Sq=2 (MTP) decode: the two query tokens see causally-staggered keys."""
    b, sq, hq, dk, dv, page = 1, 2, 4, 128, 64, 32
    kv_lens = [100]
    q = bf16ish((b, sq, hq, dk), 12)
    c = bf16ish((b, 100, dk), 13)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    scale = 1.0 / dk**0.5
    pool, bt = paginate(c, kv_lens, page, num_pages=6, shuffle_seed=3)
    got = ops.mla_decode_paged(
        q, pool, bt, kv_len, d_v=dv, scale=scale, **INTERP
    )
    contig = ops.mla_decode(
        q, c, d_v=dv, scale=scale, kv_len=kv_len, **INTERP
    )
    assert float(jnp.max(jnp.abs(got - contig))) <= PARITY_ATOL


def test_paged_cache_feeds_kernel():
    """End-to-end: PagedKVCache appends -> block_table -> kernel == oracle."""
    dk, dv, page, hq = 128, 64, 32, 4
    scale = 1.0 / dk**0.5
    kv = PagedKVCache(num_pages=16, page_size=page, width=dk, dtype=jnp.float32)
    lens = [70, 45, 100]
    datas = []
    for rid, n in enumerate(lens):
        kv.alloc(rid)
        data = np.asarray(bf16ish((n, dk), 20 + rid))
        # interleave appends across requests to scramble physical placement
        kv.append(rid, data[: n // 2])
        datas.append(data)
    for rid, n in enumerate(lens):
        kv.append(rid, datas[rid][n // 2 :])
    bt, kv_len = kv.block_table([0, 1, 2])
    q = bf16ish((3, 1, hq, dk), 30)
    got = ops.mla_decode_paged(
        q,
        kv.pages,
        jnp.asarray(bt),
        jnp.asarray(kv_len),
        d_v=dv,
        scale=scale,
        **INTERP,
    )
    c = jnp.stack(
        [
            jnp.pad(jnp.asarray(d), ((0, max(lens) - d.shape[0]), (0, 0)))
            for d in datas
        ]
    )
    contig = ops.mla_decode(
        q, c, d_v=dv, scale=scale, kv_len=jnp.asarray(kv_len), **INTERP
    )
    assert float(jnp.max(jnp.abs(got - contig))) <= PARITY_ATOL


@pytest.mark.parametrize("variant", ["base", "amla"])
def test_paged_session_continuous_batching_parity(variant):
    """Admit/evict mid-stream; every step's outputs match the contiguous
    kernel run on each request's reassembled history."""
    rng = np.random.default_rng(40)
    d_k, d_v, g = 128, 64, 4
    scale = d_k**-0.5
    sess = PagedDecodeSession(
        num_pages=10,
        page_size=32,
        d_k=d_k,
        d_v=d_v,
        scale=scale,
        variant=variant,
        interpret=True,
        dtype=jnp.float32,
    )
    lat = lambda n, s: np.asarray(bf16ish((n, d_k), s))

    r1 = sess.admit(lat(50, 1))
    r2 = sess.admit(lat(70, 2))
    assert r1 is not None and r2 is not None
    assert sess.admit(lat(300, 3)) is None  # pool admission control

    def check(outputs, queries):
        for rid, got in outputs.items():
            c = sess.kv.gather_contiguous(rid)[None]
            want = ops.mla_decode(
                jnp.asarray(queries[rid])[None, None],
                c,
                d_v=d_v,
                variant=variant,
                scale=scale,
                kv_len=jnp.asarray([c.shape[1]], jnp.int32),
                **INTERP,
            )[0, 0]
            assert float(jnp.max(jnp.abs(got - want))) <= PARITY_ATOL

    queries = {r1: lat(g, 10), r2: lat(g, 11)}
    out = sess.step(queries, {r1: lat(1, 12)[0], r2: lat(1, 13)[0]})
    assert set(out) == {r1, r2}
    check(out, queries)

    sess.evict(r1)  # mid-stream eviction frees pages...
    r3 = sess.admit(lat(60, 4))  # ...which admit a queued request
    assert r3 is not None
    queries = {r2: lat(g, 14), r3: lat(g, 15)}
    out = sess.step(queries, {r2: lat(1, 16)[0], r3: lat(1, 17)[0]})
    assert set(out) == {r2, r3}
    check(out, queries)
    assert sess.kv.seq_len(r2) == 72 and sess.kv.seq_len(r3) == 61


def test_paged_session_step_append_is_atomic():
    """A step that cannot fit ALL new latents must land none of them."""
    d_k, g = 16, 2
    sess = PagedDecodeSession(
        num_pages=3, page_size=4, d_k=d_k, d_v=8, scale=0.25,
        interpret=True, dtype=jnp.float32,
    )
    one = lambda n: np.ones((n, d_k), np.float32)
    r1 = sess.admit(one(4))   # exactly 1 page, full
    r2 = sess.admit(one(8))   # exactly 2 pages, full
    assert sess.kv.num_free_pages == 0
    q = {r1: one(g), r2: one(g)}
    with pytest.raises(OutOfPagesError):
        sess.step(q, {r1: one(1)[0], r2: one(1)[0]})
    # nothing landed: the caller can evict and retry the SAME step safely
    assert sess.kv.seq_len(r1) == 4 and sess.kv.seq_len(r2) == 8
    sess.evict(r2)
    out = sess.step({r1: q[r1]}, {r1: one(1)[0]})
    assert sess.kv.seq_len(r1) == 5 and set(out) == {r1}


def _convert_shapes(jaxpr, acc):
    """All convert_element_type output shapes, walking nested jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            acc.append(tuple(eqn.outvars[0].aval.shape))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                _convert_shapes(inner, acc)
            elif isinstance(v, (list, tuple)):
                for w in v:
                    if getattr(w, "jaxpr", None) is not None:
                        _convert_shapes(w.jaxpr, acc)
    return acc


@pytest.mark.parametrize("scheduler", ["queue", "padded"])
def test_fp32_compute_dtype_has_no_pool_sized_cast(scheduler):
    """Satellite fix: compute_dtype=float32 over a bf16 pool must not
    materialise an O(pool) `pool.astype(f32)` copy on every decode call —
    the kernels cast per staged strip instead.  Pinned on the traced jaxpr:
    no convert_element_type anywhere may produce a pool-shaped array."""
    from repro.kernels.decode_schedule import build_schedule

    b, hq, dk, dv, page = 2, 4, 128, 64, 32
    kv_lens = [96, 64]
    q = jnp.asarray(bf16ish((b, 1, hq, dk), 50), jnp.float32)
    c = bf16ish((b, max(kv_lens), dk), 51)
    pool, bt = paginate(c, kv_lens, page, num_pages=6)
    pool = pool.astype(jnp.bfloat16)
    schedule = build_schedule(kv_lens, block_k=page * 2)

    def f(q, pool, bt, kv_len):
        return ops.mla_decode_paged(
            q, pool, bt, kv_len, d_v=dv, scale=0.1, scheduler=scheduler,
            block_k=page * 2 if scheduler == "queue" else None,
            schedule=schedule if scheduler == "queue" else None,
            compute_dtype=jnp.float32, **INTERP,
        )

    kv_len = jnp.asarray(kv_lens, jnp.int32)
    jaxpr = jax.make_jaxpr(f)(q, pool, bt, kv_len)
    shapes = _convert_shapes(jaxpr.jaxpr, [])
    assert tuple(pool.shape) not in shapes, (
        f"pool-sized convert_element_type found: {pool.shape}"
    )
    # and the fp32 path still matches the bf16-compute result closely
    out32 = f(q, pool, bt, kv_len)
    out16 = ops.mla_decode_paged(
        q, pool, bt, kv_len, d_v=dv, scale=0.1, scheduler=scheduler,
        block_k=page * 2 if scheduler == "queue" else None,
        schedule=schedule if scheduler == "queue" else None, **INTERP,
    )
    assert float(jnp.max(jnp.abs(out32 - out16))) <= PARITY_ATOL


def test_paged_session_rejects_dead_rids():
    d_k = 16
    sess = PagedDecodeSession(
        num_pages=4, page_size=4, d_k=d_k, d_v=8, scale=0.25,
        interpret=True, dtype=jnp.float32,
    )
    rid = sess.admit(np.ones((4, d_k), np.float32))
    sess.evict(rid)
    with pytest.raises(KeyError):
        sess.attend({rid: np.ones((2, d_k), np.float32)})
    with pytest.raises(KeyError):
        sess.evict(rid)
