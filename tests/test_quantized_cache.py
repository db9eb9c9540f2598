"""Int8 quantized latent page pool: round-trip properties, fork/COW with
scales, and fused-dequant kernel parity.

The storage contract: an int8 pool stores symmetric per-row quantized
latents plus one fp32 scale per page row (``CacheSpec``); every write
quantizes exactly the fresh rows (stored rows are never re-rounded), every
copy (COW) moves data and scales together, and the work-queue kernel
dequantizes inside its preload pipeline.  Acceptance bound (ISSUE 5):
``|int8 − bf16| <= 3e-2`` fp32-combined on the smoke geometry across
ragged / prefix-shared / COW scenarios.

Round-trip sweeps are plain seeded parametrizations in the style of
tests/test_numerics_properties.py — hypothesis-free by construction.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_schedule import build_prefix_schedule, build_schedule
from repro.kernels.mla_decode_paged import CacheSpec
from repro.runtime.kv_cache import LayeredPagedKVCache, PagedKVCache
from repro.runtime.serve_loop import PagedDecodeSession

INTERP = dict(interpret=True)
INT8_PARITY_ATOL = 3e-2  # ISSUE-5 acceptance bound (vs the bf16 path)


def rows(n, width, seed, scale=0.3):
    return np.random.default_rng(seed).normal(0, scale, (n, width)).astype(
        np.float32
    )


def make_int8(num_pages=8, page_size=4, width=16):
    return PagedKVCache(
        num_pages=num_pages, page_size=page_size, width=width, dtype=jnp.int8
    )


# --------------------------------------------------------------------------- #
# CacheSpec
# --------------------------------------------------------------------------- #


def test_cache_spec_names_and_bytes():
    bf16 = CacheSpec.from_name("bf16")
    int8 = CacheSpec.from_name("int8")
    assert not bf16.quantized and int8.quantized
    # the headline: int8 pages move ~half the bytes, scale strip included;
    # DMAs move whole 128-lane tiles, so a 576-wide row moves 640 lanes
    assert bf16.bytes_per_page(128, 576) == 128 * 640 * 2
    assert int8.bytes_per_page(128, 576) == 128 * 640 + 128 * 4
    ratio = bf16.bytes_per_page(128, 576) / int8.bytes_per_page(128, 576)
    assert ratio >= 1.9
    with pytest.raises(ValueError, match="unknown cache dtype"):
        CacheSpec.from_name("fp4")


def test_cache_spec_normalizes_string_dtypes():
    """dtype name strings must mean exactly what from_name means: a string
    'int8' that built a real int8 pool while `quantized` stayed False
    would cast latent rows to int8 with no scales — silent data loss."""
    assert CacheSpec(dtype="int8") == CacheSpec.from_name("int8")
    assert CacheSpec(dtype="int8").quantized
    assert not CacheSpec(dtype="bf16").quantized
    with pytest.raises(ValueError, match="unknown cache dtype"):
        CacheSpec(dtype="fp4")
    kv = PagedKVCache(num_pages=2, page_size=4, width=8, dtype="int8")
    assert kv.quantized and kv.scales is not None


def test_cache_spec_only_row_granularity():
    """Per-row is the only exact-write-once granularity on a paged cache;
    others must fail loudly until their pool layouts exist."""
    with pytest.raises(NotImplementedError, match="row"):
        CacheSpec(dtype=jnp.int8, scale_granularity="page")


# --------------------------------------------------------------------------- #
# quantization round-trip properties (seeded sweeps)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("seed", range(6))
def test_roundtrip_error_within_per_row_bound(seed):
    """Symmetric per-row int8: every element round-trips within half a
    quantization step of its own row's scale (max|row|/127/2)."""
    rng = np.random.default_rng(seed)
    width = 32
    # magnitudes spanning several decades per row — per-row scales must
    # keep small rows as accurate as big ones
    mags = np.exp2(rng.uniform(-8, 4, (20, 1))).astype(np.float32)
    data = (rng.normal(0, 1, (20, width)).astype(np.float32)) * mags
    kv = make_int8(num_pages=8, page_size=4, width=width)
    kv.alloc(0)
    kv.append(0, data)
    deq = np.asarray(kv.gather_contiguous(0))
    step = np.abs(data).max(axis=1, keepdims=True) / 127.0
    assert np.all(np.abs(deq - data) <= 0.5 * step + 1e-7)


@pytest.mark.parametrize("seed", range(4))
def test_roundtrip_relative_error_row_wise(seed):
    """Row-wise relative L2 error of the dequantized rows stays below the
    ~1/127 quantization noise floor (x a small constant)."""
    data = rows(16, 64, 100 + seed)
    kv = make_int8(num_pages=8, page_size=4, width=64)
    kv.alloc(0)
    kv.append(0, data)
    deq = np.asarray(kv.gather_contiguous(0))
    num = np.linalg.norm(deq - data, axis=1)
    den = np.linalg.norm(data, axis=1) + 1e-12
    assert np.all(num / den < 3.0 / 127.0)


def test_zero_rows_roundtrip_exactly():
    kv = make_int8(width=16)
    kv.alloc(0)
    kv.append(0, np.zeros((5, 16), np.float32))
    assert np.abs(np.asarray(kv.gather_contiguous(0))).max() == 0.0


def test_append_never_requantizes_stored_rows():
    """Write-once semantics: decode-style appends into a partial page must
    leave previously stored int8 rows and their scales bitwise unchanged
    (a per-page scale would re-round them — the reason CacheSpec only
    implements per-row granularity)."""
    kv = make_int8(num_pages=4, page_size=4, width=16)
    kv.alloc(0)
    data = rows(7, 16, 7)
    snapshots = []
    for i in range(7):
        kv.append(0, data[i : i + 1])
        snapshots.append(
            (np.asarray(kv.pages).copy(), np.asarray(kv.scales).copy())
        )
    for i in range(1, 7):
        prev_pages, prev_scales = snapshots[i - 1]
        cur_pages, cur_scales = snapshots[i][0].copy(), snapshots[i][1].copy()
        pid = kv.seq_pages(0)[i // 4]
        off = i % 4
        # everything except the single fresh row is untouched
        cur_pages[pid, off] = prev_pages[pid, off]
        cur_scales[pid, off] = prev_scales[pid, off]
        np.testing.assert_array_equal(cur_pages, prev_pages)
        np.testing.assert_array_equal(cur_scales, prev_scales)


def test_incremental_append_equals_bulk_append():
    """One-row decode appends quantize identically to a bulk prefill
    append of the same rows (quantization is purely per-row)."""
    data = rows(10, 16, 8)
    bulk, inc = make_int8(), make_int8()
    bulk.alloc(0)
    bulk.append(0, data)
    inc.alloc(0)
    for i in range(10):
        inc.append(0, data[i : i + 1])
    np.testing.assert_array_equal(
        np.asarray(bulk.gather_contiguous(0)),
        np.asarray(inc.gather_contiguous(0)),
    )


# --------------------------------------------------------------------------- #
# fork / COW carry the scales
# --------------------------------------------------------------------------- #


def test_cow_copies_scales_with_data_flat():
    """A COW fault on a shared boundary page must copy the scale row in
    the same op: the parent stays bitwise intact, the child's aliased
    prefix decodes to exactly the parent's values."""
    kv = make_int8(num_pages=8, page_size=4, width=16)
    kv.alloc(0)
    data = rows(10, 16, 9)  # 2.5 pages: page 2 is the shared boundary
    kv.append(0, data)
    parent_before = np.asarray(kv.gather_contiguous(0))
    kv.fork(0, 1, 10)
    kv.append(1, rows(3, 16, 10))  # COW fault on page 2
    assert kv.seq_pages(1)[2] != kv.seq_pages(0)[2]
    np.testing.assert_array_equal(
        np.asarray(kv.gather_contiguous(0)), parent_before
    )
    np.testing.assert_array_equal(
        np.asarray(kv.gather_contiguous(1))[:10], parent_before
    )


def test_layered_cow_copies_scales_across_all_layers():
    """Layered COW: one fault copies page data *and* scale rows for every
    one of the L layers at once; each layer's rows stay exactly its own."""
    L = 3
    kv = LayeredPagedKVCache(
        num_layers=L, num_pages=6, page_size=4, width=8, dtype=jnp.int8
    )
    kv.alloc(0)
    plan = kv.reserve(0, 6)  # boundary page half full
    per_layer = [rows(6, 8, 20 + l, scale=0.1 * (l + 1)) for l in range(L)]
    for l in range(L):
        kv.write_layer(l, plan, per_layer[l])
    parent_before = np.asarray(kv.gather_contiguous(0))
    free_before = kv.num_free_pages

    kv.fork(0, 1)
    plan1 = kv.reserve(1, 1)  # COW fault: all layers in one call
    assert kv.num_free_pages == free_before - 1  # exactly the COW page
    for l in range(L):
        kv.write_layer_tokens(
            l, [plan1[0][0]], [plan1[0][1]], np.full((1, 8), 9.0, np.float32)
        )
    parent_after = np.asarray(kv.gather_contiguous(0))
    np.testing.assert_array_equal(parent_after, parent_before)
    child = np.asarray(kv.gather_contiguous(1))
    np.testing.assert_array_equal(child[:, :6], parent_before)
    # the appended row survives its own round-trip per layer
    assert np.all(np.abs(child[:, 6] - 9.0) <= 9.0 / 127.0 * 0.5 + 1e-6)


def test_refcounts_and_free_unchanged_by_quantization():
    """Page bookkeeping is storage-dtype-blind: fork/free behave exactly
    as for bf16 pools (refcount releases, last-owner recycling)."""
    kv = make_int8(num_pages=6, page_size=4)
    kv.alloc(0)
    kv.append(0, rows(8, 16, 11))
    kv.fork(0, 1)
    kv.fork(0, 2)
    assert kv.num_aliased_pages() == 2
    kv.free(0)
    kv.free(1)
    assert kv.num_free_pages == 4
    np.testing.assert_array_equal(
        np.asarray(kv.gather_contiguous(2)),
        np.asarray(kv.gather_contiguous(2)),
    )
    kv.free(2)
    assert kv.num_free_pages == 6


def test_truncate_int8_reappend_matches_never_speculated_twin():
    """Speculative rollback on a quantized pool: append draft rows,
    truncate the rejected tail, re-append the real tokens — the stored
    int8 rows AND their scales must match a twin pool that never
    speculated bit for bit (per-row symmetric quantization is positional
    only through the page layout, which truncate restores exactly)."""
    base, spec, real = rows(6, 16, 20), rows(3, 16, 21), rows(3, 16, 22)
    kv = make_int8(num_pages=8, page_size=4)
    kv.alloc(0)
    kv.append(0, base)
    kv.append(0, spec)  # 3 draft rows land
    kv.truncate(0, 7)  # accept 1 of 3
    kv.append(0, real)
    twin = make_int8(num_pages=8, page_size=4)
    twin.alloc(0)
    twin.append(0, base)
    twin.append(0, spec[:1])
    twin.append(0, real)
    assert kv.seq_len(0) == twin.seq_len(0) == 10
    np.testing.assert_array_equal(
        np.asarray(kv.gather_contiguous(0)),
        np.asarray(twin.gather_contiguous(0)),
    )


def test_truncate_int8_forked_child_rollback_preserves_parent():
    """Rolling a forked child's speculation back must leave the parent's
    quantized rows and scales untouched (the COW boundary copy absorbs the
    rejected writes) and restore the child to the shared prefix."""
    kv = make_int8(num_pages=8, page_size=4)
    base = rows(6, 16, 23)
    kv.alloc(0)
    kv.append(0, base)
    before = np.asarray(kv.gather_contiguous(0)).copy()
    kv.fork(0, 1)
    kv.append(1, rows(4, 16, 24))  # COW on the boundary page + a tail page
    kv.truncate(1, 6)  # reject everything
    np.testing.assert_array_equal(np.asarray(kv.gather_contiguous(0)), before)
    np.testing.assert_array_equal(np.asarray(kv.gather_contiguous(1)), before)
    kv.free(1)
    kv.free(0)
    assert kv.num_free_pages == 8


# --------------------------------------------------------------------------- #
# fused-dequant kernel parity (ISSUE-5 acceptance: <= 3e-2 combined)
# --------------------------------------------------------------------------- #


def _paged_pair(kv_lens, page, width, seed):
    """Twin caches (bf16, int8) filled with identical latents + queries."""
    rng = np.random.default_rng(seed)
    num_pages = sum(-(-l // page) for l in kv_lens) + 2
    kv16 = PagedKVCache(
        num_pages=num_pages, page_size=page, width=width, dtype=jnp.bfloat16
    )
    kv8 = PagedKVCache(
        num_pages=num_pages, page_size=page, width=width, dtype=jnp.int8
    )
    for rid, l in enumerate(kv_lens):
        data = rng.normal(0, 0.3, (l, width)).astype(np.float32)
        for kv in (kv16, kv8):
            kv.alloc(rid)
            kv.append(rid, data)
    return kv16, kv8


def _decode(kv, q, kv_lens, *, dv, schedule=None, block_k=None, **kw):
    bt, kv_len = kv.block_table(list(range(len(kv_lens))))
    return ops.mla_decode_paged(
        q,
        kv.pages,
        jnp.asarray(bt),
        jnp.asarray(kv_len),
        kv_scales=kv.scales,
        d_v=dv,
        scale=1.0 / q.shape[-1] ** 0.5,
        block_k=block_k,
        schedule=schedule,
        **INTERP,
        **kw,
    )


@pytest.mark.parametrize("variant", ["base", "amla"])
def test_int8_parity_ragged_batch(variant):
    """Ragged, non-page-aligned batch: |int8 − bf16| within the bound."""
    page, width, dv, hq = 32, 128, 64, 4
    kv_lens = [200, 37, 130]
    kv16, kv8 = _paged_pair(kv_lens, page, width, 30)
    q = jnp.asarray(
        np.random.default_rng(31).normal(0, 0.3, (3, 1, hq, width)),
        jnp.bfloat16,
    ).astype(jnp.float32)
    a = _decode(kv16, q, kv_lens, dv=dv, variant=variant)
    z = _decode(kv8, q, kv_lens, dv=dv, variant=variant)
    assert float(jnp.max(jnp.abs(a - z))) <= INT8_PARITY_ATOL


def test_int8_parity_prefix_shared_and_cow():
    """A forked family (shared prefix pages, one COW'd boundary) through
    the group-batched prefix schedule: int8 matches bf16 within the bound
    and the shared path matches the plain queue on the same int8 pool."""
    page, width, dv, hq, block_k = 16, 128, 64, 4, 32
    prefix_len, group = 3 * block_k, 3
    rng = np.random.default_rng(32)
    num_pages = 32
    kv16 = PagedKVCache(
        num_pages=num_pages, page_size=page, width=width, dtype=jnp.bfloat16
    )
    kv8 = PagedKVCache(
        num_pages=num_pages, page_size=page, width=width, dtype=jnp.int8
    )
    prefix = rng.normal(0, 0.3, (prefix_len, width)).astype(np.float32)
    suffixes = [
        rng.normal(0, 0.3, (n, width)).astype(np.float32)
        for n in (7, 13, 21)
    ]
    for kv in (kv16, kv8):
        kv.alloc(0)
        kv.append(0, prefix)
        for rid in range(1, group):
            kv.fork(0, rid, prefix_len)
        for rid, suf in enumerate(suffixes):
            kv.append(rid, suf)  # rid>0 COW-fault nothing (prefix aligned)
    rids = list(range(group))
    kv_lens = [kv8.seq_len(r) for r in rids]
    q = jnp.asarray(
        rng.normal(0, 0.3, (group, 1, hq, width)), jnp.bfloat16
    ).astype(jnp.float32)

    bt8, _ = kv8.block_table(rids)
    ps = build_prefix_schedule(
        kv_lens, bt8, page_size=page, block_k=block_k
    )
    assert ps.num_groups == 1  # the family actually groups
    plain = build_schedule(kv_lens, block_k=block_k)

    a = _decode(kv16, q, kv_lens, dv=dv, block_k=block_k, schedule=plain)
    z_shared = _decode(kv8, q, kv_lens, dv=dv, block_k=block_k, schedule=ps)
    z_plain = _decode(kv8, q, kv_lens, dv=dv, block_k=block_k, schedule=plain)
    assert float(jnp.max(jnp.abs(z_shared - a))) <= INT8_PARITY_ATOL
    assert float(jnp.max(jnp.abs(z_plain - a))) <= INT8_PARITY_ATOL
    # group batching must not change the int8 numerics beyond fp32 combine
    assert float(jnp.max(jnp.abs(z_shared - z_plain))) <= 2e-3


def test_int8_parity_after_cow_divergence():
    """Append past a *non-block-aligned* fork point so the child COW-copies
    the boundary page, then check parity again on both requests."""
    page, width, dv, hq = 16, 128, 64, 4
    kv16, kv8 = _paged_pair([40], page, width, 33)
    suffix = np.random.default_rng(34).normal(0, 0.3, (9, width)).astype(
        np.float32
    )
    for kv in (kv16, kv8):
        kv.fork(0, 1, 40)
        kv.append(1, suffix)  # COW fault on the shared boundary page
        assert kv.seq_pages(1)[-1] != kv.seq_pages(0)[-1]
    kv_lens = [40, 49]
    q = jnp.asarray(
        np.random.default_rng(35).normal(0, 0.3, (2, 1, hq, width)),
        jnp.float32,
    )
    a = _decode(kv16, q, kv_lens, dv=dv)
    z = _decode(kv8, q, kv_lens, dv=dv)
    assert float(jnp.max(jnp.abs(a - z))) <= INT8_PARITY_ATOL


def test_int8_zero_length_slot_yields_zeros():
    page, width, dv, hq = 16, 64, 32, 4
    kv16, kv8 = _paged_pair([48], page, width, 36)
    kv8.alloc(1)  # empty slot
    bt, kv_len = kv8.block_table([0, 1])
    q = jnp.asarray(
        np.random.default_rng(37).normal(0, 0.3, (2, 1, hq, width)),
        jnp.float32,
    )
    out = ops.mla_decode_paged(
        q, kv8.pages, jnp.asarray(bt), jnp.asarray(kv_len),
        kv_scales=kv8.scales, d_v=dv, scale=0.125, **INTERP,
    )
    assert np.abs(np.asarray(out[1])).max() == 0.0
    assert np.abs(np.asarray(out[0])).max() > 0.0


def test_int8_session_continuous_batching_parity():
    """PagedDecodeSession over an int8 spec: every step's outputs match the
    contiguous fp32 kernel on the request's dequantized history."""
    d_k, d_v, g = 128, 64, 4
    scale = d_k**-0.5
    sess = PagedDecodeSession(
        num_pages=10, page_size=32, d_k=d_k, d_v=d_v, scale=scale,
        interpret=True, cache_spec=CacheSpec(dtype=jnp.int8),
    )
    lat = lambda n, s: np.random.default_rng(s).normal(0, 0.3, (n, d_k)).astype(
        np.float32
    )
    r1 = sess.admit(lat(50, 1))
    r2 = sess.admit(lat(70, 2))
    queries = {r1: lat(g, 10), r2: lat(g, 11)}
    out = sess.step(queries, {r1: lat(1, 12)[0], r2: lat(1, 13)[0]})
    for rid, got in out.items():
        c = sess.kv.gather_contiguous(rid)[None]  # dequantized history
        want = ops.mla_decode(
            jnp.asarray(queries[rid])[None, None], c, d_v=d_v, scale=scale,
            kv_len=jnp.asarray([c.shape[1]], jnp.int32), **INTERP,
        )[0, 0]
        # vs the *dequantized* oracle only bf16-kernel noise remains
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-3


# --------------------------------------------------------------------------- #
# validation
# --------------------------------------------------------------------------- #


def test_int8_without_scales_rejected():
    kv16, kv8 = _paged_pair([32], 16, 64, 40)
    bt, kv_len = kv8.block_table([0])
    q = jnp.zeros((1, 1, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="scale pool"):
        ops.mla_decode_paged(
            q, kv8.pages, jnp.asarray(bt), jnp.asarray(kv_len),
            d_v=32, scale=0.1, **INTERP,
        )


def test_int8_padded_scheduler_rejected():
    """The padded (B, W) baseline stays bf16-only; int8 must fail fast."""
    kv16, kv8 = _paged_pair([32], 16, 64, 41)
    bt, kv_len = kv8.block_table([0])
    q = jnp.zeros((1, 1, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="padded"):
        ops.mla_decode_paged(
            q, kv8.pages, jnp.asarray(bt), jnp.asarray(kv_len),
            kv_scales=kv8.scales, d_v=32, scale=0.1, scheduler="padded",
            **INTERP,
        )
    with pytest.raises(ValueError, match="queue"):
        PagedDecodeSession(
            num_pages=4, page_size=16, d_k=64, d_v=32, scale=0.1,
            interpret=True, cache_spec=CacheSpec(dtype=jnp.int8),
            scheduler="padded",
        )


def test_scales_with_bf16_pool_rejected():
    kv16, kv8 = _paged_pair([32], 16, 64, 42)
    bt, kv_len = kv16.block_table([0])
    q = jnp.zeros((1, 1, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="int8 pools only"):
        ops.mla_decode_paged(
            q, kv16.pages, jnp.asarray(bt), jnp.asarray(kv_len),
            kv_scales=kv8.scales, d_v=32, scale=0.1, **INTERP,
        )
