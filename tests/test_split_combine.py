"""Split-KV parity tests for the work-queue decode path (interpret mode).

Flash-decoding-style splitting must be invisible in the output: for any
split factor, |split − unsplit| <= 2e-3 (FP32), across ragged kv_len,
requests that land entirely inside one split, and both rescale variants.
The combine kernel (kernels/mla_decode_combine) is additionally checked
directly against hand-built partials.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.decode_schedule import build_schedule
from repro.kernels.mla_decode_combine import combine_split_partials
from repro.runtime.kv_cache import pool_shape

INTERP = dict(interpret=True)
PARITY_ATOL = 2e-3


def bf16ish(shape, seed, scale=0.3):
    x = np.random.default_rng(seed).normal(0, scale, shape)
    return jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)


def paginate_linear(c, kv_lens, page):
    b, s, dk = c.shape
    num_pages = sum(-(-int(l) // page) for l in kv_lens) + 1
    w = max(max(-(-int(l) // page) for l in kv_lens), 1)
    pool = np.zeros(pool_shape(num_pages, page, dk), np.float32)
    bt = np.zeros((b, w), np.int32)
    nxt = 0
    for bb, l in enumerate(kv_lens):
        for j in range(-(-int(l) // page)):
            hi = min((j + 1) * page, int(l))
            pool[nxt, : hi - j * page, :dk] = np.asarray(c[bb, j * page : hi])
            bt[bb, j] = nxt
            nxt += 1
    return jnp.asarray(pool), jnp.asarray(bt)


@pytest.mark.parametrize("variant", ["base", "amla"])
@pytest.mark.parametrize("num_splits", [1, 2, 4])
def test_split_matches_unsplit_ragged_batch(variant, num_splits):
    """Core acceptance test: split factors {1, 2, 4} over a ragged batch
    (multi-block long request, sub-block short request, empty slot)."""
    b, hq, dk, dv, page, block_k = 4, 4, 128, 64, 32, 64
    kv_lens = [7 * block_k + 13, 37, 0, 3 * block_k]
    q = bf16ish((b, 1, hq, dk), 1)
    c = bf16ish((b, max(kv_lens), dk), 2)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    scale = 1.0 / dk**0.5
    pool, bt = paginate_linear(c, kv_lens, page)

    kw = dict(d_v=dv, variant=variant, scale=scale, block_k=block_k, **INTERP)
    unsplit = ops.mla_decode_paged(
        q, pool, bt, kv_len, num_splits=1, **kw
    )
    split = ops.mla_decode_paged(
        q, pool, bt, kv_len, num_splits=num_splits, **kw
    )
    assert float(jnp.max(jnp.abs(split - unsplit))) <= PARITY_ATOL
    # and both must still match the contiguous kernel
    contig = ops.mla_decode(
        q, c, d_v=dv, variant=variant, scale=scale, kv_len=kv_len, **INTERP
    )
    assert float(jnp.max(jnp.abs(split - contig))) <= PARITY_ATOL
    # empty slot stays exactly zero through split + combine
    assert np.abs(np.asarray(split[2])).max() == 0.0


@pytest.mark.parametrize("variant", ["base", "amla"])
def test_request_entirely_inside_one_split(variant):
    """A single-block request under num_splits=4 must take the
    one-live-split path through the combine (weights degenerate to 1)."""
    b, hq, dk, dv, page, block_k = 2, 4, 128, 64, 32, 128
    kv_lens = [90, 6 * block_k]  # req 0: one block; req 1: actually split
    q = bf16ish((b, 1, hq, dk), 3)
    c = bf16ish((b, max(kv_lens), dk), 4)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    scale = 1.0 / dk**0.5
    pool, bt = paginate_linear(c, kv_lens, page)

    sched = build_schedule(kv_lens, block_k=block_k, num_splits=4)
    assert sched.n_splits.tolist() == [1, 4]
    kw = dict(d_v=dv, variant=variant, scale=scale, block_k=block_k, **INTERP)
    split = ops.mla_decode_paged(
        q, pool, bt, kv_len, num_splits=4, schedule=sched, **kw
    )
    contig = ops.mla_decode(
        q, c, d_v=dv, variant=variant, scale=scale, kv_len=kv_len, **INTERP
    )
    assert float(jnp.max(jnp.abs(split - contig))) <= PARITY_ATOL


def test_split_count_exceeding_blocks_everywhere():
    """num_splits larger than any request's block count degenerates to
    unsplit scheduling and identical outputs."""
    b, hq, dk, dv, page, block_k = 3, 4, 128, 64, 32, 64
    kv_lens = [50, 64, 63]
    q = bf16ish((b, 1, hq, dk), 5)
    c = bf16ish((b, max(kv_lens), dk), 6)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    pool, bt = paginate_linear(c, kv_lens, page)
    kw = dict(d_v=dv, scale=0.1, block_k=block_k, **INTERP)
    a = ops.mla_decode_paged(q, pool, bt, kv_len, num_splits=1, **kw)
    z = ops.mla_decode_paged(q, pool, bt, kv_len, num_splits=4, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(z))


def test_combine_kernel_is_exact_lse_merge():
    """Direct check: combining softmax shards of one sequence reproduces
    the full softmax, and gated-off slots contribute nothing."""
    rng = np.random.default_rng(11)
    g, dv, shards = 8, 16, 3
    s_parts = [rng.normal(0, 1, (g, 64)).astype(np.float32) for _ in range(shards)]
    v_parts = [rng.normal(0, 1, (64, dv)).astype(np.float32) for _ in range(shards)]

    o_part = np.zeros((shards + 1, g, dv), np.float32)
    lse = np.full((shards + 1, g, 1), np.nan, np.float32)  # poison the dump
    for i, (s, v) in enumerate(zip(s_parts, v_parts)):
        m = s.max(-1, keepdims=True)
        p = np.exp(s - m)
        o_part[i] = (p @ v) / p.sum(-1, keepdims=True)
        lse[i] = m + np.log(p.sum(-1, keepdims=True))

    dest_table = np.asarray([[0, 1, 2]], np.int32)
    got = combine_split_partials(
        jnp.asarray(o_part),
        jnp.asarray(lse),
        jnp.asarray(dest_table),
        jnp.asarray([shards], jnp.int32),
        interpret=True,
    )
    s_full = np.concatenate(s_parts, axis=1)
    v_full = np.concatenate(v_parts, axis=0)
    m = s_full.max(-1, keepdims=True)
    p = np.exp(s_full - m)
    want = (p @ v_full) / p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-5)

    # zero live splits -> exact zeros, even with poisoned partials
    got0 = combine_split_partials(
        jnp.asarray(o_part),
        jnp.asarray(lse),
        jnp.asarray(dest_table),
        jnp.asarray([0], jnp.int32),
        interpret=True,
    )
    assert np.abs(np.asarray(got0)).max() == 0.0

    # an empty shard (lse = -inf) must drop out of the merge entirely
    o_part[1] = 123.0
    lse[1] = -np.inf
    got1 = combine_split_partials(
        jnp.asarray(o_part),
        jnp.asarray(lse),
        jnp.asarray(dest_table),
        jnp.asarray([shards], jnp.int32),
        interpret=True,
    )
    s_wo = np.concatenate([s_parts[0], s_parts[2]], axis=1)
    v_wo = np.concatenate([v_parts[0], v_parts[2]], axis=0)
    m = s_wo.max(-1, keepdims=True)
    p = np.exp(s_wo - m)
    want1 = (p @ v_wo) / p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got1[0]), want1, atol=1e-5)
