"""Multi-host sharded paged serving: routing, shard merge math, parity.

Three layers of coverage for :class:`ShardedPagedServingSession`:

* unit tests for the pure scheduling pieces — ``route_request`` (least
  live KV blocks wins, ties toward free pages), ``shard_work_balance``
  (max/mean imbalance proxy), and ``combine_shard_partials`` (the exact
  LSE merge a request split *across* shards would use, checked against a
  full softmax and via ``ops.mla_decode_paged(return_partials=True)``
  across two physically separate page pools);
* in-process session tests on **logical shards** (``shards=N`` on one
  device — same routing, per-shard pools and schedules as a real mesh,
  minus device placement): greedy parity with the single-host backend
  through ragged admission, mid-stream eviction, and forked-prefix
  families that must stay on one shard;
* subprocess tests that force a real 4-device CPU mesh via XLA_FLAGS
  (``examples/serve_sharded.py`` and the serve driver's ``--mesh``),
  where device placement and tensor-parallel head chunks are live.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.distributed import combine_shard_partials
from repro.kernels import ops
from repro.kernels.decode_schedule import route_request, shard_work_balance
from repro.models.model_zoo import build_model
from repro.runtime.kv_cache import pool_shape
from repro.runtime.serve_loop import (
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


def make_sharded(model, params, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("shards", 2)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("interpret", True)
    return ShardedPagedServingSession(model, params, **kw)


def make_single(model, params, **kw):
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("interpret", True)
    return PagedServingSession(model, params, **kw)


def prompts_for(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, CFG.vocab_size, size=n).tolist() for n in lengths]


# --------------------------------------------------------------------------- #
# routing + balance units
# --------------------------------------------------------------------------- #


def test_route_request_least_loaded_wins():
    assert route_request([4, 1, 2], [10, 10, 10], 1) == 1


def test_route_request_tie_breaks_toward_free_pages_then_index():
    # equal block load -> the shard with more free pages wins
    assert route_request([2, 2], [3, 9], 1) == 1
    # full tie -> lowest index, so an empty fleet fills 0, 1, 2, ...
    assert route_request([0, 0, 0], [8, 8, 8], 1) == 0


def test_route_request_skips_shards_without_room():
    # shard 0 is idle but has no pages; the loaded shard must take it
    assert route_request([0, 5], [1, 8], 2) == 1
    # nobody has room -> None (caller evicts or defers admission)
    assert route_request([1, 1], [1, 1], 2) is None
    assert route_request([], [], 1) is None


def test_shard_work_balance_imbalance_proxy():
    even = shard_work_balance([10, 10])
    assert even["imbalance"] == 1.0
    assert even["total"] == 20.0 and even["max"] == 10.0
    skew = shard_work_balance([30, 10])
    assert skew["imbalance"] == pytest.approx(1.5)
    assert skew["per_shard"] == [30.0, 10.0]
    # empty / idle fleets are defined as perfectly balanced
    assert shard_work_balance([])["imbalance"] == 1.0
    assert shard_work_balance([0, 0])["imbalance"] == 1.0


# --------------------------------------------------------------------------- #
# cross-shard (o, lse) merge
# --------------------------------------------------------------------------- #


def test_combine_shard_partials_reproduces_full_softmax():
    rng = np.random.default_rng(3)
    g, dv, n = 4, 8, 96
    z = rng.normal(0, 1, (g, n)).astype(np.float32)
    v = rng.normal(0, 1, (n, dv)).astype(np.float32)
    cuts = [0, 40, 96]
    o_parts, lse_parts = [], []
    for lo, hi in zip(cuts, cuts[1:]):
        zi = z[:, lo:hi]
        m = zi.max(-1, keepdims=True)
        p = np.exp(zi - m)
        o_parts.append((p @ v[lo:hi]) / p.sum(-1, keepdims=True))
        lse_parts.append((m + np.log(p.sum(-1, keepdims=True)))[..., 0])
    got = combine_shard_partials(np.stack(o_parts), np.stack(lse_parts))
    m = z.max(-1, keepdims=True)
    p = np.exp(z - m)
    want = (p @ v) / p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_combine_shard_partials_drops_empty_shards():
    from repro.kernels.mla_decode_combine import BIG_NEG

    rng = np.random.default_rng(4)
    g, dv = 4, 8
    o = rng.normal(0, 1, (3, g, dv)).astype(np.float32)
    lse = rng.normal(0, 1, (3, g)).astype(np.float32)
    # poison shard 1: an empty shard carries BIG_NEG lse, any o payload
    o_poison, lse_mask = o.copy(), lse.copy()
    o_poison[1] = 1e9
    lse_mask[1] = BIG_NEG
    got = combine_shard_partials(o_poison, lse_mask)
    want = combine_shard_partials(o[[0, 2]], lse[[0, 2]])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # every shard empty -> exact zeros, no NaNs from the 0/0 guard
    all_empty = combine_shard_partials(
        o_poison, np.full((3, g), BIG_NEG, np.float32)
    )
    assert np.abs(np.asarray(all_empty)).max() == 0.0


def _paginate(rows, page):
    """One request's (n, dk) rows -> a private page pool + block table."""
    n, dk = rows.shape
    n_pages = max(-(-n // page), 1)
    pool = np.zeros(pool_shape(n_pages + 1, page, dk), np.float32)  # +1 dummy
    for j in range(n_pages):
        hi = min((j + 1) * page, n)
        pool[j, : hi - j * page, :dk] = rows[j * page : hi]
    bt = np.arange(n_pages, dtype=np.int32)[None, :]
    return jnp.asarray(pool), jnp.asarray(bt)


def test_return_partials_merges_across_two_pools():
    """ops-level acceptance for the split-request path: attending the
    prefix and suffix of one request in two physically separate page pools
    and merging the ``(o, lse)`` partials with ``combine_shard_partials``
    matches attending the whole request in one pool."""
    rng = np.random.default_rng(5)
    hq, dk, dv, n, cut = 4, 64, 32, 90, 48
    q = jnp.asarray(rng.normal(0, 0.3, (1, 1, hq, dk)), jnp.float32)
    c = rng.normal(0, 0.3, (n, dk)).astype(np.float32)
    kw = dict(d_v=dv, scale=1.0 / dk**0.5, block_k=PAGE, interpret=True)

    pool, bt = _paginate(c, PAGE)
    full = ops.mla_decode_paged(
        q, pool, bt, jnp.asarray([n], jnp.int32), **kw
    )

    parts = []
    for lo, hi in ((0, cut), (cut, n)):
        p, b = _paginate(c[lo:hi], PAGE)
        # num_splits=2 so the per-row lse itself comes from a split merge
        parts.append(
            ops.mla_decode_paged(
                q, p, b, jnp.asarray([hi - lo], jnp.int32),
                num_splits=2, return_partials=True, **kw
            )
        )
    merged = combine_shard_partials(
        jnp.stack([o for o, _ in parts]),
        jnp.stack([lse for _, lse in parts]),
    )
    assert merged.shape == full.shape
    # 2e-3 is the repo-wide split-vs-unsplit parity budget: the AMLA exp2
    # accumulation reassociates across the partition boundary.
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(full), atol=2e-3
    )


def test_return_partials_rejects_non_queue_paths():
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(0, 0.3, (1, 1, 4, 64)), jnp.float32)
    pool, bt = _paginate(rng.normal(0, 0.3, (20, 64)).astype(np.float32), PAGE)
    kv_len = jnp.asarray([20], jnp.int32)
    kw = dict(d_v=32, scale=0.125, interpret=True, return_partials=True)
    with pytest.raises(ValueError, match="queue"):
        ops.mla_decode_paged(q, pool, bt, kv_len, scheduler="padded", **kw)
    with pytest.raises(ValueError, match="queue"):
        ops.mla_decode_paged(q, pool, bt, kv_len, prefix_sharing=True, **kw)


# --------------------------------------------------------------------------- #
# sharded session: logical shards on one device
# --------------------------------------------------------------------------- #


def test_sharded_greedy_parity_with_churn(model_and_params):
    """Logical 2-shard session matches the single-host paged backend
    exactly through ragged admission, forked children, and a mid-stream
    eviction — routing only decides *where* a request lives, never what it
    decodes."""
    model, params = model_and_params
    prompts = prompts_for(0, (5, 16, 9, 23))
    suffix = prompts_for(1, (6,))[0]

    def drive(sess):
        rids = [sess.add_request(p) for p in prompts]
        assert None not in rids
        for _ in range(3):
            sess.step()
        kid = sess.admit_with_prefix(rids[3], suffix, prefix_len=PAGE)
        assert kid is not None
        for _ in range(3):
            sess.step()
        early = sess.finish(rids[1])
        for _ in range(2):
            sess.step()
        return [early] + [
            sess.finish(r) for r in (rids[0], rids[2], rids[3], kid)
        ]

    single = drive(make_single(model, params))
    sharded = drive(make_sharded(model, params))
    assert single == sharded


def test_sharded_routing_spreads_and_outputs_alias(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params)
    pa, pb = prompts_for(2, (20, 12))
    ra, rb = sess.add_request(pa), sess.add_request(pb)
    # an empty fleet fills deterministically: shard 0 first, then the idle 1
    assert sess.shard_of(ra) == 0 and sess.shard_of(rb) == 1
    sess.step()
    # outputs view shares the shard session's list — stays current in place
    assert sess.outputs[ra] == sess.shards[0].outputs[sess._where[ra][1]]
    assert len(sess.outputs[ra]) == 2  # prefill token + 1 step


def test_sharded_fork_family_stays_on_one_shard(model_and_params):
    """Prefix pages alias within a pool, so every branch of a family must
    land on the parent's shard — and only that shard reports aliasing."""
    model, params = model_and_params
    sess = make_sharded(model, params)
    filler, parent_p = prompts_for(3, (8, 2 * PAGE + 5))
    sess.add_request(filler)          # occupies shard 0
    parent = sess.add_request(parent_p)  # routed to the idle shard 1
    home = sess.shard_of(parent)
    assert home == 1
    kids = [
        sess.admit_with_prefix(parent, s, prefix_len=PAGE)
        for s in prompts_for(4, (4, 7))
    ]
    twin = sess.fork(parent)
    assert {sess.shard_of(r) for r in kids + [twin]} == {home}
    per_shard = [s.cache.num_aliased_pages() for s in sess.shards]
    assert per_shard[home] > 0
    assert sum(per_shard) == per_shard[home]  # no aliasing anywhere else


def test_sharded_admission_eviction_churn(model_and_params):
    """Tight per-shard pools: admission parks on None when every shard is
    full, and finishing a request frees pages on its own shard only."""
    model, params = model_and_params
    # 2 shards x 2 pages of 16: a 20-token prompt fills a whole shard pool
    sess = make_sharded(model, params, num_pages=4)
    pa, pb, pc = prompts_for(5, (20, 20, 20))
    ra, rb = sess.add_request(pa), sess.add_request(pb)
    assert sess.shard_of(ra) != sess.shard_of(rb)
    assert sess.add_request(pc) is None  # both pools lack 2+ free pages
    out = sess.finish(ra)
    assert out  # tokens survive retirement
    frees = [s.cache.num_free_pages for s in sess.shards]
    assert frees[sess.shard_of(rb)] < max(frees)  # only ra's shard drained
    rc = sess.add_request(pc)
    assert rc is not None and sess.shard_of(rc) != sess.shard_of(rb)


def test_sharded_add_request_validation(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params, num_pages=8, max_batch=2)
    with pytest.raises(ValueError, match="at least one prompt token"):
        sess.add_request([])
    # 4 pages per shard pool: a 5-page prompt can never live on ONE shard
    with pytest.raises(ValueError, match="ONE shard"):
        sess.add_request(prompts_for(6, (4 * PAGE + 1,))[0])
    pa, pb, pc = prompts_for(6, (6, 6, 6))
    assert sess.add_request(pa) is not None
    assert sess.add_request(pb) is not None
    assert sess.add_request(pc) is None  # global max_batch, pages to spare


def test_sharded_constructor_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="not both"):
        ShardedPagedServingSession(
            model, params, num_pages=8, mesh=object(), shards=2
        )
    with pytest.raises(ValueError, match="split evenly"):
        make_sharded(model, params, num_pages=9, shards=2)


def test_sharded_work_stats_aggregate(model_and_params):
    model, params = model_and_params
    sess = make_sharded(model, params)
    for p in prompts_for(7, (10, 18, 7)):
        assert sess.add_request(p) is not None
    for _ in range(4):
        sess.step()
    work = sess.work_stats()
    assert len(work["per_shard"]) == 2
    for key in ("page_dmas", "rows_attended", "decode_steps"):
        assert work[key] == sum(st[key] for st in work["per_shard"])
    assert work["balance"]["imbalance"] >= 1.0
    stats = sess.scheduler_stats
    assert stats["hits"] + stats["rebuilds"] >= 4
    assert sess.prefill_compiles == 1  # all shards trace one chunk shape


# --------------------------------------------------------------------------- #
# real CPU mesh (subprocess: XLA_FLAGS must precede jax init)
# --------------------------------------------------------------------------- #


def run_script(args, timeout=560, extra_env=None):
    r = subprocess.run(
        [sys.executable] + args,
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src", **(extra_env or {})},
        cwd=".",
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


@pytest.mark.slow
def test_example_serve_sharded_real_mesh():
    """4 forced CPU devices, 2x2 mesh, TP head chunks: the example asserts
    exact greedy parity and single-shard fork families internally."""
    out = run_script(["examples/serve_sharded.py"])
    assert "greedy parity" in out and "shard work balance" in out
    assert "OK" in out


@pytest.mark.slow
def test_serve_driver_mesh_backend():
    """The --mesh path forces its own host devices from argv before jax
    initializes, so a clean environment still gets a real 2-device mesh."""
    out = run_script(
        [
            "-m", "repro.launch.serve", "--cache", "paged", "--smoke",
            "--interpret",
            "--mesh", "2x1", "--requests", "2", "--gen-len", "3",
            "--batch", "2",
        ]
    )
    assert "sharded over 2x1" in out and "served 2 requests" in out
    assert "shard work balance" in out


def test_serve_driver_mesh_requires_paged():
    r = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.serve",
            "--mesh", "2x1", "--smoke",
        ],
        capture_output=True,
        text=True,
        timeout=560,
        env={**os.environ, "PYTHONPATH": "src"},
        cwd=".",
    )
    assert r.returncode != 0
    assert "--mesh needs --cache paged" in (r.stdout + r.stderr)
