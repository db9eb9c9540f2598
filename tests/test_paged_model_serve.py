"""Full-model paged serving: dense-vs-paged greedy parity + backend plumbing.

The acceptance suite for the cache-backend abstraction: the same model
served through :class:`PagedServingSession` (LayeredPagedKVCache + AMLA
paged kernels) must emit **exactly** the greedy tokens of the dense
:class:`ServingSession` across ragged prompts, mid-stream admit/evict, and
forked shared-prefix families — and the paged path must build exactly one
decode schedule per step (never per layer), which the scheduler-stats
assertions pin.

Everything runs the deepseek-v2-mla smoke geometry (fp32, 2 layers) in
interpret mode; the paged kernels run at fp32 compute precision there so
argmax parity with the dense fp32 path is bit-meaningful.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model_zoo import build_model
from repro.runtime.kv_cache import LayeredPagedKVCache
from repro.runtime.serve_loop import PagedServingSession, ServingSession

CFG = get_config("deepseek-v2-mla", smoke=True)
PAGE, BLOCK_K, CHUNK = 16, 32, 16


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_paged(model, params, **kw):
    kw.setdefault("num_pages", 48)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("block_k", BLOCK_K)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("interpret", True)
    return PagedServingSession(model, params, **kw)


def prompts_for(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, CFG.vocab_size, size=n).tolist() for n in lengths]


# --------------------------------------------------------------------------- #
# dense vs paged parity
# --------------------------------------------------------------------------- #


def test_parity_ragged_prompts(model_and_params):
    """Greedy tokens match the dense backend exactly for >= 8 steps across
    ragged prompt lengths (page-aligned, non-aligned, multi-chunk)."""
    model, params = model_and_params
    prompts = prompts_for(0, (5, 16, 9, 23))
    dense = ServingSession(model, params, batch_size=4, max_len=128)
    paged = make_paged(model, params)
    drids = [dense.add_request(p) for p in prompts]
    prids = [paged.add_request(p) for p in prompts]
    assert None not in prids
    for _ in range(8):
        dense.step()
        paged.step()
    for dr, pr in zip(drids, prids):
        assert dense.outputs[dr] == paged.outputs[pr]
        assert len(paged.outputs[pr]) == 9  # prefill token + 8 steps


def test_parity_mid_stream_admit_evict(model_and_params):
    """Finishing a request mid-stream and admitting a new one onto its
    recycled pages leaves every request's tokens identical to dense."""
    model, params = model_and_params
    # Greedy-exact parity is near-tie sensitive: the dense (XLA softmax)
    # and paged (AMLA exp2 accumulation) paths agree to fp32 attention
    # noise, so a top-2 logit gap below ~1e-2 on this untrained model can
    # flip argmax.  The fixed seed keeps every step's gap comfortably wide
    # (seed 1 has one such tie at step 11; seeds 2-11 are all clean).
    pa, pb, pc = prompts_for(2, (20, 12, 40))
    dense = ServingSession(model, params, batch_size=2, max_len=128)
    # Tight pool (5 pages of 16): a(2) + b(1) leave 2 free, c needs 3 — it
    # can only admit onto a's recycled pages.
    paged = make_paged(model, params, num_pages=5)
    da, db = dense.add_request(pa), dense.add_request(pb)
    ra, rb = paged.add_request(pa), paged.add_request(pb)
    # pool too small for the third request while a+b hold it
    assert paged.add_request(pc) is None
    for _ in range(3):
        dense.step()
        paged.step()
    out_da, out_ra = dense.finish(da), paged.finish(ra)
    assert out_da == out_ra
    free_before = paged.cache.num_free_pages
    dc, rc = dense.add_request(pc), paged.add_request(pc)
    assert rc is not None and paged.cache.num_free_pages < free_before
    for _ in range(8):
        dense.step()
        paged.step()
    assert dense.outputs[db] == paged.outputs[rb]
    assert dense.outputs[dc] == paged.outputs[rc]


@pytest.mark.parametrize("prefix_sharing", [False, True])
def test_parity_forked_shared_prefix_pair(model_and_params, prefix_sharing):
    """A forked pair (shared system prompt, divergent suffixes) matches two
    independent dense requests over the concatenated prompts — through page
    aliasing, COW on the boundary page, and (parametrized) the
    group-batched prefix attention path."""
    model, params = model_and_params
    rng = np.random.default_rng(2)
    prefix = rng.integers(2, CFG.vocab_size, size=2 * BLOCK_K + 5).tolist()
    suf_a = rng.integers(2, CFG.vocab_size, size=6).tolist()
    suf_b = rng.integers(2, CFG.vocab_size, size=11).tolist()

    dense = ServingSession(model, params, batch_size=2, max_len=160)
    da = dense.add_request(prefix + suf_a)
    db = dense.add_request(prefix + suf_b)

    paged = make_paged(model, params, prefix_sharing=prefix_sharing)
    ra = paged.add_request(prefix + suf_a)
    rb = paged.admit_with_prefix(ra, suf_b, prefix_len=len(prefix))
    assert rb is not None
    assert paged.cache.num_aliased_pages() > 0  # prefix pages shared
    for _ in range(8):
        dense.step()
        paged.step()
    assert dense.outputs[da] == paged.outputs[ra]
    assert dense.outputs[db] == paged.outputs[rb]
    if prefix_sharing:
        # the group-batched path actually deduplicated prefix DMAs
        assert paged.page_dmas < paged.rows_attended // PAGE


def test_full_fork_twins_stay_identical(model_and_params):
    """fork() twins share every page; greedy decode keeps them identical
    while COW gives each a private boundary page on first append."""
    model, params = model_and_params
    prompt = prompts_for(3, (20,))[0]
    paged = make_paged(model, params)
    parent = paged.add_request(prompt)
    child = paged.fork(parent)
    assert paged.outputs[child] == paged.outputs[parent]
    aliased = paged.cache.num_aliased_pages()
    assert aliased == len(paged.cache.seq_pages(parent))
    for _ in range(4):
        paged.step()
    assert paged.outputs[child] == paged.outputs[parent]
    # boundary page was COW'd: twins no longer alias every page
    assert paged.cache.seq_pages(child) != paged.cache.seq_pages(parent)


def test_int8_cache_greedy_parity_and_bytes(model_and_params):
    """ISSUE-5 acceptance: the full-model session with an int8 latent cache
    emits the same greedy tokens as the bf16-cache session on the
    parity-seeded smoke model (int8 quantization noise ~1e-3 per attention
    output is far inside the model's top-2 logit gaps), while the
    dtype-aware page-DMA bytes proxy shows the ~2x storage win."""
    model, params = model_and_params
    prompts = prompts_for(0, (5, 16, 9))
    sessions = {
        name: make_paged(model, params, kv_dtype=name)
        for name in ("bf16", "int8")
    }
    rids = {
        name: [s.add_request(p) for p in prompts]
        for name, s in sessions.items()
    }
    for _ in range(6):
        for s in sessions.values():
            s.step()
    for rb, ri in zip(rids["bf16"], rids["int8"]):
        assert sessions["bf16"].outputs[rb] == sessions["int8"].outputs[ri]
    b16 = sessions["bf16"].work_stats()
    i8 = sessions["int8"].work_stats()
    assert b16["page_dmas"] == i8["page_dmas"]  # same schedule, same DMAs
    # Bytes the page DMAs move: 80-wide rows occupy one 128-lane tile, and
    # an int8 page's scales ride in one lane-padded fp32 row.  At this
    # 16-row page that row costs a fifth of the int8 data (the 576-wide
    # serving geometry keeps the ~2x win, tests/test_quantized_cache.py).
    assert b16["page_dma_bytes"] == b16["page_dmas"] * PAGE * 128 * 2
    assert i8["page_dma_bytes"] == i8["page_dmas"] * (PAGE * 128 + 128 * 4)
    assert b16["page_dma_bytes"] / i8["page_dma_bytes"] == 1.6
    assert sessions["int8"].cache.quantized


def test_int8_cache_forked_prefix_parity(model_and_params):
    """int8 + shared-prefix fork: the aliased-page family (group-batched
    prefix attention, COW on divergence) matches the bf16 cache greedily —
    the quantized pool composes with every PR-3/PR-4 sharing feature."""
    model, params = model_and_params
    rng = np.random.default_rng(2)
    prefix = rng.integers(2, CFG.vocab_size, size=2 * BLOCK_K + 5).tolist()
    suffix = rng.integers(2, CFG.vocab_size, size=7).tolist()
    outs = {}
    for name in ("bf16", "int8"):
        s = make_paged(model, params, kv_dtype=name, prefix_sharing=True)
        ra = s.add_request(prefix)
        rb = s.admit_with_prefix(ra, suffix, prefix_len=len(prefix))
        assert rb is not None and s.cache.num_aliased_pages() > 0
        for _ in range(6):
            s.step()
        outs[name] = (s.outputs[ra], s.outputs[rb])
    assert outs["bf16"] == outs["int8"]


# --------------------------------------------------------------------------- #
# schedule reuse: once per step, never per layer
# --------------------------------------------------------------------------- #


def test_one_schedule_per_step_not_per_layer(model_and_params):
    """hits + rebuilds == decode steps: with L layers sharing the block
    table, a per-layer scheduler would count L lookups per step."""
    model, params = model_and_params
    paged = make_paged(model, params)
    for p in prompts_for(4, (6, 14)):
        paged.add_request(p)
    n_steps = 8
    for _ in range(n_steps):
        paged.step()
    stats = paged.scheduler_stats
    assert stats["hits"] + stats["rebuilds"] == n_steps
    # and the schedule is genuinely reused across steps within a block
    assert stats["rebuilds"] < n_steps
    assert CFG.n_layers > 1  # the assertion above would be vacuous at L=1


def test_scheduler_invalidates_on_churn(model_and_params):
    """Evict + admit between steps forces a rebuild (live-rid extra_key)."""
    model, params = model_and_params
    paged = make_paged(model, params)
    pa, pb = prompts_for(5, (8, 8))
    ra = paged.add_request(pa)
    paged.step()
    rebuilds0 = paged.scheduler_stats["rebuilds"]
    paged.finish(ra)
    paged.add_request(pb)  # same block signature, different request
    paged.step()
    assert paged.scheduler_stats["rebuilds"] == rebuilds0 + 1


# --------------------------------------------------------------------------- #
# session bookkeeping satellites
# --------------------------------------------------------------------------- #


def test_dense_finish_resets_slot_state(model_and_params):
    """A freed slot's last_token is cleared and its cache_len zeroed, so a
    recycled slot can never decode from the previous request's token."""
    model, params = model_and_params
    sess = ServingSession(model, params, batch_size=1, max_len=64)
    p1, p2 = prompts_for(6, (9, 9))
    r1 = sess.add_request(p1)
    sess.step()
    sess.finish(r1)
    assert sess.last_token[0] == 0
    assert sess.cache_len[0] == 0
    # the recycled slot serves the new request exactly like a fresh session
    r2 = sess.add_request(p2)
    for _ in range(4):
        sess.step()
    got = sess.finish(r2)
    fresh = ServingSession(model, params, batch_size=1, max_len=64)
    f2 = fresh.add_request(p2)
    for _ in range(4):
        fresh.step()
    assert got == fresh.finish(f2)


def test_dense_prefill_bucketing_compile_count(model_and_params):
    """Ragged prompt lengths collapse into power-of-two buckets: lengths
    {5, 6, 7} share one prefill shape, {9} adds a second."""
    model, params = model_and_params
    sess = ServingSession(model, params, batch_size=4, max_len=64)
    for p in prompts_for(7, (5, 6, 7, 9)):
        sess.add_request(p)
    assert sess.prefill_compiles == 2
    assert sess._prefill_shapes == {8, 16}


def test_paged_prefill_single_chunk_shape(model_and_params):
    """Fixed-chunk prefill-into-pages compiles exactly one shape no matter
    how ragged the prompt stream is."""
    model, params = model_and_params
    paged = make_paged(model, params)
    for p in prompts_for(8, (3, 17, 33)):
        assert paged.add_request(p) is not None
    assert paged.prefill_compiles == 1


def test_paged_add_request_prompt_validation(model_and_params):
    """Paged admission distinguishes 'no room right now' (None — caller
    retries after evictions) from 'can never fit' (raise — even an empty
    pool lacks the pages), and rejects empty prompts outright."""
    model, params = model_and_params
    paged = make_paged(model, params, num_pages=4)  # 64 rows total
    with pytest.raises(ValueError, match="at least one prompt token"):
        paged.add_request([])
    with pytest.raises(ValueError, match="never be admitted"):
        paged.add_request(prompts_for(9, (4 * PAGE + 1,))[0])
    # a pool-filling prompt is legal; the *next* one gets a retryable None
    assert paged.add_request(prompts_for(9, (4 * PAGE,))[0]) is not None
    assert paged.add_request(prompts_for(9, (PAGE,))[0]) is None


def test_paged_incompatible_arch_rejected(model_and_params):
    del model_and_params
    gqa = build_model(get_config("qwen1.5-0.5b", smoke=True))
    with pytest.raises(ValueError, match="MLA"):
        PagedServingSession(gqa, None, num_pages=8)


# --------------------------------------------------------------------------- #
# LayeredPagedKVCache unit coverage
# --------------------------------------------------------------------------- #


def test_layered_cache_shared_bookkeeping_per_layer_data():
    """One reserve covers all layers' writes; fork/COW/free happen once per
    request while every layer keeps distinct row data."""
    kv = LayeredPagedKVCache(
        num_layers=3, num_pages=6, page_size=4, width=8, dtype=jnp.float32
    )
    kv.alloc(0)
    plan = kv.reserve(0, 6)  # spans two pages
    assert [m for _, _, m in plan] == [4, 2]
    for layer in range(3):
        rows = np.full((6, 8), float(layer + 1), np.float32)
        kv.write_layer(layer, plan, rows)
    got = np.asarray(kv.gather_contiguous(0))  # (L, n, W)
    assert got.shape == (3, 6, 8)
    for layer in range(3):
        assert np.all(got[layer] == layer + 1)

    # fork aliases pages once for all layers; COW copies all planes at once
    kv.fork(0, 1)
    assert kv.num_aliased_pages() == 2
    free_before = kv.num_free_pages
    plan1 = kv.reserve(1, 1)  # boundary page shared -> COW + in-place write
    kv.write_layer_tokens(1, [plan1[0][0]], [plan1[0][1]], np.zeros((1, 8)))
    assert kv.num_free_pages == free_before - 1  # exactly the COW page
    parent = np.asarray(kv.gather_contiguous(0))
    assert parent.shape == (3, 6, 8)  # parent rows untouched by child COW
    for layer in range(3):
        assert np.all(parent[layer] == layer + 1)

    # free is refcount-aware: parent release keeps the still-shared pages
    kv.free(0)
    assert kv.seq_len(1) == 7
    assert np.asarray(kv.gather_contiguous(1, layer=2))[:4].max() == 3.0


def test_layered_cache_append_all_layers():
    kv = LayeredPagedKVCache(
        num_layers=2, num_pages=4, page_size=4, width=8, dtype=jnp.float32
    )
    kv.alloc(7)
    rows = np.arange(2 * 5 * 8, dtype=np.float32).reshape(2, 5, 8)
    kv.append(7, rows)
    np.testing.assert_allclose(np.asarray(kv.gather_contiguous(7)), rows)
    with pytest.raises(ValueError, match="rows must be"):
        kv.append(7, np.zeros((5, 8), np.float32))
