"""The shape of the paged layer walk (``models.transformer._paged_pass``).

A pass of the walk -- a decode step, or one prefill chunk -- uploads its
inputs once, then per layer launches one program up to the layer's
kernels, the queue kernel and the combine, and one program after the last
layer; it reads nothing back from the device.  The kernels address a layer
of the stacked ``(L, P, page, lanes)`` pool in place: no program copies a
layer out of it, and the per-layer program writes into it donated.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.tracing import Tracer
from repro.kernels import decode_schedule as sched
from repro.kernels import mla_decode_paged as paged
from repro.kernels import ops
from repro.models import transformer as tf
from repro.models.model_zoo import build_model
from repro.runtime.kv_cache import CacheSpec, LayeredPagedKVCache
from repro.runtime.serve_loop import (
    SERVE_COUNTERS,
    SERVE_SPANS,
    PagedServingSession,
)

CFG = get_config("deepseek-v2-mla", smoke=True)
L = CFG.n_layers
PAGE, BLOCK_K, CHUNK, PAGES = 16, 32, 16, 24


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CFG)
    return model, model.init(jax.random.PRNGKey(0))


def session(model_and_params, **kw):
    model, params = model_and_params
    return PagedServingSession(
        model, params, num_pages=PAGES, page_size=PAGE, block_k=BLOCK_K,
        prefill_chunk=CHUNK, interpret=True, **kw,
    )


def walk_kw(sess):
    return dict(
        cfg=sess.cfg, cache=sess.cache, table_width=sess.table_width,
        block_k=sess.block_k, interpret=True, layer_params=sess._layers,
        compute_dtype=sess.compute_dtype,
    )


def traced(fn):
    """Run ``fn`` with a fresh tracer current; its stats and result."""
    tracer = Tracer(SERVE_SPANS, SERVE_COUNTERS)
    with tracer.active():
        out = fn()
    return tracer.stats(), out


def prompt(n, seed=0):
    return np.random.default_rng(seed).integers(2, CFG.vocab_size, size=n)


@pytest.mark.parametrize(
    "live,batch_rows,rows",
    [
        # 3 live rows padded to 4: the logits keep the pad row.
        pytest.param(3, 4, 4, id="padded-batch"),
        pytest.param(2, None, 2, id="live-batch"),
    ],
)
def test_a_decode_step_launches_3L_plus_2_programs_at_most(
    model_and_params, live, batch_rows, rows
):
    sess = session(model_and_params)
    params = sess.params
    rids = []
    for i in range(live):
        sess.cache.alloc(i)
        tf.lm_prefill_paged(
            params, prompt(CHUNK + 3 * i, i), rid=i, chunk=CHUNK,
            **walk_kw(sess),
        )
        rids.append(i)
    tokens = np.arange(live, dtype=np.int32)[:, None] + 5
    st, logits = traced(lambda: tf.lm_decode_step_paged(
        params, tokens, rids=rids, batch_rows=batch_rows, **walk_kw(sess)
    ))
    assert logits.shape == (rows, 1, CFG.vocab_size)
    assert st["walk_dispatches"] == 3 * L + 1 <= 3 * L + 2
    assert st["span_n.serve.sync"] == 0
    assert st["span_n.serve.schedule"] == 1


@pytest.mark.parametrize(
    "n,need_logits,launches",
    [
        pytest.param(CHUNK, True, 3 * L + 1, id="last-chunk"),
        pytest.param(CHUNK - 5, True, 3 * L + 1, id="ragged-last-chunk"),
        pytest.param(CHUNK, False, 3 * L, id="chunk-without-logits"),
        pytest.param(3 * CHUNK, True, 3 * (3 * L) + 1, id="three-chunks"),
    ],
)
def test_a_prefill_chunk_launches_3L_plus_2_programs_at_most(
    model_and_params, n, need_logits, launches
):
    sess = session(model_and_params)
    sess.cache.alloc(0)
    st, logits = traced(lambda: tf.lm_prefill_paged(
        sess.params, prompt(n), rid=0, chunk=CHUNK, need_logits=need_logits,
        **walk_kw(sess),
    ))
    chunks = -(-n // CHUNK)
    assert st["walk_dispatches"] == launches <= chunks * (3 * L + 2)
    assert st["span_n.serve.sync"] == 0
    assert st["span_n.serve.schedule"] == chunks
    assert (logits is None) == (not need_logits)
    if need_logits:
        assert logits.shape == (1, CFG.vocab_size)


def test_a_prefix_schedule_adds_the_group_prefix_kernel_per_layer(
    model_and_params,
):
    """Under the trie the decode step's schedule groups the requests that
    share a prompt block: each layer adds the group-prefix kernel."""
    sess = session(model_and_params, prefix_cache="trie", max_batch=4)
    shared = list(prompt(2 * BLOCK_K))
    for i in range(2):
        sess.add_request(shared + [7 + i])
    sess.step()
    assert isinstance(sess._scheduler.current, sched.PrefixSchedule)
    assert sess._scheduler.current.num_groups == 1
    d0 = sess.work_stats()["walk_dispatches"]
    sess.step()
    assert sess.work_stats()["walk_dispatches"] - d0 == 4 * L + 1


# --------------------------------------------------------------------------- #
# a layer of the stacked pool, addressed in place
# --------------------------------------------------------------------------- #


def stacked_cache(kv_dtype, seed=0):
    """A 3-layer pool whose layers hold different rows: a parent of 40
    rows and two children forked at one shared block, each with its own
    suffix."""
    rng = np.random.default_rng(seed)
    kv = LayeredPagedKVCache(
        num_layers=3, num_pages=PAGES, page_size=PAGE, width=64,
        spec=CacheSpec.from_name(kv_dtype),
    )

    def rows(n):
        return rng.normal(0, 0.5, (3, n, 64)).astype(np.float32)

    kv.alloc(0)
    kv.append(0, rows(40))
    for child, n in ((1, 9), (2, 21)):
        kv.fork(0, child, BLOCK_K)
        kv.append(child, rows(n))
    return kv, [0, 1, 2]


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("path", ["plain", "group-prefix"])
def test_a_layer_of_the_stacked_pool_reads_as_that_layer_alone(kv_dtype, path):
    kv, rids = stacked_cache(kv_dtype)
    bt, kv_len = kv.block_table(rids)
    if path == "plain":
        schedule = sched.build_schedule(kv_len, block_k=BLOCK_K, num_splits=2)
    else:
        schedule = sched.build_prefix_schedule(
            kv_len, bt, page_size=PAGE, block_k=BLOCK_K
        )
        assert schedule.num_groups == 1
    q = jax.random.normal(jax.random.PRNGKey(1), (len(rids), 1, 4, 64))
    kw = dict(d_v=32, scale=0.125, block_k=BLOCK_K, schedule=schedule,
              interpret=True)
    outs = []
    for layer in range(3):
        stacked = ops.mla_decode_paged(
            q, kv.pages, bt, kv_len, kv_scales=kv.scales, layer=layer, **kw
        )
        alone = ops.mla_decode_paged(
            q, kv.pages[layer], bt, kv_len,
            kv_scales=None if kv.scales is None else kv.scales[layer], **kw
        )
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(alone))
        outs.append(np.asarray(stacked))
    assert not np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[1], outs[2])


def test_the_kernels_take_a_layer_only_with_a_stacked_pool():
    kv, rids = stacked_cache("bf16")
    bt, kv_len = kv.block_table(rids)
    q = jnp.zeros((len(rids), 1, 4, 64))
    kw = dict(d_v=32, scale=0.125, block_k=BLOCK_K, interpret=True)
    with pytest.raises(ValueError, match="needs the layer"):
        ops.mla_decode_paged(q, kv.pages, bt, kv_len, **kw)
    with pytest.raises(ValueError, match="one-layer"):
        ops.mla_decode_paged(q, kv.pages[0], bt, kv_len, layer=0, **kw)


# --------------------------------------------------------------------------- #
# what the walk's programs lower to
# --------------------------------------------------------------------------- #


def _hlo(jitted, *args, **kw):
    return jitted.lower(*args, **kw).compile().as_text()


def test_the_walk_copies_no_layer_of_the_pool_and_donates_it(model_and_params):
    """No program of a decode pass has a result shaped like one layer's pool
    ``(P, page, lanes)``, and the per-layer program aliases the pool it is
    given to the pool it returns."""
    sess = session(model_and_params, prefix_cache="trie", max_batch=4)
    cache, params = sess.cache, sess.params
    shared = list(prompt(2 * BLOCK_K))
    rids = [sess.add_request(shared + [7 + i]) for i in range(2)]
    sess.step()
    layer_shape = re.compile(
        r"\[%d,%d,%d\]" % (PAGES, PAGE, cache.pages.shape[-1])
    )
    bt, kv_len = cache.block_table(rids, width=sess.table_width)
    schedule = sess._scheduler.current
    arrays = jax.tree.map(jnp.asarray, ops.schedule_arrays(schedule, rows=4))
    b, s, lanes = 4, 1, cache.pages.shape[-1]
    dense = jnp.zeros((4, b, s), jnp.int32)
    layer = cache.layer_ids()[1]
    heads = CFG.n_heads
    q_rows = jnp.zeros((b, s * heads, lanes), jnp.float32)
    rows_pos = jnp.zeros((b, s * heads), jnp.int32)
    attn = (jnp.zeros((b, s * heads, CFG.mla.d_latent), jnp.float32),)
    x = jnp.zeros((b, s, CFG.d_model), jnp.float32)
    statics = dict(cfg=CFG, compute_dtype=sess.compute_dtype, head_shards=1)
    first = _hlo(
        tf._paged_layer_in, params["embed"], None, sess._layers[0], None,
        None, dense, cache.pages, cache.scales, layer, **statics,
    )
    later = _hlo(
        tf._paged_layer_in, None, sess._layers[0], sess._layers[1], x, attn,
        dense, cache.pages, cache.scales, layer, **statics,
    )
    kw = dict(d_v=CFG.mla.d_latent, scale=0.1, block_k=BLOCK_K,
              interpret=True)
    queue = _hlo(
        paged.mla_decode_paged_queue_rows, q_rows, cache.pages,
        jnp.asarray(bt), jnp.asarray(kv_len), rows_pos,
        *arrays["suffix"], None, layer,
        num_dest_slots=schedule.suffix.num_dest_slots, **kw,
    )
    group = _hlo(
        paged.mla_decode_paged_group_prefix, q_rows, cache.pages,
        jnp.asarray(bt), rows_pos, arrays["group_member"],
        arrays["group_rep"], arrays["prefix_lens"], *arrays["prefix"], None,
        layer, num_dest_slots=schedule.prefix.num_dest_slots, **kw,
    )
    head = _hlo(
        tf._paged_head, params, sess._layers[1], x, attn, None, cfg=CFG
    )
    for text in (first, later, queue, group, head):
        assert not layer_shape.search(text)
    pool = r"\[%d,%d,%d,%d\]" % ((L,) + cache.pages.shape[1:])
    for text in (first, later):
        # The fourth output (the pool) is written into the buffer of the
        # pool parameter.
        alias = re.search(r"input_output_alias=\{[^\n]*\{3\}: \((\d+),", text)
        assert alias, "the pool is not donated"
        assert re.search(pool + r"\S* parameter\(%s\)" % alias.group(1), text)
