"""Compile-only checks of the decode kernels for a described TPU v5e.

The TPU compiler refuses what interpret mode accepts: vector loads from
scalar memory, DMA slices that are not whole 128-lane tiles, kernels that
outgrow VMEM.  These tests lower and compile the serving path's kernels at
the deepseek-v2-mla widths (Dk = 576, Dv = 512, 128 heads), and the dense
path's contiguous MLA and GQA decode kernels at repo models' widths, for one
chip of a described ``v5e:2x2`` topology; nothing runs, so no chip is
needed.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import decode_schedule as sched
from repro.kernels import mla_decode as contiguous
from repro.kernels import mla_decode_combine as combine
from repro.kernels import mla_decode_paged as paged
from repro.kernels import ops
from repro.kernels.mla_decode import lane_pad
from repro.models.mla_layer import init_latent_cache
from repro.runtime.kv_cache import pool_shape, scale_pool_shape

HEADS, DK, DV = 128, 576, 512
PAGE, BLOCK_K = 32, 512
NUM_PAGES = 2048
SCALE = 192**-0.5


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Lower and compile for the described chip; fails as the chip would."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _decode_schedule(kv_lens):
    return sched.build_schedule(
        np.asarray(kv_lens, np.int32), block_k=BLOCK_K, num_splits=2
    )


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_queue_decode_compiles(one_chip, kv_dtype):
    """The serving decode step: queue kernel + split combine through ops,
    on the lane-padded pool the cache allocates."""
    spec = paged.CacheSpec.from_name(kv_dtype)
    kv_lens = [2080, 300, 1500, 33, 2048, 900, 1024, 256]
    schedule = _decode_schedule(kv_lens)
    b, width = len(kv_lens), NUM_PAGES // 8

    def step(q, pages, scales, bt, kv_len):
        return ops.mla_decode_paged(
            q, pages, bt, kv_len, kv_scales=scales, d_v=DV, scale=SCALE,
            block_k=BLOCK_K, schedule=schedule,
        )

    s = one_chip
    scales = (
        _spec(s, scale_pool_shape(NUM_PAGES, PAGE), jnp.float32)
        if spec.quantized
        else None
    )
    _compile(
        step,
        _spec(s, (b, 1, HEADS, DK), jnp.bfloat16),
        _spec(s, pool_shape(NUM_PAGES, PAGE, DK), spec.dtype),
        scales,
        _spec(s, (b, width), jnp.int32),
        _spec(s, (b,), jnp.int32),
    )


def test_group_prefix_compiles(one_chip):
    """Shared-prefix pass: 8 members' rows (1024) stacked over one group's
    pages, more than one row tile, still one DMA per page."""
    s = one_chip
    b, n_groups, gmax, items = 8, 2, 8, 8
    width = NUM_PAGES // 8

    def group(q, pages, bt, q_pos, member, rep, plen, *queue):
        return paged.mla_decode_paged_group_prefix(
            q, pages, bt, q_pos, member, rep, plen, *queue,
            scale=SCALE, block_k=BLOCK_K, num_dest_slots=n_groups,
        )

    _compile(
        group,
        _spec(s, (b, HEADS, lane_pad(DK)), jnp.bfloat16),
        _spec(s, pool_shape(NUM_PAGES, PAGE, DK), jnp.bfloat16),
        _spec(s, (b, width), jnp.int32),
        _spec(s, (b, HEADS), jnp.int32),
        _spec(s, (n_groups, gmax), jnp.int32),
        _spec(s, (n_groups,), jnp.int32),
        _spec(s, (n_groups,), jnp.int32),
        *[_spec(s, (items,), jnp.int32)] * 6,
    )


def test_combine_compiles(one_chip):
    s = one_chip
    b, splits = 8, 4
    _compile(
        lambda o, lse, dest, n: combine.combine_split_partials(o, lse, dest, n),
        _spec(s, (b * splits, HEADS, DV), jnp.float32),
        _spec(s, (b * splits, HEADS, 1), jnp.float32),
        _spec(s, (b, splits), jnp.int32),
        _spec(s, (b,), jnp.int32),
    )


def test_prefill_rows_fit_vmem(one_chip):
    """A 32-token prefill chunk at 128 heads is 4096 query rows: more than
    the default scoped VMEM holds.  The queue kernel keeps them all in one
    program (one DMA per page), updates them in row tiles, and asks for the
    VMEM it needs."""
    n_tiles, tile_g = contiguous.row_tiling(32 * HEADS)
    assert n_tiles > 1
    assert contiguous.vmem_limit_bytes(
        n_tiles * tile_g, tile_g, d_k=lane_pad(DK), d_v=DV, block_k=BLOCK_K,
        q_dtype=jnp.bfloat16, kv_dtype=jnp.bfloat16,
    ) > contiguous.DEFAULT_SCOPED_VMEM
    schedule = _decode_schedule([2048])

    def prefill(q, pages, bt, kv_len, q_off):
        return ops.mla_decode_paged(
            q, pages, bt, kv_len, d_v=DV, scale=SCALE, q_offset=q_off,
            block_k=BLOCK_K, schedule=schedule,
        )

    s = one_chip
    _compile(
        prefill,
        _spec(s, (1, 32, HEADS, DK), jnp.bfloat16),
        _spec(s, pool_shape(NUM_PAGES, PAGE, DK), jnp.bfloat16),
        _spec(s, (1, NUM_PAGES // 8), jnp.int32),
        _spec(s, (1,), jnp.int32),
        _spec(s, (1,), jnp.int32),
    )


# The v2-agent-8k cell's pool: 4 layers of 7168 pages of 32 rows.
CELL_LAYERS, CELL_PAGES = 4, 7168


@pytest.mark.parametrize(
    "batch,rows", [(16, HEADS), (1, 32 * HEADS)], ids=["decode-16", "prefill-chunk"]
)
def test_stacked_pool_queue_kernel_compiles(one_chip, batch, rows):
    """The queue kernel on one layer of the cell's stacked pool, addressed
    in place (no layer is copied out): a decode step of 16 requests and a
    32-token prefill chunk at 128 heads."""
    kv_lens = [8192 + 131 * i for i in range(batch)]
    schedule = sched.build_schedule(
        np.asarray(kv_lens, np.int32), block_k=BLOCK_K, num_splits=1
    )
    s = one_chip

    def kernel(q, pages, bt, kv_len, q_pos, layer, *queue):
        return paged.mla_decode_paged_queue_rows(
            q, pages, bt, kv_len, q_pos, *queue, None, layer, d_v=DV,
            scale=SCALE, block_k=BLOCK_K,
            num_dest_slots=schedule.num_dest_slots,
        )

    compiled = _compile(
        kernel,
        _spec(s, (batch, rows, lane_pad(DK)), jnp.bfloat16),
        _spec(
            s, (CELL_LAYERS,) + pool_shape(CELL_PAGES, PAGE, DK), jnp.bfloat16
        ),
        _spec(s, (batch, CELL_PAGES), jnp.int32),
        _spec(s, (batch,), jnp.int32),
        _spec(s, (batch, rows), jnp.int32),
        _spec(s, (), jnp.int32),
        *[_spec(s, (schedule.queue_len,), jnp.int32)] * 6,
    )
    one_layer = "[%d,%d,%d]" % pool_shape(CELL_PAGES, PAGE, DK)
    assert one_layer not in compiled.as_text()


@pytest.mark.parametrize("batch,s", [(16, 1), (1, 32)], ids=["decode", "prefill"])
def test_fused_layer_program_compiles(one_chip, batch, s):
    """The walk's per-layer program (the previous layer's post-attention
    half, this layer's latents and queries, the write into the donated
    pool, the kernel's query rows) at deepseek-v2 widths."""
    from repro.models import transformer as tf

    cfg = get_config("deepseek-v2-mla")
    sh = one_chip
    p_like = jax.eval_shape(
        lambda: tf.layer_init(jax.random.PRNGKey(0), cfg, "global")
    )
    params = jax.tree.map(lambda a: _spec(sh, a.shape, a.dtype), p_like)
    compiled = (
        tf._paged_layer_in.lower(
            None, params, params,
            _spec(sh, (batch, s, cfg.d_model), jnp.bfloat16),
            (_spec(sh, (batch, s * HEADS, DV), jnp.float32),),
            _spec(sh, (4, batch, s), jnp.int32),
            _spec(
                sh, (CELL_LAYERS,) + pool_shape(CELL_PAGES, PAGE, DK),
                jnp.bfloat16,
            ),
            None,
            _spec(sh, (), jnp.int32),
            cfg=cfg, compute_dtype=None, head_shards=1,
        )
        .compile()
    )
    text = compiled.as_text()
    assert "input_output_alias" in text
    assert "[%d,%d,%d]" % pool_shape(CELL_PAGES, PAGE, DK) not in text


def test_prefill_rows_past_vmem_rejected():
    """A 64-token chunk at 128 heads (8192 rows) would need more VMEM than
    a core has: the queue kernel says so while tracing, before any compile."""
    schedule = _decode_schedule([2048])

    def prefill(q, pages, bt, kv_len, q_off):
        return ops.mla_decode_paged(
            q, pages, bt, kv_len, d_v=DV, scale=SCALE, q_offset=q_off,
            block_k=BLOCK_K, schedule=schedule,
        )

    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(
            prefill,
            jax.ShapeDtypeStruct((1, 64, HEADS, DK), jnp.bfloat16),
            jax.ShapeDtypeStruct(pool_shape(NUM_PAGES, PAGE, DK), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, NUM_PAGES // 8), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        )


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested jit bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            if hasattr(p, "jaxpr"):
                yield from _eqns(getattr(p.jaxpr, "jaxpr", p.jaxpr))


def test_contiguous_mla_decode_compiles(one_chip):
    """The dense path's MLA decode over the cache ``init_latent_cache``
    allocates (rows lane-padded to 640): compiles, and copies no cache."""
    cfg = get_config("deepseek-v2-mla")
    b, max_len = 8, 4096
    cache = jax.eval_shape(lambda: init_latent_cache(cfg, b, max_len))["c"]
    assert cache.shape == (b, max_len, lane_pad(DK))

    def step(q, c, kv_len):
        return ops.mla_decode(q, c, d_v=DV, scale=SCALE, kv_len=kv_len)

    args = (
        jax.ShapeDtypeStruct((b, 1, HEADS, DK), jnp.bfloat16),
        jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        jax.ShapeDtypeStruct((b,), jnp.int32),
    )
    jaxpr = jax.make_jaxpr(step)(*args)
    padded = [
        eqn for eqn in _eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "pad" and eqn.outvars[0].aval.ndim == 3
        and eqn.outvars[0].aval.shape[1] == max_len
    ]
    assert not padded, "the lane-padded cache must reach the kernel uncopied"
    s = one_chip
    _compile(step, *(_spec(s, a.shape, a.dtype) for a in args))


@pytest.mark.parametrize("name", ["gemma2-2b", "internlm2-20b"])
def test_gqa_decode_compiles(one_chip, name):
    """GQA decode at a repo model's widths (kv heads, head_dim, window and
    softcap from its config), 8 requests over a 4096-row cache."""
    cfg = get_config(name)
    b, s_len = 8, 4096

    def step(q, k, v, kv_len):
        return ops.gqa_attention(
            q, k, v, causal=True, window=cfg.window,
            softcap=cfg.attn_softcap, scale=cfg.head_dim**-0.5, kv_len=kv_len,
        )

    s = one_chip
    kv = (b, s_len, cfg.n_kv_heads, cfg.head_dim)
    _compile(
        step,
        _spec(s, (b, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        _spec(s, kv, jnp.bfloat16),
        _spec(s, kv, jnp.bfloat16),
        _spec(s, (b,), jnp.int32),
    )
