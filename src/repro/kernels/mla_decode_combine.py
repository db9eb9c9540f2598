"""Pallas TPU kernel: split-KV combine for work-queue AMLA decode.

Flash-decoding second stage.  The queue kernel
(:func:`repro.kernels.mla_decode_paged.mla_decode_paged_queue_rows`) may
split a long request's KV blocks across several destination slots; each slot
finalizes independently into a *normalized* partial output ``o_i`` and its
log-sum-exp ``lse_i = m_i + log(l_i)``.  Exact recombination is the
softmax-weighted average

    o = sum_i exp(lse_i - M) * o_i / sum_i exp(lse_i - M),   M = max_i lse_i

— independent of how the KV was partitioned, and variant-agnostic (both the
AMLA and base finalizers divide their scaling out before writing partials).

The kernel runs a ``(B, row tiles, num_splits)`` grid, sequential over splits, with the
running ``(acc, m, w)`` combine state in VMEM scratch — the same
scratch-carried-state pattern as the decode kernels, just over split slots
instead of KV blocks.  Each request's partial blocks are fetched via a
scalar-prefetched dest table; split slots past ``n_splits[b]`` are gated off
(their table entries repeat the last live slot, so the pipelined fetch stays
on warm data), and a request with zero live splits (``kv_len == 0``) yields
exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import CompilerParams
from repro.kernels.mla_decode import row_tiling

# Finite stand-in for -inf in the running max so that fully-empty slots
# (lse == -inf) contribute exp(-inf - BIG_NEG) == 0 instead of NaN.
BIG_NEG = -3.0e38


def _combine_kernel(
    # scalar prefetch
    dest_ref,  # (B, S) int32 partial-slot id per request/split (index_map)
    n_splits_ref,  # (B,) int32 live splits per request
    # inputs (blocks selected by dest_ref[b, j])
    o_part_ref,  # (G, Dv) f32 normalized partial
    lse_ref,  # (G, 1) f32 log-sum-exp of that partial
    # output
    out_ref,  # (G, Dv) f32
    # scratch
    acc_ref,  # (G, Dv) f32
    m_ref,  # (G, 1) f32 running max of lse
    w_ref,  # (G, 1) f32 running weight sum
):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, BIG_NEG)
        w_ref[...] = jnp.zeros_like(w_ref)

    @pl.when(j < n_splits_ref[b])
    def _accumulate():
        lse = lse_ref[...]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, lse)
        alpha = jnp.exp(m_prev - m_new)
        w = jnp.exp(lse - m_new)  # 0 for empty partials (lse == -inf)
        acc_ref[...] = acc_ref[...] * alpha + w * o_part_ref[...]
        w_ref[...] = w_ref[...] * alpha + w
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        w = w_ref[...]
        safe = jnp.where(w > 0, w, 1.0)
        out_ref[...] = jnp.where(w > 0, acc_ref[...] / safe, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_split_partials(
    o_part: jax.Array,  # (D, G, Dv) f32 normalized partial outputs
    lse: jax.Array,  # (D, G, 1) f32 log-sum-exp per partial
    dest_table: jax.Array,  # (B, S) int32 slot ids (see decode_schedule)
    n_splits: jax.Array,  # (B,) int32 live splits per request
    *,
    interpret: bool = False,
) -> jax.Array:
    """Merge per-slot split-KV partials into ``(B, G, Dv)`` outputs.

    Rows run in VMEM-sized tiles (``mla_decode.row_tiling``); padded rows
    carry ``lse == -inf`` and are sliced off."""
    d, g, d_v = o_part.shape
    b, s = dest_table.shape
    n_tiles, tile_g = row_tiling(g)
    g_pad = n_tiles * tile_g
    if g_pad != g:
        o_part = jnp.pad(o_part, ((0, 0), (0, g_pad - g), (0, 0)))
        lse = jnp.pad(
            lse, ((0, 0), (0, g_pad - g), (0, 0)), constant_values=-jnp.inf
        )

    def part(bb, r, jj, dest_ref, ns_ref):
        return dest_ref[bb, jj], r, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tiles, s),
        in_specs=[
            pl.BlockSpec((None, tile_g, d_v), part),
            pl.BlockSpec((None, tile_g, 1), part),
        ],
        out_specs=pl.BlockSpec(
            (None, tile_g, d_v), lambda bb, r, jj, *_: (bb, r, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((tile_g, d_v), jnp.float32),
            pltpu.VMEM((tile_g, 1), jnp.float32),
            pltpu.VMEM((tile_g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _combine_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g_pad, d_v), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        dest_table.astype(jnp.int32),
        n_splits.astype(jnp.int32),
        o_part,
        lse,
    )
    return out[:, :g]


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_hetero_partials(
    o_parts: list[jax.Array],  # each (Di, G, Dv) f32 normalized partials
    lse_parts: list[jax.Array],  # each (Di, G, 1) f32
    dest_table: jax.Array,  # (B, S) slot ids into the concatenated array
    n_splits: jax.Array,  # (B,) live partials per request
    *,
    interpret: bool = False,
) -> jax.Array:
    """Merge partials drawn from **heterogeneous sources** — e.g. a
    per-request split-KV suffix pass and a group-batched shared-prefix pass.

    The LSE-weighted merge never cared *how* the KV was partitioned, only
    that each partial is normalized with its log-sum-exp; so generalizing
    from split-KV to prefix/suffix is pure indexing: concatenate the
    partial arrays along the slot axis and point ``dest_table`` into the
    concatenated layout (``decode_schedule.PrefixSchedule
    .hetero_dest_tables`` builds exactly these tables).  Slot counts per
    request may be ragged; padding columns must repeat a live slot (warm
    gated-off fetches) as in the homogeneous case.  The concatenation and
    the merge are one program.
    """
    o_all = o_parts[0] if len(o_parts) == 1 else jnp.concatenate(o_parts, 0)
    lse_all = (
        lse_parts[0] if len(lse_parts) == 1 else jnp.concatenate(lse_parts, 0)
    )
    return combine_split_partials(
        o_all, lse_all, dest_table, n_splits, interpret=interpret
    )
