"""Pallas TPU kernels: paged MLA decode — AMLA over a block-table KV cache.

Serving-side twin of :mod:`repro.kernels.mla_decode`.  Instead of one
contiguous ``(B, S, 576)`` latent cache, the latents live in a shared pool of
fixed-size pages ``(num_pages, page_size, 576)`` and each request owns an
ordered list of physical page ids (its *block table*, see
``runtime/kv_cache.PagedKVCache``).  Two kernels share the AMLA MUL-by-ADD
state machine (init / update / finalize from ``mla_decode``):

**Work-queue kernel** (:func:`mla_decode_paged_queue_rows`, the serving
default) — the §4.2 hierarchical-tiling + flat-scheduling path.  A host-side
scheduler (:mod:`repro.kernels.decode_schedule`) compacts ``(request,
kv_block)`` work items from ``kv_len`` into a 1D queue: one grid step per
**512-row KV block** (4 pages), zero steps for empty tail pages, long
requests optionally split flash-decoding style across destination slots
whose partial ``(o, lse)`` states a combine kernel merges
(:mod:`repro.kernels.mla_decode_combine`).  Within an item the kernel runs
the paper's preload pipeline: the 4 pages are gathered from HBM into a
VMEM block with explicit ``make_async_copy`` DMAs started one page ahead of
the per-page score matmul, and all four score strips fold into a *single*
AMLA state update.  Pages past ``kv_len`` are zero-filled in VMEM — a ragged
tail costs vector stores, not HBM bandwidth.

**Group-prefix kernel** (:func:`mla_decode_paged_group_prefix`, the
shared-prefix fast path) — TyphoonMLA-style group-batched prefix attention.
Requests whose block tables alias the same leading pages (forked system
prompts, n-best sampling — see ``runtime/kv_cache.fork``) are grouped
host-side (:func:`repro.kernels.decode_schedule.find_prefix_groups`); each
group becomes a *virtual request* whose query block stacks **all members'
query rows** and whose KV is the shared prefix read through one member's
table row.  The same work-queue kernel then stages each shared KV block
through the preload pipeline **once** per group and folds it into a single
AMLA MUL-by-ADD state update over the stacked queries — the G per-member
bandwidth-bound (G_q × 576) GEMVs of the unshared path become one
compute-dense (G·G_q × 576) GEMM per block, and the block's four page DMAs
are paid once instead of G times.  Per-member partial ``(o, lse)`` rows come
back out by reshaping the stacked outputs; the combine kernel merges them
with the member's own suffix partials (split-KV combine generalized to
heterogeneous prefix/suffix partials).

**Padded-grid kernel** (:func:`mla_decode_paged_rows`, kept as the simple
baseline and work-accounting reference) — ``grid = (B, W)`` walks every
request over the *longest* block table, one page per step, resolved
logical → physical by a scalar-prefetched table feeding the input
``index_map``.  Steps past a request's length skip their FLOPs but still
DMA a page; those tail fetches are clamped to the request's *own last valid
page* (not physical page 0) so short requests in a ragged batch re-touch a
warm page instead of hammering one shared pool page.

Page size default is 128: pages are lane-tile aligned (bf16 second-minor
tiling is 16, f32 is 8) and 4 pages make up the paper's §4.2 KV block of
512.  The queue kernel's one-update-per-512-rows folding keeps the AMLA
rescale-skip statistics at the paper's block granularity while the DMA
granularity stays one page.

**Quantized pools** (:class:`CacheSpec` with ``dtype=int8``): the pool
stores symmetric per-row int8 latents and a companion fp32 scale pool
with one lane-padded row of scales per page.  The queue kernel stages each
page's int8 strip *and* its scale strip through the same double-buffered
``make_async_copy`` pipeline (scale strips on their own semaphores,
prefetched cross-step alongside the data), multiplies the scales into the
per-page score strip right after the MXU matmul and folds them into the
probability rows before the PV accumulate — so dequantization costs two VPU multiplies inside the
DMA-overlap window, page DMAs move ~half the bytes, and the AMLA
MUL-by-ADD state machine sees the same fp32 state as a bf16 pool.  The
padded baseline grid has no dequant path and stays bf16-only.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import ANY, CompilerParams

from repro.core import numerics
from repro.kernels import mla_decode as _mla

DEFAULT_PAGE_SIZE = 128


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Storage layout of a paged latent pool: dtype + scale granularity.

    The one object the serving stack threads from ``launch/serve.py
    --kv-dtype`` down to the kernels.  ``dtype`` is the page-pool storage
    dtype; ``int8`` pools are symmetric-quantized and carry a companion
    FP32 scale pool of one scale per **page row** (``scale_granularity ==
    "row"``) that every read path dequantizes against.  Per-row scales are
    the only granularity with exact write-once semantics on a paged cache:
    appends only ever touch fresh rows, so no stored row is ever
    re-quantized (a per-page scale would re-round every earlier row of a
    partial page on each decode append).  Coarser groupings (fp8 blocks,
    per-page) plug in here as new granularities with their own pools.
    """

    dtype: object = jnp.bfloat16
    scale_granularity: str = "row"

    _NAMES = {"bf16": jnp.bfloat16, "int8": jnp.int8, "f32": jnp.float32}

    def __post_init__(self):
        if isinstance(self.dtype, str):
            # Normalize name strings eagerly: jnp.zeros(..., "int8") would
            # happily build an int8 pool that `quantized` (an identity
            # check against jnp.int8) does not recognize — and the write
            # path would then cast latent rows to int8 with no scales.
            if self.dtype not in self._NAMES:
                raise ValueError(
                    f"unknown cache dtype {self.dtype!r}; choose from "
                    f"{sorted(self._NAMES)}"
                )
            object.__setattr__(self, "dtype", self._NAMES[self.dtype])
        if self.scale_granularity != "row":
            raise NotImplementedError(
                f"scale_granularity={self.scale_granularity!r}: only 'row' "
                f"(one fp32 scale per page row) is implemented — it is the "
                f"only granularity that never re-quantizes stored rows on "
                f"append; add a new pool layout here for grouped scales"
            )

    @classmethod
    def from_name(cls, name: str) -> "CacheSpec":
        """Build from a CLI-friendly dtype name (``bf16`` / ``int8``)."""
        if not isinstance(name, str):
            raise TypeError(f"from_name takes a dtype name string, got {name!r}")
        return cls(dtype=name)

    @property
    def quantized(self) -> bool:
        return self.dtype == jnp.int8

    def bytes_per_page(self, page_size: int, width: int) -> int:
        """HBM bytes one page's DMAs move: the data strip and, for int8,
        the scale strip, each padded to whole 128-lane tiles (a 576-wide
        row moves 640 lanes; see ``kv_cache.PagedKVCache``)."""
        n = page_size * _mla.lane_pad(width) * jnp.dtype(self.dtype).itemsize
        return n + (_mla.lane_pad(page_size) * 4 if self.quantized else 0)


def clamp_tail_pages(
    block_tables: jax.Array,  # (B, W) int32
    kv_len: jax.Array,  # (B,) int32
    page_size: int,
    num_pages: int,
) -> jax.Array:
    """Point tail block-table entries at the request's own last valid page.

    Entries past ``ceil(kv_len / page_size)`` are padding: the padded-grid
    kernel still DMAs them (the gather rides the grid pipeline and cannot be
    skipped), and the queue kernel may prefetch them at a block's ragged
    edge.  Directing them to the request's last live page keeps those fetches
    on data that is already warm per-request instead of serialising every
    short request in the batch onto physical page 0 — a shared-pool hotspot.
    Requests with ``kv_len == 0`` fall back to their (clamped) first entry.
    """
    bt = block_tables.astype(jnp.int32)
    w = bt.shape[1]
    pages_used = -((-kv_len.astype(jnp.int32)) // page_size)  # ceil
    last_idx = jnp.clip(pages_used - 1, 0, w - 1)
    last_page = jnp.take_along_axis(bt, last_idx[:, None], axis=1)  # (B, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    bt = jnp.where(col < pages_used[:, None], bt, last_page)
    return jnp.clip(bt, 0, num_pages - 1)


def stacked_layer_view(kv_pages, kv_scales, block_tables, layer):
    """Address layer ``layer`` of a layered ``(L, P, page, Dk)`` pool
    without copying it.

    The pool is viewed as ``(L*P, page, Dk)`` pages (merging leading axes
    is a bitcast, not a copy) and the block table is offset by
    ``layer * P``, so its page ids name that layer's pages; the scale pool
    gets the same view.  A one-layer ``(P, page, Dk)`` pool passes through
    unchanged (``layer`` must then be None).
    """
    if kv_pages.ndim == 3:
        if layer is not None:
            raise ValueError("layer given for a one-layer (P, page, Dk) pool")
        return kv_pages, kv_scales, block_tables
    if layer is None:
        raise ValueError(
            f"a layered {kv_pages.shape} pool needs the layer to attend"
        )
    n_layers, num_pages = kv_pages.shape[:2]
    kv_pages = kv_pages.reshape((n_layers * num_pages,) + kv_pages.shape[2:])
    if kv_scales is not None:
        kv_scales = kv_scales.reshape(n_layers * num_pages, kv_scales.shape[-1])
    block_tables = block_tables.astype(jnp.int32) + layer * num_pages
    return kv_pages, kv_scales, block_tables


# --------------------------------------------------------------------------- #
# padded (B, W) grid kernel — baseline
# --------------------------------------------------------------------------- #


def _mla_decode_paged_kernel(
    # scalar prefetch
    kv_len_ref,  # (B,) int32
    block_table_ref,  # (B, W) int32 logical page -> physical page id
    # inputs
    q_ref,  # (G, Dk) bf16
    q_pos_ref,  # (G, 1) int32 absolute position per query row (VMEM)
    page_ref,  # (page_size, Dk) bf16  (physical page selected by index_map)
    # outputs
    o_ref,  # (G, Dv)
    # scratch
    acc_ref,
    m_ref,
    l_ref,
    n_ref,
    gamma_ref,
    s16_ref,
    *,
    scale: float,
    d_v: int,
    variant: str,
    page_size: int,
    softcap: float | None,
):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        _mla.init_decode_state(acc_ref, m_ref, l_ref, n_ref, gamma_ref, s16_ref)

    k_len = kv_len_ref[b]
    start = i * page_size

    # Pages past the request's length are skipped entirely (their DMA still
    # lands — clamped to the request's own last valid page — but no FLOPs
    # are spent).
    @pl.when(start < k_len)
    def _compute():
        c_blk = page_ref[...]
        if c_blk.dtype != q_ref.dtype:
            # fp32 compute over bf16 pages: cast one page here, in VMEM —
            # never the whole pool (see ops.mla_decode_paged).
            c_blk = c_blk.astype(q_ref.dtype)
        s = jax.lax.dot_general(
            q_ref[...],
            c_blk,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * jnp.float32(scale)
        if softcap is not None:
            s = numerics.softcap(s, softcap)
        s = jnp.clip(s, -numerics.M_CLAMP, numerics.M_CLAMP)

        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (k_pos < k_len) & (k_pos <= q_pos_ref[...])
        s = jnp.where(mask, s, -jnp.inf)

        _mla.decode_block_update(
            s, c_blk,
            acc_ref, m_ref, l_ref, n_ref, gamma_ref, s16_ref,
            d_v=d_v, variant=variant, mm_dtype=q_ref.dtype,
        )

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        _mla.finalize_decode(o_ref, acc_ref, l_ref, s16_ref, variant=variant)


@functools.partial(
    jax.jit,
    static_argnames=(
        "d_v",
        "variant",
        "scale",
        "softcap",
        "interpret",
    ),
)
def mla_decode_paged_rows(
    q: jax.Array,  # (B, G, Dk)
    kv_pages: jax.Array,  # (P, page_size, Dk) page pool, Dk lane-padded
    block_tables: jax.Array,  # (B, W) int32
    kv_len: jax.Array,  # (B,) int32
    q_pos: jax.Array,  # (B, G) int32
    *,
    d_v: int = 512,
    variant: str = "amla",
    scale: float,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Row-level paged decode over the padded ``(B, W)`` grid.

    ``W = block_tables.shape[1]`` logical pages are walked per request;
    requests shorter than ``W * page_size`` mask the tail via ``kv_len``
    (entries past a request's last page may be arbitrary in-range ids —
    they are redirected to the request's own last valid page here and their
    compute is skipped).  A request with ``kv_len == 0`` (inactive serving
    slot) yields exact zeros.  The serving path normally goes through the
    work-queue kernel instead (see ``ops.mla_decode_paged``).
    """
    b, g, d_k = q.shape
    num_pages, page_size, _ = kv_pages.shape
    w = block_tables.shape[1]
    if w < 1:
        raise ValueError("block_tables must have at least one page column")
    kv_len = kv_len.astype(jnp.int32)
    block_tables = clamp_tail_pages(block_tables, kv_len, page_size, num_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((None, g, d_k), lambda bb, ii, *_: (bb, 0, 0)),
            pl.BlockSpec((None, g, 1), lambda bb, ii, *_: (bb, 0, 0)),
            pl.BlockSpec(
                (None, page_size, d_k),
                lambda bb, ii, kv_len_ref, bt_ref: (
                    bt_ref[bb, ii],
                    0,
                    0,
                ),
            ),
        ],
        out_specs=pl.BlockSpec((None, g, d_v), lambda bb, ii, *_: (bb, 0, 0)),
        scratch_shapes=_mla.decode_state_scratch(g, d_v),
    )
    kernel = functools.partial(
        _mla_decode_paged_kernel,
        scale=scale,
        d_v=d_v,
        variant=variant,
        page_size=page_size,
        softcap=softcap,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, d_v), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        kv_len,
        block_tables,
        q,
        q_pos.astype(jnp.int32)[..., None],
        kv_pages,
    )


# --------------------------------------------------------------------------- #
# flat work-queue kernel — hierarchical tiling + preload pipeline
# --------------------------------------------------------------------------- #


def _mla_decode_queue_kernel(
    # scalar prefetch
    kv_len_ref,  # (B,) int32
    bt_ref,  # (B, W) int32 logical page -> physical page id
    ireq_ref,  # (N,) int32 request index per work item
    iblk_ref,  # (N,) int32 kv-block index within the request
    idst_ref,  # (N,) int32 destination partial-state slot  (used by index_maps)
    ifst_ref,  # (N,) int32 1 on a dest's first item
    ilst_ref,  # (N,) int32 1 on a dest's last item
    ivld_ref,  # (N,) int32 0 for queue padding
    # inputs: q_ref, q_pos_ref, pages_hbm[, scales_hbm], then outputs and
    # scratch — the quantized variant splices the scale pool input and its
    # staging scratch in, so the tail is unpacked by arity below.
    q_ref,  # (G, Dk) bf16 (block selected by item_req)
    q_pos_ref,  # (G, 1) int32 absolute position per query row (VMEM)
    pages_hbm,  # (P, page_size, Dk) page pool, resident in HBM (ANY)
    *rest,
    scale: float,
    d_v: int,
    variant: str,
    page_size: int,
    block_k: int,
    softcap: float | None,
    quantized: bool,
    tile_g: int,
):
    if quantized:
        # scales_hbm: (P, SW) f32 per-row dequant scales, lane-padded (ANY);
        # scale_blk_ref: (2, n_sub, SW) f32 double-buffered staging;
        # scale_sem: one DMA semaphore per page of the block.
        (scales_hbm, o_ref, lse_ref, acc_ref, m_ref, l_ref, n_ref,
         gamma_ref, s16_ref, kv_blk_ref, sem, scale_blk_ref,
         scale_sem) = rest
    else:
        (o_ref, lse_ref, acc_ref, m_ref, l_ref, n_ref, gamma_ref,
         s16_ref, kv_blk_ref, sem) = rest
        scales_hbm = scale_blk_ref = scale_sem = None

    t = pl.program_id(0)
    n_items = pl.num_programs(0)
    req = ireq_ref[t]
    blk = iblk_ref[t]
    first = ifst_ref[t]
    last = ilst_ref[t]
    valid = ivld_ref[t]
    n_tiles = q_ref.shape[0] // tile_g
    state = (acc_ref, m_ref, l_ref, n_ref, gamma_ref, s16_ref)

    def tile_state(rows):
        return [ref.at[rows] for ref in state]

    # A dest slot's items are contiguous in the queue, so its online-softmax
    # state lives in scratch across grid steps: init on the first item,
    # finalize+write on the last.  Padding items re-init harmlessly.
    @pl.when(first == 1)
    def _init():
        _mla.for_each_row_tile(
            n_tiles, tile_g,
            lambda rows: _mla.init_decode_state(*tile_state(rows)),
        )

    k_len = kv_len_ref[req]
    start = blk * block_k
    n_sub = block_k // page_size
    first_page = blk * n_sub
    # Item t stages into buffer t % 2; the end-of-item lookahead targets the
    # other buffer.  Valid items are a contiguous queue prefix, so "t > 0"
    # is exactly "the previous step prefetched this item's first page" (its
    # condition below was this item's validity), and a valid item's first
    # page always intersects kv_len by construction.
    t_next = jnp.minimum(t + 1, n_items - 1)
    cur = jax.lax.rem(t, 2)

    @pl.when(valid == 1)
    def _compute():
        kv_view = kv_blk_ref.at[cur]
        scale_view = None if scale_blk_ref is None else scale_blk_ref.at[cur]

        def live(j):
            return start + j * page_size < k_len

        def src(j):
            # live(j) bounds first_page + j inside this request's table
            # row, so the gather never reads a padding entry.
            return pages_hbm.at[bt_ref[req, first_page + j]]

        def scale_src(j):
            return scales_hbm.at[pl.ds(bt_ref[req, first_page + j], 1), :]

        def update(rows, s):
            s = s * jnp.float32(scale)
            if softcap is not None:
                s = numerics.softcap(s, softcap)
            s = jnp.clip(s, -numerics.M_CLAMP, numerics.M_CLAMP)
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (k_pos < k_len) & (k_pos <= q_pos_ref[rows, :])
            s = jnp.where(mask, s, -jnp.inf)
            _mla.decode_block_update(
                s, kv_view[...], *tile_state(rows),
                d_v=d_v, variant=variant, mm_dtype=q_ref.dtype,
                kv_scale=None if scale_view is None else _mla.block_scale_row(
                    scale_view, n_sub=n_sub, sub_k=page_size
                ),
            )

        rows0 = pl.ds(0, tile_g)
        s = _mla.preload_block_scores(
            q_ref.at[rows0], kv_view, n_sub=n_sub, sub_k=page_size,
            src=src, live=live, sem=sem, first_prefetched=t > 0,
            scale_view=scale_view,
            scale_src=scale_src if quantized else None,
            scale_sem=scale_sem,
        )
        # Cross-step lookahead: start the next work item's first-page gather
        # (and, quantized, its scale strip) now so the copies overlap this
        # item's state update.
        next_cond = (t + 1 < n_items) & (ivld_ref[t_next] == 1)
        next_pid = lambda: bt_ref[ireq_ref[t_next], iblk_ref[t_next] * n_sub]
        _mla.prefetch_next_first_subtile(
            lambda: pages_hbm.at[next_pid()],
            kv_blk_ref.at[1 - cur],
            sem,
            sub_k=page_size,
            cond=next_cond,
            scale_src0=(
                (lambda: scales_hbm.at[pl.ds(next_pid(), 1), :])
                if quantized
                else None
            ),
            scale_view_next=(
                None if scale_blk_ref is None else scale_blk_ref.at[1 - cur]
            ),
            scale_sem=scale_sem,
        )
        update(rows0, s)
        # Further row tiles score against the block already staged: each
        # page is DMA'd once per work item, however many rows attend it.
        _mla.for_each_row_tile(
            n_tiles, tile_g,
            lambda rows: update(rows, _mla.staged_block_scores(
                q_ref.at[rows], kv_view, n_sub=n_sub, sub_k=page_size,
                scale_view=scale_view,
            )),
            first=1,
        )

    @pl.when((last == 1) & (valid == 1))
    def _finalize():
        def fin(rows):
            acc, m, l_st, _, _, s16 = tile_state(rows)
            _mla.finalize_decode(o_ref.at[rows], acc, l_st, s16, variant=variant)
            # lse in *standard* units (m is the true running max, l the
            # plain softmax mass in both variants) so split partials combine
            # variant-agnostically.
            l = l_st[...]
            safe = jnp.where(l > 0, l, 1.0)
            lse_ref[rows, :] = jnp.where(
                l > 0, m[...] + jnp.log(safe), -jnp.inf
            )

        _mla.for_each_row_tile(n_tiles, tile_g, fin)


@functools.partial(
    jax.jit,
    static_argnames=(
        "d_v",
        "variant",
        "scale",
        "block_k",
        "num_dest_slots",
        "softcap",
        "interpret",
    ),
)
def mla_decode_paged_queue_rows(
    q: jax.Array,  # (B, G, Dk)
    kv_pages: jax.Array,  # (P, page_size, Dk) page pool, Dk lane-padded
    block_tables: jax.Array,  # (B, W) int32
    kv_len: jax.Array,  # (B,) int32
    q_pos: jax.Array,  # (B, G) int32
    item_req: jax.Array,  # (N,) int32 ┐
    item_block: jax.Array,  # (N,) int32 │
    item_dest: jax.Array,  # (N,) int32 │ flat work queue
    item_first: jax.Array,  # (N,) int32 │ (see decode_schedule)
    item_last: jax.Array,  # (N,) int32 │
    item_valid: jax.Array,  # (N,) int32 ┘
    kv_scales: jax.Array | None = None,  # (P, SW) f32, int8 pools
    layer: jax.Array | None = None,  # int32 layer of an (L, P, ...) pool
    *,
    d_v: int = 512,
    variant: str = "amla",
    scale: float,
    block_k: int,
    num_dest_slots: int,
    softcap: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Work-queue paged decode: one grid step per §4.2 KV block work item.

    Returns ``(o_part, lse)`` of shapes ``(num_dest_slots, G, Dv)`` /
    ``(num_dest_slots, G, 1)`` — normalized partial outputs and
    log-sum-exps per destination slot, to be merged by
    ``mla_decode_combine.combine_split_partials`` (a no-op merge when each
    request has one split; slots of empty requests are never written and
    are masked out there).

    With an int8 page pool, ``kv_scales`` is its per-row FP32 scale pool
    (see :class:`CacheSpec`): each work item stages the block's int8 page
    strips *and* their scale strips through the same double-buffered
    preload pipeline, dequantizes scores per sub-tile inside the
    DMA-overlap window, and folds the scales into the PV probabilities —
    the AMLA state machine sees the same fp32 state either way, while the
    page DMAs move roughly half the bytes.

    ``kv_pages`` may also be a layered cache's whole ``(L, P, page_size,
    Dk)`` pool (and ``kv_scales`` its ``(L, P, SW)`` scales), with
    ``layer`` the layer to attend: see :func:`stacked_layer_view`.  ``q``
    may carry more rows than ``block_tables``; rows no work item names are
    never read.
    """
    kv_pages, kv_scales, block_tables = stacked_layer_view(
        kv_pages, kv_scales, block_tables, layer
    )
    g, d_k = q.shape[1:]
    num_pages, page_size, _ = kv_pages.shape
    if block_k % page_size or block_k < page_size:
        raise ValueError(
            f"block_k={block_k} must be a positive multiple of "
            f"page_size={page_size}"
        )
    quantized = kv_scales is not None
    kv_len = kv_len.astype(jnp.int32)
    block_tables = clamp_tail_pages(
        block_tables, kv_len, page_size, num_pages
    )
    n_items = item_req.shape[0]
    # A program holds all of a request's query rows (a 32-token prefill
    # chunk at 128 heads is 4096) and updates them in row tiles, so each
    # page is DMA'd once per work item.  Pad rows carry q_pos = -1, which
    # masks every key, and are sliced off below.
    n_tiles, tile_g = _mla.row_tiling(g)
    g_pad = n_tiles * tile_g
    q_pos = q_pos.astype(jnp.int32)
    if g_pad != g:
        q = jnp.pad(q, ((0, 0), (0, g_pad - g), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, g_pad - g)), constant_values=-1)

    # index_maps see (item, *scalar refs); refs[2] is item_req and refs[4]
    # item_dest.
    data_specs = [
        pl.BlockSpec((None, g_pad, d_k), lambda t, *refs: (refs[2][t], 0, 0)),
        pl.BlockSpec((None, g_pad, 1), lambda t, *refs: (refs[2][t], 0, 0)),
        pl.BlockSpec(memory_space=ANY),
    ]
    extra_scratch = []
    if quantized:
        # The scale pool rides along in HBM; strips are staged by explicit
        # DMA exactly like the page data, with their own semaphores.
        data_specs.append(pl.BlockSpec(memory_space=ANY))
        extra_scratch = [
            pltpu.VMEM(
                (2, block_k // page_size, kv_scales.shape[1]), jnp.float32
            ),
            pltpu.SemaphoreType.DMA((block_k // page_size,)),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        # Flat queue; scratch-carried state makes it sequential
        # ("arbitrary"), which is what lets one dest span several items.
        grid=(n_items,),
        in_specs=data_specs,
        out_specs=[
            pl.BlockSpec(
                (None, g_pad, d_v), lambda t, *refs: (refs[4][t], 0, 0)
            ),
            pl.BlockSpec((None, g_pad, 1), lambda t, *refs: (refs[4][t], 0, 0)),
        ],
        scratch_shapes=_mla.decode_state_scratch(g_pad, d_v)
        + [
            # Double-buffered so the cross-step lookahead can stage the next
            # item's first page while this item is still being read.
            pltpu.VMEM((2, block_k, d_k), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((block_k // page_size,)),
        ]
        + extra_scratch,
    )
    kernel = functools.partial(
        _mla_decode_queue_kernel,
        scale=scale,
        d_v=d_v,
        variant=variant,
        page_size=page_size,
        block_k=block_k,
        softcap=softcap,
        quantized=quantized,
        tile_g=tile_g,
    )
    vmem_limit = _mla.vmem_limit_bytes(
        g_pad, tile_g, d_k=d_k, d_v=d_v, block_k=block_k,
        q_dtype=q.dtype, kv_dtype=kv_pages.dtype,
    )
    if not interpret and vmem_limit is not None and vmem_limit > _mla.VMEM_BYTES:
        raise ValueError(
            f"{g} query rows per request need about {vmem_limit >> 20} MiB "
            f"of VMEM, more than a core has ({_mla.VMEM_BYTES >> 20} MiB); "
            f"use a smaller prefill chunk, draft_k or prefix group"
        )
    inputs = [q, q_pos[..., None], kv_pages]
    if quantized:
        inputs.append(kv_scales.astype(jnp.float32))
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_dest_slots, g_pad, d_v), jnp.float32),
            jax.ShapeDtypeStruct((num_dest_slots, g_pad, 1), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(
        kv_len,
        block_tables,
        item_req.astype(jnp.int32),
        item_block.astype(jnp.int32),
        item_dest.astype(jnp.int32),
        item_first.astype(jnp.int32),
        item_last.astype(jnp.int32),
        item_valid.astype(jnp.int32),
        *inputs,
    )
    return o[:, :g], lse[:, :g]


# --------------------------------------------------------------------------- #
# group-batched shared-prefix kernel — stacked queries, one DMA per block
# --------------------------------------------------------------------------- #


@functools.partial(
    jax.jit,
    static_argnames=(
        "d_v",
        "variant",
        "scale",
        "block_k",
        "num_dest_slots",
        "softcap",
        "interpret",
    ),
)
def mla_decode_paged_group_prefix(
    q: jax.Array,  # (B, G, Dk) per-request query rows
    kv_pages: jax.Array,  # (P, page_size, Dk) page pool, Dk lane-padded
    block_tables: jax.Array,  # (B, W) int32
    q_pos: jax.Array,  # (B, G) int32 real query positions
    group_member: jax.Array,  # (n_groups, gmax) int32 request idx, -1 pad
    group_rep: jax.Array,  # (n_groups,) int32 table row per group
    prefix_lens: jax.Array,  # (n_groups,) int32 shared rows (block multiple)
    item_req: jax.Array,  # ┐
    item_block: jax.Array,  # │
    item_dest: jax.Array,  # │ prefix work queue: one item per
    item_first: jax.Array,  # │ (group, shared kv_block)
    item_last: jax.Array,  # │
    item_valid: jax.Array,  # ┘
    kv_scales: jax.Array | None = None,  # (P, SW) f32, int8 pools
    layer: jax.Array | None = None,  # int32 layer of an (L, P, ...) pool
    *,
    d_v: int = 512,
    variant: str = "amla",
    scale: float,
    block_k: int,
    num_dest_slots: int,
    softcap: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Shared-prefix attention computed **once per group** over stacked
    queries.

    Each group is presented to the work-queue kernel as one virtual request:
    its query block is the concatenation of every member's ``G`` rows
    (``gmax * G`` rows, zero-padded past the member count with fully-masked
    positions), its block table is the representative member's row (the
    shared pages are aliased, so any member's row names the same physical
    pages), and its ``kv_len`` is the group's shared-prefix length.  One
    grid step per ``(group, kv_block)`` stages the block's pages through the
    preload pipeline once and runs a single AMLA state update against all
    members — the MUL-by-ADD machinery is untouched; only the work/geometry
    changes.

    Returns per-**member** partials ``(o, lse)`` of shapes
    ``(num_dest_slots * gmax, G, Dv)`` / ``(..., G, 1)``: row
    ``dest * gmax + slot`` is member ``slot``'s normalized prefix partial,
    ready to concatenate after the suffix partial array for the combine
    kernel (see ``decode_schedule.PrefixSchedule.hetero_dest_tables``).
    Padded member rows finalize to zeros with ``lse == -inf`` and are never
    referenced by a dest table.  A layered pool and its ``layer`` pass
    through to the queue kernel (:func:`stacked_layer_view`).
    """
    b, g, d_k = q.shape
    n_groups, gmax = group_member.shape
    member = jnp.clip(group_member, 0, b - 1)
    live = group_member >= 0
    # Stack member query rows: (n_groups, gmax*G, Dk).  Padded slots carry
    # q_pos = -1, which masks every key -> empty softmax -> lse = -inf.
    q_grp = jnp.take(q, member, axis=0).reshape(n_groups, gmax * g, d_k)
    pos_grp = jnp.where(
        live[:, :, None], jnp.take(q_pos, member, axis=0), -1
    ).reshape(n_groups, gmax * g)
    bt_grp = jnp.take(block_tables, group_rep, axis=0)
    o_grp, lse_grp = mla_decode_paged_queue_rows(
        q_grp,
        kv_pages,
        bt_grp,
        prefix_lens.astype(jnp.int32),
        pos_grp,
        item_req,
        item_block,
        item_dest,
        item_first,
        item_last,
        item_valid,
        kv_scales,
        layer,
        d_v=d_v,
        variant=variant,
        scale=scale,
        block_k=block_k,
        num_dest_slots=num_dest_slots,
        softcap=softcap,
        interpret=interpret,
    )
    # (D_pref, gmax*G, ·) -> per-member rows (D_pref*gmax, G, ·)
    return (
        o_grp.reshape(num_dest_slots * gmax, g, d_v),
        lse_grp.reshape(num_dest_slots * gmax, g, 1),
    )
