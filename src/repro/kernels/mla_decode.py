"""Pallas TPU kernel: MLA decode attention — the paper's AMLA kernel.

Geometry (paper §3.1): per batch element, G = S_q * 128 query rows of width
D_k = 576 attend to a *shared* latent KV cache ``c in (S2, 576)``; values are
the first D_v = 512 columns of the same cache.  KV block size is 512 (paper
§4.2).  The FP32 accumulator (G x 512) lives in VMEM scratch across the KV
grid dimension — this is the TPU translation of the paper's "O stays in GM,
updated by AtomicAdd": sequential grid steps make the update race-free, and
the AMLA reformulation turns the per-block rescale into

    acc <- AS_FP32(AS_INT32(acc) + [(n_i - n_{i-1}) + 1.5*eps] * 2^23)

which (a) is an integer VPU op instead of transcendental+multiply and (b) is
*skipped entirely* when the increment is zero — the common case, since the
running max rarely crosses a power-of-two boundary.

Preload pipeline (paper §4.2, hierarchical tiling):  each grid step covers
one 512-row KV block, fetched from HBM as four 128-row **sub-tiles** with
explicit ``pltpu.make_async_copy`` DMAs started one sub-tile ahead: while
sub-tile ``j``'s score matmul runs on the MXU, sub-tile ``j+1``'s copy is in
flight.  All four sub-tile score strips then fold into a *single* AMLA
MUL-by-ADD state update and one (G x 512) x (512 x D_v) PV matmul — 4x fewer
rescale checks and power-of-two crossings than per-page updates, full-block
MXU occupancy.  The staging buffer is double-buffered across grid steps: at
the end of each block the kernel starts the *next* block's first sub-tile
copy into the other buffer (cross-step lookahead), so consecutive blocks
never stall on a cold first copy.  Sub-tiles past ``kv_len`` are zero-filled
in VMEM instead of DMA'd — a ragged tail costs vector stores, not HBM
bandwidth.

VMEM working set per program = Q (G*576*2B = 144 KB at G=128) + KV-block
scratch (512*576*2B = 576 KB) + acc (G*512*4B = 256 KB) << 16 MB VMEM.
Matmul dims (G=128, 512, 576=512+64) are MXU-aligned multiples of 128 except
the 64-wide rope tail, which Mosaic pads by half a lane-tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import ANY, CompilerParams

from repro.core import numerics

DEFAULT_BLOCK_K = 512
# Preload-pipeline granularity: one sub-tile = one DMA = one score matmul.
# 128 keeps the (G x 128 x 576) strip MXU-aligned and equals the paged
# kernel's page size, so both kernels share the same pipeline shape.
SUB_K = 128
LANES = 128
# The TPU compiler's default scoped-VMEM limit on v5e; programs that need
# more ask for it through ``vmem_limit_bytes``, up to a core's whole VMEM.
DEFAULT_SCOPED_VMEM = 16 << 20
VMEM_BYTES = 128 << 20


def lane_pad(n: int) -> int:
    """``n`` rounded up to whole 128-lane tiles: the minor-dim width a TPU
    array occupies in memory, and the only widths a DMA may slice."""
    return -(-n // LANES) * LANES


# Most query rows one decode kernel update works on at a time; a program
# with more loops over row tiles of at most this many rows.
ROW_TILE = 512


def row_tiling(g: int) -> tuple[int, int]:
    """``(tiles, rows per tile)`` for ``g`` query rows.

    At most :data:`ROW_TILE` rows per tile, spread evenly; when there are
    several tiles each is a multiple of 16 rows (the bf16 sublane tile)."""
    n = -(-g // ROW_TILE)
    if n == 1:
        return 1, g
    return n, -(-g // (16 * n)) * 16


def for_each_row_tile(n_tiles: int, tile_g: int, fn, *, first: int = 0):
    """Call ``fn(rows)`` for row tiles ``first .. n_tiles - 1``, ``rows`` a
    ``pl.ds`` of ``tile_g`` rows.  Several tiles run as a loop, not
    unrolled, so the program's code is one tile's whatever the row count."""
    if first >= n_tiles:
        return
    if n_tiles - first == 1:
        fn(pl.ds(first * tile_g, tile_g))
        return

    def body(r, carry):
        fn(pl.ds(pl.multiple_of(r * tile_g, tile_g), tile_g))
        return carry

    jax.lax.fori_loop(first, n_tiles, body, 0)


def vmem_limit_bytes(
    g, tile_g, *, d_k, d_v, block_k, q_dtype, kv_dtype
) -> int | None:
    """Scoped-VMEM limit for a decode program that holds ``g`` query rows
    and updates ``tile_g`` of them at a time, or None when the compiler's
    default suffices.

    Per held row: the double-buffered q, position, output and lse blocks (a
    ``(g, 1)`` block occupies a whole 128-lane tile), the f32 accumulator
    and five state columns.  Per tile row: the f32 score/probability strips
    of one KV block.  The staged KV block and 4 MiB of compiler scratch come
    on top, and a quarter for what the count misses.  For 4096 rows in 8
    tiles of 512 (a 32-token prefill chunk at 128 heads) it asks for 76 MiB;
    the v5e compiler needs between 54 and 58 MiB.
    """
    q_b = jnp.dtype(q_dtype).itemsize
    col = LANES * 4  # one (rows, 1) f32/i32 column, lane-padded
    per_row = (
        2 * d_k * q_b + 2 * col  # q, q_pos blocks
        + 2 * d_v * 4 + 2 * col  # output, lse blocks
        + d_v * 4 + 5 * col  # acc, m/l/n/gamma/s16
    )
    per_tile_row = block_k * (3 * 4 + q_b)  # scores, p, scaled p; MXU p
    fixed = 2 * block_k * d_k * jnp.dtype(kv_dtype).itemsize + (4 << 20)
    need = (g * per_row + tile_g * per_tile_row + fixed) * 5 // 4
    return None if need <= DEFAULT_SCOPED_VMEM else need


def decode_state_scratch(g: int, d_v: int) -> list:
    """VMEM scratch carried across the KV grid dimension by decode kernels.

    Order matches the ``*state_refs`` convention of :func:`init_decode_state`
    / :func:`decode_block_update` / :func:`finalize_decode`:
    ``acc (G, Dv) f32, m (G, 1) f32, l (G, 1) f32, n (G, 1) i32,
    gamma (G, 1) f32, s16 (G, 1) f32`` (the last three are AMLA-only but
    allocated regardless; cheap).
    """
    return [
        pltpu.VMEM((g, d_v), jnp.float32),
        pltpu.VMEM((g, 1), jnp.float32),
        pltpu.VMEM((g, 1), jnp.float32),
        pltpu.VMEM((g, 1), jnp.int32),
        pltpu.VMEM((g, 1), jnp.float32),
        pltpu.VMEM((g, 1), jnp.float32),
    ]


def init_decode_state(acc_ref, m_ref, l_ref, n_ref, gamma_ref, s16_ref):
    """Reset the online-softmax state at the first KV grid step."""
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, numerics.M_INIT)
    l_ref[...] = jnp.zeros_like(l_ref)
    n0, inv_r0 = numerics.round_scale_to_pow2(
        jnp.full_like(m_ref, numerics.M_INIT)
    )
    n_ref[...] = n0
    gamma_ref[...] = jnp.ones_like(gamma_ref)
    s16_ref[...] = numerics.bf16_round(inv_r0)


def preload_block_scores(
    q_ref, kv_view, *, n_sub, sub_k, src, live, sem, first_prefetched,
    scale_view=None, scale_src=None, scale_sem=None,
):
    """§4.2 preload pipeline over one KV block, shared by both decode kernels.

    ``kv_view`` is this block's (n_sub*sub_k, Dk) VMEM staging buffer (one
    slot of the double-buffered scratch); ``src(j)`` returns the HBM source
    ref slice for sub-tile ``j`` (a contiguous cache slice or a block-table
    page — only called under a taken ``live(j)`` branch, so gated scalar
    reads inside it stay in-bounds); ``live(j)`` is the traced predicate
    that sub-tile ``j`` intersects ``kv_len``; ``first_prefetched`` is the
    traced predicate that the *previous* grid step already started sub-tile
    0's copy into this buffer (cross-step lookahead — see
    :func:`prefetch_next_first_subtile`).

    Copies run one sub-tile ahead of the score matmul; dead tail sub-tiles
    are zero-filled in VMEM instead of DMA'd.  Returns the concatenated
    (G, n_sub*sub_k) FP32 score strip.

    **Quantized side-channel** (int8 latent pages): when ``scale_view`` — an
    ``(n_sub, SW)`` FP32 staging slot, one row per sub-tile — is given, each
    sub-tile's per-row dequant scales (``scale_src(j)``, a ``(1, SW)`` HBM
    row whose first ``sub_k`` lanes are live, see :func:`block_scale_row`)
    ride the same one-ahead DMA pipeline on their own semaphores, and the
    sub-tile's raw int8 score strip is multiplied by them right after the
    matmul — the dequant costs a (G, sub_k) VPU multiply inside the
    DMA-overlap window instead of a pool-sized cast anywhere.  The staged
    sub-tile is cast to ``q_ref.dtype`` at matmul time, which also covers
    the fp32-compute-over-bf16-pages path scale-free.
    """

    def dma(j):
        return pltpu.make_async_copy(
            src(j),
            kv_view.at[pl.ds(j * sub_k, sub_k), :],
            sem.at[j],
        )

    def sdma(j):
        return pltpu.make_async_copy(
            scale_src(j), scale_view.at[pl.ds(j, 1), :], scale_sem.at[j]
        )

    def issue(j):
        cond = live(j)
        if j == 0:
            cond = cond & jnp.logical_not(first_prefetched)

        @pl.when(cond)
        def _start():
            dma(j).start()
            if scale_view is not None:
                sdma(j).start()

        # Tail sub-tiles past kv_len cost vector stores, never DMAs.
        @pl.when(jnp.logical_not(live(j)))
        def _zero():
            kv_view[pl.ds(j * sub_k, sub_k), :] = jnp.zeros(
                (sub_k, kv_view.shape[1]), kv_view.dtype
            )
            if scale_view is not None:
                scale_view[pl.ds(j, 1), :] = jnp.zeros(
                    (1, scale_view.shape[1]), scale_view.dtype
                )

    def wait(j):
        @pl.when(live(j))
        def _wait():
            dma(j).wait()
            if scale_view is not None:
                sdma(j).wait()

    issue(0)
    parts = []
    for j in range(n_sub):
        if j + 1 < n_sub:
            issue(j + 1)
        wait(j)
        parts.append(_strip_scores(q_ref, kv_view, scale_view, j, sub_k))
    return jnp.concatenate(parts, axis=1) if n_sub > 1 else parts[0]


def staged_block_scores(q_ref, kv_view, *, n_sub, sub_k, scale_view=None):
    """The (G, n_sub*sub_k) FP32 scores of ``q_ref`` against a block that
    :func:`preload_block_scores` has already staged (and zero-filled past
    ``kv_len``): further row tiles of the same work item reuse the staged
    pages instead of DMA-ing them again."""
    parts = [
        _strip_scores(q_ref, kv_view, scale_view, j, sub_k)
        for j in range(n_sub)
    ]
    return jnp.concatenate(parts, axis=1) if n_sub > 1 else parts[0]


def _strip_scores(q_ref, kv_view, scale_view, j, sub_k):
    """Scores of ``q_ref`` against staged sub-tile ``j``."""
    strip = kv_view[pl.ds(j * sub_k, sub_k), :]
    if strip.dtype != q_ref.dtype:
        # int8 pages (and bf16 pages under fp32 compute) are cast per strip
        # here, inside the pipeline — never a whole pool.
        strip = strip.astype(q_ref.dtype)
    s_j = jax.lax.dot_general(
        q_ref[...],
        strip,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if scale_view is not None:
        # Per-key-row dequant: score column j scales by its row's σ.
        s_j = s_j * _scale_strip(scale_view, j, sub_k)
    return s_j


def _scale_strip(scale_view, j, sub_k):
    """Sub-tile ``j``'s ``(1, sub_k)`` scales out of its staged row."""
    return scale_view[pl.ds(j, 1), :][:, :sub_k]


def block_scale_row(scale_view, *, n_sub, sub_k):
    """The block's ``(1, n_sub*sub_k)`` per-key-row scales, in key order.

    Scale pools keep one lane-padded row per page (``SW`` lanes, a
    multiple of 128, of which the first ``sub_k`` are live): a DMA may
    only move whole 128-lane tiles, so a page's scales cannot land at an
    unaligned lane offset of one block-wide row."""
    parts = [_scale_strip(scale_view, j, sub_k) for j in range(n_sub)]
    return jnp.concatenate(parts, axis=1) if n_sub > 1 else parts[0]


def prefetch_next_first_subtile(
    src0, kv_view_next, sem, *, sub_k, cond,
    scale_src0=None, scale_view_next=None, scale_sem=None,
):
    """Cross-grid-step lookahead: start the *next* block's sub-tile-0 copy.

    Called at the end of a block's compute so the copy overlaps the state
    update and the next step's own pipeline warm-up — the step never stalls
    on its first sub-tile the way a cold start would.  ``cond`` must be
    computable identically at this step and the next (both read the same
    scalar-prefetched arrays), so starts and waits pair up exactly; the
    destination is the *other* slot of the double-buffered scratch.  The
    quantized side-channel (``scale_*``, see :func:`preload_block_scores`)
    prefetches the next block's scale strip under the same condition.
    """

    @pl.when(cond)
    def _start():
        pltpu.make_async_copy(
            src0(),
            kv_view_next.at[pl.ds(0, sub_k), :],
            sem.at[0],
        ).start()
        if scale_view_next is not None:
            pltpu.make_async_copy(
                scale_src0(),
                scale_view_next.at[pl.ds(0, 1), :],
                scale_sem.at[0],
            ).start()


def decode_block_update(
    s,  # (G, Bk) f32 masked scores
    c_blk,  # (Bk, Dk) latent block; V = first d_v columns
    acc_ref, m_ref, l_ref, n_ref, gamma_ref, s16_ref,
    *,
    d_v: int,
    variant: str,
    mm_dtype,
    kv_scale=None,  # (1, Bk) f32 per-row dequant scales (int8 pages only)
):
    """One KV-block online-softmax update shared by the contiguous and paged
    decode kernels.

    ``variant == "amla"`` applies the paper's MUL-by-ADD rescale
    (``numerics.pow2_int_increment`` / ``apply_int_increment``), skipped
    entirely when the increment is all-zero; ``"base"`` is Algorithm 1's
    FP32-multiply rescale on every block.

    With int8 latent pages the true value rows are ``σ_j * q8_j``; rather
    than materialising a dequantized block, ``kv_scale`` folds σ into the
    probability rows (a (G, Bk) VPU multiply) so the PV matmul runs on the
    raw int8 block — the AMLA state (m, l, n, γ, S16) is invariant to
    where the σ multiply lands, and ``s`` already arrived dequantized from
    :func:`preload_block_scores`.
    """
    # [V1] (VPU): online softmax + power-of-two scale split.
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        p, axis=1, keepdims=True
    )
    m_ref[...] = m_new

    if variant == "amla":
        n_new, inv_r32 = numerics.round_scale_to_pow2(m_new)
        s16 = numerics.bf16_round(inv_r32)
        gamma_new = inv_r32 / s16
        eps = gamma_ref[...] / gamma_new - 1.0
        inc = numerics.pow2_int_increment(n_new - n_ref[...], eps)
        n_ref[...] = n_new
        gamma_ref[...] = gamma_new
        s16_ref[...] = s16
        p_v = p * s16 if kv_scale is None else p * s16 * kv_scale
        p_mm = p_v.astype(mm_dtype)

        # MUL-by-ADD rescale, skipped when the increment is all-zero
        # (the [V2]-elimination at the heart of the paper).
        @pl.when(jnp.any(inc != 0))
        def _rescale():
            acc_ref[...] = numerics.apply_int_increment(acc_ref[...], inc)

    else:  # base: Algorithm 1's FP32-multiply rescale, every block
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha
        p_mm = (p if kv_scale is None else p * kv_scale).astype(mm_dtype)

    # [C2] (MXU): T = P V with V = first d_v columns of the latent block.
    v_blk = c_blk[..., :d_v]
    if v_blk.dtype != mm_dtype:
        # int8 pages / fp32 compute over bf16 pages: cast this block only.
        v_blk = v_blk.astype(mm_dtype)
    t = jax.lax.dot_general(
        p_mm,
        v_blk,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] + t


def finalize_decode(o_ref, acc_ref, l_ref, s16_ref, *, variant: str):
    """Divide out the softmax denominator (and S16 for AMLA) into ``o_ref``."""
    l = l_ref[...]
    denom = l * s16_ref[...] if variant == "amla" else l
    safe = jnp.where(denom > 0, denom, 1.0)
    out = jnp.where(denom > 0, acc_ref[...] / safe, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


def _mla_decode_kernel(
    # scalar prefetch
    kv_len_ref,  # (B,) int32
    # inputs
    q_ref,  # (G, Dk) bf16
    q_pos_ref,  # (G, 1) int32 absolute position per query row (VMEM)
    c_hbm,  # (B, S_pad, Dk) bf16 latent cache, resident in HBM (ANY)
    # outputs
    o_ref,  # (G, Dv)
    # scratch
    acc_ref,  # (G, Dv) f32
    m_ref,  # (G, 1) f32
    l_ref,  # (G, 1) f32
    n_ref,  # (G, 1) i32      } amla only (allocated regardless; cheap)
    gamma_ref,  # (G, 1) f32  }
    s16_ref,  # (G, 1) f32    }
    kv_blk_ref,  # (2, block_k, Dk) double-buffered VMEM staging
    sem,  # DMA semaphores, one per sub-tile
    *,
    scale: float,
    d_v: int,
    variant: str,
    block_k: int,
    sub_k: int,
    softcap: float | None,
):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        init_decode_state(acc_ref, m_ref, l_ref, n_ref, gamma_ref, s16_ref)

    k_len = kv_len_ref[b]
    start = i * block_k
    n_sub = block_k // sub_k
    # Block i stages into buffer i % 2; the end-of-block lookahead targets
    # the other buffer.  Because live blocks of a request are a prefix of
    # its grid steps, "i > 0" is exactly "the previous step was live and
    # prefetched this block's sub-tile 0" (its condition below was this
    # block's liveness), and i == 0 (a new request row) restarts the parity
    # cleanly with a one-off warm-up.
    cur = jax.lax.rem(i, 2)

    @pl.when(start < k_len)
    def _compute():
        kv_view = kv_blk_ref.at[cur]

        def live(j):
            return start + j * sub_k < k_len

        def src(j):
            return c_hbm.at[b, pl.ds(start + j * sub_k, sub_k), :]

        s = preload_block_scores(
            q_ref, kv_view, n_sub=n_sub, sub_k=sub_k,
            src=src, live=live, sem=sem, first_prefetched=i > 0,
        )
        # Cross-step lookahead: start the next block's first sub-tile now so
        # its copy overlaps this block's state update.  No next-block check
        # against the grid bound is needed: start + block_k < k_len already
        # implies another block of this request exists (and the cache rows
        # are a whole number of sub-tiles), and a false condition at the
        # row's last live block also stops the chain before the next request
        # row.
        prefetch_next_first_subtile(
            lambda: c_hbm.at[b, pl.ds(start + block_k, sub_k), :],
            kv_blk_ref.at[1 - cur],
            sem,
            sub_k=sub_k,
            cond=start + block_k < k_len,
        )
        s = s * jnp.float32(scale)
        if softcap is not None:
            s = numerics.softcap(s, softcap)
        s = jnp.clip(s, -numerics.M_CLAMP, numerics.M_CLAMP)

        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (k_pos < k_len) & (k_pos <= q_pos_ref[...])
        s = jnp.where(mask, s, -jnp.inf)

        decode_block_update(
            s, kv_view[...],
            acc_ref, m_ref, l_ref, n_ref, gamma_ref, s16_ref,
            d_v=d_v, variant=variant, mm_dtype=q_ref.dtype,
        )

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        finalize_decode(o_ref, acc_ref, l_ref, s16_ref, variant=variant)


@functools.partial(
    jax.jit,
    static_argnames=(
        "d_v",
        "variant",
        "scale",
        "block_k",
        "softcap",
        "interpret",
    ),
)
def mla_decode_rows(
    q: jax.Array,  # (B, G, Dk)
    c_kv: jax.Array,  # (B, S, Dk)
    kv_len: jax.Array,  # (B,) int32
    q_pos: jax.Array,  # (B, G) int32
    *,
    d_v: int = 512,
    variant: str = "amla",
    scale: float,
    block_k: int = DEFAULT_BLOCK_K,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Row-level entry point; see ops.mla_decode for the (B,Sq,H,D) API.

    ``c_kv`` rows may be lane-padded past ``Dk`` (``init_latent_cache``
    allocates them so); ``q`` is zero-padded to match."""
    b, g, d_k = q.shape
    s = c_kv.shape[1]
    # Clamp to the cache length, staying a multiple of the sub-tile size so
    # the preload pipeline divides the block evenly.
    sub_k = min(SUB_K, max(block_k, 1))
    block_k = min(block_k, -(-max(s, 1) // sub_k) * sub_k)
    if block_k % sub_k:
        raise ValueError(f"block_k={block_k} must be a multiple of {sub_k}")
    # A sub-tile DMA may only move whole sub-tiles of rows and whole
    # 128-lane tiles of each row (not 576 of a row's 640 lanes), so a cache
    # that is not already aligned is copied once here.  Zero lanes in q and
    # the cache add nothing to the scores.
    rows = (-s) % sub_k
    lanes = lane_pad(d_k) - c_kv.shape[-1]
    if rows or lanes:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, rows), (0, lanes)))
    if c_kv.shape[-1] != d_k:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, c_kv.shape[-1] - d_k)))
        d_k = c_kv.shape[-1]
    n_blocks = -(-c_kv.shape[1] // block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((None, g, d_k), lambda bb, ii, *_: (bb, 0, 0)),
            pl.BlockSpec((None, g, 1), lambda bb, ii, *_: (bb, 0, 0)),
            # The latent cache stays in HBM; the kernel's preload pipeline
            # stages sub-tiles into VMEM scratch with explicit DMAs.
            pl.BlockSpec(memory_space=ANY),
        ],
        out_specs=pl.BlockSpec((None, g, d_v), lambda bb, ii, *_: (bb, 0, 0)),
        scratch_shapes=decode_state_scratch(g, d_v)
        + [
            # Double-buffered so the cross-step lookahead can stage the next
            # block's first sub-tile while this block is still being read.
            pltpu.VMEM((2, block_k, d_k), c_kv.dtype),
            pltpu.SemaphoreType.DMA((block_k // sub_k,)),
        ],
    )
    kernel = functools.partial(
        _mla_decode_kernel,
        scale=scale,
        d_v=d_v,
        variant=variant,
        block_k=block_k,
        sub_k=sub_k,
        softcap=softcap,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, d_v), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(
                g, g, d_k=d_k, d_v=d_v, block_k=block_k,
                q_dtype=q.dtype, kv_dtype=c_kv.dtype,
            ),
        ),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, q_pos.astype(jnp.int32)[..., None], c_kv)
