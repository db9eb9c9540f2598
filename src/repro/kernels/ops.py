"""Jit'd public wrappers around the Pallas kernels.

These adapt the framework's (B, S, H, D) tensor convention to the kernels'
cache-native layouts, fill in default masks/positions, and expose the
``interpret`` switch used for CPU validation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tracing import span
from repro.kernels import decode_schedule as _sched
from repro.kernels import flash_prefill as _prefill
from repro.kernels import gqa_decode as _gqa
from repro.kernels import mla_decode as _mla
from repro.kernels import mla_decode_combine as _combine
from repro.kernels import mla_decode_paged as _mla_paged


def _default_pos(b, sq, kv_len, sk):
    if kv_len is None:
        kv_len = jnp.full((b,), sk, jnp.int32)
    base = jnp.maximum(kv_len - sq, 0)
    q_pos = base[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    return kv_len.astype(jnp.int32), q_pos


def mla_decode(
    q: jax.Array,  # (B, Sq, Hq, Dk)
    c_kv: jax.Array,  # (B, S, >=Dk), rows lane-padded by init_latent_cache
    *,
    d_v: int = 512,
    variant: str = "amla",
    interpret: bool = False,
    scale: float,
    kv_len: jax.Array | None = None,
    causal: bool = True,
    q_offset: jax.Array | None = None,
    block_k: int = _mla.DEFAULT_BLOCK_K,
) -> jax.Array:
    b, sq, hq, dk = q.shape
    sk = c_kv.shape[1]
    kv_len, q_pos = _default_pos(b, sq, kv_len, sk)
    if q_offset is not None:
        q_pos = q_offset[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    if not causal:
        q_pos = jnp.full((b, sq), sk, jnp.int32)  # no causal restriction
    # rows = (Sq, Hq) flattened; every head of one token shares a position.
    rows_pos = jnp.repeat(q_pos, hq, axis=1)  # (B, Sq*Hq)
    q_rows = q.reshape(b, sq * hq, dk).astype(jnp.bfloat16)
    out = _mla.mla_decode_rows(
        q_rows,
        c_kv.astype(jnp.bfloat16),
        kv_len,
        rows_pos,
        d_v=d_v,
        variant=variant,
        scale=scale,
        block_k=block_k,
        interpret=interpret,
    )
    return out.reshape(b, sq, hq, d_v)


def default_paged_block_k(page_size: int, table_width: int) -> int:
    """§4.2 KV-block size for the work-queue path: 512 rows (4 pages of
    128), in units of whole pages, clamped to the table's capacity."""
    pages_per_block = max(1, _mla.DEFAULT_BLOCK_K // page_size)
    return page_size * min(pages_per_block, max(table_width, 1))


def _host_lens(kv_len, site: str) -> np.ndarray:
    """``kv_len`` as host numpy; reading a device array back is a
    ``serve.sync`` span, host numpy costs nothing."""
    if isinstance(kv_len, np.ndarray):
        return kv_len.reshape(-1)
    with span("serve.sync", site=site):
        return np.asarray(kv_len).reshape(-1)


def _validate_paged_geometry(
    q, kv_pages, block_tables, kv_len, block_k, kv_scales=None, scheduler="queue"
):
    """Fail fast, with actionable messages, on geometry the Pallas kernels
    would otherwise reject deep inside a trace (or worse, read garbage).

    ``kv_pages`` is one pool ``(P, page, Dk)`` or a layered ``(L, P, page,
    Dk)`` one (then ``kv_scales`` is ``(L, P, SW)``); only shapes and
    dtypes are read, so shape structs do as well as arrays."""
    b = q.shape[0]
    num_pages, page_size, dk_pages = kv_pages.shape[-3:]
    if kv_pages.dtype == jnp.int8:
        if kv_scales is None:
            raise ValueError(
                "int8 kv_pages need their per-row fp32 scale pool: pass "
                "kv_scales (PagedKVCache.scales) — symmetric int8 rows are "
                "meaningless without the dequant scales"
            )
        if scheduler != "queue":
            raise ValueError(
                f"scheduler={scheduler!r} does not support int8 pages; the "
                f"fused in-pipeline dequant lives in the work-queue kernel "
                f"(the padded (B, W) baseline stays bf16-only)"
            )
    if kv_scales is not None:
        if kv_pages.dtype != jnp.int8:
            raise ValueError(
                f"kv_scales given but kv_pages dtype is {kv_pages.dtype} — "
                f"scale pools accompany int8 pools only"
            )
        want = (num_pages, _mla.lane_pad(page_size))
        if tuple(kv_scales.shape[-2:]) != want:
            raise ValueError(
                f"kv_scales must be {want} — one fp32 scale per page row, "
                f"each page's row lane-padded (kv_cache.scale_pool_shape); "
                f"got {kv_scales.shape}"
            )
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(
            f"block_tables must be (B={b}, W); got {block_tables.shape} — "
            f"one row of logical->physical page ids per request"
        )
    w = block_tables.shape[1]
    if w < 1:
        raise ValueError(
            "block_tables must have at least one page column (W >= 1); "
            "use PagedKVCache.block_table, which pads empty sequences to "
            "width 1"
        )
    if dk_pages != _mla.lane_pad(q.shape[-1]):
        raise ValueError(
            f"queries and the page pool must share D_k: page rows are "
            f"{dk_pages} wide, but q's D_k={q.shape[-1]} needs pool rows of "
            f"{_mla.lane_pad(q.shape[-1])} (D_k padded to whole 128-lane "
            f"tiles, kv_cache.pool_shape)"
        )
    if block_k is not None and (block_k < page_size or block_k % page_size):
        raise ValueError(
            f"block_k={block_k} must be a positive multiple of the pool's "
            f"page_size={page_size} (one work item covers whole pages; "
            f"e.g. block_k={max(block_k // page_size, 1) * page_size or page_size}"
            f" or leave block_k=None for the §4.2 default)"
        )
    # Table-width bound: every valid token must resolve to a table entry.
    # A device kv_len is read back to the host here; skip silently when
    # traced.
    if not isinstance(kv_len, jax.core.Tracer):
        lens = _host_lens(kv_len, "kv_len_check")
        if lens.size and int(lens.max()) > w * page_size:
            worst = int(np.argmax(lens))
            raise ValueError(
                f"kv_len[{worst}]={int(lens.max())} exceeds the block "
                f"table's reach W*page_size={w}*{page_size}={w * page_size}"
                f" rows; widen block_tables (PagedKVCache.block_table("
                f"width=...)) or check kv_len bookkeeping"
            )


def _validate_q_positions(q_positions, b, sq, kv_len, scheduler, q_offset, causal):
    """Fail fast on the multi-row (speculative-verify) position surface."""
    if scheduler == "padded":
        raise NotImplementedError(
            "q_positions (the multi-row speculative-decode surface) is only "
            "implemented for scheduler='queue'; the padded (B, W) grid "
            "derives row positions from kv_len and has no per-row override"
        )
    if q_offset is not None:
        raise ValueError(
            "pass q_positions or q_offset, not both — q_positions already "
            "carries every row's absolute position"
        )
    if not causal:
        raise ValueError(
            "q_positions with causal=False is contradictory: explicit "
            "per-row positions exist to apply per-row causal masks"
        )
    shape = tuple(q_positions.shape)
    if shape != (b, sq):
        raise ValueError(
            f"q_positions must be (B={b}, Sq={sq}) — one absolute position "
            f"per query token row (heads share their token's position); "
            f"got {shape}"
        )
    # Value checks are host-side only (the serving path always has concrete
    # positions; traced callers skip, mirroring the kv_len bound check).
    if isinstance(q_positions, jax.core.Tracer):
        return
    arr = np.asarray(q_positions)
    if arr.size and int(arr.min()) < 0:
        raise ValueError(
            f"q_positions must be non-negative; got min {int(arr.min())} "
            f"(negative rows are the kernels' internal padding convention, "
            f"not a caller surface)"
        )
    if sq > 1 and np.any(np.diff(arr.astype(np.int64), axis=1) <= 0):
        bad = int(np.argmax(np.any(np.diff(arr, axis=1) <= 0, axis=1)))
        raise ValueError(
            f"q_positions must be strictly increasing per request "
            f"(speculative rows verify in sequence order); request {bad} "
            f"has {arr[bad].tolist()}"
        )
    if not isinstance(kv_len, jax.core.Tracer):
        lens = _host_lens(kv_len, "q_positions_check")
        over = arr >= lens[:, None]
        if np.any(over):
            bad = int(np.argmax(np.any(over, axis=1)))
            raise ValueError(
                f"q_positions[{bad}] reaches {int(arr[bad].max())} but "
                f"kv_len[{bad}]={int(lens[bad])}: every verify row must "
                f"already have its latent in the cache (append the k rows "
                f"before attending)"
            )


def validate_paged_call(
    q, kv_pages, block_tables, kv_len, *, block_k, kv_scales=None,
    q_positions=None,
):
    """The checks :func:`mla_decode_paged` makes of its geometry (and of
    ``q_positions``), for a caller that prepares the kernels' inputs
    itself: ``q`` may be a shape struct, and host numpy ``kv_len`` needs
    no read from the device."""
    _validate_paged_geometry(
        q, kv_pages, block_tables, kv_len, block_k, kv_scales
    )
    if q_positions is not None:
        b, sq = q.shape[:2]
        _validate_q_positions(q_positions, b, sq, kv_len, "queue", None, True)


def mla_decode_paged(
    q: jax.Array,  # (B, Sq, Hq, Dk)
    kv_pages: jax.Array,  # kv_cache.pool_shape(P, page_size, Dk) page pool
    block_tables: jax.Array,  # (B, W) int32 logical -> physical page ids
    kv_len: jax.Array,  # (B,) int32 valid tokens per request
    *,
    kv_scales: jax.Array | None = None,  # scale_pool_shape f32 (int8 pools)
    layer: jax.Array | int | None = None,  # layer of an (L, P, ...) pool
    d_v: int = 512,
    variant: str = "amla",
    interpret: bool = False,
    scale: float,
    causal: bool = True,
    q_offset: jax.Array | None = None,
    q_positions: jax.Array | None = None,
    softcap: float | None = None,
    scheduler: str = "queue",
    block_k: int | None = None,
    num_splits: int = 1,
    schedule=None,
    prefix_sharing: bool = False,
    min_group: int = 2,
    compute_dtype=None,
    return_partials: bool = False,
) -> jax.Array:
    """MLA decode over a paged latent cache (see runtime.kv_cache).

    Same contract as :func:`mla_decode` except the latent cache is addressed
    through per-request block tables into a shared page pool; ``kv_len`` is
    mandatory (it is what bounds each request's logical page walk).

    ``scheduler`` picks the execution strategy:

    * ``"queue"`` (default) — flat work-queue kernel: one grid step per
      §4.2 KV block (``block_k`` rows, default 4 pages) that actually
      intersects ``kv_len``, long requests split across ``num_splits``
      flash-decoding slots, partials merged by the combine kernel.  The
      schedule is built host-side from ``kv_len`` (pass a precomputed
      ``decode_schedule.DecodeSchedule`` via ``schedule`` to reuse it
      across serve-loop steps; ``kv_len`` must then describe the same
      per-request block counts).  Requires concrete (non-traced) ``kv_len``
      when ``schedule`` is None.
    * ``"padded"`` — the baseline ``(B, W)`` grid that pads every request
      to the widest block table.

    ``prefix_sharing=True`` (queue scheduler only) additionally runs the
    TyphoonMLA-style **group-batched prefix pass**: requests whose block
    tables alias the same leading pages (``PagedKVCache.fork``) have their
    shared KV blocks attended once per *group* over stacked queries
    (``mla_decode_paged_group_prefix``), per-request suffixes attended as
    usual, and the two partial sets merged exactly by the combine kernel.
    Groups need at least ``min_group`` members.  Pass a precomputed
    ``decode_schedule.PrefixSchedule`` via ``schedule`` to reuse grouping
    across steps; with no aliasing in the batch the path degenerates to the
    plain queue (at the cost of one extra gated combine column).

    ``compute_dtype`` is the kernel matmul dtype; default bf16 (the serving
    precision).  The full-model parity harness passes float32 so a paged
    fp32 smoke model is bit-comparable with the dense fp32 path.  Pages are
    staged in their storage dtype and cast **per strip inside the kernels**
    — a compute_dtype differing from the pool dtype never materialises a
    pool-sized copy.

    ``kv_scales`` (queue scheduler only) enables the int8 storage mode: the
    page pool holds symmetric int8 rows and ``kv_scales`` their per-row
    fp32 scales (``runtime.kv_cache`` with ``CacheSpec(dtype=jnp.int8)``
    maintains both).  Dequantization is fused into the preload pipeline, so
    int8 halves page-DMA bytes at unchanged kernel structure.

    ``q_positions`` (queue scheduler only) is the multi-row speculative
    surface: explicit per-row absolute positions ``(B, Sq)``, one per query
    *token* row (heads share their token's position).  It replaces the
    derived ``kv_len - Sq + arange(Sq)`` ramp — the verify step of
    draft-verify decode passes each request's k speculative rows here so
    all k attend the same page fetches with exact per-row causal masks.
    Positions must be strictly increasing per request (rows verify in
    sequence order) and inside ``[0, kv_len)``; mutually exclusive with
    ``q_offset``/``causal=False``.

    ``return_partials=True`` (plain queue scheduler only) additionally
    returns the per-row log-sum-exp alongside the output —
    ``(o (B,Sq,Hq,Dv), lse (B,Sq,Hq))`` in the normalized-partial format of
    the combine kernel — so a request whose KV is partitioned across hosts
    can merge shard-local results exactly with
    :func:`repro.core.distributed.combine_shard_partials`.

    ``kv_pages`` may be a ``LayeredPagedKVCache``'s whole ``(L, P, page,
    Dk)`` pool with ``layer`` naming the layer to attend (queue scheduler
    only): the kernels address it in place, no layer is sliced out.
    """
    b, sq, hq, dk = q.shape
    compute_dtype = jnp.bfloat16 if compute_dtype is None else compute_dtype
    _validate_paged_geometry(
        q, kv_pages, block_tables, kv_len, block_k, kv_scales, scheduler
    )
    if q_positions is not None:
        _validate_q_positions(
            q_positions, b, sq, kv_len, scheduler, q_offset, causal
        )
    kv_len = jnp.asarray(kv_len).astype(jnp.int32)
    base = jnp.maximum(kv_len - sq, 0)
    q_pos = base[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    if q_offset is not None:
        q_pos = q_offset[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    if q_positions is not None:
        q_pos = jnp.asarray(q_positions).astype(jnp.int32)
    if not causal:
        cap = block_tables.shape[1] * kv_pages.shape[-2]
        q_pos = jnp.full((b, sq), cap, jnp.int32)  # no causal restriction
    rows_pos = jnp.repeat(q_pos, hq, axis=1)  # (B, Sq*Hq)
    q_rows = q.reshape(b, sq * hq, dk).astype(compute_dtype)
    # Pool rows are lane-padded; zero query lanes add nothing to the scores.
    q_rows = jnp.pad(q_rows, ((0, 0), (0, 0), (0, kv_pages.shape[-1] - dk)))

    if isinstance(schedule, _sched.PrefixSchedule):
        prefix_sharing = True
    if return_partials and (scheduler != "queue" or prefix_sharing):
        raise ValueError(
            "return_partials needs the plain queue scheduler (padded and "
            "prefix-sharing paths merge heterogeneous partial sets and do "
            "not expose a per-row lse)"
        )
    if scheduler == "padded":
        if prefix_sharing:
            raise ValueError(
                "prefix_sharing requires scheduler='queue' (the padded "
                "(B, W) grid walks every request independently and cannot "
                "batch shared-prefix groups)"
            )
        pages, _, tables = _mla_paged.stacked_layer_view(
            kv_pages, None, block_tables, layer
        )
        out = _mla_paged.mla_decode_paged_rows(
            q_rows,
            pages,
            tables,
            kv_len,
            rows_pos,
            d_v=d_v,
            variant=variant,
            scale=scale,
            softcap=softcap,
            interpret=interpret,
        )
        return out.reshape(b, sq, hq, d_v)
    if scheduler != "queue":
        raise ValueError(f"unknown scheduler {scheduler!r}")

    page_size = kv_pages.shape[-2]
    if block_k is None:
        block_k = default_paged_block_k(page_size, block_tables.shape[1])
    if schedule is not None and prefix_sharing and not isinstance(
        schedule, _sched.PrefixSchedule
    ):
        raise ValueError(
            "prefix_sharing=True needs a decode_schedule.PrefixSchedule "
            f"(got {type(schedule).__name__}); build one with "
            "build_prefix_schedule or let schedule=None"
        )
    if schedule is not None and schedule.block_k != block_k:
        raise ValueError(
            f"schedule was built for block_k={schedule.block_k}, "
            f"call requested {block_k}"
        )
    if schedule is None:
        with span("serve.sync", site="schedule"):
            kv_host = np.asarray(kv_len)
            bt_host = np.asarray(block_tables) if prefix_sharing else None
        if prefix_sharing:
            schedule = _sched.build_prefix_schedule(
                kv_host,
                bt_host,
                page_size=page_size,
                block_k=block_k,
                num_splits=num_splits,
                min_group=min_group,
            )
        else:
            schedule = _sched.build_schedule(
                kv_host, block_k=block_k, num_splits=num_splits
            )
    arrays = jax.tree.map(jnp.asarray, schedule_arrays(schedule))
    out, lse = queue_attention(
        q_rows,
        rows_pos,
        kv_pages,
        block_tables,
        kv_len,
        schedule,
        arrays,
        layer=None if layer is None else jnp.asarray(layer, jnp.int32),
        kv_scales=kv_scales,
        d_v=d_v,
        variant=variant,
        scale=scale,
        block_k=block_k,
        softcap=softcap,
        interpret=interpret,
    )
    if return_partials:
        # Per-row local logsumexp across this call's split slots: dead slots
        # carry BIG_NEG lse, so including them adds exp(BIG_NEG) = 0.
        lse_slots = lse[..., 0] if lse.ndim == 3 else lse  # (D, G)
        per_split = lse_slots[arrays["dest"]]  # (B, S, G)
        lse_rows = jax.nn.logsumexp(
            per_split.astype(jnp.float32), axis=1
        )  # (B, G)
        return (
            out.reshape(b, sq, hq, d_v),
            lse_rows.reshape(b, sq, hq),
        )
    return out.reshape(b, sq, hq, d_v)


def schedule_arrays(schedule, rows: int | None = None) -> dict:
    """A decode schedule's kernel-side int32 arrays, as host numpy.

    ``suffix``: the six arrays of the queue kernel's work queue (the whole
    queue of a :class:`~repro.kernels.decode_schedule.DecodeSchedule`, the
    per-request suffix of a ``PrefixSchedule``); with prefix groups also
    ``prefix`` (the group-prefix queue), ``group_member``, ``group_rep``
    and ``prefix_lens``; then the combine's ``dest`` table and ``n_live``
    partials per request.  ``rows`` pads the combine's tables to that many
    requests: a padded request has no live partial and comes out as zeros.
    """
    if isinstance(schedule, _sched.PrefixSchedule):
        dest, n_live = schedule.hetero_dest_tables()
        out = {"suffix": schedule.suffix.prefetch_arrays()}
        if schedule.num_groups:
            out["prefix"] = schedule.prefix.prefetch_arrays()
            out["group_member"] = schedule.groups.group_member
            out["group_rep"] = schedule.groups.group_rep
            out["prefix_lens"] = np.asarray(schedule.prefix_lens, np.int32)
    else:
        dest, n_live = schedule.dest_table, schedule.n_splits
        out = {"suffix": schedule.prefetch_arrays()}
    pad = 0 if rows is None else rows - dest.shape[0]
    out["dest"] = np.pad(dest, ((0, pad), (0, 0)))
    out["n_live"] = np.pad(n_live, (0, pad))
    return out


def queue_attention(
    q_rows: jax.Array,  # (B', G, Dk_pool) query rows, B' >= B
    rows_pos: jax.Array,  # (B', G) int32 absolute position per row
    kv_pages: jax.Array,  # (P, page, Dk_pool) or (L, P, page, Dk_pool)
    block_tables: jax.Array,  # (B, W) int32
    kv_len: jax.Array,  # (B,) int32
    schedule,  # the DecodeSchedule or PrefixSchedule ``arrays`` came from
    arrays: dict,  # schedule_arrays(schedule, rows) on the device
    *,
    layer: jax.Array | None = None,
    kv_scales: jax.Array | None = None,
    d_v: int,
    variant: str,
    scale: float,
    block_k: int,
    softcap: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The kernels of one paged attention call over prepared query rows.

    The queue kernel runs the schedule's (suffix) queue; a
    ``PrefixSchedule`` with groups adds the group-prefix kernel; the
    combine merges the partials into ``(rows of arrays["dest"], G, Dv)``.
    Returns that and the queue kernel's per-slot lse.  Two or three device
    programs, nothing else: every input is already on the device.
    """
    if isinstance(schedule, _sched.PrefixSchedule):
        suffix, prefix = schedule.suffix, schedule.prefix
    else:
        suffix, prefix = schedule, None
    kw = dict(
        d_v=d_v, variant=variant, scale=scale, block_k=block_k,
        softcap=softcap, interpret=interpret,
    )
    o, lse = _mla_paged.mla_decode_paged_queue_rows(
        q_rows, kv_pages, block_tables, kv_len, rows_pos,
        *arrays["suffix"], kv_scales, layer,
        num_dest_slots=suffix.num_dest_slots, **kw,
    )
    if "prefix" in arrays:
        o_pref, lse_pref = _mla_paged.mla_decode_paged_group_prefix(
            q_rows, kv_pages, block_tables, rows_pos,
            arrays["group_member"], arrays["group_rep"],
            arrays["prefix_lens"], *arrays["prefix"], kv_scales, layer,
            num_dest_slots=prefix.num_dest_slots, **kw,
        )
        out = _combine.combine_hetero_partials(
            [o, o_pref], [lse, lse_pref], arrays["dest"], arrays["n_live"],
            interpret=interpret,
        )
    else:
        out = _combine.combine_split_partials(
            o, lse, arrays["dest"], arrays["n_live"], interpret=interpret
        )
    return out, lse


def gqa_attention(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Sk, Hkv, Dh)
    v: jax.Array,  # (B, Sk, Hkv, Dh)
    *,
    variant: str = "amla",
    interpret: bool = False,
    causal: bool = False,
    window: int | None = None,
    softcap: float | None = None,
    scale: float,
    kv_len: jax.Array | None = None,
    q_offset: jax.Array | None = None,
    decode_threshold: int = 8,
) -> jax.Array:
    """Dispatch decode-shaped calls to the decode kernel, else prefill."""
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    if sq <= decode_threshold:
        kv_len_a, q_pos = _default_pos(b, sq, kv_len, sk)
        if q_offset is not None:
            q_pos = q_offset[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
        if not causal and sq > 1:
            q_pos = jnp.full((b, sq), sk, jnp.int32)
        # rows within a kv head: (Sq, group) — position repeats per group.
        rows_pos = jnp.repeat(q_pos, group, axis=1)  # (B, Sq*group)
        qr = (
            q.reshape(b, sq, hkv, group, dh)
            .transpose(0, 2, 1, 3, 4)
            .reshape(b, hkv, sq * group, dh)
        )
        out = _gqa.gqa_decode_rows(
            qr.astype(jnp.bfloat16),
            k.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
            v.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
            kv_len_a,
            rows_pos,
            variant=variant,
            scale=scale,
            softcap=softcap,
            window=window,
            interpret=interpret,
        )
        out = out.reshape(b, hkv, sq, group, dh).transpose(0, 2, 1, 3, 4)
        return out.reshape(b, sq, hq, dh).astype(q.dtype)

    kv_len_a = (
        kv_len.astype(jnp.int32)
        if kv_len is not None
        else jnp.full((b,), sk, jnp.int32)
    )
    out = _prefill.flash_prefill(
        q.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
        k.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
        v.transpose(0, 2, 1, 3).astype(jnp.bfloat16),
        kv_len_a,
        variant=variant,
        scale=scale,
        softcap=softcap,
        window=window,
        causal=causal,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
