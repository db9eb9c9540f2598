"""Paged latent-KV cache: fixed-size pages + per-request block tables.

The serving-side memory manager behind ``kernels.mla_decode_paged``.  One
device-resident pool holds ``num_pages`` pages of ``page_size`` latent rows
(each row is the 576-wide ``[c ; k_rope]`` vector of MLA — but any width
works, so GQA K/V pools can reuse this class).  Requests own ordered lists of
physical page ids; appending tokens allocates pages on demand, freeing a
request returns its pages to the free list in O(1).  Because pages are
fixed-size, per-request memory waste is bounded by one page, and admission
control is a simple free-page count — the two properties contiguous
per-slot caches (runtime.serve_loop.ServingSession) lack: there, every slot
reserves ``max_len`` rows up front.

Pages are **refcounted**, so block tables may reference a physical page
many-to-one: :meth:`PagedKVCache.fork` aliases a prefix of one request's
pages into a new request (shared system prompt, multi-turn branch, n-best
sampling) without copying a single row.  A shared page is copied lazily —
**copy-on-write** — only when an append actually writes into it, which can
only happen on the partially-filled boundary page (full shared pages are
never written again: appends only ever touch the tail).  ``free`` decrements
refcounts and recycles a page only when its last owner releases it.

Page bookkeeping (free list, page lists, lengths) is host-side Python —
it is O(pages touched) per call and never enters a jit trace.  Only the page
pool itself lives on device.

Page size default follows ``kernels.mla_decode_paged.DEFAULT_PAGE_SIZE``
(128): four pages per §4.2 KV block of 512, lane-tile aligned, small enough
that ragged serving batches waste <1/8 of the pool at typical 1k contexts.
"""

from __future__ import annotations

import functools
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mla_decode import lane_pad
from repro.kernels.mla_decode_paged import DEFAULT_PAGE_SIZE, CacheSpec

__all__ = [
    "CacheSpec",
    "DoubleFreeError",
    "LayeredPagedKVCache",
    "OutOfPagesError",
    "PagedKVCache",
    "PrefixTrie",
    "pool_shape",
    "scale_pool_shape",
]


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(pages, rows, pid, off):
    """In-place-capable page write (buffer donation avoids a pool copy)."""
    return jax.lax.dynamic_update_slice(
        pages, rows[None].astype(pages.dtype), (pid, off, 0)
    )


def _quantize_rows(rows):
    """Symmetric per-row int8: ``q = round(x / σ)`` with ``σ = max|row|/127``.

    All-zero rows get ``σ = 1/127`` (any σ works — every element is 0);
    rows are widened to fp32 first so bf16 inputs quantize identically to
    their fp32 values.  Returns ``(int8 rows, fp32 scales)`` with the
    scales shaped like ``rows`` minus the trailing width axis.
    """
    rows = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(rows), axis=-1)
    scl = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(rows / scl[..., None]), -127, 127)
    return q.astype(jnp.int8), scl


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_rows_quant(pages, scales, rows, pid, off):
    """Quantize-on-write: int8 rows + per-row scales land in one call."""
    q, s = _quantize_rows(rows)
    pages = jax.lax.dynamic_update_slice(pages, q[None], (pid, off, 0))
    scales = jax.lax.dynamic_update_slice(scales, s[None], (pid, off))
    return pages, scales


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _copy_page_quant(pages, scales, dst_pid, src_pid):
    """COW fault for a quantized pool: page data and scale row move
    together — a copied page must stay exactly decodable."""
    page = jax.lax.dynamic_slice_in_dim(pages, src_pid, 1, axis=0)
    pages = jax.lax.dynamic_update_slice(pages, page, (dst_pid, 0, 0))
    srow = jax.lax.dynamic_slice_in_dim(scales, src_pid, 1, axis=0)
    scales = jax.lax.dynamic_update_slice(scales, srow, (dst_pid, 0))
    return pages, scales


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pages, dst_pid, src_pid):
    """Copy one physical page (the copy-on-write fault path)."""
    page = jax.lax.dynamic_slice_in_dim(pages, src_pid, 1, axis=0)
    return jax.lax.dynamic_update_slice(pages, page, (dst_pid, 0, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows_layered(pages, rows, pid, off):
    """Write ``rows (L, n, W)`` into page ``pid`` of every layer at once."""
    return jax.lax.dynamic_update_slice(
        pages, rows[:, None].astype(pages.dtype), (0, pid, off, 0)
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page_layered(pages, dst_pid, src_pid):
    """Copy one physical page across **all** layers (layered COW fault)."""
    page = jax.lax.dynamic_slice(
        pages, (0, src_pid, 0, 0), (pages.shape[0], 1) + pages.shape[2:]
    )
    return jax.lax.dynamic_update_slice(pages, page, (0, dst_pid, 0, 0))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_rows_layered_quant(pages, scales, rows, pid, off):
    """All-layer quantized write: ``rows (L, n, W)`` into page ``pid``."""
    q, s = _quantize_rows(rows)  # (L, n, W) int8, (L, n) f32
    pages = jax.lax.dynamic_update_slice(pages, q[:, None], (0, pid, off, 0))
    scales = jax.lax.dynamic_update_slice(scales, s[:, None], (0, pid, off))
    return pages, scales


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _copy_page_layered_quant(pages, scales, dst_pid, src_pid):
    """Layered COW fault, quantized: every layer's page plane *and* scale
    row copy in the same call — one device op covers all L layers."""
    page = jax.lax.dynamic_slice(
        pages, (0, src_pid, 0, 0), (pages.shape[0], 1) + pages.shape[2:]
    )
    pages = jax.lax.dynamic_update_slice(pages, page, (0, dst_pid, 0, 0))
    srow = jax.lax.dynamic_slice(
        scales, (0, src_pid, 0), (scales.shape[0], 1, scales.shape[2])
    )
    scales = jax.lax.dynamic_update_slice(scales, srow, (0, dst_pid, 0))
    return pages, scales


def scatter_layer_rows(pages, scales, rows, layer, pids, offs):
    """Write ``rows (R, W)`` into one layer of a layered pool: row ``i``
    lands at ``(layer, pids[i], offs[i])``, in its first ``W`` lanes.

    A row whose page id is out of range (``num_pages``, see
    :func:`plan_rows`) is dropped.  Quantized pools (``scales`` not None)
    take fp32-convertible rows and store int8 rows and their scales.  A
    traceable function: the cache's own write and the model's per-layer
    program both run it, on a donated pool.  Returns ``(pages, scales)``.
    """
    w = rows.shape[-1]
    if scales is None:
        rows = rows.astype(pages.dtype)
        return pages.at[layer, pids, offs, :w].set(rows, mode="drop"), None
    q, s = _quantize_rows(rows)
    pages = pages.at[layer, pids, offs, :w].set(q, mode="drop")
    return pages, scales.at[layer, pids, offs].set(s, mode="drop")


_write_layer_rows = jax.jit(scatter_layer_rows, donate_argnums=(0, 1))


def plan_rows(plans, rows: int, num_pages: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-row ``(page ids, offsets)`` of :meth:`PagedKVCache.reserve`
    plans, taken in order, padded to ``rows`` with page id ``num_pages``
    (out of range: :func:`scatter_layer_rows` drops those rows)."""
    pids = np.full((rows,), num_pages, np.int32)
    offs = np.zeros((rows,), np.int32)
    w = 0
    for plan in plans:
        for pid, off0, m in plan:
            pids[w : w + m] = pid
            offs[w : w + m] = off0 + np.arange(m, dtype=np.int32)
            w += m
    return pids, offs


class OutOfPagesError(RuntimeError):
    """Raised when an append needs more pages than the pool has free."""


def pool_shape(num_pages: int, page_size: int, width: int) -> tuple:
    """Shape of a device page pool of ``width``-wide rows.

    Rows are padded to whole 128-lane tiles (576 -> 640 for MLA): the
    kernels' page DMAs may only move whole tiles, and a TPU lays a 576-wide
    array out in 640 lanes anyway.  Pad lanes stay zero; writes and reads
    touch the first ``width``.  Every pool the kernels read is built to this
    shape, by :class:`PagedKVCache` and by tests that fill pools by hand."""
    return (num_pages, page_size, lane_pad(width))


def scale_pool_shape(num_pages: int, page_size: int) -> tuple:
    """Shape of an int8 pool's fp32 scale pool: one row per page, its
    first ``page_size`` lanes live, padded to whole 128-lane tiles so one
    page's scales are one aligned DMA."""
    return (num_pages, lane_pad(page_size))


class DoubleFreeError(RuntimeError):
    """Raised (debug mode only) when a request id is freed twice."""


class PagedKVCache:
    """Block-table paged KV pool with alloc/free/append.

    Parameters
    ----------
    num_pages:  total pages in the device pool.
    page_size:  latent rows per page.
    width:      row width (576 = 512 latent + 64 rope for DeepSeek MLA);
                the device pool pads it (:func:`pool_shape`).
    dtype:      storage dtype of the pool (bf16 in serving).  ``jnp.int8``
                selects the quantized mode: rows are symmetric-quantized
                on write against a companion per-row fp32 scale pool
                (:attr:`scales`), and every read path — the fused kernels,
                COW copies, :meth:`gather_contiguous` — carries the scales
                along.  Page bookkeeping (refcounts, fork, free) is
                storage-dtype-blind.
    spec:       full :class:`~repro.kernels.mla_decode_paged.CacheSpec`
                (dtype + scale granularity); overrides ``dtype``.
    debug:      when True, misuse that is silently tolerated in production
                (double-free) raises instead.
    """

    def __init__(
        self,
        *,
        num_pages: int,
        page_size: int = DEFAULT_PAGE_SIZE,
        width: int = 576,
        dtype=jnp.bfloat16,
        spec: CacheSpec | None = None,
        debug: bool = False,
    ):
        if num_pages < 1 or page_size < 1:
            raise ValueError("need at least one page of at least one row")
        self.num_pages = num_pages
        self.page_size = page_size
        self.width = width
        self.spec = spec if spec is not None else CacheSpec(dtype=dtype)
        self.dtype = self.spec.dtype
        self.debug = debug
        self.pages = self._make_pool()
        self.scales = self._make_scale_pool() if self.quantized else None
        # FIFO free list: freed pages are reused in release order, so a
        # long-lived session naturally produces fragmented (non-contiguous,
        # non-monotone) block tables — which the kernel must not care about.
        self._free: deque[int] = deque(range(num_pages))
        self._seq_pages: dict[int, list[int]] = {}
        self._seq_len: dict[int, int] = {}
        # Owners per physical page: 0 = on the free list, >1 = aliased by a
        # fork.  Host-side numpy, like all page bookkeeping.
        self._ref = np.zeros((num_pages,), np.int32)
        # Retention pins per page: references held by a prefix cache
        # (PrefixTrie) rather than by a live sequence.  Every pin is also
        # counted in ``_ref`` — a pinned page cannot return to the free
        # list — so refcount_sweep can reconcile exactly:
        # ``_ref == sequence owners + _pin`` for every page.
        self._pin = np.zeros((num_pages,), np.int32)

    # ------------------------------------------------------------------ #
    # bookkeeping
    # ------------------------------------------------------------------ #
    def to_device(self, device) -> "PagedKVCache":
        """Commit the pool (and scale pool) to ``device``.

        Sharded serving places one pool per data shard; device_put commits
        the arrays, and the donated jit write/copy helpers keep every
        subsequent pool update resident on that device.  Host-side
        bookkeeping (free list, refcounts) is untouched.  No-op when
        ``device`` is None.
        """
        if device is not None:
            self.pages = jax.device_put(self.pages, device)
            if self.scales is not None:
                self.scales = jax.device_put(self.scales, device)
        return self

    @property
    def quantized(self) -> bool:
        """True when the pool stores int8 rows + a per-row scale pool."""
        return self.spec.quantized

    @property
    def num_free_pages(self) -> int:
        return len(self._free)

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def pages_needed_for_append(self, rid: int | None, n_tokens: int) -> int:
        """New pages an append of ``n_tokens`` to ``rid`` (or a new seq) grabs.

        Includes the copy-on-write page when the append's first rows land in
        a page that is aliased by another request (``refcount > 1``).
        """
        used = self._seq_len.get(rid, 0) if rid is not None else 0
        have = len(self._seq_pages.get(rid, [])) if rid is not None else 0
        need = self.pages_needed(used + n_tokens) - have
        if (
            rid is not None
            and n_tokens > 0
            and used % self.page_size
            and self._ref[self._seq_pages[rid][used // self.page_size]] > 1
        ):
            need += 1  # COW copy of the shared boundary page
        return need

    def has_room(self, rid: int | None, n_tokens: int) -> bool:
        """Can ``n_tokens`` more rows be appended to ``rid`` (or a new seq)?"""
        return self.pages_needed_for_append(rid, n_tokens) <= self.num_free_pages

    def alloc(self, rid: int) -> None:
        """Register an empty sequence (pages are grabbed lazily by append)."""
        if rid in self._seq_pages:
            raise KeyError(f"sequence {rid} already allocated")
        self._seq_pages[rid] = []
        self._seq_len[rid] = 0

    def _grab_page(self) -> int:
        pid = self._free.popleft()
        assert self._ref[pid] == 0, f"free-list page {pid} still referenced"
        self._ref[pid] = 1
        return pid

    def _release_page(self, pid: int) -> None:
        self._ref[pid] -= 1
        assert self._ref[pid] >= 0, f"page {pid} refcount underflow"
        if self._ref[pid] == 0:
            self._free.append(pid)

    def free(self, rid: int) -> None:
        """Release ``rid``'s pages; each returns to the free list only when
        its last owner lets go (forked prefixes alias pages many-to-one).

        Idempotent: freeing an id that is not live is a no-op in production
        (double-frees must not re-enqueue live pages onto the free list);
        with ``debug=True`` it raises :class:`DoubleFreeError` instead.
        """
        pages = self._seq_pages.pop(rid, None)
        if pages is None:
            if self.debug:
                raise DoubleFreeError(
                    f"free({rid}): request is not live — it was already "
                    f"freed or never allocated"
                )
            return
        for pid in pages:
            self._release_page(pid)
        del self._seq_len[rid]

    def fork(self, src: int, dst: int, prefix_len: int | None = None) -> None:
        """Create ``dst`` sharing ``src``'s first ``prefix_len`` rows.

        Every page that intersects the prefix — including a partially-filled
        boundary page — is *aliased* (refcount bumped), so a fork allocates
        zero pages and copies zero rows.  Rows of the boundary page past
        ``prefix_len`` are dead in ``dst`` (masked by its ``seq_len``) until
        an append overwrites them; that append triggers the copy-on-write in
        :meth:`append`, as does a ``src`` append into its now-shared tail.
        ``prefix_len`` defaults to all of ``src``.
        """
        if dst in self._seq_pages:
            raise KeyError(f"sequence {dst} already allocated")
        src_len = self._seq_len[src]  # KeyError if src is not live
        if prefix_len is None:
            prefix_len = src_len
        if not 0 <= prefix_len <= src_len:
            raise ValueError(
                f"fork prefix_len={prefix_len} outside [0, {src_len}] "
                f"(seq {src} has {src_len} rows)"
            )
        shared = self._seq_pages[src][: self.pages_needed(prefix_len)]
        for pid in shared:
            self._ref[pid] += 1
        self._seq_pages[dst] = list(shared)
        self._seq_len[dst] = prefix_len

    # -- retention (prefix-cache) references ---------------------------- #
    def pin_pages(self, pids) -> None:
        """Take a retention reference on each of ``pids``.

        The prefix trie's hold: a pinned page survives every sequence
        owner releasing it (``free``/``truncate`` just decrement the
        refcount), so a finished request's prefix pages stay resident and
        re-admittable until :meth:`unpin_pages`.  Pages must be live —
        pinning is only legal while some owner (the finishing request)
        still holds them, which is what keeps a free-list page from ever
        being resurrected.
        """
        pids = [int(p) for p in pids]
        for pid in pids:
            if self._ref[pid] < 1:
                raise ValueError(
                    f"cannot pin page {pid}: it is on the free list (pin "
                    "pages before their last sequence owner releases them)"
                )
        for pid in pids:
            self._ref[pid] += 1
            self._pin[pid] += 1

    def unpin_pages(self, pids) -> None:
        """Drop retention references; pages whose last owner was the pin
        return to the free list."""
        pids = [int(p) for p in pids]
        for pid in pids:
            if self._pin[pid] < 1:
                raise ValueError(f"page {pid} holds no retention pin")
        for pid in pids:
            self._pin[pid] -= 1
            self._release_page(pid)

    def adopt_pages(self, rid: int, pids, n_rows: int) -> None:
        """Register ``rid`` as a new sequence aliasing ``pids`` (a prefix-
        cache hit): :meth:`fork` from a page list instead of a live parent.

        ``n_rows`` must exactly fill the adopted pages (prefix-cache hits
        are complete-block — hence page — aligned), so the next append
        starts on a fresh page and never copy-on-write-faults an adopted
        page mid-admission.
        """
        if rid in self._seq_pages:
            raise KeyError(f"sequence {rid} already allocated")
        pids = [int(p) for p in pids]
        if n_rows != len(pids) * self.page_size:
            raise ValueError(
                f"adopt_pages needs page-aligned rows: {n_rows} rows do "
                f"not fill {len(pids)} pages of {self.page_size}"
            )
        for pid in pids:
            if self._ref[pid] < 1:
                raise ValueError(
                    f"cannot adopt page {pid}: it is on the free list"
                )
        for pid in pids:
            self._ref[pid] += 1
        self._seq_pages[rid] = list(pids)
        self._seq_len[rid] = n_rows

    def pool_occupancy(self) -> dict:
        """Page census: sequence-owned vs retained-only vs free.

        ``retained_pages`` counts pages whose *only* remaining owners are
        retention pins — resident purely as prefix cache; ``live_pages``
        counts pages some sequence still references (possibly pinned too).
        The three always sum to ``num_pages``.
        """
        live = int(np.sum(self._ref > self._pin))
        retained = int(np.sum((self._ref > 0) & (self._ref == self._pin)))
        return {
            "live_pages": live,
            "retained_pages": retained,
            "free_pages": self.num_free_pages,
            "pinned_pages": int(np.sum(self._pin > 0)),
        }

    def truncate(self, rid: int, n: int) -> None:
        """Roll ``rid`` back to its first ``n`` rows (speculative rollback).

        The bookkeeping inverse of an append: ``seq_len`` shrinks to ``n``
        and every page wholly past the new boundary is released through the
        refcount — a page still aliased by a fork/COW sibling just loses
        this request's reference (the sibling's data is untouched); a page
        owned solely by ``rid`` returns to the free list.  The boundary
        page (holding row ``n - 1``) always survives, including a private
        COW copy made for the rows now being rejected — its live prefix
        rows belong to this request.  Stale rows inside the kept pages are
        masked by ``kv_len`` and overwritten by the next append, so no
        device data moves: rollback is exact and O(pages released).

        Truncating to the current length (e.g. a double-truncate after a
        fully-accepted verify step) is a no-op.  Raises ``KeyError`` for a
        request that is not live and ``ValueError`` when ``n`` is outside
        ``[0, seq_len]`` — rollback can only discard rows, never invent
        them.
        """
        cur = self._seq_len[rid]  # KeyError if rid is not live
        if not 0 <= n <= cur:
            raise ValueError(
                f"truncate({rid}, {n}): new length must be in [0, {cur}] "
                f"(rollback discards tail rows; it cannot extend a request)"
            )
        keep = self.pages_needed(n)
        pages = self._seq_pages[rid]
        for pid in pages[keep:]:
            self._release_page(pid)
        del pages[keep:]
        self._seq_len[rid] = n

    def seq_len(self, rid: int) -> int:
        return self._seq_len[rid]

    def seq_pages(self, rid: int) -> list[int]:
        return list(self._seq_pages[rid])

    def live_sequences(self) -> list[int]:
        return list(self._seq_pages)

    def page_refcount(self, pid: int) -> int:
        """Owners of physical page ``pid`` (0 = on the free list)."""
        return int(self._ref[pid])

    def num_aliased_pages(self) -> int:
        """Physical pages currently shared by more than one request."""
        return int(np.sum(self._ref > 1))

    def refcount_sweep(self) -> dict:
        """Audit the host-mirror accounting; raises AssertionError on a leak.

        Recomputes every page's expected refcount from the sequence tables
        and cross-checks the ``_ref`` array and the free list.  Any
        divergence — a leaked page (freed sequence still pinning it), a
        double-free (live page on the free list), a duplicated free-list
        entry — is an assertion failure naming the page.  The chaos tests
        run this after every suspend/replay storm: "no pool pages leak"
        is gated here, not inferred from ``num_free_pages``.
        """
        seq_owned = np.zeros(self.num_pages, dtype=np.int64)
        for rid, pages in self._seq_pages.items():
            for pid in pages:
                seq_owned[pid] += 1
        # Retention pins are owners too: a page's refcount must equal its
        # sequence owners plus its prefix-cache pins, exactly.
        expected = seq_owned + self._pin.astype(np.int64)
        bad = np.nonzero(expected != self._ref)[0]
        assert bad.size == 0, (
            f"refcount mismatch on pages {bad.tolist()[:8]}: "
            f"expected {expected[bad].tolist()[:8]} owners from the "
            f"sequence tables + pins, _ref says {self._ref[bad].tolist()[:8]}"
        )
        neg = np.nonzero(self._pin < 0)[0]
        assert neg.size == 0, f"negative pin count on pages {neg.tolist()[:8]}"
        free = list(self._free)
        free_set = set(free)
        assert len(free) == len(free_set), (
            f"free list holds {len(free) - len(free_set)} duplicate entries"
        )
        should_be_free = {int(p) for p in np.nonzero(expected == 0)[0]}
        assert free_set == should_be_free, (
            f"free list out of sync: {sorted(free_set - should_be_free)[:8]} "
            f"free but owned, {sorted(should_be_free - free_set)[:8]} "
            f"unowned but not free (leaked)"
        )
        return {
            "live_pages": int(np.sum(seq_owned > 0)),
            "retained_pages": int(
                np.sum((seq_owned == 0) & (self._pin > 0))
            ),
            "free_pages": len(free),
            "aliased_pages": int(np.sum(expected > 1)),
            "live_sequences": len(self._seq_pages),
        }

    # ------------------------------------------------------------------ #
    # data path
    # ------------------------------------------------------------------ #
    def _make_pool(self) -> jax.Array:
        """Allocate the device page pool (layered subclasses override)."""
        return jnp.zeros(
            pool_shape(self.num_pages, self.page_size, self.width), self.dtype
        )

    def _make_scale_pool(self) -> jax.Array:
        """Per-page-row fp32 dequant scales (quantized pools only)."""
        return jnp.zeros(
            scale_pool_shape(self.num_pages, self.page_size), jnp.float32
        )

    def _pool_copy_page(self, dst_pid: int, src_pid: int) -> None:
        """Device-side page copy (the COW fault path)."""
        if self.quantized:
            self.pages, self.scales = _copy_page_quant(
                self.pages, self.scales, jnp.int32(dst_pid), jnp.int32(src_pid)
            )
        else:
            self.pages = _copy_page(
                self.pages, jnp.int32(dst_pid), jnp.int32(src_pid)
            )

    def _pool_write(self, pid: int, off: int, rows: jax.Array) -> None:
        """Device-side row write into one page."""
        # jit'd + donated: a 1-row decode append is an in-place slice
        # write, not an O(pool) copy.  Indices are traced scalars, so
        # only distinct chunk lengths ``m`` trigger a retrace (decode
        # appends are always m == 1).
        if self.quantized:
            self.pages, self.scales = _write_rows_quant(
                self.pages, self.scales, rows, jnp.int32(pid), jnp.int32(off)
            )
        else:
            self.pages = _write_rows(
                self.pages, rows, jnp.int32(pid), jnp.int32(off)
            )

    def reserve(self, rid: int, n: int) -> list[tuple[int, int, int]]:
        """Bookkeeping half of an append: claim room for ``n`` more rows.

        Allocates pages on demand, resolves copy-on-write for a shared
        boundary page (the device copy happens here, once — for the layered
        cache that is one copy covering *all* layers), and advances
        ``seq_len``.  Returns the write plan as ``(page_id, offset, count)``
        chunks; callers then fill the rows with :meth:`write_reserved` (or
        per-layer via the layered cache's ``write_layer*``).  Raises
        :class:`OutOfPagesError` up front, leaving the sequence unchanged.
        """
        if not self.has_room(rid, n):
            raise OutOfPagesError(
                f"append of {n} rows to seq {rid} needs more than the "
                f"{self.num_free_pages} free pages"
            )
        used = self._seq_len[rid]
        page_list = self._seq_pages[rid]
        chunks: list[tuple[int, int, int]] = []
        off = 0
        while off < n:
            pos = used + off
            if pos // self.page_size == len(page_list):
                page_list.append(self._grab_page())
            pid = page_list[pos // self.page_size]
            if self._ref[pid] > 1:
                # Copy-on-write: this tail page is aliased by a forked
                # sibling/parent — give this request a private copy before
                # the write.  Only ever the (partial) boundary page.
                new_pid = self._grab_page()
                self._pool_copy_page(new_pid, pid)
                self._ref[pid] -= 1
                page_list[pos // self.page_size] = new_pid
                pid = new_pid
            in_page = pos % self.page_size
            m = min(self.page_size - in_page, n - off)
            chunks.append((pid, in_page, m))
            off += m
        self._seq_len[rid] = used + n
        return chunks

    def write_reserved(
        self, chunks: list[tuple[int, int, int]], rows: jax.Array
    ) -> None:
        """Fill reserved chunks with ``rows`` (row count must match)."""
        off = 0
        for pid, in_page, m in chunks:
            self._pool_write(pid, in_page, rows[off : off + m])
            off += m

    @property
    def _row_dtype(self):
        """Dtype appended rows are coerced to.  A quantized pool takes
        *unquantized* fp32 rows — quantization happens in the write hook
        (casting caller rows to int8 here would silently destroy them)."""
        return jnp.float32 if self.quantized else self.pages.dtype

    def _validate_rows(self, rows) -> jax.Array:
        rows = jnp.asarray(rows, self._row_dtype)
        if rows.ndim != 2 or rows.shape[1] != self.width:
            raise ValueError(f"rows must be (n, {self.width}); got {rows.shape}")
        return rows

    def append(self, rid: int, rows: jax.Array) -> None:
        """Append ``rows (n, width)`` to sequence ``rid``, allocating pages.

        Raises :class:`OutOfPagesError` (leaving the sequence unchanged) if
        the pool cannot hold the new rows.
        """
        rows = self._validate_rows(rows)
        # shape[-2] is the row count in both layouts: (n, W) and (L, n, W).
        self.write_reserved(self.reserve(rid, rows.shape[-2]), rows)

    def block_table(
        self, rids: list[int], width: int | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched ``(block_tables (B, W) int32, kv_len (B,) int32)``.

        ``W`` defaults to the max page count among ``rids`` (min 1); shorter
        rows are padded with page id 0 — the kernel skips them via kv_len.
        """
        if width is None:
            width = max([len(self._seq_pages[r]) for r in rids] + [1])
        bt = np.zeros((len(rids), width), np.int32)
        kv = np.zeros((len(rids),), np.int32)
        for i, r in enumerate(rids):
            pages = self._seq_pages[r]
            if len(pages) > width:
                raise ValueError(f"seq {r} has {len(pages)} pages > width {width}")
            bt[i, : len(pages)] = pages
            kv[i] = self._seq_len[r]
        return bt, kv

    def gather_contiguous(self, rid: int) -> jax.Array:
        """Reassemble ``rid``'s rows as a contiguous (len, width) array.

        Debug/test helper — the serving path never materialises this.
        Quantized pools dequantize (int8 × per-row scale → fp32).
        """
        n = self._seq_len[rid]
        if n == 0:
            return jnp.zeros((0, self.width), self._row_dtype)
        w, ps = self.width, self.page_size
        if self.quantized:
            parts = [
                self.pages[pid, :, :w].astype(jnp.float32)
                * self.scales[pid, :ps][:, None]
                for pid in self._seq_pages[rid]
            ]
        else:
            parts = [self.pages[pid, :, :w] for pid in self._seq_pages[rid]]
        return jnp.concatenate(parts, axis=0)[:n]


class LayeredPagedKVCache(PagedKVCache):
    """One block table + refcounts shared by all ``L`` layers of a model.

    The full-model serving cache: the page *bookkeeping* (free list, block
    tables, seq lens, refcounts, fork/COW/free) is exactly
    :class:`PagedKVCache`'s and runs **once per request** — but the device
    pool carries a leading layer axis ``(L, num_pages, page_size, width)``,
    so page ``p`` of request ``r`` names the same physical slot in every
    layer.  One ``fork`` aliases a prefix for all 60 layers of a DeepSeek
    stack; one COW fault copies the boundary page across all layers in a
    single device op; ``block_table`` returns one table the decode step
    reuses for every layer (which is also what lets the serve loop build
    one decode *schedule* per step instead of per layer).

    The data path is two-phase so appends compose with the sequential layer
    walk of a transformer: :meth:`reserve` claims the rows up front (once
    per pass), then each layer fills its own plane with
    :func:`scatter_layer_rows` — inside the model's per-layer program, or
    through :meth:`write_layer` / :meth:`write_layer_tokens`.  The
    inherited one-shot :meth:`append` takes ``(L, n, width)`` rows for
    callers that have all layers' latents in hand.
    """

    def __init__(
        self,
        *,
        num_layers: int,
        num_pages: int,
        page_size: int = DEFAULT_PAGE_SIZE,
        width: int = 576,
        dtype=jnp.bfloat16,
        spec: CacheSpec | None = None,
        debug: bool = False,
    ):
        if num_layers < 1:
            raise ValueError("need at least one layer")
        self.num_layers = num_layers
        self._layer_ids = None
        super().__init__(
            num_pages=num_pages,
            page_size=page_size,
            width=width,
            dtype=dtype,
            spec=spec,
            debug=debug,
        )

    # -- pool hooks (layer-axis aware) --------------------------------- #
    def _make_pool(self) -> jax.Array:
        return jnp.zeros(
            (self.num_layers,)
            + pool_shape(self.num_pages, self.page_size, self.width),
            self.dtype,
        )

    def _make_scale_pool(self) -> jax.Array:
        # Scales carry the layer axis too: every layer's latent rows are
        # quantized independently against their own row maxima.
        return jnp.zeros(
            (self.num_layers,) + scale_pool_shape(self.num_pages, self.page_size),
            jnp.float32,
        )

    def _pool_copy_page(self, dst_pid: int, src_pid: int) -> None:
        # One COW fault copies the page across every layer in one op —
        # quantized pools move the scale rows in the same call.
        if self.quantized:
            self.pages, self.scales = _copy_page_layered_quant(
                self.pages, self.scales, jnp.int32(dst_pid), jnp.int32(src_pid)
            )
        else:
            self.pages = _copy_page_layered(
                self.pages, jnp.int32(dst_pid), jnp.int32(src_pid)
            )

    def _pool_write(self, pid: int, off: int, rows: jax.Array) -> None:
        # rows (L, m, W): all-layer write (the one-shot append path).
        if self.quantized:
            self.pages, self.scales = _write_rows_layered_quant(
                self.pages, self.scales, rows, jnp.int32(pid), jnp.int32(off)
            )
        else:
            self.pages = _write_rows_layered(
                self.pages, rows, jnp.int32(pid), jnp.int32(off)
            )

    def _validate_rows(self, rows) -> jax.Array:
        rows = jnp.asarray(rows, self._row_dtype)
        want = (self.num_layers, self.width)
        if rows.ndim != 3 or (rows.shape[0], rows.shape[2]) != want:
            raise ValueError(
                f"rows must be (L={self.num_layers}, n, {self.width}); "
                f"got {rows.shape}"
            )
        return rows

    def write_reserved(
        self, chunks: list[tuple[int, int, int]], rows: jax.Array
    ) -> None:
        """Fill reserved chunks with all-layer ``rows (L, n, width)``."""
        off = 0
        for pid, in_page, m in chunks:
            self._pool_write(pid, in_page, rows[:, off : off + m])
            off += m

    # -- per-layer data path ------------------------------------------- #
    def write_layer(
        self, layer: int, chunks: list[tuple[int, int, int]], rows: jax.Array
    ) -> None:
        """Fill one layer's plane of reserved chunks with ``rows (n, W)``:
        :meth:`reserve` once, then every layer writes into the same
        chunks."""
        n = sum(m for _, _, m in chunks)
        self.write_layer_tokens(
            layer, *plan_rows([chunks], n, self.num_pages), rows
        )

    def write_layer_tokens(self, layer: int, pids, offs, rows) -> None:
        """Scatter ``rows (R, W)`` into one layer at ``(layer, pids[i],
        offs[i])`` in one donated device call (each row carries its own
        page and offset, so a run of rows may cross a page boundary).
        Rows past ``len(pids)`` are not written.  The model's layer walk
        runs the same :func:`scatter_layer_rows` inside its own per-layer
        program."""
        pids = np.asarray(pids, np.int32)
        rows = jnp.asarray(rows, self._row_dtype)[: pids.shape[0]]
        self.pages, self.scales = _write_layer_rows(
            self.pages, self.scales, rows, jnp.int32(layer), pids,
            np.asarray(offs, np.int32),
        )

    @property
    def device(self):
        """The pool's device when it lives on one (else None, the default
        device): where a pass's inputs are uploaded."""
        devices = self.pages.devices()
        return next(iter(devices)) if len(devices) == 1 else None

    def layer_ids(self) -> tuple[jax.Array, ...]:
        """One int32 scalar per layer on the pool's device, made once and
        reused by every pass: the kernels and the per-layer write address
        layer ``l`` of the stacked pool through ``layer_ids()[l]``, with no
        upload per layer and no per-layer copy of the pool."""
        device = self.device
        if self._layer_ids is None or self._layer_ids[0].devices() != (
            self.pages.devices()
        ):
            self._layer_ids = jax.device_put(
                tuple(np.int32(l) for l in range(self.num_layers)), device
            )
        return self._layer_ids

    def gather_contiguous(self, rid: int, layer: int | None = None) -> jax.Array:
        """Contiguous ``(len, width)`` rows of one layer (or ``(L, len,
        width)`` for all layers when ``layer`` is None).  Test helper;
        quantized pools dequantize."""
        n = self._seq_len[rid]
        if n == 0:
            shape = (
                (self.num_layers, 0, self.width)
                if layer is None
                else (0, self.width)
            )
            return jnp.zeros(shape, self._row_dtype)
        axis = 1 if layer is None else 0
        sel = self.pages if layer is None else self.pages[layer]
        sel = sel[..., : self.width]
        parts = [sel[:, pid] if layer is None else sel[pid]
                 for pid in self._seq_pages[rid]]
        out = jnp.concatenate(parts, axis=axis)
        if self.quantized:
            ssel = self.scales if layer is None else self.scales[layer]
            ssel = ssel[..., : self.page_size]
            sparts = [ssel[:, pid] if layer is None else ssel[pid]
                      for pid in self._seq_pages[rid]]
            out = out.astype(jnp.float32) * jnp.concatenate(sparts, axis=axis)[
                ..., None
            ]
        return out[:, :n] if layer is None else out[:n]


# --------------------------------------------------------------------------- #
# radix prefix trie — token-keyed retention over §4.2-aligned page runs
# --------------------------------------------------------------------------- #


class _TrieNode:
    """One radix edge: a run of complete KV blocks and the pages holding it.

    ``blocks`` is the edge label — a sequence of block-sized token tuples —
    and ``pages`` the physical page ids whose rows are those tokens' latent
    states (``len(pages) == len(blocks) * pages_per_block``, exactly).  The
    node owns one retention pin per page; children are keyed by their
    edge's *first block* (block-granular radix: two prefixes diverging
    inside a block share nothing cacheable, so sub-block branching never
    needs representing).
    """

    __slots__ = ("blocks", "pages", "children", "parent", "last_used")

    def __init__(self, blocks, pages, parent):
        self.blocks: list[tuple] = list(blocks)
        self.pages: list[int] = list(pages)
        self.children: dict[tuple, _TrieNode] = {}
        self.parent: _TrieNode | None = parent
        self.last_used = 0


class PrefixTrie:
    """Radix tree over cached prompt prefixes, at §4.2 KV-block granularity.

    The lifecycle half of automatic prefix caching: finished requests
    *retain* their prompt's complete-block pages here (one retention pin
    per page, see :meth:`PagedKVCache.pin_pages`), admission *matches* a
    new prompt token-by-token against the tree and aliases every matched
    page zero-copy (:meth:`PagedKVCache.adopt_pages`), and a cost-aware
    LRU evictor reclaims cold subtrees leaf-first when the pool or the
    ``retain_pages`` budget demands it.

    Block granularity is load-bearing three ways: (1) matched lengths are
    multiples of ``block_tokens`` — which is a multiple of ``page_size`` —
    so adoption is page-aligned and the divergent tail's first append
    grabs a fresh page (no COW fault against a retained page, ever);
    (2) trie hits are exactly the complete blocks the group-batched
    prefix kernel can batch, so a hit feeds the nested prefix scheduler
    unchanged; (3) two prompts diverging *inside* a block share no
    cacheable state, so children key on whole block-token tuples and the
    tree never represents sub-block branches.

    ``epoch`` increments on every topology change (insert, split, evict);
    the memoizing decode scheduler folds it into its key so retained-page
    churn can never serve a stale schedule.  All bookkeeping is host-side
    Python, O(blocks touched) per call, exactly like the page tables.
    """

    def __init__(
        self,
        cache: PagedKVCache,
        *,
        block_tokens: int,
        retain_pages: int | None = None,
    ):
        if block_tokens < 1 or block_tokens % cache.page_size:
            raise ValueError(
                f"block_tokens={block_tokens} must be a positive multiple "
                f"of the cache page_size={cache.page_size} (trie nodes own "
                "whole pages)"
            )
        if retain_pages is not None and retain_pages < 0:
            raise ValueError(f"retain_pages must be >= 0, got {retain_pages}")
        self.cache = cache
        self.block_tokens = int(block_tokens)
        self.pages_per_block = block_tokens // cache.page_size
        self.retain_pages = retain_pages
        self.root = _TrieNode((), (), None)
        self.epoch = 0
        self._clock = 0
        self.pinned_pages = 0
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.inserted_nodes = 0
        self.evicted_nodes = 0
        self.evicted_pages = 0

    # -- structure ------------------------------------------------------- #
    def _blocks_of(self, tokens) -> list[tuple]:
        bt = self.block_tokens
        n = len(tokens) // bt
        return [tuple(tokens[i * bt : (i + 1) * bt]) for i in range(n)]

    @property
    def num_nodes(self) -> int:
        count, stack = 0, [self.root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                count += 1
                stack.append(child)
        return count

    def _leaves(self) -> list[_TrieNode]:
        out, stack = [], [self.root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                if child.children:
                    stack.append(child)
                else:
                    out.append(child)
        return out

    def _freeable(self, node: _TrieNode) -> int:
        """Pages of ``node`` whose only remaining owners are pins —
        unpinning them actually returns pages to the free list."""
        ref, pin = self.cache._ref, self.cache._pin
        return sum(1 for p in node.pages if ref[p] == pin[p])

    def _any_freeable(self) -> bool:
        ref, pin = self.cache._ref, self.cache._pin
        return bool(np.any((pin > 0) & (ref == pin)))

    # -- lookup ---------------------------------------------------------- #
    def match(self, tokens, *, touch: bool = True, count: bool = True):
        """Longest cached prefix of ``tokens``: ``(matched_rows, pages)``.

        Walks edges greedily; a partial edge match is usable as-is (the
        edge's leading pages cover it) without splitting anything.  With
        ``touch`` the walked nodes' LRU stamps refresh; ``touch=False,
        count=False`` is the non-mutating probe the sharded router uses
        to score shards before committing an admission.
        """
        blocks = self._blocks_of(tokens)
        if touch:
            self._clock += 1
        node = self.root
        pages: list[int] = []
        i = 0
        while i < len(blocks):
            child = node.children.get(blocks[i])
            if child is None:
                break
            k = 0
            while (
                k < len(child.blocks)
                and i + k < len(blocks)
                and child.blocks[k] == blocks[i + k]
            ):
                k += 1
            if touch:
                child.last_used = self._clock
            pages.extend(child.pages[: k * self.pages_per_block])
            i += k
            if k < len(child.blocks):
                break
            node = child
        matched = i * self.block_tokens
        if count:
            if matched:
                self.hits += 1
                self.hit_tokens += matched
            else:
                self.misses += 1
        return matched, pages

    # -- retention ------------------------------------------------------- #
    def _split(self, node: _TrieNode, k: int) -> _TrieNode:
        """Split ``node``'s edge after ``k`` blocks; returns the new top
        half.  Pin counts are untouched — the same pages stay pinned once,
        they just belong to two nodes now."""
        ppb = self.pages_per_block
        top = _TrieNode(node.blocks[:k], node.pages[: k * ppb], node.parent)
        top.last_used = node.last_used
        node.parent.children[top.blocks[0]] = top
        node.blocks = node.blocks[k:]
        node.pages = node.pages[k * ppb :]
        node.parent = top
        top.children[node.blocks[0]] = node
        self.epoch += 1
        return top

    def insert(self, tokens, pages) -> int:
        """Retain ``tokens``' complete blocks, backed by ``pages``.

        Called at request finish time, **before** ``cache.free(rid)`` —
        pins only legally stack on live pages.  Blocks already cached keep
        their existing (bit-identical) pages; only the uncovered tail pins
        new ones.  ``pages`` must be the request's leading page list
        covering at least the complete blocks.  Returns how many pages
        were newly pinned; enforces the ``retain_pages`` budget by LRU
        eviction afterwards.
        """
        ppb = self.pages_per_block
        blocks = self._blocks_of(tokens)
        if len(pages) < len(blocks) * ppb:
            raise ValueError(
                f"insert of {len(blocks)} blocks needs "
                f"{len(blocks) * ppb} pages, got {len(pages)}"
            )
        self._clock += 1
        node = self.root
        i = 0
        pinned = 0
        while i < len(blocks):
            child = node.children.get(blocks[i])
            if child is None:
                tail_pages = [
                    int(p) for p in pages[i * ppb : len(blocks) * ppb]
                ]
                self.cache.pin_pages(tail_pages)
                fresh = _TrieNode(blocks[i:], tail_pages, node)
                fresh.last_used = self._clock
                node.children[blocks[i]] = fresh
                self.pinned_pages += len(tail_pages)
                pinned = len(tail_pages)
                self.inserted_nodes += 1
                self.epoch += 1
                break
            k = 0
            while (
                k < len(child.blocks)
                and i + k < len(blocks)
                and child.blocks[k] == blocks[i + k]
            ):
                k += 1
            child.last_used = self._clock
            if k < len(child.blocks):
                if i + k == len(blocks):
                    break  # ends inside the edge: already fully covered
                child = self._split(child, k)
            node = child
            i += k
        if self.retain_pages is not None:
            self.trim_to_budget()
        return pinned

    # -- eviction -------------------------------------------------------- #
    def _evict_node(self, node: _TrieNode) -> int:
        """Unpin one leaf and detach it; returns pages actually freed."""
        before = self.cache.num_free_pages
        del node.parent.children[node.blocks[0]]
        self.cache.unpin_pages(node.pages)
        self.pinned_pages -= len(node.pages)
        self.evicted_nodes += 1
        self.evicted_pages += len(node.pages)
        self.epoch += 1
        return self.cache.num_free_pages - before

    def reclaim(self, n_pages: int) -> int:
        """Evict cold subtrees until >= ``n_pages`` returned to the free
        list (pool pressure).  Cost-aware LRU, leaf-first: among leaves,
        prefer ones whose pages actually free (no live request aliases
        them), oldest ``last_used`` first; a zero-yield leaf is evicted
        only to unlock a freeable ancestor.  Stops — returning what it
        got — when nothing pinned anywhere can free a page, so retention
        of still-aliased prefixes is never torn down pointlessly."""
        freed = 0
        while freed < int(n_pages) and self._any_freeable():
            leaves = self._leaves()
            if not leaves:
                break
            victim = min(
                leaves,
                key=lambda n: (self._freeable(n) == 0, n.last_used),
            )
            freed += self._evict_node(victim)
        return freed

    def trim_to_budget(self) -> int:
        """LRU-evict until ``pinned_pages <= retain_pages`` (no-op when
        unbudgeted).  Returns pages returned to the free list."""
        if self.retain_pages is None:
            return 0
        freed = 0
        while self.pinned_pages > self.retain_pages:
            leaves = self._leaves()
            if not leaves:
                break
            freed += self._evict_node(
                min(leaves, key=lambda n: n.last_used)
            )
        return freed

    def clear(self) -> int:
        """Evict everything (session teardown); returns pages freed."""
        freed = 0
        while True:
            leaves = self._leaves()
            if not leaves:
                return freed
            for leaf in leaves:
                freed += self._evict_node(leaf)

    # -- introspection --------------------------------------------------- #
    def stats(self) -> dict:
        return {
            "pinned_pages": self.pinned_pages,
            "num_nodes": self.num_nodes,
            "epoch": self.epoch,
            "hits": self.hits,
            "misses": self.misses,
            "hit_tokens": self.hit_tokens,
            "inserted_nodes": self.inserted_nodes,
            "evicted_nodes": self.evicted_nodes,
            "evicted_pages": self.evicted_pages,
        }
