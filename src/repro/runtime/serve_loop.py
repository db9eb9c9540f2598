"""Distributed serving: sharded prefill + decode step factories and a
batched serving session (continuous-batching-lite).

Cache sharding follows runtime.sharding.cache_spec_fn: batch over ``data``
when divisible (decode_32k: 128/16), else sequence-parallel split-KV over
``data`` (long_500k: batch 1, 524288 keys), head/latent width over ``model``.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.tracing import Tracer, span
from repro.runtime import sharding

# The serving path's spans (``repro.core.tracing``), each reported by
# ``PagedServingSession.work_stats``.  "window", "step", "add_request" and
# "finish" are the chip benchmark's own span names; no span here takes one.
SERVE_SPANS = (
    "serve.step",  # the whole of PagedServingSession.step()
    "serve.decode",  # the decode pass, through the greedy readback
    "serve.prefill",  # one budgeted prefill slice, first-token read included
    "serve.schedule",  # reserve, block tables, decode schedules, slice plans
    "serve.sync",  # a device-to-host read
    "serve.admit",  # add_request
    "serve.finish",  # finish
)
# Counters kept beside the spans: the device programs the model's layer
# walk launched (``models.transformer._paged_pass``).
SERVE_COUNTERS = ("walk_dispatches",)


def _first_token(logits) -> int:
    """The greedy token of a one-request paged prefill's ``(1, vocab)``
    logits: one argmax program and its read, nothing sliced on the
    device."""
    return int(np.asarray(jnp.argmax(logits, axis=-1))[0])


def jit_serve_fns(
    model, mesh, params_like, cache_like, *, multi_pod=False, policy="tp_fsdp"
):
    """Returns (compile_prefill, compile_decode, shardings)."""
    pfn = sharding.param_spec_fn(
        mesh, multi_pod=multi_pod, policy=policy, cfg=model.cfg
    )
    cfn = sharding.cache_spec_fn(mesh, multi_pod=multi_pod, policy=policy)
    bfn = sharding.batch_spec_fn(mesh, multi_pod=multi_pod, policy=policy)

    param_sh = sharding.make_shardings(mesh, params_like, pfn)
    cache_sh = sharding.make_shardings(mesh, cache_like, cfn)
    rep = NamedSharding(mesh, P())

    def tokens_sh(tokens_like):
        return sharding.make_shardings(mesh, tokens_like, bfn)

    def decode_fn(params, cache, tokens, cache_len):
        return model.decode_step(params, cache, tokens, cache_len)

    def prefill_fn(params, cache, tokens):
        return model.prefill(params, cache, tokens)

    def compile_decode(tokens_like):
        return jax.jit(
            decode_fn,
            in_shardings=(param_sh, cache_sh, tokens_sh(tokens_like), rep),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,),
        )

    def compile_prefill(tokens_like):
        return jax.jit(
            prefill_fn,
            in_shardings=(param_sh, cache_sh, tokens_sh(tokens_like)),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,),
        )

    return compile_prefill, compile_decode, {
        "params": param_sh,
        "cache": cache_sh,
    }


class ServingSession:
    """Single-host batched serving with slot reuse (continuous-batching-lite).

    A fixed batch of decode slots; each new request is prefilled into a free
    slot (ragged lengths handled by per-slot cache_len), every ``step()``
    advances all active slots one token, and finished requests free their
    slot for the next queued prompt.  Greedy sampling.
    """

    def __init__(self, model, params, *, batch_size: int, max_len: int):
        self.model = model
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.cache = model.init_cache(params, batch_size, max_len)
        self.cache_len = np.zeros((batch_size,), np.int32)
        self.last_token = np.zeros((batch_size,), np.int32)
        self.slot_rid: list[int | None] = [None] * batch_size
        self.outputs: dict[int, list[int]] = {}
        self._next_id = 0
        self._decode = jax.jit(model.decode_step)
        # Bucketed prefill: right-pad prompts to the next power of two so a
        # stream of ragged prompt lengths compiles O(log max_len) prefill
        # shapes instead of one per distinct length.  Only attention stacks
        # tolerate right-padding (causal masking keeps pad tokens invisible
        # to real positions); recurrent/SSM state would ingest the pads, so
        # those archs prefill at exact length.
        kinds = model.cfg.layer_kinds() if hasattr(model.cfg, "layer_kinds") else []
        self._bucket_prompts = bool(kinds) and all(
            k in ("global", "local") for k in kinds
        )
        self._prefill = jax.jit(
            lambda p, c, t, lp: model.prefill(p, c, t, last_pos=lp)
        )
        self._prefill_shapes: set[int] = set()

    @property
    def active_mask(self) -> np.ndarray:
        return np.asarray([r is not None for r in self.slot_rid])

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill shapes compiled (== jit compiles of prefill)."""
        return len(self._prefill_shapes)

    def _bucket_len(self, plen: int) -> int:
        if not self._bucket_prompts:
            return plen
        blen = 1 << max(plen - 1, 0).bit_length()
        return max(min(blen, self.max_len), plen)

    def add_request(self, prompt_tokens) -> int | None:
        """Prefill a prompt into a free slot; returns request id or None."""
        prompt_tokens = list(map(int, prompt_tokens))
        plen = len(prompt_tokens)
        if plen < 1:
            raise ValueError(
                "add_request needs at least one prompt token (an empty "
                "prompt has no prefill position to decode from)"
            )
        if plen > self.max_len:
            raise ValueError(
                f"prompt of {plen} tokens exceeds this session's "
                f"max_len={self.max_len}; raise max_len or truncate the "
                "prompt (slots reserve exactly max_len cache rows)"
            )
        if None not in self.slot_rid:
            return None
        slot = self.slot_rid.index(None)
        # Recycled-slot invariant: finish() zeroes the slot's decode state,
        # so a reused slot must look factory-fresh here — decoding from a
        # stale cache_len/last_token would splice the previous request's
        # context into this one.
        assert self.cache_len[slot] == 0 and self.last_token[slot] == 0, (
            f"slot {slot} reused with stale state: cache_len="
            f"{self.cache_len[slot]}, last_token={self.last_token[slot]}"
        )
        rid = self._next_id
        self._next_id += 1
        blen = self._bucket_len(plen)
        padded = prompt_tokens + [0] * (blen - plen)
        prompt = jnp.asarray(padded, jnp.int32)[None]
        # Prefill this slot by running the full-batch decode over the prompt
        # with only this slot's cache_len advancing (other rows are no-ops on
        # their own cache positions because their tokens re-write in place).
        # Pad rows beyond plen are causally invisible and overwritten by the
        # first decode steps (cache_len = plen masks them meanwhile).
        single = self.model.init_cache(self.params, 1, self.max_len)
        self._prefill_shapes.add(blen)
        logits, single = self._prefill(
            self.params, single, prompt, jnp.int32(plen - 1)
        )
        self.cache = jax.tree.map(
            lambda full, one: _write_slot(full, one, slot), self.cache, single
        )
        self.cache_len[slot] = plen
        first = int(jnp.argmax(logits[0, -1]))
        self.last_token[slot] = first
        self.slot_rid[slot] = rid
        self.outputs[rid] = [first]
        return rid

    def step(self):
        """One decode step for every active slot."""
        if not self.active_mask.any():
            return
        logits, self.cache = self._decode(
            self.params,
            self.cache,
            jnp.asarray(self.last_token)[:, None],
            jnp.asarray(self.cache_len),
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        act = self.active_mask
        for slot, rid in enumerate(self.slot_rid):
            if rid is not None:
                self.outputs[rid].append(int(nxt[slot]))
                self.last_token[slot] = nxt[slot]
        self.cache_len = self.cache_len + act.astype(np.int32)

    def finish(self, rid: int) -> list[int]:
        slot = self.slot_rid.index(rid)
        self.slot_rid[slot] = None
        self.cache_len[slot] = 0
        # A reused slot must never decode from the previous request's token:
        # clear the stale last_token with the slot (step() skips inactive
        # slots, but the very next admit must start from its own prefill
        # logits, not this leftover).
        self.last_token[slot] = 0
        return self.outputs.pop(rid)


class NGramProposer:
    """Self-speculative n-gram drafter over a request's own token history.

    ``propose(history, k)`` returns ``k`` draft tokens by suffix matching:
    for the longest n-gram suffix of ``history`` (``max_n`` down to
    ``min_n``) it finds the most recent *earlier* occurrence and proposes
    the ``k`` tokens that followed it.  A continuation shorter than ``k``
    is padded with its own last token; with no match at all the draft
    repeats the last history token.  The drafts are free (no model call)
    and only ever *proposed* — the verify step in
    :class:`PagedServingSession` keeps greedy decoding exact regardless of
    draft quality, so a bad draft costs acceptance rate, never
    correctness.  Any object with the same ``propose(history, k)``
    signature can replace it (e.g. a small zoo draft model later).
    """

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if not 1 <= min_n <= max_n:
            raise ValueError(
                f"need 1 <= min_n <= max_n, got min_n={min_n} max_n={max_n}"
            )
        self.max_n = max_n
        self.min_n = min_n

    def propose(self, history, k: int) -> list[int]:
        if k < 1:
            raise ValueError(f"propose needs k >= 1 draft tokens, got {k}")
        hist = list(map(int, history))
        if not hist:
            return [0] * k
        best: list[int] = []
        for n in range(min(self.max_n, len(hist) - 1), self.min_n - 1, -1):
            suffix = hist[-n:]
            # Scan most-recent-first: recency tracks the current decoding
            # loop better than the global mode does, and the first match
            # with a full k-token continuation short-circuits the scan.
            for i in range(len(hist) - n - 1, -1, -1):
                if hist[i : i + n] == suffix:
                    cont = hist[i + n : i + n + k]
                    if len(cont) == k:
                        return cont
                    if len(cont) > len(best):
                        best = cont
        if best:
            return best + [best[-1]] * (k - len(best))
        return [hist[-1]] * k


@dataclasses.dataclass
class SuspendedRequest:
    """Host-side survival kit of a suspended request.

    Everything :meth:`PagedServingSession.resume` needs to rebuild the
    device state by replay: the prompt, the emitted tokens, and (for a
    forked child) which parent it branched from and how many rows the
    shared prefix covered.  ``outputs`` is the request's *live* list
    object, not a copy — sharded-session output views alias it, so they
    stay current across suspend/resume hops (including cross-shard
    re-routes off a dead shard).
    """

    prompt: list[int]
    outputs: list[int]
    parent: int | None = None
    prefix_len: int = 0

    @property
    def tokens(self) -> list[int]:
        """Cache rows replay must rebuild: prompt + emitted minus the
        pending token (``outputs[-1]`` is not a cache row yet — between
        steps the paged session keeps rows = prompt + outputs[:-1], and
        speculative rollback already restored that invariant)."""
        return self.prompt + self.outputs[:-1]


class PagedServingSession:
    """Full-model serving over the paged cache backend.

    The paged twin of :class:`ServingSession`: the same greedy
    add_request / step / finish surface, but the KV state is a
    :class:`~repro.runtime.kv_cache.LayeredPagedKVCache` — one refcounted
    block table shared by all L layers over an ``(L, pages, page, 576)``
    latent pool — and decode runs through ``ops.mla_decode_paged`` via
    ``models.transformer.lm_decode_step_paged``.  What that buys over dense
    slots:

    * admission is by free-page count (no per-slot ``max_len``
      reservation); requests admit/evict mid-stream and a request's
      context can grow until the *pool* is full;
    * prompts prefill **into pages** in fixed-size chunks (one compiled
      shape, reported via :attr:`prefill_compiles`);
    * :meth:`fork` / :meth:`admit_with_prefix` branch a live request by
      page aliasing — zero copies, one refcount bump covering every layer,
      COW on the shared boundary page at the next append — turning the
      PR 3 prefix-sharing machinery into a full-model product feature;
    * the decode schedule is built **once per step** and reused by all L
      layers (every layer shares the block table), and the memoizing
      :class:`~repro.kernels.decode_schedule.DecodeScheduler` reuses it
      across steps — :attr:`scheduler_stats` counts steps, not ``L x
      steps``;
    * the cache storage dtype is a serving knob (``kv_dtype="int8"``):
      quantized pools halve page-DMA bytes with dequant fused into the
      kernel pipeline, and :meth:`work_stats` reports the dtype-aware
      ``page_dma_bytes`` proxy;
    * ``speculate="ngram"`` turns each step into a draft-verify step:
      a :class:`NGramProposer` (pluggable via ``draft_proposer``) drafts
      ``draft_k - 1`` tokens from the request's own history, the model
      verifies all ``draft_k`` rows in **one** fused multi-row decode
      (same page DMAs as a 1-row step — the GEMV becomes a GEMM), the
      longest draft prefix matching the model's own greedy choices is
      accepted, and rejected tail rows roll back exactly via
      :meth:`~repro.runtime.kv_cache.LayeredPagedKVCache.truncate`.
      Greedy outputs are token-for-token identical to ``speculate="off"``
      — speculation changes the *cost* per emitted token, never the
      tokens.
    * ``prefill_budget=N`` turns admission into **token-budgeted
      chunked-prefill/decode interleaving**: :meth:`add_request` only
      *enqueues* the prompt (after the same pool/trie admission checks),
      and every :meth:`step` first decodes the live batch, then spends up
      to ``N`` prompt tokens advancing pending prompts by
      ``prefill_chunk``-aligned slices through the same
      ``lm_prefill_paged`` path — so a long prompt arrival never stalls
      decode.  Slice boundaries land exactly on the chunk boundaries a
      monolithic prefill would use, so cache rows and greedy outputs stay
      bit-identical to phase-separated (``prefill_budget=None``) serving.
    * ``max_batch`` caps admission and fixes the decode step's batch: the
      dense programs always run on ``max_batch`` rows (``batch_rows`` of
      ``lm_decode_step_paged``), so a request's greedy tokens do not depend
      on how many others are live, and sessions with the same
      ``max_batch`` (one chip, or one data shard of
      :class:`ShardedPagedServingSession`) agree in bf16.  A resumed request
      may push the live count past ``max_batch``; that step runs a program
      of twice the rows, and bf16 parity is no longer guaranteed for it.
    """

    def __init__(
        self,
        model,
        params,
        *,
        num_pages: int,
        page_size: int | None = None,
        block_k: int | None = None,
        num_splits: int = 1,
        prefix_sharing: bool = False,
        min_group: int = 2,
        prefill_chunk: int = 32,
        max_batch: int | None = None,
        interpret: bool = False,
        dtype=None,
        kv_dtype=None,
        device=None,
        head_shards: int = 1,
        speculate: str = "off",
        draft_k: int = 4,
        draft_proposer=None,
        prefix_cache: str = "off",
        retain_pages: int | None = None,
        prefill_budget: int | None = None,
    ):
        from repro.kernels import ops
        from repro.kernels.decode_schedule import DecodeScheduler
        from repro.models import transformer as _tf
        from repro.runtime.kv_cache import CacheSpec, PrefixTrie

        _tf.check_paged_compatible(model.cfg)
        if model.cfg.n_heads % head_shards:
            raise ValueError(
                f"head_shards={head_shards} must divide "
                f"n_heads={model.cfg.n_heads} (tensor-parallel head groups "
                "split the query head axis evenly)"
            )
        if device is not None:
            # Commit this session's params replica to its shard device so
            # the cache pool (placed below) and every kernel call stay
            # resident there; donated pool writes keep the residency.
            params = jax.device_put(params, device)
        self.device = device
        self.head_shards = head_shards
        self.model = model
        self.params = params
        self.cfg = model.cfg
        # ``dtype`` is the serving compute precision (defaults to the
        # model's); ``kv_dtype`` is the cache *storage* layout — "int8"
        # stores quantized latent pages + per-row scales at roughly half
        # the page-DMA bytes, dequantized inside the kernel pipeline.
        self.dtype = dtype or model.dtype
        self.cache_spec = (
            kv_dtype
            if isinstance(kv_dtype, CacheSpec)
            # CacheSpec normalizes dtype-name strings ("int8") itself.
            else CacheSpec(dtype=self.dtype if kv_dtype is None else kv_dtype)
        )
        self.cache = model.init_paged_cache(
            params, num_pages=num_pages, page_size=page_size,
            spec=self.cache_spec,
        )
        if device is not None:
            self.cache.to_device(device)
        # Fixed block-table width: stable kernel input shapes across
        # admits/evicts and page-boundary growth (see PagedDecodeSession).
        self.table_width = num_pages
        self.block_k = block_k or ops.default_paged_block_k(
            self.cache.page_size, self.table_width
        )
        self.num_splits = num_splits
        self.prefix_sharing = prefix_sharing
        self.prefill_chunk = prefill_chunk
        self.max_batch = max_batch
        if prefill_budget is not None and prefill_budget < prefill_chunk:
            raise ValueError(
                f"prefill_budget={prefill_budget} is below prefill_chunk="
                f"{prefill_chunk}: a budgeted step spends whole chunks "
                "(padded model invocations), so a smaller budget could "
                "never advance any pending prompt"
            )
        self.prefill_budget = (
            None if prefill_budget is None else int(prefill_budget)
        )
        # Pending-prompt queue (budgeted interleaving): admitted-but-
        # unprefilled prompts, FIFO by arrival.  ``done`` counts the rows
        # already prefilled (including trie-adopted blocks).
        self._pending: list[dict] = []
        if prefix_cache not in ("off", "trie"):
            raise ValueError(
                f"prefix_cache={prefix_cache!r} is not a cache policy; "
                "pick 'off' or 'trie'"
            )
        self.prefix_cache = prefix_cache
        self.trie = None
        if prefix_cache == "trie":
            # Trie hits alias complete §4.2 blocks, so a warm admission's
            # suffix prefill restarts exactly at a block boundary.  With
            # the chunk dividing block_k, those warm chunk boundaries
            # coincide with a cold prefill's — the cached rows and the
            # greedy stream stay bit-identical to trie-off serving.
            if self.block_k % prefill_chunk:
                raise ValueError(
                    f"prefix_cache='trie' needs prefill_chunk "
                    f"({prefill_chunk}) to divide block_k ({self.block_k}) "
                    "so warm suffix prefills land on the same chunk "
                    "boundaries as cold ones (bit-identical outputs)"
                )
            # Trie hits only pay off when the scheduler groups the aliased
            # blocks; nested grouping scores each trie node once.
            self.prefix_sharing = True
            self.trie = PrefixTrie(
                self.cache,
                block_tokens=self.block_k,
                retain_pages=retain_pages,
            )
        # Pallas interpret mode is for CPU validation and must be asked
        # for; serving compiles the kernels for the chip.
        self.interpret = bool(interpret)
        # fp32 smoke models keep fp32 kernel precision so paged greedy
        # outputs stay bit-comparable with the dense fp32 backend; bf16
        # serving uses the kernels' native bf16.
        self.compute_dtype = jnp.float32 if self.dtype == jnp.float32 else None
        self._scheduler = DecodeScheduler(
            block_k=self.block_k,
            num_splits=num_splits,
            min_group=min_group,
            nested=self.trie is not None,
        )
        self._layers = _tf.per_layer_params(params, model.cfg)
        if speculate not in ("off", "ngram"):
            raise ValueError(
                f"speculate={speculate!r} is not a draft policy; pick 'off' "
                "or 'ngram' (or pass a custom draft_proposer with ngram)"
            )
        if speculate != "off" and draft_k < 2:
            raise ValueError(
                f"draft_k={draft_k} buys nothing: a speculative step "
                "verifies 1 pending + (draft_k - 1) draft rows, so "
                "draft_k >= 2 is the smallest step that amortizes anything "
                "(use speculate='off' for plain 1-row decode)"
            )
        self.speculate = speculate
        self.draft_k = int(draft_k)
        self._proposer = draft_proposer or (
            NGramProposer() if speculate != "off" else None
        )
        # Token history per request (prompt + emitted) feeds the drafter;
        # kept even with speculation off so fork/admit_with_prefix children
        # inherit a correct history if a later session turns drafting on.
        self._prompt: dict[int, list[int]] = {}
        self.active: list[int] = []
        self.outputs: dict[int, list[int]] = {}
        self.last_token: dict[int, int] = {}
        self._next_id = 0
        self._prefill_shapes: set[tuple] = set()
        self._decode_shapes: set[int] = set()
        # Deterministic work counters (benchmarks / regression proxies).
        # ``request_steps``/``query_rows``/``accepted_tokens`` keep the
        # per-token proxies honest under speculation: a k-row verify step
        # counts k query rows but only the accepted prefix as tokens, so
        # page_dma_bytes_per_accepted_token can't be gamed by drafting
        # rows that get rejected.
        self.decode_steps = 0
        self.request_steps = 0
        self.query_rows = 0
        self.accepted_tokens = 0
        self.page_dmas = 0
        self.rows_attended = 0
        # Virtual work clock.  One work unit = one model invocation: a
        # fused decode launch or one padded prefill chunk — a deterministic
        # step-count proxy (``ServeSupervisor(arrival_unit="work_units")``).
        # ``prefill_stall_steps`` counts chunks prefilled *synchronously*
        # while decoders were live (the phase-separated head-of-line stall;
        # identically 0 under budgeted interleaving).
        self.work_units = 0
        self.prefill_chunks = 0
        self.prefill_stall_steps = 0
        # Wall-clock spans of add_request / step / finish and what runs
        # inside them (SERVE_SPANS), the layer walk's program launches
        # (SERVE_COUNTERS), and the wait of budgeted prompts from their
        # enqueue to the start of their first prefill slice.
        self.tracer = Tracer(SERVE_SPANS, SERVE_COUNTERS)
        self.prefill_wait_s = 0.0
        self.prefill_waits = 0
        # Recoverable eviction (suspend/resume by replay) + chaos state.
        # ``_family`` maps a forked child to (parent rid, shared rows) so
        # suspend records the alias point and resume can re-alias it.
        self.suspended: dict[int, SuspendedRequest] = {}
        self._family: dict[int, tuple[int, int]] = {}
        self._ballast: set[int] = set()
        self._next_ballast = 0
        self.suspends = 0
        self.resumes = 0
        self.replay_prefill_tokens = 0
        self.replay_mismatches = 0
        self.trie_admissions = 0

    # -- introspection ------------------------------------------------- #
    @property
    def scheduler_stats(self) -> dict:
        """Schedule build/reuse counters.  ``hits + rebuilds`` equals the
        number of decode steps — one schedule per step, never per layer."""
        return {
            "hits": self._scheduler.hits,
            "rebuilds": self._scheduler.rebuilds,
        }

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill chunk shapes traced (fixed chunking => 1)."""
        return len(self._prefill_shapes)

    @property
    def decode_compiles(self) -> int:
        """Distinct live-batch sizes traced by decode."""
        return len(self._decode_shapes)

    def work_stats(self) -> dict:
        """Deterministic decode-work proxies accumulated across steps.

        ``page_dma_bytes`` is the dtype-aware traffic proxy: page DMAs
        times the storage bytes one page moves (int8 pages include their
        fp32 scale strip) — the number the cache-dtype choice actually
        changes, where the raw DMA *count* does not.

        Speculation accounting: ``request_steps`` counts per-request
        decode launches, ``query_rows`` the fused rows those launches
        verified, ``accepted_tokens`` only the rows that became output.
        ``accepted_tokens_per_step`` is exactly 1.0 with speculation off;
        ``page_dma_bytes_per_accepted_token`` is the amortization headline
        — rejected draft rows inflate ``query_rows`` but never shrink it.
        """
        page_dma_bytes = self.page_dmas * self.cache_spec.bytes_per_page(
            self.cache.page_size, self.cache.width
        )
        occ = self.cache.pool_occupancy()
        if self.trie is not None:
            ts = self.trie.stats()
            trie_hits, trie_misses = ts["hits"], ts["misses"]
            reused, evicted = ts["hit_tokens"], ts["evicted_pages"]
        else:
            trie_hits = trie_misses = reused = evicted = 0
        return {
            "decode_steps": self.decode_steps,
            "request_steps": self.request_steps,
            "query_rows": self.query_rows,
            "accepted_tokens": self.accepted_tokens,
            "accepted_tokens_per_step": self.accepted_tokens
            / max(self.request_steps, 1),
            "page_dmas": self.page_dmas,
            "page_dma_bytes": page_dma_bytes,
            "page_dma_bytes_per_accepted_token": page_dma_bytes
            / max(self.accepted_tokens, 1),
            "rows_attended": self.rows_attended,
            "aliased_pages": self.cache.num_aliased_pages(),
            "free_pages": self.cache.num_free_pages,
            # Pool occupancy + trie reuse (all-zero with the trie off, so
            # the key set is stable and the sharded aggregator can sum).
            "live_pages": occ["live_pages"],
            "retained_pages": occ["retained_pages"],
            "trie_hits": trie_hits,
            "trie_misses": trie_misses,
            "trie_admissions": self.trie_admissions,
            "trie_hit_rate": trie_hits / max(trie_hits + trie_misses, 1),
            "prefix_tokens_reused": reused,
            "prefix_tokens_reused_per_admission": reused
            / max(self.trie_admissions, 1),
            "trie_evicted_pages": evicted,
            "suspends": self.suspends,
            "resumes": self.resumes,
            "suspended": len(self.suspended),
            "replay_prefill_tokens": self.replay_prefill_tokens,
            "replay_mismatches": self.replay_mismatches,
            # Interleaving proxies (virtual work clock), then wall clock:
            # the prefill queue's wait, the span totals and walk_dispatches.
            "work_units": self.work_units,
            "prefill_chunks": self.prefill_chunks,
            "prefill_stall_steps": self.prefill_stall_steps,
            "prefill_pending": len(self._pending),
            "prefill_backlog_tokens": sum(
                len(p["prompt"]) - p["done"] for p in self._pending
            ),
            "prefill_wait_s": self.prefill_wait_s,
            "prefill_waits": self.prefill_waits,
            **self.tracer.stats(),
        }

    @property
    def prefill_pending(self) -> int:
        """Admitted-but-unprefilled prompts still in the pending queue."""
        return len(self._pending)

    def resident_rids(self) -> list[int]:
        """Every rid holding pool pages: live decoders + pending prompts."""
        return list(self.active) + [p["rid"] for p in self._pending]

    # -- work accounting ------------------------------------------------ #
    def _count_prefill(self, n_tokens: int, interleaved: bool = False):
        chunks = -(-n_tokens // self.prefill_chunk)
        self.prefill_chunks += chunks
        self.work_units += chunks
        if not interleaved and self.active:
            # Synchronous prefill with live decoders: every chunk is a
            # virtual step those requests spent stalled.
            self.prefill_stall_steps += chunks

    # -- admission / branching ----------------------------------------- #
    def _admit(self, rid: int, first_token: int) -> int:
        self.active.append(rid)
        # setdefault + append: under budgeted interleaving the output list
        # was created at enqueue time and sharded sessions already hold a
        # view of that very object — replacing it would orphan their alias.
        self.outputs.setdefault(rid, []).append(first_token)
        self.last_token[rid] = first_token
        return rid

    def add_request(self, prompt_tokens) -> int | None:
        """Chunk-prefill a prompt into fresh pages; rid, or None when the
        pool lacks pages / the batch is full (caller queues and retries).

        With ``prefix_cache="trie"`` admission is automatic longest-prefix
        reuse: the prompt's complete §4.2 blocks are matched against the
        radix trie, every matched block's pages are adopted zero-copy
        (refcount bumps over all layers, exactly like :meth:`fork`), and
        only the divergent tail prefills — no caller-named parent needed.
        """
        with self.tracer.active(), span("serve.admit", rid=self._next_id):
            return self._add_request(prompt_tokens)

    def _add_request(self, prompt_tokens) -> int | None:
        from repro.models import transformer as _tf

        prompt = list(map(int, prompt_tokens))
        if len(prompt) < 1:
            raise ValueError(
                "add_request needs at least one prompt token (an empty "
                "prompt has no prefill position to decode from)"
            )
        need = -(-len(prompt) // self.cache.page_size)
        if need > self.cache.num_pages:
            raise ValueError(
                f"prompt of {len(prompt)} tokens needs {need} pages but the "
                f"pool only has {self.cache.num_pages} total; grow "
                "num_pages/page_size or truncate the prompt (it can never "
                "be admitted, even into an empty pool)"
            )
        if self.max_batch is not None and len(self.active) >= self.max_batch:
            return None
        matched = 0
        tpages: list[int] = []
        if self.trie is None:
            if not self.cache.has_room(None, len(prompt)):
                return None
        else:
            # Position len(prompt)-1 must run through prefill (it emits the
            # first logits row), so only blocks strictly before it can be
            # reused.  ``matched`` is block-aligned, hence page-aligned.
            usable = ((len(prompt) - 1) // self.block_k) * self.block_k
            matched, tpages = self.trie.match(prompt[:usable])
            self.trie_admissions += 1
        rid = self._next_id
        self._next_id += 1
        # Adopt BEFORE any reclaim: the adoption refs keep the matched
        # pages alive (and off the evictor's freeable list) even if pool
        # pressure evicts their trie node a moment later.
        if matched:
            self.cache.adopt_pages(rid, tpages, matched)
        else:
            self.cache.alloc(rid)
        deficit = need - len(tpages) - self.cache.num_free_pages
        if deficit > 0 and self.trie is not None:
            # Cold retained subtrees make way before admission fails.
            self.trie.reclaim(deficit)
        if need - len(tpages) > self.cache.num_free_pages:
            self.cache.free(rid)  # adoption refs drop; pins are untouched
            return None
        self._prompt[rid] = prompt
        if self.prefill_budget is not None:
            # Budgeted interleaving: admission only *enqueues* — the
            # matched prefix pages are already adopted, and step() advances
            # the unprefilled tail in chunk-aligned budgeted slices.  The
            # output list exists from here on so sharded sessions can alias
            # it before the first token lands.
            self.outputs[rid] = []
            self._pending.append(
                {"rid": rid, "prompt": prompt, "done": matched,
                 "enqueued": time.perf_counter()}
            )
            return rid
        self._prefill_shapes.add((1, self.prefill_chunk))
        logits = _tf.lm_prefill_paged(
            self.params,
            prompt[matched:],
            cfg=self.cfg,
            cache=self.cache,
            rid=rid,
            start_pos=matched,
            chunk=self.prefill_chunk,
            table_width=self.table_width,
            block_k=self.block_k,
            interpret=self.interpret,
            layer_params=self._layers,
            compute_dtype=self.compute_dtype,
            head_shards=self.head_shards,
        )
        self._count_prefill(len(prompt) - matched)
        if self.trie is not None:
            # Publish the *live* prefix immediately: leaves map to live or
            # retained prefixes, so concurrent same-template admissions
            # alias this request's pages without waiting for it to finish.
            self._retain_prompt(rid)
        with span("serve.sync", site="first_token"):
            first = _first_token(logits)
        return self._admit(rid, first)

    def _retain_prompt(self, rid: int) -> None:
        """Pin ``rid``'s complete prompt blocks into the trie (idempotent:
        blocks already covered add nothing).  Only :meth:`add_request`
        prompts qualify: their rows are entirely prefill-written at
        globally chunk-aligned boundaries, so a later admission adopting
        them gets rows bit-identical to its own cold prefill.  Fork /
        admit_with_prefix children are deliberately *not* retained —
        their histories contain decode-written or non-aligned rows."""
        prompt = self._prompt.get(rid, [])
        n_blocks = len(prompt) // self.block_k
        if not n_blocks:
            return
        ppb = self.block_k // self.cache.page_size
        self.trie.insert(
            prompt[: n_blocks * self.block_k],
            self.cache.seq_pages(rid)[: n_blocks * ppb],
        )

    def fork(self, rid: int, prefix_len: int | None = None) -> int:
        """Branch a live request at its full history: the child aliases
        every page (all L layers, one refcount bump) and continues with the
        parent's pending token — greedy twins until COW divergence.  To
        branch at an earlier point with new tokens, use
        :meth:`admit_with_prefix`.
        """
        if rid not in self.active:
            raise KeyError(f"request {rid} is not live")
        if prefix_len is not None and prefix_len != self.cache.seq_len(rid):
            raise ValueError(
                "model-level fork shares the whole history (the pending "
                "token is only defined there); use admit_with_prefix("
                "parent, suffix_tokens, prefix_len) to branch earlier"
            )
        child = self._next_id
        self._next_id += 1
        self.cache.fork(rid, child)
        self.active.append(child)
        self.outputs[child] = list(self.outputs[rid])
        self.last_token[child] = self.last_token[rid]
        self._prompt[child] = list(self._prompt[rid])
        self._family[child] = (rid, self.cache.seq_len(child))
        return child

    def admit_with_prefix(
        self, parent_rid: int, suffix_tokens, prefix_len: int | None = None
    ) -> int | None:
        """Admit a request as ``fork(parent, prefix_len) + prefill(suffix)``
        — the shared-system-prompt / n-best entry point.  The prefix pages
        are aliased (zero copies across all layers); only the suffix runs
        through the model, attending over the shared pages.  Returns None
        (nothing allocated) when the pool lacks pages for the suffix.

        With ``prefix_cache="trie"`` this is a compatibility shim: plain
        :meth:`add_request` already discovers the longest cached prefix
        automatically (no parent rid needed) and covers the cross-request
        case this method cannot (the parent may have finished).  Kept for
        callers that want an explicit *live*-parent alias at a
        non-block-aligned ``prefix_len``.
        """
        from repro.models import transformer as _tf

        if parent_rid not in self.active:
            raise KeyError(f"request {parent_rid} is not live")
        suffix = list(map(int, suffix_tokens))
        if not suffix:
            raise ValueError(
                "admit_with_prefix needs at least one suffix token "
                "(use fork() for a zero-copy full branch)"
            )
        if self.max_batch is not None and len(self.active) >= self.max_batch:
            return None
        child = self._next_id
        self._next_id += 1
        self.cache.fork(parent_rid, child, prefix_len)
        if not self.cache.has_room(child, len(suffix)):
            self.cache.free(child)
            return None
        start = self.cache.seq_len(child)
        # The child's draft history is the parent's cached token rows up to
        # the shared prefix plus its own suffix.  Parent rows = prompt +
        # outputs[:-1] (the last output is the pending token, not yet a
        # cache row).
        ctx = (self._prompt[parent_rid] + self.outputs[parent_rid])[:-1]
        self._prompt[child] = ctx[:start] + suffix
        self._family[child] = (parent_rid, start)
        self._prefill_shapes.add((1, self.prefill_chunk))
        logits = _tf.lm_prefill_paged(
            self.params,
            suffix,
            cfg=self.cfg,
            cache=self.cache,
            rid=child,
            start_pos=start,
            chunk=self.prefill_chunk,
            table_width=self.table_width,
            block_k=self.block_k,
            interpret=self.interpret,
            layer_params=self._layers,
            compute_dtype=self.compute_dtype,
            head_shards=self.head_shards,
        )
        self._count_prefill(len(suffix))
        return self._admit(child, _first_token(logits))

    # -- decode --------------------------------------------------------- #
    def step(self) -> None:
        """One greedy decode step for every live request (one schedule).

        With ``speculate="off"`` this feeds each request's pending token
        through a 1-row decode and emits its greedy successor.  With
        ``speculate="ngram"`` it is a draft-verify step: feed ``[pending,
        d_1 .. d_{k-1}]`` (drafts from the proposer), run **one** fused
        k-row decode, take the model's greedy choice at every row, and
        accept the longest draft prefix that matches those choices — the
        accepted rows' logits are exactly what sequential 1-row decode
        would have produced (causal masking conditions row i only on rows
        < i), so between 1 and k tokens are emitted per step and the
        stream is token-for-token identical to non-speculative decode.
        Rejected tail rows (already appended to the cache so the kernel
        could attend them) roll back via ``cache.truncate``.

        With ``prefill_budget`` set, every step is a unified work unit:
        after the decode launch (live requests always emit — no decode
        stall), up to ``prefill_budget`` tokens of pending prompts advance
        through chunk-aligned ``lm_prefill_paged`` slices; a prompt whose
        final slice lands joins the live batch with its first token and
        decodes from the next step.
        """
        with self.tracer.active(), span("serve.step", batch=len(self.active)):
            self._step()

    def _step(self) -> None:
        from repro.kernels.decode_schedule import (
            PrefixSchedule,
            prefix_queue_grid_items,
            queue_grid_items,
        )
        from repro.models import transformer as _tf

        rids = list(self.active)
        if not rids:
            if self.prefill_budget is not None and self._pending:
                self._advance_prefill()
            return
        s = self.draft_k if self.speculate != "off" else 1
        if s > 1:
            drafts = [
                self._proposer.propose(
                    self._prompt[r] + self.outputs[r], s - 1
                )
                for r in rids
            ]
            tokens = np.asarray(
                [
                    [self.last_token[r]] + list(map(int, d))
                    for r, d in zip(rids, drafts)
                ],
                np.int32,
            )
        else:
            drafts = [[] for _ in rids]
            tokens = np.asarray(
                [self.last_token[r] for r in rids], np.int32
            )[:, None]
        # Pre-append lengths: truncation targets and accounting both need
        # the lengths the *schedule* saw, not the post-rollback ones.
        pre = {r: self.cache.seq_len(r) for r in rids}
        with span("serve.decode", batch=len(rids)):
            logits = _tf.lm_decode_step_paged(
                self.params,
                tokens,
                cfg=self.cfg,
                cache=self.cache,
                rids=rids,
                scheduler=self._scheduler,
                prefix_sharing=self.prefix_sharing,
                # The trie epoch folds eviction/insert/split churn into the
                # memo key: adopted-page aliasing changes grouping structure
                # even when block counts and the live set look unchanged.
                extra_key=(
                    (tuple(rids), self.trie.epoch)
                    if self.trie is not None
                    else tuple(rids)
                ),
                table_width=self.table_width,
                block_k=self.block_k,
                num_splits=self.num_splits,
                interpret=self.interpret,
                layer_params=self._layers,
                compute_dtype=self.compute_dtype,
                head_shards=self.head_shards,
                # One decode program per session, whatever the live count.
                batch_rows=self.max_batch,
            )
            with span("serve.sync", site="greedy"):
                # (B, s) greedy picks, one per fed row
                greedy = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.work_units += 1  # one fused decode launch
        for i, r in enumerate(rids):
            # Accept the longest prefix where draft d_{m+1} equals the
            # model's greedy pick after row m; emit m+1 tokens (the first
            # greedy pick is always emitted — a fully rejected draft still
            # makes the same progress a non-speculative step would).
            m = 0
            while m < s - 1 and int(drafts[i][m]) == int(greedy[i, m]):
                m += 1
            emitted = [int(t) for t in greedy[i, : m + 1]]
            self.outputs[r].extend(emitted)
            self.last_token[r] = emitted[-1]
            if m + 1 < s:
                # Roll back rejected draft rows: keep the pending token's
                # row plus the m accepted draft rows.
                self.cache.truncate(r, pre[r] + 1 + m)
            self.accepted_tokens += m + 1
        # Deterministic work accounting: the schedule the step just used,
        # scaled by L (every layer replays the same queue).  kv is the
        # schedule-time length (pre + s appended rows) — rollback refunds
        # pages, not the DMAs this step already counted.
        self.decode_steps += 1
        self.request_steps += len(rids)
        self.query_rows += len(rids) * s
        self._decode_shapes.add(len(rids))
        sched = self._scheduler.current
        kv = np.asarray([pre[r] + s for r in rids], np.int64)
        acct = (
            prefix_queue_grid_items(
                sched, kv, self.cache.page_size, query_rows=s
            )
            if isinstance(sched, PrefixSchedule)
            else queue_grid_items(
                sched, kv, self.cache.page_size, query_rows=s
            )
        )
        n_layers = self.cfg.n_layers
        self.page_dmas += int(acct["page_dmas"]) * n_layers
        self.rows_attended += int(kv.sum()) * s * n_layers
        if self.prefill_budget is not None and self._pending:
            self._advance_prefill()

    def _advance_prefill(self) -> None:
        """Spend this step's prefill token budget on the pending queue.

        Slice sizes come from :func:`~repro.kernels.decode_schedule
        .plan_prefill_slices` (oldest-first anti-starvation chunk, then
        shortest-remaining-first); every slice runs through the same
        ``lm_prefill_paged`` path as synchronous admission with
        ``start_pos`` at the rows already written, so the chunk boundaries
        — and therefore the cache rows and the eventual greedy stream —
        are bit-identical to a phase-separated prefill.  Intermediate
        slices skip the unembed (``need_logits=False``); only a prompt's
        final slice computes logits, emits the first token, and (trie on)
        publishes the prompt blocks for prefix reuse.  A slice whose pages
        are not available right now is skipped without burning budget —
        decode keeps running and the slice retries next step.
        """
        from repro.kernels.decode_schedule import plan_prefill_slices
        from repro.models import transformer as _tf

        pending = list(self._pending)
        with span("serve.schedule"):
            slices = plan_prefill_slices(
                [len(p["prompt"]) - p["done"] for p in pending],
                self.prefill_budget,
                self.prefill_chunk,
            )
        for ent, take in zip(pending, slices):
            if take <= 0:
                continue
            rid, prompt = ent["rid"], ent["prompt"]
            if not self.cache.has_room(rid, take):
                continue
            final = ent["done"] + take == len(prompt)
            self._prefill_shapes.add((1, self.prefill_chunk))
            with span("serve.prefill", rid=rid, tokens=take):
                enqueued = ent.pop("enqueued", None)
                if enqueued is not None:
                    self.prefill_wait_s += time.perf_counter() - enqueued
                    self.prefill_waits += 1
                logits = _tf.lm_prefill_paged(
                    self.params,
                    prompt[ent["done"] : ent["done"] + take],
                    cfg=self.cfg,
                    cache=self.cache,
                    rid=rid,
                    start_pos=ent["done"],
                    chunk=self.prefill_chunk,
                    table_width=self.table_width,
                    block_k=self.block_k,
                    interpret=self.interpret,
                    layer_params=self._layers,
                    compute_dtype=self.compute_dtype,
                    head_shards=self.head_shards,
                    need_logits=final,
                )
                if final:
                    with span("serve.sync", site="first_token"):
                        first = _first_token(logits)
            self._count_prefill(take, interleaved=True)
            ent["done"] += take
            if final:
                self._pending.remove(ent)
                if self.trie is not None:
                    self._retain_prompt(rid)
                self._admit(rid, first)

    def finish(self, rid: int) -> list[int]:
        """Retire ``rid``: pages return to the pool (aliased prefix pages
        stay until their last owner goes); returns the generated tokens.

        With the trie on the request's prompt blocks were pinned at
        admission (see :meth:`_retain_prompt`), so free() here demotes
        them from *live* to *retained* — the pages never transit the free
        list, and the next admission sharing the prompt prefix adopts
        them instead of re-prefilling.
        """
        with self.tracer.active(), span("serve.finish", rid=rid):
            return self._finish(rid)

    def _finish(self, rid: int) -> list[int]:
        if rid not in self.active:
            pend = next(
                (p for p in self._pending if p["rid"] == rid), None
            )
            if pend is None:
                raise KeyError(f"request {rid} is not live")
            # Mid-prefill retire (deadline/abandon): partial rows free,
            # nothing was emitted — the output list is empty.
            self._pending.remove(pend)
            self.cache.free(rid)
            self.last_token.pop(rid, None)
            self._prompt.pop(rid, None)
            self._family.pop(rid, None)
            return self.outputs.pop(rid)
        self.active.remove(rid)
        self.cache.free(rid)
        self.last_token.pop(rid, None)
        self._prompt.pop(rid, None)
        self._family.pop(rid, None)
        return self.outputs.pop(rid)

    # -- recoverable eviction / replay ---------------------------------- #
    def suspend(self, rid: int) -> SuspendedRequest:
        """Evict ``rid`` recoverably: free its pages now (refcount/COW-
        aware — an aliased prefix page just loses one owner, a forked
        sibling's data is untouched) while keeping the prompt + emitted
        tokens host-side.  No speculation state needs clearing: between
        steps the cache rows are always prompt + outputs[:-1] (speculative
        rollback restores that invariant inside step()), so the pending
        token ``outputs[-1]`` plus the token history *is* the full decode
        state.  :meth:`resume` rebuilds the rows by replay.

        A *pending* (mid-prefill) request suspends too: its partial rows
        free now and the record carries an empty output list — replay
        restarts the prefill from scratch (re-enqueued under budgeted
        interleaving, synchronous otherwise)."""
        pend = next((p for p in self._pending if p["rid"] == rid), None)
        if pend is not None:
            rec = SuspendedRequest(
                prompt=list(self._prompt[rid]),
                outputs=self.outputs[rid],  # empty, but the live object
            )
            self._pending.remove(pend)
            self.cache.free(rid)
            self.outputs.pop(rid)
            self.last_token.pop(rid, None)
            self._prompt.pop(rid, None)
            self._family.pop(rid, None)
            self.suspended[rid] = rec
            self.suspends += 1
            return rec
        if rid not in self.active:
            raise KeyError(f"request {rid} is not live")
        parent, prefix_rows = self._family.get(rid, (None, 0))
        rec = SuspendedRequest(
            prompt=list(self._prompt[rid]),
            outputs=self.outputs[rid],
            parent=parent,
            prefix_len=prefix_rows,
        )
        self.active.remove(rid)
        self.cache.free(rid)
        self.outputs.pop(rid)
        self.last_token.pop(rid, None)
        self._prompt.pop(rid, None)
        self._family.pop(rid, None)
        self.suspended[rid] = rec
        self.suspends += 1
        return rec

    def resume(self, rid: int) -> bool:
        """Re-admit a suspended request under its old rid.

        Replays its cache rows through the fixed-chunk paged prefill —
        re-aliasing the parent's prefix pages first when the fork parent is
        still live (``admit_with_prefix`` semantics: only the divergent
        suffix recomputes) — then continues decoding from the same pending
        token, so greedy outputs match an uninterrupted run exactly.
        Returns False (nothing allocated) when the pool lacks pages;
        callers retry once pages free up.  Resume bypasses ``max_batch``:
        the request was already admitted once.
        """
        rec = self.suspended.get(rid)
        if rec is None:
            raise KeyError(f"request {rid} is not suspended")
        if not self._replay(rid, rec, rec.parent):
            return False
        del self.suspended[rid]
        return True

    def resume_pending(self) -> list[int]:
        """Resume every suspended request the pool has room for, ascending
        rid — prefix parents precede their forked children, so a family
        re-aliases in order.  Returns the rids that made it back."""
        return [rid for rid in sorted(self.suspended) if self.resume(rid)]

    def adopt_suspended(
        self, rec: SuspendedRequest, parent: int | None = None
    ) -> int | None:
        """Admit another session's suspended record under a fresh local rid
        — the cross-shard re-route path of ``fail_shard``.  ``parent``
        names the prefix parent's rid *in this session* when the family
        moved together; None replays the full history standalone.  Returns
        the new rid, or None (nothing allocated) when the pool lacks room.
        """
        rid = self._next_id
        self._next_id += 1
        if not self._replay(rid, rec, parent):
            return None
        return rid

    def discard_suspended(self, rid: int) -> list[int]:
        """Drop a suspended request for good (abandon); returns its output
        so far.  Nothing device-side is held — suspend already freed it."""
        return self.suspended.pop(rid).outputs

    def _replay(
        self, rid: int, rec: SuspendedRequest, parent: int | None
    ) -> bool:
        from repro.models import transformer as _tf

        if not rec.outputs:
            # Suspended mid-prefill: nothing was ever emitted, so there is
            # no pending token to replay toward — restart the prompt from
            # scratch (budgeted sessions re-enqueue it; synchronous ones
            # prefill it here and now).
            prompt = list(rec.prompt)
            if not self.cache.has_room(None, len(prompt)):
                return False
            self.cache.alloc(rid)
            self._prompt[rid] = prompt
            self.outputs[rid] = rec.outputs
            if self.prefill_budget is not None:
                self._pending.append(
                    {"rid": rid, "prompt": prompt, "done": 0}
                )
                self.resumes += 1
                return True
            self._prefill_shapes.add((1, self.prefill_chunk))
            logits = _tf.lm_prefill_paged(
                self.params,
                prompt,
                cfg=self.cfg,
                cache=self.cache,
                rid=rid,
                start_pos=0,
                chunk=self.prefill_chunk,
                table_width=self.table_width,
                block_k=self.block_k,
                interpret=self.interpret,
                layer_params=self._layers,
                compute_dtype=self.compute_dtype,
                head_shards=self.head_shards,
            )
            self._count_prefill(len(prompt))
            self.replay_prefill_tokens += len(prompt)
            if self.trie is not None:
                self._retain_prompt(rid)
            self._admit(rid, _first_token(logits))
            self.resumes += 1
            return True
        tokens = rec.tokens
        use_parent = parent is not None and parent in self.active
        prefix = (
            min(rec.prefix_len, len(tokens), self.cache.seq_len(parent))
            if use_parent
            else 0
        )
        suffix = tokens[prefix:]
        if use_parent:
            self.cache.fork(parent, rid, prefix)
            if suffix and not self.cache.has_room(rid, len(suffix)):
                self.cache.free(rid)
                return False
        else:
            if not self.cache.has_room(None, len(suffix)):
                return False
            self.cache.alloc(rid)
        if suffix:
            self._prefill_shapes.add((1, self.prefill_chunk))
            logits = _tf.lm_prefill_paged(
                self.params,
                suffix,
                cfg=self.cfg,
                cache=self.cache,
                rid=rid,
                start_pos=prefix,
                chunk=self.prefill_chunk,
                table_width=self.table_width,
                block_k=self.block_k,
                interpret=self.interpret,
                layer_params=self._layers,
                compute_dtype=self.compute_dtype,
                head_shards=self.head_shards,
            )
            # Replay-integrity probe: the replayed prefill's final greedy
            # pick must re-derive the pending token the original run
            # emitted from these same rows.  Counted rather than raised —
            # the stream stays serviceable — and gated at 0 by the
            # fault-tolerance tests and the failure_recovery benchmark.
            if _first_token(logits) != int(rec.outputs[-1]):
                self.replay_mismatches += 1
            self.replay_prefill_tokens += len(suffix)
            self._count_prefill(len(suffix))
        self.active.append(rid)
        self._prompt[rid] = list(rec.prompt)
        self.outputs[rid] = rec.outputs
        self.last_token[rid] = int(rec.outputs[-1])
        if use_parent:
            self._family[rid] = (parent, prefix)
        self.resumes += 1
        return True

    # -- chaos hooks ----------------------------------------------------- #
    def hold_pages(self, n: int) -> int:
        """Seize up to ``n`` free pages as ballast (pool-pressure fault
        injection): the pages leave the free list under an internal
        negative rid, so admission control and ``has_room`` see real
        pressure without any request state being touched.  Returns a
        handle for :meth:`release_pages`; the host-mirror refcount sweep
        counts the ballast as live until released."""
        n = min(int(n), self.cache.num_free_pages)
        self._next_ballast -= 1
        handle = self._next_ballast  # negative: never collides with rids
        self.cache.alloc(handle)
        if n > 0:
            self.cache.reserve(handle, n * self.cache.page_size)
        self._ballast.add(handle)
        return handle

    def release_pages(self, handle: int) -> None:
        """Return a :meth:`hold_pages` ballast's pages to the free list."""
        if handle not in self._ballast:
            raise KeyError(f"{handle} is not a held ballast handle")
        self._ballast.discard(handle)
        self.cache.free(handle)

    # -- prefix-cache lifecycle ----------------------------------------- #
    def reclaim_retained(self, n_pages: int) -> int:
        """Evict cold retained trie subtrees to free ``>= n_pages`` pages
        (best effort; leaf-first LRU).  Returns the pages actually freed —
        0 with the trie off or nothing freeable, which tells callers
        (the :class:`ServeSupervisor` pool-pressure path) to fall back to
        suspending a live request."""
        if self.trie is None:
            return 0
        return self.trie.reclaim(n_pages)

    def close(self) -> dict:
        """Tear the session down and audit the pool: finish every live
        request, release ballast, drop suspended records, clear the trie,
        then :meth:`~repro.runtime.kv_cache.PagedKVCache.refcount_sweep`
        — a page leak fails loudly here in every run, not only under
        chaos.  Returns the sweep report."""
        for pend in list(self._pending):
            self.finish(pend["rid"])
        for rid in list(self.active):
            self.finish(rid)
        for handle in list(self._ballast):
            self.release_pages(handle)
        self.suspended.clear()
        if self.trie is not None:
            self.trie.clear()
        report = self.cache.refcount_sweep()
        assert report["free_pages"] == self.cache.num_pages, (
            f"page leak at teardown: {report}"
        )
        return report


class ShardedPagedServingSession:
    """Multi-host paged serving: the page pool + decode work queue sharded
    over a ``(data, model)`` mesh with data-parallel request routing.

    Each ``data`` shard owns one :class:`PagedServingSession` — a full
    :class:`~repro.runtime.kv_cache.LayeredPagedKVCache` slice of the global
    pool, its own memoizing :class:`~repro.kernels.decode_schedule
    .DecodeScheduler`, and (on a real mesh) a committed params replica on
    the shard's anchor device.  A request's pages *and* its queue items
    live entirely on one shard:

    * **admission** routes through
      :func:`~repro.kernels.decode_schedule.route_request` — least live
      KV blocks wins (live blocks = queue items per decode step, the work
      proxy), ties toward more free pages;
    * **fork / admit_with_prefix** pin the child to the parent's shard:
      page aliasing is pool-local, so a forked-prefix family never
      straddles shards (and the PR 3 prefix-grouping machinery composes
      unchanged within a shard);
    * **decode** runs each shard's own ``build_schedule`` over its local
      ``kv_lens`` and its local ``ops.mla_decode_paged``; the ``model``
      axis carries tensor-parallel head groups within a shard
      (``head_shards`` query-head chunks — exact, heads are independent).
      A request split *across* shards would merge its per-shard ``(o,
      lse)`` partials with the combine formula
      (:func:`repro.core.distributed.combine_shard_partials`); the
      data-parallel router never needs to, which is what keeps sharded
      greedy outputs **bit-identical** to the single-host backend.

    ``mesh=None, shards=N`` runs N logical shards on the default device —
    same routing, same per-shard pools and schedules, no placement — so
    single-device CI exercises the full code path; a real mesh only adds
    ``device_put`` placement per shard.
    """

    def __init__(
        self,
        model,
        params,
        *,
        num_pages: int,
        mesh=None,
        shards: int | None = None,
        head_shards: int | None = None,
        page_size: int | None = None,
        block_k: int | None = None,
        num_splits: int = 1,
        prefix_sharing: bool = False,
        min_group: int = 2,
        prefill_chunk: int = 32,
        max_batch: int | None = None,
        interpret: bool = False,
        dtype=None,
        kv_dtype=None,
        speculate: str = "off",
        draft_k: int = 4,
        draft_proposer=None,
        prefix_cache: str = "off",
        retain_pages: int | None = None,
        prefill_budget: int | None = None,
    ):
        if mesh is not None and shards is not None:
            raise ValueError("pass mesh= or shards=, not both")
        if mesh is not None:
            devices = sharding.serving_shard_devices(mesh)
            n_data = len(devices)
            n_model = int(np.prod(mesh.devices.shape)) // n_data
        else:
            n_data = int(shards or 1)
            n_model = 1
            devices = [None] * n_data
        if n_data < 1:
            raise ValueError(f"need at least one data shard, got {n_data}")
        if num_pages % n_data:
            raise ValueError(
                f"num_pages={num_pages} must split evenly over {n_data} "
                f"data shards (use a multiple of {n_data})"
            )
        self.mesh = mesh
        self.num_shards = n_data
        self.head_shards = int(head_shards or n_model)
        self.max_batch = max_batch
        self.shards = [
            PagedServingSession(
                model,
                params,
                num_pages=num_pages // n_data,
                page_size=page_size,
                block_k=block_k,
                num_splits=num_splits,
                prefix_sharing=prefix_sharing,
                min_group=min_group,
                prefill_chunk=prefill_chunk,
                # A shard never holds more than the global batch; with the
                # same max_batch its decode step runs the program one
                # unsharded session runs, so greedy tokens match.
                max_batch=max_batch,
                interpret=interpret,
                dtype=dtype,
                kv_dtype=kv_dtype,
                device=dev,
                head_shards=self.head_shards,
                # Speculation is shard-local: each shard drafts/verifies/
                # rolls back its own requests, so sharded greedy output
                # stays bit-identical to a single-host session.
                speculate=speculate,
                draft_k=draft_k,
                draft_proposer=draft_proposer,
                # The trie is shard-local (pages cannot alias across
                # pools); the retention budget splits evenly like the pool.
                prefix_cache=prefix_cache,
                retain_pages=(
                    None if retain_pages is None else retain_pages // n_data
                ),
                # The budget is per shard per step: each shard interleaves
                # its own pending prompts with its own decode batch.
                prefill_budget=prefill_budget,
            )
            for dev in devices
        ]
        self.block_k = self.shards[0].block_k
        # global rid -> (shard index, shard-local rid)
        self._where: dict[int, tuple[int, int]] = {}
        self.active: list[int] = []
        self.outputs: dict[int, list[int]] = {}
        self._next_id = 0
        # Shard lifecycle: healthy shards admit, draining shards only
        # finish what they hold, dead shards are gone (fail_shard suspended
        # and re-routed their requests).  attach_shard grows the fleet with
        # a fresh pool slice built from the same constructor kwargs.
        self._health: list[str] = ["healthy"] * n_data
        self._model = model
        self._params = params
        self._pages_per_shard = num_pages // n_data
        self._page_size = self.shards[0].cache.page_size
        self._shard_kwargs = dict(
            page_size=page_size,
            block_k=block_k,
            num_splits=num_splits,
            prefix_sharing=prefix_sharing,
            min_group=min_group,
            prefill_chunk=prefill_chunk,
            max_batch=max_batch,
            interpret=interpret,
            dtype=dtype,
            kv_dtype=kv_dtype,
            speculate=speculate,
            draft_k=draft_k,
            draft_proposer=draft_proposer,
            prefix_cache=prefix_cache,
            retain_pages=(
                None if retain_pages is None else retain_pages // n_data
            ),
            prefill_budget=prefill_budget,
        )
        # Suspended records live at this level: cross-shard resume must not
        # depend on a (possibly dead) origin shard's bookkeeping.
        self.suspended: dict[int, SuspendedRequest] = {}
        # child gid -> (parent gid, shared rows): family pinning for resume.
        self._gfamily: dict[int, tuple[int, int]] = {}

    # -- routing -------------------------------------------------------- #
    def _live_blocks(self, shard: PagedServingSession) -> int:
        # Pending (mid-prefill) rids count the blocks they already hold:
        # their rows are real queue work the moment they go live.
        return sum(
            -(-shard.cache.seq_len(r) // self.block_k)
            for r in shard.resident_rids()
        )

    def shard_of(self, rid: int) -> int:
        """Which data shard holds ``rid``'s pages + queue items."""
        return self._where[rid][0]

    def _register(self, shard_idx: int, local_rid: int) -> int:
        gid = self._next_id
        self._next_id += 1
        self._where[gid] = (shard_idx, local_rid)
        self.active.append(gid)
        # Share the list object: the shard session appends generated tokens
        # in place, so this view stays current without copying.
        self.outputs[gid] = self.shards[shard_idx].outputs[local_rid]
        return gid

    # -- admission / branching ------------------------------------------ #
    def add_request(self, prompt_tokens) -> int | None:
        """Route a prompt to the least-loaded shard and prefill it there;
        returns a global rid, or None when no shard has room."""
        from repro.kernels.decode_schedule import route_request

        prompt = list(map(int, prompt_tokens))
        if len(prompt) < 1:
            raise ValueError(
                "add_request needs at least one prompt token (an empty "
                "prompt has no prefill position to decode from)"
            )
        pages = -(-len(prompt) // self._page_size)
        if pages > self._pages_per_shard:
            raise ValueError(
                f"prompt of {len(prompt)} tokens needs {pages} pages but "
                f"each of the {self.num_shards} shard pools only has "
                f"{self._pages_per_shard}; a request lives on ONE shard — "
                "grow num_pages or truncate the prompt"
            )
        if self.max_batch is not None and len(self.active) >= self.max_batch:
            return None
        # Probe each shard-local trie read-only (no LRU touch, no hit/miss
        # accounting — only the winning shard's real admission counts):
        # routing prefers prefix locality on live-block ties, and a shard
        # whose hit covers most of the prompt is eligible even when its
        # free pool alone could not hold it.
        hit_pages = None
        if any(s.trie is not None for s in self.shards):
            usable = ((len(prompt) - 1) // self.block_k) * self.block_k
            hit_pages = [
                (
                    len(s.trie.match(
                        prompt[:usable], touch=False, count=False
                    )[1])
                    if s.trie is not None
                    else 0
                )
                for s in self.shards
            ]
        idx = route_request(
            [self._live_blocks(s) for s in self.shards],
            [s.cache.num_free_pages for s in self.shards],
            pages,
            shard_ok=[h == "healthy" for h in self._health],
            shard_hit_pages=hit_pages,
        )
        if idx is None:
            return None  # no shard has room right now: evict and retry
        local = self.shards[idx].add_request(prompt)
        if local is None:
            return None
        return self._register(idx, local)

    def fork(self, rid: int, prefix_len: int | None = None) -> int:
        """Branch at full history on the parent's shard (aliasing is
        pool-local, so the family stays together — even on a draining
        shard: the parent was admitted before the drain)."""
        idx, local = self._where[rid]
        child_local = self.shards[idx].fork(local, prefix_len)
        gid = self._register(idx, child_local)
        self._gfamily[gid] = (rid, self.shards[idx].cache.seq_len(child_local))
        return gid

    def admit_with_prefix(
        self, parent_rid: int, suffix_tokens, prefix_len: int | None = None
    ) -> int | None:
        """Branch + suffix prefill on the parent's shard.  Returns None when
        *that* shard lacks pages — prefix pages cannot alias across pools,
        so there is no cross-shard fallback (callers evict or fall back to
        a plain add_request)."""
        idx, local = self._where[parent_rid]
        child_local = self.shards[idx].admit_with_prefix(
            local, suffix_tokens, prefix_len
        )
        if child_local is None:
            return None
        gid = self._register(idx, child_local)
        self._gfamily[gid] = (
            parent_rid, self.shards[idx]._family[child_local][1]
        )
        return gid

    # -- decode ---------------------------------------------------------- #
    def step(self) -> None:
        """One greedy decode step on every shard with live requests.

        Each shard batches only its own requests — per-shard
        ``build_schedule`` from per-shard ``kv_lens`` — so the queue math
        per request is identical to a single-host session holding the same
        requests (schedules are per-request up to dest slots), which is
        what the greedy-parity acceptance tests pin down.  Shards with
        only pending prompts still step: their budgeted prefill slices
        advance even before anything decodes there.
        """
        for shard in self.shards:
            if shard.active or shard.prefill_pending:
                shard.step()

    def finish(self, rid: int) -> list[int]:
        idx, local = self._where.pop(rid)
        self.active.remove(rid)
        self.outputs.pop(rid)
        self._gfamily.pop(rid, None)
        return self.shards[idx].finish(local)

    # -- recoverable eviction / shard lifecycle -------------------------- #
    @property
    def shard_health(self) -> list[str]:
        """Per-shard lifecycle state: healthy / draining / dead."""
        return list(self._health)

    def suspend(self, gid: int) -> SuspendedRequest:
        """Suspend ``gid`` wherever it lives: its shard frees the pages
        (refcount/COW-aware) and the replay record moves up to this level,
        so resume can re-route it to any shard.  ``self.outputs[gid]``'s
        view keeps aliasing the record's output list."""
        if gid not in self.active:
            raise KeyError(f"request {gid} is not live")
        idx, local = self._where.pop(gid)
        rec = self.shards[idx].suspend(local)
        self.shards[idx].suspended.pop(local, None)
        self.active.remove(gid)
        self.suspended[gid] = rec
        return rec

    def resume(self, gid: int) -> bool:
        """Re-route + replay one suspended request.

        Family pinning: while the fork parent is live, the child resumes
        on the parent's (possibly new) shard and re-aliases its prefix
        pages; otherwise ``route_request`` picks a healthy shard and the
        full history replays standalone.  Returns False when no eligible
        shard has room yet — callers retry after pages free up.
        """
        from repro.kernels.decode_schedule import route_request

        rec = self.suspended.get(gid)
        if rec is None:
            raise KeyError(f"request {gid} is not suspended")
        parent_gid, _rows = self._gfamily.get(gid, (None, 0))
        if parent_gid is not None and parent_gid in self.active:
            idx, parent_local = self._where[parent_gid]
            local = self.shards[idx].adopt_suspended(rec, parent=parent_local)
        else:
            pages = -(-len(rec.tokens) // self._page_size)
            idx = route_request(
                [self._live_blocks(s) for s in self.shards],
                [s.cache.num_free_pages for s in self.shards],
                pages,
                shard_ok=[h == "healthy" for h in self._health],
            )
            if idx is None:
                return False
            local = self.shards[idx].adopt_suspended(rec, parent=None)
        if local is None:
            return False
        del self.suspended[gid]
        self._where[gid] = (idx, local)
        self.active.append(gid)
        self.outputs[gid] = self.shards[idx].outputs[local]
        return True

    def resume_pending(self) -> list[int]:
        """Try to resume every suspended request, ascending gid — a fork
        parent always has a smaller gid than its children, so family roots
        re-place first and the children re-alias on the root's new shard.
        Returns the gids that made it back; the rest stay suspended for
        the next call (the supervisor's retry loop)."""
        return [gid for gid in sorted(self.suspended) if self.resume(gid)]

    def discard_suspended(self, gid: int) -> list[int]:
        """Drop a suspended request for good (abandon); returns its output
        so far."""
        rec = self.suspended.pop(gid)
        self.outputs.pop(gid, None)
        self._gfamily.pop(gid, None)
        return rec.outputs

    def drain_shard(self, idx: int) -> int:
        """Stop admitting onto shard ``idx``; its live requests keep
        decoding to completion.  Returns how many are still draining.
        Idempotent; a dead shard stays dead."""
        if self._health[idx] != "dead":
            self._health[idx] = "draining"
        return len(self.shards[idx].active)

    def fail_shard(self, idx: int) -> dict:
        """Shard loss: mark ``idx`` dead, suspend every live request it
        held (the host-side prompt/output mirror survives on the
        controller; the device pool is gone), then immediately try to
        re-route them to the survivors via :meth:`resume_pending` —
        family roots first, children re-aliasing on the root's new shard.
        Requests that do not fit yet stay suspended for the retry loop.
        Returns ``{"suspended": [...], "resumed": [...]}``."""
        if self._health[idx] == "dead":
            return {"suspended": [], "resumed": []}
        self._health[idx] = "dead"
        victims = [g for g in list(self.active) if self._where[g][0] == idx]
        for gid in victims:
            self.suspend(gid)
        return {"suspended": victims, "resumed": self.resume_pending()}

    def attach_shard(self, device=None) -> int:
        """Elastic grow: bring up a fresh shard — a params replica placed
        through :func:`repro.runtime.elastic.serving_params_replica` (a
        host-staged put; no-op placement for logical shards) plus an empty
        pool slice the same size as every other shard's — and open it for
        routing.  Returns the new shard's index; being empty, it wins
        ``route_request`` for new admissions immediately."""
        from repro.runtime import elastic

        replica = elastic.serving_params_replica(self._params, device)
        self.shards.append(
            PagedServingSession(
                self._model,
                replica,
                num_pages=self._pages_per_shard,
                device=device,
                head_shards=self.head_shards,
                **self._shard_kwargs,
            )
        )
        self._health.append("healthy")
        self.num_shards += 1
        return len(self.shards) - 1

    # -- chaos hooks ------------------------------------------------------ #
    def hold_pages(self, n: int, shard: int = 0) -> tuple[int, int]:
        """Pool-pressure ballast on one shard (see
        :meth:`PagedServingSession.hold_pages`)."""
        return shard, self.shards[shard].hold_pages(n)

    def release_pages(self, handle: tuple[int, int]) -> None:
        shard, h = handle
        self.shards[shard].release_pages(h)

    # -- introspection --------------------------------------------------- #
    @property
    def scheduler_stats(self) -> dict:
        """Summed schedule build/reuse counters across shards."""
        return {
            "hits": sum(s.scheduler_stats["hits"] for s in self.shards),
            "rebuilds": sum(
                s.scheduler_stats["rebuilds"] for s in self.shards
            ),
        }

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill chunk shapes across shards (fixed chunking
        keeps this at 1: every shard traces the same (1, chunk) shape)."""
        return len(set().union(*(s._prefill_shapes for s in self.shards)))

    @property
    def work_units(self) -> int:
        """Summed virtual work clock (decode launches + prefill chunks)."""
        return sum(s.work_units for s in self.shards)

    @property
    def prefill_pending(self) -> int:
        """Pending (admitted-but-unprefilled) prompts across shards."""
        return sum(s.prefill_pending for s in self.shards)

    def work_stats(self) -> dict:
        """Aggregate work proxies + per-shard balance.

        ``balance`` is :func:`~repro.kernels.decode_schedule
        .shard_work_balance` over per-shard page DMAs (the queue-work
        proxy): ``imbalance`` = max/mean, 1.0 when perfectly even, gated
        <= 2.0 on the ragged benchmark scenario.
        """
        from repro.kernels.decode_schedule import shard_work_balance

        per_shard = [s.work_stats() for s in self.shards]
        agg = {
            k: sum(st[k] for st in per_shard)
            for k in (
                "decode_steps",
                "request_steps",
                "query_rows",
                "accepted_tokens",
                "page_dmas",
                "page_dma_bytes",
                "rows_attended",
                "aliased_pages",
                "free_pages",
                "live_pages",
                "retained_pages",
                "trie_hits",
                "trie_misses",
                "trie_admissions",
                "prefix_tokens_reused",
                "trie_evicted_pages",
                "suspends",
                "resumes",
                "replay_prefill_tokens",
                "replay_mismatches",
                "work_units",
                "prefill_chunks",
                "prefill_stall_steps",
                "prefill_pending",
                "prefill_backlog_tokens",
                "prefill_wait_s",
                "prefill_waits",
                *SERVE_COUNTERS,
            )
        }
        # Span totals (``span_n.*``, ``span_s.*``, ``span_self_s.*``) sum
        # over the shards' own tracers.
        for k in sorted({k for st in per_shard for k in st}):
            if k.startswith("span_"):
                agg[k] = sum(st.get(k, 0) for st in per_shard)
        # Requests suspended at this level (awaiting re-route) are held by
        # no shard; shard-level "suspended" counts are always 0 here
        # because suspend() moves the records up immediately.
        agg["suspended"] = len(self.suspended)
        agg["shard_health"] = self.shard_health
        # Ratios recompute from the summed raw counters — averaging the
        # per-shard ratios would weight empty shards equally with busy ones.
        agg["accepted_tokens_per_step"] = agg["accepted_tokens"] / max(
            agg["request_steps"], 1
        )
        agg["page_dma_bytes_per_accepted_token"] = agg[
            "page_dma_bytes"
        ] / max(agg["accepted_tokens"], 1)
        agg["trie_hit_rate"] = agg["trie_hits"] / max(
            agg["trie_hits"] + agg["trie_misses"], 1
        )
        agg["prefix_tokens_reused_per_admission"] = agg[
            "prefix_tokens_reused"
        ] / max(agg["trie_admissions"], 1)
        agg["per_shard"] = per_shard
        agg["balance"] = shard_work_balance(
            [st["page_dmas"] for st in per_shard]
        )
        return agg

    def reclaim_retained(self, n_pages: int) -> int:
        """Evict cold retained subtrees across shards, fullest pool first,
        until ``n_pages`` freed or nothing is freeable.  Returns pages
        actually freed (see :meth:`PagedServingSession.reclaim_retained`)."""
        freed = 0
        for shard in sorted(self.shards, key=lambda s: s.cache.num_free_pages):
            if freed >= n_pages:
                break
            freed += shard.reclaim_retained(n_pages - freed)
        return freed

    def close(self) -> dict:
        """Per-shard teardown audit (:meth:`PagedServingSession.close`):
        every shard pool must come back fully free."""
        self.suspended.clear()
        reports = [s.close() for s in self.shards]
        self.active.clear()
        self.outputs.clear()
        self._where.clear()
        self._gfamily.clear()
        return {
            "free_pages": sum(r["free_pages"] for r in reports),
            "per_shard": reports,
        }


def latency_percentile(samples, q: float) -> float:
    """Nearest-rank percentile (inclusive) of a latency sample list.

    Deterministic and interpolation-free: p99 of 10 samples is the 10th
    largest, never a blend — the right definition for the small, exact
    step-count samples the serve benchmarks gate on.  Empty input → 0.0.
    """
    if not 0 <= q <= 100:
        raise ValueError(f"percentile must be in [0, 100], got {q}")
    xs = sorted(float(x) for x in samples)
    if not xs:
        return 0.0
    rank = max(1, int(np.ceil(q / 100.0 * len(xs))))
    return xs[rank - 1]


class ServeSupervisor:
    """Supervised continuous-batching serve loop with deterministic chaos.

    Drives a :class:`PagedServingSession` or
    :class:`ShardedPagedServingSession` until every submitted request has
    either completed ``gen_len`` tokens or been abandoned.  Between decode
    steps it:

    * applies due :class:`~repro.runtime.fault_injection.FaultPlan` events
      — shard loss, slow shard, pool-pressure ballast, request abandon —
      through the session's own fault-tolerance surface, so injected and
      real faults share one recovery path;
    * retries suspended requests (recoverable eviction/replay, including
      re-routes off dead shards), with exponential backoff for requests it
      itself evicted under pool pressure so they cannot livelock the pool;
    * admits queued prompts in :func:`~repro.kernels.decode_schedule
      .admission_order` — priority class first, then deadline slack, then
      submission index — with exponential backoff after a rejection and
      **skip-ahead**: a rejected (or backing-off) prompt no longer blocks
      later queued prompts that fit right now.  Results stay keyed by
      submission index, so admission reordering never reorders results,
      and the order is deterministic given the submissions;
    * enforces deadlines (decode steps since admission) — the global
      ``deadline`` or a per-request ``submit(deadline=...)`` override —
      abandoning over-deadline requests with their partial output intact
      (including requests still mid-prefill that never emitted a token);
    * tracks per-request latency on the session's virtual work clock
      (submit→first-token, inter-emission gaps) and reports p50/p99 TTFT
      and per-token latency in :meth:`stats`;
    * feeds wall-clock step times to a
      :class:`~repro.runtime.fault_tolerance.StragglerMonitor`;
    * on :class:`~repro.runtime.kv_cache.OutOfPagesError` suspends — not
      kills — the most-complete request on the fullest pool and retries
      the step; the victim resumes when pages free up.

    Faults never change tokens: every request's greedy stream depends only
    on its own prompt (per-request kernel math is batch- and
    shard-independent, and replay re-derives the same rows), so a chaos
    run's outputs are bit-identical to a fault-free run of the same
    stream — which the ``failure_recovery`` benchmark and the chaos tests
    gate exactly.  :meth:`run` returns results keyed by **submission
    index** (not rid) so those comparisons line up directly.
    """

    def __init__(
        self,
        sess,
        *,
        gen_len: int,
        deadline: int | None = None,
        plan=None,
        max_steps: int | None = None,
        backoff_base: int = 1,
        backoff_cap: int = 16,
        deadline_guard: int = 2,
        arrival_unit: str = "steps",
    ):
        from repro.runtime.fault_tolerance import StragglerMonitor

        if not hasattr(sess, "suspended"):
            raise ValueError(
                "ServeSupervisor needs a paged session (suspend/resume "
                "rides the paged pool's refcounted free/replay; dense "
                "slot sessions have neither)"
            )
        if gen_len < 1:
            raise ValueError(f"gen_len must be >= 1, got {gen_len}")
        if deadline is not None and deadline < 1:
            raise ValueError(f"deadline must be >= 1 steps, got {deadline}")
        self.sess = sess
        self.gen_len = int(gen_len)
        self.deadline = deadline
        self.plan = plan
        self.max_steps = max_steps
        self.backoff_base = max(1, int(backoff_base))
        self.backoff_cap = max(self.backoff_base, int(backoff_cap))
        if deadline_guard < 0:
            raise ValueError(
                f"deadline_guard must be >= 0 steps, got {deadline_guard}"
            )
        self.deadline_guard = int(deadline_guard)
        if arrival_unit not in ("steps", "work_units"):
            raise ValueError(
                f"arrival_unit={arrival_unit!r} is not a clock; pick "
                "'steps' (supervisor loop iterations) or 'work_units' "
                "(the session's virtual work clock — a synchronous prefill "
                "visibly delays later arrivals, like wall time would)"
            )
        self.arrival_unit = arrival_unit
        # Idle-time skew for the work-unit arrival clock: waiting for a
        # future arrival does no session work, so idle ticks advance this
        # instead (wall time passes even when the accelerator is idle).
        self._clock_skew = 0
        self.straggler = StragglerMonitor()
        self._submitted: list[list[int]] = []
        # Per-submission SLA metadata: priority class (higher = more
        # urgent), per-request deadline override, arrival step.
        self._meta: list[dict] = []
        # Per-submission latency record on the session's virtual work
        # clock (``work_units``): submit/admit/first-token marks + the
        # inter-emission gaps that feed the per-token percentiles.
        self._lat: list[dict] = []
        self._queue: list[list[int]] = []  # [sub_idx, not_before, backoff]
        self._live: dict[int, dict] = {}  # rid -> idx/remaining/admitted
        self._results: dict[int, list[int]] = {}
        self.abandoned_idx: set[int] = set()
        self._ballast: list[tuple[int, object]] = []  # (release_step, handle)
        self._slow: tuple[int, float] | None = None  # (until_step, factor)
        self._resume_hold: dict[int, list[int]] = {}  # rid -> [step, backoff]
        self._plan_applied: set[int] = set()
        self.steps = 0
        self.completed = 0
        self.abandoned = 0
        self.tokens_out = 0
        self.admission_retries = 0
        self.evictions = 0
        self.reclaims = 0
        self.faults_applied = 0
        self.faults_skipped = 0
        self.events: list[str] = []

    def submit(
        self,
        prompt_tokens,
        *,
        priority: int = 0,
        deadline: int | None = None,
        arrival: int = 0,
    ) -> int:
        """Queue a prompt; returns its submission index (the results key).

        ``priority`` is the request's class (higher admits first),
        ``deadline`` overrides the supervisor-wide deadline for this
        request (steps since admission), and ``arrival`` is the step the
        request enters the queue — pre-loading a whole traffic trace with
        staggered arrivals keeps multi-tenant runs deterministic.
        """
        if deadline is not None and deadline < 1:
            raise ValueError(f"deadline must be >= 1 steps, got {deadline}")
        if arrival < 0:
            raise ValueError(f"arrival step must be >= 0, got {arrival}")
        idx = len(self._submitted)
        self._submitted.append(list(map(int, prompt_tokens)))
        self._meta.append(
            {
                "priority": int(priority),
                "deadline": None if deadline is None else int(deadline),
                "arrival": int(arrival),
            }
        )
        self._lat.append(
            {
                "submit_vt": None,
                "submit_step": None,
                "admit_vt": None,
                "admit_step": None,
                "first_vt": None,
                "first_step": None,
                "last_vt": None,
                "gaps": [],
            }
        )
        self._queue.append([idx, 0, self.backoff_base])
        return idx

    def _vt(self) -> int:
        """The session's virtual work clock (decode launches + prefill
        chunks) — the deterministic latency proxy all records share."""
        return int(getattr(self.sess, "work_units", 0))

    def _now(self, step: int) -> int:
        """The arrival clock: supervisor steps, or the virtual work clock
        (+ idle skew) when ``arrival_unit="work_units"``."""
        if self.arrival_unit == "steps":
            return step
        return self._vt() + self._clock_skew

    def _lat_clock(self) -> int:
        """The clock latency marks are stamped on: the raw virtual work
        clock, plus idle skew when arrivals ride the work-unit clock (so
        marks and arrivals stay comparable)."""
        vt = self._vt()
        return vt if self.arrival_unit == "steps" else vt + self._clock_skew

    def _effective_deadline(self, info: dict) -> int | None:
        ddl = info.get("deadline")
        return self.deadline if ddl is None else ddl

    def _slack(self, rid: int, step: int) -> float:
        """Steps until ``rid``'s deadline expires (inf without one)."""
        info = self._live[rid]
        ddl = self._effective_deadline(info)
        if ddl is None:
            return float("inf")
        return (info["admitted"] + ddl) - step

    # -- the loop -------------------------------------------------------- #
    def run(self) -> dict[int, list[int]]:
        """Serve until every submission completed or was abandoned.

        Returns ``{submission index: generated tokens}``.  Raises
        RuntimeError when the stream provably cannot make progress (a
        prompt that can never admit, suspended requests with no path back)
        or exceeds the step safety limit.
        """
        from repro.runtime.kv_cache import OutOfPagesError

        sess = self.sess
        limit = self.max_steps
        if limit is None:
            # Generous ceiling: a stalled loop must fail loudly, never spin.
            limit = (self.gen_len + 8) * (len(self._submitted) + 4) * 4
        while self._queue or self._live:
            step = self.steps
            if step >= limit:
                raise RuntimeError(
                    f"supervised serve stalled after {step} steps with "
                    f"{len(self._live)} live and {len(self._queue)} queued "
                    "requests"
                )
            self._apply_plan(step)
            self._tick_ballast(step)
            self._try_resume(step)
            self._admit(step)
            steppable = [r for r in self._live if r not in sess.suspended]
            if not steppable:
                self._idle_tick(step)
                continue
            before = {r: len(sess.outputs[r]) for r in steppable}
            t0 = time.perf_counter()
            oom = False
            try:
                sess.step()
            except OutOfPagesError:
                # Appends are atomic per shard pool, so every request either
                # emitted normally or not at all — account what did emit
                # below, then recoverably evict a victim for the next step.
                oom = True
            dt = time.perf_counter() - t0
            if not oom:
                if self._slow is not None:
                    until, factor = self._slow
                    if step < until:
                        # Injected straggle: inflate the observation against
                        # the monitor's own baseline (deterministic flagging
                        # — factor > threshold — with no real sleeps).
                        if self.straggler.ewma is not None:
                            dt += factor * self.straggler.ewma
                    else:
                        self._slow = None
                self.straggler.observe(step, dt)
            for rid in list(self._live):
                info = self._live[rid]
                if rid in before:
                    emitted = len(sess.outputs[rid]) - before[rid]
                    if emitted:
                        self.tokens_out += emitted
                        info["remaining"] -= emitted
                        lat = self._lat[info["idx"]]
                        vt = self._lat_clock()
                        if lat["first_vt"] is None:
                            lat["first_vt"] = vt
                            lat["first_step"] = step
                        else:
                            lat["gaps"].append(vt - lat["last_vt"])
                        if emitted > 1:
                            # A fused speculative step lands its extra
                            # accepted tokens at the same clock.
                            lat["gaps"].extend([0] * (emitted - 1))
                        lat["last_vt"] = vt
                    if info["remaining"] <= 0:
                        self._results[info["idx"]] = sess.finish(rid)
                        self.completed += 1
                        del self._live[rid]
                        continue
                ddl = self._effective_deadline(info)
                if ddl is not None and step - info["admitted"] >= ddl:
                    # Steps count against the deadline whether or not the
                    # request emitted — a prompt still mid-prefill under a
                    # tiny budget can expire with zero tokens out.
                    self._abandon(rid)
            if oom:
                # Retained prefix pages are the cheapest thing to give
                # back — evicting a cold trie subtree costs a future
                # re-prefill at worst, while suspending a live request
                # costs a certain replay.  Only when nothing retained is
                # freeable does a live victim get suspended.
                reclaimed = 0
                if hasattr(sess, "reclaim_retained"):
                    reclaimed = sess.reclaim_retained(
                        max(len(steppable), 1)
                    )
                if reclaimed > 0:
                    self.reclaims += 1
                    self.events.append(
                        f"step {self.steps}: pool full — reclaimed "
                        f"{reclaimed} retained page(s)"
                    )
                else:
                    victims = [
                        r
                        for r in steppable
                        if r in self._live and r not in sess.suspended
                    ]
                    if victims:
                        self._suspend_victim(victims)
            self.steps += 1
        for _, handle in self._ballast:
            sess.release_pages(handle)
        self._ballast.clear()
        return dict(self._results)

    def latency_records(self) -> list[dict]:
        """Per-submission latency records (virtual-clock marks + gaps)."""
        return [dict(r, gaps=list(r["gaps"])) for r in self._lat]

    def stats(self) -> dict:
        """Supervision counters + the session's suspend/replay work +
        latency percentiles on the virtual work clock.

        ``ttft_units_*`` is submit→first-token in work units (decode
        launches + prefill chunks — the deterministic step-count latency
        proxy); ``tpot_units_*`` are the inter-emission gaps on the same
        clock.  Percentiles are nearest-rank (:func:`latency_percentile`).
        """
        work = self.sess.work_stats()
        ttft = [
            r["first_vt"] - r["submit_vt"]
            for r in self._lat
            if r["first_vt"] is not None and r["submit_vt"] is not None
        ]
        ttft_steps = [
            r["first_step"] - r["submit_step"]
            for r in self._lat
            if r["first_step"] is not None and r["submit_step"] is not None
        ]
        gaps = [g for r in self._lat for g in r["gaps"]]
        return {
            "steps": self.steps,
            "completed": self.completed,
            "abandoned": self.abandoned,
            "tokens_out": self.tokens_out,
            "admission_retries": self.admission_retries,
            "evictions": self.evictions,
            "reclaims": self.reclaims,
            "faults_applied": self.faults_applied,
            "faults_skipped": self.faults_skipped,
            "straggler_events": len(self.straggler.events),
            "suspends": work.get("suspends", 0),
            "resumes": work.get("resumes", 0),
            "replay_prefill_tokens": work.get("replay_prefill_tokens", 0),
            "replay_mismatches": work.get("replay_mismatches", 0),
            "work_units": self._vt(),
            "prefill_chunks": work.get("prefill_chunks", 0),
            "prefill_stall_steps": work.get("prefill_stall_steps", 0),
            "ttft_samples": len(ttft),
            "ttft_units_p50": latency_percentile(ttft, 50),
            "ttft_units_p99": latency_percentile(ttft, 99),
            "ttft_steps_p50": latency_percentile(ttft_steps, 50),
            "ttft_steps_p99": latency_percentile(ttft_steps, 99),
            "tpot_units_p50": latency_percentile(gaps, 50),
            "tpot_units_p99": latency_percentile(gaps, 99),
        }

    # -- internals ------------------------------------------------------- #
    def _admit(self, step: int, force: bool = False) -> bool:
        from repro.kernels.decode_schedule import admission_order

        admitted = False
        now = self._now(step)
        arrived = [
            it for it in self._queue if self._meta[it[0]]["arrival"] <= now
        ]
        # Stamp queue-entry time once, before any admission work this
        # step: TTFT starts when the request arrives, not when it admits.
        # On the work-unit clock the arrival itself is the queue-entry
        # mark — a request that arrived mid-way through a long synchronous
        # prefill has been waiting since then, invisibly to the step loop.
        for it in arrived:
            lat = self._lat[it[0]]
            if lat["submit_vt"] is None:
                lat["submit_vt"] = (
                    self._lat_clock()
                    if self.arrival_unit == "steps"
                    else self._meta[it[0]]["arrival"]
                )
                lat["submit_step"] = step
        order = admission_order(
            (
                it[0],
                self._meta[it[0]]["priority"],
                # Queued slack is the deadline window itself (admission
                # has not started the clock): tighter deadlines first.
                self._meta[it[0]]["deadline"]
                if self._meta[it[0]]["deadline"] is not None
                else self.deadline,
            )
            for it in arrived
        )
        by_idx = {it[0]: it for it in arrived}
        for idx in order:
            item = by_idx[idx]
            if not force and item[1] > step:
                continue  # backing off — skip ahead to later submissions
            rid = self.sess.add_request(self._submitted[idx])
            if rid is None:
                item[1] = step + item[2]
                item[2] = min(item[2] * 2, self.backoff_cap)
                self.admission_retries += 1
                continue  # skip-ahead: a later queued prompt may still fit
            self._queue.remove(item)
            meta = self._meta[idx]
            self._live[rid] = {
                "idx": idx,
                # Under budgeted interleaving the first token arrives as a
                # later step's emission delta; synchronous admission
                # emitted it just now (the delta loop never sees it) —
                # either way the request owes gen_len tokens beyond its
                # first.
                "remaining": self.gen_len
                + (0 if self.sess.outputs[rid] else 1),
                "admitted": step,
                "priority": meta["priority"],
                "deadline": meta["deadline"],
            }
            lat = self._lat[idx]
            lat["admit_vt"] = self._lat_clock()
            lat["admit_step"] = step
            if self.sess.outputs[rid]:
                # Synchronous prefill emitted the first token inside
                # add_request: stamp it at the post-prefill clock (the
                # prompt's own chunks are part of its TTFT).
                lat["first_vt"] = self._lat_clock()
                lat["first_step"] = step
                lat["last_vt"] = lat["first_vt"]
            admitted = True
            force = False
        return admitted

    def _try_resume(self, step: int) -> None:
        sess = self.sess
        if not sess.suspended:
            return
        # Ascending id: fork parents precede children (family re-aliasing).
        # Requests this supervisor evicted under pool pressure sit out
        # their backoff first — resuming them straight into the pressure
        # that evicted them would re-evict immediately (livelock) while
        # the backoff lets the other requests finish and free real pages.
        for rid in sorted(sess.suspended):
            hold = self._resume_hold.get(rid)
            if hold is not None and hold[0] > step:
                continue
            if sess.resume(rid):
                self._resume_hold.pop(rid, None)

    def _idle_tick(self, step: int) -> None:
        # Nothing steppable.  Progress can still arrive from a ballast
        # release, an eviction backoff expiring, or an admission backoff
        # timer — otherwise waiting is spinning: fail loudly, like the
        # plain serve loop's cannot-ever-admit check.
        if self._ballast:
            self.steps += 1
            return
        sess = self.sess
        if self._live:
            if any(
                self._resume_hold.get(r, [0])[0] > step for r in self._live
            ):
                self.steps += 1  # eviction backoff pending: wait it out
                return
            raise RuntimeError(
                f"{len(self._live)} suspended request(s) cannot be resumed "
                "— no eligible shard has room and nothing live could free "
                "pages"
            )
        if self._queue:
            arrived = [
                it
                for it in self._queue
                if self._meta[it[0]]["arrival"] <= self._now(step)
            ]
            if not arrived:
                # Stream gap: the next arrival is later.  Idle waiting does
                # no session work, so the work-unit arrival clock advances
                # via skew (wall time passes on an idle accelerator).
                self._clock_skew += 1
                self.steps += 1
                return
            if self._admit(step, force=True):
                return  # same step re-runs with live requests
            raise RuntimeError(
                f"request of {len(self._submitted[arrived[0][0]])} "
                "tokens cannot be admitted even with an idle session — "
                "grow the pool or truncate the prompt"
            )
        del sess  # loop condition handles the all-done case

    def _suspend_victim(self, steppable: list[int]) -> None:
        # Pool exhausted by decode-time growth: recoverably evict from the
        # fullest pool by SLA — lowest priority class first, then most
        # deadline slack, then most-complete (most pages back for one
        # suspension, finishing soonest once resumed).  A request within
        # ``deadline_guard`` steps of its deadline is never evicted while
        # any other candidate exists: suspension costs a replay it cannot
        # afford.
        sess = self.sess
        if hasattr(sess, "shards"):
            def free(r):
                return sess.shards[sess.shard_of(r)].cache.num_free_pages
        else:
            def free(r):
                return sess.cache.num_free_pages
        step = self.steps
        candidates = [
            r
            for r in steppable
            if self._slack(r, step) > self.deadline_guard
        ] or steppable
        victim = max(
            candidates,
            key=lambda r: (
                -free(r),
                -self._live[r]["priority"],
                self._slack(r, step),
                len(sess.outputs[r]),
                -r,
            ),
        )
        sess.suspend(victim)
        hold = self._resume_hold.setdefault(victim, [0, self.backoff_base])
        hold[1] = min(hold[1] * 2, 32)
        hold[0] = self.steps + hold[1]
        self.evictions += 1
        self.events.append(
            f"step {self.steps}: pool full — suspended request {victim}"
        )

    def _abandon(self, rid: int) -> None:
        info = self._live.pop(rid)
        sess = self.sess
        if rid in sess.suspended:
            out = sess.discard_suspended(rid)
        else:
            out = sess.finish(rid)
        self._results[info["idx"]] = out
        self.abandoned_idx.add(info["idx"])
        self.abandoned += 1
        self.events.append(
            f"step {self.steps}: abandoned request {rid} "
            f"(submission {info['idx']}) with {len(out)} tokens"
        )

    def _apply_plan(self, step: int) -> None:
        # The same step index can re-enter after an eviction retry; plan
        # events must fire exactly once.
        if self.plan is None or step in self._plan_applied:
            return
        self._plan_applied.add(step)
        sess = self.sess
        for ev in self.plan.events_at(step):
            if ev.kind == "shard_loss":
                healthy = [
                    i
                    for i, h in enumerate(getattr(sess, "shard_health", []))
                    if h == "healthy"
                ]
                if len(healthy) < 2:
                    # Never kill the last healthy shard: an unrecoverable
                    # plan gates nothing.  Count the skip and keep serving.
                    self.faults_skipped += 1
                    continue
                target = (
                    ev.shard
                    if ev.shard in healthy
                    else healthy[ev.shard % len(healthy)]
                )
                res = sess.fail_shard(target)
                self.faults_applied += 1
                self.events.append(
                    f"step {step}: shard {target} lost — "
                    f"{len(res['suspended'])} suspended, "
                    f"{len(res['resumed'])} re-routed immediately"
                )
            elif ev.kind == "slow_shard":
                self._slow = (step + max(1, ev.duration), ev.factor)
                self.faults_applied += 1
                self.events.append(
                    f"step {step}: slow shard x{ev.factor:g} for "
                    f"{max(1, ev.duration)} steps"
                )
            elif ev.kind == "pool_pressure":
                if hasattr(sess, "shards"):
                    shard = ev.shard % sess.num_shards
                    if sess.shard_health[shard] != "healthy":
                        self.faults_skipped += 1
                        continue
                    handle = sess.hold_pages(ev.pages, shard=shard)
                else:
                    handle = sess.hold_pages(ev.pages)
                self._ballast.append((step + max(1, ev.duration), handle))
                self.faults_applied += 1
                self.events.append(
                    f"step {step}: pool pressure — {ev.pages} pages held "
                    f"for {max(1, ev.duration)} steps"
                )
            elif ev.kind == "abandon":
                if not self._live:
                    self.faults_skipped += 1
                    continue
                rid = min(self._live, key=lambda r: self._live[r]["idx"])
                self.faults_applied += 1
                self._abandon(rid)

    def _tick_ballast(self, step: int) -> None:
        keep = []
        for due, handle in self._ballast:
            if due <= step:
                self.sess.release_pages(handle)
            else:
                keep.append((due, handle))
        self._ballast = keep


class PagedDecodeSession:
    """Continuous batching over a paged latent cache (the real thing).

    Where :class:`ServingSession` reserves a contiguous ``max_len`` cache row
    per slot, this session shares one page pool across every live request:
    admission is by free-page count, eviction returns pages immediately, and
    a request's context can grow until the *pool* (not its slot) is full.
    Requests are admitted/evicted mid-stream; each ``step()`` batches all
    live requests into one ``ops.mla_decode_paged`` call with ragged
    per-request ``kv_len`` and block tables padded to the batch max.

    The session operates at the attention level: callers bring absorbed
    queries ``(G, d_k)`` and the new token's latent row per step (in a full
    model server these come from the layer stack; examples/tests drive it
    with synthetic latents).  ``attend()`` is the pure read path, ``step()``
    = append-then-attend, which matches decode semantics (the new token
    attends to itself).
    """

    def __init__(
        self,
        *,
        num_pages: int,
        page_size: int | None = None,
        d_k: int = 576,
        d_v: int = 512,
        scale: float,
        variant: str = "amla",
        interpret: bool = False,
        dtype=jnp.bfloat16,
        cache_spec=None,
        scheduler: str = "queue",
        num_splits: int = 1,
        block_k: int | None = None,
        prefix_sharing: bool = False,
        min_group: int = 2,
    ):
        from repro.kernels import ops
        from repro.kernels.decode_schedule import DecodeScheduler
        from repro.kernels.mla_decode_paged import DEFAULT_PAGE_SIZE
        from repro.runtime.kv_cache import PagedKVCache

        # cache_spec selects the storage layout (e.g. int8 + per-row
        # scales, which needs the queue scheduler's fused dequant).
        self.kv = PagedKVCache(
            num_pages=num_pages,
            page_size=page_size or DEFAULT_PAGE_SIZE,
            width=d_k,
            dtype=dtype,
            spec=cache_spec,
        )
        if self.kv.quantized and scheduler != "queue":
            raise ValueError(
                "int8 cache_spec needs scheduler='queue' (the padded grid "
                "has no fused dequant path)"
            )
        self.d_k, self.d_v = d_k, d_v
        self.scale, self.variant, self.interpret = scale, variant, interpret
        # Fixed block-table width keeps the jit'd kernel's input shapes
        # stable across admits/evicts and page-boundary growth (no retrace
        # per step); sized for the worst case of one request owning the pool.
        self.table_width = num_pages
        self.scheduler = scheduler
        self.prefix_sharing = prefix_sharing and scheduler == "queue"
        self.block_k = block_k or ops.default_paged_block_k(
            self.kv.page_size, self.table_width
        )
        # One memoizing scheduler for the whole session: a schedule stays
        # valid while every live request's KV-block count is unchanged, so
        # consecutive decode steps (each +1 token) reuse it ~block_k times
        # before a rebuild.  The live-rid tuple rides along as the identity
        # key, so admit/evict churn (a recycled slot with a coincidentally
        # identical block count) can never serve a stale schedule.
        self._scheduler = DecodeScheduler(
            block_k=self.block_k, num_splits=num_splits, min_group=min_group
        )
        self.active: list[int] = []
        self._next_id = 0

    @property
    def scheduler_stats(self) -> dict:
        """Schedule reuse counters (see decode_schedule.DecodeScheduler)."""
        return {
            "hits": self._scheduler.hits,
            "rebuilds": self._scheduler.rebuilds,
        }

    def admit(self, latent_prompt) -> int | None:
        """Admit a request whose prompt latents are ``(S, d_k)``.

        Returns the request id, or None when the pool lacks pages (caller
        queues and retries after an eviction — continuous batching).
        """
        latent_prompt = jnp.asarray(latent_prompt)
        if not self.kv.has_room(None, latent_prompt.shape[0]):
            return None
        rid = self._next_id
        self._next_id += 1
        self.kv.alloc(rid)
        self.kv.append(rid, latent_prompt)
        self.active.append(rid)
        return rid

    def evict(self, rid: int) -> None:
        """Finish/cancel ``rid``: its pages return to the pool immediately
        (pages aliased by forked siblings stay until their last owner goes).
        """
        if rid not in self.active:
            raise KeyError(f"request {rid} is not live")
        self.active.remove(rid)
        self.kv.free(rid)

    def fork(self, rid: int, prefix_len: int | None = None) -> int:
        """Branch a live request: the child shares ``rid``'s first
        ``prefix_len`` rows (default: all of them) by page aliasing.

        Zero pages, zero row copies — refcounts go up, and the shared
        boundary page is copied lazily on the first append that writes into
        it (``PagedKVCache`` copy-on-write).  With ``prefix_sharing`` on,
        the scheduler groups the family's shared blocks so their attention
        is computed once per step for the whole group.
        """
        if rid not in self.active:
            raise KeyError(f"request {rid} is not live")
        child = self._next_id
        self._next_id += 1
        self.kv.fork(rid, child, prefix_len)
        self.active.append(child)
        return child

    def admit_with_prefix(
        self, parent_rid: int, latent_suffix, prefix_len: int | None = None
    ) -> int | None:
        """Admit a request as ``fork(parent) + append(suffix)`` — the
        continuous-batching entry for shared-system-prompt / n-best traffic.

        ``latent_suffix`` is the new request's own ``(S, d_k)`` rows (its
        divergent turn); may be empty.  Returns the request id, or None when
        the pool lacks pages for the suffix (+ the boundary COW page), in
        which case nothing is left allocated — callers queue and retry,
        exactly like :meth:`admit`.
        """
        latent_suffix = jnp.asarray(latent_suffix)
        if latent_suffix.ndim == 1 and latent_suffix.shape[0]:
            # Single (d_k,) row — same normalization as step(); without it
            # admission control counts n = d_k "rows" and append rejects
            # the shape.  A 0-length 1-D array stays the empty suffix.
            latent_suffix = latent_suffix[None]
        n = int(latent_suffix.shape[0]) if latent_suffix.ndim >= 2 else 0
        child = self.fork(parent_rid, prefix_len)
        if n:
            if not self.kv.has_room(child, n):
                self.evict(child)
                return None
            self.kv.append(child, latent_suffix)
        return child

    def attend(self, queries: dict[int, jax.Array]) -> dict[int, jax.Array]:
        """Batched paged attention for ``{rid: (G, d_k)}`` absorbed queries.

        Returns ``{rid: (G, d_v)}``.  All queried rids must be live (KeyError
        otherwise — failing fast beats a silently missing output); lengths
        may be ragged — shorter requests mask their block-table tail via
        kv_len.
        """
        unknown = set(queries) - set(self.active)
        if unknown:
            raise KeyError(f"requests not live: {sorted(unknown)}")
        rids = [r for r in self.active if r in queries]
        if not rids:
            return {}
        bt, kv_len = self.kv.block_table(rids, width=self.table_width)
        q = jnp.stack([jnp.asarray(queries[r]) for r in rids])[:, None]
        from repro.kernels import ops

        schedule = None
        if self.scheduler == "queue":
            # kv_len is host-side numpy here, so scheduling costs no device
            # sync; the memoized schedule is reused until a request crosses
            # a block_k boundary or the active set changes (the rid tuple is
            # the identity key — a recycled slot forces a rebuild even at an
            # identical block signature).
            if self.prefix_sharing:
                schedule = self._scheduler.schedule_prefix(
                    kv_len,
                    bt,
                    page_size=self.kv.page_size,
                    extra_key=tuple(rids),
                )
            else:
                schedule = self._scheduler.schedule(
                    kv_len, extra_key=tuple(rids)
                )
        out = ops.mla_decode_paged(
            q,
            self.kv.pages,
            jnp.asarray(bt),
            jnp.asarray(kv_len),
            kv_scales=self.kv.scales,
            d_v=self.d_v,
            variant=self.variant,
            scale=self.scale,
            interpret=self.interpret,
            scheduler=self.scheduler,
            block_k=self.block_k if self.scheduler == "queue" else None,
            schedule=schedule,
        )  # (B, 1, G, d_v)
        return {r: out[i, 0] for i, r in enumerate(rids)}

    def step(
        self,
        queries: dict[int, jax.Array],
        new_latents: dict[int, jax.Array] | None = None,
    ) -> dict[int, jax.Array]:
        """One decode step: append each request's new latent row, then attend.

        The appends are atomic: if the pool cannot hold *all* of this step's
        new rows, OutOfPagesError is raised before any row lands, so the
        caller can evict and retry the same step without double-appending.
        """
        if new_latents:
            from repro.runtime.kv_cache import OutOfPagesError

            rows = {
                rid: (jnp.asarray(r)[None] if jnp.ndim(r) == 1 else jnp.asarray(r))
                for rid, r in new_latents.items()
            }
            need = sum(
                self.kv.pages_needed_for_append(rid, r.shape[0])
                for rid, r in rows.items()
            )
            if need > self.kv.num_free_pages:
                raise OutOfPagesError(
                    f"step needs {need} new pages for {len(rows)} appends; "
                    f"only {self.kv.num_free_pages} free — evict and retry"
                )
            for rid, r in rows.items():
                self.kv.append(rid, r)
        return self.attend(queries)


def _write_slot(full, one, slot):
    """Write a batch-1 cache leaf into ``full`` at batch position ``slot``.

    Handles both stacked leaves (L/groups leading dim: batch is axis 1) and
    flat leaves (batch is axis 0).
    """
    if one.ndim == full.ndim and one.shape[0] == full.shape[0] and full.shape[0] != 1:
        # stacked: (L, 1, ...) into (L, B, ...)
        start = [0] * full.ndim
        start[1] = slot
    else:
        start = [0] * full.ndim
        start[0] = slot
    return jax.lax.dynamic_update_slice(full, one.astype(full.dtype), tuple(start))
