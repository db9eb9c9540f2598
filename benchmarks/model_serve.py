"""Full-model serving benchmark: dense vs paged cache backend, end to end.

    PYTHONPATH=src python -m benchmarks.model_serve [--smoke | --full]

Where benchmarks/paged_decode.py measures the paged *kernel* against
synthetic latents, this section serves an actual transformer through both
runtime.serve_loop backends and reports, per scenario:

* **tokens/s** dense vs paged — the headline serving rate (real on TPU;
  informational in CPU interpret mode, where Python dispatch dominates);
* **deterministic work proxies** — paged page DMAs (per-step schedule
  accounting x L layers), the dense backend's equivalent row reads
  (B x max_len x L per step: a contiguous cache scans every reserved row),
  their reduction factor, decode-schedule rebuilds (one per block_k
  boundary / churn event, never per layer) and prefill compile counts
  (pow2 buckets dense, fixed chunk paged).  These gate CI regressions
  exactly (see benchmarks/run.py check_regression);
* **speculative decode** — a draft-verify row (``--speculate ngram``)
  gated on exact greedy parity with off, accepted_tokens_per_step > 1.0,
  and page-DMA bytes per *accepted* token at or below the off baseline;
* **SLA scheduling** — a multi-tenant bursty-traffic row comparing
  token-budgeted prefill/decode interleaving against phased admission:
  p50/p99 TTFT and per-token latency per priority class on the
  deterministic work-unit clock, gated on exact greedy parity and an
  interactive-class p99 TTFT at or below phased at equal units/token.

``run()`` returns a JSON-able dict merged into BENCH_decode.json under
``model_serve`` and summarized into BENCH_history.json.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from benchmarks.paged_decode import require_tpu
from repro.configs import get_config
from repro.models.model_zoo import build_model
from repro.runtime.serve_loop import (
    PagedServingSession,
    ServingSession,
    ShardedPagedServingSession,
)


def _geometry(tier: str) -> dict:
    """Scenario matrix per tier.  Prompts are ragged on purpose: raggedness
    is where paging beats per-slot max_len reservation."""
    if tier == "full":  # serving scale (TPU)
        return dict(
            n_layers=8, max_len=4096, page=128, block_k=512, chunk=256,
            num_pages=512, steps=64,
            prompts=[384, 1536, 801, 2040, 512, 999],
            prefix=1024, suffixes=[64, 33, 17],
        )
    if tier == "smoke":  # CI interpret mode: seconds
        return dict(
            n_layers=2, max_len=128, page=16, block_k=32, chunk=16,
            num_pages=64, steps=6,
            prompts=[24, 49, 16],
            prefix=40, suffixes=[5, 9],
        )
    return dict(  # default: local CPU sanity, ~a minute
        n_layers=2, max_len=256, page=16, block_k=64, chunk=32,
        num_pages=128, steps=10,
        prompts=[24, 49, 16, 70],
        prefix=66, suffixes=[5, 9, 13],
    )


def _build(tier: str):
    cfg = get_config("deepseek-v2-mla", smoke=True)
    g = _geometry(tier)
    if g["n_layers"] != cfg.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=g["n_layers"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, g


def _timed_steps(sess, n: int) -> float:
    """Wall time of ``n`` decode steps after one warmup step (seconds)."""
    sess.step()  # warmup: compiles / first schedule build
    t0 = time.perf_counter()
    for _ in range(n):
        sess.step()
    cache = getattr(sess, "cache", None)
    if cache is not None and hasattr(cache, "pages"):
        jax.block_until_ready(cache.pages)
    elif hasattr(sess, "shards"):  # sharded: one pool per data shard
        jax.block_until_ready([s.cache.pages for s in sess.shards])
    return time.perf_counter() - t0


def _serve_scenario(cfg, model, params, g, *, shared_prefix: bool) -> dict:
    rng = np.random.default_rng(0)
    paged = PagedServingSession(
        model, params, interpret=g["interpret"],
        num_pages=g["num_pages"], page_size=g["page"],
        block_k=g["block_k"], prefill_chunk=g["chunk"],
        prefix_sharing=shared_prefix,
    )
    # Size the dense batch to exactly the admitted requests: every slot
    # decodes every step whether occupied or not, so spare slots would
    # understate dense tokens/s and flatter the paged ratio.
    dense_slots = (
        1 + len(g["suffixes"]) if shared_prefix else len(g["prompts"])
    )
    dense = ServingSession(model, params, batch_size=dense_slots,
                           max_len=g["max_len"])

    if shared_prefix:
        prefix = rng.integers(2, cfg.vocab_size, size=g["prefix"]).tolist()
        parent = paged.add_request(prefix)
        dense.add_request(prefix)
        for n in g["suffixes"]:
            suffix = rng.integers(2, cfg.vocab_size, size=n).tolist()
            paged.admit_with_prefix(parent, suffix, prefix_len=len(prefix))
            dense.add_request(prefix + suffix)
        n_live = 1 + len(g["suffixes"])
    else:
        for n in g["prompts"]:
            prompt = rng.integers(2, cfg.vocab_size, size=n).tolist()
            paged.add_request(prompt)
            dense.add_request(prompt)
        n_live = len(g["prompts"])

    steps = g["steps"]
    dt_dense = _timed_steps(dense, steps)
    dt_paged = _timed_steps(paged, steps)
    toks = n_live * steps

    # Deterministic proxies: the dense backend reads every reserved row of
    # every active slot in every layer each step; the paged backend fetches
    # exactly the live pages its one-per-step schedule names.
    n_layers = cfg.n_layers
    dense_row_reads = (steps + 1) * n_live * g["max_len"] * n_layers
    work = paged.work_stats()
    fetched_rows = work["page_dmas"] * g["page"]
    res = {
        "requests": n_live,
        "decode_steps": work["decode_steps"],
        "tokens_per_s_dense": toks / max(dt_dense, 1e-9),
        "tokens_per_s_paged": toks / max(dt_paged, 1e-9),
        "page_dmas_paged": work["page_dmas"],
        "page_dma_bytes_paged": work["page_dma_bytes"],
        "rows_attended_paged": work["rows_attended"],
        "dense_row_reads": dense_row_reads,
        "read_reduction_vs_dense": dense_row_reads / max(fetched_rows, 1),
        "schedule_rebuilds": paged.scheduler_stats["rebuilds"],
        "schedule_hits": paged.scheduler_stats["hits"],
        "prefill_compiles_paged": paged.prefill_compiles,
        "prefill_compiles_dense": dense.prefill_compiles,
        "aliased_pages": work["aliased_pages"],
    }
    paged.close()  # leak audit: raises unless every page returns to free
    return res


def _sharded_scenario(cfg, model, params, g, *, shards: int = 2) -> dict:
    """Sharded-vs-single-host row: the same ragged request stream served
    through one paged session and a :class:`ShardedPagedServingSession`
    with the pool + work queue split over ``shards`` data shards.

    Logical shards (mesh=None) keep this runnable on single-device CI; the
    CPU-mesh job drives the same class over real devices.  Gates: greedy
    outputs must match the single-host backend exactly
    (``greedy_match_vs_single == 1.0`` — routing is data-parallel, so each
    request's kernel math is shard-local and bit-identical), and the
    per-shard page-DMA work split must stay balanced
    (``shard_imbalance = max/mean <= 2.0`` on this ragged stream).
    """
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(2, cfg.vocab_size, size=n).tolist() for n in g["prompts"]
    ]
    single = PagedServingSession(
        model, params, interpret=g["interpret"],
        num_pages=g["num_pages"], page_size=g["page"],
        block_k=g["block_k"], prefill_chunk=g["chunk"],
    )
    sharded = ShardedPagedServingSession(
        model, params, interpret=g["interpret"],
        num_pages=g["num_pages"], shards=shards,
        page_size=g["page"], block_k=g["block_k"], prefill_chunk=g["chunk"],
    )
    r_single = [single.add_request(p) for p in prompts]
    r_sharded = [sharded.add_request(p) for p in prompts]
    dt_single = _timed_steps(single, g["steps"])
    dt_sharded = _timed_steps(sharded, g["steps"])
    outs_single = [single.outputs[r] for r in r_single]
    outs_sharded = [sharded.outputs[r] for r in r_sharded]
    matches = sum(a == b for a, b in zip(outs_single, outs_sharded))
    toks = len(prompts) * g["steps"]
    work = sharded.work_stats()
    bal = work["balance"]
    res = {
        "requests": len(prompts),
        "num_shards": shards,
        "decode_steps": work["decode_steps"],
        "tokens_per_s_paged": toks / max(dt_sharded, 1e-9),
        "tokens_per_s_single_host": toks / max(dt_single, 1e-9),
        "page_dmas_paged": work["page_dmas"],
        "page_dma_bytes_paged": work["page_dma_bytes"],
        "schedule_rebuilds": sharded.scheduler_stats["rebuilds"],
        "greedy_match_vs_single": matches / len(prompts),
        "shard_imbalance": bal["imbalance"],
    }
    for i, st in enumerate(work["per_shard"]):
        res[f"shard{i}_page_dmas"] = st["page_dmas"]
        res[f"shard{i}_rows_attended"] = st["rows_attended"]
    single.close()  # leak audits: raise unless every pool drains to free
    sharded.close()
    return res


def _dtype_scenario(cfg, model, params, g) -> dict:
    """Int8-vs-bf16 cache-dtype row: the same ragged request stream served
    through two paged sessions that differ only in kv_dtype.

    Identical schedules fetch identical page counts; what the dtype changes
    is **bytes per page** (the bandwidth decode is bound by) — ISSUE-5
    gates ``dma_bytes_reduction_vs_bf16 >= 1.9`` — plus greedy parity,
    reported as the fraction of requests whose tokens match exactly.
    """
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(2, cfg.vocab_size, size=n).tolist() for n in g["prompts"]
    ]
    sessions, outs, dts = {}, {}, {}
    for name in ("bf16", "int8"):
        sess = PagedServingSession(
            model, params, interpret=g["interpret"],
            num_pages=g["num_pages"], page_size=g["page"],
            block_k=g["block_k"], prefill_chunk=g["chunk"], kv_dtype=name,
        )
        rids = [sess.add_request(p) for p in prompts]
        dts[name] = _timed_steps(sess, g["steps"])
        sessions[name] = sess
        outs[name] = [sess.outputs[r] for r in rids]
    toks = len(prompts) * g["steps"]
    work = {k: s.work_stats() for k, s in sessions.items()}
    matches = sum(a == b for a, b in zip(outs["bf16"], outs["int8"]))
    for s in sessions.values():
        s.close()  # leak audit
    return {
        "requests": len(prompts),
        "decode_steps": work["int8"]["decode_steps"],
        "tokens_per_s_paged": toks / max(dts["int8"], 1e-9),
        "tokens_per_s_paged_bf16": toks / max(dts["bf16"], 1e-9),
        "page_dmas_paged": work["int8"]["page_dmas"],
        "page_dma_bytes_paged": work["int8"]["page_dma_bytes"],
        "page_dma_bytes_bf16": work["bf16"]["page_dma_bytes"],
        "dma_bytes_reduction_vs_bf16": (
            work["bf16"]["page_dma_bytes"]
            / max(work["int8"]["page_dma_bytes"], 1)
        ),
        "greedy_match_vs_bf16": matches / len(prompts),
        "schedule_rebuilds": sessions["int8"].scheduler_stats["rebuilds"],
    }


def _speculative_scenario(cfg, model, params, g, *, draft_k: int = 4) -> dict:
    """Speculative-vs-off row on a repetitive-suffix stream.

    Two paged sessions serve the same prompts (random head + cycled 3-token
    tail — the traffic shape n-gram drafting exists for); ``off`` emits one
    token per request-step, ``ngram`` verifies ``draft_k`` rows per fused
    step and rolls rejected drafts back.  Gates: the speculative token
    stream must be an exact prefix-match of the non-speculative one
    (``greedy_match_vs_off == 1.0`` — drafting can never change tokens),
    ``accepted_tokens_per_step > 1.0`` (the drafter actually lands
    something), and ``page_dma_bytes_per_accepted_token`` at or below the
    off baseline — the amortization headline: the same page fetches feeding
    k verified rows instead of one.
    """
    rng = np.random.default_rng(0)
    pattern = rng.integers(2, cfg.vocab_size, size=3).tolist()
    prompts = []
    for n in g["prompts"][:2]:
        head = rng.integers(2, cfg.vocab_size, size=max(n // 2, 1)).tolist()
        tail = [pattern[i % 3] for i in range(n - len(head))]
        prompts.append(head + tail)
    # Long enough for the greedy stream to settle into the loops the
    # drafter feeds on (acceptance is back-loaded: early steps are the
    # stream finding its cycle, so short horizons understate it).
    target = 5 * g["steps"]

    def _mk(speculate):
        return PagedServingSession(
            model, params, interpret=g["interpret"],
            num_pages=g["num_pages"], page_size=g["page"],
            block_k=g["block_k"], prefill_chunk=g["chunk"],
            speculate=speculate, draft_k=draft_k,
        )

    off = _mk("off")
    r_off = [off.add_request(p) for p in prompts]
    t0 = time.perf_counter()
    for _ in range(target):
        off.step()
    jax.block_until_ready(off.cache.pages)
    dt_off = time.perf_counter() - t0

    spec = _mk("ngram")
    r_spec = [spec.add_request(p) for p in prompts]
    t0 = time.perf_counter()
    it = 0
    while it < draft_k * target and any(
        len(spec.outputs[rs]) < len(off.outputs[ro])
        for ro, rs in zip(r_off, r_spec)
    ):
        spec.step()
        it += 1
    jax.block_until_ready(spec.cache.pages)
    dt_spec = time.perf_counter() - t0

    # A speculative step can overshoot the horizon by up to draft_k - 1
    # tokens; parity is prefix-exact against the off stream.
    matches = sum(
        off.outputs[ro] == spec.outputs[rs][: len(off.outputs[ro])]
        for ro, rs in zip(r_off, r_spec)
    )
    work, work_off = spec.work_stats(), off.work_stats()
    toks_off = len(prompts) * target
    toks_spec = sum(len(spec.outputs[r]) - 1 for r in r_spec)
    off.close()  # leak audits
    spec.close()
    return {
        "requests": len(prompts),
        "draft_k": draft_k,
        "decode_steps": work["decode_steps"],
        "request_steps": work["request_steps"],
        "query_rows": work["query_rows"],
        "accepted_tokens": work["accepted_tokens"],
        "accepted_tokens_per_step": work["accepted_tokens_per_step"],
        "tokens_per_s_paged": toks_spec / max(dt_spec, 1e-9),
        "tokens_per_s_off": toks_off / max(dt_off, 1e-9),
        "page_dmas_paged": work["page_dmas"],
        "page_dma_bytes_paged": work["page_dma_bytes"],
        "page_dma_bytes_per_accepted_token": work[
            "page_dma_bytes_per_accepted_token"
        ],
        "page_dma_bytes_per_accepted_token_off": work_off[
            "page_dma_bytes_per_accepted_token"
        ],
        "dma_per_token_vs_off": (
            work_off["page_dma_bytes_per_accepted_token"]
            / max(work["page_dma_bytes_per_accepted_token"], 1e-9)
        ),
        "greedy_match_vs_off": matches / len(prompts),
        "schedule_rebuilds": spec.scheduler_stats["rebuilds"],
    }


def _failure_recovery_scenario(cfg, model, params, g, *, shards: int = 2) -> dict:
    """Chaos row: seeded shard loss mid-stream vs the fault-free run.

    The same ragged stream runs twice through two-logical-shard sessions
    under :class:`~repro.runtime.serve_loop.ServeSupervisor` — once
    fault-free, once with a :class:`~repro.runtime.fault_injection
    .FaultPlan` that kills shard 1 halfway through.  Every victim must be
    suspended, re-routed to the survivor, replayed, and complete with the
    exact tokens of the fault-free run: gates ``completed_fraction ==
    1.0`` and ``greedy_match_vs_nofault == 1.0`` (plus a zero-leak
    host-mirror refcount sweep and zero replay mismatches).  The cost of
    recovery is the reported ``replay_token_overhead`` — replayed prefill
    tokens per generated token.
    """
    from repro.runtime.fault_injection import FaultEvent, FaultPlan
    from repro.runtime.serve_loop import ServeSupervisor

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(2, cfg.vocab_size, size=n).tolist() for n in g["prompts"]
    ]
    gen_len = g["steps"]
    plan = FaultPlan(
        [FaultEvent(step=max(2, g["steps"] // 2), kind="shard_loss", shard=1)]
    )

    def _run(active_plan):
        sess = ShardedPagedServingSession(
            model, params, interpret=g["interpret"],
            num_pages=g["num_pages"], shards=shards,
            page_size=g["page"], block_k=g["block_k"],
            prefill_chunk=g["chunk"],
        )
        sup = ServeSupervisor(sess, gen_len=gen_len, plan=active_plan)
        for p in prompts:
            sup.submit(p)
        t0 = time.perf_counter()
        results = sup.run()
        jax.block_until_ready([s.cache.pages for s in sess.shards])
        dt = time.perf_counter() - t0
        return sess, sup, results, dt

    _, _, base, dt_base = _run(None)
    sess, sup, faulted, dt_fault = _run(plan)
    stats = sup.stats()
    completed = sum(
        i not in sup.abandoned_idx and len(faulted.get(i, [])) >= gen_len
        for i in range(len(prompts))
    )
    matches = sum(base[i] == faulted[i] for i in base if i in faulted)
    leaked = 0
    for s in sess.shards:
        sweep = s.cache.refcount_sweep()  # raises on refcount divergence
        leaked += sweep["live_pages"]
    work = sess.work_stats()
    sess.close()  # full teardown audit on the faulted session
    toks = stats["tokens_out"]
    return {
        "requests": len(prompts),
        "num_shards": shards,
        "decode_steps": work["decode_steps"],
        "supervised_steps": stats["steps"],
        "tokens_per_s_paged": toks / max(dt_fault, 1e-9),
        "tokens_per_s_nofault": toks / max(dt_base, 1e-9),
        "page_dmas_paged": work["page_dmas"],
        "page_dma_bytes_paged": work["page_dma_bytes"],
        "schedule_rebuilds": sess.scheduler_stats["rebuilds"],
        "completed_fraction": completed / len(prompts),
        "greedy_match_vs_nofault": matches / len(prompts),
        "suspends": stats["suspends"],
        "resumes": stats["resumes"],
        "replay_mismatches": stats["replay_mismatches"],
        "replay_prefill_tokens": stats["replay_prefill_tokens"],
        "replay_token_overhead": stats["replay_prefill_tokens"] / max(toks, 1),
        "leaked_pages": leaked,
    }


def _multi_tenant_scenario(cfg, model, params, g) -> dict:
    """Prefix-trie row: a multi-tenant template stream, trie-on vs trie-off.

    Every request shares a 3-block system-prompt template with a private
    ragged tail — the sustained multi-tenant traffic shape the radix trie
    exists for.  Admissions arrive in staggered waves with overlapping
    lifetimes, so hits alias both *live* requests' pages and *retained*
    (finished) prefixes.  The off twin serves the identical stream through
    the pre-trie default path (no sharing, full prefill per request).

    Gates (both cache dtypes): ``greedy_match_vs_off == 1.0`` — automatic
    admission must be invisible in the tokens, which is why only
    chunk-aligned prefill-written blocks are ever retained — and
    ``dma_bytes_reduction_vs_off >= 2.0`` at equal output tokens, the
    zero-copy adoption + nested group-prefix scheduling headline.  After
    the stream, ``reclaim_retained`` drains every retained subtree
    (eviction churn) and ``close()`` runs the refcount sweep — any leaked
    page raises, reported as ``sweep_clean``.
    """
    rng = np.random.default_rng(0)
    template = rng.integers(2, cfg.vocab_size, size=3 * g["block_k"]).tolist()
    n_req = 2 * max(len(g["prompts"]), 4)
    prompts = [
        template + rng.integers(2, cfg.vocab_size, size=5 + 3 * i).tolist()
        for i in range(n_req)
    ]
    wave_steps = max(g["steps"] // 2, 2)

    def _serve(prefix_cache, kv_dtype=None):
        sess = PagedServingSession(
            model, params, interpret=g["interpret"],
            num_pages=g["num_pages"], page_size=g["page"],
            block_k=g["block_k"], prefill_chunk=g["chunk"],
            prefix_cache=prefix_cache, kv_dtype=kv_dtype,
        )
        outs, live = {}, []
        t0 = time.perf_counter()
        for w in range(n_req // 2):
            for j in range(2):
                rid = sess.add_request(prompts[2 * w + j])
                assert rid is not None, "pool sized to admit every wave"
                live.append(rid)
            for _ in range(wave_steps):
                sess.step()
            if len(live) >= 4:  # overlapping lifetimes: finish the oldest
                for r in live[:2]:
                    outs[r] = sess.finish(r)
                live = live[2:]
        for _ in range(wave_steps):
            sess.step()
        for r in live:
            outs[r] = sess.finish(r)
        jax.block_until_ready(sess.cache.pages)
        dt = time.perf_counter() - t0
        # Eviction churn before the sweep: drain every retained subtree,
        # then tear down — close() raises if any page fails to come home.
        sess.reclaim_retained(g["num_pages"])
        work = sess.work_stats()
        work["schedule_rebuilds"] = sess.scheduler_stats["rebuilds"]
        sweep = sess.close()
        clean = sweep["free_pages"] == g["num_pages"]
        return outs, work, dt, clean

    res = {"requests": n_req, "template_tokens": 3 * g["block_k"]}
    toks = {}
    for dname, dtype in (("bf16", None), ("int8", "int8")):
        off, w_off, dt_off, clean_off = _serve("off", dtype)
        on, w_on, dt_on, clean_on = _serve("trie", dtype)
        toks[dname] = sum(len(v) for v in on.values())
        assert sum(len(v) for v in off.values()) == toks[dname]
        matches = sum(on[r] == off[r] for r in off)
        suffix = "" if dname == "bf16" else "_int8"
        res[f"greedy_match_vs_off{suffix}"] = matches / n_req
        res[f"dma_bytes_reduction_vs_off{suffix}"] = (
            w_off["page_dma_bytes"] / max(w_on["page_dma_bytes"], 1)
        )
        res[f"sweep_clean{suffix}"] = float(clean_off and clean_on)
        if dname == "bf16":
            res.update({
                "decode_steps": w_on["decode_steps"],
                "tokens_per_s_paged": toks[dname] / max(dt_on, 1e-9),
                "tokens_per_s_off": toks[dname] / max(dt_off, 1e-9),
                "page_dmas_paged": w_on["page_dmas"],
                "page_dma_bytes_paged": w_on["page_dma_bytes"],
                "page_dma_bytes_off": w_off["page_dma_bytes"],
                "prefix_hit_rate": w_on["trie_hit_rate"],
                "prefix_tokens_reused": w_on["prefix_tokens_reused"],
                "prefix_tokens_reused_per_admission": w_on[
                    "prefix_tokens_reused_per_admission"
                ],
                "trie_evicted_pages": w_on["trie_evicted_pages"],
                "schedule_rebuilds": w_on["schedule_rebuilds"],
            })
    return res


def _sla_traffic(cfg, g, *, seed: int = 7, n_bursts: int = 2) -> list:
    """Seeded bursty multi-tenant traffic: (arrival, prompt, priority) per
    request, arrivals on the deterministic work-unit clock.

    Each burst opens with one *batch* long prompt (16-20 prefill chunks —
    the 32k-prompt regime scaled to the tier's chunk), then five
    *interactive* shorts and one *standard* mid-length request land inside
    the window the long's synchronous prefill would occupy.  That overlap
    is the whole scenario: under phased admission every one of them waits
    behind the long's full prefill; under token-budgeted interleaving they
    chunk in beside it.
    """
    rng = np.random.default_rng(seed)
    chunk = g["chunk"]
    subs, u = [], 0
    for _ in range(n_bursts):
        n = int(rng.integers(16 * chunk, 20 * chunk + 1))
        subs.append(
            (u, rng.integers(2, cfg.vocab_size, size=n).tolist(), 0)
        )
        for _ in range(5):
            arr = u + int(rng.integers(0, 18))
            n = int(rng.integers(max(chunk // 4, 2), 3 * chunk // 2))
            subs.append(
                (arr, rng.integers(2, cfg.vocab_size, size=n).tolist(), 2)
            )
        arr = u + int(rng.integers(0, 18))
        n = int(rng.integers(3 * chunk // 2 + 1, 7 * chunk // 2))
        subs.append(
            (arr, rng.integers(2, cfg.vocab_size, size=n).tolist(), 1)
        )
        u += int(rng.integers(30, 40))
    return subs


def _multi_tenant_sla_scenario(cfg, model, params, g, *, gen_len: int = 6) -> dict:
    """SLA row: token-budgeted prefill/decode interleaving vs phased.

    The same seeded bursty traffic (``_sla_traffic``: priority classes
    interactive/standard/batch, work-unit arrivals) runs twice per cache
    dtype — once phased (``prefill_budget=None``: admission prefills the
    whole prompt synchronously, stalling every live decoder) and once
    interleaved (``prefill_budget = 3 x chunk``: pending prompts advance by
    chunk-aligned slices inside ``step()`` while decode proceeds).  Both
    runs go through :class:`ServeSupervisor` with priority+deadline
    admission ordering and ``arrival_unit="work_units"`` so the latency
    clock charges phased for the stall it actually causes.

    Gates (ABSOLUTE_FLOORS in benchmarks/run.py):

    * ``greedy_match_vs_phased{,_int8} == 1.0`` — budgeted slices land on
      the same chunk boundaries as monolithic prefill, so every token is
      bit-identical;
    * ``ttft_interactive_p99_improvement >= 1.0`` — the p99 TTFT proxy for
      the interactive class (nearest-rank, work-unit clock) at or below
      phased;
    * ``units_per_token_ratio >= 1.0`` — equal-or-better tokens/s proxy:
      (request_steps + prefill_chunks) per output token, identical by
      construction because interleaving re-slices the same chunks;
    * ``sweep_clean == 1.0`` — every ``close()`` drains the pool leak-free
      (pending mid-prefill rows included).

    A final interleaved run attaches a tight deadline to the batch request
    (abandon/timeout traffic): ``deadline_abandons`` reports how many
    requests the supervisor dropped at their deadline — informational, the
    parity runs carry no deadlines so the token streams stay comparable.
    """
    from repro.runtime.serve_loop import ServeSupervisor, latency_percentile

    budget = 3 * g["chunk"]
    traffic = {"bf16": _sla_traffic(cfg, g), "int8": _sla_traffic(cfg, g, n_bursts=1)}

    def _run(subs, prefill_budget, kv_dtype=None, deadlines=None):
        sess = PagedServingSession(
            model, params, interpret=g["interpret"],
            num_pages=g["num_pages"], page_size=g["page"],
            block_k=g["block_k"], prefill_chunk=g["chunk"],
            prefill_budget=prefill_budget, kv_dtype=kv_dtype,
        )
        sup = ServeSupervisor(sess, gen_len=gen_len, arrival_unit="work_units")
        t0 = time.perf_counter()
        for i, (arr, prompt, pri) in enumerate(subs):
            sup.submit(prompt, priority=pri, arrival=arr,
                       deadline=(deadlines or {}).get(i))
        results = sup.run()
        jax.block_until_ready(sess.cache.pages)
        dt = time.perf_counter() - t0
        stats, recs, work = sup.stats(), sup.latency_records(), sess.work_stats()
        work["schedule_rebuilds"] = sess.scheduler_stats["rebuilds"]
        sweep = sess.close()
        clean = sweep["free_pages"] == g["num_pages"]
        return results, stats, recs, work, dt, clean, sup

    def _class_ttft(subs, recs):
        by_class = {0: [], 1: [], 2: []}
        for (_, _, pri), rec in zip(subs, recs):
            if rec["first_vt"] is not None:
                by_class[pri].append(rec["first_vt"] - rec["submit_vt"])
        return by_class

    res = {"requests": len(traffic["bf16"]), "prefill_budget": budget,
           "gen_len": gen_len}
    clean_all = True
    for dname, dtype in (("bf16", None), ("int8", "int8")):
        subs = traffic[dname]
        r_ph, s_ph, rec_ph, w_ph, dt_ph, c_ph, _ = _run(subs, None, dtype)
        r_il, s_il, rec_il, w_il, dt_il, c_il, _ = _run(subs, budget, dtype)
        clean_all = clean_all and c_ph and c_il
        suffix = "" if dname == "bf16" else "_int8"
        matches = sum(r_ph[i] == r_il[i] for i in r_ph if i in r_il)
        res[f"greedy_match_vs_phased{suffix}"] = matches / len(subs)
        if dname != "bf16":
            continue
        toks = sum(len(v) for v in r_il.values())
        ttft_ph, ttft_il = _class_ttft(subs, rec_ph), _class_ttft(subs, rec_il)
        upt_ph = (w_ph["request_steps"] + w_ph["prefill_chunks"]) / max(toks, 1)
        upt_il = (w_il["request_steps"] + w_il["prefill_chunks"]) / max(toks, 1)
        p99_ph = latency_percentile(ttft_ph[2], 99)
        p99_il = latency_percentile(ttft_il[2], 99)
        res.update({
            "tokens_out": toks,
            "tokens_per_s_paged": toks / max(dt_il, 1e-9),
            "tokens_per_s_phased": toks / max(dt_ph, 1e-9),
            "page_dmas_paged": w_il["page_dmas"],
            "page_dma_bytes_paged": w_il["page_dma_bytes"],
            "schedule_rebuilds": w_il["schedule_rebuilds"],
            "ttft_interactive_p50_phased": latency_percentile(ttft_ph[2], 50),
            "ttft_interactive_p50_interleaved": latency_percentile(ttft_il[2], 50),
            "ttft_interactive_p99_phased": p99_ph,
            "ttft_interactive_p99_interleaved": p99_il,
            "ttft_interactive_p99_improvement": p99_ph / max(p99_il, 1e-9),
            "ttft_standard_p99_phased": latency_percentile(ttft_ph[1], 99),
            "ttft_standard_p99_interleaved": latency_percentile(ttft_il[1], 99),
            "ttft_batch_p99_phased": latency_percentile(ttft_ph[0], 99),
            "ttft_batch_p99_interleaved": latency_percentile(ttft_il[0], 99),
            "tpot_units_p99_phased": s_ph["tpot_units_p99"],
            "tpot_units_p99_interleaved": s_il["tpot_units_p99"],
            "tpot_p99_improvement": (
                s_ph["tpot_units_p99"] / max(s_il["tpot_units_p99"], 1e-9)
            ),
            "prefill_stall_steps_phased": s_ph["prefill_stall_steps"],
            "prefill_stall_steps_interleaved": s_il["prefill_stall_steps"],
            "units_per_token_phased": upt_ph,
            "units_per_token_interleaved": upt_il,
            "units_per_token_ratio": upt_ph / max(upt_il, 1e-9),
        })
    # Abandon/timeout traffic: the batch long gets a deadline it cannot
    # meet, the supervisor must drop it at the deadline and still finish
    # everyone else (informational — parity runs carry no deadlines).
    subs = traffic["int8"]
    _, _, _, _, _, c_dl, sup = _run(subs, budget, None, deadlines={0: 3})
    clean_all = clean_all and c_dl
    res["deadline_abandons"] = len(sup.abandoned_idx)
    res["sweep_clean"] = float(clean_all)
    return res


def run(full: bool = False, smoke: bool = False, interpret: bool = False) -> dict:
    """``interpret`` runs the Pallas kernels in interpret mode on the CPU
    (mode ``cpu-interpret``: work proxies only); otherwise a TPU is
    required."""
    tier = "full" if full else ("smoke" if smoke else "default")
    if not interpret:
        require_tpu()
    mode = "cpu-interpret" if interpret else "tpu"
    cfg, model, params, g = _build(tier)
    g["interpret"] = interpret
    report = {"mode": mode, "tier": tier, "scenarios": {}}
    for name, shared in (("ragged", False), ("shared_prefix", True)):
        res = _serve_scenario(cfg, model, params, g, shared_prefix=shared)
        report["scenarios"][name] = res
        for k, v in sorted(res.items()):
            val = f"{v:.1f}" if isinstance(v, float) else v
            print(f"model_serve,{name},{k},{val}")
    sh = _sharded_scenario(cfg, model, params, g)
    report["scenarios"]["sharded"] = sh
    for k, v in sorted(sh.items()):
        val = f"{v:.2f}" if isinstance(v, float) else v
        print(f"model_serve,sharded,{k},{val}")
    res = _dtype_scenario(cfg, model, params, g)
    report["scenarios"]["int8_vs_bf16"] = res
    for k, v in sorted(res.items()):
        val = f"{v:.2f}" if isinstance(v, float) else v
        print(f"model_serve,int8_vs_bf16,{k},{val}")
    sp = _speculative_scenario(cfg, model, params, g)
    report["scenarios"]["speculative"] = sp
    for k, v in sorted(sp.items()):
        val = f"{v:.2f}" if isinstance(v, float) else v
        print(f"model_serve,speculative,{k},{val}")
    fr = _failure_recovery_scenario(cfg, model, params, g)
    report["scenarios"]["failure_recovery"] = fr
    for k, v in sorted(fr.items()):
        val = f"{v:.2f}" if isinstance(v, float) else v
        print(f"model_serve,failure_recovery,{k},{val}")
    mt = _multi_tenant_scenario(cfg, model, params, g)
    report["scenarios"]["multi_tenant"] = mt
    for k, v in sorted(mt.items()):
        val = f"{v:.2f}" if isinstance(v, float) else v
        print(f"model_serve,multi_tenant,{k},{val}")
    sla = _multi_tenant_sla_scenario(cfg, model, params, g)
    report["scenarios"]["multi_tenant_sla"] = sla
    for k, v in sorted(sla.items()):
        val = f"{v:.2f}" if isinstance(v, float) else v
        print(f"model_serve,multi_tenant_sla,{k},{val}")
    rag = report["scenarios"]["ragged"]
    print(
        f"model_serve,summary,read_reduction_vs_dense,"
        f"{rag['read_reduction_vs_dense']:.1f},schedules_per_step,"
        f"{(rag['schedule_rebuilds'] + rag['schedule_hits']) / max(rag['decode_steps'], 1):.2f}"
    )
    int8_ok = (
        res["dma_bytes_reduction_vs_bf16"] >= 1.9
        and res["greedy_match_vs_bf16"] == 1.0
    )
    print(
        f"model_serve,acceptance_int8,dma_bytes_reduction,"
        f"{res['dma_bytes_reduction_vs_bf16']:.2f},greedy_match,"
        f"{res['greedy_match_vs_bf16']:.2f},pass,{int(int8_ok)}"
    )
    sharded_ok = (
        sh["greedy_match_vs_single"] == 1.0 and sh["shard_imbalance"] <= 2.0
    )
    print(
        f"model_serve,acceptance_sharded,greedy_match,"
        f"{sh['greedy_match_vs_single']:.2f},shard_imbalance,"
        f"{sh['shard_imbalance']:.2f},pass,{int(sharded_ok)}"
    )
    spec_ok = (
        sp["accepted_tokens_per_step"] > 1.0
        and sp["greedy_match_vs_off"] == 1.0
        and sp["page_dma_bytes_per_accepted_token"]
        <= sp["page_dma_bytes_per_accepted_token_off"]
    )
    print(
        f"model_serve,acceptance_speculative,accepted_per_step,"
        f"{sp['accepted_tokens_per_step']:.2f},greedy_match,"
        f"{sp['greedy_match_vs_off']:.2f},dma_per_token_vs_off,"
        f"{sp['dma_per_token_vs_off']:.2f},pass,{int(spec_ok)}"
    )
    fr_ok = (
        fr["completed_fraction"] == 1.0
        and fr["greedy_match_vs_nofault"] == 1.0
        and fr["replay_mismatches"] == 0
        and fr["leaked_pages"] == 0
        and fr["suspends"] >= 1  # the injected loss must actually bite
    )
    print(
        f"model_serve,acceptance_failure_recovery,completed,"
        f"{fr['completed_fraction']:.2f},greedy_match,"
        f"{fr['greedy_match_vs_nofault']:.2f},replay_token_overhead,"
        f"{fr['replay_token_overhead']:.2f},pass,{int(fr_ok)}"
    )
    mt_ok = (
        mt["greedy_match_vs_off"] == 1.0
        and mt["greedy_match_vs_off_int8"] == 1.0
        and mt["dma_bytes_reduction_vs_off"] >= 2.0
        and mt["dma_bytes_reduction_vs_off_int8"] >= 2.0
        and mt["sweep_clean"] == 1.0
        and mt["sweep_clean_int8"] == 1.0
    )
    print(
        f"model_serve,acceptance_multi_tenant,dma_bytes_reduction,"
        f"{mt['dma_bytes_reduction_vs_off']:.2f},greedy_match,"
        f"{mt['greedy_match_vs_off']:.2f},hit_rate,"
        f"{mt['prefix_hit_rate']:.2f},pass,{int(mt_ok)}"
    )
    sla_ok = (
        sla["greedy_match_vs_phased"] == 1.0
        and sla["greedy_match_vs_phased_int8"] == 1.0
        and sla["ttft_interactive_p99_improvement"] >= 1.0
        and sla["units_per_token_ratio"] >= 1.0
        and sla["prefill_stall_steps_interleaved"] == 0
        and sla["sweep_clean"] == 1.0
    )
    print(
        f"model_serve,acceptance_multi_tenant_sla,ttft_p99_improvement,"
        f"{sla['ttft_interactive_p99_improvement']:.2f},greedy_match,"
        f"{sla['greedy_match_vs_phased']:.2f},units_per_token_ratio,"
        f"{sla['units_per_token_ratio']:.2f},stall_steps,"
        f"{sla['prefill_stall_steps_interleaved']},pass,{int(sla_ok)}"
    )
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU validation: Pallas interpret mode, no TPU")
    args = ap.parse_args()
    run(full=args.full, smoke=args.smoke, interpret=args.interpret)


if __name__ == "__main__":
    main()
