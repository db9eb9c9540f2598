"""Serving driver: batched decode behind a cache-backend flag.

``--cache dense`` (default) runs the slot-reuse :class:`ServingSession`
over contiguous ``(B, max_len)`` caches; ``--cache paged`` runs the same
request stream through :class:`PagedServingSession` — the full model
decoding over a LayeredPagedKVCache via the AMLA paged kernels, with
chunked prefill-into-pages and one decode schedule per step shared by all
layers.  Greedy outputs are identical across backends (the parity suite
``tests/test_paged_model_serve.py`` pins this exactly).

The paged kernels compile for the TPU.  On a CPU (``JAX_PLATFORMS=cpu``)
pass ``--interpret`` to run them in Pallas interpret mode; ``--mesh`` then
forces that many host devices.  ``chip_smoke.py`` at the repo root drives
this path at full model width on one chip.

Usage (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.serve --cache paged --smoke \
        --interpret --requests 6 --gen-len 16
    PYTHONPATH=src python -m repro.launch.serve --cache paged --smoke \
        --interpret --shared-prefix   # forked system-prompt demo
    PYTHONPATH=src python -m repro.launch.serve --cache paged --smoke \
        --interpret --speculate ngram --draft-k 4   # speculative decode
    PYTHONPATH=src python -m repro.launch.serve --cache paged --smoke \
        --interpret --prefill-budget 64 --priority-classes 2,0,1

The paged backend needs an MLA geometry; with no explicit ``--arch`` it
serves the paper's (``deepseek-v2-mla``), while dense defaults to
``qwen1.5-0.5b`` as before.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _cpu_platform() -> bool:
    """True when jax is pinned to the CPU (``JAX_PLATFORMS=cpu``)."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def _ensure_cpu_mesh_devices(n: int) -> None:
    """On a CPU-only run, ask XLA's host platform for ``n`` devices
    before backends init; on an accelerator the mesh uses real chips.

    Importing repro modules below initializes the jax backend (kernel
    constants touch device state at import), so this must run from
    ``sys.argv`` at module top — after argparse it would be too late in
    the ``python -m repro.launch.serve`` entry path.  A pre-set XLA_FLAGS
    carrying the option is respected (e.g. the CI job exports it).
    """
    if not _cpu_platform():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def _peek_mesh_devices(argv) -> None:
    """Scan raw argv for ``--mesh DxM`` / ``--mesh=DxM`` pre-import.

    Malformed specs are ignored here — argparse and parse_mesh_spec
    produce the real errors once main() runs.
    """
    spec = None
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            spec = argv[i + 1]
        elif a.startswith("--mesh="):
            spec = a.split("=", 1)[1]
    if spec is None:
        return
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        return
    if d >= 1 and m >= 1:
        _ensure_cpu_mesh_devices(d * m)


_peek_mesh_devices(sys.argv[1:])

import jax
import numpy as np

from repro.configs import get_config
from repro.models.model_zoo import build_model
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.kv_cache import OutOfPagesError
from repro.runtime.serve_loop import (
    PagedServingSession,
    ServingSession,
    ShardedPagedServingSession,
)


def _build_session(args, cfg, model, params):
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh

        return ShardedPagedServingSession(
            model,
            params,
            num_pages=args.num_pages,
            mesh=make_serving_mesh(args.mesh),
            page_size=args.page_size,
            block_k=args.block_k,
            prefill_chunk=args.prefill_chunk,
            prefix_sharing=args.shared_prefix,
            max_batch=args.batch,
            kv_dtype=args.kv_dtype,
            speculate=args.speculate,
            draft_k=args.draft_k,
            prefix_cache=args.prefix_cache,
            retain_pages=args.retain_pages,
            prefill_budget=args.prefill_budget,
            interpret=args.interpret,
        )
    if args.cache == "paged":
        return PagedServingSession(
            model,
            params,
            num_pages=args.num_pages,
            page_size=args.page_size,
            block_k=args.block_k,
            prefill_chunk=args.prefill_chunk,
            prefix_sharing=args.shared_prefix,
            max_batch=args.batch,
            kv_dtype=args.kv_dtype,
            speculate=args.speculate,
            draft_k=args.draft_k,
            prefix_cache=args.prefix_cache,
            retain_pages=args.retain_pages,
            prefill_budget=args.prefill_budget,
            interpret=args.interpret,
        )
    if args.kv_dtype is not None:
        raise SystemExit("--kv-dtype needs --cache paged (dense caches "
                         "store activations at the model dtype)")
    return ServingSession(model, params, batch_size=args.batch, max_len=args.max_len)


def _serve_stream(sess, pending, gen_len, requests):
    """Admit-as-room-allows / step / finish loop shared by both backends."""
    live: dict[int, int] = {}  # rid -> remaining tokens
    done = 0
    t0 = time.time()
    tokens_out = 0
    results: dict[int, list[int]] = {}
    idle_steps = 0
    while done < requests:
        # admit as many queued prompts as there is room (slots or pages)
        while pending:
            rid = sess.add_request(pending[0])
            if rid is None:
                break
            pending.pop(0)
            # Phased admission emits the first token inside add_request;
            # under a prefill budget it arrives later as a step delta, so
            # the request owes one extra emission to reach the same total.
            live[rid] = gen_len + (0 if sess.outputs[rid] else 1)
            print(f"admitted request {rid} ({len(pending)} queued)")
        if not live and pending:
            # Nothing running and the head prompt still won't admit: with
            # every slot/page free this can never clear (e.g. a prompt
            # larger than the whole paged pool) — fail instead of spinning.
            raise SystemExit(
                f"request of {len(pending[0])} tokens cannot be admitted "
                f"even with an idle session — increase --num-pages/"
                f"--page-size (paged) or --batch/--max-len (dense)"
            )
        # Count what the step actually emitted (speculative steps accept a
        # variable 1..draft_k tokens per request) via output-length deltas.
        before = {rid: len(sess.outputs[rid]) for rid in live}
        try:
            sess.step()
        except OutOfPagesError:
            # Paged pool exhausted by decode-time growth: retire the
            # most-complete live request early (its output is kept) to free
            # pages, then retry the step — continuous batching's backstop.
            victim = max(live, key=lambda r: len(sess.outputs[r]))
            out = sess.finish(victim)
            results[victim] = out
            done += 1
            del live[victim]
            print(
                f"pool full: retired request {victim} early with "
                f"{len(out)} tokens: {out[:8]}..."
            )
            continue
        step_emitted = 0
        for rid in list(live):
            emitted = len(sess.outputs[rid]) - before[rid]
            tokens_out += emitted
            step_emitted += emitted
            live[rid] -= emitted
            if live[rid] <= 0:
                out = sess.finish(rid)
                results[rid] = out
                done += 1
                print(f"request {rid} done: {len(out)} tokens: {out[:8]}...")
                del live[rid]
        # Zero-emission steps are normal while budgeted prefill chunks a
        # long prompt in, but a loop that never emits again is a stall —
        # fail loudly instead of spinning (any backlog clears within
        # ceil(backlog / chunk) steps, bounded far below this).
        idle_steps = 0 if step_emitted else idle_steps + 1
        if idle_steps > 64 + sum(len(p) for p in pending):
            raise SystemExit(
                f"serve stream stalled: {idle_steps} consecutive steps "
                f"with no tokens emitted ({len(live)} live, "
                f"{len(pending)} queued)"
            )
    dt = time.time() - t0
    return results, tokens_out, dt


def _serve_supervised(sess, pending, args):
    """Chaos / deadline serving: the supervised loop over the same stream.

    Builds a seeded :class:`FaultPlan` when ``--chaos`` is given (shard
    loss only materializes on a sharded session; generated plans never
    abandon, so every request must still complete) and runs the stream
    through :class:`ServeSupervisor` — recoverable eviction/replay,
    re-routing, admission backoff, straggler tracking.  Exits nonzero if
    any request is lost or any replay diverges: the chaos smoke in CI
    gates on this.
    """
    from repro.runtime.fault_injection import FaultPlan
    from repro.runtime.serve_loop import ServeSupervisor

    plan = None
    if args.chaos is not None:
        sharded = hasattr(sess, "shards")
        pool = (
            sess._pages_per_shard if sharded else sess.cache.num_pages
        )
        plan = FaultPlan.generate(
            args.chaos,
            num_shards=sess.num_shards if sharded else 1,
            horizon=max(2, args.gen_len),
            pool_pages=pool,
        )
        print(f"chaos plan (seed {args.chaos}): {plan.describe()}")
    sup = ServeSupervisor(
        sess, gen_len=args.gen_len, deadline=args.deadline, plan=plan
    )
    classes = args.priority_classes or [0]
    for i, prompt in enumerate(pending):
        sup.submit(prompt, priority=classes[i % len(classes)])
    t0 = time.time()
    results = sup.run()
    dt = time.time() - t0
    stats = sup.stats()
    tokens_out = stats["tokens_out"]
    for idx in sorted(results):
        tag = "abandoned" if idx in sup.abandoned_idx else "done"
        out = results[idx]
        print(f"request {idx} {tag}: {len(out)} tokens: {out[:8]}...")
    print(
        f"served {len(results)}/{args.requests} requests, {tokens_out} "
        f"decode tokens in {dt:.1f}s ({tokens_out / max(dt, 1e-9):.1f} "
        f"tok/s) over {stats['steps']} supervised steps"
    )
    print(
        f"supervision: {stats['faults_applied']} faults applied "
        f"({stats['faults_skipped']} skipped), {stats['suspends']} "
        f"suspends / {stats['resumes']} resumes, "
        f"{stats['replay_prefill_tokens']} replay prefill tokens, "
        f"{stats['evictions']} pool evictions, "
        f"{stats['admission_retries']} admission retries, "
        f"{stats['straggler_events']} straggler events, "
        f"{stats['abandoned']} abandoned"
    )
    print(
        f"latency (work units): TTFT p50/p99 {stats['ttft_units_p50']:.0f}/"
        f"{stats['ttft_units_p99']:.0f}, per-token p50/p99 "
        f"{stats['tpot_units_p50']:.0f}/{stats['tpot_units_p99']:.0f} over "
        f"{stats['work_units']} units ({stats['prefill_chunks']} prefill "
        f"chunks, {stats['prefill_stall_steps']} decode-stall chunks)"
    )
    if hasattr(sess, "shard_health"):
        print(f"shard health: {sess.shard_health}")
    for event in sup.events:
        print(f"  {event}")
    # Leak audit: after the stream drains, every shard pool must be empty.
    caches = (
        [s.cache for s in sess.shards]
        if hasattr(sess, "shards")
        else [sess.cache]
    )
    for i, cache in enumerate(caches):
        sweep = cache.refcount_sweep()
        if sweep["live_sequences"] or sweep["live_pages"]:
            raise SystemExit(
                f"page leak on shard {i} after drain: {sweep}"
            )
    # Full teardown (clears retained trie prefixes, asserts a fully free
    # pool) — close() raises on any leak the per-cache sweep can't see.
    sess.close()
    if len(results) != args.requests:
        raise SystemExit(
            f"lost requests: {args.requests - len(results)} of "
            f"{args.requests} never completed"
        )
    if stats["replay_mismatches"]:
        raise SystemExit(
            f"{stats['replay_mismatches']} replay(s) diverged from the "
            "original stream (suspend/resume is supposed to be exact)"
        )
    if stats["abandoned"] and args.deadline is None:
        raise SystemExit(
            f"{stats['abandoned']} request(s) abandoned without a "
            "--deadline: a generated chaos plan must complete everything"
        )


def _shared_prefix_demo(sess, cfg, seed, gen_len):
    """Forked system-prompt traffic: one parent, aliased children."""
    rng = np.random.default_rng(seed)
    system = rng.integers(2, cfg.vocab_size, size=48).tolist()
    parent = sess.add_request(system)
    if parent is None:
        raise SystemExit(
            "shared-prefix demo: the system prompt does not fit — raise "
            "--num-pages/--batch"
        )
    kids = []
    for _ in range(2):
        suffix = rng.integers(2, cfg.vocab_size, size=int(rng.integers(4, 10)))
        kid = sess.admit_with_prefix(parent, suffix.tolist())
        if kid is None:
            raise SystemExit(
                "shared-prefix demo: could not admit a forked child — raise "
                "--num-pages/--batch (needs the parent plus two children live)"
            )
        kids.append(kid)
    print(
        f"shared-prefix demo: parent {parent} + children {kids}; "
        f"{sess.work_stats()['aliased_pages']} pages aliased across "
        f"{cfg.n_layers} layers (zero rows copied)"
    )
    for _ in range(gen_len):
        sess.step()
    for rid in [parent] + kids:
        out = sess.finish(rid)
        print(f"request {rid} done: {len(out)} tokens: {out[:8]}...")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="default: qwen1.5-0.5b (dense) / deepseek-v2-mla (paged)")
    ap.add_argument("--cache", choices=("dense", "paged"), default="dense",
                    help="cache backend: contiguous per-slot caches, or the "
                    "layered paged latent pool")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--num-pages", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=32)
    ap.add_argument("--block-k", type=int, default=None)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default=None,
                    help="paged only: latent-cache storage dtype; int8 "
                    "halves page-DMA bytes (per-row scales, dequant fused "
                    "into the kernel pipeline); default = model dtype")
    ap.add_argument("--speculate", choices=("off", "ngram"), default="off",
                    help="paged only: draft-verify speculative decode; "
                    "'ngram' drafts from each request's own history and "
                    "verifies draft-k rows per fused step (greedy outputs "
                    "identical to off — see runtime.serve_loop)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative rows per step (1 pending token + "
                    "draft-k-1 drafts); >= 2")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="paged only: serve a forked system-prompt family "
                    "with group-batched prefix attention")
    ap.add_argument("--prefix-cache", choices=("off", "trie"), default="off",
                    help="paged only: automatic longest-prefix reuse via a "
                    "radix trie over §4.2 page runs — admissions alias "
                    "matched blocks zero-copy and prefill only the tail; "
                    "finished prompts retain their prefix pages (LRU-"
                    "evicted under pool pressure).  Greedy outputs are "
                    "identical to off; the prompt stream here gets a "
                    "shared template so hits actually occur")
    ap.add_argument("--retain-pages", type=int, default=None,
                    help="prefix-cache trie: cap on pages pinned by "
                    "retained (non-live) prefixes; default = unbounded "
                    "(pool pressure still reclaims LRU subtrees)")
    ap.add_argument("--mesh", default=None,
                    help="paged only: DxM serving mesh, e.g. 2x1 — shard "
                    "the page pool + decode queue over D data shards with "
                    "M-way tensor-parallel heads each (under "
                    "JAX_PLATFORMS=cpu the host devices are forced via "
                    "XLA_FLAGS automatically)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="paged only: run the supervised serve loop under a "
                    "seeded FaultPlan (shard loss / slow shard / pool "
                    "pressure); every request must still complete with its "
                    "exact greedy output or the run exits nonzero")
    ap.add_argument("--deadline", type=int, default=None, metavar="STEPS",
                    help="paged only: per-request decode-step deadline; "
                    "over-deadline requests are abandoned with their "
                    "partial output (implies the supervised loop)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    metavar="TOKENS",
                    help="paged only: per-step prefill token budget — "
                    "admission enqueues the prompt and step() interleaves "
                    "chunk-aligned prefill slices with decode instead of "
                    "stalling every live request for the whole prompt; "
                    "must be >= --prefill-chunk; greedy outputs are "
                    "identical to phased admission")
    ap.add_argument("--priority-classes", default=None, metavar="P0,P1,...",
                    help="paged only: comma-separated integer priority "
                    "classes assigned round-robin to submitted requests "
                    "(higher admits first; ties by deadline slack then "
                    "submission order); implies the supervised loop")
    ap.add_argument("--interpret", action="store_true",
                    help="paged only: run the Pallas kernels in interpret "
                    "mode (CPU validation); without it they compile for "
                    "the TPU")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    if args.priority_classes is not None:
        try:
            args.priority_classes = [
                int(x) for x in args.priority_classes.split(",") if x.strip()
            ]
        except ValueError:
            raise SystemExit(
                f"--priority-classes must be comma-separated integers, "
                f"got {args.priority_classes!r}"
            )
        if not args.priority_classes:
            raise SystemExit("--priority-classes must name at least one class")
    supervised = (
        args.chaos is not None
        or args.deadline is not None
        or args.priority_classes is not None
    )
    if supervised and args.cache != "paged":
        raise SystemExit("--chaos/--deadline/--priority-classes need "
                         "--cache paged (recoverable eviction/replay and "
                         "priority admission ride the paged pool's "
                         "refcounted free + chunked re-prefill)")
    if args.prefill_budget is not None and args.cache != "paged":
        raise SystemExit("--prefill-budget needs --cache paged (budgeted "
                         "prefill slices ride the chunked paged prefill "
                         "path; dense admission is monolithic)")
    if args.speculate != "off" and args.cache != "paged":
        raise SystemExit("--speculate needs --cache paged (rollback rides "
                         "the paged pool's refcounted truncate; dense slots "
                         "have no page bookkeeping to roll back)")
    if args.prefix_cache != "off" and args.cache != "paged":
        raise SystemExit("--prefix-cache needs --cache paged (the trie "
                         "pins refcounted pages; dense slots have none)")
    if args.retain_pages is not None and args.prefix_cache == "off":
        raise SystemExit("--retain-pages needs --prefix-cache trie")
    if args.mesh:
        if args.cache != "paged":
            raise SystemExit("--mesh needs --cache paged (the dense backend "
                             "shards through runtime.serve_loop.jit_serve_fns)")
        from repro.launch.mesh import parse_mesh_spec

        d, m = parse_mesh_spec(args.mesh)
        _ensure_cpu_mesh_devices(d * m)

    arch = args.arch or (
        "deepseek-v2-mla" if args.cache == "paged" else "qwen1.5-0.5b"
    )
    cfg = get_config(arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    sess = _build_session(args, cfg, model, params)
    backend = args.cache + (f" (sharded over {args.mesh})" if args.mesh else "")
    print(f"serving {arch} with the {backend} cache backend")
    if args.mesh:
        print(
            f"mesh {args.mesh}: {sess.num_shards} data shards x "
            f"{sess.head_shards}-way heads, "
            f"{args.num_pages // sess.num_shards} pages per shard pool"
        )

    if args.shared_prefix:
        if args.cache != "paged":
            raise SystemExit("--shared-prefix needs --cache paged")
        _shared_prefix_demo(sess, cfg, args.seed, args.gen_len)
        stats = sess.scheduler_stats
        print(
            f"scheduler: {stats['rebuilds']} rebuilds, {stats['hits']} "
            f"reuse hits; prefill compiles: {sess.prefill_compiles}"
        )
        return

    rng = np.random.default_rng(args.seed)
    template = []
    if args.prefix_cache == "trie":
        # Multi-tenant template traffic: every prompt opens with the same
        # block-aligned system template, so repeated admissions hit the
        # trie instead of re-prefilling it.
        sess0 = sess.shards[0] if args.mesh else sess
        template = rng.integers(
            2, cfg.vocab_size, size=2 * sess0.block_k
        ).tolist()
    pending = [
        template
        + rng.integers(2, cfg.vocab_size, size=int(rng.integers(4, 24))).tolist()
        for _ in range(args.requests)
    ]
    if supervised:
        _serve_supervised(sess, pending, args)
        return
    _, tokens_out, dt = _serve_stream(sess, pending, args.gen_len, args.requests)
    print(
        f"served {args.requests} requests, {tokens_out} decode tokens "
        f"in {dt:.1f}s ({tokens_out / max(dt, 1e-9):.1f} tok/s)"
    )
    # Compile accounting: bucketed prefill keeps this O(log max_len) for a
    # ragged prompt stream (dense), and exactly one chunk shape (paged).
    print(f"prefill compiles: {sess.prefill_compiles}")
    if args.cache == "paged":
        stats = sess.scheduler_stats
        work = sess.work_stats()
        print(
            f"decode schedules: {stats['rebuilds']} built, {stats['hits']} "
            f"step reuses across {work['decode_steps']} steps x "
            f"{cfg.n_layers} layers; {work['page_dmas']} page DMAs "
            f"({work['page_dma_bytes'] / 1e6:.2f} MB at "
            f"{args.kv_dtype or 'model'} cache dtype)"
        )
        if args.speculate != "off":
            print(
                f"speculative ({args.speculate}, draft_k={args.draft_k}): "
                f"{work['accepted_tokens']} tokens over "
                f"{work['request_steps']} request-steps = "
                f"{work['accepted_tokens_per_step']:.2f} accepted/step; "
                f"{work['page_dma_bytes_per_accepted_token'] / 1e3:.2f} KB "
                f"page DMA per accepted token"
            )
        if args.prefix_cache == "trie":
            print(
                f"prefix cache: {work['trie_hits']}/{work['trie_admissions']}"
                f" admissions hit ({work['trie_hit_rate']:.2f} hit rate), "
                f"{work['prefix_tokens_reused']} prefix tokens reused "
                f"({work['prefix_tokens_reused_per_admission']:.1f}/adm); "
                f"pool: {work['live_pages']} live / "
                f"{work['retained_pages']} retained / "
                f"{work['free_pages']} free pages, "
                f"{work['trie_evicted_pages']} evicted"
            )
        if args.mesh:
            bal = work["balance"]
            for i, st in enumerate(work["per_shard"]):
                print(
                    f"shard {i}: {st['page_dmas']} page DMAs, "
                    f"{st['decode_steps']} decode steps, "
                    f"{st['free_pages']} pages free"
                )
            print(
                f"shard work balance: max/mean = {bal['imbalance']:.2f} "
                f"({bal['max']:.0f}/{bal['mean']:.1f} page DMAs)"
            )
        # Teardown leak audit in every paged run: close() clears the trie
        # and refcount-sweeps the pool — a leaked page exits nonzero here.
        sweep = sess.close()
        print(f"teardown sweep: {sweep['free_pages']} pages free (clean)")


if __name__ == "__main__":
    main()
